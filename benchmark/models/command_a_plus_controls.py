"""``python -m benchmark.models.command_a_plus_controls --config <name>
--seed <n>``: on the chip, at the configuration's own size, the serving check's
sound readings beside its controls, and what the router's near-ties do.
Prints one JSON line.

The engine serves a few requests through the scheduler, gives its logits
on the check's inputs and its routing on one prompt; it is then
released, and the reference computes the check once sound and once
wrong in each of ``command_a_plus.CONTROLS``' ways. Every control has to
read beyond a limit of the configuration's ``check`` (or be written down
in ``check.why`` as not separated) and the sound run inside all of
them. ``router``: the share of (token, layer) pairs whose chosen set
differs between the program and the float32 reference (and between
the reference with bfloat16 operands and itself), how close the 8th and
the 9th score lie (the median of ``1 - s9 / s8``, and the share of pairs
with it under 1%: those that a bfloat16 input decides), and the share of
the reference's routed (token, choice) pairs that landed on an expert
held here (an eighth under even routing).
"""
import argparse
import json

import numpy as np

from .. import manifest
from . import command_a_plus
from .jamba_controls import served_requests
from .lfm2_controls import _flip_share, program_routing


def router_report(config, seed, ids, program):
    k = config["model"]["num_experts_per_tok"]

    def routing(**wrong):
        _, found = command_a_plus._at(config, seed, [ids],
                               [np.zeros((1,), np.int64)],
                               return_routing=True, **wrong)
        return {layer: (c[:len(ids)], p[:len(ids)])
                for layer, (c, p) in found[0].items()}

    ref = routing()
    chosen = {layer: c for layer, (c, _) in ref.items()}
    low = {layer: c for layer, (c, _) in routing(rounding="bfloat16").items()}
    ranked = np.concatenate([-np.sort(-p, axis=-1)[:, k - 1:k + 1]
                             for _, p in ref.values()])
    gap = 1.0 - ranked[:, 1] / ranked[:, 0]
    experts = command_a_plus.reference.router_experts(config["model"])
    first, past = command_a_plus.reference.experts_held(config["model"])
    landed = np.concatenate([c.ravel() for c in chosen.values()])
    return {"held_share": float(((landed >= first) & (landed < past)).mean()),
            "tokens": len(ids), "expert_layers": len(ref),
            "program_flip_share": _flip_share(program, chosen),
            "bfloat16_reference_flip_share": _flip_share(low, chosen),
            "gap_8th_9th_median": float(np.median(gap)),
            "gap_8th_9th_under_1pct_share": float((gap < 0.01).mean()),
            "hottest_over_mean_rows": {
                str(layer): round(float(np.bincount(
                    c.ravel(), minlength=experts).max() * experts / c.size),
                    3) for layer, c in chosen.items()}}


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="benchmark.models.command_a_plus_controls")
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--controls",
                        default=",".join(command_a_plus.CONTROLS),
                        help="comma-separated; '' for the sound run only")
    args = parser.parse_args(argv)
    config = manifest.load_config(manifest.load_manifest(), args.config)
    engine = command_a_plus.build_serve_engine(config, args.seed)
    served = served_requests(config, args.seed, engine)
    got = command_a_plus.serve_engine_outputs(config, args.seed, engine)
    rng = np.random.default_rng([args.seed, 0xF11B])
    ids = rng.integers(0, config["model"]["padded_vocab_size"],
                       config["inference"]["prefill_buckets"][-1])
    program = program_routing(engine, ids)
    pools = engine.page_pool_stats()
    command_a_plus.release(engine.params, engine.kv.k, engine.kv.v)
    del engine
    sequences, lens = command_a_plus.serve_check_inputs(config, args.seed)
    ref = command_a_plus.reference_logits(config, args.seed, sequences,
                                          lens)
    result = {"seed": args.seed, "page_pools": pools,
              "sound": command_a_plus.serve_check(config, args.seed, got,
                                                  served, ref=ref)}
    print(json.dumps(result), flush=True)
    result["router"] = router_report(config, args.seed, ids, program)
    result["bfloat16_matmuls"] = command_a_plus.serve_check(
        config, args.seed, rounding="bfloat16", ref=ref)
    for control in filter(None, args.controls.split(",")):
        result[control] = command_a_plus.serve_control(
            config, args.seed, control, served, ref=ref)
        print(json.dumps({control: result[control]}), flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
