"""``python -m benchmark.models.granite_moe_hybrid_controls --config
<name> --seed <n> [--controls a,b] [--state-steps <k>]``: on the chip, at
the configuration's own size, the serving check's sound readings beside
its controls, and what the router's near-ties do. Prints one JSON line.

The engine serves a few requests through the scheduler, gives its
logits on the check's inputs and its routing on one prompt; it is then
released, and the reference computes the check once sound, once with
bfloat16 matmul operands (the precision the configuration states: the
best the engine could read) and once wrong in each of
``granite_moe_hybrid.CONTROLS``' ways. Every control has to read beyond
a limit of the configuration's ``check`` (or be written down in
``check.why`` as not rejected) and the sound run inside all of them.
``router``: the share of (token, layer) pairs whose chosen set differs
between the program and the float32 reference (and between the
reference with bfloat16 operands and itself), how close the 10th and
the 11th logit lie, and the share of the reference's routed (token,
choice) pairs that landed on an expert held here (a half under even
routing).

``--state-steps k``: the reference alone over one sequence, its logits
at the last ``k`` positions with the Mamba-2 state rounded to bfloat16
after every token, and with bfloat16 matmul operands, each against the
float32 reference, over growing horizons: what a later PR that wants a
bfloat16 state has to argue against.
"""
import argparse
import json

import numpy as np

from .. import manifest
from . import granite_moe_hybrid as family
from .jamba_controls import served_requests
from .lfm2_controls import _flip_share, program_routing
from .lfm2 import row_rel_err


def router_report(config, seed, ids, program):
    model = config["model"]
    k = model["num_experts_per_tok"]

    def routing(**wrong):
        _, found = family.reference.forward_many(
            model, seed, [family._padded(config, ids)],
            [np.zeros((1,), np.int64)], return_routing=True, **wrong)
        return {layer: (c[:len(ids)], z[:len(ids)])
                for layer, (c, z) in found[0].items()}

    ref = routing()
    chosen = {layer: c for layer, (c, _) in ref.items()}
    low = {layer: c for layer, (c, _) in routing(rounding="bfloat16").items()}
    ranked = np.concatenate([-np.sort(-z, axis=-1)[:, k - 1:k + 1]
                             for _, z in ref.values()])
    gap = ranked[:, 0] - ranked[:, 1]
    experts = family.reference.router_experts(model)
    first, past = family.reference.experts_held(model)
    landed = np.concatenate([c.ravel() for c in chosen.values()])
    return {"held_share": float(((landed >= first) & (landed < past)).mean()),
            "tokens": len(ids), "expert_layers": len(ref),
            "program_flip_share": _flip_share(program, chosen),
            "bfloat16_reference_flip_share": _flip_share(low, chosen),
            "logit_gap_10th_11th_median": float(np.median(gap)),
            "logit_gap_10th_11th_over_spread": float(
                np.median(gap) / ranked.std()),
            "hottest_over_mean_rows": {
                str(layer): round(float(np.bincount(
                    c.ravel(), minlength=experts).max() * experts / c.size),
                    3) for layer, c in chosen.items()}}


def state_dtype_measurement(config, seed, steps):
    """rel-RMS logit error over the last ``steps`` positions of one
    sequence, worst position within each horizon."""
    model = config["model"]
    rng = np.random.default_rng([seed, 0x57A7E])
    ids = rng.integers(0, model["padded_vocab_size"], steps + 64)
    positions = np.arange(64, steps + 64)
    ref = np.asarray(family.reference.logits_at(model, seed, ids, positions))
    out = {}
    for name, wrong in (("state_bfloat16", {"state_rounding": "bfloat16"}),
                        ("matmuls_bfloat16", {"rounding": "bfloat16"})):
        got = np.asarray(family.reference.logits_at(
            model, seed, ids, positions, **wrong))
        err = row_rel_err(got, ref)
        out[name] = {str(h): float(err[:h].max())
                     for h in (64, 128, 256, 512, 1024, 2048) if h <= steps}
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="benchmark.models.granite_moe_hybrid_controls")
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--controls", default=",".join(family.CONTROLS),
                        help="comma-separated; '' for the sound run only")
    parser.add_argument("--state-steps", type=int, default=0)
    args = parser.parse_args(argv)
    config = manifest.load_config(manifest.load_manifest(), args.config)
    result = {"seed": args.seed}
    if args.state_steps:
        result["state_dtype"] = state_dtype_measurement(
            config, args.seed, args.state_steps)
        print(json.dumps(result), flush=True)
        return
    engine = family.build_serve_engine(config, args.seed)
    served = served_requests(config, args.seed, engine)
    got = family.serve_engine_outputs(config, args.seed, engine)
    rng = np.random.default_rng([args.seed, 0xF11B])
    ids = rng.integers(0, config["model"]["padded_vocab_size"],
                       config["inference"]["prefill_buckets"][-1])
    program = program_routing(engine, ids)
    family.release(engine.params, engine.kv.k, engine.kv.v)
    del engine
    sequences, lens = family.serve_check_inputs(config, args.seed)
    ref = family.reference_logits(config, args.seed, sequences, lens)
    result["sound"] = family.serve_check(config, args.seed, got, served,
                                         ref=ref)
    print(json.dumps(result), flush=True)
    result["router"] = router_report(config, args.seed, ids, program)
    result["bfloat16_matmuls"] = family.serve_check(
        config, args.seed, rounding="bfloat16", ref=ref)
    print(json.dumps({k: result[k] for k in ("router", "bfloat16_matmuls")}),
          flush=True)
    for control in filter(None, args.controls.split(",")):
        result[control] = family.serve_control(
            config, args.seed, control, served, ref=ref)
        print(json.dumps({control: result[control]}), flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
