"""``python -m benchmark.models.moonlight_controls --config <name> --seed
<n>``: on the chip, at the configuration's own size, the serving check's
sound readings beside its controls, the router's flips and the experts'
load. Prints one JSON line.

The engine serves a few requests through the scheduler, gives its
logits on the check's inputs and its routing on one prompt; it is then
released, and the reference computes the check once sound and once
wrong in each of ``moonlight.CONTROLS``' ways. Every control has to
read beyond a limit of the configuration's ``check`` and the sound run
inside all of them. ``router`` is ``lfm2_controls``' measurement at
this router (6 of 64).
"""
import argparse
import json

import numpy as np

from .. import manifest
from . import moonlight
from .jamba_controls import served_requests
from .lfm2_controls import _flip_share, program_routing


def _hottest(chosen, experts):
    rows = np.bincount(chosen.ravel(), minlength=experts)
    return round(float(rows.max() / rows.mean()), 3)


def router_report(config, seed, ids, program):
    """``lfm2_controls.router_report`` at this family's router, the
    reference given the prompt padded to the window like every other
    sequence (one compiled length)."""
    experts = config["model"]["n_routed_experts"]

    def routing(**wrong):
        _, found = moonlight._at(config, seed, [ids],
                                 [np.zeros((1,), np.int64)],
                                 return_routing=True, **wrong)
        return {layer: c[:len(ids)] for layer, c in found[0].items()}

    ref = routing()
    return {"tokens": len(ids), "expert_layers": len(ref),
            "program_flip_share": _flip_share(program, ref),
            "bfloat16_reference_flip_share": _flip_share(
                routing(rounding="bfloat16"), ref),
            "hottest_over_mean_rows": {
                str(layer): _hottest(c, experts)
                for layer, c in ref.items()}}


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="benchmark.models.moonlight_controls")
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--controls", default=",".join(moonlight.CONTROLS),
                        help="comma-separated; '' for the sound run only")
    args = parser.parse_args(argv)
    config = manifest.load_config(manifest.load_manifest(), args.config)
    engine = moonlight.build_serve_engine(config, args.seed)
    served = served_requests(config, args.seed, engine)
    got = moonlight.serve_engine_outputs(config, args.seed, engine)
    rng = np.random.default_rng([args.seed, 0xF11B])
    ids = rng.integers(0, config["model"]["padded_vocab_size"],
                       config["inference"]["prefill_buckets"][-1])
    program = program_routing(engine, ids)
    moonlight.release(engine.params, engine.kv.k, engine.kv.v)
    del engine
    sequences, lens = moonlight.serve_check_inputs(config, args.seed)
    ref = moonlight.reference_logits(config, args.seed, sequences, lens)
    result = {"seed": args.seed,
              "sound": moonlight.serve_check(config, args.seed, got, served,
                                             ref=ref),
              "router": router_report(config, args.seed, ids, program),
              "bfloat16_matmuls": moonlight.serve_check(
                  config, args.seed, rounding="bfloat16", ref=ref)}
    print(json.dumps({"seed": args.seed, "sound": result["sound"]}),
          flush=True)
    for control in filter(None, args.controls.split(",")):
        result[control] = moonlight.serve_control(config, args.seed,
                                                  control, served, ref=ref)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
