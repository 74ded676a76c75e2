"""``python -m benchmark.models.olmo_hybrid_controls --config <name>
--seed <n> [--state-steps <k>]``: on the chip, at the configuration's
own size, the serving check's sound readings beside its controls.
Prints one JSON line.

The engine serves a few requests through the scheduler and gives its
logits on the check's inputs; it is then released, and the reference
computes the check once sound, once with bfloat16 matmul operands (the
precision the configuration states: the best the engine could read)
and once wrong in each of ``olmo_hybrid.CONTROLS``' ways. Every control
has to read beyond a limit of the configuration's ``check`` and the
sound run inside all of them.

``--state-steps k``: the reference alone over one sequence, its logits
at the last ``k`` positions with the delta rule's state rounded to
bfloat16 after every token, and with bfloat16 matmul operands, each
against the float32 reference, over growing horizons: what a later PR
that wants a bfloat16 state has to argue against.
"""
import argparse
import json

import numpy as np

from .. import manifest
from . import olmo_hybrid
from .jamba_controls import served_requests


def state_dtype_measurement(config, seed, steps):
    """rel-RMS logit error over the last ``steps`` positions of one
    sequence, worst position within each horizon."""
    model = config["model"]
    rng = np.random.default_rng([seed, 0x57A7E])
    ids = rng.integers(0, model["padded_vocab_size"], steps + 64)
    positions = np.arange(64, steps + 64)
    ref = np.asarray(olmo_hybrid.reference.logits_at(model, seed, ids,
                                                     positions))
    out = {}
    for name, wrong in (("state_bfloat16", {"state_rounding": "bfloat16"}),
                        ("matmuls_bfloat16", {"rounding": "bfloat16"})):
        got = np.asarray(olmo_hybrid.reference.logits_at(
            model, seed, ids, positions, **wrong))
        err = np.sqrt(((got - ref) ** 2).mean(-1)) / np.sqrt(
            ((ref - ref.mean(-1, keepdims=True)) ** 2).mean(-1))
        out[name] = {str(h): float(err[:h].max())
                     for h in (64, 128, 256, 512, 1024, 2048) if h <= steps}
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="benchmark.models.olmo_hybrid_controls")
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--state-steps", type=int, default=0)
    args = parser.parse_args(argv)
    config = manifest.load_config(manifest.load_manifest(), args.config)
    result = {"seed": args.seed}
    if args.state_steps:
        result["state_dtype"] = state_dtype_measurement(
            config, args.seed, args.state_steps)
    else:
        engine = olmo_hybrid.build_serve_engine(config, args.seed)
        served = served_requests(config, args.seed, engine)
        got = olmo_hybrid.serve_engine_outputs(config, args.seed, engine)
        olmo_hybrid.release(engine.params, engine.kv.k, engine.kv.v)
        del engine
        sequences, lens = olmo_hybrid.serve_check_inputs(config, args.seed)
        ref = olmo_hybrid.reference_logits(config, args.seed, sequences,
                                           lens)
        result["sound"] = olmo_hybrid.serve_check(
            config, args.seed, got, served, ref=ref)
        result["bfloat16_matmuls"] = olmo_hybrid.serve_check(
            config, args.seed, rounding="bfloat16", ref=ref)
        for control in olmo_hybrid.CONTROLS:
            result[control] = olmo_hybrid.serve_control(
                config, args.seed, control, served, ref=ref)
            print(json.dumps({control: result[control]}), flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
