"""``python -m benchmark.models.jamba_controls --config <name> --seed <n>
[--state-steps <k>]``: on the chip, at the configuration's own size,
the serving check's sound readings beside its controls, and the
measurement that decided the SSM state's dtype. Prints one JSON line.

The engine serves a few requests through the scheduler and gives its
logits on the check's inputs; it is then released, and the reference
computes the check once sound and once wrong in each of
``jamba.CONTROLS``' ways. Every control has to read beyond a limit of
the configuration's ``check`` and the sound run inside all of them.

``--state-steps k``: the reference alone over one sequence, its logits
at the last ``k`` positions with the SSM state rounded to bfloat16
after every step, and with bfloat16 matmul operands, each against the
float32 reference, over growing horizons.
"""
import argparse
import json

import numpy as np

from .. import manifest
from . import jamba


def served_requests(config, seed, engine, answers=96):
    """(prompt, tokens) of four requests through the scheduler, two of
    them in two chunks, two slots reused."""
    from deepspeed_tpu.inference.scheduler import \
        ContinuousBatchingScheduler
    rng = np.random.default_rng([seed, 0xBEEF])
    vocab = config["model"]["padded_vocab_size"]
    largest = config["inference"]["prefill_buckets"][-1]
    lens = [largest // 4, largest + largest // 8, largest // 2,
            largest + largest // 4]
    sched = ContinuousBatchingScheduler(engine)
    prompts = [rng.integers(0, vocab, n).tolist() for n in lens]
    uids = [sched.submit(p, max_new_tokens=answers, eos_token_id=None)
            for p in prompts]
    results = sched.run()
    return [(p, list(results[u])) for p, u in zip(prompts, uids)]


def state_dtype_measurement(config, seed, steps):
    """rel-RMS logit error over the last ``steps`` positions of one
    sequence, worst position within each horizon."""
    model = config["model"]
    rng = np.random.default_rng([seed, 0x57A7E])
    ids = rng.integers(0, model["padded_vocab_size"], steps + 64)
    positions = np.arange(64, steps + 64)
    ref = np.asarray(jamba.reference.logits_at(model, seed, ids, positions))
    out = {}
    for name, wrong in (("state_bfloat16", {"state_rounding": "bfloat16"}),
                        ("matmuls_bfloat16", {"rounding": "bfloat16"})):
        got = np.asarray(jamba.reference.logits_at(model, seed, ids,
                                                   positions, **wrong))
        err = np.sqrt(((got - ref) ** 2).mean(-1)) / np.sqrt(
            ((ref - ref.mean(-1, keepdims=True)) ** 2).mean(-1))
        out[name] = {str(h): float(err[:h].max())
                     for h in (64, 128, 256, 512, 1024, 2048) if h <= steps}
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(prog="benchmark.models.jamba_controls")
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
        engine = jamba.build_serve_engine(config, args.seed)
        served = served_requests(config, args.seed, engine)
        got = jamba.serve_engine_outputs(config, args.seed, engine)
        jamba.release(engine.params, engine.kv.k, engine.kv.v)
        del engine
        result["sound"] = jamba.serve_check(config, args.seed, got, served)
        result["bfloat16_matmuls"] = jamba.serve_check(
            config, args.seed, rounding="bfloat16")
        for control in jamba.CONTROLS:
            result[control] = jamba.serve_control(config, args.seed,
                                                  control, served)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
