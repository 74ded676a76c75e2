"""``python -m benchmark.models.lfm2_controls --config <name> --seed <n>``:
on the chip, at the configuration's own size, the serving check's sound
readings beside its controls, the router's flips and the experts' load.
Prints one JSON line.

The engine serves a few requests through the scheduler, gives its
logits on the check's inputs and its routing on one prompt; it is then
released, and the reference computes the check once sound and once
wrong in each of ``lfm2.CONTROLS``' ways. Every control has to read
beyond a limit of the configuration's ``check`` and the sound run
inside all of them.

``router``: over one prompt of the largest bucket, the share of
(token, expert layer) pairs at which the program (its plain forward,
the serving weights and kernels) and the float32 reference choose
another SET of experts (a token whose 4th and 5th scores are nearly
tied flips under bfloat16 inputs), the same share for the reference
itself with bfloat16 matmul operands, and per expert layer the hottest
expert's rows over the mean (what the drawn selection bias makes of
the load).
"""
import argparse
import json

import numpy as np

from .. import manifest
from . import lfm2
from .jamba_controls import served_requests


def program_routing(engine, ids):
    """{expert layer: chosen (s, k)} of the program's plain forward
    over one sequence, run op by op with ``ops.moe.route`` listened
    to."""
    import jax.numpy as jnp
    from deepspeed_tpu.ops import moe
    seen, route = [], moe.route

    def listening(*args, **kwargs):
        chosen, weights = route(*args, **kwargs)
        seen.append(np.asarray(chosen))
        return chosen, weights

    moe.route = listening
    try:
        engine.decoder.forward_hidden(
            engine.params, jnp.asarray(ids, jnp.int32)[None],
            engine.model_config)
    finally:
        moe.route = route
    return dict(zip(engine.model_config.expert_layers, seen))


def _flip_share(a, b):
    """Share of (token, layer) pairs whose chosen sets differ."""
    differ = total = 0
    for layer in a:
        differ += int((np.sort(a[layer], -1) !=
                       np.sort(b[layer], -1)).any(-1).sum())
        total += len(a[layer])
    return differ / total


def router_measurement(config, seed, engine):
    model = config["model"]
    rng = np.random.default_rng([seed, 0xF11B])
    ids = rng.integers(0, model["padded_vocab_size"],
                       config["inference"]["prefill_buckets"][-1])
    program = program_routing(engine, ids)
    return ids, program


def router_report(config, seed, ids, program):
    model = config["model"]
    _, ref = lfm2.reference.forward_many(model, seed, [ids], [[0]],
                                         return_routing=True)
    _, low = lfm2.reference.forward_many(model, seed, [ids], [[0]],
                                         rounding="bfloat16",
                                         return_routing=True)
    hottest = {}
    for layer, chosen in ref[0].items():
        rows = np.bincount(chosen.ravel(), minlength=model["num_experts"])
        hottest[str(layer)] = round(float(rows.max() / rows.mean()), 3)
    return {"tokens": len(ids), "expert_layers": len(ref[0]),
            "program_flip_share": _flip_share(program, ref[0]),
            "bfloat16_reference_flip_share": _flip_share(low[0], ref[0]),
            "hottest_over_mean_rows": hottest}


def main(argv=None):
    parser = argparse.ArgumentParser(prog="benchmark.models.lfm2_controls")
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--controls", default=",".join(lfm2.CONTROLS),
                        help="comma-separated; '' for the sound run only")
    parser.add_argument("--rows-out", default=None, help=(
        "a file for every compared position's own relative error, "
        "sound and controls: what a statistic is chosen on"))
    args = parser.parse_args(argv)
    config = manifest.load_config(manifest.load_manifest(), args.config)
    engine = lfm2.build_serve_engine(config, args.seed)
    served = served_requests(config, args.seed, engine)
    got = lfm2.serve_engine_outputs(config, args.seed, engine)
    ids, program = router_measurement(config, args.seed, engine)
    lfm2.release(engine.params, engine.kv.k, engine.kv.v)
    del engine
    sequences, lens = lfm2.serve_check_inputs(config, args.seed)
    ref = lfm2.reference_logits(config, args.seed, sequences, lens)
    result = {"seed": args.seed,
              "sound": lfm2.serve_check(config, args.seed, got, served,
                                        ref=ref),
              "router": router_report(config, args.seed, ids, program),
              "bfloat16_matmuls": lfm2.serve_check(
                  config, args.seed, rounding="bfloat16", ref=ref)}
    controls = list(filter(None, args.controls.split(",")))
    for control in controls:
        result[control] = lfm2.serve_control(config, args.seed, control,
                                             served, ref=ref)
    print(json.dumps(result), flush=True)
    if args.rows_out:
        rows = {"lens": lens, "sound": [
            lfm2.row_rel_err(g, r).tolist() for g, r in zip(got, ref)]}
        for control in controls:
            try:
                wrong = lfm2.control_kwargs(config, control)
            except KeyError:
                continue
            rows[control] = [
                lfm2.row_rel_err(g, r).tolist() for g, r in zip(
                    lfm2.reference_logits(config, args.seed, sequences,
                                          lens, **wrong), ref)]
        with open(args.rows_out, "w") as f:
            json.dump(rows, f)


if __name__ == "__main__":
    main()
