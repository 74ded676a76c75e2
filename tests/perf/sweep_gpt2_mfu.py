"""MFU sweep for the bench workload (GPT-2 125M, ZeRO-2, one chip).

Tries (micro_batch, remat_policy, loss_chunk) combos and prints the MFU of
each, so bench.py can pin the best configuration. Run manually:

    python tests/perf/sweep_gpt2_mfu.py
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def run_one(micro_batch, remat_policy, loss_chunk, seq=1024, steps=10,
            warmup=2, remat=True, size="gpt2_small"):
    import jax
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import gpt2

    cfg = gpt2.config_for(size, max_seq_len=seq, remat=remat,
                          remat_policy=remat_policy, loss_chunk=loss_chunk)
    n_params = gpt2.num_params(cfg)
    model = gpt2.make_gpt2_model(config=cfg)
    ds_config = {
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = deepspeed.initialize(model=model,
                                           config_params=ds_config)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size,
                      size=(1, micro_batch, seq)).astype(np.int32)
    batch = (ids, ids.copy())
    # steps chain through donated state, so blocking on the last loss
    # fences the whole loop
    for _ in range(warmup):
        loss = engine.train_batch(batch=batch)
    jax.block_until_ready(loss)
    t0 = time.time()
    for _ in range(steps):
        loss = engine.train_batch(batch=batch)
    jax.block_until_ready(loss)
    dt = (time.time() - t0) / steps
    toks = micro_batch * seq / dt
    sys.path.insert(0, ".")
    from bench import peak_for
    mfu = 6.0 * n_params * toks / peak_for(jax.devices()[0])
    return dict(micro_batch=micro_batch, remat_policy=remat_policy,
                remat=remat, loss_chunk=loss_chunk,
                step_ms=round(dt * 1e3, 1), tokens_per_s=round(toks),
                mfu=round(mfu, 4))


def main():
    combos = [
        # (size, micro_batch, policy, loss_chunk, remat)
        ("gpt2_small", 192, "full", 128, True),   # current bench config
        ("gpt2_small", 16, "dots", 128, True),    # dots: crash or OOM?
        ("gpt2_small", 48, "dots", 128, True),
        ("gpt2_small", 192, "full", 256, True),
        ("gpt2_small", 256, "full", 64, True),
        ("gpt2_medium", 96, "full", 128, True),   # d=1024: better MXU tiling
        ("gpt2_medium", 64, "full", 128, True),
        ("gpt2_small", 48, "full", 128, False),   # no remat
    ]
    results = []
    for size, mb, pol, chunk, remat in combos:
        try:
            r = run_one(mb, pol, chunk, remat=remat, size=size)
        except Exception as e:  # noqa: BLE001
            r = dict(micro_batch=mb, remat_policy=pol, loss_chunk=chunk,
                     remat=remat, error=str(e)[:200])
        r["size"] = size
        print(json.dumps(r), flush=True)
        results.append(r)
    ok = [r for r in results if "mfu" in r]
    if ok:
        best = max(ok, key=lambda r: r["mfu"])
        print("BEST:", json.dumps(best), flush=True)


if __name__ == "__main__":
    main()
