"""Sparse vs dense attention crossover sweep on the real chip.

Times fwd+bwd attention (grad wrt q/k/v, scan-amortized) for the dense
packed flash kernel vs the block-sparse kernel (fixed layout: local
window + global blocks, unidirectional) across sequence lengths, and
writes tests/perf/SPARSE_VS_DENSE.json with the measured crossover.

The sparse timing includes the (b,s,h,d)->(b,h,s,d) relayout its kernel
needs — the honest end-to-end cost from the model's activation layout.

    python tests/perf/sweep_sparse_vs_dense.py
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

HEADS, DHEAD = 16, 64
BATCH = 2
REPS = 12


def _roundtrip_s():
    """Per-run calibration of the dispatch constant: the wall time
    of fetching one scalar from an already-compiled trivial jit. A fixed
    constant drifts run to run (and once measured -0.6 ms for a 2k dense
    layer); calibrating each sweep keeps the small-ms rows honest."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.float32(0.0)
    float(f(x))
    ts = []
    for _ in range(5):
        t0 = time.time()
        float(f(x))
        ts.append(time.time() - t0)
    return sorted(ts)[len(ts) // 2]


_RT = None


def timed_scan(step_fn, init, reps=REPS):
    import jax
    import jax.numpy as jnp
    global _RT
    if _RT is None:
        _RT = _roundtrip_s()

    @jax.jit
    def run(x):
        def body(c, _):
            return step_fn(c), None
        out, _ = jax.lax.scan(body, x, None, length=reps)
        return out.astype(jnp.float32).ravel()[0]

    float(run(init))
    t0 = time.time()
    float(run(init))
    return ((time.time() - t0) - _RT) / reps * 1e3


def main():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    from deepspeed_tpu.ops.sparse_attention import (
        BigBirdSparsityConfig, FixedSparsityConfig,
        make_block_sparse_attention)
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
        causal_sliding_window_layout)

    results = {"config": {
        "batch": BATCH, "heads": HEADS, "d_head": DHEAD,
        "sparse": "fixed, block 128, 4 local blocks + 1 global, "
                  "unidirectional",
        "timing": "fwd+bwd (grad wrt q,k,v), scan-amortized, ms/layer",
        "bigbird_note": "bigbird (a bidirectional-class layout in the "
                        "reference) is run with causal=True: its "
                        "above-diagonal active blocks are fetched and "
                        "computed but fully masked, so the row is "
                        "COST-faithful for the layout while the math is "
                        "causal, and its reported density overstates "
                        "useful (unmasked) work",
    }, "rows": []}

    for seq in (2048, 4096, 8192, 16384, 32768, 65536):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(BATCH, seq, HEADS, DHEAD) * 0.1,
                        jnp.bfloat16)

        def dense_step(t):
            g = jax.grad(lambda q: fa.flash_attention_bshd(q, q, q)
                         .astype(jnp.float32).sum())(t)
            return g.astype(t.dtype)

        # short sequences run sub-ms per layer: scale reps up so the
        # scan-amortized total dwarfs the dispatch roundtrip jitter (a
        # fixed 12 reps once measured a negative dense ms at 2k)
        reps = max(REPS, (16384 // seq) * REPS)

        row = {"seq": seq}
        try:
            row["dense_ms"] = round(timed_scan(dense_step, x, reps=reps), 2)
        except Exception as err:  # noqa: BLE001
            row["dense_ms"] = "failed: " + str(err)[:80]

        block = 128
        cfg = FixedSparsityConfig(num_heads=HEADS, block=block,
                                  num_local_blocks=4, num_global_blocks=1,
                                  attention="unidirectional")
        layout = np.asarray(cfg.make_layout(seq))
        # pure sliding-window (8 blocks = 1024 tokens lookback): the
        # truly LINEAR layout — the fixed mode's global columns keep its
        # active count growing with position (still ~quadratic overall)
        nb = seq // block
        win = causal_sliding_window_layout(HEADS, nb, 8)
        # bigbird (ITC): window + random + leading-global — the SKEWED
        # layout class the balanced grid exists for (global rows/cols
        # populate a few rows far past the mean)
        bb = np.asarray(BigBirdSparsityConfig(
            num_heads=HEADS, block=block, num_random_blocks=2,
            num_sliding_window_blocks=3, num_global_blocks=1,
            seed=0).make_layout(seq))

        for name, lay in (("sparse", layout), ("window", win),
                          ("bigbird", bb)):
            density = float(lay.mean())
            row[name + "_density"] = round(density, 4)
            attn = make_block_sparse_attention(lay, block, causal=True)

            def sparse_step(t, attn=attn):
                def loss(q):
                    qh = q.transpose(0, 2, 1, 3)   # (b,h,s,d) kernel layout
                    out = attn(qh, qh, qh, None, None)
                    return out.astype(jnp.float32).sum()
                g = jax.grad(loss)(t)
                return g.astype(t.dtype)

            try:
                row[name + "_ms"] = round(
                    timed_scan(sparse_step, x, reps=reps), 2)
            except Exception as err:  # noqa: BLE001
                row[name + "_ms"] = "failed: " + str(err)[:80]

        for name in ("sparse", "window", "bigbird"):
            if isinstance(row.get("dense_ms"), float) and \
                    isinstance(row.get(name + "_ms"), float) and \
                    row["dense_ms"] > 0:
                row[name + "_vs_dense"] = round(
                    row[name + "_ms"] / row["dense_ms"], 2)
        results["rows"].append(row)
        print(json.dumps(row), flush=True)

    for name in ("sparse", "window", "bigbird"):
        wins = [r for r in results["rows"]
                if isinstance(r.get(name + "_ms"), float)
                and isinstance(r.get("dense_ms"), float)
                and r[name + "_ms"] < r["dense_ms"]]
        results[name + "_crossover"] = (
            min(w["seq"] for w in wins) if wins else
            "none at tested lengths")
    path = os.path.join(os.path.dirname(__file__), "SPARSE_VS_DENSE.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps({k: results[k] for k in
                      ("sparse_crossover", "window_crossover",
                       "bigbird_crossover")}))


if __name__ == "__main__":
    main()
