"""Block-size sweep for the packed flash kernels at the bench shape.

Amortizes the per-dispatch latency with lax.scan inside one jit: each
timing runs REPS chained attention steps and fetches one scalar.

    python tests/perf/sweep_flash_blocks.py [--b 96] [--grad]
"""
import argparse
import itertools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

REPS = 8


def timed_scan(step_fn, init, reps=REPS):
    """step_fn: x -> x (same shape). Returns ms per step, amortized."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(x):
        def body(c, _):
            return step_fn(c), None
        out, _ = jax.lax.scan(body, x, None, length=reps)
        return out.astype(jnp.float32).ravel()[0]

    float(run(init))          # compile + warmup
    t0 = time.time()
    float(run(init))
    dt = time.time() - t0
    return round((dt - 0.094) / reps * 1e3, 1)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--b", type=int, default=96)
    parser.add_argument("--s", type=int, default=1024)
    parser.add_argument("--h", type=int, default=16)
    parser.add_argument("--d", type=int, default=64)
    parser.add_argument("--grad", action="store_true")
    args = parser.parse_args()
    b, s, h, d = args.b, args.s, args.h, args.d

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.transformer import flash_attention as fa

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(b, s, h, d) * 0.1, jnp.bfloat16)

    rows = {}
    for bq, bk in [(256, 256), (256, 512), (512, 256), (512, 512),
                   (256, 1024), (512, 1024), (1024, 1024)]:
        def fwd_step(t, bq=bq, bk=bk):
            # chain: out feeds the next call's q so scan can't CSE
            return fa.flash_attention_bshd(t, t, t, block_q=bq, block_k=bk)

        def grad_step(t, bq=bq, bk=bk):
            # pass bwd blocks explicitly: fwd blocks no longer flow into
            # the backward (the bwd defaults to auto_blocks otherwise)
            g = jax.grad(lambda q: fa.flash_attention_bshd(
                q, q, q, block_q=bq, block_k=bk,
                bwd_block_q=bq, bwd_block_k=bk)
                .astype(jnp.float32).sum())(t)
            return g.astype(t.dtype)

        key = "bq{}_bk{}".format(bq, bk)
        try:
            rows[key + "_fwd"] = timed_scan(fwd_step, x)
            if args.grad:
                rows[key + "_grad"] = timed_scan(grad_step, x)
        except Exception as e:  # noqa: BLE001
            rows[key] = "failed: " + str(e)[:90]
    print(json.dumps(rows, indent=2))


if __name__ == "__main__":
    main()
