"""Device time of the gated delta rule's two forms at Olmo-Hybrid-7B's
shape (30 heads, a float32 state of 96 x 5,760 a slot, 12 layers, 64
slots), each beside its oracle. Needs a TPU.

    chiprun -- python tests/perf/gated_delta_microbench.py \
        [--slots 64] [--slot-blocks 8,16,32] [--chunks 128,256,512]

The step: one launch of all 12 layers in place on the pool, the mean of
20 after a warm-up, as ms a layer and as a share of the HBM peak for the
state read and written once (2 x 2.21 MB a slot and layer), and the
largest difference from ``gated_delta_step_xla``. The chunk: one layer's
``gated_delta_chunk`` (XLA, sub-chunks of 64) at each bucket, ms a call,
and the largest difference from the token-by-token oracle.
"""
import argparse
import json
import os
import sys
import time

import numpy as np


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--slots", type=int, default=64)
    parser.add_argument("--slot-blocks", default="8,16,32")
    parser.add_argument("--chunks", default="128,256,512")
    args = parser.parse_args(argv)
    sys.path.insert(0, os.getcwd())
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas import gated_delta as gd
    assert jax.default_backend() == "tpu", "needs a TPU"
    H, dk, dv, L, slots = 30, 96, 192, 12, args.slots
    rng = np.random.default_rng(0)

    def inputs(n):
        q = rng.normal(size=(n, H, dk)).astype(np.float32)
        k = rng.normal(size=(n, H, dk)).astype(np.float32)
        q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
        k /= np.linalg.norm(k, axis=-1, keepdims=True)
        v = rng.normal(size=(n, H, dv)).astype(np.float32)
        g = -rng.uniform(0, 0.3, size=(n, H)).astype(np.float32)
        beta = rng.uniform(0, 2, size=(n, H)).astype(np.float32)
        return tuple(map(jnp.asarray, (q, k, v, g, beta)))

    def timed(fn, *xs, n=20):
        out = fn(*xs)
        jax.block_until_ready(out)
        t = time.perf_counter()
        for _ in range(n):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t) / n, out

    q, k, v, g, beta = inputs(slots)
    a = jnp.exp(g)
    pool = jnp.asarray(rng.normal(size=(L, slots, dk, H * dv)) * 0.1,
                       jnp.float32)
    want_o, want_pool = jax.jit(
        lambda p: gd.gated_delta_step_xla(p, q, k, v, a, beta, 3))(pool)
    state_bytes = 2 * 4 * slots * dk * H * dv
    for sb in map(int, args.slot_blocks.split(",")):
        one = jax.jit(lambda p: gd.gated_delta_step(
            p, q, k, v, a, beta, 3, slot_block=sb))
        got_o, got_pool = one(pool)
        err = (float(jnp.abs(got_o - want_o).max()),
               float(jnp.abs(got_pool - want_pool).max()))

        def all_layers(p):
            outs = []
            for m in range(L):
                o, p = gd.gated_delta_step(p, q, k, v, a, beta, m,
                                           slot_block=sb)
                outs.append(o.sum())
            return p, sum(outs)

        step = jax.jit(all_layers, donate_argnums=0)
        p = pool + 0
        p, _ = step(p)
        jax.block_until_ready(p)
        t = time.perf_counter()
        for _ in range(20):
            p, s = step(p)
        jax.block_until_ready(p)
        ms = 1e3 * (time.perf_counter() - t) / 20 / L
        print(json.dumps({
            "step": {"slots": slots, "slot_block": sb, "ms_a_layer": ms,
                     "hbm_share_pct": 100 * state_bytes / 819e9 / (ms * 1e-3),
                     "max_err_o": err[0], "max_err_state": err[1]}}),
            flush=True)
    s0 = pool[3, 0]
    for T in map(int, args.chunks.split(",")):
        xs = inputs(T)
        for vl in (T, T - 37):
            chunk = jax.jit(lambda *x: gd.gated_delta_chunk(*x, s0, vl))
            oracle = jax.jit(lambda *x: gd.gated_delta_chunk_xla(*x, s0, vl))
            s_c, (o, sT) = timed(chunk, *xs)
            s_o, (o2, sT2) = timed(oracle, *xs, n=3)
            print(json.dumps({"chunk": {
                "tokens": T, "valid_len": vl, "ms": 1e3 * s_c,
                "oracle_ms": 1e3 * s_o,
                "max_err_o": float(jnp.abs(o[:vl] - o2[:vl]).max()),
                "max_err_state": float(jnp.abs(sT - sT2).max()),
                "max_abs_o": float(jnp.abs(o2[:vl]).max())}}), flush=True)


if __name__ == "__main__":
    main()
