"""Device time of Mamba-2's two forms at granite-4.0-h-small's shape (128
heads of 64, a float32 state of 128 x 8,192 a slot, 9 layers, 96
slots), each beside its oracle. Needs a TPU.

    chiprun -- python tests/perf/ssd_microbench.py [--slots 96] \
        [--tiles 8x1024,8x2048,16x1024] [--chunks 256,2048] \
        [--blocks 128,256]

The step: one launch of all 9 layers in place on the pool, the mean of
20 after a warm-up, as ms a layer and as a share of the HBM peak for the
state read and written once (2 x 4.19 MB a slot and layer), by tile
(slots x lanes a grid step), and the largest difference from
``ssd_step_xla``. The chunk: one layer's ``ssd_chunk`` (XLA) at each
bucket and block of tokens, ms a call, and the largest difference from
the token-by-token oracle.
"""
import argparse
import json
import os
import sys
import time

import numpy as np


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--slots", type=int, default=96)
    parser.add_argument("--tiles", default="8x1024,8x2048,16x1024")
    parser.add_argument("--chunks", default="256,2048")
    parser.add_argument("--blocks", default="128,256")
    args = parser.parse_args(argv)
    sys.path.insert(0, os.getcwd())
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas import mamba2
    assert jax.default_backend() == "tpu", "needs a TPU"
    H, P, N, L, slots = 128, 64, 128, 9, args.slots
    rng = np.random.default_rng(0)

    def inputs(n):
        x = rng.normal(size=(n, H * P)).astype(np.float32)
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                size=(n, H))).astype(np.float32)
        A = -rng.uniform(1, 16, size=(H,)).astype(np.float32)
        B = rng.normal(size=(n, N)).astype(np.float32)
        C = rng.normal(size=(n, N)).astype(np.float32)
        return tuple(map(jnp.asarray, (x, dt, B, C, dt * A,
                                       np.ones((H,), np.float32))))

    def timed(fn, *xs, n=20):
        out = fn(*xs)
        jax.block_until_ready(out)
        t = time.perf_counter()
        for _ in range(n):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t) / n, out

    x, dt, B, C, g, D = inputs(slots)
    a = jnp.exp(g)
    pool = jnp.asarray(rng.normal(size=(L, slots, N, H * P)) * 0.1,
                       jnp.float32)
    want_y, want_pool = jax.jit(
        lambda p: mamba2.ssd_step_xla(p, 3, x, dt, B, C, a, D))(pool)
    state_bytes = 2 * 4 * slots * N * H * P
    for tile in args.tiles.split(","):
        sb, lb = map(int, tile.split("x"))
        one = jax.jit(lambda p: mamba2.ssd_step(
            p, 3, x, dt, B, C, a, D, slot_block=sb, lane_block=lb))
        got_y, got_pool = one(pool)
        err = (float(jnp.abs(got_y - want_y).max()),
               float(jnp.abs(got_pool - want_pool).max()))
        del got_pool

        def all_layers(p):
            outs = []
            for m in range(L):
                y, p = mamba2.ssd_step(p, m, x, dt, B, C, a, D,
                                       slot_block=sb, lane_block=lb)
                outs.append(y.sum())
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
        del p
        print(json.dumps({
            "step": {"slots": slots, "tile": tile, "ms_a_layer": ms,
                     "hbm_share_pct": 100 * state_bytes / 819e9 / (ms * 1e-3),
                     "max_err_y": err[0], "max_err_state": err[1]}}),
            flush=True)
    s0 = pool[3, 0]
    del want_pool
    for T in map(int, args.chunks.split(",")):
        xs = inputs(T)
        for vl in (T, T - 37):
            oracle = jax.jit(lambda *v: mamba2.ssd_chunk_xla(*v, s0, vl))
            s_o, (y2, sT2) = timed(oracle, *xs, n=2)
            for block in map(int, args.blocks.split(",")):
                chunk = jax.jit(lambda *v: mamba2.ssd_chunk(
                    *v, s0, vl, block=block))
                s_c, (y, sT) = timed(chunk, *xs)
                print(json.dumps({"chunk": {
                    "tokens": T, "valid_len": vl, "block": block,
                    "ms": 1e3 * s_c, "oracle_ms": 1e3 * s_o,
                    "max_err_y": float(jnp.abs(y[:vl] - y2[:vl]).max()),
                    "max_err_state": float(jnp.abs(sT - sT2).max()),
                    "max_abs_y": float(jnp.abs(y2[:vl]).max())}}),
                    flush=True)


if __name__ == "__main__":
    main()
