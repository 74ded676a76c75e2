"""Device time of a prefill chunk's attention over its pages at the ide
cell's shape (``mellum2-12b-a2.5b-serve``: 32 query heads on 4 key-value
heads of 128, pages of 16 tokens, bfloat16 pools), one layer, ONE chip:
the ``chunk_attention`` kernel (ops/pallas/chunk_attention.py) beside the
XLA loop it stands in for (ops/chunk_attention.py), both read from a
profiler trace (not a host clock). Needs a TPU.

    chiprun -- python tests/perf/chunk_attention_microbench.py \
        [--s 512,1024,2048] [--starts 0,4096,8192,22528] \
        [--windows 1024,0] [--no-loop] [TQ,TK,SUB ...]

A chunk of ``s`` queries starts at absolute position ``start``; without
a window (``0``) its table is the full group's 2,048 columns, with one
the sliding group's 193, whose column 0 is the first page with a visible
key (inference/paging.py), so the chunk sits at ``start - base`` in it.
A tile given as ``TQ,TK,SUB`` takes the place of the kernel's own
(``tiles``); none: its own only. One JSON line a configuration: the
kernel's device ms a call (the ``%chunk_attention`` events), the whole
call's (the transposes into and out of the kernel's layout with it), the
loop's, the block pairs a call visits of the dense rectangle's (every
tile against every live block), the share of the MXU's bf16 peak for the
pairs visited (2 matmuls x 2 flops over 197 TFLOP/s), and the largest
difference between kernel and loop.
"""
import argparse
import json
import os
import sys
import tempfile

from flash_attention_microbench import kernel_ms

ITERS = 5
HEADS, KV_HEADS, D_HEAD, PAGE = 32, 4, 128, 16
WINDOW_COLUMNS, FULL_COLUMNS = 193, 2048
PEAK_FLOPS = 197e12       # benchmark/peaks.json, TPU v5e bf16


def traced(fn, args):
    import jax
    out = jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(ITERS):
            last = fn(*args)
        jax.block_until_ready(last)
        jax.profiler.stop_trace()
        # the flash tool's reader: {Mosaic call: ms}, everything's ms
        kernels, busy_ms, _ = kernel_ms(tmp, ITERS)
        return out, (kernels.get("chunk_attention", 0.0), busy_ms)


def pairs_visited(at, s, tq, tk, window):
    """(tile, block) pairs the kernel visits for a chunk of ``s`` live
    queries at table position ``at``, and the dense rectangle's."""
    live = at + s - 1
    visited = 0
    for q0 in range(at, at + s, tq):
        first = 0 if window is None else max(q0 - window + 1, 0)
        visited += min(q0 + tq - 1, live) // tk - first // tk + 1
    return visited, (s // tq) * (live // tk + 1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--s", default="512,1024,2048")
    ap.add_argument("--starts", default="0,4096,8192,22528")
    ap.add_argument("--windows", default="1024,0")
    ap.add_argument("--no-loop", action="store_true")
    ap.add_argument("tiles", nargs="*")
    ns = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        sys.exit("the micro-benchmark measures device time: it needs a TPU")
    from deepspeed_tpu.ops.chunk_attention import paged_blocked_attention
    from deepspeed_tpu.ops.pallas import chunk_attention as kernel

    rng = np.random.default_rng(0)
    lanes, group = KV_HEADS * D_HEAD, HEADS // KV_HEADS
    pools = {}
    for window, columns, layers in ((None, FULL_COLUMNS, 2),
                                    (1024, WINDOW_COLUMNS, 6)):
        shape = (columns + 1, layers, PAGE, lanes)
        pools[window] = tuple(
            jnp.asarray(rng.standard_normal(shape, np.float32), jnp.bfloat16)
            for _ in range(2)) + (jnp.asarray(
                rng.permutation(np.arange(1, columns + 1))[None], jnp.int32),)
    layer = jnp.ones((1,), jnp.int32)

    for s in (int(v) for v in ns.s.split(",")):
        q = jnp.asarray(rng.standard_normal((1, s, HEADS, D_HEAD),
                                            np.float32), jnp.bfloat16) * 0.3
        valid = jnp.full((1,), s, jnp.int32)
        for window in (int(v) or None for v in ns.windows.split(",")):
            k_pool, v_pool, table = pools[window]
            own = kernel.tiles(s, group, D_HEAD, lanes, 2,
                               table.shape[1] * PAGE, PAGE, window)
            loop = jax.jit(lambda q, at: paged_blocked_attention(
                q, k_pool, v_pool, 1, table, at, valid, PAGE, window))
            for tile in [None] + [tuple(int(v) for v in t.split(","))
                                  for t in ns.tiles]:
                if tile is not None and s % tile[0]:
                    continue
                fn = jax.jit(lambda q, at: kernel._call(
                    q, k_pool, v_pool, layer, table, at, valid,
                    window=window, interpret=False, tile=tile))
                for start in (int(v) for v in ns.starts.split(",")):
                    base = 0 if window is None else \
                        max(start - window + 1, 0) // PAGE * PAGE
                    at = start - base
                    if at + s > table.shape[1] * PAGE:
                        continue
                    pos = jnp.full((1,), at, jnp.int32)
                    tq, tk, sub = tile or own
                    line = dict(
                        s=s, window=window, start=start, table_position=at,
                        tile=[tq, tk, sub], own_tile=tile is None)
                    try:
                        got, (kernel_ms, call_ms) = traced(fn, (q, pos))
                    except Exception as e:  # noqa: BLE001 - a refused tile
                        line["error"] = str(e)[:300]
                        print(json.dumps(line), flush=True)
                        break
                    visited, dense = pairs_visited(at, s, tq, tk, window)
                    flops = visited * 4 * tq * tk * HEADS * D_HEAD
                    line.update(
                        kernel_ms=round(kernel_ms, 4),
                        call_ms=round(call_ms, 4),
                        pairs_visited=visited, pairs_dense=dense,
                        us_a_pair=round(kernel_ms * 1e3 / visited, 2),
                        mxu_peak_share=round(
                            flops / PEAK_FLOPS / (kernel_ms * 1e-3), 4))
                    if not ns.no_loop and tile is None:
                        want, (_, loop_ms) = traced(loop, (q, pos))
                        line.update(
                            loop_ms=round(loop_ms, 4),
                            speedup=round(loop_ms / call_ms, 2),
                            max_abs_diff=float(jnp.max(jnp.abs(
                                got - want))))
                    line["device"] = jax.devices()[0].device_kind
                    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
