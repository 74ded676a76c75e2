"""Device time of a prefill chunk's attention over its pages at a cell's
shape, one layer, ONE chip: the ``chunk_attention`` kernel
(ops/pallas/chunk_attention.py) beside the XLA loop it stands in for
(ops/chunk_attention.py), both read from a profiler trace (not a host
clock). Needs a TPU.

    chiprun -- python tests/perf/chunk_attention_microbench.py \
        [--shape ide|rag] [--s 512,1024,2048] \
        [--starts 0,4096,8192,22528] [--windows W,0] [--no-loop] \
        [--seed 0] [--module label=path/to/chunk_attention.py] \
        [TQ,TK,SUB ...]

``--shape``: ``ide`` is ``mellum2-12b-a2.5b-serve`` (32 query heads on 4
key-value heads of 128: 8 heads a group, 512 lanes a pool row, a sliding
table of 193 columns, window 1,024), ``rag`` is ``command-a-plus-serve``
(128 on 8: 16 a group, 1,024 lanes, 385 columns, window 4,096); pages of
16 tokens and bfloat16 pools in both. A chunk of ``s`` queries starts at
absolute position ``start``; without a window (``0``) its table is the
full group's 2,048 columns, with one (the shape's unless ``--windows``
names others) the sliding group's, whose column 0 is the first page with
a visible key (inference/paging.py), so the chunk sits at ``start -
base`` in it. A tile given as ``TQ,TK,SUB`` takes the place of the
kernel's own (``tiles``); none: its own only. ``--module``: another
file's kernel beside the tree's on the same inputs (the parent commit's
under ``_chip_checkout/parent``), every tile run on both. One JSON line
a kernel and configuration: the tile it ran, the block pairs the call
visits of the dense rectangle's (every tile against every live block),
the kernel's device ms a call (the ``%chunk_attention`` events), the
whole call's (the transposes into and out of the kernel's layout with
it), the share of the MXU's bf16 peak for the keys the visited blocks hold
(2 matmuls x 2 flops over 197 TFLOP/s) and for the keys the queries MUST visit (what
``chunk_attention_roofline.rag`` counts: a coarser tile reads lower),
the largest difference between kernel and loop, and on the tree's own
tile the loop's ms.
"""
import argparse
import importlib.util
import json
import os
import sys
import tempfile

from flash_attention_microbench import kernel_ms

ITERS = 5
D_HEAD, PAGE, FULL_COLUMNS = 128, 16, 2048
# query heads, key-value heads, the sliding table's columns, the window
SHAPES = {"ide": (32, 4, 193, 1024), "rag": (128, 8, 385, 4096)}
PEAK_FLOPS = 197e12       # benchmark/peaks.json, TPU v5e bf16


def load(path):
    """Another file's kernel as a module of the package (its relative
    imports are the tree's)."""
    spec = importlib.util.spec_from_file_location(
        "deepspeed_tpu.ops.pallas._chunk_variant_%d" % abs(hash(path)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


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


def pairs_visited(at, s, tq, tk, window, short=None):
    """(tile, block) pairs the kernel visits for a chunk of ``s`` live
    queries at table position ``at``, the dense rectangle's, and the
    keys the visited blocks hold. ``short``: the walk starts at the page
    of a tile's first visible key and may end in a block of ``short``
    keys (PR 53; 0: no short block); None: blocks at multiples of ``tk``
    (the kernel before it)."""
    live = at + s - 1
    visited = keys = 0
    for q0 in range(at, at + s, tq):
        first = 0 if window is None else max(q0 - window + 1, 0)
        last = min(q0 + tq - 1, live)
        if short is None:
            blocks = last // tk - first // tk + 1
            keys += blocks * tk
        else:
            whole, rest = divmod(last - first // PAGE * PAGE + 1, tk)
            blocks = whole + (rest > 0)
            keys += whole * tk + (0 if not rest else
                                  short if rest <= short else tk)
        visited += blocks
    return visited, (s // tq) * (live // tk + 1), keys


def keys_needed(at, s, window):
    """Keys the chunk's queries must visit, summed over the queries."""
    return sum(min(p + 1, window or p + 1) for p in range(at, at + s))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), default="ide")
    ap.add_argument("--s", default="512,1024,2048")
    ap.add_argument("--starts", default="0,4096,8192,22528")
    ap.add_argument("--windows", default=None)
    ap.add_argument("--no-loop", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--module", action="append", default=[])
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
    from deepspeed_tpu.ops.pallas import chunk_attention as tree

    heads, kv_heads, window_columns, shape_window = SHAPES[ns.shape]
    windows = [shape_window, None] if ns.windows is None else \
        [int(v) or None for v in ns.windows.split(",")]
    kernels = [(label, load(path)) for label, path in
               (m.split("=", 1) for m in ns.module)] + [("tree", tree)]
    rng = np.random.default_rng(ns.seed)
    lanes, group = kv_heads * D_HEAD, heads // kv_heads
    pools = {}
    for windowed, columns in ((False, FULL_COLUMNS), (True, window_columns)):
        shape = (columns + 1, 2, PAGE, lanes)
        pools[windowed] = tuple(
            jnp.asarray(rng.standard_normal(shape, np.float32), jnp.bfloat16)
            for _ in range(2)) + (jnp.asarray(
                rng.permutation(np.arange(1, columns + 1))[None], jnp.int32),)
    layer = jnp.ones((1,), jnp.int32)
    asked = [tuple(int(v) for v in t.split(",")) for t in ns.tiles]

    for s in (int(v) for v in ns.s.split(",")):
        q = jnp.asarray(rng.standard_normal((1, s, heads, D_HEAD),
                                            np.float32), jnp.bfloat16) * 0.3
        valid = jnp.full((1,), s, jnp.int32)
        for window in windows:
            k_pool, v_pool, table = pools[window is not None]
            loop = jax.jit(lambda q, at: paged_blocked_attention(
                q, k_pool, v_pool, 1, table, at, valid, PAGE, window))
            # one compile a (kernel, tile): the start is an argument
            fns = {}
            for start in (int(v) for v in ns.starts.split(",")):
                base = 0 if window is None else \
                    max(start - window + 1, 0) // PAGE * PAGE
                at = start - base
                if at + s > table.shape[1] * PAGE:
                    continue
                pos = jnp.full((1,), at, jnp.int32)
                want = loop_ms = None
                if not ns.no_loop:
                    want, (_, loop_ms) = traced(loop, (q, pos))
                needed = keys_needed(at, s, window) * 4 * heads * D_HEAD
                for label, module in kernels:
                    own = module.tiles(s, group, D_HEAD, lanes, 2,
                                       table.shape[1] * PAGE, PAGE, window)
                    for tile in [None] + [t for t in asked if s % t[0] == 0]:
                        tq, tk, sub = tile or own
                        line = dict(
                            kernel=label, shape=ns.shape, s=s, window=window,
                            start=start, table_position=at,
                            tile=[tq, tk, sub], own_tile=tile is None)
                        if (label, tile) not in fns:
                            fns[label, tile] = jax.jit(
                                lambda q, at, call=module._call, tile=tile:
                                call(q, k_pool, v_pool, layer, table, at,
                                     valid, window=window, interpret=False,
                                     tile=tile))
                        if fns[label, tile] is None:
                            continue       # refused at an earlier start
                        try:
                            got, (kernel, call) = traced(fns[label, tile],
                                                         (q, pos))
                        except Exception as e:  # noqa: BLE001 - a refused tile
                            fns[label, tile] = None
                            line["error"] = str(e)[:300]
                            print(json.dumps(line), flush=True)
                            continue
                        short = getattr(module, "_short_block", None)
                        visited, dense, keys = pairs_visited(
                            at, s, tq, tk, window,
                            short and short(tq, tk, PAGE))
                        flops = 4 * tq * keys * heads * D_HEAD
                        line.update(
                            pairs_visited=visited, pairs_dense=dense,
                            kernel_ms=round(kernel, 4),
                            call_ms=round(call, 4),
                            us_a_pair=round(kernel * 1e3 / visited, 2),
                            mxu_peak_share=round(
                                flops / PEAK_FLOPS / (kernel * 1e-3), 4),
                            roofline_share=round(
                                needed / PEAK_FLOPS / (kernel * 1e-3), 4))
                        if want is not None:
                            line["max_abs_diff"] = float(jnp.max(jnp.abs(
                                got - want)))
                            if tile is None and label == "tree":
                                line.update(
                                    loop_ms=round(loop_ms, 4),
                                    speedup=round(loop_ms / call, 2))
                        line["device"] = jax.devices()[0].device_kind
                        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
