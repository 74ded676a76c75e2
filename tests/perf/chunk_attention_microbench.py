"""Device time of a prefill chunk's attention over its pages at a cell's
shape, one layer, ONE chip: the ``chunk_attention`` kernel
(ops/pallas/chunk_attention.py) beside the XLA loop it stands in for
(ops/chunk_attention.py), both read from a profiler trace (not a host
clock). Needs a TPU.

    chiprun -- python tests/perf/chunk_attention_microbench.py \
        [--shape ide|rag|docs|chat] [--s 512,1024,2048] \
        [--starts 0,4096,8192,22528] [--windows W,0] [--valid N] \
        [--no-loop] [--seed 0] \
        [--module label=path/to/chunk_attention.py] [TQ,TK,SUB ...]

``--shape``: ``ide`` is ``mellum2-12b-a2.5b-serve`` (32 query heads on 4
key-value heads of 128: 8 heads a group, 512 lanes a pool row, a sliding
table of 193 columns, window 1,024), ``rag`` is ``command-a-plus-serve``
(128 on 8: 16 a group, 1,024 lanes, 385 columns, window 4,096); pages of
16 tokens and bfloat16 pools in both. A chunk of ``s`` queries starts at
absolute position ``start``; without a window (``0``) its table is the
full group's 2,048 columns, with one (the shape's unless ``--windows``
names others) the sliding group's, whose column 0 is the first page with
a visible key (inference/paging.py), so the chunk sits at ``start -
base`` in it. ``docs`` and ``chat`` are GPT-2's two cells
(``gpt2-350m-serve-batch``, ``gpt2-350m-serve``: 16 heads of 64, one a
key-value head, 1,024 lanes, the cells' own table of 64 columns, no
window): q goes in and the result comes out as the projection's packed
``(1, s, 1024)`` rows in bfloat16, as ``models/gpt2.py`` calls the
kernel, and "the loop" beside it is the gather read the kernel stands in
for there (``_attend_cache_rows`` over ``_gather_pages``). ``--valid``:
the real rows of a prompt shorter than its bucket ``s`` (the rest is the
bucket's padding). A tile given as ``TQ,TK,SUB`` takes the place of the
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
PAGE = 16
# query heads, key-value heads, d_head, the full table's columns, the
# sliding table's columns, the window
SHAPES = {"ide": (32, 4, 128, 2048, 193, 1024),
          "rag": (128, 8, 128, 2048, 385, 4096),
          "docs": (16, 16, 64, 64, None, None),
          "chat": (16, 16, 64, 64, None, None)}
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


def pairs_visited(at, s, tq, tk, window, widths=None, valid=None):
    """(tile, block) pairs the kernel visits for a chunk of ``s``
    queries, ``valid`` of them live (all), at table position ``at``, the
    dense rectangle's, and the keys the visited blocks hold. ``widths``:
    the walk starts at the page of a tile's first visible key and its
    last block may hold one of these lengths short of ``tk`` (PR 53's
    short block, PR 57's steps of a table of one block; ``()``: whole
    blocks only); None: blocks at multiples of ``tk`` (the kernel before
    PR 53)."""
    live = at + (valid or s) - 1
    visited = keys = 0
    for q0 in range(at, min(at + s, live + 1), tq):
        first = 0 if window is None else max(q0 - window + 1, 0)
        last = min(q0 + tq - 1, live)
        if widths is None:
            blocks = last // tk - first // tk + 1
            keys += blocks * tk
        else:
            whole, rest = divmod(last - first // PAGE * PAGE + 1, tk)
            blocks = whole + (rest > 0)
            keys += whole * tk + (rest and min(
                [w for w in widths if w >= rest], default=tk))
        visited += blocks
    return visited, (s // tq) * (live // tk + 1), keys


def keys_needed(at, s, window):
    """Keys the chunk's ``s`` live queries must visit, summed over the
    queries."""
    return sum(min(p + 1, window or p + 1) for p in range(at, at + s))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), default="ide")
    ap.add_argument("--s", default="512,1024,2048")
    ap.add_argument("--starts", default="0,4096,8192,22528")
    ap.add_argument("--windows", default=None)
    ap.add_argument("--valid", type=int, default=None)
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

    heads, kv_heads, d_head, full_columns, window_columns, shape_window = \
        SHAPES[ns.shape]
    packed = window_columns is None        # GPT-2's cells
    windows = [None] if packed else [shape_window, None] \
        if ns.windows is None else \
        [int(v) or None for v in ns.windows.split(",")]
    kernels = [(label, load(path)) for label, path in
               (m.split("=", 1) for m in ns.module)] + [("tree", tree)]
    rng = np.random.default_rng(ns.seed)
    lanes, group = kv_heads * d_head, heads // kv_heads
    pools = {}
    for windowed, columns in ((False, full_columns), (True, window_columns)):
        if columns is None:
            continue
        shape = (columns + 1, 2, PAGE, lanes)
        pools[windowed] = tuple(
            jnp.asarray(rng.standard_normal(shape, np.float32), jnp.bfloat16)
            for _ in range(2)) + (jnp.asarray(
                rng.permutation(np.arange(1, columns + 1))[None], jnp.int32),)
    layer = jnp.ones((1,), jnp.int32)
    asked = [tuple(int(v) for v in t.split(",")) for t in ns.tiles]

    for s in (int(v) for v in ns.s.split(",")):
        # GPT-2's cells: the projection's packed rows in, packed rows out
        q = jnp.asarray(rng.standard_normal(
            (1, s, lanes) if packed else (1, s, heads, d_head), np.float32),
            jnp.bfloat16) * 0.3
        live = min(ns.valid or s, s)
        valid = jnp.full((1,), live, jnp.int32)
        for window in windows:
            k_pool, v_pool, table = pools[window is not None]
            if packed:
                from deepspeed_tpu.models import gpt2

                def rows_of(pool):
                    return gpt2._gather_pages(pool, table, 1).reshape(
                        1, table.shape[1] * PAGE, -1, d_head) \
                        .transpose(0, 2, 1, 3)

                loop = jax.jit(lambda q, at: gpt2._attend_cache_rows(
                    q.reshape(1, s, heads, d_head), rows_of(k_pool),
                    rows_of(v_pool), at, d_head, valid_lens=valid)
                    .astype(q.dtype).reshape(q.shape))
            else:
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
                needed = keys_needed(at, live, window) * 4 * heads * d_head
                for label, module in kernels:
                    own = module.tiles(s, group, d_head, lanes, 2,
                                       table.shape[1] * PAGE, PAGE, window)
                    for tile in [None] + [t for t in asked if s % t[0] == 0]:
                        tq, tk, sub = tile or own
                        line = dict(
                            kernel=label, shape=ns.shape, s=s, valid=live,
                            window=window,
                            start=start, table_position=at,
                            tile=[tq, tk, sub], own_tile=tile is None)
                        if (label, tile) not in fns:
                            # (GPT-2's cells: the packed rows reshaped
                            # as ``models/gpt2.py`` does, bfloat16 out)
                            extra = {"out_dtype": "bfloat16"} if packed \
                                else {}
                            fns[label, tile] = jax.jit(
                                lambda q, at, call=module._call, tile=tile:
                                call(q.reshape(1, s, heads, d_head), k_pool,
                                     v_pool, layer, table, at, valid,
                                     window=window, interpret=False,
                                     tile=tile, **extra).reshape(q.shape))
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
                        if hasattr(module, "_last_widths"):
                            widths = module._last_widths(
                                tq, tk, PAGE, table.shape[1] * PAGE)
                        elif hasattr(module, "_short_block"):
                            short = module._short_block(tq, tk, PAGE)
                            widths = (short,) if short else ()
                        else:
                            widths = None
                        visited, dense, keys = pairs_visited(
                            at, s, tq, tk, window, widths, live)
                        flops = 4 * tq * keys * heads * d_head
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
                                (got - want)[:, :live].astype(jnp.float32))))
                            if tile is None and label == "tree":
                                line.update(
                                    loop_ms=round(loop_ms, 4),
                                    speedup=round(loop_ms / call, 2))
                        line["device"] = jax.devices()[0].device_kind
                        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
