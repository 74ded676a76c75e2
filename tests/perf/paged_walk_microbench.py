"""Device time of the three page walks of ops/pallas/paged_attention.py
(``paged_attention``, ``paged_attention_grouped``, ``mla_decode``) for one
layer's decode step at the shapes of the cells that run them, ONE chip,
bfloat16 pools, read from a profiler trace (not a host clock). Needs a
TPU.

    chiprun -- python tests/perf/paged_walk_microbench.py \
        [--shapes docs,chat,evals,ide_full,ide_window,extract,rollouts,rag_full,rag_window,reasoning] \
        [--blocks 0,8,16,32,64] \
        [--module label=path/to/paged_attention.py[@chunk=16]]

``--blocks``: pages a loop turn handed to the walk (``0``: the kernel's
own choice). ``--module``: another file's kernel beside the tree's (the
parent's, say, unpacked under ``_chip_checkout/``), loaded in the
tree's package, and handed the integer keywords after ``@`` (the parent's
``chunk``). One JSON line a shape, kernel
and block: the kernel's device ms a call (the
``%paged_attention_grouped`` events), the whole call's (the queries'
layout with it), the share of the HBM roofline (the live pages' bytes of
K and V over 819 GB/s over the kernel's time: what
``paged_attention_roofline.ide`` prices) and the largest difference from
the first kernel of the line's shape.

The latent shape (``reasoning``: Moonlight's 16 heads over rows of 640
lanes, 5 layers in the pool so that the layer strides as in the cell)
reads the ``%mla_decode`` events; its share prices the live pages'
USEFUL bytes, 576 of a row's 640 lanes, as ``mla_decode_roofline`` does;
``dead_fetched_share`` is the share of the pages a whole-block fetch
brings that lie past a slot's live ones (``@live_only=1`` after a
``--module``'s path: that kernel fetches live pages only, the parent's
before PR 47). ``--blocks`` and ``@block=`` set the module's
``_MLA_BLOCK_TOKENS`` there: the block follows from it.

The shapes ``docs``, ``chat`` and ``evals`` are those of the walk for
pools with a key-value head a query head (``_kernel``, the
``%paged_attention`` events): GPT-2 medium's 16 heads of 64 over 1,024
lanes in the two GPT-2 cells, Olmo-Hybrid's 30 of 128 over 3,840. Chat's
57 dead slots come as the decode program hands them: position 0, ONE
query (``valid_lens`` is the launch's width for every slot) and a row of
the garbage page. ``--blocks`` and ``@block=`` set the module's
``_BLOCK_TOKENS`` there (a file without it, the parent's before PR 55,
takes no block); ``@walks_dead=1`` says that the file's kernel walks a
dead slot's garbage page, which is all a walk that fetches a slot's live
pages only can have in ``dead_fetched_share``.
"""
import argparse
import importlib
import importlib.util
import json
import os
import sys
import tempfile

from flash_attention_microbench import kernel_ms

ITERS = 5
PAGE = 16
PEAK_BYTES_PER_S = 819e9      # benchmark/peaks.json, TPU v5e

# cell: slots, query heads, key-value heads, d_head, table columns,
# window, (fewest, most) live tokens a slot
SHAPES = {
    # mellum2-12b-a2.5b-serve.ide: 2 full layers, 6 sliding
    "ide_full": (128, 32, 4, 128, 2048, None, (1000, 12000)),
    "ide_window": (128, 32, 4, 128, 193, 1024, (1024, 1040)),
    # lfm2-8b-a1b-serve.extract, jamba2-3b-serve.rollouts
    "extract": (384, 32, 8, 64, 192, None, (300, 1300)),
    "rollouts": (384, 20, 1, 128, 192, None, (300, 1500)),
    # command-a-plus-serve.rag: 1 full layer, 3 sliding; 16 query heads
    # a key-value head, a decode query 128 rows over 1,024 packed lanes
    "rag_full": (40, 128, 8, 128, 2048, None, (2300, 25000)),
    "rag_window": (40, 128, 8, 128, 385, 4096, (4096, 4112)),
}
# cell: slots, heads, d_head, table columns, live slots, (fewest, most)
# live tokens of a live slot: the walk of ``_kernel``
FULL = {
    # gpt2-350m-serve-batch.docs, gpt2-350m-serve.chat
    "docs": (128, 16, 64, 64, 128, (528, 1008)),
    "chat": (64, 16, 64, 48, 7, (32, 768)),
    # olmo-hybrid-7b-serve.evals: 4 full layers of 30 heads of 128
    "evals": (64, 30, 128, 192, 64, (160, 3072)),
}
# moonlight-16b-a3b-serve.reasoning: slots, heads, a row's lanes, the
# lanes that are its value, its useful lanes, table columns, layers in
# the pool, live tokens a slot (lognormal about 2,300, clipped: the mean
# comes out near 2,500)
LATENT = {"reasoning": (320, 16, 640, 512, 576, 512, 5, (1000, 6000))}


def load(path):
    spec = importlib.util.spec_from_file_location(
        "deepspeed_tpu.ops.pallas._walk_variant_%d" % abs(hash(path)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def traced(fn, args, event="paged_attention_grouped"):
    import jax
    out = jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(ITERS):
            last = fn(*args)
        jax.block_until_ready(last)
        jax.profiler.stop_trace()
        kernels, busy_ms, _ = kernel_ms(tmp, ITERS)
        return out, kernels.get(event, 0.0), busy_ms


def table_of(rng, pages, columns):
    """A page table whose rows hold each slot's live pages, drawn
    without order from 1 .. the pages' sum, then the garbage page."""
    import numpy as np
    total = int(pages.sum())
    table = np.zeros((len(pages), columns), np.int32)
    order = rng.permutation(np.arange(1, total + 1))
    for i, at in enumerate(np.cumsum(pages) - pages):
        table[i, :pages[i]] = order[at:at + pages[i]]
    return table, total


def latent(name, kernels, rng):
    """One line a kernel and block for the latent walk at ``name``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    b, h, lanes, rank, useful, columns, layers, (low, high) = LATENT[name]
    live = np.clip(rng.lognormal(np.log(2300.0), 0.45, b), low,
                   high).astype(np.int64)
    pages = -(-live // PAGE)
    table, total = table_of(rng, pages, columns)
    # one layer's rows under every layer (a draw of the whole pool would
    # take twice its 5 GB in float32 on the way), pad lanes zero
    pool = jnp.tile(jax.random.normal(
        jax.random.PRNGKey(1), (total + 1, 1, PAGE, lanes), jnp.bfloat16)
        * (jnp.arange(lanes) < useful).astype(jnp.bfloat16),
        (1, layers, 1, 1))
    q = jax.random.normal(jax.random.PRNGKey(3), (b, 1, h, lanes),
                          jnp.bfloat16).at[..., useful:].set(0)
    args = (q, pool, jnp.asarray(table), jnp.asarray(live - 1, jnp.int32),
            jnp.ones((b,), jnp.int32))
    floor_s = total * PAGE * useful * 2 / PEAK_BYTES_PER_S
    own = {id(m): m._MLA_BLOCK_TOKENS for _, m, _ in kernels}
    first = None
    for label, module, more in kernels:
        more = dict(more)
        live_only = more.pop("live_only", 0)
        block = more.pop("block", 0) or own[id(module)] // PAGE
        fetched = -(-pages // block) * block
        line = dict(shape=name, kernel=label, block=block,
                    mean_live_tokens=float(live.mean()),
                    pages_read=total, dead_fetched_share=0.0 if live_only
                    else round(1 - total / int(fetched.sum()), 4), **more)
        try:
            module._MLA_BLOCK_TOKENS = block * PAGE
            fn = jax.jit(lambda *a: module.mla_decode(
                *a, layer_idx=layers - 2, page_size=PAGE, rank=rank,
                sm_scale=192 ** -0.5, interpret=False, **more))
            out, walk_ms, call_ms = traced(fn, args, "mla_decode")
        except Exception as e:  # noqa: BLE001 - a refused block
            line["error"] = str(e)[-300:]
            print(json.dumps(line), flush=True)
            continue
        first = out if first is None else first
        line.update(
            kernel_ms=walk_ms, call_ms=call_ms,
            hbm_roofline_share=round(floor_s / (walk_ms * 1e-3), 4),
            max_abs_diff=float(jnp.max(jnp.abs(out - first))),
            device=jax.devices()[0].device_kind)
        print(json.dumps(line), flush=True)


def full(name, kernels, rng):
    """One line a kernel and block for ``_kernel``'s walk at ``name``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    b, h, dh, columns, alive, (low, high) = FULL[name]
    live = np.zeros(b, np.int64)
    live[rng.permutation(b)[:alive]] = rng.integers(low, high, alive)
    pages = -(-live // PAGE)
    table, total = table_of(rng, pages, columns)
    shape = (total + 1, 2, PAGE, h * dh)
    k_pool, v_pool = (jax.random.normal(jax.random.PRNGKey(i), shape,
                                        jnp.bfloat16) for i in (1, 2))
    q = jax.random.normal(jax.random.PRNGKey(3), (b, 1, h, dh), jnp.bfloat16)
    args = (q, k_pool, v_pool, jnp.asarray(table),
            jnp.asarray(np.maximum(live - 1, 0), jnp.int32),
            jnp.ones((b,), jnp.int32))
    floor_s = total * 2 * PAGE * h * dh * 2 / PEAK_BYTES_PER_S
    own = {id(m): getattr(m, "_BLOCK_TOKENS", None) for _, m, _ in kernels}
    first = None
    for label, module, more in kernels:
        more = dict(more)
        walks_dead = more.pop("walks_dead", 0)
        line = dict(shape=name, kernel=label, pages_read=total,
                    live_slots=alive, **more)
        try:
            if own[id(module)] is not None:
                module._BLOCK_TOKENS = more.get("block", 0) * PAGE \
                    or own[id(module)]
            elif "block" in more:
                raise ValueError("this file's walk takes no block")
            fetched = total + (b - alive) * walks_dead
            line.update(
                pages_a_turn=module._pages_per_block(columns, PAGE, h * dh,
                                                     2),
                dead_fetched_share=round(1 - total / fetched, 4))
            fn = jax.jit(lambda *a: module.paged_attention(
                *a, layer_idx=1, page_size=PAGE, interpret=False))
            out, walk_ms, call_ms = traced(fn, args, "paged_attention")
        except Exception as e:  # noqa: BLE001 - a refused block
            line["error"] = str(e)[-300:]
            print(json.dumps(line), flush=True)
            continue
        # a dead slot's row is read by nobody: compare the live ones
        out = out[live > 0]
        first = out if first is None else first
        line.update(
            kernel_ms=walk_ms, call_ms=call_ms,
            hbm_roofline_share=round(floor_s / (walk_ms * 1e-3), 4),
            max_abs_diff=float(jnp.max(jnp.abs(out - first))),
            device=jax.devices()[0].device_kind)
        print(json.dumps(line), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join([*FULL, *SHAPES, *LATENT]))
    ap.add_argument("--blocks", default="0")
    ap.add_argument("--module", action="append", default=[])
    ns = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        sys.exit("the micro-benchmark measures device time: it needs a TPU")
    # the package exports a function of the module's name
    tree = importlib.import_module("deepspeed_tpu.ops.pallas.paged_attention")

    kernels = []
    for label, path in (m.split("=", 1) for m in ns.module):
        path, _, more = path.partition("@")
        kernels.append((label, load(path), {
            k: int(v) for k, v in (kv.split("=") for kv in
                                   more.split(",") if kv)}))
    kernels += [("tree", tree, {"block": int(v)} if int(v) else {})
                for v in ns.blocks.split(",")]
    rng = np.random.default_rng(0)
    for name in ns.shapes.split(","):
        if name in LATENT:
            latent(name, kernels, rng)
            continue
        if name in FULL:
            full(name, kernels, rng)
            continue
        b, h, kvh, dh, columns, window, (low, high) = SHAPES[name]
        live = rng.integers(low, high, b)
        pages = -(-live // PAGE)
        table, total = table_of(rng, pages, columns)
        shape = (total + 1, 2, PAGE, kvh * dh)
        k_pool, v_pool = (jax.random.normal(jax.random.PRNGKey(i), shape,
                                            jnp.bfloat16) for i in (1, 2))
        q = jax.random.normal(jax.random.PRNGKey(3), (b, 1, h, dh),
                              jnp.bfloat16)
        args = (q, k_pool, v_pool, jnp.asarray(table),
                jnp.asarray(live - 1, jnp.int32), jnp.ones((b,), jnp.int32))
        # what the walk must read: with a window the pages from the
        # first that holds a visible key
        seen = pages if window is None else \
            pages - np.maximum(live - window, 0) // PAGE
        floor_s = int(seen.sum()) * 2 * PAGE * kvh * dh * 2 / PEAK_BYTES_PER_S
        first = None
        for label, module, more in kernels:
            line = dict(shape=name, kernel=label, pages_read=int(seen.sum()),
                        **more)
            try:
                fn = jax.jit(lambda *a: module._grouped_paged_attention(
                    *a, layer_idx=1, page_size=PAGE, interpret=False,
                    window=window, **more))
                out, walk_ms, call_ms = traced(fn, args)
            except Exception as e:  # noqa: BLE001 - a refused block
                line["error"] = str(e)[-300:]
                print(json.dumps(line), flush=True)
                continue
            first = out if first is None else first
            line.update(
                kernel_ms=walk_ms, call_ms=call_ms,
                hbm_roofline_share=round(floor_s / (walk_ms * 1e-3), 4),
                max_abs_diff=float(jnp.max(jnp.abs(out - first))),
                device=jax.devices()[0].device_kind)
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
