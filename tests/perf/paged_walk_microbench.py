"""Device time of the grouped page walk (``paged_attention_grouped``,
ops/pallas/paged_attention.py) for one layer's decode step at the shapes
of the three cells that run it, ONE chip, bfloat16 pools, read from a
profiler trace (not a host clock). Needs a TPU.

    chiprun -- python tests/perf/paged_walk_microbench.py \
        [--shapes ide_full,ide_window,extract,rollouts] \
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
}


def load(path):
    spec = importlib.util.spec_from_file_location(
        "deepspeed_tpu.ops.pallas._walk_variant_%d" % abs(hash(path)), path)
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
        kernels, busy_ms, _ = kernel_ms(tmp, ITERS)
        return out, kernels.get("paged_attention_grouped", 0.0), busy_ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
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
        b, h, kvh, dh, columns, window, (low, high) = SHAPES[name]
        live = rng.integers(low, high, b)
        pages = -(-live // PAGE)
        total = int(pages.sum())
        table = np.zeros((b, columns), np.int32)
        order = rng.permutation(np.arange(1, total + 1))
        for i, at in enumerate(np.cumsum(pages) - pages):
            table[i, :pages[i]] = order[at:at + pages[i]]
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
