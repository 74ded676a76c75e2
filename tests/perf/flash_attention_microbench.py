"""Device time of the flash kernels at the training cell's shape, by block
shape: ``fused_ln_qkv_attention`` forward and gradient at (20, 1024, 1024)
bf16 on ONE chip, the two Mosaic calls' time read from a profiler trace
(not a host clock). Needs a TPU.

    chiprun -- python tests/perf/flash_attention_microbench.py \
        [--root DIR] [--shape B,S,H,DH] [--yardstick] FQ,FK,BQ,BK ...

A block given as 0 is left to the file's own tables. ``--shape`` is
another (batch, sequence, heads, d_head) than the cell's. ``--root`` runs
another checkout's ``deepspeed_tpu`` (the parent commit unpacked under
``_chip_checkout/``), one process a side. ``--yardstick`` times
``jax.experimental.pallas.ops.tpu``'s flash and splash attention on the
same work in their (b, h, s, d) layout. One JSON line a configuration:
the Mosaic calls' device ms a call by name (``jvp__`` the forward,
``transpose_jvp___`` the backward: the names the benchmark's
``flash_attention_roofline`` reads), the device's busy ms a call, and the
largest other operations.
"""
import argparse
import collections
import glob
import json
import os
import re
import sys
import tempfile
import time

ITERS = 5
B, S, H, DH = 20, 1024, 16, 64        # gpt2-350m-train.seq1024, a layer


_CONTAINER = re.compile(r" (while|conditional|call)\(")


def kernel_ms(trace_dir, iters):
    """{kernel name: device ms a call of the traced function} over the
    Mosaic custom calls of the trace."""
    import jax
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    totals = collections.Counter()
    busy = 0
    others = collections.Counter()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                if _CONTAINER.search(ev.name):
                    continue       # its body's operations are events too
                busy += ev.duration_ns
                if "tpu_custom_call" in ev.name:
                    name = re.sub(r"\.\d+$", "", ev.name.split(" = ")[0])
                    totals[name.lstrip("%")] += ev.duration_ns
                else:
                    others[re.sub(r"\.\d+$", "", ev.name.split(" = ")[0])
                           ] += ev.duration_ns
    return ({k: round(v * 1e-6 / iters, 4) for k, v in totals.items()},
            round(busy * 1e-6 / iters, 4),
            {k: round(v * 1e-6 / iters, 4)
             for k, v in others.most_common(12)})


def timed(fn, args, tag):
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        t0 = time.perf_counter()
        for _ in range(ITERS):
            out = fn(*args)
        jax.block_until_ready(out)
        wall_ms = (time.perf_counter() - t0) * 1e3 / ITERS
        jax.profiler.stop_trace()
        kernels, busy_ms, others = kernel_ms(tmp, ITERS)
    line = {"config": tag, "kernels_ms": kernels, "device_ms": busy_ms,
            "other_ops_ms": others,
            "kernels_sum_ms": round(sum(kernels.values()), 4),
            "wall_ms": round(wall_ms, 3), "first_call_s": round(compile_s, 1),
            "device": jax.devices()[0].device_kind}
    print(json.dumps(line), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    ap.add_argument("--yardstick", action="store_true")
    ap.add_argument("--shape", default=f"{B},{S},{H},{DH}")
    ap.add_argument("--resident-fwd-elems", type=int, default=None,
                    help="RESIDENT_FWD_MAX_ELEMS for this run")
    ap.add_argument("blocks", nargs="*")
    ns = ap.parse_args()
    sys.path.insert(0, ns.root)
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        sys.exit("the micro-benchmark measures device time: it needs a TPU")
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    from deepspeed_tpu.ops.transformer.flash_attention import \
        fused_ln_qkv_attention
    if ns.resident_fwd_elems is not None:
        fa.RESIDENT_FWD_MAX_ELEMS = ns.resident_fwd_elems
    b, s, h, dh = (int(v) for v in ns.shape.split(","))

    d = h * dh
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    bf16 = jnp.bfloat16
    x = jax.random.normal(keys[0], (b, s, d), bf16)
    w = (jax.random.normal(keys[1], (d, 3 * d)) * 0.02).astype(bf16)
    bias = jnp.zeros((3 * d,), bf16)
    ln_s, ln_b = jnp.ones((d,), bf16), jnp.zeros((d,), bf16)
    cot = jax.random.normal(keys[2], (b, s, d), bf16)

    for spec in ns.blocks:
        fq, fk, bq, bk = (int(v) or None for v in spec.split(","))

        def loss(x, ln_s, ln_b, w, bias):
            out = fused_ln_qkv_attention(
                x, ln_s, ln_b, w, bias, h, block_q=fq, block_k=fk,
                bwd_block_q=bq, bwd_block_k=bk)
            return (out.astype(jnp.float32) * cot).sum()

        try:
            timed(jax.jit(jax.grad(loss, argnums=(0, 3))),
                  (x, ln_s, ln_b, w, bias),
                  {"root": ns.root, "shape": ns.shape, "blocks": spec,
                   "resident_fwd_elems": fa.RESIDENT_FWD_MAX_ELEMS,
                   "bwd_mode": fa.BWD_MODE})
        except Exception as e:  # noqa: BLE001 - a refused block set
            print(json.dumps({"config": spec, "error": str(e)[:300]}),
                  flush=True)

    if ns.yardstick:
        from jax.experimental.pallas.ops.tpu import flash_attention as jfa
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk, splash_attention_mask as sm)
        q, k, v = (jax.random.normal(kk, (b, h, s, dh), bf16)
                   for kk in keys[:3])
        cot4 = jax.random.normal(keys[3], (b, h, s, dh), bf16)
        for blk in (256, 512):
            sizes = jfa.BlockSizes(
                block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
                block_q_major_dkv=blk, block_k_major_dkv=blk,
                block_k_dkv=blk, block_q_dkv=blk, block_k_major_dq=blk,
                block_k_dq=blk, block_q_dq=blk)

            def loss_j(q, k, v):
                out = jfa.flash_attention(q, k, v, causal=True,
                                          sm_scale=dh ** -0.5,
                                          block_sizes=sizes)
                return (out.astype(jnp.float32) * cot4).sum()

            timed(jax.jit(jax.grad(loss_j, argnums=(0, 1, 2))), (q, k, v),
                  {"yardstick": "pallas.ops.tpu.flash_attention",
                   "blocks": blk})

            mask = sm.MultiHeadMask([sm.CausalMask((s, s))] * h)
            splash = sk.make_splash_mha(
                mask, head_shards=1, q_seq_shards=1,
                block_sizes=sk.BlockSizes(
                    block_q=blk, block_kv=blk, block_kv_compute=blk,
                    block_q_dkv=blk, block_kv_dkv=blk,
                    block_kv_dkv_compute=blk, block_q_dq=blk,
                    block_kv_dq=blk))

            def loss_s(q, k, v):
                out = jax.vmap(splash)(q * dh ** -0.5, k, v)
                return (out.astype(jnp.float32) * cot4).sum()

            timed(jax.jit(jax.grad(loss_s, argnums=(0, 1, 2))), (q, k, v),
                  {"yardstick": "splash_attention", "blocks": blk})


if __name__ == "__main__":
    main()
