"""Flash backward bake-off across model widths: resident-dq fused vs
explicit-DMA fused vs the split dq + dk/dv pair.

The single-pass fused backward (5 dots/pair vs split's 7) comes in two
variants: the resident-dq kernel (dq accumulates in a whole-(s, h*d) fp32
VMEM output block — no cross-walk DMAs) and the older explicit-DMA
read-modify-write kernel. This times the full grad path
(flash_attention_bshd grad wrt q/k/v) under all three policies on the
real chip at GPT-2-medium (hd 1024), 1280, and gpt2-xl (hd 1600, grouped
13+12 heads) widths.

Single-shot timings swung 10-40% between processes in round 5 (one
probed sample hit 2x). All paths are therefore compiled up front and timed in interleaved round-robin ROUNDS;
the reported number is the per-path MINIMUM (the uncontended floor),
with the median alongside so the artifact shows the noise it was
measured under.

    python tests/perf/compare_xl_bwd.py

Writes XL_BWD_COMPARE.json; the shipped default (auto) must match the
per-width winner.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

REPS = 10          # grad steps chained inside one jit call
ROUNDS = 12        # interleaved timing rounds per path


def _force(x):
    import jax
    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(leaf.ravel()[0])


def _make_loop(q, k, v):
    """Compile a REPS-step chained grad loop under the CURRENT dispatch
    mode (the mode is baked in at trace time)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from deepspeed_tpu.ops.transformer import flash_attention as fa

    def loss(q, k, v):
        return fa.flash_attention_bshd(q, k, v).astype(jnp.float32).sum()

    grad = jax.grad(loss, argnums=(0, 1, 2))

    @jax.jit
    def loop(q, k, v):
        def body(_, carry):
            q, k, v = carry
            dq, dk, dv = grad(q, k, v)
            eps = jnp.bfloat16(1e-6)
            return (q + eps * dq.astype(q.dtype),
                    k + eps * dk.astype(k.dtype),
                    v + eps * dv.astype(v.dtype))
        return lax.fori_loop(0, REPS, body, (q, k, v))

    _force(loop(q, k, v))                      # compile + warm
    return loop


def measure_width(b, s, h, d):
    from deepspeed_tpu.ops.transformer import flash_attention as fa

    rng = np.random.RandomState(0)
    import jax.numpy as jnp
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d) * 0.1, jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    row = {"b": b, "s": s, "h": h, "d": d, "hd": h * d}

    saved_budget = fa.RESIDENT_DQ_MAX_BYTES
    loops = {}

    fa.BWD_MODE = "auto"
    row["auto_plan"] = fa._fused_plan(h * d, h, s)
    row["auto_blocks"] = fa.auto_blocks(h * d, num_heads=h, seq_len=s)
    loops["resident_fused"] = _make_loop(q, k, v)

    fa.BWD_MODE = "fused"
    fa.RESIDENT_DQ_MAX_BYTES = 0          # force the explicit-DMA variant
    loops["dma_fused"] = _make_loop(q, k, v)
    fa.RESIDENT_DQ_MAX_BYTES = saved_budget

    fa.BWD_MODE = "split"
    row["split_blocks"] = fa.auto_blocks(h * d, num_heads=h, seq_len=s)
    loops["split"] = _make_loop(q, k, v)
    fa.BWD_MODE = "auto"

    samples = {name: [] for name in loops}
    for _ in range(ROUNDS):
        for name, loop in loops.items():
            t0 = time.time()
            _force(loop(q, k, v))
            samples[name].append((time.time() - t0) * 1e3 / REPS)
    for name, xs in samples.items():
        row[f"{name}_grad_ms"] = round(min(xs), 2)
        row[f"{name}_grad_ms_median"] = round(sorted(xs)[len(xs) // 2], 2)
    row["resident_vs_split"] = round(
        row["split_grad_ms"] / row["resident_fused_grad_ms"], 3)
    row["resident_vs_dma"] = round(
        row["dma_fused_grad_ms"] / row["resident_fused_grad_ms"], 3)
    return row


def main():
    import jax
    out = {"device": jax.devices()[0].device_kind,
           "method": f"min over {ROUNDS} interleaved rounds of {REPS} "
                     "chained grad steps (shared-chip contention makes "
                     "single-shot timings swing 10-40%)",
           "widths": [measure_width(96, 1024, 16, 64),   # bench shape
                      measure_width(24, 1024, 20, 64),   # hd 1280
                      measure_width(8, 1024, 25, 64)]}   # gpt2-xl, grouped
    path = os.path.join(os.path.dirname(__file__), "XL_BWD_COMPARE.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
