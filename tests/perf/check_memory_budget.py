"""Memory-regression guard at REALISTIC widths (VERDICT r2 #9).

Compiles (never runs) the production-config GPT-2-medium fused train step
and the packed flash kernels at bench shapes ON THE TPU and asserts the
compiler's HBM estimates stay inside the v5e budget. A kernel change that
reintroduces a whole-K/V-resident operand (the seq-8k OOM fixed in r1) or
breaks remat turns this red — as a compile failure (a scoped-vmem
overflow is a compile error) or a budget assert.

Needs the real chip (CPU buffer assignment does not model fwd/bwd
liveness — remat is invisible there; tests/unit/test_pipe_memory.py covers
the loop-carry class of regression on the CPU mesh). Run manually:

    python tests/perf/check_memory_budget.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

V5E_HBM = 16 * 2 ** 30
# measured 2026-07-31 (r3): temp+args = 14.88 GB at the bench shape — the
# bench deliberately sits near the HBM ceiling (mb=32 OOMs by ~21 MB), so
# the budget is a thin guard band under the 16 GB chip: any regression
# that grows the step's working set >4% would also kill the bench config
STEP_BUDGET = 15.5 * 2 ** 30


def main():
    import jax
    import jax.numpy as jnp
    import jax.random as jrandom
    assert jax.devices()[0].platform != "cpu", \
        "this guard needs the TPU (CPU buffer stats don't model liveness)"

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import gpt2

    results = {}

    # --- full train step, GPT-2 medium bench shape (mb=24, seq=1024) ---
    cfg = gpt2.config_for("gpt2_medium")
    model = gpt2.make_gpt2_model(config=cfg)
    engine, _, _, _ = deepspeed.initialize(model=model, config_params={
        "train_micro_batch_size_per_gpu": 24,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "steps_per_print": 10 ** 9,
    })
    ids = np.zeros((1, 24, 1024), np.int32)
    batch = engine._to_device_stacked((ids, ids.copy()))
    fused = engine._get_jit("fused_train", engine._fused_train_fn,
                            donate_argnums=(0,))
    compiled = fused.lower(engine.state, batch, jrandom.PRNGKey(0),
                           engine._hyper(), None).compile()
    ma = compiled.memory_analysis()
    step_total = ma.temp_size_in_bytes + ma.argument_size_in_bytes
    results["gpt2_medium_step"] = {
        "temp_bytes": int(ma.temp_size_in_bytes),
        "arg_bytes": int(ma.argument_size_in_bytes),
        "total_bytes": int(step_total),
        "budget_bytes": int(STEP_BUDGET),
    }
    assert step_total <= STEP_BUDGET, (
        "GPT-2-medium step HBM estimate {:.2f} GB exceeds the {:.2f} GB "
        "guard budget".format(step_total / 2 ** 30, STEP_BUDGET / 2 ** 30))

    # --- flash kernels at long seq (the whole-K/V-residency regression
    # class): compiling fwd+bwd at seq 8192 IS the assertion — resident
    # operands overflow the 16M scoped-vmem budget and fail to compile ---
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    b, s, h, d = 4, 8192, 16, 64
    x = jnp.zeros((b, s, h, d), jnp.bfloat16)

    def attn_loss(q):
        return fa.flash_attention_bshd(q, q, q).astype(jnp.float32).sum()

    c2 = jax.jit(jax.grad(attn_loss)).lower(x).compile()
    ma2 = c2.memory_analysis()
    results["flash_seq8k_grad"] = {
        "temp_bytes": int(ma2.temp_size_in_bytes),
        "arg_bytes": int(ma2.argument_size_in_bytes),
    }

    print(json.dumps(results, indent=2))
    out = os.path.join(os.path.dirname(__file__), "MEMORY_BUDGET.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print("OK — wrote", out)


if __name__ == "__main__":
    main()
