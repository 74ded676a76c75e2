"""End-to-end engine tests: the cifar-smoke equivalent on the CPU mesh.

Mirrors reference tests/unit/test_fp16.py / test_zero.py patterns: tiny
models, a few steps, loss decreases, feature combos agree with each other.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as deepspeed
from simple_model import make_simple_model, SimpleDataset, base_config

HIDDEN = 8
WORLD = 8


def train_steps(engine, dataset, steps, micro_batch=None):
    """Classic DeepSpeed loop: forward/backward/step per micro batch."""
    mb = micro_batch or engine.train_micro_batch_size_per_gpu() * \
        engine.dp_world_size
    losses = []
    idx = 0
    for _ in range(steps):
        x = np.stack([dataset[i % len(dataset)][0]
                      for i in range(idx, idx + mb)])
        y = np.stack([dataset[i % len(dataset)][1]
                      for i in range(idx, idx + mb)])
        idx += mb
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    return losses


def make_engine(config, seed=0, **kwargs):
    model = make_simple_model(HIDDEN, seed=seed)
    engine, _, _, _ = deepspeed.initialize(model=model, config_params=config,
                                           **kwargs)
    return engine


def test_forward_backward_step_reduces_loss():
    engine = make_engine(base_config(WORLD))
    dataset = SimpleDataset(256, HIDDEN)
    losses = train_steps(engine, dataset, 20)
    assert losses[-1] < losses[0] * 0.9, losses


def test_eval_mode_no_grads():
    engine = make_engine(base_config(WORLD))
    dataset = SimpleDataset(64, HIDDEN)
    engine.eval()
    x = np.stack([dataset[i][0] for i in range(32)])
    y = np.stack([dataset[i][1] for i in range(32)])
    loss1 = float(engine(x, y))
    loss2 = float(engine(x, y))
    assert loss1 == pytest.approx(loss2)
    engine.train()


def test_gradient_accumulation_equivalence():
    """gas=2 over half-batches == gas=1 over the full batch."""
    dataset = SimpleDataset(256, HIDDEN)
    cfg1 = base_config(WORLD, micro_batch=8, gas=1)
    cfg2 = base_config(WORLD, micro_batch=4, gas=2)
    e1 = make_engine(cfg1, seed=3)
    e2 = make_engine(cfg2, seed=3)

    full = 8 * WORLD
    half = 4 * WORLD
    for step in range(3):
        x = np.stack([dataset[i][0] for i in range(step * full,
                                                   (step + 1) * full)])
        y = np.stack([dataset[i][1] for i in range(step * full,
                                                   (step + 1) * full)])
        loss = e1(x, y)
        e1.backward(loss)
        e1.step()
        for g in range(2):
            xs = x[g * half:(g + 1) * half]
            ys = y[g * half:(g + 1) * half]
            loss = e2(xs, ys)
            e2.backward(loss)
            e2.step()

    p1 = jax.tree_util.tree_leaves(e1.get_params())
    p2 = jax.tree_util.tree_leaves(e2.get_params())
    for a, b in zip(p1, p2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_boundary_logic():
    engine = make_engine(base_config(WORLD, gas=4))
    assert engine.is_gradient_accumulation_boundary() is False
    engine.micro_steps = 3
    assert engine.is_gradient_accumulation_boundary() is True


def test_fused_train_batch_matches_unfused():
    dataset = SimpleDataset(256, HIDDEN)
    cfg = base_config(WORLD, micro_batch=4, gas=2)
    e1 = make_engine(cfg, seed=5)
    e2 = make_engine(cfg, seed=5)
    half = 4 * WORLD

    for step in range(2):
        xs = [np.stack([dataset[i][0] for i in range(
            (2 * step + g) * half, (2 * step + g + 1) * half)])
            for g in range(2)]
        ys = [np.stack([dataset[i][1] for i in range(
            (2 * step + g) * half, (2 * step + g + 1) * half)])
            for g in range(2)]
        for g in range(2):
            loss = e1(xs[g], ys[g])
            e1.backward(loss)
            e1.step()
        e2.train_batch(batch=(np.stack(xs), np.stack(ys)))

    for a, b in zip(jax.tree_util.tree_leaves(e1.get_params()),
                    jax.tree_util.tree_leaves(e2.get_params())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert e1.global_steps == e2.global_steps


def test_lr_scheduler_warmup():
    cfg = base_config(WORLD)
    cfg["scheduler"] = {"type": "WarmupLR",
                        "params": {"warmup_min_lr": 0.0,
                                   "warmup_max_lr": 0.01,
                                   "warmup_num_steps": 10}}
    engine = make_engine(cfg)
    dataset = SimpleDataset(128, HIDDEN)
    lrs = []
    mb = engine.train_micro_batch_size_per_gpu() * WORLD
    for step in range(5):
        x = np.stack([dataset[i][0] for i in range(mb)])
        y = np.stack([dataset[i][1] for i in range(mb)])
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
        lrs.append(engine.get_lr()[0])
    assert lrs == sorted(lrs)
    assert lrs[-1] < 0.01


def test_fp16_dynamic_loss_scale_overflow_skip():
    cfg = base_config(WORLD)
    cfg["fp16"] = {"enabled": True, "initial_scale_power": 8,
                   "loss_scale_window": 1000}
    engine = make_engine(cfg)
    dataset = SimpleDataset(64, HIDDEN)
    mb = engine.train_micro_batch_size_per_gpu() * WORLD

    x = np.stack([dataset[i][0] for i in range(mb)])
    y = np.stack([dataset[i][1] for i in range(mb)])
    scale0 = engine.loss_scale()
    assert scale0 == 2 ** 8

    # poison one micro batch -> inf loss -> overflow skip + scale halves
    params_before = jax.tree_util.tree_map(np.asarray, engine.get_params())
    x_bad = x.copy()
    x_bad[0, 0] = np.float16(1e4) ** 2 if False else 1e30
    loss = engine(x_bad, y)
    engine.backward(loss)
    engine.step()
    assert engine.skipped_steps == 1
    # default hysteresis=2: first overflow spends hysteresis, keeps scale
    assert engine.loss_scale() == scale0
    params_after = jax.tree_util.tree_map(np.asarray, engine.get_params())
    for a, b in zip(jax.tree_util.tree_leaves(params_before),
                    jax.tree_util.tree_leaves(params_after)):
        np.testing.assert_array_equal(a, b)

    # second overflow halves the scale
    loss = engine(x_bad, y)
    engine.backward(loss)
    engine.step()
    assert engine.skipped_steps == 2
    assert engine.loss_scale() == scale0 / 2

    # clean step trains normally
    loss = engine(x, y)
    engine.backward(loss)
    engine.step()
    assert engine.skipped_steps == 2
    assert engine.global_steps == 3


def test_fp16_converges():
    cfg = base_config(WORLD)
    cfg["fp16"] = {"enabled": True, "loss_scale": 0}
    engine = make_engine(cfg)
    dataset = SimpleDataset(256, HIDDEN)
    losses = train_steps(engine, dataset, 20)
    assert losses[-1] < losses[0] * 0.9


def test_bf16_converges():
    cfg = base_config(WORLD)
    cfg["bf16"] = {"enabled": True}
    engine = make_engine(cfg)
    dataset = SimpleDataset(256, HIDDEN)
    losses = train_steps(engine, dataset, 20)
    assert losses[-1] < losses[0] * 0.9


def test_gradient_clipping_applied():
    cfg = base_config(WORLD, gradient_clipping=1e-4)
    engine = make_engine(cfg)
    dataset = SimpleDataset(64, HIDDEN)
    before = jax.tree_util.tree_map(np.asarray, engine.get_params())
    train_steps(engine, dataset, 1)
    after = jax.tree_util.tree_map(np.asarray, engine.get_params())
    # updates bounded by lr * (clip-influenced update); just check tiny change
    max_delta = max(np.max(np.abs(a - b)) for a, b in
                    zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves(after)))
    assert max_delta < 1e-1


def test_lamb_optimizer():
    cfg = base_config(WORLD)
    cfg["optimizer"] = {"type": "Lamb", "params": {"lr": 1e-2}}
    engine = make_engine(cfg)
    dataset = SimpleDataset(256, HIDDEN)
    losses = train_steps(engine, dataset, 10)
    assert losses[-1] < losses[0]


def test_overflow_fetch_policy():
    """Per-step host overflow readback: required for fp16 (the reference's
    FP16_Optimizer runs CheckOverflow even with a STATIC scale), skipped
    for bf16/fp32 (reference non-fp16 path has no overflow machinery; the
    in-jit guard still no-ops a non-finite step)."""
    import jax.numpy as jnp

    cfg = base_config(WORLD)
    cfg["bf16"] = {"enabled": True}
    assert not make_engine(cfg)._overflow_fetch_needed()

    cfg = base_config(WORLD)
    cfg["fp16"] = {"enabled": True, "loss_scale": 128}   # static fp16
    eng = make_engine(cfg)
    if eng.compute_dtype == jnp.float16:  # on TPU fp16 maps to bf16
        assert eng._overflow_fetch_needed()

    cfg = base_config(WORLD)
    cfg["fp16"] = {"enabled": True}                      # dynamic fp16
    eng = make_engine(cfg)
    assert eng.state["scaler"].dynamic
    assert eng._overflow_fetch_needed()


def test_bf16_state_dtypes_and_convergence():
    """Round-5 HBM levers: optimizer.params.moments_dtype=bf16 stores the
    Adam moments in bf16 (update math fp32) and
    data_types.grad_accum_dtype=bf16 stores the accumulation buffer in
    bf16. State dtypes reflect the config; training still converges and
    tracks the fp32-state trajectory closely at gas=1 (where bf16
    accumulation is lossless — micro grads arrive in the compute dtype)."""
    cfg = base_config(WORLD, bf16={"enabled": True})
    cfg["optimizer"]["params"]["moments_dtype"] = "bf16"
    cfg["data_types"] = {"grad_accum_dtype": "bf16"}
    engine = make_engine(cfg, seed=7)
    acc = jax.tree_util.tree_leaves(engine.state["acc_grads"])[0]
    mom = jax.tree_util.tree_leaves(engine.state["opt"]["exp_avg"])[0]
    assert acc.dtype == jnp.bfloat16
    assert mom.dtype == jnp.bfloat16

    ref_cfg = base_config(WORLD, bf16={"enabled": True})
    ref = make_engine(ref_cfg, seed=7)
    assert jax.tree_util.tree_leaves(
        ref.state["acc_grads"])[0].dtype == jnp.float32

    ds = SimpleDataset(64, HIDDEN)
    losses = train_steps(engine, ds, 30)
    ref_losses = train_steps(ref, ds, 30)
    assert losses[-1] < losses[0] * 0.6
    # same data, same seed: trajectories stay close (moments rounding only)
    drift = max(abs(a - b) for a, b in zip(losses, ref_losses))
    assert drift < 0.15 * abs(ref_losses[0]) + 1e-3, drift


def test_grad_accum_dtype_validation():
    """Unknown grad_accum_dtype values are rejected at config parse."""
    cfg = base_config(WORLD, bf16={"enabled": True})
    cfg["data_types"] = {"grad_accum_dtype": "fp8"}
    with pytest.raises(Exception, match="grad_accum_dtype"):
        make_engine(cfg)


def test_bf16_moments_update_math_fp32():
    """adam_update with bf16 stored moments computes in fp32 and matches
    the fp32-state update to bf16 rounding of the state itself."""
    from deepspeed_tpu.ops.adam.fused_adam import adam_init, adam_update
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(16, 16), jnp.float32)}
    grads = {"w": jnp.asarray(rng.randn(16, 16) * 0.1, jnp.float32)}
    s32 = adam_init(params)
    s16 = adam_init(params, moments_dtype=jnp.bfloat16)
    p32, n32 = adam_update(grads, s32, params, 1e-2, 0.9, 0.999, 1e-8, 0.0,
                           use_pallas=False)
    p16, n16 = adam_update(grads, s16, params, 1e-2, 0.9, 0.999, 1e-8, 0.0,
                           use_pallas=False)
    assert n16["exp_avg"]["w"].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(p16["w"]), np.asarray(p32["w"]),
                               rtol=2e-2, atol=2e-4)


def test_lamb_bf16_moments():
    """FusedLamb carries the same moments_dtype lever as Adam (the
    round-5 BERT bench rides it): bf16 stored moments, fp32 update
    math, pallas combo rejected loudly."""
    from deepspeed_tpu.ops.lamb.fused_lamb import FusedLamb, lamb_update
    import pytest as _pytest
    opt = FusedLamb(lr=1e-3, moments_dtype="bf16")
    params = {"w": jnp.ones((8, 8), jnp.float32)}
    state = opt.init_state(params)
    assert state["exp_avg"]["w"].dtype == jnp.bfloat16
    grads = {"w": jnp.full((8, 8), 0.1, jnp.float32)}
    new_p, new_s = opt.update(grads, state, params, lr=1e-3, beta1=0.9,
                              beta2=0.999, eps=1e-8, weight_decay=0.0)
    assert new_s["exp_avg"]["w"].dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(new_p["w"])).all()
    with _pytest.raises(ValueError, match="incompatible"):
        FusedLamb(use_pallas=True, moments_dtype="bf16")


# ------------------------------------------- the program family is closed
_ZERO2_BF16 = dict(
    bf16={"enabled": True}, zero_optimization={"stage": 2},
    optimizer={"type": "Adam",
               "params": {"lr": 1e-2, "moments_dtype": "bf16"}},
    data_types={"grad_accum_dtype": "bf16"})
_WARMUP = {"type": "WarmupLR", "params": {
    "warmup_min_lr": 0.0, "warmup_max_lr": 0.01, "warmup_num_steps": 100}}

# case -> (config, how a step is driven, what happens between the first
# two steps and the four that must compile nothing)
_TRAIN_FAMILY = {
    # the training cell's shape of config (benchmark/configs/
    # gpt2-350m-train.json): ZeRO-2, bf16 moments and accumulation
    "zero2_bf16": (base_config(WORLD, **_ZERO2_BF16), "fused", None),
    # a changing _hyper() must be an operand, not a constant
    "zero2_bf16_lr_warmup": (
        base_config(WORLD, scheduler=_WARMUP, **_ZERO2_BF16), "fused", None),
    "gas4_train_batch": (
        base_config(WORLD, gas=4, **_ZERO2_BF16), "fused", None),
    # the `micro` and `apply` programs
    "gas4_forward_backward_step": (
        base_config(WORLD, gas=4, **_ZERO2_BF16), "micro", None),
    "zero3": (
        base_config(WORLD, bf16={"enabled": True},
                    zero_optimization={
                        "stage": 3,
                        "stage3_param_persistence_threshold": 0}),
        "fused", None),
    "zero2_quantized_gradients": (
        base_config(WORLD, bf16={"enabled": True},
                    zero_optimization={"stage": 2,
                                       "zero_quantized_gradients": True}),
        "fused", None),
    # scalar state leaves (scale, skip count) after a skipped step
    "fp16_overflow_step": (
        base_config(WORLD, fp16={"enabled": True, "initial_scale_power": 8,
                                 "hysteresis": 1}),
        "fused", "overflow"),
    # loaded leaves: the dtypes and shardings the program was traced with
    "after_checkpoint_load": (
        base_config(WORLD, **_ZERO2_BF16), "fused", "reload"),
}


# a defect this test found (PR 31) and, by that PR's terms, left alone:
# _load_checkpoint_tag casts every optimizer leaf to float32 (bf16
# moments come back float32 and stay so) and leaves opt.step, the
# scaler's leaves and skip_count uncommitted on one device
_RELOAD_DEFECT = pytest.mark.xfail(
    strict=True,
    reason="jit(fused) compiles twice more after load_checkpoint: the "
           "loaded optimizer leaves are float32 whatever moments_dtype "
           "says, and the loaded scalars are not committed replicated")


@pytest.mark.parametrize("case", [
    pytest.param(case, marks=_RELOAD_DEFECT)
    if case == "after_checkpoint_load" else case
    for case in sorted(_TRAIN_FAMILY)])
def test_train_program_family_is_closed(case, compiled_programs, tmp_path):
    """After the first two steps, four more compile nothing: the step's
    programs are fixed by the config, and nothing a step leaves behind
    (a learning rate, a loss scale, a skip count, leaves read back from
    a checkpoint) is a new program to the next one."""
    config, path, between = _TRAIN_FAMILY[case]
    gas = config["gradient_accumulation_steps"]
    dataset = SimpleDataset(1024, HIDDEN)
    micro = config["train_micro_batch_size_per_gpu"] * WORLD
    cursor = iter(range(0, 1 << 30, micro))

    def micro_batch(poison=False):
        lo = next(cursor)
        rows = [dataset[i % len(dataset)] for i in range(lo, lo + micro)]
        x = np.stack([r[0] for r in rows])
        if poison:
            x[0, 0] = 1e30
        return x, np.stack([r[1] for r in rows])

    def step(engine, poison=False):
        batches = [micro_batch(poison) for _ in range(gas)]
        if path == "fused":
            engine.train_batch(batch=tuple(
                np.stack(leaf) for leaf in zip(*batches)))
            return
        for x, y in batches:
            engine.backward(engine(x, y))
            engine.step()

    def warm_engine(seed):
        engine = make_engine(config, seed=seed)
        for _ in range(2):
            step(engine)
        return engine

    engine = warm_engine(0)
    if between == "reload":
        engine.save_checkpoint(str(tmp_path), tag="two")
        # a fresh engine compiles its programs in its first two steps;
        # what it then loads must fit them
        engine = warm_engine(1)
        engine.load_checkpoint(str(tmp_path), tag="two")
    before = len(compiled_programs)
    for i in range(4):
        step(engine, poison=between == "overflow" and i == 1)
    assert compiled_programs[before:] == []
    if between == "overflow":
        assert engine.skipped_steps == 1
        assert engine.loss_scale() == 2 ** 7
