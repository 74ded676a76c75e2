"""Flash attention numerics vs jnp reference (mirrors reference
test_cuda_forward/backward.py tolerance sweeps)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.attention import (
    causal_attention, reference_causal_attention)
from deepspeed_tpu.ops.transformer.flash_attention import flash_attention


def rand_qkv(b, s, h, d, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d) * 0.5, jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("b,s,h,d", [(1, 128, 2, 32), (2, 256, 4, 64),
                                     (1, 384, 2, 64)])
def test_flash_forward_matches_reference(b, s, h, d):
    q, k, v = rand_qkv(b, s, h, d)
    ref = reference_causal_attention(q, k, v)
    out = causal_attention(q, k, v, use_flash=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_flash_backward_matches_reference():
    b, s, h, d = 1, 256, 2, 32
    q, k, v = rand_qkv(b, s, h, d, seed=3)

    def loss_flash(q, k, v):
        out = causal_attention(q, k, v, use_flash=True, interpret=True)
        return jnp.sum(out * jnp.cos(out))

    def loss_ref(q, k, v):
        out = reference_causal_attention(q, k, v)
        return jnp.sum(out * jnp.cos(out))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3)


def test_flash_uneven_seq_blocks():
    # seq not a multiple of the q block: exercises grid cdiv + masking
    b, s, h, d = 1, 320, 2, 32
    q, k, v = rand_qkv(b, s, h, d, seed=5)
    ref = reference_causal_attention(q, k, v)
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    out = flash_attention(fold(q), fold(k), fold(v), None, True, 128, True)
    out = out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_flash_ragged_k_tail_grads():
    # seq with no nice divisor (2*prime) AND block_k < seq so K is truly
    # zero-padded (202 -> 4 blocks of 64): exercises the padded-tail
    # masking in BOTH kernels (fwd scores and bwd dk/dv slicing)
    b, s, h, d = 1, 202, 2, 32
    q, k, v = rand_qkv(b, s, h, d, seed=11)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    def loss_flash(q, k, v):
        out = flash_attention(fold(q), fold(k), fold(v), None, True, 64,
                              True, 64)
        return jnp.sum(out * jnp.sin(out))

    def loss_ref(q, k, v):
        out = reference_causal_attention(q, k, v)
        return jnp.sum(out * jnp.sin(out))

    np.testing.assert_allclose(np.asarray(loss_flash(q, k, v)),
                               np.asarray(loss_ref(q, k, v)),
                               rtol=1e-4, atol=1e-4)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3)


def test_non_causal_mode():
    b, s, h, d = 1, 128, 2, 32
    q, k, v = rand_qkv(b, s, h, d, seed=7)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    out = flash_attention(fold(q), fold(k), fold(v), None, False, 128, True)
    # reference non-causal
    scale = 1.0 / (d ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    probs = jax.nn.softmax(scores, axis=-1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    ref = fold(ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("s", [202, 320, 130])
def test_packed_bshd_ragged_grads(s):
    """The packed (b,s,h*d) kernels' padding masks: seq lengths that are
    not multiples of block_q/block_k must produce reference-equal grads
    (padded q rows SUM into dk/dv if unmasked). Pins the path
    causal_attention actually routes to on TPU."""
    b, h, d = 1, 2, 32
    q, k, v = rand_qkv(b, s, h, d, seed=11)
    from deepspeed_tpu.ops.transformer.flash_attention import (
        flash_attention_bshd)

    def loss_packed(q, k, v):
        out = flash_attention_bshd(q, k, v, None, True, 64, True, 64)
        return jnp.sum(out * jnp.sin(out))

    def loss_ref(q, k, v):
        out = reference_causal_attention(q, k, v)
        return jnp.sum(out * jnp.sin(out))

    np.testing.assert_allclose(np.asarray(loss_packed(q, k, v)),
                               np.asarray(loss_ref(q, k, v)),
                               rtol=1e-4, atol=1e-4)
    gp = jax.grad(loss_packed, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3)


def test_streaming_fwd_matches_resident(monkeypatch):
    """The k-blocked streaming forward (long-seq path) must match the
    resident fast path; force it by shrinking the dispatch threshold."""
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    b, s, h, d = 1, 256, 2, 32
    q, k, v = rand_qkv(b, s, h, d, seed=13)
    ref = reference_causal_attention(q, k, v)
    monkeypatch.setattr(fa, "RESIDENT_FWD_MAX_ELEMS", 0)
    out = fa.flash_attention_bshd(q, k, v, None, True, 64, True, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_streaming_fwd_bwd_grads(monkeypatch):
    """Streaming-forward lse feeds the split backward: gradients through
    the long-seq path must match the reference too."""
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    b, s, h, d = 1, 192, 2, 32
    q, k, v = rand_qkv(b, s, h, d, seed=17)
    monkeypatch.setattr(fa, "RESIDENT_FWD_MAX_ELEMS", 0)

    def loss_stream(q, k, v):
        out = fa.flash_attention_bshd(q, k, v, None, True, 64, True, 64)
        return jnp.sum(out * jnp.sin(out))

    def loss_ref(q, k, v):
        out = reference_causal_attention(q, k, v)
        return jnp.sum(out * jnp.sin(out))

    gs = jax.grad(loss_stream, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3)


def test_packed_bshd_key_padding_mask():
    """mask_bias (key-padding) path vs masked reference, fwd + grads."""
    b, s, h, d = 2, 192, 2, 32
    q, k, v = rand_qkv(b, s, h, d, seed=19)
    rng = np.random.RandomState(19)
    keep = np.ones((b, s), np.float32)
    keep[0, 150:] = 0.0       # pad the tail of example 0
    keep[1, 100:] = 0.0
    bias = jnp.asarray((1.0 - keep) * -1e9)

    from deepspeed_tpu.ops.transformer.flash_attention import (
        flash_attention_bshd)

    def ref(q, k, v):
        scale = 1.0 / (d ** 0.5)
        scores = jnp.einsum("bqhd,bkhd->bhqk",
                            q.astype(jnp.float32) * scale,
                            k.astype(jnp.float32))
        scores = scores + bias[:, None, None, :]
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs,
                          v.astype(jnp.float32)).astype(q.dtype)

    def loss_flash(q, k, v):
        out = flash_attention_bshd(q, k, v, None, False, 64, True, 64,
                                   mask_bias=bias)
        return jnp.sum(out * jnp.sin(out))

    def loss_ref(q, k, v):
        out = ref(q, k, v)
        return jnp.sum(out * jnp.sin(out))

    np.testing.assert_allclose(np.asarray(loss_flash(q, k, v)),
                               np.asarray(loss_ref(q, k, v)),
                               rtol=1e-4, atol=1e-4)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3)


def test_streaming_fwd_key_padding_mask(monkeypatch):
    """The STREAMING forward's bias BlockSpec indexes by k-block; pin it
    with a nonzero mask (the resident-path mask test can't catch a wrong
    index map there)."""
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    b, s, h, d = 1, 192, 2, 32
    q, k, v = rand_qkv(b, s, h, d, seed=23)
    keep = np.ones((b, s), np.float32)
    keep[0, 120:] = 0.0
    bias = jnp.asarray((1.0 - keep) * -1e9)

    ref_out = fa.flash_attention_bshd(q, k, v, None, False, 64, True, 64,
                                      mask_bias=bias)   # resident path
    monkeypatch.setattr(fa, "RESIDENT_FWD_MAX_ELEMS", 0)
    stream_out = fa.flash_attention_bshd(q, k, v, None, False, 64, True, 64,
                                         mask_bias=bias)
    np.testing.assert_allclose(np.asarray(stream_out), np.asarray(ref_out),
                               rtol=1e-4, atol=1e-4)


def test_fused_ln_qkv_attention_matches_unfused():
    """fused_ln_qkv_attention (the remat-friendly custom_vjp: saves
    out/lse, recomputes LN+QKV in bwd) must match the straight-line
    LN -> QKV gemm -> flash composition in value and all five grads."""
    from deepspeed_tpu.ops.transformer.flash_attention import (
        fused_ln_qkv_attention, flash_attention_bshd)
    from deepspeed_tpu.ops.transformer.fused_ops import fused_layer_norm

    b, s, h, d = 2, 128, 4, 32
    dm = h * d
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(b, s, dm) * 0.3, jnp.float32)
    ln_s = jnp.asarray(1.0 + 0.1 * rng.randn(dm), jnp.float32)
    ln_b = jnp.asarray(0.1 * rng.randn(dm), jnp.float32)
    w = jnp.asarray(rng.randn(dm, 3 * dm) * 0.05, jnp.float32)
    bb = jnp.asarray(0.01 * rng.randn(3 * dm), jnp.float32)

    def loss_fused(x, ln_s, ln_b, w, bb):
        out = fused_ln_qkv_attention(x, ln_s, ln_b, w, bb, h,
                                     1e-5, True, 64, 64, True)
        return jnp.sum(out * jnp.sin(out))

    def loss_ref(x, ln_s, ln_b, w, bb):
        ln = fused_layer_norm(x, ln_s, ln_b, 1e-5)
        qkv = ln @ w + bb
        q, k, v = jnp.split(qkv, 3, axis=-1)
        rs = lambda t: t.reshape(b, s, h, d)
        out = flash_attention_bshd(rs(q), rs(k), rs(v), None, True,
                                   64, True, 64)
        return jnp.sum(out.reshape(b, s, dm)
                       * jnp.sin(out.reshape(b, s, dm)))

    np.testing.assert_allclose(
        np.asarray(loss_fused(x, ln_s, ln_b, w, bb)),
        np.asarray(loss_ref(x, ln_s, ln_b, w, bb)), rtol=1e-4, atol=1e-4)
    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4))(x, ln_s, ln_b, w, bb)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(x, ln_s, ln_b, w, bb)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3)


def test_fused_attn_under_remat_matches():
    """jax.checkpoint around the consumer of the fused op: gradients must
    survive the remat rebuild unchanged (the whole point of the op)."""
    from deepspeed_tpu.ops.transformer.flash_attention import (
        fused_ln_qkv_attention)

    b, s, h, d = 2, 128, 4, 32
    dm = h * d
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(b, s, dm) * 0.3, jnp.float32)
    ln_s = jnp.ones((dm,), jnp.float32)
    ln_b = jnp.zeros((dm,), jnp.float32)
    w = jnp.asarray(rng.randn(dm, 3 * dm) * 0.05, jnp.float32)
    bb = jnp.zeros((3 * dm,), jnp.float32)

    def network(x, w, remat):
        ctx = fused_ln_qkv_attention(x, ln_s, ln_b, w, bb, h,
                                     1e-5, True, 64, 64, True)
        rest = lambda x, ctx: jnp.sum((x + ctx) ** 2)
        if remat:
            rest = jax.checkpoint(rest)
        return rest(x, ctx)

    g_plain = jax.grad(network, argnums=(0, 1))(x, w, False)
    g_remat = jax.grad(network, argnums=(0, 1))(x, w, True)
    for a, b_ in zip(g_plain, g_remat):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# What a block pair traces follows what the call can see (PR 41): no bias
# operand without a ``mask_bias``, no tail compare for a sequence that is a
# block's multiple, the scale on q where it is a power of two, heads a
# 128-lane tile at a time, and in the resident backward a diagonal pair
# that walks only the keys its queries see.
# ---------------------------------------------------------------------------
def _dense_reference(q, k, v, causal):
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / (d ** 0.5)
    if causal:
        s = q.shape[1]
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))


def _assert_value_and_grads_match(flash_fn, ref_fn, q, k, v,
                                  tol=1e-4, grad_tol=2e-3):
    def loss(fn):
        def of(q, k, v):
            out = fn(q, k, v).astype(jnp.float32)
            return jnp.sum(out * jnp.sin(out))
        return of
    np.testing.assert_allclose(np.asarray(loss(flash_fn)(q, k, v)),
                               np.asarray(loss(ref_fn)(q, k, v)),
                               rtol=tol, atol=tol)
    got = jax.grad(loss(flash_fn), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref_fn), argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", got, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32),
            rtol=grad_tol, atol=grad_tol, err_msg="d" + name)


def _packed(causal, block_q, block_k, **kw):
    from deepspeed_tpu.ops.transformer.flash_attention import (
        flash_attention_bshd)
    return lambda q, k, v: flash_attention_bshd(
        q, k, v, None, causal, block_q, True, block_k,
        bwd_block_q=block_q, bwd_block_k=block_k, **kw)


@pytest.mark.parametrize("blocks", [(128, 128), (128, 256), (256, 128),
                                    (256, 256)], ids=str)
def test_packed_block_shapes_match_reference(blocks):
    """s 512 cut four ways: pairs wholly under the diagonal, pairs the
    diagonal crosses, and (k block twice the q block) pairs half above
    it, which the resident backward walks by live sub-blocks. Two heads
    of 64: one 128-lane tile (`_head_group`)."""
    q, k, v = rand_qkv(1, 512, 2, 64, seed=41)
    _assert_value_and_grads_match(_packed(True, *blocks),
                                  reference_causal_attention, q, k, v)


@pytest.mark.parametrize("s", [320, 402])
def test_packed_tail_under_a_wide_k_block(s):
    """A sequence that is no multiple of the block (the tail compare is
    traced) with the k block twice the q block (the diagonal pair's
    sub-block walk): padded keys and padded query rows count nowhere."""
    q, k, v = rand_qkv(1, s, 2, 64, seed=43)
    _assert_value_and_grads_match(_packed(True, 128, 256),
                                  reference_causal_attention, q, k, v)


@pytest.mark.parametrize("causal", [True, False])
def test_a_zero_mask_bias_is_no_mask_bias(causal):
    """The bias operand exists only where the caller gave a mask_bias;
    a bias of zeros gives what no bias gives, value and gradients."""
    q, k, v = rand_qkv(2, 256, 2, 64, seed=47)
    zero = jnp.zeros((2, 256), jnp.float32)
    loss = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))
    with_bias = _packed(causal, 128, 128, mask_bias=zero)
    without = _packed(causal, 128, 128)
    np.testing.assert_allclose(np.asarray(with_bias(q, k, v)),
                               np.asarray(without(q, k, v)),
                               rtol=1e-6, atol=1e-6)
    for a, b_ in zip(
            jax.grad(loss(with_bias), argnums=(0, 1, 2))(q, k, v),
            jax.grad(loss(without), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("blocks", [(128, 128), (128, 256)], ids=str)
def test_packed_non_causal_matches_reference(blocks):
    """Non-causal at a block's multiple: no mask is traced at all."""
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    assert fa._score_mask(0, 0, block_q=128, block_k=128, causal=False,
                          seq_len=256) is None
    q, k, v = rand_qkv(1, 256, 2, 64, seed=53)
    _assert_value_and_grads_match(
        _packed(False, *blocks),
        lambda q, k, v: _dense_reference(q, k, v, False), q, k, v)


@pytest.mark.parametrize("causal", [True, False])
def test_packed_d_head_80_keeps_the_scale_on_the_scores(causal):
    """1/sqrt(80) is no power of two: the scale stays on the float32
    scores, and heads of 80 lanes go one at a time."""
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    assert not fa._scale_folds(80 ** -0.5) and fa._head_group(2, 80) == 1
    q, k, v = rand_qkv(1, 256, 2, 80, seed=59)
    _assert_value_and_grads_match(
        _packed(causal, 128, 128),
        lambda q, k, v: _dense_reference(q, k, v, causal), q, k, v)


def test_packed_bf16_tiles_match_reference():
    """The training cell's dtype: bfloat16 operands into the MXU, float32
    softmax; the folded scale (1/8) is exact in bfloat16."""
    q, k, v = (t.astype(jnp.bfloat16) for t in rand_qkv(1, 256, 4, 64, 61))
    _assert_value_and_grads_match(
        _packed(True, 128, 256),
        lambda q, k, v: _dense_reference(q, k, v, True), q, k, v,
        tol=2e-2, grad_tol=4e-2)


@pytest.mark.parametrize("d_head,folds", [(16, True), (32, False),
                                          (64, True), (80, False),
                                          (128, False), (256, True)])
def test_scale_folds_only_for_a_power_of_two(d_head, folds):
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    assert fa._scale_folds(1.0 / d_head ** 0.5) is folds


@pytest.mark.parametrize("heads,d_head,group", [
    (16, 64, 2), (12, 64, 2), (25, 64, 1), (14, 64, 2), (8, 32, 4),
    (6, 32, 1), (20, 80, 1), (16, 128, 1), (4, 256, 1)])
def test_head_group_is_the_heads_a_tile_holds(heads, d_head, group):
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    assert fa._head_group(heads, d_head) == group


def test_auto_blocks_by_width(monkeypatch):
    """Width-aware block defaults, keyed to the backward path taken. AUTO
    mode (the default) runs the resident-dq fused kernel wherever its fp32
    dq slab fits its budget — (256, 512) blocks since the calls ask for
    `VMEM_LIMIT_BYTES` (PR 41), per head group past the single-call cap —
    and the split pair for long sequences or when forced
    (DS_FLASH_BWD_MODE=split)."""
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    monkeypatch.setattr(fa, "BWD_MODE", "split")
    assert fa._fused_plan(1024, 16, 1024) == "split"
    assert fa.auto_blocks(1024) == (256, 512)
    assert fa.auto_blocks(1280) == (256, 256)
    assert fa.auto_blocks(1600) == (128, 256)
    monkeypatch.setattr(fa, "BWD_MODE", "auto")
    # auto at model context lengths: fused family
    assert fa._fused_plan(1024, 16, 1024) == "fused"
    assert fa._fused_plan(1280, 20, 1024) == "fused"
    assert fa.auto_blocks(768, num_heads=12, seq_len=1024) == (256, 512)
    assert fa.auto_blocks(1024, num_heads=16, seq_len=1024) == (256, 512)
    assert fa.auto_blocks(1280, num_heads=20, seq_len=1024) == (256, 512)
    # gpt2-xl: 25 heads x 64 -> two fused groups (13+12, widths 832/768,
    # padded 896/768)
    assert fa._fused_plan(1600, 25, 1024) == "grouped"
    assert fa.auto_blocks(1600, num_heads=25) == (256, 512)
    # 20 heads x 80 groups 10+10 and PADS to 16 heads = width 1280
    assert fa.auto_blocks(1600, num_heads=20, seq_len=1024) == (256, 512)
    assert fa.auto_blocks(1600) == (128, 256)   # no head info: split
    # long sequence: the resident dq slab outgrows its budget -> split
    assert fa._fused_plan(1024, 16, 4096) == "split"
    assert fa.auto_blocks(1024, num_heads=16, seq_len=4096) == (256, 512)
    assert fa.auto_fwd_blocks(1024) == (256, 512)
    assert fa.auto_fwd_blocks(1600) == (256, 256)


@pytest.mark.parametrize("hd,heads,seq,itemsize,blocks", [
    (768, 12, 1024, 2, (256, 512)),
    (1024, 16, 1024, 2, (256, 512)),      # the training cell
    (1280, 20, 1024, 2, (256, 512)),
    (1600, 25, 1024, 2, (256, 512)),      # gpt2-xl's two groups
    (1024, 16, 2048, 2, (256, 512)),      # the largest resident dq slab
    (1024, 16, 1024, 4, (256, 512)),      # float32 operands (the tests')
    (1024, 16, 2048, 4, (256, 256)),      # ... past 3/4 of the limit
    (1024, 16, 4096, 2, (256, 512)),      # split pair, as before
])
def test_auto_blocks_follow_width_sequence_and_itemsize(
        hd, heads, seq, itemsize, blocks, monkeypatch):
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    monkeypatch.setattr(fa, "BWD_MODE", "auto")
    assert fa.auto_blocks(hd, num_heads=heads, seq_len=seq,
                          itemsize=itemsize) == blocks


@pytest.mark.parametrize("hd,seq,blocks", [
    (1024, 1024, (256, 512)), (1280, 1024, (256, 512)),
    (1600, 1024, (256, 512)),             # K/V resident
    (1024, 2048, (256, 512)), (1600, 2048, (256, 256)),   # streaming
    (1024, None, (256, 512)), (1600, None, (256, 256))])
def test_auto_fwd_blocks_by_kernel(hd, seq, blocks):
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    assert fa.auto_fwd_blocks(hd, seq) == blocks


@pytest.mark.parametrize("b,s,h,d,itemsize", [
    (20, 1024, 16, 64, 2),                # gpt2-350m-train.seq1024
    (20, 1024, 12, 64, 2), (16, 1024, 20, 64, 2), (8, 1024, 14, 64, 2),
    (10, 2048, 16, 64, 2), (4, 1024, 16, 64, 4), (4, 2048, 16, 64, 4),
    (2, 1024, 16, 80, 2)], ids=str)
def test_table_blocks_fit_the_vmem_limit(b, s, h, d, itemsize):
    """The block sets the tables choose, reckoned from the calls' specs
    (two buffers a blocked operand or output, one a scratch), fit the
    ``vmem_limit_bytes`` every call of the file asks for, with a quarter
    of it left for the kernels' (Bq, Bk) float32 intermediates."""
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    hd = h * d
    room = fa.VMEM_LIMIT_BYTES * 3 // 4
    assert fa._compiler_params().vmem_limit_bytes == fa.VMEM_LIMIT_BYTES \
        <= 96 * 2 ** 20                   # the chip has 128 MiB
    fq, _ = fa.auto_fwd_blocks(hd, s, itemsize)
    if fa._resident_fwd_fits(hd, s, itemsize):
        assert fa._fwd_resident_vmem_bytes(fq, s, hd, h, itemsize) <= room
    else:                                 # the streaming forward's turn
        assert (s, itemsize) == (2048, 4)
    assert fa._fused_plan(hd, h, s, mode="auto") == "fused"
    bq, bk = fa.auto_blocks(hd, num_heads=h, seq_len=s, itemsize=itemsize)
    assert bk % 128 == 0 and s % bq == 0
    assert fa._bwd_resident_vmem_bytes(bq, bk, s, hd, h, itemsize) <= room


def test_head_groups_partition():
    """Grouping covers all heads contiguously, balanced to one head, and
    every group's packed width fits the single-call fused cap."""
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    for h, d in [(16, 64), (25, 64), (20, 80), (32, 128), (12, 64),
                 (40, 64), (1, 64), (18, 112)]:
        groups = fa._head_groups(h, d)
        assert groups is not None
        assert sum(n for _, n in groups) == h
        assert groups[0][0] == 0
        for (s0, n0), (s1, _) in zip(groups, groups[1:]):
            assert s1 == s0 + n0
        sizes = [n for _, n in groups]
        assert max(sizes) - min(sizes) <= 1
        # the cap must hold for the width the kernel RUNS at (after
        # 128-lane alignment padding), not the on-paper group width
        assert max(fa._padded_heads(n, d) for n in sizes) * d \
            <= fa.FUSED_BWD_MAX_WIDTH
    # a single head wider than the cap cannot be grouped
    assert fa._head_groups(1, 2048) is None


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("variant", ["resident", "dma"])
def test_fused_bwd_matches_split(causal, variant, monkeypatch):
    """Both single-pass fused backwards (one walk, 5 dots/pair) — the
    default resident-dq kernel and the explicit-DMA HBM-accumulation
    variant it replaced — are numerically identical to the split
    dq + dk/dv kernels, including ragged seq (q-padding) and both mask
    polarities."""
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    if variant == "dma":
        monkeypatch.setattr(fa, "RESIDENT_DQ_MAX_BYTES", 0)
    rng = np.random.RandomState(0)
    b, s, h, d = 2, 192, 4, 32
    hd = h * d
    mk = lambda: jnp.asarray(rng.randn(b, s, hd) * 0.3, jnp.float32)
    q, k, v, do = mk(), mk(), mk(), mk()
    bias = jnp.zeros((b, 1, 128), jnp.float32)
    scale = 1.0 / d ** 0.5
    out, lse = fa._fwd_packed(q, k, v, bias, scale, causal, 128, 128,
                              True, h)
    ref = fa._bwd_split_packed(q, k, v, bias, out, do, lse, scale, causal,
                               128, 128, True, h)
    got = fa._bwd_fused_packed(q, k, v, bias, out, do, lse, scale, causal,
                               128, 128, True, h)
    for name, a, g in zip(("dq", "dk", "dv"), ref, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(g),
                                   atol=2e-4, rtol=2e-4, err_msg=name)


def test_bwd_packed_dispatch_plan():
    """Auto mode routes narrow widths to the single fused call and wide
    ones (gpt2-xl class) fused-per-head-group; sequences whose resident
    dq slab overflows VMEM fall back to the split pair. Forced modes
    override the fit logic."""
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    assert fa._fused_plan(16 * 64, 16, 1024, mode="auto") == "fused"
    assert fa._fused_plan(25 * 64, 25, 1024, mode="auto") == "grouped"
    assert len(fa._head_groups(25, 64)) == 2
    assert fa._fused_plan(16 * 64, 16, 8192, mode="auto") == "split"
    assert fa._fused_plan(16 * 64, 16, 8192, mode="fused") == "fused"
    assert fa._fused_plan(16 * 64, 16, 1024, mode="split") == "split"
    # resident fit boundary: 8 MiB budget / fp32 -> s*hd <= 2M elements
    assert fa._resident_dq_fits(1024, 1536)
    assert fa._resident_dq_fits(1024, 2048)
    assert not fa._resident_dq_fits(1024, 2304)
    assert fa._fused_plan(16 * 64, 16, 2048, mode="auto") == "fused"
    assert fa._fused_plan(16 * 64, 16, 2304, mode="auto") == "split"


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.slow
def test_grouped_fused_bwd_matches_split(causal):
    """gpt2-xl-width backward (25 heads x 64 = 1600 > single-call cap):
    the per-head-group fused path is numerically identical to the split
    kernels, including the ragged q tail."""
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    rng = np.random.RandomState(3)
    b, s, h, d = 1, 160, 25, 64
    hd = h * d
    mk = lambda: jnp.asarray(rng.randn(b, s, hd) * 0.2, jnp.float32)
    q, k, v, do = mk(), mk(), mk(), mk()
    bias = jnp.zeros((b, 1, 256), jnp.float32)
    scale = 1.0 / d ** 0.5
    out, lse = fa._fwd_packed(q, k, v, bias, scale, causal, 128, 128,
                              True, h)
    ref = fa._bwd_split_packed(q, k, v, bias, out, do, lse, scale, causal,
                               128, 128, True, h)
    got = fa._bwd_packed(q, k, v, bias, out, do, lse, scale, causal,
                         128, 128, True, h)
    for name, a, g in zip(("dq", "dk", "dv"), ref, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(g),
                                   atol=2e-4, rtol=2e-4, err_msg=name)




def _check_packed_bwd_matches_split(b, s, h, d, causal, seed,
                                    block_q=128, block_k=128):
    """Shared harness: fwd once, then split-pair reference vs whatever
    backward _bwd_fused_packed/_bwd_packed dispatches for this shape."""
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    rng = np.random.RandomState(seed)
    hd = h * d
    mk = lambda: jnp.asarray(rng.randn(b, s, hd) * 0.2, jnp.float32)
    q, k, v, do = mk(), mk(), mk(), mk()
    pad_k = ((s + block_k - 1) // block_k) * block_k
    bias = jnp.zeros((b, 1, pad_k), jnp.float32)
    scale = 1.0 / d ** 0.5
    out, lse = fa._fwd_packed(q, k, v, bias, scale, causal, block_q,
                              block_k, True, h)
    ref = fa._bwd_split_packed(q, k, v, bias, out, do, lse, scale, causal,
                               block_q, block_k, True, h)
    got = fa._bwd_fused_packed(q, k, v, bias, out, do, lse, scale, causal,
                               block_q, block_k, True, h)
    for name, a, g in zip(("dq", "dk", "dv"), ref, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(g),
                                   atol=2e-4, rtol=2e-4, err_msg=name)


@pytest.mark.parametrize("causal", [True, False])
def test_fused_bwd_chunked_rmw_d80(causal):
    """d_head 80 exercises the resident kernel's chunked dq
    read-modify-write with a NON-ZERO chunk offset: 128/gcd(80,128) = 8
    heads per chunk, so 10 heads write chunks at lane offsets 0 and 640
    (both 128-multiples — the Mosaic constraint on output-ref stores).
    Numerics must match the split pair exactly."""
    _check_packed_bwd_matches_split(1, 160, 10, 80, causal, seed=11)


def test_a_kernel_is_traced_from_the_head_of_a_stack_chunk():
    """CPython 3.12 keeps a thread's frames in 16 KiB chunks; a call that
    crosses a chunk's end maps a chunk and unmaps it on return, and where
    that end falls inside the frames a kernel body's tracing calls
    through, the tracing pays it thousands of times (PERF.md section 6,
    PR 41: 102 s against 14 on the chip's host). `_call_kernel`'s frame is
    larger than a chunk, so what it calls starts at the head of a fresh
    one, whatever the depth it is called at."""
    import statistics
    import sys
    import time
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    assert fa._call_kernel(lambda a, b: a - b, 5, 3) == 2
    assert fa._call_kernel.__code__.co_stacksize * 8 >= 2 * 16384

    def leaf():
        pass

    def hot():
        start = time.perf_counter()
        for _ in range(20000):
            leaf()
        return time.perf_counter() - start

    def at_depth(n, fn):
        return fn() if n == 0 else at_depth(n - 1, fn)

    limit = sys.getrecursionlimit()
    sys.setrecursionlimit(max(limit, 2000))
    try:
        plain = [at_depth(n, hot) for n in range(300)]
        headed = [at_depth(n, lambda: fa._call_kernel(hot))
                  for n in range(300)]
    finally:
        sys.setrecursionlimit(limit)
    if max(plain) < 20 * statistics.median(plain):
        pytest.skip("this interpreter shows no chunk boundary to avoid")
    # (the third largest: a loaded host may stall a reading or two)
    assert sorted(headed)[-3] < max(plain) / 5
