"""Pallas flash attention (causal), forward + backward.

Reference parity: csrc/transformer/softmax_kernels.cu +
strided_batch_gemm.h + transform_kernels.cu — the reference's fused
attention pipeline (QK^T, masked softmax, ·V as batched cublas + custom
kernels). On TPU this becomes one Pallas kernel with online softmax
(FlashAttention-style): scores never touch HBM, the MXU sees (Bq, d)·(d, Bk)
and (Bq, Bk)·(Bk, d) matmuls per block pair, and k-blocks strictly above the
causal diagonal are skipped (the inner loop's trip count shrinks with the
query-block index, ~2x less MXU work for causal).

Layout: K/V for one (batch, head) live in VMEM whole (fine to ~8K sequence
at d_head<=128: 8K*128*4B*2 = 8 MB); the query axis is blocked via the grid
and the key axis by an in-kernel fori_loop over VMEM slices. Backward
follows the standard flash decomposition (dq accumulated across the k loop;
dk/dv accumulated in VMEM scratch across the sequential TPU grid).

What a kernel traces follows what the call can see (all static): the bias
operand and its add exist only where the caller gave a ``mask_bias``; the
compare against the sequence's end only where the sequence is no multiple
of the key block; the causal compare only where ``causal``; and a scale
that is a power of two goes onto the q block instead of onto the scores
(`_scale_folds`).
"""
import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30

# What every pallas_call of this file asks of the chip's VMEM. XLA gives a
# Mosaic call 16 MiB unless the call asks; a v5e core has 128 MiB. Sized
# from the block sets the tables below choose, not from the chip: the
# resident-dq backward at its widest (width 1280, sequence 1024, blocks
# (256, 512)) holds 33 MiB of blocks and scratch by `_bwd_resident_vmem_bytes`,
# and the compiler's own (Bq, Bk) float32 intermediates come on top; a test
# holds the tables to three quarters of the limit.
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _call_kernel(fn, *args):
    return fn(*args)


# CPython 3.12 keeps a thread's frames in chunks of 16 KiB, and a call
# that does not fit the current chunk maps a new one and unmaps it on
# return. Tracing a kernel body is thousands of calls some 40 frames
# deep; where a chunk's end falls inside that span, every call across it
# pays both system calls (PR 40 met this in Mosaic's lowering). On the
# chip's host the training step's 48 kernel bodies then traced in 102 s
# (the parent's in 50: it sat on such an end too) against 14 s with the
# calls below (PR 41). A frame larger than a chunk is given a chunk of
# its own, twice its size: the kernel's tracing starts there with 32 KiB
# before the next end, whatever the depth it was called at. The frame is
# made large by its declared stack size alone; the code is `fn(*args)`.
_KERNEL_FRAME_SLOTS = 4096          # 32 KiB of 8-byte slots
_call_kernel.__code__ = _call_kernel.__code__.replace(
    co_stacksize=_KERNEL_FRAME_SLOTS)


def _scale_folds(sm_scale):
    """Whether ``sm_scale`` is a power of two. Multiplying by one is
    exact in every float dtype, so the scale can go onto the (Bq, d) q
    block in the operand's own dtype instead of onto every (Bq, Bk) block
    of float32 scores, and scores, ds, dq and dk come out bit for bit
    (1/8 at d_head 64; not at d_head 80 or 128, where it stays on the
    scores)."""
    return math.frexp(sm_scale)[0] == 0.5


def _when_live(qi, ki, block_q, block_k, causal, fn):
    """Run ``fn`` for the grid cell's (q block, k block) pair unless the
    pair lies wholly above the causal diagonal."""
    if causal:
        pl.when(ki * block_k < (qi + 1) * block_q)(fn)
    else:
        fn()


def _score_mask(qi, ki, *, block_q, block_k, causal, seq_len, keys=None):
    """The (Bq, keys) mask of a block pair's scores (``keys``: the k
    block's first keys, all ``block_k`` of them by default), or None where
    every score counts: no compare is traced for a sequence that is a
    multiple of ``block_k`` (no zero-padded k tail) nor for a non-causal
    call."""
    mask = None
    shape = (block_q, keys or block_k)
    if seq_len % block_k:
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = k_pos < seq_len              # zero-padded k tail
    if causal:
        # q_pos >= k_pos, with the grid position on the scalar side
        ahead = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                 - jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        under = ahead >= ki * block_k - qi * block_q
        mask = under if mask is None else jnp.logical_and(mask, under)
    return mask


def _pad_kv(k, v, block_k):
    """Zero-pad K/V on the sequence axis to a block_k multiple; padded keys
    are masked out in-kernel via ``k_pos < seq_len``."""
    s = k.shape[1]
    pad = (-s) % block_k
    if pad:
        widths = ((0, 0), (0, pad), (0, 0))
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    return k, v


def _num_visible(qi, block_q, block_k, num_k_blocks, causal):
    """How many k blocks the q block `qi` attends to (trip count of the
    inner loop). Causal: ceil((qi+1)*block_q / block_k), clamped."""
    if not causal:
        return num_k_blocks
    visible = ((qi + 1) * block_q + block_k - 1) // block_k
    return jnp.minimum(visible, num_k_blocks)


def _head_group(num_heads, d_head):
    """Heads a 128-lane tile holds whole (2 at d_head 64, 4 at 32): the
    resident kernels take them together (`_tile_operands`). 1 where a
    head fills a tile or more, where heads do not tile 128 lanes (d_head
    80), or where the heads do not come out even."""
    g = 128 // d_head if d_head < 128 and 128 % d_head == 0 else 1
    return g if num_heads % g == 0 else 1


def _tile_operands(tile, g, d_head, scale=None):
    """A tile of ``g`` heads side by side -> (own, [the tile with every
    lane but head j's zeroed, for j in range(g)]), ``own[j]`` the (1, g*d)
    lane mask of head j. A head's matmul against the zeroed copy contracts
    over the whole tile and costs the MXU the pass d_head lanes cost, and
    every load and store is a whole tile (at d_head 64 every odd head's
    own slice would start at lane 64). ``scale``: multiplied in, in
    float32, on the way."""
    x = tile if scale is None else tile.astype(jnp.float32) * scale
    if g == 1:
        return [None], [x.astype(tile.dtype)]
    x = x.astype(jnp.float32)    # v5e's vector unit selects no bfloat16
    head_of_lane = jax.lax.broadcasted_iota(
        jnp.int32, (1, g * d_head), 1) // d_head
    own = [head_of_lane == j for j in range(g)]
    return own, [jnp.where(o, x, 0).astype(tile.dtype) for o in own]


def _fwd_compute(q, load_kv, out_dtype, *, qi, sm_scale, block_q, block_k,
                 num_k_blocks, causal, seq_len, load_bias=None, d_head=None):
    """Online-softmax forward over one q block. ``load_kv(ki)`` returns the
    ki-th (Bk, w) K/V slices — the only layout-dependent part, so the 3D
    (bh, s, d) and 4D (b, s, h, d) kernels share this body.
    ``load_bias(ki)`` (optional) returns a (1, Bk) additive score bias —
    the key-padding mask path.

    ``d_head`` below ``q``'s width w: the slice is a tile of w / d_head
    heads (`_tile_operands`), whose chains are independent in ONE loop
    body, so that the MXU works on one head's matmuls under the other's
    softmax; a head's p @ v yields all w lanes, of which its own are kept.
    Returns (out (Bq, w), lse (Bq, heads in the tile))."""
    w = q.shape[-1]
    g = w // (d_head or w)
    fold = _scale_folds(sm_scale)
    own, q_of = _tile_operands(q, g, w // g, sm_scale if fold else None)

    def lanes(per_head):
        """(Bq, 1) values a head -> (Bq, w), each in its head's lanes."""
        full = per_head[0]
        for o, x in zip(own[1:], per_head[1:]):
            full = jnp.where(o, x, full)
        return full

    def body(ki, carry):
        acc, ms, ls = carry
        k_blk, v_blk = load_kv(ki)
        mask = _score_mask(qi, ki, block_q=block_q, block_k=block_k,
                           causal=causal, seq_len=seq_len)
        new_ms, new_ls, corrs, pvs = [], [], [], []
        for q_j, m, l in zip(q_of, ms, ls):
            s_blk = jax.lax.dot_general(
                q_j, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # (Bq, Bk)
            if not fold:
                s_blk = s_blk * sm_scale
            if load_bias is not None:
                s_blk = s_blk + load_bias(ki)
            if mask is not None:
                s_blk = jnp.where(mask, s_blk, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1, keepdims=True))
            p = jnp.exp(s_blk - m_new)
            corr = jnp.exp(m - m_new)
            new_ms.append(m_new)
            new_ls.append(l * corr + jnp.sum(p, axis=-1, keepdims=True))
            corrs.append(corr)
            pvs.append(jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))      # (Bq, w)
        return acc * lanes(corrs) + lanes(pvs), tuple(new_ms), tuple(new_ls)

    carry = (jnp.zeros((block_q, w), jnp.float32),
             (jnp.full((block_q, 1), NEG_INF, jnp.float32),) * g,
             (jnp.zeros((block_q, 1), jnp.float32),) * g)
    visible = _num_visible(qi, block_q, block_k, num_k_blocks, causal)
    acc, ms, ls = jax.lax.fori_loop(0, visible, body, carry)

    ls = [jnp.where(l == 0.0, 1.0, l) for l in ls]
    lse = [m + jnp.log(l) for m, l in zip(ms, ls)]
    return ((acc / lanes(ls)).astype(out_dtype),
            lse[0] if g == 1 else jnp.concatenate(lse, axis=1))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, block_q,
                block_k, num_k_blocks, causal, seq_len):
    qi = pl.program_id(1)
    # Dots run with the INPUT dtype (bf16 on the fast path -> full-rate
    # MXU) and fp32 accumulation; the softmax itself stays fp32.
    load_kv = lambda ki: (k_ref[0, pl.ds(ki * block_k, block_k), :],
                          v_ref[0, pl.ds(ki * block_k, block_k), :])
    out, lse = _fwd_compute(q_ref[0], load_kv, o_ref.dtype, qi=qi,
                            sm_scale=sm_scale, block_q=block_q,
                            block_k=block_k, num_k_blocks=num_k_blocks,
                            causal=causal, seq_len=seq_len)
    o_ref[0] = out
    lse_ref[0] = lse                                     # (Bq, 1)


def _fwd_kernel_packed_resident(q_ref, k_ref, v_ref, *rest, sm_scale,
                                block_q, block_k, num_k_blocks, causal,
                                seq_len, num_heads, d_head, has_bias):
    """(b, s, h*d)-packed forward, whole K/V resident in VMEM: the fast
    path for ordinary sequence lengths. The k loop's online-softmax state
    lives in registers (no scratch round-trips), which measures ~3x faster
    than the streaming variant at GPT-2 shapes. Heads go a 128-lane tile
    at a time (`_head_group`). ``rest``: the bias ref where the caller
    gave a ``mask_bias`` (``has_bias``), then the outputs."""
    bias_ref = rest[0] if has_bias else None
    o_ref, lse_ref = rest[-2:]
    qi = pl.program_id(1)
    q_all = q_ref[0]                                      # (Bq, h*d)
    load_bias = None
    if has_bias:
        load_bias = lambda ki: bias_ref[0, :, pl.ds(ki * block_k, block_k)]
    outs, lses = [], []
    g = _head_group(num_heads, d_head)
    for hi in range(0, num_heads, g):
        sl = slice(hi * d_head, (hi + g) * d_head)
        load_kv = lambda ki, sl=sl: (
            k_ref[0, pl.ds(ki * block_k, block_k), sl],
            v_ref[0, pl.ds(ki * block_k, block_k), sl])
        out, lse = _fwd_compute(q_all[:, sl], load_kv, o_ref.dtype, qi=qi,
                                sm_scale=sm_scale, block_q=block_q,
                                block_k=block_k, num_k_blocks=num_k_blocks,
                                causal=causal, seq_len=seq_len,
                                load_bias=load_bias, d_head=d_head)
        outs.append(out)
        lses.append(lse)
    o_ref[0] = jnp.concatenate(outs, axis=1)
    lse_ref[0] = jnp.concatenate(lses, axis=1)            # (Bq, h)


# The whole-K/V forward runs up to this many packed elements (s * h * d)
# of bf16 operands (4 MB a K/V buffer, 18 MiB of VMEM in all by
# `_fwd_resident_vmem_bytes`); wider dtypes halve it. Measured on the chip
# (PR 41, tests/perf/flash_attention_microbench.py) against the streaming
# kernel it replaces there: width 1280 at s 1024 1.39 against 2.88 ms,
# gpt2-xl's 1600 1.00 against 1.78, width 1024 at s 2048 2.08 against
# 3.48. Beyond, the streaming kernel keeps long sequences compiling.
RESIDENT_FWD_MAX_ELEMS = 2 * 1024 * 1024


def _resident_fwd_fits(hd, s_p, itemsize):
    return s_p * hd * itemsize <= RESIDENT_FWD_MAX_ELEMS * 2


def _fwd_kernel_packed(q_ref, k_ref, v_ref, *rest, sm_scale, block_q,
                       block_k, num_k_blocks, causal, seq_len, num_heads,
                       d_head, has_bias):
    """(b, s, h*d)-packed forward: operands stay in the model's natural
    activation layout (the qkv matmul's output), so no host-side head
    transpose ever happens — the (b,s,h,d)->(bh,s,d) relayout at d_head 64
    costs more HBM time than the attention math itself. Heads are a static
    in-kernel loop over lane slices; all ref stores are full blocks.

    Grid (b, q blocks, k blocks): K/V are streamed block-by-block with the
    online-softmax state (acc/m/l per head) carried in VMEM scratch across
    the sequential innermost k dimension, so sequence length is bounded by
    HBM, not by whole-K/V VMEM residency. Causal cells above the diagonal
    are skipped (~2x less MXU work). ``rest``: the bias ref where the
    caller gave a ``mask_bias``, the outputs, the scratch."""
    bias_ref = rest[0] if has_bias else None
    o_ref, lse_ref, acc_s, m_s, l_s = rest[-5:]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    def _accumulate():
        mask = _score_mask(qi, ki, block_q=block_q, block_k=block_k,
                           causal=causal, seq_len=seq_len)
        for hi in range(num_heads):
            sl = slice(hi * d_head, (hi + 1) * d_head)
            q = q_ref[0][:, sl]                           # (Bq, d)
            k_blk = k_ref[0][:, sl]                       # (Bk, d)
            v_blk = v_ref[0][:, sl]
            s_blk = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if has_bias:
                s_blk = s_blk + bias_ref[0]               # (1, Bk) bias
            if mask is not None:
                s_blk = jnp.where(mask, s_blk, NEG_INF)
            m_old = m_s[:, hi:hi + 1]                     # (Bq, 1)
            m_new = jnp.maximum(m_old,
                                jnp.max(s_blk, axis=-1, keepdims=True))
            p = jnp.exp(s_blk - m_new)
            corr = jnp.exp(m_old - m_new)
            l_s[:, hi:hi + 1] = (l_s[:, hi:hi + 1] * corr
                                 + jnp.sum(p, axis=-1, keepdims=True))
            m_s[:, hi:hi + 1] = m_new
            acc_s[:, sl] = acc_s[:, sl] * corr + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _when_live(qi, ki, block_q, block_k, causal, _accumulate)

    @pl.when(ki == num_k_blocks - 1)
    def _flush():
        l = l_s[:]                                        # (Bq, h)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        scale = 1.0 / l_safe                              # (Bq, h)
        # per-head rescale: broadcast (Bq, h) -> lane slices of (Bq, h*d)
        outs = [acc_s[:, hi * d_head:(hi + 1) * d_head]
                * scale[:, hi:hi + 1] for hi in range(num_heads)]
        o_ref[0] = jnp.concatenate(outs, axis=1).astype(o_ref.dtype)
        lse_ref[0] = m_s[:] + jnp.log(l_safe)             # (Bq, h)


def _bwd_compute(q, o, do, lse, load_kv, accum_dkv, *, qi, sm_scale,
                 block_q, block_k, num_k_blocks, causal, seq_len):
    """Backward over one q block; ``accum_dkv(ki, dk_upd, dv_upd)`` adds
    the ki-th k-block's dk/dv partials into VMEM scratch. Returns dq.
    Layout-independent (see _fwd_compute)."""
    d = q.shape[-1]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    # Rows past the true sequence end (padded tail of the last q block) carry
    # undefined q/do/lse; unlike the forward (whose padded outputs are simply
    # discarded), dk/dv SUM over q rows — mask them out.
    row_valid = q_pos[:, :1] < seq_len
    # q/o/do on padded rows are undefined (may be NaN); they enter dk/dv
    # through row reductions (ds.T@q, p.T@do, delta) where 0 * NaN = NaN,
    # so every padded row is zeroed at the source. Dots run with the input
    # dtype (full-rate MXU for bf16) and fp32 accumulation.
    q = jnp.where(row_valid, q, jnp.zeros_like(q))
    do = jnp.where(row_valid, do, jnp.zeros_like(do))
    delta = jnp.where(row_valid,
                      jnp.sum(do.astype(jnp.float32) * o, axis=-1,
                              keepdims=True), 0.0)

    def body(ki, dq):
        k_blk, v_blk = load_kv(ki)
        s_blk = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (Bq, Bk)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s_blk.shape, 1)
        mask = k_pos < seq_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s_blk = jnp.where(mask, s_blk, NEG_INF)
        p = jnp.exp(s_blk - lse)                          # (Bq, Bk)
        p = jnp.where(jnp.logical_and(row_valid, mask), p, 0.0)
        p_cast = p.astype(do.dtype)
        dv_upd = jax.lax.dot_general(
            p_cast, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale                  # (Bq, Bk)
        ds_cast = ds.astype(q.dtype)
        dk_upd = jax.lax.dot_general(
            ds_cast, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        accum_dkv(ki, dk_upd, dv_upd)
        return dq + jax.lax.dot_general(
            ds_cast, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    visible = _num_visible(qi, block_q, block_k, num_k_blocks, causal)
    return jax.lax.fori_loop(0, visible, body, jnp.zeros((block_q, d),
                                                         jnp.float32))


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, block_q,
                block_k, num_k_blocks, causal, num_q_blocks, seq_len):
    # seq_len masks BOTH the padded q tail (rows summed into dk/dv) and the
    # padded k tail (columns of the score block).
    qi = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    load_kv = lambda ki: (k_ref[0, pl.ds(ki * block_k, block_k), :],
                          v_ref[0, pl.ds(ki * block_k, block_k), :])

    def accum_dkv(ki, dk_upd, dv_upd):
        rows = pl.ds(ki * block_k, block_k)
        dk_acc[rows, :] = dk_acc[rows, :] + dk_upd
        dv_acc[rows, :] = dv_acc[rows, :] + dv_upd

    dq = _bwd_compute(q_ref[0], o_ref[0].astype(jnp.float32), do_ref[0],
                      lse_ref[0], load_kv, accum_dkv, qi=qi,
                      sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                      num_k_blocks=num_k_blocks, causal=causal,
                      seq_len=seq_len)
    dq_ref[0] = dq.astype(dq_ref.dtype)

    @pl.when(qi == num_q_blocks - 1)
    def _flush():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_head_terms(q, k_blk, v_blk, do, lse, delta, mask, sm_scale, bias,
                    folded=False):
    """Per-head backward intermediates shared by the packed dq and dk/dv
    kernels (one definition so a numerics change cannot diverge them):
    p = softmax probabilities, ds = dL/dscores (input dtype). ``mask``:
    the (Bq, Bk) mask of the pair's scores, else None (`_score_mask`);
    ``bias``: the (1, Bk) additive score bias (key-padding mask), else
    None. ``folded``: ``q`` arrives times ``sm_scale`` (a power of two,
    `_scale_folds`) and ds leaves WITHOUT it: dk = ds^T (sm_scale q) is
    then what it was bit for bit, and the caller scales its (Bq, d) dq
    update instead of two (Bq, Bk) blocks."""
    s_blk = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (Bq, Bk)
    if not folded:
        s_blk = s_blk * sm_scale
    if bias is not None:
        s_blk = s_blk + bias
    p = jnp.exp(s_blk - lse)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(
        do, v_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    if not folded:
        ds = ds * sm_scale
    return p, ds.astype(q.dtype)


def _bwd_dq_kernel_packed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          *rest, sm_scale, block_q, block_k, num_k_blocks,
                          causal, seq_len, num_heads, d_head, has_bias):
    """Packed-layout dq: grid (b, q blocks, k blocks), accumulating into a
    (Bq, h*d) fp32 scratch across the (sequential, innermost) k dimension.
    The flash backward is split MaxText-style into a dq kernel and a dk/dv
    kernel, both with every operand blocked, so the sequence length is
    bounded by HBM and not by VMEM. ``rest``: the bias ref where the
    caller gave a ``mask_bias``, the output, the scratch."""
    bias_ref = rest[0] if has_bias else None
    dq_ref, dq_acc = rest[-2:]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _accumulate():
        mask = _score_mask(qi, ki, block_q=block_q, block_k=block_k,
                           causal=causal, seq_len=seq_len)
        bias = bias_ref[0] if has_bias else None
        for hi in range(num_heads):
            sl = slice(hi * d_head, (hi + 1) * d_head)
            k_blk = k_ref[0][:, sl]                       # (Bk, d)
            _, ds = _bwd_head_terms(
                q_ref[0][:, sl], k_blk, v_ref[0][:, sl],
                do_ref[0][:, sl], lse_ref[0][:, hi:hi + 1],
                delta_ref[0][:, hi:hi + 1], mask, sm_scale, bias)
            dq_acc[:, sl] = dq_acc[:, sl] + jax.lax.dot_general(
                ds, k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _when_live(qi, ki, block_q, block_k, causal, _accumulate)

    @pl.when(ki == num_k_blocks - 1)
    def _flush():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel_packed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           *rest, sm_scale, block_q, block_k, num_q_blocks,
                           num_k_blocks, causal, seq_len, num_heads, d_head,
                           has_bias):
    """Packed-layout dk/dv: grid (b, k blocks, q blocks) — each cell sees
    one (Bq, h*d) q/do slab and one (Bk, h*d) K/V slab, accumulating into
    (Bk, h*d) fp32 scratch across the (sequential, innermost) q dimension.
    Causal cells above the diagonal are skipped (pl.when), matching the
    forward's ~2x saving. The q rows past the sequence's end are the
    caller's zero padding: zero q and do put exactly zero into dk and dv,
    so only the padded KEYS mask (`_score_mask`). ``rest``: the bias ref
    where the caller gave a ``mask_bias``, the outputs, the scratch."""
    bias_ref = rest[0] if has_bias else None
    dk_ref, dv_ref, dk_acc, dv_acc = rest[-4:]
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _accumulate():
        mask = _score_mask(qi, ki, block_q=block_q, block_k=block_k,
                           causal=causal, seq_len=seq_len)
        bias = bias_ref[0] if has_bias else None
        for hi in range(num_heads):
            sl = slice(hi * d_head, (hi + 1) * d_head)
            q = q_ref[0][:, sl]                           # (Bq, d)
            do = do_ref[0][:, sl]
            p, ds = _bwd_head_terms(
                q, k_ref[0][:, sl], v_ref[0][:, sl], do,
                lse_ref[0][:, hi:hi + 1], delta_ref[0][:, hi:hi + 1],
                mask, sm_scale, bias)
            dv_acc[:, sl] = dv_acc[:, sl] + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[:, sl] = dk_acc[:, sl] + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _when_live(qi, ki, block_q, block_k, causal, _accumulate)

    @pl.when(qi == num_q_blocks - 1)
    def _flush():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _attn_cost(*, mults, n, s_q, s_k, d, heads, causal, operands,
               out_bytes):
    """``pl.CostEstimate`` for one attention pallas_call so MFU pricing
    sees through the custom call (a zero-flop estimate under-prices the
    step and corrupts the scoreboard gate — DSL011).

    ``mults``: matmuls per (q, k) score element — 2 fwd (QK^T + PV), 5
    one-pass fused bwd, 3 dq-only, 4 dk/dv-only. Causal kernels skip the
    dead upper-triangle blocks, so priced work is halved. ``operands``:
    kernel inputs, charged one HBM read each (streaming re-reads are a
    pipeline detail XLA's own cost model also ignores)."""
    pairs = n * s_q * s_k * heads
    frac = 0.5 if causal else 1.0
    read = sum(a.size * a.dtype.itemsize for a in operands)
    return pl.CostEstimate(
        flops=int(2 * mults * pairs * d * frac),
        transcendentals=int(pairs * frac),
        bytes_accessed=int(read + out_bytes))


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    k, v = _pad_kv(k, v, block_k)
    s_p = k.shape[1]
    num_k_blocks = s_p // block_k
    grid = (bh, pl.cdiv(s, block_q))
    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    kv_spec = pl.BlockSpec((1, s_p, d), lambda b, i: (b, 0, 0))
    out, lse = _call_kernel(pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, num_k_blocks=num_k_blocks,
                          causal=causal, seq_len=s),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=(q_spec,
                   pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))),
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)),
        interpret=interpret,
        compiler_params=_compiler_params(),
        cost_estimate=_attn_cost(
            mults=2, n=bh, s_q=s, s_k=s, d=d, heads=1, causal=causal,
            operands=(q, k, v),
            out_bytes=q.size * q.dtype.itemsize + bh * s * 4),
    ), q, k, v)
    return out, lse


def _bwd(q, k, v, o, do, lse, sm_scale, causal, block_q, block_k, interpret):
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    k, v = _pad_kv(k, v, block_k)
    s_p = k.shape[1]
    num_k_blocks = s_p // block_k
    num_q_blocks = pl.cdiv(s, block_q)
    grid = (bh, num_q_blocks)
    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    kv_spec = pl.BlockSpec((1, s_p, d), lambda b, i: (b, 0, 0))
    lse_spec = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))
    dq, dk, dv = _call_kernel(pl.pallas_call(
        functools.partial(_bwd_kernel, sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, num_k_blocks=num_k_blocks,
                          causal=causal, num_q_blocks=num_q_blocks,
                          seq_len=s),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, lse_spec],
        out_specs=(q_spec, kv_spec, kv_spec),
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, s_p, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, s_p, d), q.dtype)),
        scratch_shapes=[pltpu.VMEM((s_p, d), jnp.float32),
                        pltpu.VMEM((s_p, d), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(),
        cost_estimate=_attn_cost(
            mults=5, n=bh, s_q=s, s_k=s, d=d, heads=1, causal=causal,
            operands=(q, k, v, o, do, lse),
            out_bytes=3 * q.size * q.dtype.itemsize),
    ), q, k, v, o, do, lse)
    return dq, dk[:, :s], dv[:, :s]


def _pad_bias(bias, b, s, block_k):
    """(b, s) / (b, 1, s) additive bias -> (b, 1, s_p) fp32; None (the
    caller has no mask) stays None. The k-tail padding value (0) is
    harmless: padded keys are masked by seq_len in-kernel."""
    if bias is None:
        return None
    pad = (-s) % block_k
    if bias.ndim == 2:
        bias = bias[:, None, :]
    bias = bias.astype(jnp.float32)
    if pad:
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, pad)))
    return bias


def _fwd_packed(q, k, v, bias, sm_scale, causal, block_q, block_k,
                interpret, num_heads):
    """q/k/v: (b, s, h*d) packed; returns (out (b, s, h*d), lse (b, s, h)).
    ``bias``: (b, 1, s_p) fp32 additive scores (key-padding mask), or None
    where the caller has none: the kernels then take no bias operand and
    add nothing to the scores."""
    b, s, hd = q.shape
    d = hd // num_heads
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    k, v = _pad_kv(k, v, block_k)
    s_p = k.shape[1]
    num_k_blocks = s_p // block_k
    has_bias = bias is not None
    operands = (q, k, v) + ((bias,) if has_bias else ())
    statics = dict(sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                   num_k_blocks=num_k_blocks, causal=causal, seq_len=s,
                   num_heads=num_heads, d_head=d, has_bias=has_bias)
    out_shape = (jax.ShapeDtypeStruct((b, s, hd), q.dtype),
                 jax.ShapeDtypeStruct((b, s, num_heads), jnp.float32))
    cost = _attn_cost(
        mults=2, n=b, s_q=s, s_k=s, d=d, heads=num_heads, causal=causal,
        operands=operands,
        out_bytes=q.size * q.dtype.itemsize + b * s * num_heads * 4)

    if _resident_fwd_fits(hd, s_p, q.dtype.itemsize):
        # fast path: K/V whole per (batch, q-block) cell, softmax state in
        # registers across an in-kernel fori over k blocks
        q_spec = pl.BlockSpec((1, block_q, hd), lambda bi, qi: (bi, qi, 0))
        kv_spec = pl.BlockSpec((1, s_p, hd), lambda bi, qi: (bi, 0, 0))
        bias_spec = pl.BlockSpec((1, 1, s_p), lambda bi, qi: (bi, 0, 0))
        return _call_kernel(pl.pallas_call(
            functools.partial(_fwd_kernel_packed_resident, **statics),
            grid=(b, pl.cdiv(s, block_q)),
            in_specs=[q_spec, kv_spec, kv_spec] + [bias_spec] * has_bias,
            out_specs=(q_spec,
                       pl.BlockSpec((1, block_q, num_heads),
                                    lambda bi, qi: (bi, qi, 0))),
            out_shape=out_shape,
            interpret=interpret,
            compiler_params=_compiler_params(),
            cost_estimate=cost,
        ), *operands)

    # every operand blocked (grid b x q x k): sequence length is bounded
    # by HBM only
    q_spec = pl.BlockSpec((1, block_q, hd), lambda bi, qi, ki: (bi, qi, 0))
    kv_spec = pl.BlockSpec((1, block_k, hd), lambda bi, qi, ki: (bi, ki, 0))
    bias_spec = pl.BlockSpec((1, 1, block_k), lambda bi, qi, ki: (bi, 0, ki))
    return _call_kernel(pl.pallas_call(
        functools.partial(_fwd_kernel_packed, **statics),
        grid=(b, pl.cdiv(s, block_q), num_k_blocks),
        in_specs=[q_spec, kv_spec, kv_spec] + [bias_spec] * has_bias,
        out_specs=(q_spec,
                   pl.BlockSpec((1, block_q, num_heads),
                                lambda bi, qi, ki: (bi, qi, 0))),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32),
                        pltpu.VMEM((block_q, num_heads), jnp.float32),
                        pltpu.VMEM((block_q, num_heads), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(),
        cost_estimate=cost,
    ), *operands)


def _bwd_fused_kernel_packed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                             *rest, sm_scale, block_q, block_k,
                             num_q_blocks, num_k_blocks, causal, seq_len,
                             num_heads, d_head, has_bias):
    """Single-pass packed backward: grid (b, k blocks, q blocks). One walk
    of the (q, k) block pairs computes ALL of dq/dk/dv — 5 dots per pair
    vs the split kernels' 7 (each split pass re-derives s = qk^T and
    dp = do v^T). dk/dv accumulate in fp32 scratch across the inner q
    dimension exactly like the split dk/dv kernel; dq — whose accumulation
    runs across the OUTER k dimension — lives in an fp32 HBM output and is
    read-modified-written per step by explicit DMAs. The in-step
    ``wait()`` on the write-back makes the cross-step accumulation
    well-defined on the sequential TPU grid (the BlockSpec pipeline offers
    no such guarantee for revisited blocks, which is why round 2 split the
    kernels); the blocking transfers are ~1 MB against ~ms of MXU work
    per step. ``rest``: the bias ref where the caller gave a
    ``mask_bias``, the outputs, the scratch."""
    bias_ref = rest[0] if has_bias else None
    dq_hbm, dk_ref, dv_ref, dk_acc, dv_acc, dq_vmem, sem_rd, sem_wr = \
        rest[-8:]
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    dq_slice = dq_hbm.at[bi, pl.ds(qi * block_q, block_q)]

    def _compute():
        # causality keeps ki == 0 live for every row, so the first visit
        # of each dq block is always at ki == 0: zero-init there, read the
        # running sum back otherwise
        @pl.when(ki == 0)
        def _zero():
            dq_vmem[:] = jnp.zeros_like(dq_vmem)

        @pl.when(ki > 0)
        def _read():
            cp = pltpu.make_async_copy(dq_slice, dq_vmem, sem_rd)
            cp.start()
            cp.wait()

        mask = _score_mask(qi, ki, block_q=block_q, block_k=block_k,
                           causal=causal, seq_len=seq_len)
        bias = bias_ref[0] if has_bias else None
        for hi in range(num_heads):
            sl = slice(hi * d_head, (hi + 1) * d_head)
            q = q_ref[0][:, sl]
            do = do_ref[0][:, sl]
            k_blk = k_ref[0][:, sl]
            p, ds = _bwd_head_terms(
                q, k_blk, v_ref[0][:, sl], do,
                lse_ref[0][:, hi:hi + 1], delta_ref[0][:, hi:hi + 1],
                mask, sm_scale, bias)
            dq_vmem[:, sl] = dq_vmem[:, sl] + jax.lax.dot_general(
                ds, k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dv_acc[:, sl] = dv_acc[:, sl] + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[:, sl] = dk_acc[:, sl] + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        cp = pltpu.make_async_copy(dq_vmem, dq_slice, sem_wr)
        cp.start()
        cp.wait()

    _when_live(qi, ki, block_q, block_k, causal, _compute)

    @pl.when(qi == num_q_blocks - 1)
    def _flush():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel_packed_resident_dq(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest, sm_scale,
        block_q, block_k, num_q_blocks, num_k_blocks, causal, seq_len,
        num_heads, d_head, has_bias):
    """Single-pass packed backward with dq RESIDENT in VMEM. Same grid
    (b, k blocks, q blocks) and 5-dots-per-pair math as the DMA variant
    above, but dq accumulates over the k walk in a whole-(s, h*d) fp32
    scratch and leaves once, at the batch row's last step, through an
    output block whose index map ignores (ki, qi). The cross-k-walk dq
    accumulation therefore costs NO DMAs — the DMA variant's per-step
    blocking read-modify-write waits (~1 MB each way against only ~µs of
    MXU work per step) were exactly why it measured 0.7-0.9x of the split
    pair. Feasible when three such slabs (the scratch and the output's two
    buffers) fit VMEM next to the block operands (RESIDENT_DQ_MAX_BYTES).
    (dq leaves as fp32 and XLA casts it: a bf16 output measured 0.2 ms a
    layer slower at the training cell's shape, XLA then copies it into
    the qkv cotangent in a pass of its own.)

    Heads go a 128-lane tile at a time (`_tile_operands`): every
    accumulator update is a whole tile's read-modify-write. Where the k
    block is a multiple of the q block, a pair that the diagonal crosses
    works on the keys its queries see and no others: one body a count of
    live sub-blocks, chosen by the grid position (at (256, 512) a third of
    the live pairs are half dead). ``rest``: the bias ref where the
    caller gave a ``mask_bias``, the outputs, the scratch."""
    bias_ref = rest[0] if has_bias else None
    dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, dq_acc = rest[-6:]
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(jnp.logical_and(ki == 0, qi == 0))
    def _init_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _init_kv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    rows = pl.ds(qi * block_q, block_q)
    g = _head_group(num_heads, d_head)
    fold = _scale_folds(sm_scale)
    tn = (((0,), (0,)), ((), ()))

    def _compute(keys=block_k):
        # ``keys``: the block's first keys, those that a query here sees
        mask = _score_mask(qi, ki, block_q=block_q, block_k=block_k,
                           causal=causal, seq_len=seq_len, keys=keys)
        bias = bias_ref[0][:, :keys] if has_bias else None
        for h0 in range(0, num_heads, g):
            sl = slice(h0 * d_head, (h0 + g) * d_head)
            k_t = k_ref[0][:keys, sl]
            v_t = v_ref[0][:keys, sl]
            own, q_of = _tile_operands(q_ref[0][:, sl], g, d_head,
                                       sm_scale if fold else None)
            _, do_of = _tile_operands(do_ref[0][:, sl], g, d_head)
            dq_u = dk_u = dv_u = None
            for j, (q_j, do_j) in enumerate(zip(q_of, do_of)):
                hi = h0 + j
                p, ds = _bwd_head_terms(
                    q_j, k_t, v_t, do_j, lse_ref[0][:, hi:hi + 1],
                    delta_ref[0][:, hi:hi + 1], mask, sm_scale, bias,
                    folded=fold)
                # ds @ k fills the whole tile: head j's lanes are kept.
                # p^T @ do_j and ds^T @ q_j are zero outside them: summed.
                dq_j = jax.lax.dot_general(
                    ds, k_t, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dv_j = jax.lax.dot_general(
                    p.astype(do_j.dtype), do_j, tn,
                    preferred_element_type=jnp.float32)
                dk_j = jax.lax.dot_general(
                    ds, q_j, tn, preferred_element_type=jnp.float32)
                if j == 0:
                    dq_u, dk_u, dv_u = dq_j, dk_j, dv_j
                else:
                    dq_u = jnp.where(own[j], dq_j, dq_u)
                    dk_u = dk_u + dk_j
                    dv_u = dv_u + dv_j
            dv_acc[:keys, sl] = dv_acc[:keys, sl] + dv_u
            dk_acc[:keys, sl] = dk_acc[:keys, sl] + dk_u
            if fold:
                dq_u = dq_u * sm_scale
            dq_acc[rows, sl] = dq_acc[rows, sl] + dq_u

    n_sub = block_k // block_q if causal and block_k % block_q == 0 else 1
    if n_sub == 1:
        _when_live(qi, ki, block_q, block_k, causal, _compute)
    else:
        # the diagonal leaves this q block the first m * block_q keys of
        # this k block (m < 1: none, the pair is dead)
        m = qi - ki * n_sub + 1
        for j in range(1, n_sub):
            pl.when(m == j)(functools.partial(_compute, j * block_q))
        pl.when(m >= n_sub)(_compute)

    @pl.when(qi == num_q_blocks - 1)
    def _flush():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(ki == num_k_blocks - 1,
                             qi == num_q_blocks - 1))
    def _flush_dq():
        dq_ref[0] = dq_acc[:]


def _q_side_padded(q, do, lse, delta, block_q):
    """The q-side arrays zero-padded to a block_q multiple, for uniform
    in-kernel slicing. Zero q and do rows put exactly zero into dq, dk and
    dv, so no kernel masks them."""
    pad_q = (-q.shape[1]) % block_q
    if not pad_q:
        return q, do, lse, delta
    pad3 = lambda t: jnp.pad(t, ((0, 0), (0, pad_q), (0, 0)))
    return pad3(q), pad3(do), pad3(lse), pad3(delta)


def _bwd_fused_packed(q, k, v, bias, o, do, lse, sm_scale, causal, block_q,
                      block_k, interpret, num_heads):
    """Driver for the single-pass fused backward. Returns (dq, dk, dv)
    numerically identical to _bwd_packed (same _bwd_head_terms math).
    Picks the resident-dq kernel when the whole fp32 dq slab for one batch
    row fits VMEM (the common case at model context lengths), the DMA
    read-modify-write variant beyond."""
    b, s, hd = q.shape
    d = hd // num_heads
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    k, v = _pad_kv(k, v, block_k)
    s_kp = k.shape[1]
    num_k_blocks = s_kp // block_k

    delta = (do.astype(jnp.float32).reshape(b, s, num_heads, d)
             * o.astype(jnp.float32).reshape(b, s, num_heads, d)).sum(-1)
    q_p, do_p, lse_p, delta_p = _q_side_padded(q, do, lse, delta, block_q)
    s_qp = q_p.shape[1]
    nqb = s_qp // block_q

    # A dead cell (q block wholly above the k block's diagonal) names the
    # k block's first live q block: the pipeline fetches nothing for a
    # block it already holds (1.70 -> 1.62 ms a layer at the training
    # cell's shape, where 2 of 8 cells a batch row are dead).
    if causal:
        q_row = lambda ki, qi: jnp.maximum(qi, (ki * block_k) // block_q)
    else:
        q_row = lambda ki, qi: qi
    q_blk = pl.BlockSpec((1, block_q, hd),
                         lambda bi, ki, qi: (bi, q_row(ki, qi), 0))
    kv_blk = pl.BlockSpec((1, block_k, hd), lambda bi, ki, qi: (bi, ki, 0))
    lse_blk = pl.BlockSpec((1, block_q, num_heads),
                           lambda bi, ki, qi: (bi, q_row(ki, qi), 0))
    bias_blk = pl.BlockSpec((1, 1, block_k), lambda bi, ki, qi: (bi, 0, ki))
    has_bias = bias is not None
    operands = (q_p, k, v, do_p, lse_p, delta_p) + (bias,) * has_bias
    in_specs = [q_blk, kv_blk, kv_blk, q_blk, lse_blk, lse_blk] \
        + [bias_blk] * has_bias
    statics = dict(sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                   num_q_blocks=nqb, num_k_blocks=num_k_blocks,
                   causal=causal, seq_len=s, num_heads=num_heads, d_head=d,
                   has_bias=has_bias)
    out_shape = (jax.ShapeDtypeStruct((b, s_qp, hd), jnp.float32),
                 jax.ShapeDtypeStruct((b, s_kp, hd), q.dtype),
                 jax.ShapeDtypeStruct((b, s_kp, hd), q.dtype))
    acc = pltpu.VMEM((block_k, hd), jnp.float32)
    cost = _attn_cost(
        mults=5, n=b, s_q=s, s_k=s, d=d, heads=num_heads, causal=causal,
        operands=operands,
        out_bytes=b * s_qp * hd * 4 + 2 * k.size * k.dtype.itemsize)
    if _resident_dq_fits(hd, s_qp):
        kernel = _bwd_fused_kernel_packed_resident_dq
        dq_spec = pl.BlockSpec((1, s_qp, hd), lambda bi, ki, qi: (bi, 0, 0))
        dq_scratch = [pltpu.VMEM((s_qp, hd), jnp.float32)]
    else:
        kernel = _bwd_fused_kernel_packed
        dq_spec = pl.BlockSpec(memory_space=pl.ANY)
        dq_scratch = [pltpu.VMEM((block_q, hd), jnp.float32),
                      pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA]
    dq_f32, dk, dv = _call_kernel(pl.pallas_call(
        functools.partial(kernel, **statics),
        grid=(b, num_k_blocks, nqb),
        in_specs=in_specs,
        out_specs=(dq_spec, kv_blk, kv_blk),
        out_shape=out_shape,
        scratch_shapes=[acc, acc] + dq_scratch,
        interpret=interpret,
        compiler_params=_compiler_params(),
        cost_estimate=cost,
    ), *operands)
    return dq_f32[:, :s].astype(q.dtype), dk[:, :s], dv[:, :s]


def _bwd_packed(q, k, v, bias, o, do, lse, sm_scale, causal, block_q,
                block_k, interpret, num_heads):
    """Packed backward dispatcher (policy in _fused_plan): the single-pass
    fused kernel where one call fits (hd <= 1280 — one walk of the block
    pairs, 5 dots each, dq resident in VMEM); per-HEAD-GROUP fused calls
    for wider models (attention is independent per head, so the packed
    width slices cleanly); the split dq + dk/dv pair for long sequences
    (resident dq slab overflows VMEM) or when forced. ``bias`` as in
    _fwd_packed."""
    hd = q.shape[-1]
    plan = _fused_plan(hd, num_heads, q.shape[1])
    if plan == "fused":
        return _bwd_fused_packed(q, k, v, bias, o, do, lse, sm_scale,
                                 causal, block_q, block_k, interpret,
                                 num_heads)
    if plan == "grouped":
        groups = _head_groups(num_heads, hd // num_heads)
        return _bwd_fused_grouped(q, k, v, bias, o, do, lse, sm_scale,
                                  causal, block_q, block_k, interpret,
                                  num_heads, groups)
    return _bwd_split_packed(q, k, v, bias, o, do, lse, sm_scale, causal,
                             block_q, block_k, interpret, num_heads)


def _bwd_fused_grouped(q, k, v, bias, o, do, lse, sm_scale, causal,
                       block_q, block_k, interpret, num_heads, groups):
    """Fused backward for widths past the single-call cap: run the fused
    kernel once per contiguous head group (independent math per head —
    softmax, lse and delta never mix heads), then concatenate dq/dk/dv on
    the packed minor dim. Each group is a standalone (b, s, group_width)
    array, so the kernels see whole minor dims (no sub-lane blocking) and
    keep the fat blocks of the narrow-width path. ``bias`` is per-KEY,
    shared by every head, so it passes through unsliced."""
    d = q.shape[-1] // num_heads
    dqs, dks, dvs = [], [], []
    for start, n in groups:
        # The fused kernel's dq HBM read-modify-write DMA needs the minor
        # dim 128-lane aligned; pad the group with zero FAKE heads up to
        # alignment. Zero q/k/v/do make every fake-head term exactly zero
        # (dv = p^T·0, ds = p·(0−0), dq/dk = 0·k / 0·q), so numerics are
        # untouched — the cost is the fake heads' dots on zeros (~4% for
        # gpt2-xl's 13-head group).
        n_p = _padded_heads(n, d)
        pad_w = (n_p - n) * d
        cs = slice(start * d, (start + n) * d)
        hs = slice(start, start + n)
        padw = lambda t: jnp.pad(t[:, :, cs], ((0, 0), (0, 0), (0, pad_w)))
        padh = lambda t: jnp.pad(t[:, :, hs],
                                 ((0, 0), (0, 0), (0, n_p - n)))
        dq_g, dk_g, dv_g = _bwd_fused_packed(
            padw(q), padw(k), padw(v), bias, padw(o), padw(do),
            padh(lse), sm_scale, causal, block_q, block_k, interpret, n_p)
        gw = n * d
        dqs.append(dq_g[:, :, :gw])
        dks.append(dk_g[:, :, :gw])
        dvs.append(dv_g[:, :, :gw])
    cat = lambda ts: jnp.concatenate(ts, axis=-1)
    return cat(dqs), cat(dks), cat(dvs)


def _bwd_split_packed(q, k, v, bias, o, do, lse, sm_scale, causal, block_q,
                      block_k, interpret, num_heads):
    """Two pallas calls (dq; then dk/dv over k-blocks), every operand
    blocked — the path of sequences whose resident fp32 dq slab outgrows
    `RESIDENT_DQ_MAX_BYTES`."""
    b, s, hd = q.shape
    d = hd // num_heads
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    k, v = _pad_kv(k, v, block_k)
    s_kp = k.shape[1]
    num_k_blocks = s_kp // block_k

    # delta_i = sum_d do*o per head: (b, s, h) fp32 (XLA fuses this)
    delta = (do.astype(jnp.float32).reshape(b, s, num_heads, d)
             * o.astype(jnp.float32).reshape(b, s, num_heads, d)).sum(-1)
    q_p, do_p, lse_p, delta_p = _q_side_padded(q, do, lse, delta, block_q)
    s_qp = q_p.shape[1]
    nqb = s_qp // block_q
    has_bias = bias is not None
    operands = (q_p, k, v, do_p, lse_p, delta_p) + (bias,) * has_bias
    statics = dict(sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                   num_k_blocks=num_k_blocks, causal=causal, seq_len=s,
                   num_heads=num_heads, d_head=d, has_bias=has_bias)

    dq_q_spec = pl.BlockSpec((1, block_q, hd), lambda bi, qi, ki: (bi, qi, 0))
    dq_kv_spec = pl.BlockSpec((1, block_k, hd), lambda bi, qi, ki: (bi, ki, 0))
    dq_lse_spec = pl.BlockSpec((1, block_q, num_heads),
                               lambda bi, qi, ki: (bi, qi, 0))
    dq_bias_spec = pl.BlockSpec((1, 1, block_k),
                                lambda bi, qi, ki: (bi, 0, ki))
    dq = _call_kernel(pl.pallas_call(
        functools.partial(_bwd_dq_kernel_packed, **statics),
        grid=(b, nqb, num_k_blocks),
        in_specs=[dq_q_spec, dq_kv_spec, dq_kv_spec, dq_q_spec,
                  dq_lse_spec, dq_lse_spec] + [dq_bias_spec] * has_bias,
        out_specs=dq_q_spec,
        out_shape=jax.ShapeDtypeStruct((b, s_qp, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(),
        cost_estimate=_attn_cost(
            mults=3, n=b, s_q=s, s_k=s, d=d, heads=num_heads,
            causal=causal, operands=operands,
            out_bytes=b * s_qp * hd * q.dtype.itemsize),
    ), *operands)
    dq = dq[:, :s]

    q_blk = pl.BlockSpec((1, block_q, hd), lambda bi, ki, qi: (bi, qi, 0))
    kv_blk = pl.BlockSpec((1, block_k, hd), lambda bi, ki, qi: (bi, ki, 0))
    lse_blk = pl.BlockSpec((1, block_q, num_heads),
                           lambda bi, ki, qi: (bi, qi, 0))
    bias_blk = pl.BlockSpec((1, 1, block_k), lambda bi, ki, qi: (bi, 0, ki))
    dk, dv = _call_kernel(pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_packed, num_q_blocks=nqb,
                          **statics),
        grid=(b, num_k_blocks, nqb),
        in_specs=[q_blk, kv_blk, kv_blk, q_blk, lse_blk, lse_blk]
        + [bias_blk] * has_bias,
        out_specs=(kv_blk, kv_blk),
        out_shape=(jax.ShapeDtypeStruct((b, s_kp, hd), q.dtype),
                   jax.ShapeDtypeStruct((b, s_kp, hd), q.dtype)),
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(),
        cost_estimate=_attn_cost(
            mults=4, n=b, s_q=s, s_k=s, d=d, heads=num_heads,
            causal=causal, operands=operands,
            out_bytes=2 * k.size * k.dtype.itemsize),
    ), *operands)
    return dq, dk[:, :s], dv[:, :s]


# Packed-kernel block defaults: q 256, k 512 (fewer, larger dots amortize
# the MXU fill/drain latency that dominates at d_head 64). Swept again at
# the training cell's shape (20, 1024, 16 x 64, bf16) with
# `VMEM_LIMIT_BYTES` in place (PR 41): forward 1.39 ms a layer at
# (256, 512), against 1.96 at (256, 256), 1.95 at (512, 256), 2.02 at
# (512, 512), 1.81 at (256, 1024), 1.68 at (128, 512): a larger block is
# no longer refused, it is slower (its float32 scores pass through VMEM).
DEFAULT_BLOCK_PACKED = 256
DEFAULT_BLOCK_PACKED_K = 512


# The single-pass FUSED backward (5 dots/pair vs the split kernels' 7)
# carries a larger VMEM working set (k/v + dk/dv scratch + the resident
# dq slab); a single kernel call is capped at hd = 1280 (the compile limit
# measured inside XLA's default 16 MiB; not swept again since the calls
# ask for `VMEM_LIMIT_BYTES`). Wider models need not fall back to the
# split kernels:
# attention is independent per head, so _bwd_packed slices the packed
# width into head GROUPS of <= FUSED_GROUP_TARGET and runs the fused
# kernel per group — gpt2-xl (25 heads x 64 = 1600) runs as two groups
# (13 + 12 heads, widths 832/768) with the blocks the <=1024 path earns.
#
# DEFAULT: AUTO — the resident-dq fused kernel wherever its fp32 dq slab
# fits its budget (`RESIDENT_DQ_MAX_BYTES`), the split pair elsewhere.
# History: round 2 shipped the fused kernel with dq as an HBM
# read-modify-write behind explicit DMA waits; that variant's advantage
# was environment-dependent (1.12x over split in one session, 0.7-0.9x
# in the next — the blocking ~1 MB waits sat on the critical path) and
# round 4 demoted it to an env flag. The resident-dq rewrite removes the
# DMAs entirely and beats split at every anchor width on the real chip
# (1.11x at hd 1024 and 1280, 1.44x at 1600 grouped — min over
# interleaved rounds, tests/perf/XL_BWD_COMPARE.json), so fusion is the
# default again, by fit rather than by flag. DS_FLASH_BWD_MODE=fused|
# split forces a path (fused uses the DMA variant where resident
# doesn't fit); the legacy
# DS_FLASH_FUSED_BWD=1/0 maps to fused/split. Numerics are identical on
# every path (test_fused_bwd_matches_split).
def _bwd_mode_from_env():
    mode = os.environ.get("DS_FLASH_BWD_MODE")
    if mode is not None:                  # the new var wins when both set
        if mode not in ("auto", "fused", "split"):
            raise ValueError(
                f"DS_FLASH_BWD_MODE={mode!r}: want auto|fused|split")
        return mode
    legacy = os.environ.get("DS_FLASH_FUSED_BWD")
    if legacy is not None:
        return "fused" if legacy != "0" else "split"
    return "auto"


BWD_MODE = _bwd_mode_from_env()
FUSED_BWD_MAX_WIDTH = 1280
FUSED_GROUP_TARGET = 1024
# Budget for the resident-dq fused kernel's whole-(s, hd) fp32 dq slab,
# of which VMEM holds three (the accumulator and the output's two
# buffers): 8 MiB takes hd 1024 to s 2048 (38.5 MiB in all by
# `_bwd_resident_vmem_bytes`; measured there 2.81 ms a layer against the
# split pair's 5.06, PR 41) and hd 1280 to s 1536. Longer sequences take
# the split pair (measured faster than the DMA fused variant).
RESIDENT_DQ_MAX_BYTES = 8 * 2**20


def _tile_lanes(n):
    return -(-n // 128) * 128


def _fwd_resident_vmem_bytes(block_q, s_p, hd, num_heads, itemsize):
    """VMEM the resident forward's specs take: the pipeline holds two
    buffers of every operand and output block (the (Bq, Bk) float32
    intermediates of the heads in flight come on top)."""
    q_out = 2 * block_q * hd * itemsize + block_q * _tile_lanes(num_heads) * 4
    return 2 * (q_out + 2 * s_p * hd * itemsize)


def _bwd_resident_vmem_bytes(block_q, block_k, s_qp, hd, num_heads,
                             itemsize):
    """VMEM the resident-dq backward's specs take: two buffers of every
    operand and output block (the whole fp32 dq among them), one of each
    scratch (dk, dv, dq accumulators)."""
    q_side = 2 * block_q * (hd * itemsize + _tile_lanes(num_heads) * 4)
    kv_side = 4 * block_k * hd * itemsize      # k, v in; dk, dv out
    slab = s_qp * hd * 4
    return 2 * (q_side + kv_side + slab) + 2 * block_k * hd * 4 + slab


def _resident_dq_fits(hd, s_qp):
    return s_qp * hd * 4 <= RESIDENT_DQ_MAX_BYTES


def _resident_blocks(w, s_qp=1024, itemsize=2):
    """(block_q, block_k) for the resident-dq kernel at the width ``w`` it
    RUNS at. (256, 512) measured fastest at every shape swept on the chip
    with `VMEM_LIMIT_BYTES` in place (PR 41, ms a layer, bf16, 20k
    tokens): width 768 1.22 against 1.30 at (256, 256); 1024 1.62 against
    1.70 at (256, 256), 1.81 at (128, 512), 2.28 at (128, 256), 1.97 at
    (512, 256), 3.6 at (512, 512) and 3.8 at (256, 1024); 1280 1.62
    against 2.19 at (256, 128); gpt2-xl's two groups 1.04 against 1.11 at
    (256, 256). The k block twice the q block is what lets a diagonal
    pair walk half a block. Where the specs' VMEM (`_bwd_resident_vmem_
    bytes`: it grows with width, sequence and itemsize) passes three
    quarters of the limit the calls ask for — float32 operands at the
    longest resident sequences — the k block halves. block_k stays a
    128-multiple (the bias block's lane dim)."""
    for blocks in ((256, 512), (256, 256)):
        if _bwd_resident_vmem_bytes(*blocks, s_qp, w, max(w // 64, 1),
                                    itemsize) <= VMEM_LIMIT_BYTES * 3 // 4:
            return blocks
    return (128, 256)


def _est_s_qp(s):
    """Conservative padded-q estimate for fit decisions made before the
    block size is final (candidate fused block_q values are <= 256)."""
    return -(-s // 256) * 256


def _bwd_dispatch(hd, num_heads, s, mode=None):
    """(plan, run_width) for the packed backward: 'fused' (single call),
    'grouped' (per-head-group fused calls), or 'split'; run_width is the
    packed width the fused kernel actually runs at (the 128-lane-padded
    group width under 'grouped') — the width block sizes must be keyed
    on. In auto mode the fused family is chosen exactly when every call
    it would make gets the resident-dq kernel (the DMA variant never
    wins its bake-off)."""
    mode = BWD_MODE if mode is None else mode
    if mode == "split":
        return "split", hd
    s_qp = _est_s_qp(s)
    if hd <= FUSED_BWD_MAX_WIDTH:
        if _resident_dq_fits(hd, s_qp) or mode == "fused":
            return "fused", hd
        return "split", hd
    d_head = hd // num_heads if num_heads else 0
    groups = _head_groups(num_heads, d_head) if num_heads else None
    if groups is None:
        return "split", hd
    gw = max(_padded_heads(n, d_head) for _, n in groups) * d_head
    if _resident_dq_fits(gw, s_qp) or mode == "fused":
        return "grouped", gw
    return "split", hd


def _fused_plan(hd, num_heads, s, mode=None):
    """Plan name alone — see _bwd_dispatch."""
    return _bwd_dispatch(hd, num_heads, s, mode)[0]


def _padded_heads(n, d_head):
    """Smallest head count >= n whose packed width is 128-lane aligned
    (the fused kernel's dq DMA slices need it; the extra heads are zero
    FAKE heads, see _bwd_fused_grouped)."""
    n_p = n
    while (n_p * d_head) % 128:
        n_p += 1
    return n_p


def _head_groups(num_heads, d_head):
    """Partition heads into the fewest contiguous groups whose packed
    width — AFTER 128-lane alignment padding — fits the single-call
    fused backward, balanced to within one head. Sizing on the unpadded
    width would overshoot: e.g. 18 heads of d=112 split as 9+9 (1008
    each) pads to 16 heads = 1792 > the 1280 cap. Returns
    [(start_head, n_heads), ...], or None when no feasible grouping
    exists (single padded head wider than the cap)."""
    hd = num_heads * d_head
    if hd <= FUSED_BWD_MAX_WIDTH:
        return [(0, num_heads)]
    if _padded_heads(1, d_head) * d_head > FUSED_BWD_MAX_WIDTH:
        return None
    for n_groups in range(-(-hd // FUSED_GROUP_TARGET), num_heads + 1):
        base, rem = divmod(num_heads, n_groups)
        sizes = [base + (1 if gi < rem else 0) for gi in range(n_groups)]
        if max(_padded_heads(n, d_head) * d_head for n in sizes) \
                <= FUSED_BWD_MAX_WIDTH:
            groups, start = [], 0
            for n in sizes:
                groups.append((start, n))
                start += n
            return groups
    return None


def auto_blocks(hd, num_heads=None, seq_len=None, itemsize=2):
    """BACKWARD (block_q, block_k) for the packed kernels by activation
    width h*d, keyed to the path _bwd_packed will take (pass seq_len so
    the fused-vs-split fit decision matches the dispatcher's; without it
    the fused family is assumed where width allows). Fused (one walk
    computes dq/dk/dv) with dq resident: `_resident_blocks`, keyed on
    the PADDED width the kernel really runs at (wider widths run the
    fused kernel per HEAD GROUP of width <= FUSED_GROUP_TARGET; 20 heads
    of d=80 split 10+10 is 800 wide on paper but pads to 1280). The
    forced DMA variant and the split fallback keep the blocks they were
    tuned to inside XLA's default 16 MiB of VMEM (rounds 3-5; not swept
    again since the calls ask for `VMEM_LIMIT_BYTES`): the split kernels
    hold q/do (Bq, hd) and k/v (Bk, hd) slabs double-buffered plus a
    (Bq or Bk, hd) fp32 scratch, and their blocks shrink as the width
    grows."""
    seq_len = seq_len if seq_len else 1024
    plan, w = _bwd_dispatch(hd, num_heads, seq_len)
    if plan in ("fused", "grouped"):
        s_qp = _est_s_qp(seq_len)
        if _resident_dq_fits(w, s_qp):
            return _resident_blocks(w, s_qp, itemsize)
        # forced fused past the resident budget -> the explicit-DMA
        # variant, whose working set has no resident slab: the round-3
        # tuned blocks stand
        return (256, 256) if w <= 1024 else (128, 256)
    if hd <= 1024:
        return DEFAULT_BLOCK_PACKED, DEFAULT_BLOCK_PACKED_K
    if hd <= 1280:
        return 256, 256
    return 128, 256


def auto_fwd_blocks(hd, seq_len=None, itemsize=2):
    """FORWARD (block_q, block_k). The resident kernel (``seq_len`` given
    and inside `RESIDENT_FWD_MAX_ELEMS`): (256, 512) at every width (at
    1280: 1.39 ms a layer against 1.95 at (256, 256), PR 41). The
    streaming kernel, and a caller that names no sequence: (256, 512) to
    hd 1024, (256, 256) past it, as tuned inside XLA's default 16 MiB."""
    resident = seq_len is not None and \
        _resident_fwd_fits(hd, seq_len, itemsize)
    if resident or hd <= 1024:
        return DEFAULT_BLOCK_PACKED, DEFAULT_BLOCK_PACKED_K
    return 256, 256


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_bshd_core(q, k, v, bias, sm_scale, causal, block_q, interpret,
                     block_k, bwd_block_q, bwd_block_k):
    out, _ = _flash_fwd_bshd(q, k, v, bias, sm_scale, causal, block_q,
                             interpret, block_k)
    return out


def _flash_fwd_bshd(q, k, v, bias, sm_scale, causal, block_q, interpret,
                    block_k):
    b, s, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    pack = lambda t: t.reshape(b, s, h * d)
    bias_p = _pad_bias(bias, b, s, min(block_k, s))
    out, lse = _fwd_packed(pack(q), pack(k), pack(v), bias_p, scale, causal,
                           block_q, block_k, interpret, h)
    return out.reshape(b, s, h, d), (q, k, v, bias_p, out, lse)


def _flash_fwd_bshd_rule(q, k, v, bias, sm_scale, causal, block_q,
                         interpret, block_k, bwd_block_q, bwd_block_k):
    return _flash_fwd_bshd(q, k, v, bias, sm_scale, causal, block_q,
                           interpret, block_k)


def _flash_bwd_bshd_rule(sm_scale, causal, block_q, interpret, block_k,
                         bwd_block_q, bwd_block_k, res, do):
    q, k, v, bias_p, out, lse = res  # q/k/v (b,s,h,d); out packed
    b, s, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    pack = lambda t: t.reshape(b, s, h * d)
    bbq = bwd_block_q or block_q
    bbk = bwd_block_k or block_k
    # bias was padded to the FWD block_k grain; re-pad to the bwd grain so
    # the kernels' (1, 1, block_k) bias slices can never run off the end
    bias_b = None if bias_p is None else \
        _pad_bias(bias_p[:, 0, :s], b, s, min(bbk, s))
    dq, dk, dv = _bwd_packed(pack(q), pack(k), pack(v), bias_b, out,
                             pack(do), lse, scale, causal,
                             bbq, bbk, interpret, h)
    unpack = lambda t: t.reshape(b, s, h, d)
    # bias is a MASK, not a trainable term: zero cotangent by contract
    # (the wrapper stop_gradients it too)
    d_bias = None if bias_p is None else jnp.zeros_like(bias_p[:, :, :s])
    return unpack(dq), unpack(dk), unpack(dv), d_bias


_flash_bshd_core.defvjp(_flash_fwd_bshd_rule, _flash_bwd_bshd_rule)


def flash_attention_bshd(q, k, v, sm_scale=None, causal=True,
                         block_q=None, interpret=False,
                         block_k=None, mask_bias=None,
                         bwd_block_q=None, bwd_block_k=None):
    """q/k/v: (batch, seq, heads, d_head) -> same layout. Heads are never
    transposed: the arrays are viewed as packed (b, s, h*d) — a free
    minor-dim merge — and the kernel loops heads over lane slices. (The
    (b,s,h,d)->(b*h,s,d) relayout at d_head 64 costs more HBM time than
    the attention math itself: measured 275 ms vs ~25 ms per GPT-2-125M
    forward at batch 192.)

    ``mask_bias``: optional (b, s) additive score bias per KEY position
    (0 keep / -1e9 drop — the BERT key-padding mask). Treated as a
    constant: no gradient flows into it."""
    b, s, h, d = q.shape
    # None block args resolve by width, sequence and itemsize so EVERY
    # caller (GPT-2, the BERT encoder layer, module_inject'ed models)
    # gets the measured blocks inside `VMEM_LIMIT_BYTES`. Explicit FWD
    # blocks do NOT flow into the backward: its working set is larger and
    # its best blocks are others (a forward tuned to block_q=512 would
    # run the backward at a third of its speed). Sweep both with
    # tests/perf/flash_attention_microbench.py.
    itemsize = q.dtype.itemsize
    fq, fk = auto_fwd_blocks(h * d, s, itemsize)
    bq_auto, bk_auto = auto_blocks(h * d, num_heads=h, seq_len=s,
                                   itemsize=itemsize)
    bwd_block_q = bwd_block_q or bq_auto
    bwd_block_k = bwd_block_k or bk_auto
    block_q = block_q or fq
    block_k = block_k or fk
    bias = None      # no operand and no add in the kernels (GPT-2)
    if mask_bias is not None:
        bias = jax.lax.stop_gradient(mask_bias.astype(jnp.float32))
        if bias.ndim == 2:
            bias = bias[:, None, :]
    return _flash_bshd_core(q, k, v, bias, sm_scale, causal, block_q,
                            interpret, block_k, bwd_block_q, bwd_block_k)


# ---------------------------------------------------------------------------
# Fused LN + QKV-projection + flash attention with remat-friendly residuals.
#
# Under per-block jax.checkpoint (full remat), the backward rebuild re-runs
# the flash FORWARD kernel just to regenerate the custom_vjp residuals
# (q/k/v/out/lse) — ~6.8 ms/layer at the GPT-2-medium bench shape. This op
# moves the attention out of the remat region and picks its residuals
# deliberately: save (out, lse), recompute q/k/v from the block input via
# LN + QKV gemm in the backward (cheap MXU work the full-remat path was
# recomputing anyway). Saved per layer: out (shared with the downstream
# checkpoint's input — one buffer) + lse. The backward derives the LN/gemm
# cotangents with jax.vjp of the same recompute function, so the fused path
# cannot numerically diverge from the unfused one.
# ---------------------------------------------------------------------------
def _lnqkv(x, ln_scale, ln_bias, qkv_w, qkv_b, eps):
    """Block input -> packed (b, s, h*d) q, k, v (the model's natural
    layout; heads stay merged in the minor dim)."""
    from .fused_ops import fused_layer_norm
    # the kernels beside it stay outside every scope: an unnamed
    # ``pallas_call`` takes its trace event's name (``%jvp__.N``) from
    # the innermost one (docs/telemetry.md, "Device scopes")
    with jax.named_scope("attn.proj"):
        ln = fused_layer_norm(x, ln_scale, ln_bias, eps)
        qkv = ln @ qkv_w.astype(ln.dtype) + qkv_b.astype(ln.dtype)
        return jnp.split(qkv, 3, axis=-1)


def fused_ln_qkv_attention(x, ln_scale, ln_bias, qkv_w, qkv_b, num_heads,
                           eps=1e-5, causal=True, block_q=None,
                           block_k=None, interpret=False,
                           bwd_block_q=None, bwd_block_k=None):
    """x: (b, s, d_model) -> attention context (b, s, d_model), causal,
    sm_scale fixed at 1/sqrt(d_head). None block args resolve by width
    (auto_fwd_blocks / auto_blocks); explicit fwd blocks do NOT flow into
    the bwd (its best blocks are others — pass bwd_block_* to tune it)."""
    hd = x.shape[-1]
    itemsize = x.dtype.itemsize
    fq, fk = auto_fwd_blocks(hd, x.shape[1], itemsize)
    bq_auto, bk_auto = auto_blocks(hd, num_heads=num_heads,
                                   seq_len=x.shape[1], itemsize=itemsize)
    bwd_block_q = bwd_block_q or bq_auto
    bwd_block_k = bwd_block_k or bk_auto
    return _fused_lnqkv_core(x, ln_scale, ln_bias, qkv_w, qkv_b, num_heads,
                             eps, causal, block_q or fq, block_k or fk,
                             interpret, bwd_block_q, bwd_block_k)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _fused_lnqkv_core(x, ln_scale, ln_bias, qkv_w, qkv_b, num_heads,
                      eps, causal, block_q, block_k, interpret,
                      bwd_block_q, bwd_block_k):
    out, _ = _fused_lnqkv_attn_fwd(x, ln_scale, ln_bias, qkv_w, qkv_b,
                                   num_heads, eps, causal, block_q, block_k,
                                   interpret, bwd_block_q, bwd_block_k)
    return out


def _fused_lnqkv_attn_fwd(x, ln_scale, ln_bias, qkv_w, qkv_b, num_heads,
                          eps, causal, block_q, block_k, interpret,
                          bwd_block_q, bwd_block_k):
    b, s, hd = x.shape
    d = hd // num_heads
    q, k, v = _lnqkv(x, ln_scale, ln_bias, qkv_w, qkv_b, eps)
    # no key-padding mask reaches this op: the kernels take no bias
    out, lse = _fwd_packed(q, k, v, None, 1.0 / (d ** 0.5), causal,
                           block_q, block_k, interpret, num_heads)
    return out, (x, ln_scale, ln_bias, qkv_w, qkv_b, out, lse)


def _fused_lnqkv_attn_bwd(num_heads, eps, causal, block_q, block_k,
                          interpret, bwd_block_q, bwd_block_k, res, do):
    x, ln_scale, ln_bias, qkv_w, qkv_b, out, lse = res
    b, s, hd = x.shape
    d = hd // num_heads
    (q, k, v), lnqkv_vjp = jax.vjp(
        lambda x_, s_, b_, w_, bb_: _lnqkv(x_, s_, b_, w_, bb_, eps),
        x, ln_scale, ln_bias, qkv_w, qkv_b)
    dq, dk, dv = _bwd_packed(q, k, v, None, out, do, lse,
                             1.0 / (d ** 0.5), causal, bwd_block_q,
                             bwd_block_k, interpret, num_heads)
    return lnqkv_vjp([dq, dk, dv])  # list: matches _lnqkv's jnp.split output


_fused_lnqkv_core.defvjp(_fused_lnqkv_attn_fwd, _fused_lnqkv_attn_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, sm_scale=None, causal=True,
                    block_q=DEFAULT_BLOCK_Q, interpret=False,
                    block_k=DEFAULT_BLOCK_K):
    """q/k/v: (batch_heads, seq, d_head) -> (batch_heads, seq, d_head)."""
    out, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, interpret,
                        block_k)
    return out


def _flash_fwd(q, k, v, sm_scale, causal, block_q, interpret, block_k):
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, interpret,
                    block_k=DEFAULT_BLOCK_K):
    out, res = _flash_fwd(q, k, v, sm_scale, causal, block_q, interpret,
                          block_k)
    return out, res


def _flash_bwd_rule(sm_scale, causal, block_q, interpret, block_k, res, do):
    q, k, v, out, lse = res
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    dq, dk, dv = _bwd(q, k, v, out, do, lse, scale, causal, block_q,
                      block_k, interpret)
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
