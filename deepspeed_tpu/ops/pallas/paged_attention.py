"""Pallas paged-attention: decode over the paged KV pool without the
gather-back.

The XLA paged path (``models/gpt2.py::_paged_attn_ctx``) reads the cache
by gathering every slot's pages back into contiguous ``(b, h,
max_pages * page_size, d_head)`` rows — ``jnp.take`` materializes each
slot's FULL logical KV window in HBM per layer per decode step, then the
dense masked attention reads it again. This kernel walks each slot's
page table inside the kernel instead: physical pages stream
HBM -> VMEM through double-buffered ``pltpu.make_async_copy`` fetches
(page p+1's DMA is in flight while page p's scores are on the MXU), and
an online-softmax accumulator (flash-attention style, fp32) folds each
page in as it lands. Bytes touched per step drop from
``2 * max_pages * page_size`` rows per slot to ``2 * ceil(live_len /
page_size)`` pages — and nothing is ever re-materialized contiguously.

Masking contract (bit-compatible with the slot oracle,
``_attend_cache_rows``):

* absolute-position causality: key position ``k_pos`` contributes to
  query ``q_pos`` iff ``k_pos <= q_pos`` — stale K/V from recycled
  pages past a slot's live window is unreachable, so page reuse needs
  no clearing;
* the V side is additionally ZEROED past the live window (``k_pos >
  positions + valid_lens - 1``): masked scores give softmax weight
  exactly 0.0, but ``0 * NaN = NaN`` — a NaN-poisoned recycled page
  would contaminate the weighted sum despite the mask (the same guard
  the oracle applies, pinned by tests/unit/test_pallas_kernels.py);
* garbage-page-0 redirects are read-safe for free: a slot's page-table
  entries are ``GARBAGE_PAGE`` only at logical pages past its live
  window, and the page walk stops at ``ceil((positions + valid_lens) /
  page_size)`` — the garbage page's content is only ever reached by
  inactive slots, whose outputs the scheduler ignores (exactly as on
  the oracle path).

Pool layout: ``(pages + 1, layers, page_size, heads * d_head)`` — heads
PACKED in the minor dimension, so one page of one layer is a contiguous
``(page_size, heads * d_head)`` slab whose minor dimension is a multiple
of the chip's 128 lanes at every GPT-2 width. (A ``(..., page_size,
d_head)`` minor pair is refused by the chip's compiler at d_head 64:
"Slice shape along dimension 4 must be aligned to tiling (128), but is
64" — and padded to 128 lanes in HBM.) Heads are a static in-kernel
loop over lane slices, the packed flash kernels' pattern
(ops/transformer/flash_attention.py).

The kernel is grid-parallel over slots; the page-table row, position
and valid length ride ``PrefetchScalarGridSpec`` scalar prefetch so the
DMA source indices are known before the body runs. Off-TPU it runs
under the Pallas interpreter (``interpret=True``) — the numerics-pinning
vehicle for tier-1/dryrun, not a serving configuration
(``inference.paged_attention_kernel: "auto"`` keeps CPU on the XLA
gather path). Flops are pinned to the dense math via ``pl.CostEstimate``
so the compile-observatory/cost-analysis pricing seam sees the same
count the XLA path reports.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import default_interpret, shard_kernel, split_axes

NEG_INF = -1e30


def _kernel(pt_ref, pos_ref, vlen_ref, q_ref, k_pool_ref, v_pool_ref,
            o_ref, k_buf, v_buf, k_sem, v_sem, *, layer_idx, page_size,
            num_heads, d_head, sm_scale, seq):
    """One slot's page-table walk. Refs:

    pt_ref (b, max_pages) / pos_ref (b,) / vlen_ref (b,): SMEM scalar
    prefetch; q_ref (1, s, h*dh) VMEM block; k/v_pool_ref the whole
    paged pools (pages+1, L, page_size, h*dh) left in HBM; o_ref
    (1, s, h*dh) fp32; k/v_buf (2, page_size, h*dh) double buffers.
    """
    i = pl.program_id(0)
    pos = pos_ref[i]
    vlen = vlen_ref[i]
    live = pos + vlen - 1                  # last live absolute position
    n_pages = jnp.maximum(live, 0) // page_size + 1

    def fetch(slot, p):
        phys = pt_ref[i, p]
        return (pltpu.make_async_copy(k_pool_ref.at[phys, layer_idx],
                                      k_buf.at[slot], k_sem.at[slot]),
                pltpu.make_async_copy(v_pool_ref.at[phys, layer_idx],
                                      v_buf.at[slot], v_sem.at[slot]))

    kd, vd = fetch(0, 0)
    kd.start()
    vd.start()

    qf = q_ref[0].astype(jnp.float32) * sm_scale          # (s, h*dh)
    q_pos = pos + jax.lax.broadcasted_iota(jnp.int32, (seq, page_size), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (seq, page_size), 1)
    vcol = jax.lax.broadcasted_iota(jnp.int32, (page_size, 1), 0)

    def body(p, carry):
        acc, m, l = carry                  # (s,h*dh), (s,h), (s,h) fp32
        slot = jax.lax.rem(p, 2)

        @pl.when(p + 1 < n_pages)
        def _prefetch():
            kn, vn = fetch(jax.lax.rem(p + 1, 2), p + 1)
            kn.start()
            vn.start()

        kw, vw = fetch(slot, p)
        kw.wait()
        vw.wait()
        k_pg = k_buf[slot].astype(jnp.float32)            # (ps, h*dh)
        v_pg = v_buf[slot].astype(jnp.float32)

        k_pos = p * page_size + col                       # (s, ps)
        mask = jnp.logical_and(k_pos <= q_pos, k_pos <= live)
        vmask = (p * page_size + vcol) <= live            # (ps, 1)
        v_pg = jnp.where(vmask, v_pg, 0.0)

        new_acc, new_m, new_l = [], [], []
        for hi in range(num_heads):
            sl = slice(hi * d_head, (hi + 1) * d_head)
            scores = jax.lax.dot_general(
                qf[:, sl], k_pg[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # (s, ps)
            scores = jnp.where(mask, scores, NEG_INF)
            m_old = m[:, hi:hi + 1]
            m_new = jnp.maximum(m_old,
                                jnp.max(scores, axis=-1, keepdims=True))
            pexp = jnp.exp(scores - m_new)
            corr = jnp.exp(m_old - m_new)
            new_m.append(m_new)
            new_l.append(l[:, hi:hi + 1] * corr
                         + jnp.sum(pexp, axis=-1, keepdims=True))
            new_acc.append(acc[:, sl] * corr + jax.lax.dot_general(
                pexp, v_pg[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        return (jnp.concatenate(new_acc, axis=1),
                jnp.concatenate(new_m, axis=1),
                jnp.concatenate(new_l, axis=1))

    acc0 = jnp.zeros((seq, num_heads * d_head), jnp.float32)
    m0 = jnp.full((seq, num_heads), NEG_INF, jnp.float32)
    l0 = jnp.zeros((seq, num_heads), jnp.float32)
    acc, _, l = jax.lax.fori_loop(0, n_pages, body, (acc0, m0, l0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    # per-head rescale: (s, h) -> lane slices of (s, h*dh)
    o_ref[0] = jnp.concatenate(
        [acc[:, hi * d_head:(hi + 1) * d_head] / l_safe[:, hi:hi + 1]
         for hi in range(num_heads)], axis=1)


def _grouped_kernel(pt_ref, pos_ref, vlen_ref, q_ref, k_pool_ref,
                    v_pool_ref, o_ref, k_buf, v_buf, k_sem, v_sem, *,
                    layer_idx, page_size, kv_heads, group, d_head,
                    sm_scale, seq, chunk):
    """One slot's page-table walk where ``group`` query heads share
    each key-value head (grouped-query attention; ``kv_heads = 1`` is
    multi-query). The masking contract is :func:`_kernel`'s. What
    differs: a page of ``kv_heads * d_head`` lanes is small (4 KB at
    one head of 128), so pages are fetched ``chunk`` at a time into one
    buffer of ``chunk * page_size`` tokens (the next chunk's copies in
    flight while this one is on the MXU), and a key-value head's
    ``seq * group`` queries are the rows of ONE matmul per chunk.

    q_ref / o_ref (1, kv_heads, seq * group, d_head), rows ordered
    (query, head of the group); k/v_buf (2, chunk * page_size,
    kv_heads * d_head)."""
    i = pl.program_id(0)
    pos = pos_ref[i]
    vlen = vlen_ref[i]
    live = pos + vlen - 1                  # last live absolute position
    n_pages = jnp.maximum(live, 0) // page_size + 1
    n_chunks = (n_pages + chunk - 1) // chunk
    rows, tokens = seq * group, chunk * page_size

    def transfer(slot, c, start):
        # a chunk's last pages may lie past the live window: no copy,
        # and what the buffer holds there is masked below
        for j in range(chunk):
            p = c * chunk + j

            @pl.when(p < n_pages)
            def _copy():
                phys = pt_ref[i, p]
                dst = pl.ds(j * page_size, page_size)
                for pool, buf, sem in ((k_pool_ref, k_buf, k_sem),
                                       (v_pool_ref, v_buf, v_sem)):
                    copy = pltpu.make_async_copy(
                        pool.at[phys, layer_idx], buf.at[slot, dst],
                        sem.at[slot])
                    copy.start() if start else copy.wait()

    transfer(0, 0, True)
    q_pos = pos + jax.lax.broadcasted_iota(
        jnp.int32, (rows, tokens), 0) // group
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, tokens), 1)
    vcol = jax.lax.broadcasted_iota(jnp.int32, (tokens, 1), 0)
    qs = [q_ref[0, h].astype(jnp.float32) * sm_scale
          for h in range(kv_heads)]                       # (rows, dh)

    def body(c, carry):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _prefetch():
            transfer(jax.lax.rem(c + 1, 2), c + 1, True)

        transfer(slot, c, False)
        k_pos = c * tokens + col
        mask = jnp.logical_and(k_pos <= q_pos, k_pos <= live)
        vmask = (c * tokens + vcol) <= live
        k_all, v_all = k_buf[slot], v_buf[slot]
        out = []
        for h in range(kv_heads):
            acc, m, l = carry[h]
            sl = slice(h * d_head, (h + 1) * d_head)
            k_h = k_all[:, sl].astype(jnp.float32)
            v_h = jnp.where(vmask, v_all[:, sl].astype(jnp.float32), 0.0)
            scores = jax.lax.dot_general(
                qs[h], k_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # (rows, tokens)
            scores = jnp.where(mask, scores, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            pexp = jnp.exp(scores - m_new)
            corr = jnp.exp(m - m_new)
            out.append((acc * corr + jax.lax.dot_general(
                pexp, v_h, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32),
                m_new, l * corr + jnp.sum(pexp, axis=-1, keepdims=True)))
        return tuple(out)

    init = tuple((jnp.zeros((rows, d_head), jnp.float32),
                  jnp.full((rows, 1), NEG_INF, jnp.float32),
                  jnp.zeros((rows, 1), jnp.float32))
                 for _ in range(kv_heads))
    final = jax.lax.fori_loop(0, n_chunks, body, init)
    for h, (acc, _, l) in enumerate(final):
        o_ref[0, h] = acc / jnp.where(l == 0.0, 1.0, l)


def _grouped_paged_attention(q, k_pool, v_pool, page_tables, positions,
                             valid_lens, *, layer_idx, page_size,
                             interpret, chunk=8):
    """:func:`paged_attention` for pools of fewer key-value heads than
    query heads. q (b, s, h, dh); pools (pages+1, layers, page_size,
    kvh * dh) with ``h % kvh == 0``."""
    b, s, h, dh = q.shape
    kvh = k_pool.shape[3] // dh
    group = h // kvh
    rows = s * group
    max_pages = page_tables.shape[1]
    chunk = min(chunk, max_pages)
    # rows of one key-value head: (query, head of its group)
    q = q.reshape(b, s, kvh, group, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(b, kvh, rows, dh)
    block = pl.BlockSpec((1, kvh, rows, dh), lambda i, *_: (i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[block, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=block,
        scratch_shapes=[
            pltpu.VMEM((2, chunk * page_size, kvh * dh), k_pool.dtype),
            pltpu.VMEM((2, chunk * page_size, kvh * dh), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    kernel = functools.partial(
        _grouped_kernel, layer_idx=layer_idx, page_size=page_size,
        kv_heads=kvh, group=group, d_head=dh,
        sm_scale=1.0 / math.sqrt(dh), seq=s, chunk=chunk)
    window = max_pages * page_size
    cost = pl.CostEstimate(
        flops=4 * b * s * window * h * dh,
        bytes_accessed=(q.size * q.dtype.itemsize
                        + 2 * b * window * kvh * dh
                        * k_pool.dtype.itemsize + b * s * h * dh * 4),
        transcendentals=b * s * window * h)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rows, dh), jnp.float32),
        cost_estimate=cost, interpret=interpret,
        name="paged_attention_grouped",
    )(page_tables.astype(jnp.int32), positions.astype(jnp.int32),
      valid_lens.astype(jnp.int32), q, k_pool, v_pool)
    return out.reshape(b, kvh, s, group, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(b, s, h, dh)


def paged_attention(q, k_pool, v_pool, page_tables, positions, valid_lens,
                    *, layer_idx, page_size, interpret=None, mesh=None):
    """Paged attention for ``s`` new queries per slot against the pool.
    ``mesh``: the mesh the calling program spans — the kernel then runs
    under a shard_map over it (common.shard_kernel), heads split over
    its ``model`` axis like the pool's packed minor dimension
    (inference/kv_cache.py PAGED_KV_CACHE_SPEC), the rest replicated.

    ``q``: (b, s, h, dh) — the new tokens' queries (cache writes for the
    SAME tokens must already have landed via the masked scatter, exactly
    as on the XLA gather path; this kernel replaces only the read side).
    ``k_pool``/``v_pool``: (pages+1, layers, page_size, h*dh), or
    (..., kvh*dh) with ``kvh`` key-value heads each shared by ``h / kvh``
    query heads (grouped-query attention: :func:`_grouped_kernel`);
    ``page_tables``: (b, max_pages) int32; ``positions``/``valid_lens``:
    (b,) int32. ``layer_idx`` is trace-static (the model's python layer
    loop). Returns fp32 ctx (b, s, h, dh) — within 1e-5 of the slot
    oracle's dense masked softmax (same contributing entries, online
    accumulation order).
    """
    if interpret is None:
        interpret = default_interpret()
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        from ...parallel.topology import MODEL_AXIS
        heads = split_axes(mesh, (MODEL_AXIS,), q.shape[2])
        q_spec, pool_spec = P(None, None, heads), P(None, None, None, heads)
        kernel = functools.partial(
            paged_attention, layer_idx=layer_idx, page_size=page_size,
            interpret=interpret)
        return shard_kernel(
            kernel, mesh, (q_spec, pool_spec, pool_spec, P(), P(), P()),
            q_spec)(q, k_pool, v_pool, page_tables, positions, valid_lens)
    b, s, h, dh = q.shape
    hd = h * dh
    packed = k_pool.shape[3]
    if packed != hd and packed % dh == 0 and h % (packed // dh) == 0 \
            and k_pool.shape[2] == page_size:
        # fewer key-value heads than query heads: the grouped kernel
        return _grouped_paged_attention(
            q, k_pool, v_pool, page_tables, positions, valid_lens,
            layer_idx=layer_idx, page_size=page_size, interpret=interpret)
    if k_pool.shape[2:] != (page_size, hd):
        raise ValueError(
            "paged_attention wants pools (pages+1, layers, page_size {}, "
            "heads*d_head {}), got {}".format(page_size, hd, k_pool.shape))
    max_pages = page_tables.shape[1]
    full_window = max_pages * page_size
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, s, hd), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, s, hd), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, page_size, hd), k_pool.dtype),
            pltpu.VMEM((2, page_size, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    kernel = functools.partial(
        _kernel, layer_idx=layer_idx, page_size=page_size, num_heads=h,
        d_head=dh, sm_scale=1.0 / math.sqrt(dh), seq=s)
    # flops pinned to the dense math over the full logical window (qk^T
    # + p@v), the same count the XLA gather path's dots report — keeps
    # the cost-analysis pricing seam (telemetry/programs.py) honest.
    cost = pl.CostEstimate(
        flops=4 * b * s * full_window * hd,
        bytes_accessed=(q.size * q.dtype.itemsize
                        + 2 * b * full_window * hd
                        * k_pool.dtype.itemsize
                        + b * s * hd * 4),
        transcendentals=b * s * full_window * h)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, hd), jnp.float32),
        cost_estimate=cost,
        interpret=interpret,
    )(page_tables.astype(jnp.int32), positions.astype(jnp.int32),
      valid_lens.astype(jnp.int32), q.reshape(b, s, hd), k_pool, v_pool)
    return out.reshape(b, s, h, dh)
