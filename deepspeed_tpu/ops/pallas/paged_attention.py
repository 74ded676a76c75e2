"""Pallas paged-attention: decode over the paged KV pool without the
gather-back.

The XLA paged path (``models/gpt2.py::_paged_attn_ctx``) reads the cache
by gathering every slot's pages back into contiguous ``(b, h,
max_pages * page_size, d_head)`` rows — ``jnp.take`` materializes each
slot's FULL logical KV window in HBM per layer per decode step, then the
dense masked attention reads it again. This kernel walks each slot's
page table inside the kernel instead, a BLOCK of pages a loop turn:
the block's physical pages stream HBM -> VMEM as one
``pltpu.make_async_copy`` each into a double buffer (the next block's
copies are in flight while this one is on the MXU, and during a slot's
last block the next slot's first), and an online-softmax accumulator
(flash-attention style, fp32) folds the block in, every head at once.
Bytes touched per step drop from ``2 * max_pages * page_size`` rows per
slot to ``2 * ceil(live_len / page_size)`` pages — and nothing is ever
re-materialized contiguously.

Pages a block (:func:`_pages_per_block`), from static shapes only:
``_BLOCK_TOKENS`` (512) of tokens, no more than ``_KV_BLOCK_VMEM_BYTES``
(8 MiB) holds of K and V, double-buffered, at the pool's ``page_size *
heads * d_head * itemsize`` a page, and no more than a row has: 32 pages
(4 MiB of buffers) at GPT-2 medium's 1,024 bf16 lanes and where a
tensor-parallel shard holds a quarter of the heads, 16 (7.5 MiB) at
Olmo-Hybrid's 3,840. How a block comes, all three walks: the pages'
copies started in a straight line and ONE wait a pool on a descriptor of
the whole buffer half (a start and a wait a page in loops left a turn
waiting on scalar work: 72 -> 87% of the live pages' HBM time at the
document cell's shape, 64 -> 91% at Olmo's, PERF.md section 6, PR 55).
:func:`_kernel` takes every FULL block so and a slot's last block by its
live pages alone, a start and a wait each (whole with its dead pages the
walk read 8-26% slower), and a DEAD slot (no query, or a row that begins
on the garbage page) fetches and folds nothing and writes zeros: a grid
step, where it walked the garbage page for 0.6 us. The grouped walk
(:func:`_grouped_block`) and the latent one take 512 tokens a turn and
fetch every block WHOLE, dead pages too.

One pass over the packed lanes folds every head (:func:`_kernel`, and
:func:`_grouped_kernel` where query heads share key-value heads): the
slot's queries are laid out block-diagonally, row ``(query, head)``
holding that head's ``d_head`` lanes and zeros elsewhere, so the scores
of all heads against a block of keys are ONE matmul ``(s * h, h * dh) x
(tokens, h * dh)^T`` and the weighted values ONE matmul ``(s * h,
tokens) x (tokens, h * dh)``, whose block diagonal is picked out after
the walk: every operand lane-dense, no 64-lane slice of a page.

Precision, both walks: K and V enter the MXU in the pool's dtype, as
stored (no float32 copy of a page), the queries cast to it (the bf16
the model's qkv matmul produced, under a bf16 pool), ``sm_scale``
multiplies the float32 scores, every accumulation and every softmax
statistic is float32, and the weights ``exp(scores - m)`` enter the
second matmul in the pool's dtype, as the flash kernels' do
(ops/transformer/flash_attention.py) and the families' XLA reads
(models/jamba.py ``_attend``). With a float32 pool nothing is rounded.

Masking contract (bit-compatible with the XLA read,
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
  the oracle path; :func:`_kernel` reads it not even for them: a slot
  whose row begins on it is written zeros).

Pool layout: ``(pages + 1, layers, page_size, heads * d_head)`` — heads
PACKED in the minor dimension, so one page of one layer is a contiguous
``(page_size, heads * d_head)`` slab whose minor dimension is a multiple
of the chip's 128 lanes at every GPT-2 width. (A ``(..., page_size,
d_head)`` minor pair is refused by the chip's compiler at d_head 64:
"Slice shape along dimension 4 must be aligned to tiling (128), but is
64" — and padded to 128 lanes in HBM.)

The grid is one step a slot, in order (a slot's first block is fetched
during the slot before it, in :func:`_kernel` the live slot before it,
so the axis is ``arbitrary``, not ``parallel``); the page tables,
positions and valid lengths ride ``PrefetchScalarGridSpec`` scalar
prefetch so the DMA source indices, of this slot and the next, are known
before the body runs (:func:`_kernel`'s and the latent walk's table flat,
a start's entry a base plus a constant; the grouped walk's a slot's ROW
and the next slot's at a time, two blocks of one array in scalar memory:
128 x 2,048 entries are all of it). Off-TPU the kernels run under the
Pallas interpreter, the numerics-pinning vehicle for tier-1/dryrun, not
a serving configuration
(``paged_attention_kernel: "auto"`` keeps CPU on the XLA gather path).
Flops are pinned to the dense math via ``pl.CostEstimate``.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import default_interpret, shard_kernel, split_axes

NEG_INF = -1e30


# The garbage page (inference/paging.py GARBAGE_PAGE; a test pins the two
# equal): a slot whose row BEGINS on it holds no page at all, which is
# how the walk sees a slot the scheduler has not filled. The decode
# program hands every slot its width as ``valid_lens``, the dead ones too.
_GARBAGE_PAGE = 0

# What a turn of the walk fetches, in tokens and in the bytes of its K
# and V buffers (two pools, each double-buffered): 32 pages of 16 at GPT-2
# medium's 1,024 bf16 lanes (4 MiB), 16 at Olmo-Hybrid's 3,840 (7.5 MiB).
# One layer's call by pages a turn (my chip runs, PR 55, PERF.md section
# 6; before: 8 and 2 pages, a start and a wait a live page in loops):
# docs' shape 0.698 -> 8: 0.671, 16: 0.596, 32: 0.576 ms (87% of the live
# pages' HBM time); evals' 3.264 -> 8: 2.277, 16: 2.280, 32: 2.284 ms (91%);
# chat's 0.061 -> 0.022 ms (57 of 64 slots dead).
_BLOCK_TOKENS = 512
_KV_BLOCK_VMEM_BYTES = 8 << 20


def _pages_per_block(max_pages, page_size, packed, itemsize):
    """Pages one turn of the walk fetches and folds: ``_BLOCK_TOKENS`` of
    them, no more than ``_KV_BLOCK_VMEM_BYTES`` holds of K and V
    double-buffered (in whole lane tiles of tokens where one fits), and
    no more than a row has. From static shapes."""
    held = _KV_BLOCK_VMEM_BYTES // (4 * page_size * packed * itemsize)
    tile = max(1, 128 // page_size)
    return max(1, min(max_pages, _BLOCK_TOKENS // page_size,
                      held // tile * tile or held))


def _kernel(layer_ref, pt_ref, pos_ref, vlen_ref, q_ref, k_pool_ref,
            v_pool_ref, o_ref, k_buf, v_buf, k_sem, v_sem, half_ref, *,
            page_size, num_heads, d_head, sm_scale, seq, block, max_pages):
    """One slot's page-table walk, ``block`` pages and every head a
    loop turn. Refs:

    layer_ref (1,): the pools' layer / pt_ref (b * max_pages,): the
    table's rows end to end (a start's entry is a base plus a constant)
    / pos_ref (b,) / vlen_ref (b,): SMEM scalar prefetch; q_ref (1, s,
    h*dh) VMEM block; k/v_pool_ref the whole paged pools (pages+1, L,
    page_size, h*dh) left in HBM; o_ref (1, s, h*dh) fp32; k/v_buf (2,
    block * page_size, h*dh) double buffers, one DMA semaphore a half;
    half_ref (1,) SMEM: the buffer half that holds the next live slot's
    first block.

    A FULL block (every page live: all but a slot's last) is fetched by
    straight-line starts and awaited by ONE wait a pool on a descriptor
    of the whole buffer half; a slot's last block by its live pages only,
    a start and a wait each in loops (whole with its dead pages it read
    8-26% slower at the cells' shapes, PERF.md section 6, PR 55). A DEAD
    slot (no query, or a row that begins on the garbage page) fetches and
    folds nothing and writes zeros: it costs a grid step.

    The scratch outlives a grid step and the grid is sequential, so the
    copies of the NEXT LIVE slot's first block start during a slot's
    last, across the dead slots between them (the first grid step starts
    the first live slot's; the last live slot starts none).
    """
    # Index arithmetic is on non-negative ints, so ``lax.div`` / ``rem``
    # stand for ``//`` / ``%``: those lower through ``sign``, 4 s of a
    # 24-layer decode program's lowering on every start (PERF.md, PR 33).
    i = pl.program_id(0)
    num_slots = pl.num_programs(0)
    layer_idx = layer_ref[0]
    rows, lanes = seq * num_heads, num_heads * d_head
    tokens = block * page_size
    pools = ((k_pool_ref, k_buf, k_sem), (v_pool_ref, v_buf, v_sem))

    def dead(slot):
        return jnp.logical_or(vlen_ref[slot] == 0,
                              pt_ref[slot * max_pages] == _GARBAGE_PAGE)

    def live_from(slot):
        # the first slot from ``slot`` on that has a walk (num_slots: none)
        return jax.lax.while_loop(
            lambda s: jnp.logical_and(
                s < num_slots, dead(jnp.minimum(s, num_slots - 1))),
            lambda s: s + 1, slot)

    def pages_of(slot):
        # ceil((positions + valid_lens) / page_size)
        live = pos_ref[slot] + vlen_ref[slot] - 1
        return jnp.minimum(
            jax.lax.div(jnp.maximum(live, 0), page_size) + 1, max_pages)

    def live_pages(slot, c):
        # of block ``c`` of the slot's row; a block the walk takes has one
        return jnp.minimum(pages_of(slot) - c * block, block)

    def fetch(slot, c, half, rolled=False):
        first = slot * max_pages + c * block   # an entry: first + a constant
        n = live_pages(slot, c)

        def page(j, carry):
            phys = pt_ref[first + j]
            dst = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            for pool, buf, sem in pools:
                pltpu.make_async_copy(pool.at[phys, layer_idx],
                                      buf.at[half, dst], sem.at[half]).start()
            return carry

        if rolled:
            return jax.lax.fori_loop(0, n, page, 0)

        @pl.when(n == block)
        def _whole():
            jax.lax.fori_loop(0, block, page, 0, unroll=True)  # traced once

        @pl.when(n < block)
        def _by_page():
            jax.lax.fori_loop(0, n, page, 0)

    def await_block(n, half):
        def wait(pages):
            for _, buf, sem in pools:
                rows_of = buf.at[half, pl.ds(0, pages * page_size)]
                pltpu.make_async_copy(rows_of, rows_of, sem.at[half]).wait()

        pl.when(n == block)(lambda: wait(block))    # the whole block

        @pl.when(n < block)
        def _by_page():
            jax.lax.fori_loop(0, n, lambda j, carry: (wait(1), carry)[1], 0)

    @pl.when(i == 0)
    def _first_slot():
        half_ref[0] = 0
        slot = live_from(0)

        @pl.when(slot < num_slots)
        def _its_first_block():
            fetch(slot, 0, 0, rolled=True)     # once a call

    is_dead = dead(i)

    @pl.when(is_dead)
    def _dead_slot():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(jnp.logical_not(is_dead))
    def _walk():
        pos = pos_ref[i]
        live = pos + vlen_ref[i] - 1       # last live absolute position
        n_blocks = jax.lax.div(pages_of(i) + block - 1, block)
        first_half = half_ref[0]
        nxt_live = live_from(i + 1)

        # the slot's queries block-diagonal over the packed lanes: row
        # (query, head) holds that head's d_head lanes of the query and
        # zeros elsewhere, so ONE matmul over all h*dh lanes scores every
        # head against a block of keys
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
        row_query = jax.lax.div(row, num_heads)
        own_lanes = jax.lax.div(lane, d_head) == jax.lax.rem(row, num_heads)
        q = q_ref[0].astype(jnp.float32)                      # (s, h*dh)
        q_rows = q[0:1]
        for j in range(1, seq):
            q_rows = jnp.where(row_query == j, q[j:j + 1], q_rows)
        q_bd = jnp.where(own_lanes, q_rows, 0.0).astype(k_buf.dtype)

        q_pos = pos + jax.lax.div(jax.lax.broadcasted_iota(
            jnp.int32, (rows, tokens), 0), num_heads)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, tokens), 1)
        token = jax.lax.broadcasted_iota(jnp.int32, (tokens, 1), 0)

        def body(c, carry):
            acc, m, l = carry          # (rows, h*dh), (rows, 1) x 2 fp32
            half = jax.lax.rem(first_half + c, 2)

            # next in flight while this block is on the MXU: this slot's
            # next block, or after its last the next live slot's first
            last = c + 1 == n_blocks
            nxt_slot = jnp.where(last, nxt_live, i)

            @pl.when(nxt_slot < num_slots)
            def _prefetch():
                fetch(nxt_slot, jnp.where(last, 0, c + 1), 1 - half)

            await_block(live_pages(i, c), half)

            # only a slot's last block reaches past its live window: zero
            # V there in place, before it meets a weight (the rows of a
            # page that was not fetched hold what an older block left)
            @pl.when(last)
            def _zero_dead_values():
                v_blk = v_buf[half]
                v_buf[half] = jnp.where(c * tokens + token <= live, v_blk,
                                        jnp.zeros_like(v_blk))

            scores = jax.lax.dot_general(
                q_bd, k_buf[half], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            k_pos = c * tokens + col                       # (rows, tokens)
            scores = jnp.where(
                jnp.logical_and(k_pos <= q_pos, k_pos <= live), scores,
                NEG_INF)
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            pexp = jnp.exp(scores - m_new)
            corr = jnp.exp(m - m_new)
            # every head's weights over ALL h*dh value lanes (h times the
            # useful flops, every operand lane-dense); a row's own head's
            # lanes are picked out after the walk
            acc = acc * corr + jax.lax.dot_general(
                pexp.astype(v_buf.dtype), v_buf[half],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return acc, m_new, l * corr + jnp.sum(pexp, axis=-1,
                                                  keepdims=True)

        init = (jnp.zeros((rows, lanes), jnp.float32),
                jnp.full((rows, 1), NEG_INF, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32))
        # a live slot's walk has a block at least, and position 0 is a
        # key every query sees: every row's l counts a token
        acc, _, l = jax.lax.fori_loop(0, n_blocks, body, init)
        half_ref[0] = jax.lax.rem(first_half + n_blocks, 2)

        out = jnp.where(own_lanes, acc / l, 0.0)
        for j in range(seq):
            mine = out if seq == 1 else jnp.where(row_query == j, out, 0.0)
            o_ref[0, j:j + 1, :] = jnp.sum(mine, axis=0, keepdims=True)


# Tokens a turn of the grouped walk fetches and folds: of 128 / 256 / 512
# / 1,024 the best or within 5% of it at the three cells' shapes (PERF.md
# section 6, PR 46): 32 pages of 16, 512 KB a buffer half at 512 bf16 lanes.
_GROUPED_BLOCK_TOKENS = 512


def _grouped_block(max_pages, page_size, seq, window):
    """Pages one turn of the grouped walk fetches and folds, all of them
    whether live or not: ``_GROUPED_BLOCK_TOKENS`` of tokens, and no
    more than a row has. The pages a ``window`` can touch are known, so
    a windowed walk takes them in its fewest turns, evenly, in whole
    lane tiles of tokens (65 pages: 3 turns of 24, not of 32)."""
    block = max(1, _GROUPED_BLOCK_TOKENS // page_size)
    if window is not None:
        span = (window + seq - 2) // page_size + 2
        turns, tile = -(-span // block), max(1, 128 // page_size)
        block = min(block, -(-span // (turns * tile)) * tile)
    return min(block, max_pages)


def _grouped_kernel(pos_ref, vlen_ref, pt_ref, nxt_ref, q_ref, k_pool_ref,
                    v_pool_ref, o_ref, k_buf, v_buf, k_sem, v_sem, half_ref,
                    *, layer_idx, page_size, kv_heads, group, d_head,
                    sm_scale, seq, block, window=None):
    """One slot's page-table walk where ``group`` query heads share each
    key-value head (``kv_heads = 1`` is multi-query), ``block`` pages
    and every head a loop turn, folded as :func:`_kernel` folds them
    (its precision, masking contract and prefetch across slots): the
    queries come in the pool's dtype, block-diagonal over its packed
    lanes (row ``(query, head)`` holds the head's query in its key-value
    head's ``d_head`` lanes), so all heads' scores are ONE matmul a
    block and their weighted values one more, whose block diagonal is
    picked out after the walk. With a ``window`` a query also sees only
    its last ``window`` keys, its own among them: the walk starts at the
    first page with a key the slot's first query can see (column 0 of a
    sliding table, inference/paging.py) and masks that page's older
    keys; ``None`` traces no window at all. Refs as :func:`_kernel`'s,
    but pt_ref / nxt_ref (1, 1, max_pages): this slot's row of the page
    table and the next slot's in SMEM (the whole table would fill it);
    q_ref (1, seq * heads, lanes); o_ref (1, seq * heads, d_head)."""
    i, num_slots = pl.program_id(0), pl.num_programs(0)
    max_pages, heads = pt_ref.shape[2], kv_heads * group
    rows, tokens = seq * heads, block * page_size

    def walk_of(slot):     # (first page walked, pages to the last live one)
        live = pos_ref[slot] + vlen_ref[slot] - 1
        seen = 0 if window is None else pos_ref[slot] - window + 1
        return jax.lax.div(jnp.maximum(seen, 0), page_size), jnp.minimum(
            jax.lax.div(jnp.maximum(live, 0), page_size) + 1, max_pages)

    def fetch(at, half, of_next=False, unroll=True):
        # ALL the block's pages from column ``at`` of this slot's row or the
        # next's: one wait a pool covers them (past the live: garbage, masked)
        def page(j, carry):
            column = jnp.minimum(at + j, max_pages - 1)
            phys = jnp.where(of_next, nxt_ref[0, 0, column],
                             pt_ref[0, 0, column])
            dst = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            for pool, buf, sem in ((k_pool_ref, k_buf, k_sem),
                                   (v_pool_ref, v_buf, v_sem)):
                pltpu.make_async_copy(pool.at[phys, layer_idx],
                                      buf.at[half, dst], sem.at[half]).start()
            return carry
        jax.lax.fori_loop(0, block, page, 0, unroll=unroll)  # traced once

    first, pages = walk_of(i)

    @pl.when(i == 0)
    def _first_slot():
        half_ref[0] = 0
        fetch(first, 0, unroll=False)      # once a call: a rolled loop

    nxt_first, _ = walk_of(jnp.minimum(i + 1, num_slots - 1))
    pos, live = pos_ref[i], pos_ref[i] + vlen_ref[i] - 1   # the last live
    # a block at least: the next slot's first is fetched during it
    n_blocks = jnp.maximum(jax.lax.div(pages - first + block - 1, block), 1)
    first_half = half_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, tokens), 1)
    q_pos = pos + jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (rows, tokens), 0), heads)
    token = jax.lax.broadcasted_iota(jnp.int32, (tokens, 1), 0)

    def body(c, carry):
        acc, m, l = carry              # (rows, lanes), (rows, 1) x 2 fp32
        half = jax.lax.rem(first_half + c, 2)
        at, last = first + c * block, c + 1 == n_blocks
        base = at * page_size              # the block's first position

        # in flight meanwhile: the next block, or the next slot's first
        @pl.when(jnp.logical_or(jnp.logical_not(last), i + 1 < num_slots))
        def _prefetch():
            fetch(jnp.where(last, nxt_first, at + block), 1 - half, last)

        for buf, sem in ((k_buf, k_sem), (v_buf, v_sem)):   # the whole block
            pltpu.make_async_copy(buf.at[half], buf.at[half],
                                  sem.at[half]).wait()

        @pl.when(last)     # as _kernel does, and the dead pages with it
        def _zero_dead_values():
            v_blk = v_buf[half]
            v_buf[half] = jnp.where(base + token <= live, v_blk,
                                    jnp.zeros_like(v_blk))

        scores = jax.lax.dot_general(
            q_ref[0], k_buf[half], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (rows, tokens)
        k_pos = base + col
        mask = jnp.logical_and(k_pos <= q_pos, k_pos <= live)
        if window is not None:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        scores = jnp.where(mask, scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        pexp = jnp.exp(scores - m_new)
        corr = jnp.exp(m - m_new)
        acc = acc * corr + jax.lax.dot_general(
            pexp.astype(v_buf.dtype), v_buf[half], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l * corr + jnp.sum(pexp, axis=-1, keepdims=True)

    acc, _, l = jax.lax.fori_loop(0, n_blocks, body, (
        jnp.zeros((rows, kv_heads * d_head), jnp.float32),
        jnp.full((rows, 1), NEG_INF, jnp.float32),
        jnp.zeros((rows, 1), jnp.float32)))
    half_ref[0] = jax.lax.rem(first_half + n_blocks, 2)
    # of all the packed lanes a row keeps its own key-value head's
    out = acc / jnp.where(l == 0.0, 1.0, l)
    kv_head = jax.lax.div(jax.lax.rem(jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0), heads), group)
    o_ref[0] = sum(jnp.where(kv_head == h, out[:, h * d_head:(h + 1) * d_head],
                             0.0) for h in range(kv_heads))


def _grouped_paged_attention(q, k_pool, v_pool, page_tables, positions,
                             valid_lens, *, layer_idx, page_size,
                             interpret, block=None, window=None):
    """:func:`paged_attention` for pools of fewer key-value heads than
    query heads. q (b, s, h, dh); pools (pages+1, layers, page_size,
    kvh * dh) with ``h % kvh == 0``; ``window``: :func:`_grouped_kernel`'s
    (``positions`` and the table then count from the same origin);
    ``block``: pages a loop turn (:func:`_grouped_block`'s unless
    given)."""
    b, s, h, dh = q.shape
    lanes = k_pool.shape[3]
    kvh = lanes // dh
    group = h // kvh
    rows = s * h
    max_pages = page_tables.shape[1]
    if block is None:
        block = _grouped_block(max_pages, page_size, s, window)
    # the queries block-diagonal over the pool's packed lanes, in its
    # dtype: row (query, head) holds the head's query in the lanes of
    # its key-value head
    own = (jnp.arange(rows)[:, None] % h // group
           == jnp.arange(lanes)[None, :] // dh)
    q = jnp.where(own, jnp.tile(q.reshape(b, rows, dh), (1, 1, kvh)),
                  0).astype(k_pool.dtype)
    # a slot's row (b, 1, max_pages): a block's last two dimensions are
    # the array's; the same array twice, the next slot's row beside it
    tables = page_tables.astype(jnp.int32)[:, None, :]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, max_pages), lambda i, *_: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, max_pages),
                         lambda i, *_: (jnp.minimum(i + 1, b - 1), 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, rows, lanes), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, rows, dh), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block * page_size, lanes), k_pool.dtype),
            pltpu.VMEM((2, block * page_size, lanes), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ])
    kernel = functools.partial(
        _grouped_kernel, layer_idx=layer_idx, page_size=page_size,
        kv_heads=kvh, group=group, d_head=dh,
        sm_scale=1.0 / math.sqrt(dh), seq=s, block=block,
        **({} if window is None else {"window": window}))
    span = max_pages * page_size
    cost = pl.CostEstimate(
        flops=4 * b * s * span * h * dh,
        bytes_accessed=(q.size * q.dtype.itemsize
                        + 2 * b * span * lanes
                        * k_pool.dtype.itemsize + b * s * h * dh * 4),
        transcendentals=b * s * span * h)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, dh), jnp.float32),
        cost_estimate=cost, interpret=interpret,
        # a slot's first block is fetched during the slot before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_attention_grouped",
    )(positions.astype(jnp.int32), valid_lens.astype(jnp.int32),
      tables, tables, q, k_pool, v_pool)
    return out.reshape(b, s, h, dh)


# Tokens a turn of the latent page walk fetches and folds, all of them
# whether live or not: 32 pages of 16 at 640 bf16 lanes, 640 KB a buffer
# half (PERF.md section 6, PR 47).
_MLA_BLOCK_TOKENS = 512


def _mla_kernel(pt_ref, pos_ref, vlen_ref, q_ref, pool_ref, o_ref, buf,
                sem, half_ref, *, layer_idx, page_size, heads, rank,
                sm_scale, seq, block, max_pages):
    """One slot's walk over LATENT pages (ops/mla.py, the absorbed
    form): every head's query ``[q_lat | q_pe | 0]`` against one shared
    "key-value head", the cached row ``[c~ | k_pe | 0]``, whose values
    are the first ``rank`` lanes of the same fetched block: ONE pool,
    one DMA a page, where the other kernels make two. The fetch and the
    fold are :func:`_grouped_kernel`'s: a block is fetched WHOLE, the
    table's entries past a slot's live pages (the garbage page) with the
    rest, by straight-line starts and ONE wait on a descriptor of the
    whole buffer half; the next block, or after a slot's last the next
    slot's first, is in flight meanwhile; all ``seq * heads`` queries of
    the slot fold a block at once. The masking contract is the module's,
    and what a dead page brought is zeroed on a slot's last block, the
    only one that can hold any. Refs:

    pt_ref (b * max_pages,): the table's rows end to end (a start is
    scalar work, and an entry of a 2-D table in scalar memory is a
    tile's address arithmetic further away) / pos_ref (b,) / vlen_ref
    (b,): SMEM scalar prefetch; q_ref (1, seq * heads, lanes), rows
    ordered (query, head); pool_ref (pages+1, L, page_size, lanes) left
    in HBM; o_ref (1, seq * heads, rank) fp32; buf (2, block *
    page_size, lanes); half_ref (1,) SMEM: the buffer half of this
    slot's first block."""
    i = pl.program_id(0)
    num_slots = pl.num_programs(0)
    rows = seq * heads
    tokens = block * page_size

    def fetch(slot, at, half, unroll=True):
        # ALL the block's pages from column ``at`` of the slot's row: one
        # wait covers them (past the live: the garbage page, zeroed below)
        row = slot * max_pages
        first = row + at                   # an entry is ``first`` + a constant

        def page(j, carry):
            entry = first + j
            if max_pages % block:          # the last block overhangs the row
                entry = jnp.minimum(entry, row + max_pages - 1)
            phys = pt_ref[entry]
            dst = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            pltpu.make_async_copy(pool_ref.at[phys, layer_idx],
                                  buf.at[half, dst], sem.at[half]).start()
            return carry
        jax.lax.fori_loop(0, block, page, 0, unroll=unroll)  # traced once

    @pl.when(i == 0)
    def _first_slot():
        half_ref[0] = 0
        fetch(0, 0, 0, unroll=False)       # once a call: a rolled loop

    pos = pos_ref[i]
    live = pos + vlen_ref[i] - 1           # last live absolute position
    pages = jnp.minimum(
        jax.lax.div(jnp.maximum(live, 0), page_size) + 1, max_pages)
    # a block at least: the next slot's first is fetched during it
    n_blocks = jax.lax.div(pages + block - 1, block)
    first_half = half_ref[0]
    q = q_ref[0]                                       # (rows, lanes)
    q_pos = pos + jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (rows, tokens), 0), heads)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, tokens), 1)
    token = jax.lax.broadcasted_iota(jnp.int32, (tokens, 1), 0)

    def body(c, carry):
        acc, m, l = carry                  # (rows, rank), (rows, 1) x 2
        half = jax.lax.rem(first_half + c, 2)
        last = c + 1 == n_blocks
        nxt_slot = jnp.where(last, i + 1, i)

        # in flight meanwhile: the next block, or the next slot's first
        @pl.when(nxt_slot < num_slots)
        def _prefetch():
            fetch(nxt_slot, jnp.where(last, 0, (c + 1) * block), 1 - half)

        pltpu.make_async_copy(buf.at[half], buf.at[half],
                              sem.at[half]).wait()     # the whole block

        # only a slot's last block reaches past its live window: zero
        # the rows there in place (values, and the keys with them)
        @pl.when(last)
        def _zero_dead_rows():
            blk = buf[half]
            buf[half] = jnp.where(c * tokens + token <= live, blk,
                                  jnp.zeros_like(blk))

        blk = buf[half]
        scores = jax.lax.dot_general(
            q, blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (rows, tokens)
        k_pos = c * tokens + col
        scores = jnp.where(
            jnp.logical_and(k_pos <= q_pos, k_pos <= live), scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        pexp = jnp.exp(scores - m_new)
        corr = jnp.exp(m - m_new)
        acc = acc * corr + jax.lax.dot_general(
            pexp.astype(blk.dtype), blk[:, :rank],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return acc, m_new, l * corr + jnp.sum(pexp, axis=-1, keepdims=True)

    init = (jnp.zeros((rows, rank), jnp.float32),
            jnp.full((rows, 1), NEG_INF, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32))
    # a walk has a block at least, so every row's l counts a token
    acc, _, l = jax.lax.fori_loop(0, n_blocks, body, init)
    half_ref[0] = jax.lax.rem(first_half + n_blocks, 2)
    o_ref[0] = acc / l


def mla_decode(q_abs, pool, page_tables, positions, valid_lens, *,
               layer_idx, page_size, rank, sm_scale, interpret=None):
    """Absorbed latent attention (ops/mla.py) for ``s`` new queries a
    slot against the latent page pool, whose rows for the SAME tokens
    must already have landed. q_abs (b, s, h, lanes): ``[q_lat | q_pe |
    0]``; pool (pages+1, layers, page_size, lanes): ``[c~ | k_pe | 0]``,
    pad lanes zero in every live row; ``rank``: the lanes of a row that
    are its value (a multiple of 128). A slot's row is walked in blocks
    of ``_MLA_BLOCK_TOKENS`` (no more than the table holds), each
    fetched whole: every entry of ``page_tables`` is read as a page of
    the pool, so the entries past a slot's live pages name one that may
    be read (the garbage page, inference/paging.py). Returns fp32
    ctx_lat (b, s, h, rank). One chip: the pool is replicated on a
    mesh."""
    if interpret is None:
        interpret = default_interpret()
    b, s, h, lanes = q_abs.shape
    if pool.shape[2:] != (page_size, lanes) or rank % 128 or lanes % 128:
        raise ValueError(
            "mla_decode wants a pool (pages+1, layers, page_size {}, "
            "lanes {}) and whole-lane rank, got {} and rank {}".format(
                page_size, lanes, pool.shape, rank))
    rows = s * h
    max_pages = page_tables.shape[1]
    block = max(1, min(max_pages, _MLA_BLOCK_TOKENS // page_size))
    window = max_pages * page_size
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, rows, lanes), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, rows, rank), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block * page_size, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ])
    kernel = functools.partial(
        _mla_kernel, layer_idx=layer_idx, page_size=page_size, heads=h,
        rank=rank, sm_scale=sm_scale, seq=s, block=block,
        max_pages=max_pages)
    cost = pl.CostEstimate(
        flops=2 * b * rows * window * (lanes + rank),
        bytes_accessed=(q_abs.size * q_abs.dtype.itemsize
                        + b * window * lanes * pool.dtype.itemsize
                        + b * rows * rank * 4),
        transcendentals=b * rows * window)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, rank), jnp.float32),
        cost_estimate=cost, interpret=interpret,
        # a slot's first block is fetched during the slot before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="mla_decode",
    )(page_tables.astype(jnp.int32).reshape(-1),
      positions.astype(jnp.int32), valid_lens.astype(jnp.int32),
      q_abs.reshape(b, rows, lanes).astype(pool.dtype), pool)
    return out.reshape(b, s, h, rank)


def paged_attention(q, k_pool, v_pool, page_tables, positions, valid_lens,
                    *, layer_idx, page_size, interpret=None, mesh=None,
                    window=None):
    """Paged attention for ``s`` new queries per slot against the pool.
    ``mesh``: the mesh the calling program spans — the kernel then runs
    under a shard_map over it (common.shard_kernel), heads split over
    its ``model`` axis like the pool's packed minor dimension
    (inference/kv_cache.py PAGED_KV_CACHE_SPEC), the rest replicated.

    ``q``: (b, s, h, dh) — the new tokens' queries (cache writes for the
    SAME tokens must already have landed, ``kv_cache.write_tokens``, exactly
    as on the XLA gather path; this kernel replaces only the read side).
    ``k_pool``/``v_pool``: (pages+1, layers, page_size, h*dh), or
    (..., kvh*dh) with ``kvh`` key-value heads each shared by ``h / kvh``
    query heads (grouped-query attention: :func:`_grouped_kernel`);
    ``page_tables``: (b, max_pages) int32; ``positions``/``valid_lens``:
    (b,) int32. ``layer_idx`` is trace-static (the model's python layer
    loop). ``window`` (grouped pools on one chip only): a query sees
    the last ``window`` keys, its own among them. Returns fp32 ctx
    (b, s, h, dh) — with a float32 pool within 1e-5 of the slot
    oracle's dense masked softmax (same contributing entries, online
    accumulation order).
    """
    if interpret is None:
        interpret = default_interpret()
    if window is not None and mesh is not None:
        raise ValueError("a windowed page walk has no mesh form yet")
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
            layer_idx=layer_idx, page_size=page_size, interpret=interpret,
            window=window)
    if window is not None:
        raise ValueError("only the grouped page walk takes a window")
    if k_pool.shape[2:] != (page_size, hd):
        raise ValueError(
            "paged_attention wants pools (pages+1, layers, page_size {}, "
            "heads*d_head {}), got {}".format(page_size, hd, k_pool.shape))
    block = _pages_per_block(page_tables.shape[1], page_size, hd,
                             k_pool.dtype.itemsize)
    return _walk(q, k_pool, v_pool, page_tables.astype(jnp.int32),
                 positions.astype(jnp.int32), valid_lens.astype(jnp.int32),
                 jnp.full((1,), layer_idx, jnp.int32), page_size=page_size,
                 block=block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("page_size", "block",
                                             "interpret"))
def _walk(q, k_pool, v_pool, page_tables, positions, valid_lens, layer, *,
          page_size, block, interpret):
    """:func:`_kernel` over ``block`` pages a turn. The layer rides in
    scalar memory and the call is a jitted function, so a program's
    layers share ONE traced and lowered kernel (as ``kv_page_write``'s
    do): GPT-2's decode program holds 24 calls, and with the layer static
    each was traced and lowered on its own, 7 s of every start with the
    parent's kernel and 11 s with a block's 64 unrolled starts (my chip
    runs, PR 55)."""
    b, s, h, dh = q.shape
    hd = h * dh
    max_pages = page_tables.shape[1]
    full_window = max_pages * page_size
    itemsize = k_pool.dtype.itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, s, hd), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, s, hd), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block * page_size, hd), k_pool.dtype),
            pltpu.VMEM((2, block * page_size, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ])
    kernel = functools.partial(
        _kernel, page_size=page_size, num_heads=h, d_head=dh,
        sm_scale=1.0 / math.sqrt(dh), seq=s, block=block,
        max_pages=max_pages)
    # flops pinned to the dense math over the full logical window (qk^T
    # + p@v), the same count the XLA gather path's dots report — keeps
    # the cost-analysis pricing seam (telemetry/programs.py) honest.
    cost = pl.CostEstimate(
        flops=4 * b * s * full_window * hd,
        bytes_accessed=(q.size * q.dtype.itemsize
                        + 2 * b * full_window * hd * itemsize
                        + b * s * hd * 4),
        transcendentals=b * s * full_window * h)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, hd), jnp.float32),
        cost_estimate=cost,
        interpret=interpret,
        # a slot's first block is fetched during the live slot before it;
        # the buffers' bytes twice over, beside the 16 MiB a call has
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(16 << 20)
            + 8 * block * page_size * hd * itemsize),
        name="paged_attention",
    )(layer, page_tables.reshape(-1), positions, valid_lens,
      q.reshape(b, s, hd), k_pool, v_pool)
    return out.reshape(b, s, h, dh)
