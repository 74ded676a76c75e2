"""Pallas chunk attention: a prefill chunk's queries over the pages of
one group, a block of keys at a time, the block's scores never leaving
VMEM.

The XLA form (``ops/chunk_attention.py::paged_blocked_attention``, which
stays the CPU path and the oracle) is a loop whose every turn gathers a
block of keys and values by the page table, writes the float32 scores of
ALL the chunk's queries against it to HBM (134 MB at 2,048 queries x 32
heads x 512 keys) and passes over that array four times: about 1 ms a
block where its two matmuls cost the MXU 0.09 (PERF.md section 5, PR 42).
Here the grid is (slot, query tile). A tile is ``tq`` queries x the
``group`` query heads of a key-value head as the rows of one matmul (rows
ordered (query, head of the group), ``_grouped_paged_attention``'s
layout) against a block of ``tk`` keys that streams HBM -> VMEM page by
page, one ``pltpu.make_async_copy`` a page and pool by the slot's row of
the page table in scalar memory, into a double buffer (the next block's
copies are in flight while this one is on the MXU); every key-value head
folds the block into its float32 accumulator (flash-attention's
recurrence) before the next block is waited for.

What a tile does not do:

* it visits only the blocks that hold a key one of its queries can see:
  table positions ``[max(0, q0 - window + 1), min(q1, live)]`` for queries
  ``[q0, q1]`` (from 0 without a window), so a sliding layer's tile walks
  at most ``(window + tq) / tk + 1`` blocks whatever the context, and a
  tile wholly past ``valid_lens`` fetches nothing and writes zeros. The
  walk's length is data (``positions``, ``valid_lens``): one program a
  bucket and kind of layer, none a context length;
* it masks only edge blocks: a block whose every key every query of the
  tile sees (``k_hi <= q0``, which is no later than ``live`` in a tile
  that walks at all, and ``q1 - k_lo < window``) takes a body without
  iotas, compares and selects.

Tiles come from the shapes (:func:`tiles`): ``tk`` is what
``_KV_BLOCK_VMEM_BYTES`` holds of K and V double-buffered at the pool's
lanes and itemsize (half a window at most), ``tq`` what
``_TILE_VMEM_BYTES`` holds of a tile's queries, accumulator and
statistics, ``sub`` (the rows a turn of the tile's inner loop scores:
the body is compiled once whatever the tile holds) what
``_TURN_SCORES_BYTES`` holds of float32 scores against a block, and
``vmem_limit_bytes`` is counted from the three. The layer is DATA (a
scalar in SMEM, as ``kv_page_write`` carries it) and the call is jitted,
so a program's layers of one kind share ONE traced and lowered kernel.

Masking contract and precision are the page walk's
(ops/pallas/paged_attention.py) and the XLA loop's: ``k_pos <= q_pos``,
with a window ``q_pos - k_pos < window``, nothing past the live length,
the value side ZEROED there (a recycled page may hold NaN), a query that
sees no key gives zeros; K and V enter the MXU in the pool's dtype, the
queries cast to it, the scale multiplies the float32 scores, the running
max, sum and accumulator are float32, the weights enter the second matmul
in the values' dtype. Positions are the TABLE's (``positions`` counts from
the table's column 0, as a sliding table's base moves).

Off-TPU the kernel runs under the Pallas interpreter, the tests' vehicle;
a serving engine there keeps the XLA loop (``auto``).
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..chunk_attention import NEG_INF
from .common import default_interpret

# VMEM a block of keys and values may take (two pools, each
# double-buffered): 1,024 keys at 4 key-value heads of 128 bf16 lanes.
# What a block costs beside its matmuls is paid a ROW of scores (the
# running max and sum, their lane reductions and broadcasts), so a
# longer row is cheaper a key: 6.4 ms at 1,024 keys where 512 take 8.3
# (a chunk of 2,048 at 22,528 in a full layer; my chip runs, PR 43).
_KV_BLOCK_VMEM_BYTES = 4 << 20
# VMEM a tile's resident arrays may take: its queries and its float32
# accumulator (both double-buffered by the pipeline), the running max
# and sum (a 128-lane tile a row each): 256 queries x 32 heads of 128.
_TILE_VMEM_BYTES = 24 << 20
# float32 scores a turn of the rows' loop holds against a block: 512
# rows x 1,024 keys. A block's keys and values are the MXU's latched
# operand, so more rows a turn are fewer latches a row (256 rows: 10.5
# ms where 1,024 take 8.3, at 512 keys), until a turn's arrays outgrow
# what the compiler keeps close (2,048 rows: 11.1 ms), and its compile
# time grows with them (4 s a kernel at 256 rows, 10 at 1,024, 17-25 at
# 2,048).
_TURN_SCORES_BYTES = 2 << 20


def _largest(n, fit, unit):
    """The largest divisor of ``n`` that is at most ``fit`` and a
    multiple of ``unit``; ``n`` where there is none."""
    whole = [t for t in range(unit, min(n, fit) + 1, unit) if n % t == 0]
    return whole[-1] if whole else n


def _query_bytes(group, d_head, lanes, itemsize):
    """VMEM a query keeps resident in its tile, every head: q and the
    float32 accumulator (both double-buffered by the pipeline), the
    running max and sum (a 128-lane tile a row each)."""
    padded = -(-d_head // 128) * 128
    return lanes // d_head * group * (2 * padded * itemsize + 2 * padded * 4
                                      + 2 * 128 * 4)


def _vmem_limit(tq, tk, sub, group, d_head, lanes, itemsize):
    """``vmem_limit_bytes`` for such tiles: twice what is counted (the
    tile, the K and V double buffers, a turn's scores, weights and what
    lies between them), 32 MiB at least, 100 of the chip's 128 at most."""
    used = tq * _query_bytes(group, d_head, lanes, itemsize) \
        + 4 * tk * lanes * itemsize + 8 * sub * tk * 4
    return min(max(2 * used, 32 << 20), 100 << 20)


def tiles(s, group, d_head, lanes, itemsize, table_tokens, page_size,
          window=None):
    """-> ``(tq, tk, sub)`` for a chunk of ``s`` queries whose ``group``
    heads share a key-value head of ``d_head`` lanes, over pools of
    ``lanes`` lanes a token and a table of ``table_tokens`` positions.
    ``tk``: keys a block, whole pages, no more than the table has nor
    than half a ``window`` (a tile sees ``window + tq`` keys and walks
    whole blocks: at 1,024 keys a block a sliding layer's chunk took
    0.75 ms where 512 take 0.61); ``tq``: queries a tile, a divisor of
    ``s``; ``sub``: rows (query, head of the group) a turn of a tile's
    loop, whole queries and whole sublane tiles of the pool's dtype."""
    pages = max(1, _KV_BLOCK_VMEM_BYTES // (4 * page_size * lanes * itemsize))
    if window is not None:
        pages = min(pages, max(1, window // (2 * page_size)))
    tk = min(pages, -(-table_tokens // page_size)) * page_size
    sublanes = 8 * 4 // itemsize
    fit = _TILE_VMEM_BYTES // _query_bytes(group, d_head, lanes, itemsize)
    tq = _largest(s, max(1, fit), sublanes // math.gcd(sublanes, group))
    sub = _largest(tq * group, max(1, _TURN_SCORES_BYTES // (4 * tk)),
                   group * sublanes // math.gcd(sublanes, group))
    return tq, tk, sub


def _kernel(pos_ref, vlen_ref, layer_ref, pt_ref, q_ref, k_pool_ref,
            v_pool_ref, o_ref, k_buf, v_buf, m_ref, l_ref, k_sem, v_sem, *,
            page_size, kv_heads, group, d_head, sm_scale, tq, tk, sub,
            window):
    """One tile of one slot's chunk. In SMEM: pos_ref / vlen_ref (b,)
    and layer_ref (1,), scalar prefetch; pt_ref (1, 1, max_pages), the
    slot's own row of the page table. q_ref (1, kv_heads, tq * group,
    d_head), rows ordered (query, head of the group); the pools (pages
    + 1, layers, page_size, kv_heads * d_head) left in HBM; o_ref like
    q_ref, float32: the accumulator, normalised at the end. k/v_buf (2,
    tk, kv_heads * d_head), one DMA semaphore a half; m_ref / l_ref
    (kv_heads, tq * group, 1) float32."""
    # non-negative ints throughout: ``lax.div`` / ``rem`` stand for
    # ``//`` / ``%``, which lower through ``sign`` (PERF.md, PR 33)
    i, t = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    pos = pos_ref[i]
    live = pos + vlen_ref[i] - 1           # last live table position
    n_pages = jnp.minimum(jax.lax.div(jnp.maximum(live, 0), page_size) + 1,
                          pt_ref.shape[2])
    q0 = pos + t * tq
    q1 = q0 + tq - 1
    first_key = 0 if window is None else jnp.maximum(q0 - window + 1, 0)
    c_lo = jax.lax.div(first_key, tk)
    c_hi = jax.lax.div(jnp.maximum(jnp.minimum(q1, live), 0), tk)
    # a tile wholly past the live length walks nothing
    n_blocks = jnp.where(q0 <= live, c_hi - c_lo + 1, 0)
    rows, per_block = tq * group, tk // page_size

    def transfer(c, half, start):
        # a block's last pages may lie past the live length: no copy,
        # and what the buffer holds there is masked below
        first = c * per_block

        def page(j, carry):
            phys = pt_ref[0, 0, first + j]
            dst = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            for pool, buf, sem in ((k_pool_ref, k_buf, k_sem),
                                   (v_pool_ref, v_buf, v_sem)):
                copy = pltpu.make_async_copy(
                    pool.at[phys, layer], buf.at[half, dst], sem.at[half])
                copy.start() if start else copy.wait()
            return carry

        jax.lax.fori_loop(0, jnp.clip(n_pages - first, 0, per_block),
                          page, 0)

    @pl.when(n_blocks > 0)
    def _first_block():
        transfer(c_lo, 0, True)

    o_ref[...] = jnp.zeros_like(o_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def fold(half, k_lo, masked):
        """The block in ``k/v_buf[half]``, keys from table position
        ``k_lo``, into every key-value head's accumulator, ``sub`` rows
        of the tile a turn (a loop, so that the body is compiled once
        whatever the tile holds)."""
        if masked:
            # q_pos - k_pos = ahead - (k_lo - q0) for the tile's first
            # rows: the block's, the tile's and the turn's places stay
            # on the scalar side of every compare
            col = jax.lax.broadcasted_iota(jnp.int32, (sub, tk), 1)
            ahead = jax.lax.div(
                jax.lax.broadcasted_iota(jnp.int32, (sub, tk), 0),
                group) - col
            alive = col <= live - k_lo
            token = jax.lax.broadcasted_iota(jnp.int32, (tk, 1), 0)
        for h in range(kv_heads):
            sl = slice(h * d_head, (h + 1) * d_head)
            k_h, v_h = k_buf[half, :, sl], v_buf[half, :, sl]
            if masked:
                v_h = jnp.where(token <= live - k_lo, v_h,
                                jnp.zeros_like(v_h))

            def rows_turn(r, carry):
                at = pl.ds(pl.multiple_of(r * sub, sub), sub)
                scores = jax.lax.dot_general(
                    q_ref[0, h, at, :], k_h, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                if masked:
                    behind = k_lo - q0 - r * (sub // group)
                    mask = jnp.logical_and(ahead >= behind, alive)
                    if window is not None:
                        mask = jnp.logical_and(mask, ahead < behind + window)
                    scores = jnp.where(mask, scores, NEG_INF)
                m = m_ref[h, at, :]
                m_new = jnp.maximum(
                    m, jnp.max(scores, axis=-1, keepdims=True))
                pexp = jnp.exp(scores - m_new)
                if masked:
                    # a query may see no key of a block (the window)
                    pexp = jnp.where(mask, pexp, 0.0)
                corr = jnp.exp(m - m_new)
                o_ref[0, h, at, :] = o_ref[0, h, at, :] * corr + \
                    jax.lax.dot_general(
                        pexp.astype(v_h.dtype), v_h, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                l_ref[h, at, :] = l_ref[h, at, :] * corr + \
                    jnp.sum(pexp, axis=-1, keepdims=True)
                m_ref[h, at, :] = m_new
                return carry

            jax.lax.fori_loop(0, rows // sub, rows_turn, 0)

    def body(step, carry):
        c, half = c_lo + step, jax.lax.rem(step, 2)

        @pl.when(step + 1 < n_blocks)
        def _prefetch():
            transfer(c + 1, 1 - half, True)

        transfer(c, half, False)
        k_lo = c * tk
        k_hi = k_lo + tk - 1
        # every key of the block seen by every query of the tile (a
        # tile that walks has q0 <= live)
        interior = k_hi <= q0
        if window is not None:
            interior = jnp.logical_and(interior, q1 - k_lo < window)
        pl.when(interior)(lambda: fold(half, k_lo, False))
        pl.when(jnp.logical_not(interior))(lambda: fold(half, k_lo, True))
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)
    for h in range(kv_heads):
        l = l_ref[h]
        # a padded query past the window of every live key saw none
        o_ref[0, h] = o_ref[0, h] / jnp.where(l == 0.0, 1.0, l)


def chunk_attention(q, k_pool, v_pool, layer_idx, page_tables, positions,
                    valid_lens, page_size, window=None, *, interpret=None):
    """``paged_blocked_attention``'s contract, argument for argument, as
    one kernel: ``s`` new queries a slot over the pages of one group
    (pools ``(pages + 1, layers, page_size, kvh * dh)``), whose rows for
    the same tokens have landed. q (b, s, h, dh), ``h % kvh == 0``;
    ``page_tables`` (b, max_pages); ``positions`` (b,): the first
    query's position in the TABLE; ``valid_lens`` (b,); ``window``: the
    keys a query sees, its own among them, or None for all.
    ``layer_idx`` may be traced. -> ctx (b, s, h, dh) float32."""
    if interpret is None:
        interpret = default_interpret()
    dh = q.shape[3]
    if k_pool.shape[2] != page_size or k_pool.shape[3] % dh \
            or q.shape[2] % (k_pool.shape[3] // dh):
        raise ValueError(
            "chunk_attention wants pools (pages+1, layers, page_size {}, "
            "kv_heads * d_head {}) whose heads divide the queries' {}, got "
            "{}".format(page_size, dh, q.shape[2], k_pool.shape))
    with jax.named_scope("attn.chunk_blocks"):
        return _call(q.astype(k_pool.dtype), k_pool, v_pool,
                     jnp.full((1,), layer_idx, jnp.int32),
                     page_tables.astype(jnp.int32),
                     positions.astype(jnp.int32),
                     valid_lens.astype(jnp.int32), window=window,
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret", "tile"))
def _call(q, k_pool, v_pool, layer, page_tables, positions, valid_lens, *,
          window, interpret, tile=None):
    """``tile``: ``(tq, tk, sub)`` in the place of :func:`tiles`' (the
    micro-benchmark's sweep, tests/perf/chunk_attention_microbench.py)."""
    b, s, h, dh = q.shape
    page_size, lanes = k_pool.shape[2:]
    kvh = lanes // dh
    group = h // kvh
    max_pages = page_tables.shape[1]
    itemsize = k_pool.dtype.itemsize
    tq, tk, sub = tile or tiles(s, group, dh, lanes, itemsize,
                                max_pages * page_size, page_size, window)
    rows = tq * group
    # rows of one key-value head: (query, head of its group)
    q = q.reshape(b, s, kvh, group, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(b, kvh, s * group, dh)
    block = pl.BlockSpec((1, kvh, rows, dh), lambda i, t, *_: (i, 0, t, 0))
    # the slot's row (b, 1, max_pages): a block's last two dimensions
    # are the array's
    table = pl.BlockSpec((1, 1, max_pages), lambda i, t, *_: (i, 0, 0),
                         memory_space=pltpu.SMEM)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    span = max_pages * page_size if window is None \
        else min(max_pages * page_size, window + tq)
    out = pl.pallas_call(
        functools.partial(
            _kernel, page_size=page_size, kv_heads=kvh, group=group,
            d_head=dh, sm_scale=1.0 / math.sqrt(dh), tq=tq, tk=tk, sub=sub,
            window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, s // tq),
            in_specs=[table, block, anywhere, anywhere],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((2, tk, lanes), k_pool.dtype),
                pltpu.VMEM((2, tk, lanes), v_pool.dtype),
                pltpu.VMEM((kvh, rows, 1), jnp.float32),
                pltpu.VMEM((kvh, rows, 1), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kvh, s * group, dh), jnp.float32),
        # the dense math over what a tile may visit of the table
        cost_estimate=pl.CostEstimate(
            flops=4 * b * s * span * h * dh,
            bytes_accessed=(q.size * q.dtype.itemsize + b * s * h * dh * 4
                            + 2 * b * (s // tq) * span * lanes
                            * k_pool.dtype.itemsize),
            transcendentals=b * s * span * h),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit(
            tq, tk, sub, group, dh, lanes, itemsize)),
        interpret=interpret,
        name="chunk_attention",
    )(positions, valid_lens, layer, page_tables[:, None, :], q, k_pool,
      v_pool)
    return out.reshape(b, kvh, s, group, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(b, s, h, dh)
