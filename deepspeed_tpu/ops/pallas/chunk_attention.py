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
ordered (query, head of the group), each key-value head against its own
``d_head`` lanes of the block: the grouped page walk's layout until PR 46,
which since folds every head in one matmul, block-diagonal over the
packed lanes; heads NARROWER than a lane tile, GPT-2's and LFM2's 64, are
folded that way here too, the heads of one 128-lane tile at a time, and
their queries and result are the pool's packed rows: see ``_kernel``)
against a block of ``tk`` keys that
streams HBM -> VMEM into a double buffer, one ``pltpu.make_async_copy`` a
page and pool by the slot's row of the page table in scalar memory: the
starts of ALL the block's pages in a straight line behind ONE wait a pool
for the block's bytes (the next block's copies are in flight while this
one is on the MXU); every key-value head folds the block into its float32
accumulator (flash-attention's recurrence) before the next block is
waited for. A block's pages past the live length are fetched like the
others, by the table's own entries and, past its last column, by the
garbage page the wrapper pads the row with: what they hold is masked, and
the value side zeroed, as a partly live page's dead rows always were.

What a tile does not do:

* it visits only the keys one of its queries can see: table positions
  from the PAGE of ``max(0, q0 - window + 1)`` (0 without a window) to
  ``min(q1, live)`` for queries ``[q0, q1]``, in whole blocks from
  there, so a sliding layer's tile past its window walks ``window / tk``
  blocks and its own ``tq`` keys, whatever the context, and a tile wholly
  past ``valid_lens`` fetches nothing and writes zeros. Where the last
  block holds no more than the tile's own keys (a quarter of a block or
  less: :func:`_short_block`) it is fetched and folded at that length.
  A table that is ONE block (GPT-2's 1,024 positions) is fetched and
  folded at the length the tile's queries see, in steps of a quarter of
  it (:func:`_last_widths`), by a plain softmax: no block comes after,
  so there is no running max or sum to carry and no accumulator to
  rescale, and what a fold costs a ROW (section "tiles" below) is paid
  once a tile whatever it skips.
  The walk's length is data (``positions``, ``valid_lens``): one program
  a bucket and kind of layer, none a context length;
* it masks only edge blocks: a block whose every key every query of the
  tile sees (``k_hi <= q0``, which is no later than ``live`` in a tile
  that walks at all, and ``q1 - k_lo < window``) takes a body without
  iotas, compares and selects; an edge block's mask is made once a turn
  of the rows' loop and serves every key-value head.

Tiles come from the shapes (:func:`tiles`): ``tk`` is ``_BLOCK_KEYS``
(what ``_KV_BLOCK_VMEM_BYTES`` holds of K and V double-buffered at the
pool's lanes and itemsize, if that is less; half a window at most),
``tq`` the queries whose rows of one key-value head are ``_TILE_ROWS``
(what ``_TILE_VMEM_BYTES`` holds of a tile's queries, accumulator and
statistics, if that is less), ``sub`` (the rows a turn of the tile's
inner loop scores: the body is compiled once whatever the tile holds)
what ``_TURN_SCORES_BYTES`` holds of float32 scores against a block, and
``vmem_limit_bytes`` is counted from the three: 256 queries against 1,024
keys (512 under a window of 1,024) at Mellum's 8 heads a key-value head
over 512 lanes, 128 against 1,024 at Command A+'s 16 over 1,024, 256
against the one block of 1,024 at GPT-2's 16 heads of 64. The
layer is DATA (a scalar in SMEM, as ``kv_page_write`` carries it) and the
call is jitted, so a program's layers of one kind share ONE traced and
lowered kernel.

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

# Keys a block, at most, and the VMEM its keys and values may take (two
# pools, each double-buffered). What a block costs beside its matmuls is
# paid a ROW of scores (the running max and sum, their lane reductions
# and broadcasts, the accumulator's rescaling), so a longer row is
# cheaper a key: at ide's shape (8 heads a key-value head, 512 lanes) a
# chunk of 2,048 at 22,528 in a full layer took 6.4 ms at 1,024 keys
# where 512 take 8.3 (my chip runs, PR 43); at rag's (16 heads, 1,024
# lanes) a chunk at 8,192 took 10.7 ms at 1,024 where 512 take 13.4
# (PR 53: the compiler's schedule of a turn of 512 K scores is 2,475
# bundles at 512 rows x 1,024 keys and 3,341 at 1,024 x 512, where its
# matmuls are 2,048).
_BLOCK_KEYS = 1024
_KV_BLOCK_VMEM_BYTES = 8 << 20
# A table that is ONE block (GPT-2's 1,024 positions) is folded at the
# length a tile's queries can see, in steps of this share of the block,
# and a tile holds no more queries than a step has keys
# (:func:`_last_widths`). Cutting the BLOCK finer skips the same work and
# pays for it a row. At GPT-2's shape (16 heads of 64, 1,024 lanes), a
# bucket of 1,024 with 728 real rows from position 0, device us a call
# (my chip runs, PR 57): one tile against one block, the rule of longer
# tables, 79.2 (67.0 fetched at the live length); blocks of 256 keys
# under tiles of 256 queries 84.7 (6 of 16 block pairs, 13 us each: the
# compiler's schedule of a turn of 512 rows is 1,110 bundles whatever
# the keys plus 355 every 256 keys); blocks of 512 71.1; the one block
# at steps of 256 keys under tiles of 256 queries 57.3 with the running
# statistics and 38.8 without (tiles of 128: 51.7, of 512: 50.7; 128
# rows a turn 40.9). The whole bucket real: 59.1 for 79.2.
_ONE_BLOCK_STEPS = 4
# Rows of ONE key-value head a tile holds (queries x the heads of a
# group), at most, and the VMEM the tile's resident arrays may take: its
# queries and its float32 accumulator (both double-buffered by the
# pipeline), the running max and sum (a 128-lane tile a row each). A
# block's fetch (two DMA starts a page) and an edge block's iotas are
# paid a tile, whatever rows it holds: 2,048 rows are 256 queries at 8
# heads a group (ide) and 128 at 16 (rag: 40 MiB over 8 key-value
# heads), where the byte budget alone gave rag's tile 64 queries.
_TILE_ROWS = 2048
_TILE_VMEM_BYTES = 48 << 20
# float32 scores a turn of the rows' loop holds against a block: 512
# rows x 1,024 keys. A block's keys and values are the MXU's latched
# operand, so more rows a turn are fewer latches a row (256 rows: 10.5
# ms where 1,024 take 8.3, at 512 keys and ide's shape), until a turn's
# arrays outgrow what the compiler keeps close (2,048 rows: 11.1 ms),
# and its compile time grows with them (4 s a kernel at 256 rows, 10 at
# 1,024, 17-25 at 2,048; at rag's 8 key-value heads 11 s at 512 rows x
# 1,024 keys and 19 at 1,024 x 1,024, which also ran slower: 12.8 ms for
# 10.7).
_TURN_SCORES_BYTES = 2 << 20


# Key-value heads a turn of the heads' loop folds in a straight line
# (the loop's index picks their 128-lane slices of the block). Every head
# unrolled, Command A+'s 8 made a kernel of 71,000 bundles that the
# chip's compiler took 15 s over, and a third body (the short block)
# then cost more than it saved: 5.19 ms a sliding layer's chunk where
# the loop reads 5.11 (and 4 s to compile); one head a turn loses what
# the straight line overlaps between heads (5.17; at Mellum's 4 heads
# 0.605 ms where all 4 in line read 0.579) (my chip runs, PR 53). The
# same unit is a lane tile of heads narrower than one: at GPT-2's 8 lane
# tiles of two heads, four in line read 39.0 us a chunk, two 40.5, one
# 41.9 (and compile in 5.1 / 3.2 / 1.9 s; my chip runs, PR 57).
_HEADS_TOGETHER = 4


# Pages whose DMA starts (one a pool) a turn of a block's fetch issues in
# a straight line: 10 bundles a start where a loop turn a page took 22 a
# start and 7 a wait (the compiler's bundle dump, PR 53). Every page of
# a block in line (64) reads no faster, and TRACING its 256 starts a
# fetch made a prefill program's trace 6.5 s where the parent's was 2.5
# (rag's traced runs, PR 53: `setup_s` +13%).
_PAGES_IN_LINE = 8


def _largest(n, fit, unit):
    """The largest divisor of ``n`` that is at most ``fit`` and a
    multiple of ``unit``; ``n`` where there is none."""
    whole = [t for t in range(unit, min(n, fit) + 1, unit) if n % t == 0]
    return whole[-1] if whole else n


def _heads_a_lane_tile(d_head, lanes):
    """Key-value heads that share one 128-lane tile of a pool row: 1 for
    heads of whole lane tiles (the kernel slices a head's own lanes), 2
    at GPT-2's and LFM2's 64. Such heads are folded a lane tile at a
    time (:func:`_kernel`), which wants the row in whole lane tiles;
    rows that are not (the interpreter's small shapes) keep a slice a
    head."""
    if d_head % 128 and 128 % d_head == 0 and lanes % 128 == 0:
        return 128 // d_head
    return 1


def _query_bytes(group, d_head, lanes, itemsize):
    """VMEM a query keeps resident in its tile, every head: q and the
    float32 accumulator (both double-buffered by the pipeline), the
    running max and sum (a 128-lane tile a row each). Heads that share a
    lane tile: q and the result in packed rows (double-buffered, the
    result no wider than float32), the float32 accumulator once (a
    scratch), the statistics as ever."""
    heads = lanes // d_head * group
    if _heads_a_lane_tile(d_head, lanes) > 1:
        return group * lanes * (2 * itemsize + 2 * 4 + 4) \
            + heads * 2 * 128 * 4
    padded = -(-d_head // 128) * 128
    return heads * (2 * padded * itemsize + 2 * padded * 4 + 2 * 128 * 4)


def _vmem_limit(tq, tk, sub, group, d_head, lanes, itemsize):
    """``vmem_limit_bytes`` for such tiles: twice what is counted (the
    tile, the K and V double buffers, a turn's scores, weights and what
    lies between them: ``sub`` rows of every head of a lane tile), 32
    MiB at least, 100 of the chip's 128 at most."""
    used = tq * _query_bytes(group, d_head, lanes, itemsize) \
        + 4 * tk * lanes * itemsize \
        + 8 * sub * _heads_a_lane_tile(d_head, lanes) * tk * 4
    return min(max(2 * used, 32 << 20), 100 << 20)


def tiles(s, group, d_head, lanes, itemsize, table_tokens, page_size,
          window=None):
    """-> ``(tq, tk, sub)`` for a chunk of ``s`` queries whose ``group``
    heads share a key-value head of ``d_head`` lanes, over pools of
    ``lanes`` lanes a token and a table of ``table_tokens`` positions.
    ``tk``: keys a block, whole pages, ``_BLOCK_KEYS`` if their double
    buffers fit ``_KV_BLOCK_VMEM_BYTES``, no more than the table has nor
    than half a ``window`` (a tile sees ``window + tq`` keys and walks
    whole blocks: at a window of 1,024 and 1,024 keys a block a sliding
    layer's chunk took 0.75 ms where 512 take 0.61, PR 43; at a window
    of 4,096 the half is 2,048 and the block 1,024: 5.4 ms where 512
    take 6.7, PR 53); ``tq``: queries a tile, a divisor of ``s``,
    ``_TILE_ROWS`` rows of a key-value head if they fit
    ``_TILE_VMEM_BYTES``, and where the table is one block no more than
    the step of the lengths it is folded at (:func:`_last_widths`);
    ``sub``: rows (query, head of the group) a turn of a tile's loop,
    whole queries and whole sublane tiles of the pool's dtype (heads
    that share a lane tile score ``sub`` rows EACH a turn)."""
    pages = max(1, min(_BLOCK_KEYS // page_size, _KV_BLOCK_VMEM_BYTES
                       // (4 * page_size * lanes * itemsize)))
    if window is not None:
        pages = min(pages, max(1, window // (2 * page_size)))
    tk = min(pages, -(-table_tokens // page_size)) * page_size
    sublanes = 8 * 4 // itemsize
    fit = min(_TILE_ROWS // group, _TILE_VMEM_BYTES
              // _query_bytes(group, d_head, lanes, itemsize))
    if table_tokens <= tk:
        fit = min(fit, _one_block_step(tk, page_size))
    tq = _largest(s, max(1, fit), sublanes // math.gcd(sublanes, group))
    together = _heads_a_lane_tile(d_head, lanes)
    sub = _largest(tq * group,
                   max(1, _TURN_SCORES_BYTES // (4 * tk * together)),
                   group * sublanes // math.gcd(sublanes, group))
    return tq, tk, sub


def _short_block(tq, tk, page_size):
    """Keys of a tile's short last block, or 0 for none. A walk ends
    with the tile's own ``tq`` keys (and up to a page before them where
    it starts at a window's first page): where those are the whole of
    its last block, the block is fetched and folded at this length, in
    whole lane tiles of scores, if that is a quarter of a block or
    less."""
    short = -(-(tq + page_size - 1) // 128) * 128
    return short if 4 * short <= tk and short % page_size == 0 else 0


def _one_block_step(tk, page_size):
    """The step of the lengths a table of one block is folded at: a
    ``_ONE_BLOCK_STEPS``-th of it in whole pages and whole lane tiles of
    scores."""
    unit = math.lcm(128, page_size)
    return -(-tk // (_ONE_BLOCK_STEPS * unit)) * unit


def _last_widths(tq, tk, page_size, table_tokens):
    """Keys a walk's LAST block may be fetched and folded at instead of
    the ``tk`` a block holds: ascending, each less than ``tk``; the
    kernel takes the first that holds the keys the block has left.
    A table of one block: the multiples of :func:`_one_block_step`, so
    that a tile's only block is as long as its last query sees (or the
    live length allows) to a step: the causal triangle's upper half and
    a bucket's padding cost a step at most, and the per-row work of a
    fold is paid once a tile whatever it skips. A longer table: the
    short block of :func:`_short_block`, if any."""
    if table_tokens <= tk:
        step = _one_block_step(tk, page_size)
        return tuple(range(step, tk, step))
    short = _short_block(tq, tk, page_size)
    return (short,) if short else ()


def _kernel(pos_ref, vlen_ref, layer_ref, pt_ref, q_ref, k_pool_ref,
            v_pool_ref, o_ref, k_buf, v_buf, k_sem, v_sem, m_ref=None,
            l_ref=None, acc_ref=None, *, page_size, kv_heads, group, d_head,
            sm_scale, tq, tk, sub, widths, single, window):
    """One tile of one slot's chunk. In SMEM: pos_ref / vlen_ref (b,)
    and layer_ref (1,), scalar prefetch; pt_ref (1, 1, columns), the
    slot's own row of the page table, a block of the garbage page past
    its own columns. q_ref (1, kv_heads, tq * group, d_head), rows
    ordered (query, head of the group); the pools (pages + 1, layers,
    page_size, kv_heads * d_head) left in HBM; o_ref like q_ref,
    float32: the accumulator, normalised at the end. k/v_buf (2, tk,
    kv_heads * d_head), one DMA semaphore a half; m_ref / l_ref
    (kv_heads, tq * group, 1) float32. ``widths``: the keys a walk's
    last block may hold short of ``tk`` (:func:`_last_widths`).
    ``single``: the table is one block, so a tile's walk is one block
    or none: its softmax is the plain one (no running statistics, no
    accumulator to rescale: what they cost is paid a row of scores and
    a row here is short), written to o_ref normalised; there is no
    m_ref, l_ref or acc_ref then.

    Heads narrower than a lane tile (``d_head`` divides 128 and the
    pool's row is whole lane tiles): q_ref and o_ref are the POOL's
    packed rows, (1, tq * group, kv_heads * d_head), o_ref in the
    caller's dtype; acc_ref (tq * group, kv_heads * d_head) float32 is
    the accumulator. The ``n`` heads of a 128-lane tile are
    folded together, block-diagonal over the tile's lanes (the grouped
    page walk's way, PR 46): the first matmul takes ``n`` copies of the
    turn's rows, copy ``j`` keeping head ``j``'s ``d_head`` lanes and
    zeros in the others, against the tile's 128 lanes of K (an aligned
    slice; a head's own lanes at a traced ``h * d_head`` are half a lane
    tile, which the chip's compiler refuses), so rows ``[j * sub, (j +
    1) * sub)`` of the scores are head ``j``'s; the second gives ``(n *
    sub, 128)`` of which each copy keeps its own head's lanes: a select,
    no lane moves, puts the tile's heads back as one packed row. The
    MXU does what ``n`` matmuls a ``1 / n`` filled would. m_ref / l_ref
    are then (kv_heads / n, n * tq * group, 1), a turn's ``n * sub``
    rows together."""
    # non-negative ints throughout: ``lax.div`` / ``rem`` stand for
    # ``//`` / ``%``, which lower through ``sign`` (PERF.md, PR 33)
    i, t = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    pos = pos_ref[i]
    live = pos + vlen_ref[i] - 1           # last live table position
    q0 = pos + t * tq
    q1 = q0 + tq - 1
    # the walk starts at the page of the first key the tile's first
    # query sees (column 0 without a window) and ends at the last key
    # its last query sees: whole blocks from there, so a sliding layer's
    # tile past its window walks window / tk blocks and a short one
    first_page = 0 if window is None else \
        jax.lax.div(jnp.maximum(q0 - window + 1, 0), page_size)
    first_key = first_page * page_size
    keys = jnp.maximum(jnp.minimum(q1, live) - first_key + 1, 0)
    whole = jax.lax.div(keys, tk)
    rest = keys - whole * tk
    # a tile wholly past the live length walks nothing
    n_blocks = jnp.where(q0 <= live, whole + (rest > 0), 0)
    rows, per_block = tq * group, tk // page_size
    line = math.gcd(per_block, *(w // page_size for w in widths),
                    _PAGES_IN_LINE)
    # the last block at the first of ``widths`` that holds its keys
    last_at = [jnp.logical_and(rest > below, rest <= width)
               for below, width in zip((0,) + widths, widths)]

    def is_at(step, i):
        return jnp.logical_and(last_at[i], step == n_blocks - 1)

    def is_whole(step):
        return jnp.logical_not(functools.reduce(
            jnp.logical_or, [is_at(step, i) for i in range(len(widths))],
            False))

    def fetch(step, half):
        # ALL the block's pages, ``line`` starts of each pool in a
        # straight line a turn, so that one wait a pool covers them:
        # past the live length the table's own entry or its padding
        # (the garbage page), masked
        first = first_page + step * per_block

        def pages(g, carry):
            base = pl.multiple_of(g * (line * page_size), line * page_size)
            for j in range(line):
                phys = pt_ref[0, 0, first + g * line + j]
                dst = pl.ds(base + j * page_size, page_size)
                for pool, buf, sem in ((k_pool_ref, k_buf, k_sem),
                                       (v_pool_ref, v_buf, v_sem)):
                    pltpu.make_async_copy(
                        pool.at[phys, layer], buf.at[half, dst],
                        sem.at[half]).start()
            return carry

        turns = per_block // line
        for i, width in enumerate(widths):
            turns = jnp.where(is_at(step, i), width // page_size // line,
                              turns)
        jax.lax.fori_loop(0, turns, pages, 0)

    def wait(step, half):
        # ONE wait a pool, for the bytes the block's fetch started
        def block_of(n):
            for buf, sem in ((k_buf, k_sem), (v_buf, v_sem)):
                at = buf.at[half, pl.ds(0, n)]
                pltpu.make_async_copy(at, at, sem.at[half]).wait()

        if widths:
            for i, width in enumerate(widths):
                pl.when(is_at(step, i))(functools.partial(block_of, width))
            pl.when(is_whole(step))(lambda: block_of(tk))
        else:
            block_of(tk)

    @pl.when(n_blocks > 0)
    def _first_block():
        fetch(0, 0)

    n = _heads_a_lane_tile(d_head, kv_heads * d_head)
    packed = n > 1
    acc = o_ref if acc_ref is None else acc_ref

    def own_lanes(x):
        """Of ``n`` copies of ``sub`` rows, (n * sub, 128) or (n * sub,
        1), copy ``j``'s values in head ``j``'s lanes of a 128-lane tile:
        a select, no lane moves."""
        head = jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (sub, 128), 1), d_head)
        out = x[:sub]
        for j in range(1, n):
            out = jnp.where(head == j, x[j * sub:(j + 1) * sub], out)
        return out

    if single:
        @pl.when(n_blocks == 0)
        def _no_block():
            o_ref[...] = jnp.zeros_like(o_ref)
    else:
        acc[...] = jnp.zeros_like(acc)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def alone(scores, v):
        """The plain softmax of a walk's only block -> the weighted
        values, normalised. The running max's first value stands under
        the maximum so that a query that sees no key gives zeros."""
        pexp = jnp.exp(scores - jnp.maximum(
            jnp.max(scores, axis=-1, keepdims=True), NEG_INF))
        total = jnp.sum(pexp, axis=-1, keepdims=True)
        return jax.lax.dot_general(
            pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) \
            * (1.0 / jnp.where(total == 0.0, 1.0, total))

    def fold(half, k_lo, masked, width=tk):
        """The block in ``k/v_buf[half]``, keys from table position
        ``k_lo``, into every key-value head's accumulator, ``sub`` rows
        of the tile a turn (a loop, so that the body is compiled once
        whatever the tile holds). An edge block's mask is made once a
        turn and serves every head. ``width``: the keys it holds (a
        short block's are the buffer's first)."""
        if masked:
            # q_pos - k_pos = ahead - (k_lo - q0) for the tile's first
            # rows: the block's, the tile's and the turn's places stay
            # on the scalar side of every compare
            col = jax.lax.broadcasted_iota(jnp.int32, (n * sub, width), 1)
            row = jax.lax.broadcasted_iota(jnp.int32, (n * sub, width), 0)
            if packed:                 # n copies of the turn's rows
                row = jax.lax.rem(row, sub)
            ahead = jax.lax.div(row, group) - col
            alive = col <= live - k_lo
            token = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)

        def head_turn(h, r, mask):
            at = pl.ds(pl.multiple_of(r * sub, sub), sub)
            sl = pl.ds(pl.multiple_of(h * d_head, d_head), d_head)
            v_h = v_buf[half, :width, sl]
            scores = jax.lax.dot_general(
                q_ref[0, h, at, :], k_buf[half, :width, sl],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if masked:
                v_h = jnp.where(token <= live - k_lo, v_h,
                                jnp.zeros_like(v_h))
                # below the running max's first value: a query that
                # sees no key of the block (the window) weighs none
                scores = jnp.where(mask, scores, 2 * NEG_INF)
            if single:
                o_ref[0, h, at, :] = alone(scores, v_h)
                return
            m = m_ref[h, at, :]
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            pexp = jnp.exp(scores - m_new)
            corr = jnp.exp(m - m_new)
            o_ref[0, h, at, :] = o_ref[0, h, at, :] * corr + \
                jax.lax.dot_general(
                    pexp.astype(v_h.dtype), v_h, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            l_ref[h, at, :] = l_ref[h, at, :] * corr + \
                jnp.sum(pexp, axis=-1, keepdims=True)
            m_ref[h, at, :] = m_new

        def tile_turn(p, r, mask):
            """Lane tile ``p``'s ``n`` heads, ``sub`` rows of each."""
            at = pl.ds(pl.multiple_of(r * sub, sub), sub)
            sl = pl.ds(pl.multiple_of(p * 128, 128), 128)
            stats = pl.ds(pl.multiple_of(r * (n * sub), n * sub), n * sub)
            head = jax.lax.div(
                jax.lax.broadcasted_iota(jnp.int32, (sub, 128), 1), d_head)
            q_t, v_t = q_ref[0, at, sl], v_buf[half, :width, sl]
            scores = jax.lax.dot_general(
                jnp.concatenate([jnp.where(head == j, q_t,
                                           jnp.zeros_like(q_t))
                                 for j in range(n)], axis=0),
                k_buf[half, :width, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if masked:
                v_t = jnp.where(token <= live - k_lo, v_t,
                                jnp.zeros_like(v_t))
                scores = jnp.where(mask, scores, 2 * NEG_INF)
            if single:
                o_ref[0, at, sl] = own_lanes(alone(scores, v_t)) \
                    .astype(o_ref.dtype)
                return
            m = m_ref[p, stats, :]
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            pexp = jnp.exp(scores - m_new)
            corr = jnp.exp(m - m_new)
            new = jax.lax.dot_general(
                pexp.astype(v_t.dtype), v_t, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            l_ref[p, stats, :] = l_ref[p, stats, :] * corr + \
                jnp.sum(pexp, axis=-1, keepdims=True)
            m_ref[p, stats, :] = m_new
            # every copy of the rows keeps its own head's lanes
            acc[at, sl] = acc[at, sl] * own_lanes(corr) + own_lanes(new)

        turns = rows // sub
        units = kv_heads // n          # heads, or lane tiles of n heads
        unit_turn = tile_turn if packed else head_turn
        together = _largest(units, _HEADS_TOGETHER, 1)

        def heads_turn(g, r, mask, carry):
            for j in range(together):
                unit_turn(g * together + j, r, mask)
            return carry

        def rows_turn(r, carry):       # an edge block: the heads inside
            behind = k_lo - q0 - r * (sub // group)
            mask = jnp.logical_and(ahead >= behind, alive)
            if window is not None:
                mask = jnp.logical_and(mask, ahead < behind + window)
            return jax.lax.fori_loop(
                0, units // together,
                lambda g, c: heads_turn(g, r, mask, c), carry)

        if masked:
            jax.lax.fori_loop(0, turns, rows_turn, 0)
        else:
            jax.lax.fori_loop(
                0, units // together * turns,
                lambda x, c: heads_turn(jax.lax.div(x, turns),
                                        jax.lax.rem(x, turns), None, c), 0)

    def body(step, carry):
        half = jax.lax.rem(step, 2)

        @pl.when(step + 1 < n_blocks)
        def _prefetch():
            fetch(step + 1, 1 - half)

        wait(step, half)
        k_lo = first_key + step * tk
        # every key of the block seen by every query of the tile (a
        # tile that walks has q0 <= live)
        interior = k_lo + tk - 1 <= q0
        if window is not None:
            interior = jnp.logical_and(interior, q1 - k_lo < window)
        edge = jnp.logical_not(interior)
        if widths:
            for i, width in enumerate(widths):
                pl.when(is_at(step, i))(
                    functools.partial(fold, half, k_lo, True, width))
            # (never interior: such a block ends at the walk's last key)
            edge = jnp.logical_and(edge, is_whole(step))
        pl.when(interior)(lambda: fold(half, k_lo, False))
        pl.when(edge)(lambda: fold(half, k_lo, True))
        return carry

    if single:
        @pl.when(n_blocks > 0)
        def _only_block():
            wait(0, 0)
            for i, width in enumerate(widths):
                pl.when(is_at(0, i))(
                    functools.partial(fold, 0, first_key, True, width))
            pl.when(is_whole(0))(lambda: fold(0, first_key, True))
        return
    jax.lax.fori_loop(0, n_blocks, body, 0)
    if packed:
        for p in range(kv_heads // n):
            for r in range(rows // sub):
                at = slice(r * sub, (r + 1) * sub)
                sl = slice(p * 128, (p + 1) * 128)
                l = own_lanes(l_ref[p, r * n * sub:(r + 1) * n * sub])
                o_ref[0, at, sl] = (acc[at, sl] / jnp.where(
                    l == 0.0, 1.0, l)).astype(o_ref.dtype)
        return
    for h in range(kv_heads):
        l = l_ref[h]
        # a padded query past the window of every live key saw none
        o_ref[0, h] = o_ref[0, h] / jnp.where(l == 0.0, 1.0, l)


def chunk_attention(q, k_pool, v_pool, layer_idx, page_tables, positions,
                    valid_lens, page_size, window=None, *,
                    out_dtype=jnp.float32, interpret=None):
    """``paged_blocked_attention``'s contract, argument for argument, as
    one kernel: ``s`` new queries a slot over the pages of one group
    (pools ``(pages + 1, layers, page_size, kvh * dh)``), whose rows for
    the same tokens have landed. q (b, s, h, dh), ``h % kvh == 0``;
    ``page_tables`` (b, max_pages); ``positions`` (b,): the first
    query's position in the TABLE; ``valid_lens`` (b,); ``window``: the
    keys a query sees, its own among them, or None for all.
    ``layer_idx`` may be traced. -> ctx (b, s, h, dh) in ``out_dtype``
    (heads that share a lane tile leave the kernel in it; at one head a
    key-value head their q and ctx are the pool's packed rows, so a
    caller that reshapes the projection's ``(b, s, h * dh)`` to q and ctx
    back to it moves nothing)."""
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
                     interpret=interpret,
                     out_dtype=jnp.dtype(out_dtype).name)


@functools.partial(jax.jit, static_argnames=("window", "interpret", "tile",
                                             "out_dtype"))
def _call(q, k_pool, v_pool, layer, page_tables, positions, valid_lens, *,
          window, interpret, tile=None, out_dtype="float32"):
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
    n = _heads_a_lane_tile(dh, lanes)
    # a table of one block: a plain softmax, no state between blocks
    single = max_pages * page_size <= tk
    # a block of the garbage page past the table's own columns: a
    # block's fetch takes every page of it
    page_tables = jnp.pad(page_tables, ((0, 0), (0, tk // page_size)))
    if n > 1:
        # the pool's packed rows, (query, head of its group) x (key-value
        # head, d_head): at a group of 1 the projection's own
        q = q.reshape(b, s, kvh, group, dh).transpose(0, 1, 3, 2, 4) \
            .reshape(b, s * group, lanes)
        block = pl.BlockSpec((1, rows, lanes), lambda i, t, *_: (i, t, 0))
        out_shape = jax.ShapeDtypeStruct(q.shape, out_dtype)
        stats = pltpu.VMEM((kvh // n, n * rows, 1), jnp.float32)
        state = [stats, stats, pltpu.VMEM((rows, lanes), jnp.float32)]
    else:
        # rows of one key-value head: (query, head of its group)
        q = q.reshape(b, s, kvh, group, dh).transpose(0, 2, 1, 3, 4) \
            .reshape(b, kvh, s * group, dh)
        block = pl.BlockSpec((1, kvh, rows, dh),
                             lambda i, t, *_: (i, 0, t, 0))
        out_shape = jax.ShapeDtypeStruct(q.shape, jnp.float32)
        stats = pltpu.VMEM((kvh, rows, 1), jnp.float32)
        state = [stats, stats]      # the accumulator is the output block
    # the slot's row (b, 1, max_pages): a block's last two dimensions
    # are the array's
    table = pl.BlockSpec((1, 1, page_tables.shape[1]),
                         lambda i, t, *_: (i, 0, 0),
                         memory_space=pltpu.SMEM)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    span = max_pages * page_size if window is None \
        else min(max_pages * page_size, window + tq)
    out = pl.pallas_call(
        functools.partial(
            _kernel, page_size=page_size, kv_heads=kvh, group=group,
            d_head=dh, sm_scale=1.0 / math.sqrt(dh), tq=tq, tk=tk, sub=sub,
            widths=_last_widths(tq, tk, page_size, max_pages * page_size),
            single=single, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, s // tq),
            in_specs=[table, block, anywhere, anywhere],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((2, tk, lanes), k_pool.dtype),
                pltpu.VMEM((2, tk, lanes), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                # the running max and sum (and accumulator): none where
                # no block comes after a tile's first
            ] + ([] if single else state)),
        out_shape=out_shape,
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
    if n > 1:
        return out.reshape(b, s, group, kvh, dh).transpose(0, 1, 3, 2, 4) \
            .reshape(b, s, h, dh)
    return out.reshape(b, kvh, s, group, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(b, s, h, dh).astype(out_dtype)
