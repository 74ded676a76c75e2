"""The chunk-attention kernel (ops/pallas/chunk_attention.py) under the
Pallas interpreter against its oracle, the XLA loop it stands in for on
the chip (ops/chunk_attention.py::paged_blocked_attention): shuffled
page tables, windows inside a block, across blocks and wider than every
key, chunks that start at 0, inside and past the window, padded rows,
NaN past the live length, tiles that really come from the shapes, and
heads of 64 lanes, two to a lane tile (GPT-2's and LFM2's), over tables
no longer than a block.
The interpreter proves numerics, not compilability: the chip's compiler
has its cases in test_tpu_compile.py."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.chunk_attention import paged_blocked_attention
from deepspeed_tpu.ops.pallas import chunk_attention as kernel_module
from deepspeed_tpu.ops.pallas.chunk_attention import (
    _short_block, _vmem_limit, chunk_attention, tiles)

pytestmark = pytest.mark.pallas

PAGE = 8
# (query heads, key-value heads, d_head): Mellum's group of 8 on 4 heads
# of 128, a smaller shape of group 1, and Command A+'s group of 16 on 8
# heads at a small width
WIDE, NARROW, MANY = (32, 4, 128), (2, 2, 32), (128, 8, 16)
# heads of 64 lanes, two to a 128-lane tile of the pool's row: GPT-2's 16
# over 1,024 lanes at a group of 1, LFM2's 8 over 512 at a group of 4
GPT2_HEADS, LFM2_HEADS = (16, 16, 64), (32, 8, 64)


def _inputs(seed, b, s, shape, max_pages, layers=2, dtype=jnp.float32):
    h, kvh, dh = shape
    rng = np.random.default_rng(seed)
    pages = b * max_pages + 3
    pools = [jnp.asarray(rng.normal(size=(pages + 1, layers, PAGE, kvh * dh)),
                         dtype) for _ in range(2)]
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)), dtype)
    tables = rng.permutation(np.arange(1, pages + 1))[:b * max_pages] \
        .reshape(b, max_pages).astype(np.int32)
    return q, pools, jnp.asarray(tables)


def _both(q, pools, tables, positions, valid, window, tile=None, layer=1):
    positions = jnp.asarray(positions, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    want = paged_blocked_attention(q, *pools, layer, tables, positions,
                                   valid, PAGE, window)
    got = kernel_module._call(
        q, *pools, jnp.full((1,), layer, jnp.int32), tables, positions,
        valid, window=window, interpret=True, tile=tile)
    return np.asarray(got), np.asarray(want)


# tiles of 8 queries and blocks of 16 keys: a window of 5 lies inside a
# block, one of 20 across two, one of 4096 is wider than every key
@pytest.mark.parametrize("window", [None, 5, 20, 4096],
                         ids=["none", "in_block", "across", "wider"])
@pytest.mark.parametrize("start", [0, 11, 40],
                         ids=["at_0", "inside_window", "past_window"])
@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("shape, sub", [(NARROW, 4), (MANY, 32)],
                         ids=["group1", "group16"])
def test_the_kernel_matches_the_loop(window, start, b, shape, sub):
    """Shuffled tables, every slot at its own start, tiles smaller than
    the chunk and blocks smaller than the table, so that a tile walks
    some blocks, skips others and takes both bodies; at one head a
    key-value head and at Command A+'s 16 on 8 key-value heads (two
    queries a turn of the rows' loop)."""
    s, max_pages = 32, 12
    q, pools, tables = _inputs(1, b, s, shape, max_pages)
    positions = [start, max(start - 3, 0)][:b]
    got, want = _both(q, pools, tables, positions, [s] * b, window,
                      tile=(8, 16, sub))
    np.testing.assert_allclose(got, want, atol=2e-5)


# tiles of 8 queries over pages of 8: their own keys (and a page before
# them) are 128 keys of scores at most, a quarter of a block of 512
@pytest.mark.parametrize("window", [None, 520, 700, 37],
                         ids=["none", "a_block", "blocks", "in_short"])
@pytest.mark.parametrize("start", [0, 509, 1000, 1100],
                         ids=["at_0", "at_a_block", "short_last", "long_last"])
def test_a_short_last_block_is_the_loops(window, start):
    """A walk that starts at the page of the first visible key and ends
    in a short block (its last keys a quarter of a block or less:
    fetched and folded at that length), in a whole edge block, or in
    the short block alone."""
    s, max_pages = 32, 160
    assert _short_block(8, 512, PAGE) == 128
    q, pools, tables = _inputs(9, 2, s, NARROW, max_pages)
    valid = [s, s - 5]
    got, want = _both(q, pools, tables, [start, max(start - 3, 0)], valid,
                      window, tile=(8, 512, 4))
    for slot, n in enumerate(valid):
        np.testing.assert_allclose(got[slot, :n], want[slot, :n], atol=2e-5)


@pytest.mark.parametrize("shape", [WIDE, NARROW, MANY],
                         ids=["group8", "group1", "group16"])
@pytest.mark.parametrize("window", [None, 24], ids=["full", "window"])
def test_tiles_come_from_the_shapes(shape, window):
    """No tile handed in: what :func:`tiles` makes of the shapes, at
    Mellum's head layout (8 query heads a key-value head of 128), at a
    smaller one of group 1 and at Command A+'s (16 query heads a
    key-value head, 8 of those)."""
    b, s, max_pages = 2, 16, 6
    h, kvh, dh = shape
    tq, tk, sub = tiles(s, h // kvh, dh, kvh * dh, 4, max_pages * PAGE,
                        PAGE, window)
    assert s % tq == 0 and tk % PAGE == 0 and tk <= max_pages * PAGE
    assert (tq * h // kvh) % sub == 0 and sub % (h // kvh) == 0
    q, pools, tables = _inputs(2, b, s, shape, max_pages)
    got, want = _both(q, pools, tables, [17, 3], [s, s], window)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("shape", [GPT2_HEADS, LFM2_HEADS],
                         ids=["gpt2_16x64", "lfm2_8x64_group4"])
@pytest.mark.parametrize("max_pages", [64, 48])
@pytest.mark.parametrize("start, valid", [(0, 256), (0, 150), (120, 256),
                                          (128, 70)],
                         ids=["at_0", "at_0_padded", "past_0",
                              "past_0_padded"])
def test_heads_of_64_share_a_lane_tile(shape, max_pages, start, valid):
    """Heads narrower than a lane tile, two folded together over the
    tile's 128 lanes (q and the result in the pool's packed rows), over
    a shuffled table no longer than a block (64 pages, GPT-2's row, and
    48): the table is ONE block, folded at the length a tile's queries
    can see in steps of 128 keys (a quarter of it in whole lane tiles),
    or not at all (a chunk at 0, one past it as after a prefix hit,
    ``valid_lens`` short of the bucket)."""
    s = 256
    h, kvh, dh = shape
    tq, tk, sub = tiles(s, h // kvh, dh, kvh * dh, 4, max_pages * PAGE,
                        PAGE)
    assert kernel_module._heads_a_lane_tile(dh, kvh * dh) == 2
    assert (tq, tk) == (128, max_pages * PAGE)
    assert kernel_module._last_widths(tq, tk, PAGE, tk) == \
        tuple(range(128, tk, 128))
    q, pools, tables = _inputs(11, 2, s, shape, max_pages)
    valid = [valid, s]
    got, want = _both(q, pools, tables, [start, max(start - 5, 0)], valid,
                      None)
    for slot, n in enumerate(valid):
        np.testing.assert_allclose(got[slot, :n], want[slot, :n], atol=2e-5)
        assert np.isfinite(got[slot]).all()
        assert not got[slot, -(-n // tq) * tq:].any()


def test_heads_of_64_leave_the_kernel_in_the_callers_dtype():
    """bfloat16 pools as the cells hold them: the result is the caller's
    dtype straight from the kernel's float32 accumulator, within
    bfloat16's rounding of the loop's."""
    q, pools, tables = _inputs(12, 1, 64, GPT2_HEADS, 64, dtype=jnp.bfloat16)
    positions, valid = jnp.asarray([40], jnp.int32), jnp.asarray([50], jnp.int32)
    got = chunk_attention(q, *pools, 1, tables, positions, valid, PAGE,
                          out_dtype=jnp.bfloat16, interpret=True)
    want = paged_blocked_attention(q, *pools, 1, tables, positions, valid,
                                   PAGE, None)
    assert got.dtype == jnp.bfloat16 and got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got[0, :50], np.float32),
                               np.asarray(want[0, :50]), atol=3e-2)


@pytest.mark.parametrize("bucket", [512, 1024, 2048])
@pytest.mark.parametrize("cell, want", [
    # Mellum (8 heads a key-value head, 512 lanes): 256 queries x 8
    # heads a tile; a full layer's blocks are 1,024 keys, 512 rows a
    # turn; a sliding layer's half its window, 1,024 rows a turn
    (("ide", None), (256, 1024, 512)),
    (("ide", 1024), (256, 512, 1024)),
    # Command A+ (16 heads a key-value head, 1,024 lanes): the same
    # 2,048 rows of a key-value head a tile are 128 queries (the
    # parent's byte budgets gave 64 queries against 512 keys in every
    # bucket and both kinds of layer)
    (("rag", None), (128, 1024, 512)),
    (("rag", 4096), (128, 1024, 512)),
], ids=["ide_full", "ide_sliding", "rag_full", "rag_sliding"])
def test_tiles_at_the_cells_shapes(cell, want, bucket):
    """A chunk of every bucket over bfloat16 pools at the two cells that
    run the kernel, a full layer's table of 2,048 columns and a sliding
    layer's own: the tile is the same in every bucket, and what the call
    asks of VMEM for it is inside the 100 MiB a kernel may."""
    (name, window), full = cell, 2048 * 16
    group, lanes, sliding = {"ide": (8, 512, 193 * 16),
                             "rag": (16, 1024, 385 * 16)}[name]
    table = full if window is None else sliding
    got = tiles(bucket, group, 128, lanes, 2, table, 16, window)
    assert got == want
    assert _vmem_limit(*got, group, 128, lanes, 2) <= 100 << 20


@pytest.mark.parametrize("bucket", [128, 256, 512])
def test_tiles_at_evals_shape(bucket):
    """Olmo-Hybrid's full layers (a group of ONE over 3,840 lanes, a
    table of 192 columns): what 8 MiB holds of K and V double-buffered
    is 272 keys a block, and the bucket is one tile."""
    assert tiles(bucket, 1, 128, 3840, 2, 192 * 16, 16) == \
        (bucket, 272, bucket)


@pytest.mark.parametrize("bucket, want", [(128, (128, 1024, 128)),
                                          (256, (256, 1024, 256)),
                                          (512, (256, 1024, 256)),
                                          (1024, (256, 1024, 256))])
def test_tiles_at_gpt2s_shape(bucket, want):
    """GPT-2 medium in docs and chat (16 heads of 64, a group of 1 over
    1,024 lanes, a table of 64 columns: ONE block): the block is the
    table, fetched and folded at 256, 512 or 768 keys where a tile's
    queries see no more, under tiles of 256 queries, 256 rows of each
    of a lane tile's two heads a turn."""
    assert tiles(bucket, 1, 64, 1024, 2, 64 * 16, 16) == want
    assert kernel_module._last_widths(*want[:2], 16, 64 * 16) == \
        (256, 512, 768)
    assert _vmem_limit(*want, 1, 64, 1024, 2) <= 100 << 20


def test_tiles_of_other_shapes():
    """A bucket shorter than a tile is one tile; float32 pools at 512
    lanes still hold 1,024 keys a block in 8 MiB; a tile's short last
    block where its own keys are a quarter of a block or less."""
    assert tiles(128, 8, 128, 512, 2, 193 * 16, 16, 1024) == (128, 512, 1024)
    assert tiles(2048, 8, 128, 512, 4, 2048 * 16, 16) == (256, 1024, 512)
    assert _short_block(128, 1024, 16) == 256        # rag, both kinds
    assert _short_block(256, 1024, 16) == 0          # ide, full: 384 keys
    assert _short_block(256, 512, 16) == 0           # ide, sliding
    assert _short_block(8, 512, 8) == 128


@pytest.mark.parametrize("window", [None, 20], ids=["full", "window"])
@pytest.mark.parametrize("positions, valid", [([21, 0], [13, 8]),
                                              ([0, 40], [5, 1])],
                         ids=["pages", "one_live_page"])
def test_padded_rows_are_finite_and_a_tile_past_them_is_zero(
        window, positions, valid):
    """``valid_lens`` short of ``s``: the live rows are the loop's, the
    padded rows of a tile that holds a live one are finite, and a tile
    wholly past the live length fetched nothing and wrote zeros; a slot
    of one live page, whose block is fetched whole all the same, and one
    whose only live query is the first of a page."""
    s, max_pages = 32, 12
    q, pools, tables = _inputs(3, 2, s, NARROW, max_pages)
    got, want = _both(q, pools, tables, positions, valid, window,
                      tile=(8, 16, 4))
    for slot, n in enumerate(valid):
        np.testing.assert_allclose(got[slot, :n], want[slot, :n], atol=2e-5)
        assert np.isfinite(got[slot]).all()
        first_dead_tile = -(-n // 8) * 8
        assert not got[slot, first_dead_tile:].any()
        assert got[slot, :n].any()


@pytest.mark.parametrize("shape", [NARROW, GPT2_HEADS],
                         ids=["group1", "gpt2_16x64"])
@pytest.mark.parametrize("window", [None, 20, 12],
                         ids=["full", "window", "past_the_table"])
@pytest.mark.parametrize("start, n", [(9, 13), (32, 16)],
                         ids=["inside", "to_the_end"])
def test_nan_past_the_live_length_reaches_no_query(window, start, n, shape):
    """A slot's last page partly live, NaN in every pool row past the
    live length (a recycled page) and in the garbage page: a block is
    fetched WHOLE, its dead pages by the table's own entries and, where
    a window's walk runs past the table's last column (window 12: the
    last tile's second block starts at key 40 of 48), by the padding's
    garbage page. Masked scores weigh nothing, and the value side is
    zeroed so that ``0 * NaN`` is never formed."""
    s, max_pages = 16, 6
    q, pools, tables = _inputs(4, 1, s, shape, max_pages)
    live = start + n                       # tokens the slot holds
    clean = _both(q, pools, tables, [start], [n], window, tile=(8, 16, 4))[0]
    poisoned = []
    for pool in pools:
        pool = pool.at[0].set(jnp.nan)     # the garbage page
        rows = np.array(pool[tables[0]])   # (max_pages, layers, PAGE, lanes)
        flat = rows.transpose(1, 0, 2, 3).reshape(
            rows.shape[1], max_pages * PAGE, -1)
        flat[:, live:] = np.nan
        rows = flat.reshape(rows.shape[1], max_pages, PAGE, -1) \
            .transpose(1, 0, 2, 3)
        poisoned.append(pool.at[tables[0]].set(jnp.asarray(rows)))
    assert np.isnan(np.asarray(poisoned[1])).any()
    got, want = _both(q, poisoned, tables, [start], [n], window,
                      tile=(8, 16, 4))
    assert np.isfinite(got[0, :n]).all()
    np.testing.assert_array_equal(got[0, :n], clean[0, :n])
    np.testing.assert_allclose(got[0, :n], want[0, :n], atol=2e-5)


def test_a_window_no_key_is_older_than_is_the_windowless_program():
    """Bit for bit, as the walk's test holds the walk."""
    q, pools, tables = _inputs(5, 2, 32, NARROW, 12)
    args = (q, pools, tables, [40, 7], [32, 29])
    none = _both(*args, None, tile=(8, 16, 4))[0]
    wide = _both(*args, 4096, tile=(8, 16, 4))[0]
    np.testing.assert_array_equal(none, wide)


def test_bfloat16_pools_enter_the_matmuls_as_stored():
    """The loop's arithmetic: operands in the pool's dtype, float32
    statistics, the weights cast to the values' dtype."""
    q, pools, tables = _inputs(6, 1, 32, WIDE, 12, dtype=jnp.bfloat16)
    got, want = _both(q, pools, tables, [50], [32], 24, tile=(16, 32, 64))
    np.testing.assert_allclose(got, want, atol=2e-2)


def test_the_layer_is_data():
    """One traced kernel serves a group's layers: the layer is an
    operand, and a traced value at the public entry."""
    q, pools, tables = _inputs(7, 1, 16, NARROW, 6, layers=3)
    positions, valid = jnp.asarray([5], jnp.int32), jnp.asarray([16], jnp.int32)

    @jax.jit
    def run(layer):
        return chunk_attention(q, *pools, layer, tables, positions, valid,
                               PAGE, 12, interpret=True)

    for layer in (0, 2):
        want = paged_blocked_attention(q, *pools, layer, tables, positions,
                                       valid, PAGE, 12)
        np.testing.assert_allclose(run(jnp.int32(layer)), want, atol=2e-5)
    assert run._cache_size() == 1


def test_pools_of_another_layout_are_refused():
    q, pools, tables = _inputs(8, 1, 16, NARROW, 6)
    with pytest.raises(ValueError, match="chunk_attention wants pools"):
        chunk_attention(q, *pools, 0, tables, jnp.zeros((1,), jnp.int32),
                        jnp.full((1,), 16, jnp.int32), PAGE * 2,
                        interpret=True)
