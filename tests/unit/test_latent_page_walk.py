"""The latent page walk (``ops/pallas/paged_attention.py::_mla_kernel``)
interpreted on the CPU against its XLA oracle,
``ops/mla.py::absorbed_attention_rows`` over the gathered rows. Since PR
47 the walk fetches a block WHOLE, the table's entries past a slot's live
pages (the garbage page) with the rest, so what a dead page holds is in
the buffer and must reach no output. The oracle is handed a bfloat16
pool's values as float32 (XLA:CPU has no bf16 x bf16 -> f32 dot at these
shapes): products and sums are float32 on both sides, and the kernel
alone rounds a weight ``exp(score - m)`` to bfloat16 before it meets a
value, so the tolerance is a bfloat16 weight's rounding, not a lower
precision's."""
import importlib

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.ops import mla

# the package exports a function of the module's name
kernels = importlib.import_module("deepspeed_tpu.ops.pallas.paged_attention")

pytestmark = pytest.mark.pallas

PAGE = 16
DIMS = mla.MLADims(heads=4, nope=32, rope=64, v=32, rank=128,
                   rope_theta=1e4)
USEFUL = DIMS.rank + DIMS.rope          # the lanes past it are padding
BLOCK = 2                      # pages a loop turn in these tests
# live tokens a slot: inside a page, short of a block, exactly one
# block, many blocks (an even and an odd count: the buffer half the next
# slot starts in), one token
LIVE = (5, 31, 32, 150, 97, 1)
BF16_ATOL = 2e-2


def _inputs(rng, live, seq=1, max_pages=12, dtype=jnp.bfloat16):
    """A latent pool whose page 0 (the garbage page) is NaN in every
    lane, a table whose entries past a slot's live pages are the garbage
    page, and absorbed queries: slot ``i`` holds ``live[i]`` tokens, its
    ``seq`` queries the last; pad lanes zero in queries and live rows."""
    b = len(live)
    live = np.asarray(live)
    pages = -(-live // PAGE)
    total = int(pages.sum())
    pool = jnp.asarray(rng.standard_normal(
        (total + 1, 2, PAGE, DIMS.lanes)), dtype)
    pool = pool.at[..., USEFUL:].set(0).at[0].set(jnp.nan)
    tables = np.zeros((b, max_pages), np.int32)
    order = rng.permutation(np.arange(1, total + 1))
    for i, at in enumerate(np.cumsum(pages) - pages):
        tables[i, :pages[i]] = order[at:at + pages[i]]
    q = jnp.asarray(rng.standard_normal((b, seq, DIMS.heads, DIMS.lanes)),
                    dtype).at[..., USEFUL:].set(0)
    positions = jnp.asarray(np.maximum(live - seq, 0), jnp.int32)
    valid = jnp.asarray(np.minimum(live, seq), jnp.int32)
    return q, pool, jnp.asarray(tables), positions, valid


def _walk(monkeypatch, q, pool, tables, positions, valid, block=BLOCK,
          layer_idx=1):
    if block is not None:
        monkeypatch.setattr(kernels, "_MLA_BLOCK_TOKENS", block * PAGE)
    return kernels.mla_decode(
        q, pool, tables, positions, valid, layer_idx=layer_idx,
        page_size=PAGE, rank=DIMS.rank, sm_scale=DIMS.scale,
        interpret=True)


def _oracle(q, pool, tables, positions, valid, layer_idx=1):
    b, max_pages = tables.shape
    rows = pool[tables, layer_idx].reshape(b, max_pages * PAGE, DIMS.lanes)
    return mla.absorbed_attention_rows(
        q.astype(jnp.float32), rows.astype(jnp.float32), positions, valid,
        DIMS)


def _real(valid, seq):
    """(b, seq, 1, 1): a padded query (the one-token slot's second) is
    no output."""
    return (np.arange(seq)[None, :]
            < np.asarray(valid)[:, None])[:, :, None, None]


@pytest.mark.parametrize("seq", [1, 2])
@pytest.mark.parametrize("layer_idx", [0, 1])
def test_the_walk_on_a_bf16_pool_matches_its_oracle(monkeypatch, seq,
                                                    layer_idx):
    """Slots shorter than a block, of exactly one and of many, in one
    launch over a bfloat16 pool: the same live rows as the gathered read
    sees, at a bfloat16 weight's rounding."""
    rng = np.random.default_rng(21)
    args = _inputs(rng, LIVE, seq)
    got = _walk(monkeypatch, *args, layer_idx=layer_idx)
    assert got.dtype == jnp.float32 and got.shape == (
        len(LIVE), seq, DIMS.heads, DIMS.rank)
    assert bool(jnp.isfinite(got).all())
    real = _real(args[-1], seq)
    np.testing.assert_allclose(
        np.where(real, got, 0.0),
        np.where(real, _oracle(*args, layer_idx=layer_idx), 0.0),
        atol=BF16_ATOL)


@pytest.mark.parametrize(
    "max_pages, block, live",
    [(3, None, (5, 33, 48)), (3, 2, (5, 33, 48)), (12, None, (5, 80, 192)),
     (12, 5, (5, 80, 192)), (12, 5, (5, 192, 177)), (12, 4, (192, 5, 192))],
    ids=["table_shorter_than_the_block", "short_table_block_overhangs",
         "own_block", "block_not_a_divisor", "last_column_live_mid_row",
         "last_column_live_block_divides"])
@pytest.mark.parametrize("seq", [1, 2])
def test_the_walk_whatever_the_block_is_to_the_table(monkeypatch, seq,
                                                     max_pages, block, live):
    """A table shorter than the kernel's own block of 512 tokens (the
    block is then the table), a block that does not divide the row, of
    3 columns or of 12 (the last block's columns past the table are
    clipped to its last), the table's last column live: what the fetch
    brings past the live pages never weighs."""
    rng = np.random.default_rng(22)
    args = _inputs(rng, live, seq, max_pages=max_pages)
    got = _walk(monkeypatch, *args, block=block)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, _oracle(*args), atol=BF16_ATOL)


@pytest.mark.parametrize("seq", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_nan_in_the_garbage_page_or_a_recycled_page_reaches_no_output(
        monkeypatch, seq, dtype):
    """The garbage page is NaN in every lane and every table entry past
    a slot's live pages points at it: the walk FETCHES those. The rows
    past ``live`` of each slot's last page (a recycled page's old
    tenant) and every page no slot holds are NaN too, pad lanes
    included: the outputs are those of a pool that holds zeros there,
    bit for bit."""
    rng = np.random.default_rng(23)
    live = (5, 31, 150, 97, 64)
    q, pool, tables, positions, valid = _inputs(rng, live, seq, dtype=dtype)
    clean = pool.at[0].set(0.0)
    for i, n in enumerate(live):
        last, used = int(tables[i, (n - 1) // PAGE]), (n - 1) % PAGE + 1
        pool = pool.at[last, :, used:].set(jnp.nan)
        clean = clean.at[last, :, used:].set(0.0)
    got, want = (_walk(monkeypatch, q, p, tables, positions, valid)
                 for p in (pool, clean))
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("before", [(5,), (40,), (70,), (97, 150)],
                         ids=["one_block_before", "two_blocks_before",
                              "three_blocks_before", "two_slots_before"])
@pytest.mark.parametrize("seq", [1, 2])
def test_a_slots_result_does_not_depend_on_the_slot_before(monkeypatch, seq,
                                                           before):
    """A slot's first block is fetched during the last block of the slot
    before it, into the buffer half that slot leaves free: whatever the
    earlier slots' lengths (1, 2 or 3 blocks: an odd or an even count),
    the slot's result is the one it has alone, bit for bit."""
    rng = np.random.default_rng(24)
    q, pool, tables, positions, valid = _inputs(rng, before + (70, 33),
                                                seq)

    def walk(rows):
        return _walk(monkeypatch, q[rows], pool, tables[rows],
                     positions[rows], valid[rows])

    n = len(before)
    together = walk(slice(None))
    np.testing.assert_array_equal(together[n:], walk(slice(n, None)))
    np.testing.assert_array_equal(together[n + 1:],
                                  walk(slice(n + 1, None)))


@pytest.mark.parametrize("dead", [(0,), (1,), (0, 2), (3,)],
                         ids=["first", "middle", "two", "last"])
@pytest.mark.parametrize("seq", [1, 2])
def test_a_slot_whose_row_is_all_garbage_beside_live_ones(monkeypatch, seq,
                                                          dead):
    """An empty slot (``valid_lens`` 0, every table entry the NaN
    garbage page) walks one block of garbage: its own rows come out
    finite (the scheduler ignores them) and the live slots beside it,
    whose first block was fetched during it or its during theirs, read
    what they read without it, bit for bit."""
    rng = np.random.default_rng(25)
    live = [40, 70, 33, 97]
    q, pool, tables, positions, valid = _inputs(rng, live, seq)
    alone = _walk(monkeypatch, q, pool, tables, positions, valid)
    idx = np.asarray(dead)
    tables = tables.at[idx].set(0)
    positions = positions.at[idx].set(0)
    valid = valid.at[idx].set(0)
    got = _walk(monkeypatch, q, pool, tables, positions, valid)
    assert bool(jnp.isfinite(got).all())
    keep = np.setdiff1d(np.arange(len(live)), idx)
    np.testing.assert_array_equal(got[keep], alone[keep])


@pytest.mark.parametrize("seq", [1, 2])
def test_a_float32_pool_is_not_rounded(monkeypatch, seq):
    """With a float32 pool and queries nothing is cast: the walk is
    within float32 accumulation order of the gathered read, at the
    kernel's own block too."""
    rng = np.random.default_rng(26)
    args = _inputs(rng, (5, 32, 97), seq, dtype=jnp.float32)
    for block in (BLOCK, None):
        np.testing.assert_allclose(_walk(monkeypatch, *args, block=block),
                                   _oracle(*args), atol=2e-5)
