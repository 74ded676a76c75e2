"""The grouped page walk (``ops/pallas/paged_attention.py::_grouped_kernel``)
interpreted on the CPU with bfloat16 pools, at the head shapes of the
three cells that run it (Mellum 32 on 4 of 128, LFM2 32 on 8 of 64,
Jamba 20 on 1 of 128), against the families' XLA reads:
``ops/chunk_attention.py::paged_blocked_attention`` (Mellum's; takes a
window) and ``models/jamba.py::_attend`` over the gathered rows (Jamba's
and, with per-head norms before it, LFM2's). Both oracles feed the MXU
bfloat16 operands and accumulate in float32, as the kernel does, so the
tolerance is a bfloat16 weight's rounding, not a lower precision's."""
import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.models import jamba
from deepspeed_tpu.ops.chunk_attention import paged_blocked_attention
from deepspeed_tpu.ops.pallas.paged_attention import (
    _grouped_block, _grouped_paged_attention, paged_attention)

pytestmark = pytest.mark.pallas

PAGE = 16
# query heads, key-value heads, d_head
HEADS = {"mellum_32_on_4_of_128": (32, 4, 128),
         "lfm2_32_on_8_of_64": (32, 8, 64),
         "jamba_20_on_1_of_128": (20, 1, 128)}
BLOCK = 2                      # pages a loop turn in these tests
# live tokens a slot: inside a page, short of a block, exactly one
# block, many blocks (an even and an odd count: the buffer half the next
# slot starts in), one token
LIVE = (5, 31, 32, 150, 97, 1)
BF16_ATOL = 2e-2


def _inputs(rng, heads, live, seq=1, max_pages=12, dtype=jnp.bfloat16):
    """Pools whose page 0 (the garbage page) is NaN, a table whose
    entries past a slot's live pages are the garbage page, and queries:
    slot ``i`` holds ``live[i]`` tokens, its ``seq`` queries the last."""
    h, kvh, dh = HEADS[heads]
    b = len(live)
    live = np.asarray(live)
    pages = -(-live // PAGE)
    total = int(pages.sum())
    pools = [jnp.asarray(rng.standard_normal(
        (total + 1, 2, PAGE, kvh * dh)), dtype).at[0].set(jnp.nan)
        for _ in range(2)]
    tables = np.zeros((b, max_pages), np.int32)
    order = rng.permutation(np.arange(1, total + 1))
    for i, at in enumerate(np.cumsum(pages) - pages):
        tables[i, :pages[i]] = order[at:at + pages[i]]
    q = jnp.asarray(rng.standard_normal((b, seq, h, dh)), dtype)
    positions = jnp.asarray(np.maximum(live - seq, 0), jnp.int32)
    valid = jnp.asarray(np.minimum(live, seq), jnp.int32)
    return q, pools, jnp.asarray(tables), positions, valid


def _gathered(q, pools, tables, positions, valid):
    """Jamba's and LFM2's read: every slot's whole row gathered, then
    ``_attend`` (the garbage page's NaN keys are masked by position and
    its values zeroed past the live window)."""
    b, s, h, dh = q.shape
    kvh = pools[0].shape[3] // dh
    rows = [p[tables, 1].reshape(b, -1, kvh, dh) for p in pools]
    cfg = jamba.JambaConfig(n_heads=h, n_kv_heads=kvh, d_model=h * dh)
    return jamba._attend(q, *rows, positions, valid, cfg) \
        .reshape(b, s, h, dh)


@pytest.mark.parametrize("seq", [1, 2])
@pytest.mark.parametrize("window", [None, 40, 4096],
                         ids=["no_window", "window_inside", "window_wider"])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_the_walk_on_bf16_pools_matches_the_families_reads(heads, window,
                                                           seq):
    """Slots shorter than a block, of exactly one and of many, in one
    launch: the same live entries as the XLA reads see, at a bfloat16
    weight's rounding."""
    rng = np.random.default_rng(11)
    q, pools, tables, positions, valid = _inputs(rng, heads, LIVE, seq)
    got = _grouped_paged_attention(
        q, *pools, tables, positions, valid, layer_idx=1, page_size=PAGE,
        interpret=True, block=BLOCK, window=window)
    assert got.dtype == jnp.float32 and bool(jnp.isfinite(got).all())
    want = paged_blocked_attention(q, *pools, 1, tables, positions, valid,
                                   PAGE, window)
    np.testing.assert_allclose(got, want, atol=BF16_ATOL)
    if window is None or window > max(LIVE):
        # a padded query (the one-token slot's second) is no output: the
        # gathered read does not mask its keys by ``live``
        real = (np.arange(seq)[None, :] < np.asarray(valid)[:, None])
        np.testing.assert_allclose(
            np.where(real[:, :, None, None], got, 0.0),
            np.where(real[:, :, None, None],
                     _gathered(q, pools, tables, positions, valid), 0.0),
            atol=BF16_ATOL)


@pytest.mark.parametrize("max_pages, block", [(3, None), (3, 4), (12, None),
                                              (12, 5)],
                         ids=["table_is_the_block", "table_under_the_block",
                              "own_block", "block_not_a_divisor"])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_the_walk_whatever_the_block_is_to_the_table(heads, max_pages,
                                                     block):
    """A table narrower than a block (a block never outgrows the table
    by the kernel's own choice, but the buffer may), a table that is one
    block, a block that does not divide the row: the last block's dead
    pages are never fetched and never weigh."""
    rng = np.random.default_rng(12)
    live = (5, 33, 48) if max_pages == 3 else (5, 80, 192)
    q, pools, tables, positions, valid = _inputs(rng, heads, live,
                                                 max_pages=max_pages)
    if block is None:
        assert _grouped_block(max_pages, PAGE, 1, None) == max_pages
    for window in (None, 24):
        got = _grouped_paged_attention(
            q, *pools, tables, positions, valid, layer_idx=1,
            page_size=PAGE, interpret=True, block=block, window=window)
        want = paged_blocked_attention(q, *pools, 1, tables, positions,
                                       valid, PAGE, window)
        np.testing.assert_allclose(got, want, atol=BF16_ATOL)


@pytest.mark.parametrize("window", [None, 40],
                         ids=["no_window", "window_inside"])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_nan_in_a_garbage_or_a_recycled_page_reaches_no_output(heads,
                                                               window):
    """The garbage page is NaN and every table entry past a slot's live
    pages points at it; the rows past ``live`` of each slot's last page
    (a recycled page's old tenant) are NaN in K and in V: the outputs
    are those of pools that hold zeros there."""
    rng = np.random.default_rng(13)
    live = (5, 31, 150, 97)
    q, pools, tables, positions, valid = _inputs(rng, heads, live)
    clean = [p.at[0].set(0.0) for p in pools]
    for i, n in enumerate(live):
        last, used = int(tables[i, (n - 1) // PAGE]), (n - 1) % PAGE + 1
        pools = [p.at[last, :, used:].set(jnp.nan) for p in pools]
        clean = [p.at[last, :, used:].set(0.0) for p in clean]
    got, want = (_grouped_paged_attention(
        q, *p, tables, positions, valid, layer_idx=1, page_size=PAGE,
        interpret=True, block=BLOCK, window=window) for p in (pools, clean))
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("before", [(5,), (40,), (97, 150)],
                         ids=["one_block_before", "two_blocks_before",
                              "two_slots_before"])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_a_slots_result_does_not_depend_on_the_slot_before(heads, before):
    """A slot's first block is fetched during the last block of the slot
    before it, into the buffer half that slot leaves free: whatever the
    earlier slots' lengths (an odd or an even count of blocks), the
    slot's result is the one it has alone, bit for bit."""
    rng = np.random.default_rng(14)
    q, pools, tables, positions, valid = _inputs(rng, heads,
                                                 before + (70, 33))

    def walk(rows, window):
        return _grouped_paged_attention(
            q[rows], *pools, tables[rows], positions[rows], valid[rows],
            layer_idx=1, page_size=PAGE, interpret=True, block=BLOCK,
            window=window)

    n = len(before)
    for window in (None, 40):
        together = walk(slice(None), window)
        np.testing.assert_array_equal(together[n:],
                                      walk(slice(n, None), window))
        np.testing.assert_array_equal(together[n + 1:],
                                      walk(slice(n + 1, None), window))


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_a_float32_pool_is_not_rounded(heads):
    """With float32 pools and queries nothing is cast: the walk is
    within float32 accumulation order of the gathered read."""
    rng = np.random.default_rng(15)
    q, pools, tables, positions, valid = _inputs(
        rng, heads, (5, 32, 97), dtype=jnp.float32)
    got = paged_attention(q, *pools, tables, positions, valid, layer_idx=1,
                          page_size=PAGE, interpret=True)
    np.testing.assert_allclose(
        got, _gathered(q, pools, tables, positions, valid), atol=2e-5)
