"""``kv_cache.write_tokens``: the one write of new cache rows into the
paged pools, at both granularities, against a numpy oracle and against
each other — on pools poisoned with NaN first and compared bit for bit
outside the garbage page 0 (what it holds after a write is unspecified).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import kv_cache

PAGE, MAX_PAGES, PAGES, LAYERS = 4, 6, 20, 3
WINDOW = PAGE * MAX_PAGES
# a slot's table: its live pages, then garbage entries (0)
TABLES = np.array([[7, 3, 12, 9, 5, 17], [2, 14, 6, 0, 0, 0]], np.int32)

# name: (s, [(start, valid_len) a slot])
CASES = {
    "starts_on_a_page_whole_bucket": (8, [(8, 8)]),
    "starts_mid_page_whole_bucket": (8, [(6, 8)]),
    "valid_len_one": (8, [(5, 1)]),
    "ends_mid_page": (8, [(4, 6)]),
    "starts_and_ends_mid_page": (8, [(3, 7)]),
    "runs_past_the_window": (8, [(20, 8)]),
    "runs_past_the_window_from_mid_page": (8, [(18, 8)]),
    "garbage_entries_past_the_live_pages": (8, [(7, 3)]),
    "bucket_no_multiple_of_the_page": (10, [(7, 10)]),
    "bucket_of_one_page": (4, [(3, 4)]),
    "two_slots": (8, [(6, 8), (0, 5)]),
    "decode_step": (1, [(9, 1), (4, 1)]),
    "speculative_verify": (3, [(10, 3), (3, 3)]),
}
# lanes of each pool: keys and values of heads * d_head, one latent pool
POOLS = {"kv": (16, 16), "latent": (640,)}


def _bits(x):
    return np.asarray(x).view(np.uint16)


def _inputs(case, pools, seed=0):
    s, slots = CASES[case]
    rng = np.random.default_rng(seed)
    tables = TABLES[:len(slots)]
    if case == "garbage_entries_past_the_live_pages":
        tables = TABLES[1:2]
    made, news = [], []
    for lanes in POOLS[pools]:
        shape = (PAGES + 1, LAYERS, PAGE, lanes)
        pool = rng.standard_normal(shape).astype(np.float32)
        pool[rng.random(shape) < 0.5] = np.nan
        made.append(jnp.asarray(pool, jnp.bfloat16))
        news.append(jnp.asarray(
            rng.standard_normal((len(slots), s, lanes)), jnp.bfloat16))
    starts, valid = (np.array(x, np.int32) for x in zip(*slots))
    return tuple(made), tuple(news), tables, starts, valid


def _oracle(pools, news, layer, tables, starts, valid):
    out = [_bits(p).copy() for p in pools]
    for pool, new in zip(out, news):
        for b in range(len(starts)):
            for i in range(min(int(valid[b]), new.shape[1])):
                pos = int(starts[b]) + i
                if pos < WINDOW:
                    pool[tables[b, pos // PAGE], layer, pos % PAGE] = \
                        _bits(new)[b, i]
    return out


@pytest.mark.parametrize("layer", [0, LAYERS - 1], ids=["first", "last"])
@pytest.mark.parametrize("pools", sorted(POOLS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_write_tokens_changes_the_valid_rows_and_no_other(case, pools,
                                                          layer):
    made, news, tables, starts, valid = _inputs(case, pools)
    s = CASES[case][0]
    args = (made, news, layer, jnp.asarray(tables), jnp.asarray(starts),
            jnp.asarray(valid), PAGE)
    want = _oracle(made, news, layer, tables, starts, valid)
    by_rows = kv_cache._write_rows(*args)
    got = kv_cache.write_tokens(*args)
    assert len(got) == len(made)
    for g, r, w in zip(got, by_rows, want):
        assert g.dtype == jnp.bfloat16 and g.shape == r.shape
        np.testing.assert_array_equal(_bits(r)[1:], w[1:])
        np.testing.assert_array_equal(_bits(g)[1:], w[1:])
    if s < PAGE:
        # a decode step or a verify: today's row scatter, to the letter
        assert kv_cache.write_path(s, PAGE) == "rows"
        assert str(jax.make_jaxpr(
            lambda *a: kv_cache.write_tokens(*a[:2], layer, *a[2:], PAGE))(
                made, news, *args[3:6])) == str(jax.make_jaxpr(
                    lambda *a: kv_cache._write_rows(
                        *a[:2], layer, *a[2:], PAGE))(
                            made, news, *args[3:6]))
    else:
        # whole pages move, by the kernel: no scatter is left
        assert kv_cache.write_path(s, PAGE) == "pages"
        text = str(jax.make_jaxpr(
            lambda *a: kv_cache.write_tokens(*a[:2], layer, *a[2:], PAGE))(
                made, news, *args[3:6]))
        assert "kv_page_write" in text and "scatter" not in text
        # and the garbage page is not written at all
        for g, m in zip(got, made):
            np.testing.assert_array_equal(_bits(g)[0], _bits(m)[0])
