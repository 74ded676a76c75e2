"""The gated delta rule's two forms (ops/pallas/gated_delta.py) against
their oracles on the CPU: the step kernel under the interpreter against
its einsum oracle, the chunked form against the recurrence token by
token, and the two oracles against each other."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import gated_delta as gd

H, DK, DV = 4, 8, 32          # four heads make one 128-lane block


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(n, seed=0, heads=H, dk=DK, dv=DV):
    """n steps' q, k (normalised, q scaled), v, the log of the decay
    and beta in (0, 2)."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((n, heads, dk)).astype(np.float32)
    k = rng.standard_normal((n, heads, dk)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((n, heads, dv)).astype(np.float32)
    g = -rng.uniform(0, 0.5, (n, heads)).astype(np.float32)
    beta = rng.uniform(0, 2, (n, heads)).astype(np.float32)
    return tuple(jnp.asarray(x) for x in (q, k, v, g, beta))


def _state(*lead, seed=1, heads=H, dk=DK, dv=DV):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(lead + (dk, heads * dv)),
                       jnp.float32)


# ------------------------------------------------------------------ chunk
@pytest.mark.parametrize("tokens, valid", [
    (128, 128), (128, 70), (128, 64), (128, 0), (40, 33), (200, 200),
    (16, 5)], ids=["whole", "inside_a_sub_chunk", "at_a_sub_chunks_end",
                   "nothing_real", "short_chunk", "no_multiple_of_64",
                   "one_block"])
def test_chunked_form_matches_the_recurrence(tokens, valid):
    q, k, v, g, beta = _inputs(tokens, seed=tokens + valid)
    s0 = _state(seed=valid)
    want_o, want_s = gd.gated_delta_chunk_xla(q, k, v, g, beta, s0, valid)
    got_o, got_s = jax.jit(gd.gated_delta_chunk)(q, k, v, g, beta, s0,
                                                 valid)
    np.testing.assert_allclose(got_o[:valid], want_o[:valid], atol=5e-6)
    np.testing.assert_allclose(got_s, want_s, atol=5e-6)
    # positions past valid changed nothing: the state is the state
    # after `valid` tokens of the unpadded chunk
    if valid:
        _, short = gd.gated_delta_chunk_xla(
            q[:valid], k[:valid], v[:valid], g[:valid], beta[:valid], s0,
            valid)
        np.testing.assert_allclose(got_s, short, atol=5e-6)
    else:
        np.testing.assert_array_equal(got_s, s0)


def test_padded_positions_may_hold_anything():
    q, k, v, g, beta = _inputs(64, seed=9)
    s0 = _state(seed=9)
    want_o, want_s = gd.gated_delta_chunk(q, k, v, g, beta, s0, 21)
    nan = lambda x: x.at[21:].set(jnp.nan)
    got_o, got_s = gd.gated_delta_chunk(nan(q), nan(k), nan(v), nan(g),
                                        nan(beta), s0, 21)
    np.testing.assert_array_equal(got_s, want_s)
    np.testing.assert_array_equal(got_o[:21], want_o[:21])


def test_repeated_keys_at_beta_two_stay_exact():
    """Every token the same key, beta at its edge: the strict triangle's
    powers grow past float32 before they vanish, forward substitution
    does not care."""
    q, k, v, g, beta = _inputs(128, seed=4)
    k = jnp.broadcast_to(k[:1], k.shape)
    beta, g = jnp.full_like(beta, 1.99), jnp.full_like(g, -1e-3)
    s0 = jnp.zeros((DK, H * DV), jnp.float32)
    want_o, want_s = gd.gated_delta_chunk_xla(q, k, v, g, beta, s0, 128)
    got_o, got_s = gd.gated_delta_chunk(q, k, v, g, beta, s0, 128)
    scale = float(jnp.abs(want_o).max())
    np.testing.assert_allclose(got_o, want_o, atol=1e-4 * scale)
    np.testing.assert_allclose(got_s, want_s, atol=1e-4 * scale)


def test_two_chunks_are_one():
    q, k, v, g, beta = _inputs(96, seed=2)
    s0 = _state(seed=2)
    whole_o, whole_s = gd.gated_delta_chunk(q, k, v, g, beta, s0, 96)
    first = tuple(x[:48] for x in (q, k, v, g, beta))
    second = tuple(x[48:] for x in (q, k, v, g, beta))
    o1, s1 = gd.gated_delta_chunk(*first, s0, 48)
    o2, s2 = gd.gated_delta_chunk(*second, s1, 48)
    np.testing.assert_allclose(jnp.concatenate([o1, o2]), whole_o,
                               atol=5e-6)
    np.testing.assert_allclose(s2, whole_s, atol=5e-6)


# ------------------------------------------------------------------- step
@pytest.mark.pallas
@pytest.mark.parametrize("slots, slot_block, heads, dv", [
    (32, 16, 4, 32), (8, 16, 4, 32), (16, 8, 6, 64), (4, 16, 3, 24)],
    ids=["two_tiles", "one_short_tile", "pairs_of_heads",
         "no_whole_lane_tile"])
def test_step_kernel_in_interpret_mode_matches_xla(slots, slot_block,
                                                   heads, dv):
    q, k, v, g, beta = _inputs(slots, seed=slots, heads=heads, dv=dv)
    a = jnp.exp(g)
    pool = _state(3, slots, heads=heads, dv=dv)
    # a slot held back, as its caller gives it
    hold = 1 if slots > 5 else 0
    a, beta = a.at[hold].set(1.0), beta.at[hold].set(0.0)
    q, k, v = (x.at[hold].set(0.0) for x in (q, k, v))
    want_o, want_pool = gd.gated_delta_step_xla(pool, q, k, v, a, beta, 1)
    got_o, got_pool = gd.gated_delta_step(pool, q, k, v, a, beta, 1,
                                          interpret=True,
                                          slot_block=slot_block)
    np.testing.assert_allclose(got_o, want_o, atol=2e-6)
    np.testing.assert_allclose(got_pool, want_pool, atol=2e-6)
    # to the bit: the slot held back, and the layers not named
    np.testing.assert_array_equal(got_pool[1, hold], pool[1, hold])
    np.testing.assert_array_equal(got_pool[0], pool[0])
    np.testing.assert_array_equal(got_pool[2], pool[2])


def test_a_step_is_a_chunk_of_one_token():
    q, k, v, g, beta = _inputs(6, seed=11)
    pool = _state(2, 6, seed=11)
    o, got = gd.gated_delta_step_xla(pool, q, k, v, jnp.exp(g), beta, 0)
    for slot in range(6):
        one = tuple(x[slot:slot + 1] for x in (q, k, v, g, beta))
        want_o, want_s = gd.gated_delta_chunk_xla(*one, pool[0, slot], 1)
        np.testing.assert_allclose(o[slot], want_o[0], atol=2e-6)
        np.testing.assert_allclose(got[0, slot], want_s, atol=2e-6)


def test_heads_a_block_fill_whole_lane_tiles():
    assert gd._heads_a_block(30, 192) == 2         # the published shape
    assert gd._heads_a_block(4, 32) == 4
    assert gd._heads_a_block(8, 128) == 1
    assert gd._heads_a_block(3, 24) == 3           # none does: all of them
