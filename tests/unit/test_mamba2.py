"""Mamba-2's two forms (ops/pallas/mamba2.py) against the token-by-token
oracle on the CPU: the chunked form for a ``valid_len`` inside a block,
at its end and 0, the step kernel under the interpreter, and the step
against Mamba-1's with broadcast operands: the two families'
recurrences are one."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import mamba2
from deepspeed_tpu.ops.pallas.mamba import mamba_step_xla

H, P, N = 4, 64, 16


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(T, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    dt = jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 2.0)
    A = -jnp.exp(jax.random.normal(k[4], (H,)))
    return dict(
        x=jax.random.normal(k[0], (T, H * P)), dt=dt,
        B=jax.random.normal(k[2], (T, N)), C=jax.random.normal(k[3], (T, N)),
        a_log=dt * A, D=jax.random.normal(k[5], (H,)),
        S0=jax.random.normal(k[6], (N, H * P)))


@pytest.mark.parametrize("block", [8, 16, 256])
@pytest.mark.parametrize("valid_len", [0, 7, 16, 40],
                         ids=["none", "inside_a_block", "at_a_blocks_end",
                              "whole"])
def test_ssd_chunk_is_the_recurrence_whatever_valid_len(valid_len, block):
    """Blocks of 8 and 16 tokens (valid_len 16 ends one, 7 lies inside
    one) and one block over the whole chunk: outputs within 1e-5 of the
    recurrence's at every real position (float32 sums in another order),
    and the state that of the last real token: the state it started from
    where there is none."""
    v = _inputs(40)
    y0, s0 = mamba2.ssd_chunk_xla(valid_len=valid_len, **v)
    y1, s1 = mamba2.ssd_chunk(valid_len=valid_len, block=block, **v)
    np.testing.assert_allclose(np.asarray(y1)[:valid_len],
                               np.asarray(y0)[:valid_len], atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), atol=2e-6)
    if valid_len == 0:
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(v["S0"]))


def test_ssd_chunk_ignores_what_a_padded_position_holds():
    v = _inputs(24, seed=1)
    y0, s0 = mamba2.ssd_chunk(valid_len=13, block=8, **v)
    bad = dict(v)
    for name in ("x", "dt", "B", "C", "a_log"):
        bad[name] = v[name].at[13:].set(jnp.nan)
    y1, s1 = mamba2.ssd_chunk(valid_len=13, block=8, **bad)
    np.testing.assert_array_equal(np.asarray(y1)[:13], np.asarray(y0)[:13])
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))


def test_two_chunks_are_one():
    v = _inputs(32, seed=2)
    y, s = mamba2.ssd_chunk(valid_len=32, block=8, **v)
    part = lambda lo, hi: {k: (x[lo:hi] if x.shape[0] == 32 and k not in
                               ("D", "S0") else x) for k, x in v.items()}
    y1, s1 = mamba2.ssd_chunk(valid_len=16, **part(0, 16))
    y2, s2 = mamba2.ssd_chunk(valid_len=16, **dict(part(16, 32), S0=s1))
    np.testing.assert_allclose(np.concatenate([y1, y2]), np.asarray(y),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), atol=2e-6)


def _step_inputs(slots=16, seed=3):
    v = _inputs(slots, seed)
    pool = jax.random.normal(jax.random.PRNGKey(seed + 10),
                             (3, slots, N, H * P))
    return pool, (v["x"], v["dt"], v["B"], v["C"], jnp.exp(v["a_log"]),
                  v["D"])


@pytest.mark.pallas
def test_ssd_step_kernel_is_its_oracle_and_in_place_on_its_layer():
    pool, args = _step_inputs()
    y0, p0 = mamba2.ssd_step_xla(pool, 1, *args)
    y1, p1 = mamba2.ssd_step(pool, 1, *args, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-5)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p0), atol=1e-6)
    for layer in (0, 2):
        np.testing.assert_array_equal(np.asarray(p1[layer]),
                                      np.asarray(pool[layer]))


@pytest.mark.pallas
@pytest.mark.parametrize("step", [mamba2.ssd_step_xla, mamba2.ssd_step],
                         ids=["xla", "pallas"])
def test_a_slot_held_back_keeps_its_state_to_the_bit(step):
    pool, (x, dt, B, C, a, D) = _step_inputs()
    hold = (jnp.arange(16) % 3 == 0)[:, None]
    extra = {"interpret": True} if step is mamba2.ssd_step else {}
    _, new = step(pool, 1, jnp.where(hold, 0.0, x), jnp.where(hold, 0.0, dt),
                  B, C, jnp.where(hold, 1.0, a), D, **extra)
    held = np.asarray(hold[:, 0])
    np.testing.assert_array_equal(np.asarray(new[1])[held],
                                  np.asarray(pool[1])[held])
    assert not np.array_equal(np.asarray(new[1])[~held],
                              np.asarray(pool[1])[~held])


def test_ssd_step_is_mamba_1s_step_with_broadcast_operands():
    """``A`` constant along the state and ``dt`` constant within a head:
    Mamba-1's oracle gives the same state and, less ``D x``, the same
    output."""
    pool, (x, dt, B, C, a, D) = _step_inputs()
    y, new = mamba2.ssd_step_xla(pool, 1, x, dt, B, C, a, D)
    A = jnp.log(a) / dt                                   # (slots, H)
    np.testing.assert_allclose(np.asarray(A), np.asarray(A[:1]).repeat(
        16, 0), rtol=1e-4)
    wide = jnp.broadcast_to(jnp.repeat(A[0], P)[None, :], (N, H * P))
    y1, new1 = mamba_step_xla(pool, 1, x, jnp.repeat(dt, P, axis=1), B, C,
                              wide)
    np.testing.assert_allclose(np.asarray(new1), np.asarray(new), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(y1), np.asarray(y - jnp.repeat(D, P)[None, :] * x),
        atol=1e-5)


def test_steps_one_after_the_other_are_a_chunk():
    v = _inputs(12, seed=5)
    y, s = mamba2.ssd_chunk(valid_len=12, **v)
    pool = v["S0"][None, None]
    rows = []
    for t in range(12):
        row, pool = mamba2.ssd_step_xla(
            pool, 0, v["x"][t:t + 1], v["dt"][t:t + 1], v["B"][t:t + 1],
            v["C"][t:t + 1], jnp.exp(v["a_log"][t:t + 1]), v["D"])
        rows.append(row[0])
    np.testing.assert_allclose(np.stack(rows), np.asarray(y), atol=1e-5)
    np.testing.assert_allclose(np.asarray(pool[0, 0]), np.asarray(s),
                               atol=2e-6)
