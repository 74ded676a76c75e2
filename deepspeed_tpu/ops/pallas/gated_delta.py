"""The gated delta rule (Yang, Kautz, Hatamizadeh 2024, "Gated Delta
Networks"): one decode step over every slot as a Pallas kernel, in place
on the state pool (``gated_delta_step``), and a prompt chunk in
sub-chunks of 64 tokens (``gated_delta_chunk``, XLA).

The recurrence, per head, on a state ``S`` of ``d_k x d_v``::

    S'  = a_t S_{t-1}
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

with ``a_t`` in (0, 1] the decay and ``beta_t`` the writing strength
(in (0, 2) where negative eigenvalues are allowed). ``q`` and ``k``
come normalised, ``q`` scaled, by the caller.

The state is held ``(d_k, heads * d_v)``, the heads side by side in the
minor dimension: a head's 192 value lanes alone would be padded to 256
of the chip's lanes, 30 x 192 = 5,760 = 45 x 128 are not, and a PAIR of
heads is 384 = 3 x 128 lanes, the block a grid step of the kernel takes.
``d_k`` lies on the sublanes: ``S'^T k`` and ``S^T q`` are reductions
over them, ``k u^T`` a column times a row.

``gated_delta_step`` has no operand for a slot held back: its caller
gives it ``a = 1``, ``beta = 0`` and zero ``q``, ``k``, ``v`` (selected,
not multiplied: the row may hold anything), and its state stays as it
was. ``gated_delta_chunk`` takes the chunk's ``valid_len``: a position
at or past it is made such a step (``a = 1``, ``beta = 0``), so a padded
bucket ends in the state of its last real token.

The chunked form is the paper's: within a sub-chunk the ``u`` of all
tokens solve a unit lower triangular system, ``(I + strict_lower(
diag(beta) (K K^T) * decay)) U = diag(beta) (V - diag(decay) K S_0)``,
linear in the state the sub-chunk starts from, so ``T^-1 diag(beta) V``
and ``T^-1 diag(beta decay) K`` are worked out for EVERY sub-chunk of
the chunk at once, by forward substitution (exact, and stable where a
power series of the strict triangle is not: rows of 16, then blocks),
and the walk over the sub-chunks in series is three matmuls each with
the state carried in float32. Every product is float32 at the highest
matmul precision.

The step kernel carries a ``name=`` (the device trace's event name);
the chunked form runs under the named scope ``gdn.chunk``. ``*_xla``
are the oracles: the step as einsums, the chunk as the token-by-token
recurrence under ``lax.scan``; they are what runs off the TPU unless a
test asks for the interpreter.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import default_interpret

SUB_CHUNK = 64        # tokens whose u's one triangular system gives
_ROWS = 16            # rows a diagonal block's substitution takes
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


# ------------------------------------------------------------------ step
def gated_delta_step_xla(state, q, k, v, a, beta, layer):
    """The oracle. state (layers, slots, dk, H * dv); q, k (slots, H,
    dk); v (slots, H, dv); a, beta (slots, H). -> o (slots, H * dv)
    f32, the pool with layer ``layer`` advanced one step."""
    slots, H, dk = q.shape
    dv = v.shape[2]
    q, k, v, a, beta = (x.astype(_F32) for x in (q, k, v, a, beta))
    S = state[layer].astype(_F32).reshape(slots, dk, H, dv)
    S = S * a[:, None, :, None]
    kv = jnp.einsum("sdhe,shd->she", S, k, precision=_HIGHEST)
    u = beta[:, :, None] * (v - kv)
    S = S + k.transpose(0, 2, 1)[:, :, :, None] * u[:, None, :, :]
    o = jnp.einsum("sdhe,shd->she", S, q, precision=_HIGHEST)
    S = S.reshape(slots, dk, H * dv).astype(state.dtype)
    return o.reshape(slots, H * dv), state.at[layer].set(S)


def _heads_a_block(H, dv):
    """Heads whose value lanes make whole 128-lane tiles together, as
    few as do (2 at 192 lanes a head); all of them where none does."""
    for n in range(1, H):
        if H % n == 0 and (n * dv) % 128 == 0:
            return n
    return H


def _step_kernel(kq_ref, v_ref, a_ref, b_ref, s_in_ref, o_ref, s_out_ref,
                 *, slots, heads, dv):
    """One tile of ``slots`` slots by a block of ``heads`` heads: the
    state blocks (slots, dk, heads * dv) of the pool's layer; v, a,
    beta and o rows (slots, heads * dv), a and beta repeated over a
    head's lanes; kq (heads, dk, 2 * slots): a head's ``k`` of every
    slot of the tile as COLUMNS (dk on the sublanes, a slot a lane),
    then its ``q`` likewise."""
    dk, lanes = s_in_ref.shape[1], s_in_ref.shape[2]
    kq = [kq_ref[h] for h in range(heads)]                 # (dk, 2 slots)
    lane = jax.lax.broadcasted_iota(jnp.int32, (dk, lanes), 1)

    def columns(at):
        """(dk, lanes): lane block h holds column ``at`` of head h."""
        out = jnp.broadcast_to(kq[heads - 1][:, at:at + 1], (dk, lanes))
        for h in range(heads - 2, -1, -1):
            out = jnp.where(lane < (h + 1) * dv,
                            jnp.broadcast_to(kq[h][:, at:at + 1],
                                             (dk, lanes)), out)
        return out

    for s in range(slots):
        kc, qc = columns(s), columns(slots + s)
        S = s_in_ref[s].astype(_F32) * a_ref[s:s + 1, :]
        kv = jnp.sum(S * kc, axis=0, keepdims=True)        # (1, lanes)
        u = b_ref[s:s + 1, :] * (v_ref[s:s + 1, :] - kv)
        S = S + kc * u
        s_out_ref[s] = S.astype(s_out_ref.dtype)
        o_ref[s:s + 1, :] = jnp.sum(S * qc, axis=0, keepdims=True)


def gated_delta_step(state, q, k, v, a, beta, layer, *, interpret=None,
                     slot_block=16):
    """One decode step of one linear-attention layer for every slot, in
    place on the state pool: the pool is aliased input to output and
    only layer ``layer``'s blocks are read and written (``layer`` is
    trace-static), each once. A slot given ``a = 1``, ``beta = 0`` and
    zero ``k`` keeps its state. Shapes as :func:`gated_delta_step_xla`.

    ``k`` and ``q`` enter as columns: ``(slot tiles, H, dk, 2 *
    slot_block)``, a slot a lane, so that a tile's block pads its
    ``2 * slot_block`` lanes to 128 in HBM (4 x at 16 slots a tile: 2%
    of the state's bytes at the published shape)."""
    if interpret is None:
        interpret = default_interpret()
    layers, slots, dk, lanes = state.shape
    H, dv = v.shape[1], v.shape[2]
    assert lanes == H * dv and q.shape == k.shape == (slots, H, dk)
    hb = _heads_a_block(H, dv)
    sb = slot_block if slots % slot_block == 0 else slots
    tiles = slots // sb

    def columns(x):          # (slots, H, dk) -> (tiles, H, dk, sb)
        return x.astype(_F32).reshape(tiles, sb, H, dk).transpose(0, 2, 3, 1)

    def rows(x):             # (slots, H) -> (slots, H * dv)
        return jnp.repeat(x.astype(_F32), dv, axis=1)

    kq = jnp.concatenate([columns(k), columns(q)], axis=3)
    row = pl.BlockSpec((sb, hb * dv), lambda i, j: (i, j))
    block = pl.BlockSpec((None, sb, dk, hb * dv),
                         lambda i, j: (layer, i, 0, j))
    n_state = slots * dk * lanes
    cost = pl.CostEstimate(
        flops=9 * n_state, transcendentals=0,
        bytes_accessed=(2 * n_state * state.dtype.itemsize +
                        4 * (4 * slots * lanes + 2 * slots * H * dk)))
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, slots=sb, heads=hb, dv=dv),
        cost_estimate=cost, grid=(tiles, H // hb),
        in_specs=[pl.BlockSpec((None, hb, dk, 2 * sb),
                               lambda i, j: (i, j, 0, 0)),
                  row, row, row, block],
        out_specs=[row, block],
        out_shape=[jax.ShapeDtypeStruct((slots, lanes), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name="gated_delta_step",
    )(kq, v.astype(_F32).reshape(slots, lanes), rows(a), rows(beta), state)
    return o, state


# ----------------------------------------------------------------- chunk
def gated_delta_chunk_xla(q, k, v, g, beta, s0, valid_len):
    """The oracle: the recurrence token by token under ``lax.scan``.
    q, k (T, H, dk); v (T, H, dv); g (T, H) the LOG of the decay; beta
    (T, H); s0 (dk, H * dv); valid_len scalar: positions at or past it
    leave the state as it was. -> o (T, H * dv) f32, sT (dk, H * dv)
    f32."""
    T, H, dk = q.shape
    dv = v.shape[2]
    live = jnp.arange(T) < valid_len

    def step(S, inputs):
        live_t, q_t, k_t, v_t, g_t, b_t = inputs
        new = S * jnp.exp(g_t)[:, None, None]
        kv = jnp.einsum("hde,hd->he", new, k_t, precision=_HIGHEST)
        u = b_t[:, None] * (v_t - kv)
        new = new + k_t[:, :, None] * u[:, None, :]
        # a select, not a product with 0: a padded position's inputs
        # may be anything
        S = jnp.where(live_t, new, S)
        return S, jnp.einsum("hde,hd->he", S, q_t, precision=_HIGHEST)

    S0 = s0.astype(_F32).reshape(dk, H, dv).transpose(1, 0, 2)
    S, o = jax.lax.scan(step, S0, (live,) + tuple(
        x.astype(_F32) for x in (q, k, v, g, beta)))
    return (o.reshape(T, H * dv),
            S.transpose(1, 0, 2).reshape(dk, H * dv))


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGHEST)


def _unit_lower_solve(A, B):
    """``(I + A)^-1 B`` for strictly lower triangular ``A`` (..., C, C)
    and ``B`` (..., C, n), by forward substitution: the diagonal blocks
    of ``_ROWS`` rows are inverted a row at a time (every block of every
    system together), then the blocks are taken in order."""
    C = A.shape[-1]
    rows = min(_ROWS, C)
    nb = C // rows
    lead = A.shape[:-2]
    blocks = A.reshape(lead + (nb, rows, nb, rows))
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(nb)], axis=-3)
    inv = jnp.broadcast_to(jnp.eye(rows, dtype=A.dtype), diag.shape)
    for r in range(1, rows):
        # rows at or past r of ``inv`` are the identity's still, and
        # ``diag[r]`` is zero there
        row = inv[..., r, :] - _mm("...j,...jn->...n", diag[..., r, :], inv)
        inv = inv.at[..., r, :].set(row)
    Bb = B.reshape(lead + (nb, rows, B.shape[-1]))
    out = []
    for i in range(nb):
        rhs = Bb[..., i, :, :]
        for j in range(i):
            rhs = rhs - _mm("...tj,...jn->...tn", blocks[..., i, :, j, :],
                            out[j])
        out.append(_mm("...tj,...jn->...tn", inv[..., i, :, :], rhs))
    return jnp.concatenate(out, axis=-2)


def gated_delta_chunk(q, k, v, g, beta, s0, valid_len, *, sub=SUB_CHUNK):
    """One chunk of one linear-attention layer in sub-chunks of ``sub``
    tokens. Shapes and ``valid_len`` as :func:`gated_delta_chunk_xla`;
    a chunk that is no multiple of ``sub`` is padded to one."""
    with jax.named_scope("gdn.chunk"):
        T, H, dk = q.shape
        dv = v.shape[2]
        C = min(sub, -(-T // _ROWS) * _ROWS)
        n = -(-T // C)
        live = (jnp.arange(n * C) < jnp.minimum(valid_len, T))[:, None]

        def sub_chunks(x):
            # (T, H[, w]) -> (n, H, C, w); a padded position is a step
            # that leaves the state as it was: zero inputs, no decay,
            # nothing written (selected, not multiplied)
            x = x.astype(_F32).reshape(T, H, -1)
            x = jnp.pad(x, ((0, n * C - T), (0, 0), (0, 0)))
            x = jnp.where(live[:, :, None], x, 0.0)
            return x.reshape(n, C, H, -1).transpose(0, 2, 1, 3)

        q, k, v = sub_chunks(q), sub_chunks(k), sub_chunks(v)
        beta = sub_chunks(beta)                             # (n, H, C, 1)
        G = jnp.cumsum(sub_chunks(g), axis=2)               # (n, H, C, 1)
        t, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
        diff = G - G.transpose(0, 1, 3, 2)                  # G_t - G_j
        # decay from j to t, j <= t (the exponent is <= 0 there)
        decay = jnp.where(t >= j, jnp.exp(jnp.where(t >= j, diff, 0.0)),
                          0.0)
        A = jnp.where(t > j, beta * _mm("nhtd,nhjd->nhtj", k, k) * decay,
                      0.0)
        eG = jnp.exp(G)
        # u = u0 - w S_0, both through the one triangular system
        solved = _unit_lower_solve(
            A, jnp.concatenate([beta * v, beta * eG * k], axis=-1))
        u0, w = solved[..., :dv], solved[..., dv:]
        qk = _mm("nhtd,nhjd->nhtj", q, k) * decay
        q_in = q * eG                      # what reads the state entering
        # what of each token's write is left at the sub-chunk's end
        k_out = k * jnp.exp(G[:, :, -1:, :] - G)
        g_all = eG[:, :, -1, :, None]                       # (n, H, 1, 1)

        def step(S, xs):
            u0_c, w_c, qk_c, q_c, k_c, g_c = xs
            u = u0_c - _mm("htd,hde->hte", w_c, S)
            o = _mm("htd,hde->hte", q_c, S) + _mm("htj,hje->hte", qk_c, u)
            return g_c * S + _mm("htd,hte->hde", k_c, u), o

        S0 = s0.astype(_F32).reshape(dk, H, dv).transpose(1, 0, 2)
        S, o = jax.lax.scan(step, S0, (u0, w, qk, q_in, k_out, g_all))
        o = o.transpose(0, 2, 1, 3).reshape(n * C, H * dv)[:T]
        return o, S.transpose(1, 0, 2).reshape(dk, H * dv)
