"""Mamba-2's recurrence (Dao and Gu 2024, "Transformers are SSMs": the
state-space dual, SSD): one decode step over every slot as a Pallas
kernel, in place on the state pool (``ssd_step``), and a prompt chunk in
blocks of ``BLOCK`` tokens on the MXU (``ssd_chunk``, XLA).

The recurrence, per head ``h`` of ``H`` on a state ``S_h`` of ``P x N``
(``P`` the head's channels, ``N`` the state's width; ``B_t`` and ``C_t``
of ``N`` numbers are shared by every head: one group)::

    S_h,t = a_h,t S_h,t-1 + dt_h,t x_h,t (outer) B_t
    y_h,t = S_h,t C_t + D_h x_h,t

with ``a_h,t = exp(dt_h,t A_h)`` in (0, 1] a SCALAR a head and step:
Mamba-1's recurrence (ops/pallas/mamba.py) with ``A`` constant along the
state and ``dt`` constant within a head, at a state sixteen times as
wide.

The state is held ``(N, H * P)``, the heads side by side in the minor
dimension (the transpose of the ``S_h`` above; Mamba-1's layout): a
head's 64 channels alone would be half a lane tile, 128 x 64 = 8,192 are
64 whole ones. ``N`` lies on the sublanes: ``B_t`` enters as a column
that broadcasts along the lanes, ``S C`` adds rows and never reduces
across lanes.

``ssd_step`` has no operand for a slot held back: its caller gives it
``dt = 0``, ``a = 1`` and a zero ``x`` (selected, not multiplied: the
row may hold anything), and its state stays as it was to the bit (``1 *
S + 0``). ``ssd_chunk`` takes the chunk's ``valid_len``: a position at
or past it is made such a step, so a padded bucket ends in the state of
its last real token.

The chunked form is the paper's. In a block of ``Q`` tokens that starts
from ``S0``, with ``l_t = sum_{s <= t} log a_s`` a head (every exponent
below is <= 0)::

    G   = C B^T                                   (Q x Q, all heads')
    Y_h = ((G * exp(l_t - l_s) * dt_s) [s <= t]) X_h
          + exp(l_t) * (C S0_h^T) + D_h X_h
    S_h = exp(l_Q) S0_h + sum_s exp(l_Q - l_s) dt_s x_s (outer) B_s

The blocks are taken in series under ``lax.scan`` with the state carried
in float32; every product is float32 at the highest matmul precision.
Any ``Q`` gives the same numbers (the published ``mamba_chunk_size`` is
a parameter of that code's algorithm, not of the function).

The step kernel carries a ``name=`` (the device trace's event name); the
chunked form runs under the named scope ``ssd.chunk``. ``*_xla`` are the
oracles: the step as broadcasts and a sum, the chunk as the
token-by-token recurrence under ``lax.scan``; they are what runs off the
TPU unless a test asks for the interpreter.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import default_interpret
from .mamba import _lane_block

BLOCK = 256           # tokens a block of the chunked form takes
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _rows(x, p):
    """(.., H) -> (.., H * p): a head's number on each of its lanes."""
    return jnp.repeat(x.astype(_F32), p, axis=-1)


# ------------------------------------------------------------------ step
def ssd_step_xla(pool, layer, x, dt, B, C, a, D):
    """The oracle. pool (layers, slots, N, H * P); x (slots, H * P); dt,
    a (slots, H); B, C (slots, N); D (H,). -> y (slots, H * P) f32, the
    pool with layer ``layer`` advanced one step."""
    p = pool.shape[3] // dt.shape[1]
    x = x.astype(_F32)
    S = pool[layer].astype(_F32) * _rows(a, p)[:, None, :] + \
        B.astype(_F32)[:, :, None] * (_rows(dt, p) * x)[:, None, :]
    S = S.astype(pool.dtype)
    y = (S.astype(_F32) * C.astype(_F32)[:, :, None]).sum(1) + \
        _rows(D, p) * x
    return y, pool.at[layer].set(S)


def _step_kernel(bc_ref, a_ref, u_ref, s_in_ref, y_ref, s_out_ref, *,
                 slots):
    """One tile of ``slots`` slots by a block of lanes: the state blocks
    (slots, N, lanes) of the pool's layer; a (the decay) and u (``dt
    x``) rows (slots, lanes); bc (N, 2 * slots): every slot's ``B`` as a
    COLUMN (N on the sublanes, a slot a lane), then its ``C``
    likewise."""
    n, lanes = s_in_ref.shape[1], s_in_ref.shape[2]
    bc = bc_ref[...]
    for s in range(slots):
        b = jnp.broadcast_to(bc[:, s:s + 1], (n, lanes))
        c = jnp.broadcast_to(bc[:, slots + s:slots + s + 1], (n, lanes))
        S = s_in_ref[s].astype(_F32) * a_ref[s:s + 1, :] + \
            b * u_ref[s:s + 1, :]
        S = S.astype(s_out_ref.dtype)
        s_out_ref[s] = S
        y_ref[s:s + 1, :] = jnp.sum(S.astype(_F32) * c, axis=0,
                                    keepdims=True)


def ssd_step(pool, layer, x, dt, B, C, a, D, *, interpret=None,
             slot_block=8, lane_block=1024):
    """One decode step of one Mamba-2 layer for every slot, in place on
    the state pool: the pool is aliased input to output and only layer
    ``layer``'s blocks are read and written (``layer`` is trace-static),
    each once: a block of ``slot_block`` slots by ``lane_block`` lanes a
    grid step (4 MB of float32 state at 8 x 128 x 1,024). A slot given
    ``dt = 0``, ``a = 1`` keeps its state to the bit. Shapes as
    :func:`ssd_step_xla`.

    ``B`` and ``C`` enter as columns, ``(slot tiles, N, 2 *
    slot_block)``, a slot a lane, so that a tile's block pads its ``2 *
    slot_block`` lanes to 128 in HBM (0.1% of the state's bytes); ``dt
    x`` and the decay as rows, a head's number on each of its lanes;
    ``D x`` is added here, outside the kernel."""
    if interpret is None:
        interpret = default_interpret()
    layers, slots, n, lanes = pool.shape
    H = dt.shape[1]
    p = lanes // H
    assert lanes == H * p and x.shape == (slots, lanes) and \
        B.shape == C.shape == (slots, n)
    blk = _lane_block(lanes, lane_block)
    sb = slot_block if slots % slot_block == 0 else slots
    tiles = slots // sb

    def columns(v):          # (slots, N) -> (tiles, N, sb)
        return v.astype(_F32).reshape(tiles, sb, n).transpose(0, 2, 1)

    x = x.astype(_F32)
    row = pl.BlockSpec((sb, blk), lambda i, j: (i, j))
    block = pl.BlockSpec((None, sb, n, blk), lambda i, j: (layer, i, 0, j))
    n_state = slots * n * lanes
    cost = pl.CostEstimate(
        flops=5 * n_state, transcendentals=0,
        bytes_accessed=(2 * n_state * pool.dtype.itemsize +
                        4 * (3 * slots * lanes + 2 * slots * n)))
    y, pool = pl.pallas_call(
        functools.partial(_step_kernel, slots=sb), cost_estimate=cost,
        grid=(tiles, lanes // blk),
        in_specs=[pl.BlockSpec((None, n, 2 * sb), lambda i, j: (i, 0, 0)),
                  row, row, block],
        out_specs=[row, block],
        out_shape=[jax.ShapeDtypeStruct((slots, lanes), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="ssd_step",
    )(jnp.concatenate([columns(B), columns(C)], axis=2), _rows(a, p),
      _rows(dt, p) * x, pool)
    return y + _rows(D, p) * x, pool


# ----------------------------------------------------------------- chunk
def ssd_chunk_xla(x, dt, B, C, a_log, D, S0, valid_len):
    """The oracle: the recurrence token by token under ``lax.scan``.
    x (T, H * P); dt (T, H); B, C (T, N); a_log (T, H) the LOG of the
    step's decay (``dt A``, <= 0); D (H,); S0 (N, H * P); valid_len
    scalar: positions at or past it leave the state as it was. -> y (T,
    H * P) f32, ST (N, H * P) f32."""
    p = x.shape[1] // dt.shape[1]
    live = jnp.arange(x.shape[0]) < valid_len

    def step(S, inputs):
        live_t, x_t, dt_t, B_t, C_t, g_t = inputs
        new = S * jnp.exp(g_t)[None, :] + B_t[:, None] * (dt_t * x_t)[None, :]
        # a select, not a product with 0: a padded position's inputs
        # may be anything
        S = jnp.where(live_t, new, S)
        return S, (S * C_t[:, None]).sum(0)

    x = x.astype(_F32)
    S, y = jax.lax.scan(step, S0.astype(_F32), (
        live, x, _rows(dt, p), B.astype(_F32), C.astype(_F32),
        _rows(a_log, p)))
    return y + _rows(D, p) * x, S


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGHEST)


def ssd_chunk(x, dt, B, C, a_log, D, S0, valid_len, *, block=BLOCK):
    """One chunk of one Mamba-2 layer in blocks of ``block`` tokens.
    Shapes and ``valid_len`` as :func:`ssd_chunk_xla`; a chunk that is
    no multiple of ``block`` is padded to one."""
    with jax.named_scope("ssd.chunk"):
        T, lanes = x.shape
        H, N = dt.shape[1], B.shape[1]
        p = lanes // H
        Q = min(block, -(-T // 8) * 8)
        n = -(-T // Q)
        live = (jnp.arange(n * Q) < jnp.minimum(valid_len, T))[:, None]

        def blocks(v):
            # (T, w) -> (n, Q, w); a padded position is a step that
            # leaves the state as it was: zero inputs, no decay, nothing
            # written (selected, not multiplied)
            v = jnp.pad(v.astype(_F32), ((0, n * Q - T), (0, 0)))
            return jnp.where(live, v, 0.0).reshape(n, Q, -1)

        xf = x.astype(_F32)
        t, s = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]
        causal = (t >= s)[None]

        def step(S, xs):
            x_b, dt_b, B_b, C_b, g_b = xs
            l = jnp.cumsum(g_b, axis=0)                          # (Q, H)
            lT = l.T                                             # (H, Q)
            # decay from s to t, s <= t (the exponent is <= 0 there)
            decay = jnp.exp(jnp.where(causal, lT[:, :, None] -
                                      lT[:, None, :], 0.0))
            G = _mm("tn,sn->ts", C_b, B_b)                       # (Q, Q)
            M = jnp.where(causal, G[None] * decay * dt_b.T[:, None, :],
                          0.0)                                   # (H, Q, Q)
            y = _mm("hts,shp->thp", M, x_b.reshape(Q, H, p))
            # what reads the state the block starts from
            y = y + jnp.exp(l)[:, :, None] * \
                _mm("tn,nc->tc", C_b, S).reshape(Q, H, p)
            # what of each token's write is left at the block's end
            left = jnp.exp(l[-1:] - l) * dt_b                    # (Q, H)
            w = jnp.repeat(left, p, axis=1) * x_b                # (Q, lanes)
            S = jnp.repeat(jnp.exp(l[-1]), p)[None, :] * S + \
                _mm("tn,tc->nc", B_b, w)
            return S, y.reshape(Q, lanes)

        S, y = jax.lax.scan(step, S0.astype(_F32), (
            blocks(xf), blocks(dt), blocks(B), blocks(C), blocks(a_log)))
        return y.reshape(n * Q, lanes)[:T] + _rows(D, p) * xf, S
