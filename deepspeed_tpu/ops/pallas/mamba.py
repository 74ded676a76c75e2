"""Pallas kernels for the Mamba-1 selective scan: a chunk of prefill
(``mamba_scan``) and one decode step over every slot, in place on the
state pool (``mamba_step``).

The recurrence, per channel ``c`` of ``d_inner`` and state ``n`` of
``d_state``::

    h_t[n, c] = exp(dt_t[c] * A[n, c]) * h_{t-1}[n, c]
                + dt_t[c] * x_t[c] * B_t[n]
    y_t[c]    = sum_n h_t[n, c] * C_t[n]

The state is held ``(d_state, d_inner)``, ``d_inner`` minor: a minor
dimension of 16 would be padded to the chip's 128 lanes, eight times
the bytes. ``B`` and ``C`` come as ``(..., d_state, 1)`` columns, so a
step's ``B_t`` is a sublane vector that broadcasts along the lanes (as
a flash kernel's running maximum does).

``mamba_scan`` takes the chunk's ``valid_len``: a position at or past
it leaves the state exactly as it was (a select: a padded position's
inputs may be anything, NaN included), so a padded bucket ends in the
state of its last real token. ``mamba_step`` has no such operand: its
caller holds a slot back by giving it ``dt == 0`` and a zero input
(``exp(0) = 1`` and the input term vanishes).

Both kernels carry a ``name=``: it becomes the device trace's event
name, by which the benchmark's rooflines find them. ``*_xla`` are the
``lax.scan`` / einsum oracles, and what runs off the TPU unless a test
asks for the interpreter.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import default_interpret

_GROUP = 8            # time steps per loop iteration: one sublane tile


def _lane_block(d_inner, want):
    """The largest multiple of 128 that divides ``d_inner`` and is at
    most ``want``; ``d_inner`` itself where none does (tiny presets)."""
    best = None
    for blk in range(128, min(want, d_inner) + 1, 128):
        if d_inner % blk == 0:
            best = blk
    return best or d_inner


# ------------------------------------------------------------------ scan
def mamba_scan_xla(x, dt, B, C, A, h0, valid_len):
    """The oracle: ``lax.scan`` over time. x, dt (T, di); B, C (T, n);
    A, h0 (n, di); valid_len scalar. -> y (T, di) f32, hT (n, di) f32."""
    live = jnp.arange(x.shape[0]) < valid_len

    def step(h, inputs):
        live_t, dt_t, x_t, B_t, C_t = inputs
        new = jnp.exp(dt_t[None, :] * A) * h + \
            (dt_t * x_t)[None, :] * B_t[:, None]
        h = jnp.where(live_t, new, h)
        return h, (h * C_t[:, None]).sum(0)

    f32 = jnp.float32
    h, y = jax.lax.scan(step, h0.astype(f32),
                        (live, dt.astype(f32), x.astype(f32),
                         B.astype(f32), C.astype(f32)))
    return y, h


def _scan_kernel(vlen_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref,
                 y_ref, h_ref, *, tile):
    """Grid (lane blocks, time tiles), time innermost and sequential.
    x/dt/y blocks (tile, blk) f32; b/c (tile, n, 1) f32; a/h0 (n, blk)
    f32; h_ref (n, blk) f32 is the final state AND the carry between
    time tiles (its block index does not depend on the time tile, so it
    stays in VMEM across the chunk)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _start():
        h_ref[...] = h0_ref[...]

    valid = vlen_ref[0]
    a = a_ref[...]
    n, blk = a.shape
    t_base = j * tile

    def group(g, h):
        t0 = pl.multiple_of(g * _GROUP, _GROUP)
        x8 = x_ref[pl.ds(t0, _GROUP), :]
        dt8 = dt_ref[pl.ds(t0, _GROUP), :]
        rows = []
        for i in range(_GROUP):
            dt_t = dt8[i:i + 1, :]                             # (1, blk)
            u_t = dt_t * x8[i:i + 1, :]
            b_t = b_ref[t0 + i]                                # (n, 1)
            c_t = c_ref[t0 + i]
            new = jnp.exp(jnp.broadcast_to(dt_t, (n, blk)) * a) * h + \
                jnp.broadcast_to(u_t, (n, blk)) * b_t
            # a select, not a product with 0: what a padded position
            # holds may be anything, NaN included
            h = jnp.where((t_base + t0 + i) < valid, new, h)
            rows.append(jnp.sum(h * c_t, axis=0, keepdims=True))
        y_ref[pl.ds(t0, _GROUP), :] = jnp.concatenate(rows, axis=0)
        return h

    h_ref[...] = jax.lax.fori_loop(0, tile // _GROUP, group, h_ref[...])


def mamba_scan(x, dt, B, C, A, h0, valid_len, *, interpret=None,
               lane_block=512, time_tile=128):
    """One chunk of one Mamba layer: the state stays in VMEM across the
    chunk. ``x``, ``dt`` (T, d_inner); ``B``, ``C`` (T, d_state); ``A``,
    ``h0`` (d_state, d_inner); ``valid_len`` int32 scalar: positions at
    or past it leave the state as it was (their ``y`` is not meant to
    be read). -> ``y`` (T, d_inner) f32, ``hT`` (d_state, d_inner) f32.
    T must be a multiple of 8."""
    if interpret is None:
        interpret = default_interpret()
    T, di = x.shape
    n = A.shape[0]
    blk = _lane_block(di, lane_block)
    tile = time_tile if T % time_tile == 0 else T
    assert tile % _GROUP == 0, "chunk of {} is no multiple of 8".format(T)
    f32 = jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(di // blk, T // tile),
        in_specs=[
            pl.BlockSpec((tile, blk), lambda i, j, *_: (j, i)),
            pl.BlockSpec((tile, blk), lambda i, j, *_: (j, i)),
            pl.BlockSpec((tile, n, 1), lambda i, j, *_: (j, 0, 0)),
            pl.BlockSpec((tile, n, 1), lambda i, j, *_: (j, 0, 0)),
            pl.BlockSpec((n, blk), lambda i, j, *_: (0, i)),
            pl.BlockSpec((n, blk), lambda i, j, *_: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((tile, blk), lambda i, j, *_: (j, i)),
            pl.BlockSpec((n, blk), lambda i, j, *_: (0, i)),
        ])
    # a step is an exp, two products and a sum per state entry, and the
    # reduction over d_state for y
    cost = pl.CostEstimate(
        flops=6 * T * n * di, transcendentals=T * n * di,
        bytes_accessed=4 * (3 * T * di + 2 * T * n + 3 * n * di))
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, tile=tile),
        grid_spec=grid_spec, cost_estimate=cost,
        out_shape=[jax.ShapeDtypeStruct((T, di), f32),
                   jax.ShapeDtypeStruct((n, di), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mamba_scan",
    )(jnp.reshape(valid_len, (1,)).astype(jnp.int32), x.astype(f32),
      dt.astype(f32), B.astype(f32)[..., None], C.astype(f32)[..., None],
      A.astype(f32), h0.astype(f32))
    return y, h


# ------------------------------------------------------------------ step
def mamba_step_xla(pool, layer, x, dt, B, C, A):
    """The oracle. pool (layers, slots, n, di); x, dt (slots, di); B, C
    (slots, n); A (n, di). -> y (slots, di) f32, the pool with layer
    ``layer`` advanced one step."""
    f32 = jnp.float32
    h = pool[layer].astype(f32)
    dt, x = dt.astype(f32), x.astype(f32)
    h = jnp.exp(dt[:, None, :] * A[None]) * h + \
        (dt * x)[:, None, :] * B.astype(f32)[:, :, None]
    h = h.astype(pool.dtype)
    y = (h.astype(f32) * C.astype(f32)[:, :, None]).sum(1)
    return y, pool.at[layer].set(h)


def _step_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, h_in_ref, y_ref,
                 h_out_ref, *, slots):
    """One block of ``slots`` slots by ``blk`` lanes: h blocks (slots,
    n, blk) of the pool's layer, x/dt/y (slots, blk), b/c (slots, n, 1),
    a (n, blk)."""
    a = a_ref[...]
    n, blk = a.shape
    for s in range(slots):
        dt_s = dt_ref[s:s + 1, :]
        u_s = dt_s * x_ref[s:s + 1, :]
        h = jnp.exp(jnp.broadcast_to(dt_s, (n, blk)) * a) * \
            h_in_ref[s].astype(jnp.float32) + \
            jnp.broadcast_to(u_s, (n, blk)) * b_ref[s]
        h = h.astype(h_out_ref.dtype)
        h_out_ref[s] = h
        y_ref[s:s + 1, :] = jnp.sum(h.astype(jnp.float32) * c_ref[s],
                                    axis=0, keepdims=True)


def mamba_step(pool, layer, x, dt, B, C, A, *, interpret=None,
               slot_block=8, lane_block=2560):
    """One decode step of one Mamba layer for every slot, in place on
    the state pool: the pool is aliased input to output and only layer
    ``layer``'s blocks are read and written (``layer`` is trace-static).
    A slot with ``dt == 0`` keeps its state. Shapes as
    :func:`mamba_step_xla`."""
    if interpret is None:
        interpret = default_interpret()
    layers, slots, n, di = pool.shape
    blk = _lane_block(di, lane_block)
    sb = slot_block if slots % slot_block == 0 else slots
    f32 = jnp.float32
    row = pl.BlockSpec((sb, blk), lambda i, j: (i, j))
    col = pl.BlockSpec((sb, n, 1), lambda i, j: (i, 0, 0))
    state = pl.BlockSpec((None, sb, n, blk), lambda i, j: (layer, i, 0, j))
    cost = pl.CostEstimate(
        flops=6 * slots * n * di, transcendentals=slots * n * di,
        bytes_accessed=(2 * slots * n * di * pool.dtype.itemsize +
                        4 * (3 * slots * di + 2 * slots * n + n * di)))
    y, pool = pl.pallas_call(
        functools.partial(_step_kernel, slots=sb), cost_estimate=cost,
        grid=(slots // sb, di // blk),
        in_specs=[row, row, col, col,
                  pl.BlockSpec((n, blk), lambda i, j: (0, j)), state],
        out_specs=[row, state],
        out_shape=[jax.ShapeDtypeStruct((slots, di), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="mamba_step",
    )(x.astype(f32), dt.astype(f32), B.astype(f32)[..., None],
      C.astype(f32)[..., None], A.astype(f32), pool)
    return y, pool
