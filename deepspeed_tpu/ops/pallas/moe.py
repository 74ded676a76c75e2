"""Pallas grouped matmul over the experts a chip holds (``moe_gmm``):
rows sorted by expert, ragged groups of them, each group against its
own expert's matrix.

``lhs`` (m, k) holds the routed rows, group after group; ``rhs`` (E, k,
n) the experts' stacked matrices; ``group_sizes`` (E,) int32 the rows
of each group (their sum is at most m). ``out[r] = lhs[r] @
rhs[group of r]`` with float32 accumulation; a row past the groups'
total holds ANYTHING (a tile that no group reaches is never written):
the caller selects, it does not multiply by zero.

The work is cut into ITEMS, one per (group, row tile the group
reaches): a tile that two groups share is two items, each masked to
its own rows and accumulated into the tile's block, which stays in
VMEM between them. The items are laid out by :func:`group_metadata` in
XLA (a few integers an expert) and ride scalar prefetch, so the block
indices of an item are known before its body runs. The grid is (column
tiles, items), items innermost: an expert's ``(k, tn)`` block is
fetched once for all the row tiles of its group (consecutive items of
one group name the same block, and the pipeline fetches a block only
when its index changes), the rows are re-read once a column tile (a
few per cent of the weights' bytes at 48 rows an expert), and AN EXPERT
WITH NO ROW HAS NO ITEM: its weights are never fetched. Items past the
real ones repeat the last real item's indices (no fetch) and skip the
body.

Shapes come from the arguments: one kernel serves a decode step's 48
rows an expert and a prefill chunk's 64-256. The item slots are ``m //
tm + E - 1``, so they follow the rows the caller hands over: a chip
that holds a share of the experts hands over its capacity of held rows
(ops/moe.py ``share_capacity``: 4,096 of a chunk's 16,384 routed rows
at 16 of 128 experts, 47 slots for 143), a pass at a time, each pass
with the group sizes of its own rows. ``name="moe_gmm"`` is the
device trace's event name, by which the benchmark's roofline finds it.
``moe_gmm_xla`` (``lax.ragged_dot``) is the oracle, and what runs off
the TPU unless a test asks for the interpreter.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import default_interpret

ROW_TILE = 128                       # the MXU's rows
_RHS_BLOCK_BYTES = 4 * 2 ** 20       # one (k, tn) block of an expert
_VMEM_LIMIT_BYTES = 40 * 2 ** 20


def moe_gmm_xla(lhs, rhs, group_sizes, out_dtype=None):
    """The oracle: ``lax.ragged_dot``. Rows past the groups' total
    come out zero."""
    out = jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    return out.astype(out_dtype or lhs.dtype)


def row_tile(m):
    """Rows a tile: 128, or all of fewer rows (tiny presets), m rounded
    up to the sublane tile of 8."""
    return ROW_TILE if m >= ROW_TILE else -(-m // 8) * 8


def _column_tile(k, n, itemsize):
    """The widest multiple of 128 that divides ``n`` and keeps a (k, tn)
    block within ``_RHS_BLOCK_BYTES``; ``n`` itself where no multiple
    of 128 divides it (tiny presets)."""
    best = None
    for tn in range(128, n + 1, 128):
        if n % tn == 0 and (best is None or
                            k * tn * itemsize <= _RHS_BLOCK_BYTES):
            best = tn
    return best or n


def group_metadata(group_sizes, m, tm):
    """The items of ``group_sizes`` (E,) over ``m`` rows (a multiple of
    ``tm``) in tiles of ``tm``: ``(group of item, tile of item, group
    starts, group ends, number of real items)``, the first two of the
    static length ``m // tm + E - 1`` (every tile once, and once more
    for every group that starts inside one)."""
    sizes = group_sizes.astype(jnp.int32)
    E = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    item_end = jnp.cumsum(tiles)
    num = item_end[-1]
    items = jnp.arange(m // tm + E - 1, dtype=jnp.int32)
    # an item past the real ones repeats the last real one
    at = jnp.clip(jnp.minimum(items, num - 1), 0)
    group = jnp.minimum(jnp.searchsorted(item_end, at, side="right"),
                        E - 1).astype(jnp.int32)
    tile = first[group] + at - (item_end - tiles)[group]
    tile = jnp.clip(tile, 0, m // tm - 1).astype(jnp.int32)
    return group, tile, starts, ends, jnp.reshape(num, (1,))


def _kernel(group_ref, tile_ref, start_ref, end_ref, num_ref, lhs_ref,
            rhs_ref, out_ref, acc_ref, *, tm):
    """One item: lhs block (tm, k), the group's rhs block (k, tn), the
    tile's out block (tm, tn); acc (tm, tn) f32 carries a tile's sum
    between the items that share it."""
    i = pl.program_id(1)

    @pl.when(i < num_ref[0])
    def _item():
        g, t = group_ref[i], tile_ref[i]
        prod = jnp.dot(lhs_ref[...], rhs_ref[...],
                       preferred_element_type=jnp.float32)
        row = t * tm + jax.lax.broadcasted_iota(jnp.int32, prod.shape, 0)
        # a select: a row of another group, or of none, may be anything
        mine = jnp.where((row >= start_ref[g]) & (row < end_ref[g]),
                         prod, 0.0)
        opens = jnp.logical_or(i == 0,
                               tile_ref[jnp.maximum(i - 1, 0)] != t)

        @pl.when(opens)
        def _first():
            acc_ref[...] = mine

        @pl.when(jnp.logical_not(opens))
        def _more():
            acc_ref[...] += mine

        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def moe_gmm(lhs, rhs, group_sizes, *, metadata=None, out_dtype=None,
            interpret=None):
    """The grouped matmul of the module docstring. ``metadata``:
    :func:`group_metadata` of ``(group_sizes, m, row_tile(m))`` where
    the caller has it already (two matmuls of one expert layer share
    it). ``m`` must be a multiple of ``row_tile(m)``."""
    if interpret is None:
        interpret = default_interpret()
    m, k = lhs.shape
    E, _, n = rhs.shape
    tm = row_tile(m)
    assert m % tm == 0, "{} rows are no multiple of the tile {}".format(
        m, tm)
    out_dtype = out_dtype or lhs.dtype
    tn = _column_tile(k, n, rhs.dtype.itemsize)
    if metadata is None:
        metadata = group_metadata(group_sizes, m, tm)
    num_items = m // tm + E - 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n // tn, num_items),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, i, g, t, *_: (t[i], 0)),
            pl.BlockSpec((None, k, tn), lambda j, i, g, t, *_: (g[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i, g, t, *_: (t[i], j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)])
    # what the chip must do at the least: every row once, and the
    # matrices of the experts hit (all of them, for this estimate)
    cost = pl.CostEstimate(
        flops=2 * m * k * n, transcendentals=0,
        bytes_accessed=(E * k * n * rhs.dtype.itemsize +
                        m * k * lhs.dtype.itemsize +
                        m * n * jnp.dtype(out_dtype).itemsize))
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        cost_estimate=cost, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="moe_gmm",
    )(*metadata, lhs, rhs)
