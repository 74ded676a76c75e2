"""The paged cache's page write (``inference/kv_cache.py::write_tokens``
from a page of new tokens up): whole ``(page_size, lanes)`` pages moved
into the pools by DMA, one a page, in place.

XLA lowers a scatter on the chip to one update after another: about
130 ns a 2 KB row (a prefill chunk of 1,024 tokens: 49,152 of them,
6.2 ms), and still 0.4 us a 32 KB page window (1.3 ms; at some shapes a
slower algorithm altogether: 4.2 ms for a bucket of 256 in a pool of
8,501 pages; my chip run, PR 39). The DMA engine moves the same pages at
what their bytes cost. The pools stay in HBM (``pl.ANY``), aliased
input to output as ``mamba_step`` holds its pool; the chunk's rows
arrive already cut into page frames (``frames``), so a page that the
chunk fills is one HBM-to-HBM copy, started for all such pages before
any is waited for. The at most two pages a slot's chunk fills in part
(its first if it starts mid-page, its last if it ends mid-page) go
through VMEM: the page is read, the frame's valid rows laid over it,
and the page written back, so the rows before the chunk and at or past
``valid_len`` keep what they held. A frame with no valid token (bucket
padding, positions past the window) is not written anywhere.

The name does NOT start with ``paged_``: the benchmark's
``paged_attention_roofline`` reads every ``%paged_*`` call as the
attention kernel.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import default_interpret, shard_kernel, split_axes


def _kernel(meta_ref, layer_ref, *refs, n_pools, n_frames, page_size):
    """In SMEM: meta_ref (3, n_frames) int32, a frame's physical page
    and the rows ``[lo, hi)`` of it that hold valid tokens; layer_ref
    (1,). refs: a frames array ``(n_frames, page_size, lanes)`` a pool,
    the pools (inputs, aliased), the pools (outputs), then for each
    pool a VMEM page for the old rows and one for the new, and the DMA
    semaphores (3,)."""
    frames = refs[:n_pools]
    pools = refs[2 * n_pools:3 * n_pools]
    scratch = refs[3 * n_pools:]
    sem = scratch[-1]
    layer = layer_ref[0]

    def whole(j):
        return meta_ref[2, j] - meta_ref[1, j] == page_size

    def copies(j):
        return [pltpu.make_async_copy(
            frame.at[j], pool.at[meta_ref[0, j], layer], sem.at[0])
            for frame, pool in zip(frames, pools)]

    def start(j, carry):
        @pl.when(whole(j))
        def _():
            for copy in copies(j):
                copy.start()
        return carry

    def merge(j, carry):
        lo, hi = meta_ref[1, j], meta_ref[2, j]

        @pl.when((hi > lo) & jnp.logical_not(whole(j)))
        def _():
            for p, (frame, pool) in enumerate(zip(frames, pools)):
                old, new = scratch[2 * p], scratch[2 * p + 1]
                page = pool.at[meta_ref[0, j], layer]
                reads = [pltpu.make_async_copy(page, old, sem.at[1]),
                         pltpu.make_async_copy(frame.at[j], new, sem.at[2])]
                for read in reads:
                    read.start()
                for read in reads:
                    read.wait()
                row = jax.lax.broadcasted_iota(jnp.int32, old.shape, 0)
                old[...] = jnp.where((row >= lo) & (row < hi), new[...],
                                     old[...])
                write = pltpu.make_async_copy(old, page, sem.at[1])
                write.start()
                write.wait()
        return carry

    def wait(j, carry):
        @pl.when(whole(j))
        def _():
            for copy in copies(j):
                copy.wait()
        return carry

    jax.lax.fori_loop(0, n_frames, start, 0)
    jax.lax.fori_loop(0, n_frames, merge, 0)
    jax.lax.fori_loop(0, n_frames, wait, 0)


def kv_page_write(pools, frames, meta, layer_idx, *, interpret=None,
                  mesh=None):
    """Write the frames' valid rows into layer ``layer_idx`` of their
    pages. ``pools``: arrays ``(pages + 1, layers, page_size, lanes)``;
    ``frames``: for each pool ``(n_frames, page_size, lanes)`` in its
    dtype; ``meta`` (3, n_frames) int32: a frame's page and the rows
    ``[lo, hi)`` of it that hold valid tokens. No two frames with a
    valid row may name the same page. Returns the pools, in place under
    donation. ``mesh``: the mesh the calling program spans; the kernel
    then runs under a shard_map over it (common.shard_kernel), the lanes
    split over its ``model`` axis as the pool's are
    (inference/kv_cache.py PAGED_KV_CACHE_SPEC).

    The layer rides in SMEM beside ``meta`` and the call is a jitted
    function, so a program's layers share ONE traced and lowered kernel:
    with the layer static each layer's call was lowered on its own,
    0.2 s a call, 10 s of every start of the docs cell (48 calls; my chip
    runs, PR 39)."""
    if interpret is None:
        interpret = default_interpret()
    pools, frames = tuple(pools), tuple(frames)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        from ...parallel.topology import MODEL_AXIS
        lanes = split_axes(mesh, (MODEL_AXIS,), pools[0].shape[3])
        pool_specs = (P(None, None, None, lanes),) * len(pools)
        kernel = functools.partial(kv_page_write, interpret=interpret)
        return shard_kernel(
            kernel, mesh,
            (pool_specs, (P(None, None, lanes),) * len(frames), P(), P()),
            pool_specs)(pools, frames, meta, jnp.int32(layer_idx))
    return _call(pools, frames, meta.astype(jnp.int32),
                 jnp.full((1,), layer_idx, jnp.int32), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(pools, frames, meta, layer, *, interpret):
    n_pools, n_frames = len(pools), frames[0].shape[0]
    page_size = pools[0].shape[2]
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    scratch = []
    for pool in pools:
        scratch += [pltpu.VMEM(pool.shape[2:], pool.dtype)] * 2
    nbytes = sum(f.size * f.dtype.itemsize for f in frames)
    out = pl.pallas_call(
        functools.partial(_kernel, n_pools=n_pools, n_frames=n_frames,
                          page_size=page_size),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[anywhere] * (2 * n_pools),
            out_specs=[anywhere] * n_pools,
            scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((3,))]),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # operands 0 and 1 are meta and layer: pool p is 2 + n_pools + p
        input_output_aliases={2 + n_pools + p: p for p in range(n_pools)},
        cost_estimate=pl.CostEstimate(flops=0, transcendentals=0,
                                      bytes_accessed=2 * nbytes),
        interpret=interpret,
        name="kv_page_write",
    )(meta, layer, *frames, *pools)
    return tuple(out)
