"""Pallas ring GEMMs: the collective-matmul loops with EXPLICIT overlap.

``parallel/collective_matmul.py``'s ppermute backend decomposes the TP
all-gather/reduce-scatter into per-chunk hops and leaves XLA's
latency-hiding scheduler to sink each hop under the partial GEMM that
consumes the previous chunk. These kernels express the overlap directly
— fused computation-collective operations (arXiv 2305.06942) / T3
(arXiv 2401.16677): each ring step STARTS the next chunk's
``pltpu.make_async_remote_copy`` before issuing the current chunk's
partial matmul and only semaphore-waits the transfer when the next
iteration actually needs the data, so the ICI hop is in flight while
the MXU works by construction, not by scheduler luck.

Three per-device bodies, mirroring the ppermute impls 1:1 (same chunk
-> output-block mapping, same wire-dtype policy, same accumulation
order — the ppermute path stays the numerics oracle and
tests/unit/test_pallas_kernels.py pins fp32 column output bitwise):

* :func:`ag_matmul_pallas`  — allgather(x, dim=-2) @ w, output block
  per ring step, gathered x never materializes;
* :func:`matmul_rs_pallas`  — reduce_scatter(psum_partial(x @ w)): the
  rotating accumulator picks up one partial per hop and each output
  shard is complete the moment its last partial lands;
* :func:`gather_contract_pallas` — the dW ring gather-contract both
  custom_vjp backwards share.

Design notes:

* the comm scratch carries **one slot per ring step** (``n`` slots, no
  reuse), so no capacity handshake is needed between neighbors — the
  per-step send/recv semaphore waits are the only synchronization
  inside a call, and a neighbor barrier at kernel entry
  (``pltpu.get_barrier_semaphore``, hardware only — the interpreter
  has no lowering for it) fences back-to-back invocations reusing the
  scratch;
* ``chunks`` (the ppermute granularity knob) does not apply here: the
  transfer IS explicit, one DMA per ring step — it keeps governing the
  ppermute paths that still run (the zero3 gather, the loud fallbacks);
* off-TPU the kernels run under the Pallas interpreter
  (``interpret=True``) — remote copies are simulated faithfully on the
  CPU mesh, which is how tier-1 pins the backend against the oracle
  without hardware;
* flops are pinned to the dense math via ``pl.CostEstimate`` (the same
  count the unfused dot reports) so cost-analysis pricing and the MFU
  scoreboard see through the custom call.

Called per-device inside ``shard_map`` with ``axis_name`` bound — the
same contract as the ppermute impls; ``parallel/collective_matmul.py``
dispatches here when ``comm.collective_matmul.backend: "pallas"``.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (bound_axes, default_interpret,     # noqa: F401
                     pallas_ring_env_supported)  # re-exported gates

# one collective_id per kernel flavor: concurrent ring kernels on the
# same mesh must not share a barrier semaphore (hardware only)
_AG_COLLECTIVE_ID = 11
_RS_COLLECTIVE_ID = 12
_GC_COLLECTIVE_ID = 13
COLLECTIVE_IDS = (_AG_COLLECTIVE_ID, _RS_COLLECTIVE_ID, _GC_COLLECTIVE_ID)


def _compiler_kwargs(collective_id, interpret):
    """``pallas_call`` kwargs carrying the kernel's collective id — only
    the compiled path takes ``compiler_params`` (the interpreter has no
    barrier semaphore to key)."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        collective_id=collective_id)}


def pallas_ring_supported(x, w):
    """Shape gate shared with the dispatch layer: the kernels handle the
    TP-site layout (x rank 3 batched over leading dim, w rank 2)."""
    return x.ndim == 3 and w.ndim == 2


def _ring_size(axis_name):
    """Static ring size (mesh axis sizes are trace-time constants)."""
    return lax.psum(1, axis_name)


def _neighbor_barrier(axis_name, n, interpret):
    """Entry barrier with both ring neighbors: back-to-back invocations
    share the comm scratch, so a fast neighbor must not start writing
    this call's slots while the previous call still reads them. The
    interpreter has no barrier-semaphore lowering — and simulated
    devices run lock-step, so it needs none."""
    if interpret or n <= 1:
        return
    my = lax.axis_index(axis_name)
    left = lax.rem(my - 1 + n, n)
    right = lax.rem(my + 1, n)
    bar = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(bar, 1, device_id=left,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_signal(bar, 1, device_id=right,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(bar, 2)


def _require_axes():
    """The bound-axes tuple, or a LOUD error: remote-copy addressing is
    derived from it, and a guess on a multi-axis mesh would corrupt
    results silently. The dispatch layer (``pallas_ring_env_supported``)
    falls back to ppermute before ever reaching this; direct kernel
    callers get the explicit failure."""
    axes = bound_axes()
    if axes is None:
        raise RuntimeError(
            "pallas ring kernels need mesh-axis introspection "
            "(jax._src.core.get_axis_env unavailable on this jax "
            "version) — run comm.collective_matmul.backend='ppermute'")
    return axes


def _ring_device_id(axis_name, right, axes):
    """Address of the right ring neighbor: a scalar LOGICAL id on a
    single-axis mesh (also what the CPU interpreter supports), the full
    per-axis MESH tuple — every other axis at its own index — when the
    shard_map binds more (DP x TP on hardware)."""
    if len(axes) <= 1:
        return right, pltpu.DeviceIdType.LOGICAL
    return (tuple(right if a == axis_name else lax.axis_index(a)
                  for a in axes), pltpu.DeviceIdType.MESH)


def _ring_send(comm, send_sem, recv_sem, t, device_id, device_id_type):
    """Start the hop moving slot ``t`` to the right neighbor's slot
    ``t+1``. SPMD symmetry: our recv_sem[t+1] is signaled by the LEFT
    neighbor's copy of this same call, so waiting the returned
    descriptor waits both our outgoing send and the incoming chunk."""
    rdma = pltpu.make_async_remote_copy(
        src_ref=comm.at[t], dst_ref=comm.at[t + 1],
        send_sem=send_sem.at[t], recv_sem=recv_sem.at[t + 1],
        device_id=device_id, device_id_type=device_id_type)
    rdma.start()
    return rdma


def _dot2d(a, b):
    """(rows, k) @ (k, cols) on the MXU with fp32 accumulation."""
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


# ------------------------------------------------------ allgather-matmul
def _ag_kernel(x_ref, w_ref, o_ref, comm, send_sem, recv_sem, *,
               axis_name, n, axes, interpret):
    my = lax.axis_index(axis_name)
    right = lax.rem(my + 1, n)
    dev_id, dev_type = _ring_device_id(axis_name, right, axes)
    _neighbor_barrier(axis_name, n, interpret)
    b, s_loc, d = x_ref.shape
    f = w_ref.shape[-1]
    w = w_ref[...]
    comm[0] = x_ref[...].astype(comm.dtype)
    for t in range(n):
        rdma = (_ring_send(comm, send_sem, recv_sem, t, dev_id, dev_type)
                if t + 1 < n else None)
        # the local chunk (t=0) multiplies UNCAST — only rotated
        # payloads ride the wire dtype, matching ring_rotate's
        # cast-for-the-hop-only policy
        cur = x_ref[...] if t == 0 else comm[t].astype(x_ref.dtype)
        blk = lax.rem(my - t + n, n)
        part = _dot2d(cur.reshape(b * s_loc, d), w)
        o_ref[:, pl.ds(blk * s_loc, s_loc), :] = \
            part.reshape(b, s_loc, f).astype(o_ref.dtype)
        if rdma is not None:
            rdma.wait()


def ag_matmul_pallas(x, w, axis_name, wire_dtype=None, interpret=None):
    """Ring ``allgather(x, dim=-2) @ w`` with explicit async hops.

    x: [b, s_loc, d] (this device's ring shard); w: [d, f_loc].
    Returns [b, n*s_loc, f_loc] in ``result_type(x, w)`` — the ppermute
    oracle's output, fp32 bitwise (same per-block dots, same order).
    """
    if interpret is None:
        interpret = default_interpret()
    n = _ring_size(axis_name)
    b, s_loc, d = x.shape
    f = w.shape[-1]
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    comm_dtype = jnp.dtype(wire_dtype) if wire_dtype is not None \
        else x.dtype
    kw = _compiler_kwargs(_AG_COLLECTIVE_ID, interpret)
    return pl.pallas_call(
        functools.partial(_ag_kernel, axis_name=axis_name, n=n,
                          axes=_require_axes(), interpret=interpret),
        out_shape=jax.ShapeDtypeStruct((b, n * s_loc, f), out_dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((n, b, s_loc, d), comm_dtype),
                        pltpu.SemaphoreType.DMA((n,)),
                        pltpu.SemaphoreType.DMA((n,))],
        cost_estimate=pl.CostEstimate(
            flops=2 * b * n * s_loc * d * f,
            bytes_accessed=(x.size + w.size + b * n * s_loc * f) * 4,
            transcendentals=0),
        interpret=interpret,
        **kw,
    )(x, w)


# -------------------------------------------------- matmul-reducescatter
def _rs_kernel(x_ref, w_ref, o_ref, comm, send_sem, recv_sem, *,
               axis_name, n, axes, out_dtype, interpret):
    my = lax.axis_index(axis_name)
    right = lax.rem(my + 1, n)
    dev_id, dev_type = _ring_device_id(axis_name, right, axes)
    _neighbor_barrier(axis_name, n, interpret)
    b, s, f = x_ref.shape
    s_loc = s // n
    d = w_ref.shape[-1]
    w = w_ref[...]
    acc = None
    rdma = None
    for t in range(n):
        blk = lax.rem(my - 1 - t + 2 * n, n)
        xb = x_ref[:, pl.ds(blk * s_loc, s_loc), :]
        # partial FIRST: the accumulator hop started last step is in
        # flight during this GEMM, waited only at the add
        part = _dot2d(xb.reshape(b * s_loc, f), w) \
            .reshape(b, s_loc, d).astype(out_dtype)
        if t == 0:
            acc = part
        else:
            rdma.wait()
            acc = comm[t].astype(out_dtype) + part
        if t + 1 < n:
            comm[t] = acc.astype(comm.dtype)
            rdma = _ring_send(comm, send_sem, recv_sem, t, dev_id,
                              dev_type)
    o_ref[...] = acc.astype(o_ref.dtype)


def matmul_rs_pallas(x, w, axis_name, wire_dtype=None, interpret=None):
    """Ring ``reduce_scatter(psum_partial(x @ w), dim=-2)``.

    x: [b, n*s_loc, f_loc] (full-length partials); w: [f_loc, d].
    Returns [b, s_loc, d] — this device's shard of the sum, matching
    the ppermute oracle's partial-sum order hop for hop.
    """
    if interpret is None:
        interpret = default_interpret()
    n = _ring_size(axis_name)
    b, s, f = x.shape
    s_loc = s // n
    d = w.shape[-1]
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    comm_dtype = jnp.dtype(wire_dtype) if wire_dtype is not None \
        else out_dtype
    kw = _compiler_kwargs(_RS_COLLECTIVE_ID, interpret)
    return pl.pallas_call(
        functools.partial(_rs_kernel, axis_name=axis_name, n=n,
                          axes=_require_axes(), out_dtype=out_dtype,
                          interpret=interpret),
        out_shape=jax.ShapeDtypeStruct((b, s_loc, d), out_dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((n, b, s_loc, d), comm_dtype),
                        pltpu.SemaphoreType.DMA((n,)),
                        pltpu.SemaphoreType.DMA((n,))],
        cost_estimate=pl.CostEstimate(
            flops=2 * b * s * f * d,
            bytes_accessed=(x.size + w.size + b * s_loc * d) * 4,
            transcendentals=0),
        interpret=interpret,
        **kw,
    )(x, w)


# ------------------------------------------------- dW gather-contract
def _gc_kernel(rot_ref, fixed_ref, o_ref, comm, send_sem, recv_sem, *,
               axis_name, n, axes, rot_is_lhs, interpret):
    my = lax.axis_index(axis_name)
    right = lax.rem(my + 1, n)
    dev_id, dev_type = _ring_device_id(axis_name, right, axes)
    _neighbor_barrier(axis_name, n, interpret)
    b, s_loc, a = rot_ref.shape
    comm[0] = rot_ref[...].astype(comm.dtype)
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for t in range(n):
        rdma = (_ring_send(comm, send_sem, recv_sem, t, dev_id, dev_type)
                if t + 1 < n else None)
        cur = rot_ref[...] if t == 0 else comm[t].astype(rot_ref.dtype)
        blk = lax.rem(my - t + n, n)
        fb = fixed_ref[:, pl.ds(blk * s_loc, s_loc), :]
        # contract leading (batch, ring) dims: (b*s, a)^T-style GEMM
        term = _dot2d(cur.reshape(b * s_loc, a).T,
                      fb.reshape(b * s_loc, fb.shape[-1]))     # (a, bd)
        acc = acc + (term if rot_is_lhs else term.T)
        if rdma is not None:
            rdma.wait()
    o_ref[...] = acc.astype(o_ref.dtype)


def gather_contract_pallas(rot, fixed, axis_name, wire_dtype=None,
                           rot_is_lhs=True, interpret=None):
    """The dW accumulation both fused backwards share: ``sum_j
    block_j(allgather(rot)) ^T-contract fixed[block_j]`` with the
    rotating operand's hops explicit. rot: [b, s_loc, a]; fixed:
    [b, n*s_loc, c]. Returns [a, c] (``rot_is_lhs``) else [c, a]."""
    if interpret is None:
        interpret = default_interpret()
    n = _ring_size(axis_name)
    b, s_loc, a = rot.shape
    c = fixed.shape[-1]
    out_dtype = jnp.result_type(rot.dtype, fixed.dtype)
    comm_dtype = jnp.dtype(wire_dtype) if wire_dtype is not None \
        else rot.dtype
    shape = (a, c) if rot_is_lhs else (c, a)
    kw = _compiler_kwargs(_GC_COLLECTIVE_ID, interpret)
    return pl.pallas_call(
        functools.partial(_gc_kernel, axis_name=axis_name, n=n,
                          axes=_require_axes(), rot_is_lhs=rot_is_lhs,
                          interpret=interpret),
        out_shape=jax.ShapeDtypeStruct(shape, out_dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((n, b, s_loc, a), comm_dtype),
                        pltpu.SemaphoreType.DMA((n,)),
                        pltpu.SemaphoreType.DMA((n,))],
        cost_estimate=pl.CostEstimate(
            flops=2 * b * n * s_loc * a * c,
            bytes_accessed=(rot.size + fixed.size + a * c) * 4,
            transcendentals=0),
        interpret=interpret,
        **kw,
    )(rot, fixed)
