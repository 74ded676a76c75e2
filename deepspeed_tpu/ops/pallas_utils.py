"""Shared scaffolding for flat elementwise Pallas kernels (Adam, LAMB).

A tensor of any shape is flattened, cast to f32, zero-padded to a multiple
of one (8, 128) tile, and viewed as (rows, 128). Kernels block over rows;
the last grid block may be ragged — Pallas fills the out-of-range region
with unspecified values, so kernels that REDUCE must mask by global row id
(``row_mask``); pure elementwise outputs are safe (out-of-range rows are
dropped on write-back).
"""
import jax
import jax.numpy as jnp

LANE = 128
BLOCK_ROWS = 1024


def flatten_pad_2d(*arrays):
    """Flatten + f32-cast + zero-pad each array to (rows, LANE); returns
    (views, rows, unpad) where ``unpad(x2d)`` restores the first array's
    shape."""
    first = arrays[0]
    shape = first.shape
    n = first.size
    pad = (-n) % (LANE * 8)
    views = []
    for a in arrays:
        flat = a.reshape(-1).astype(jnp.float32)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        views.append(flat.reshape(-1, LANE))
    rows = views[0].shape[0]

    def unpad(x2d):
        return x2d.reshape(-1)[:n].reshape(shape)

    return views, rows, unpad


def default_use_pallas():
    """Shared kernel-dispatch rule for FusedAdam/FusedLamb: Pallas on a
    single-chip TPU; under a multi-chip GSPMD mesh the kernel must go
    through shard_map (the engine wires that up), so default to the
    XLA-fused path there."""
    return jax.default_backend() == "tpu" and jax.device_count() == 1


def resolve_fused_kernel(use_pallas, moments_dtype):
    """What a FusedAdam/FusedLamb apply runs: ``"pallas"`` (compiled
    kernel), ``"interpret"`` (the kernel under the Pallas interpreter —
    a forced kernel off the TPU, parity/debug only) or ``"xla"``.
    ``use_pallas`` None is "auto": :func:`default_use_pallas`, and the
    XLA path for non-fp32 moments (the kernel is fp32-state). Forcing
    the kernel onto non-fp32 moments raises — no quiet XLA fallback."""
    fp32_state = moments_dtype == jnp.float32
    if use_pallas and not fp32_state:
        raise ValueError(
            "fused optimizer kernel forced to 'pallas' with moments_dtype "
            "{}: the Pallas apply kernel is fp32-state — use fused_kernel "
            "'auto'/'xla' or fp32 moments".format(
                jnp.dtype(moments_dtype).name))
    if use_pallas is None:
        use_pallas = fp32_state and default_use_pallas()
    if not use_pallas:
        return "xla"
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def row_mask(block_shape, block_index, total_rows):
    """Bool mask of shape ``block_shape`` marking rows that exist in the
    logical array (guards reductions in ragged last blocks). Use with
    ``jnp.where`` — multiplicative masking would keep NaN/Inf garbage
    (0 * NaN = NaN)."""
    base = block_index * block_shape[0]
    row_ids = jax.lax.broadcasted_iota(jnp.int32, block_shape, 0) + base
    return row_ids < total_rows
