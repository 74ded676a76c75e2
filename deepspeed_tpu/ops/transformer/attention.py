"""Attention dispatch: Pallas flash kernel on TPU, jnp reference elsewhere."""
import functools as _functools
import jax
import jax.numpy as jnp

NEG_INF = -1e30


def reference_causal_attention(q, k, v, sm_scale=None):
    """Plain XLA attention, (b, s, h, d) layout; numerically the spec for the
    flash kernel (mirrors reference tests test_cuda_forward's python BERT)."""
    b, s, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32) * scale
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return ctx.astype(q.dtype)


# ds_config spellings of transformer.flash_attention (bools are the
# legacy form: true -> "auto", false -> "xla").
FLASH_BACKEND_MODES = ("auto", "pallas", "xla")

_warned_forced_pallas = set()


def resolve_flash_backend(requested):
    """Resolve the ``transformer.flash_attention`` tri-state to what this
    process will actually run: ``"pallas"`` (compiled kernel, TPU),
    ``"interpret"`` (kernel under the Pallas interpreter — forced
    ``"pallas"`` on a non-TPU backend, parity/debug speed), or ``"xla"``
    (the reference oracle). ``"auto"`` picks the kernel exactly on TPU and
    falls back to XLA elsewhere; forcing ``"pallas"`` off-TPU warns LOUDLY
    once instead of silently flipping the dense flag."""
    if isinstance(requested, bool):
        requested = "auto" if requested else "xla"
    if requested not in FLASH_BACKEND_MODES:
        raise ValueError(
            f"flash_attention backend {requested!r}: want a bool or one of "
            f"{FLASH_BACKEND_MODES}")
    if requested == "xla":
        return "xla"
    backend = jax.default_backend()
    if backend == "tpu":
        return "pallas"
    if requested == "auto":
        return "xla"
    if backend not in _warned_forced_pallas:
        _warned_forced_pallas.add(backend)
        from ...utils.logging import logger
        logger.warning(
            "transformer.flash_attention: 'pallas' forced on the %s "
            "backend — running the flash kernel under the Pallas "
            "INTERPRETER (orders of magnitude slower; parity/debug only). "
            "Use 'auto' to take the XLA oracle off-TPU.", backend)
    return "interpret"


def _batch_axes():
    from ...parallel.topology import (DATA_AXIS, DATA_REPLICA_AXIS,
                                      DATA_SHARD_AXIS)
    return (DATA_AXIS, DATA_REPLICA_AXIS, DATA_SHARD_AXIS)


def causal_attention(q, k, v, use_flash=True, sm_scale=None, interpret=None,
                     backend=None, mesh=None):
    """(b, s, h, d) in, (b, s, h, d) out.

    ``backend``: a RESOLVED tri-state ("pallas"|"interpret"|"xla", see
    :func:`resolve_flash_backend`) — wins over the legacy ``use_flash``
    bool when given. ``mesh``: the mesh the calling program spans; the
    kernel then runs under a shard_map over it (ops/pallas/common.py
    ``shard_kernel``), batch rows split over the data axes and heads
    over ``model``."""
    if backend is None:
        if not use_flash:
            backend = "xla"
        elif jax.default_backend() == "tpu":
            backend = "pallas"
        else:
            # explicit interpret=True is a direct (test) request for the
            # kernel — no config involved, so no loud warning here
            backend = "interpret" if interpret else "xla"
    if backend == "xla":
        return reference_causal_attention(q, k, v, sm_scale)
    # (b,s,h,d)-native kernel: no head fold/unfold relayout (that
    # transpose costs more than the attention math at d_head 64);
    # block sizes resolve by width inside the op (auto_blocks), so
    # wide models (gpt2-xl's h*d=1600) stay inside scoped vmem.
    from ..pallas.common import shard_kernel, split_axes
    from .flash_attention import flash_attention_bshd
    kernel = _functools.partial(
        flash_attention_bshd, sm_scale=sm_scale, causal=True,
        interpret=(backend == "interpret") or bool(interpret))
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        from ...parallel.topology import MODEL_AXIS
        spec = P(split_axes(mesh, _batch_axes(), q.shape[0]), None,
                 split_axes(mesh, (MODEL_AXIS,), q.shape[2]), None)
        kernel = shard_kernel(kernel, mesh, (spec,) * 3, spec)
    return kernel(q, k, v)


def fused_causal_attention(x, ln_scale, ln_bias, qkv_w, qkv_b, num_heads,
                           interpret=False, mesh=None):
    """LN + QKV projection + causal flash attention as one op
    (flash_attention.fused_ln_qkv_attention): ``x`` (b, s, d) in, the
    attention context (b, s, d) out. Under ``mesh`` batch rows split
    over the data axes and the four weight operands enter replicated
    (their cotangents sum over the axes that split the batch); a
    ``model`` axis replicates the op — callers keep it for meshes
    without tensor parallelism."""
    from ..pallas.common import shard_kernel, split_axes
    from .flash_attention import fused_ln_qkv_attention
    kernel = _functools.partial(fused_ln_qkv_attention,
                                num_heads=num_heads, interpret=interpret)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        rows = P(split_axes(mesh, _batch_axes(), x.shape[0]))
        kernel = shard_kernel(kernel, mesh, (rows,) + (P(),) * 4, rows)
    return kernel(x, ln_scale, ln_bias, qkv_w, qkv_b)


@_functools.lru_cache(maxsize=None)
def causal_attention_fn(use_flash=True, backend=None):
    """Hashable, cached (q, k, v) -> ctx callable — the form
    sequence_parallel_attention's jit cache needs (a fresh partial per call
    would miss that cache every time)."""
    return _functools.partial(causal_attention, use_flash=use_flash,
                              backend=backend)
