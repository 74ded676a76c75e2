"""Adam/AdamW as pure pytree transforms.

Reference parity: csrc/adam/multi_tensor_adam.cu + deepspeed/ops/adam/
fused_adam.py. The reference needs a multi-tensor-apply CUDA kernel to fuse
per-tensor launches; under XLA one jitted tree_map over the (sharded) state
compiles to fused fusions per shard, and the hot flat-shard path is upgraded
to a Pallas kernel in ops/adam/pallas_adam.py.

State layout: {"step": i32, "exp_avg": tree, "exp_avg_sq": tree} — matching
the reference's per-param ``exp_avg``/``exp_avg_sq`` naming for checkpoint
compatibility.
"""
import jax
import jax.numpy as jnp


def adam_init(params, moments_dtype=jnp.float32):
    """``moments_dtype``: storage dtype of exp_avg/exp_avg_sq. bf16 halves
    the moment HBM (8N -> 4N bytes) — on a 16 GB chip that buys
    micro-batch (see docs/roofline_gpt2_medium_v5e.md); the update math
    always runs in fp32 (moments are cast up, computed, cast back)."""
    zeros = lambda p: jnp.zeros(p.shape, dtype=moments_dtype)
    return {
        "step": jnp.zeros((), dtype=jnp.int32),
        "exp_avg": jax.tree_util.tree_map(zeros, params),
        "exp_avg_sq": jax.tree_util.tree_map(zeros, params),
    }


def adam_update(grads, state, params, lr, beta1, beta2, eps, weight_decay,
                bias_correction=True, adam_w_mode=True, use_pallas=False,
                interpret=False):
    """One Adam step over a pytree. All hyperparams may be traced scalars.

    Returns (new_params, new_state). With ``adam_w_mode`` weight decay is
    decoupled (AdamW); otherwise it is L2-added to the gradient.
    """
    step = state["step"] + 1
    if bias_correction:
        bc1 = 1.0 - jnp.power(beta1, step.astype(jnp.float32))
        bc2 = 1.0 - jnp.power(beta2, step.astype(jnp.float32))
    else:
        bc1 = bc2 = 1.0

    if use_pallas:
        from .pallas_adam import fused_adam_shard
        def leaf(p, g, m, v):
            if m.dtype != jnp.float32:      # pallas kernel is fp32-state
                raise ValueError(
                    "pallas Adam path requires fp32 moments; "
                    f"got {m.dtype} (set use_pallas=False)")
            return fused_adam_shard(p, g.astype(jnp.float32), m, v, lr, beta1,
                                    beta2, eps, weight_decay, bc1, bc2,
                                    adam_w_mode, interpret=interpret)
    else:
        def leaf(p, g, m, v):
            g = g.astype(jnp.float32)
            p32 = p.astype(jnp.float32)
            if not adam_w_mode:
                g = g + weight_decay * p32
            m_new = beta1 * m.astype(jnp.float32) + (1.0 - beta1) * g
            v_new = beta2 * v.astype(jnp.float32) + (1.0 - beta2) * (g * g)
            update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
            if adam_w_mode:
                update = update + weight_decay * p32
            p_new = p32 - lr * update
            return (p_new.astype(p.dtype), m_new.astype(m.dtype),
                    v_new.astype(v.dtype))

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["exp_avg"])
    flat_v = treedef.flatten_up_to(state["exp_avg_sq"])
    out = [leaf(p, g, m, v) for p, g, m, v in
           zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
    return new_params, {"step": step, "exp_avg": new_m, "exp_avg_sq": new_v}


class FusedAdam:
    """Optimizer handle with mutable hyperparams (read each host step) over
    the pure :func:`adam_update` (reference deepspeed/ops/adam/fused_adam.py).
    """

    name = "adam"
    supports_zero = True

    _DTYPES = {"fp32": jnp.float32, "float32": jnp.float32,
               "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16}

    def __init__(self, lr=1e-3, bias_correction=True, betas=(0.9, 0.999),
                 eps=1e-8, adam_w_mode=True, weight_decay=0.0, amsgrad=False,
                 use_pallas=None, moments_dtype=None, **kwargs):
        if amsgrad:
            raise RuntimeError("FusedAdam does not support the AMSGrad variant.")
        self.lr = lr
        self.bias_correction = bias_correction
        self.betas = tuple(betas)
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.weight_decay = weight_decay
        self.use_pallas = use_pallas
        if isinstance(moments_dtype, str):
            try:
                moments_dtype = self._DTYPES[moments_dtype.lower()]
            except KeyError:
                raise ValueError(
                    f"moments_dtype={moments_dtype!r}: want one of "
                    f"{sorted(self._DTYPES)}") from None
        self.moments_dtype = moments_dtype or jnp.float32
        if use_pallas and self.moments_dtype != jnp.float32:
            raise ValueError(
                "use_pallas=True is incompatible with bf16 moments (the "
                "pallas Adam kernel is fp32-state); drop one of the two")

    def init_state(self, params):
        return adam_init(params, self.moments_dtype)

    def hyperparams(self):
        """Traced-scalar hyperparams fed to the jitted step each iteration."""
        return {
            "lr": float(self.lr),
            "beta1": float(self.betas[0]),
            "beta2": float(self.betas[1]),
            "eps": float(self.eps),
            "weight_decay": float(self.weight_decay),
        }

    def resolved_kernel(self):
        """What :meth:`update` runs: "pallas" | "interpret" | "xla"
        (ops/pallas_utils.resolve_fused_kernel)."""
        from ..pallas_utils import resolve_fused_kernel
        return resolve_fused_kernel(self.use_pallas, self.moments_dtype)

    def update(self, grads, state, params, lr, beta1, beta2, eps, weight_decay):
        kernel = self.resolved_kernel()
        return adam_update(grads, state, params, lr, beta1, beta2, eps,
                           weight_decay, bias_correction=self.bias_correction,
                           adam_w_mode=self.adam_w_mode,
                           use_pallas=kernel != "xla",
                           interpret=kernel == "interpret")

    def state_dict_names(self):
        return ["exp_avg", "exp_avg_sq", "step"]


class DeepSpeedCPUAdam(FusedAdam):
    """Host-offloaded Adam (reference csrc/adam/cpu_adam.cpp).

    Same math as FusedAdam; the engine places optimizer state and fp32 master
    params in host memory and runs this update on the CPU backend, streaming
    updated params back to HBM (ZeRO-Offload). The native AVX path lives in
    ops/adam/cpu_adam_native.py and is used automatically when built.
    """

    name = "cpu_adam"
    placement = "cpu"

    def __init__(self, *args, use_native=None, **kwargs):
        kwargs.pop("use_pallas", None)
        super().__init__(*args, use_pallas=False, **kwargs)
        self.use_native = use_native

    def update(self, grads, state, params, lr, beta1, beta2, eps, weight_decay):
        use_native = self.use_native
        if use_native is None:
            # The SIMD kernel's win comes from OpenMP across host cores; on
            # a 1-2 core host the pure_callback round-trip costs more than
            # the kernel saves (measured: tests/perf/adam_test.py), so
            # default to XLA there. Count the cores this process can USE
            # (affinity/cgroup aware, same as omp_get_max_threads), not the
            # machine total.
            import os
            try:
                cores = len(os.sched_getaffinity(0))
            except AttributeError:  # non-Linux
                cores = os.cpu_count() or 1
            use_native = cores >= 4
        if use_native:
            try:
                from .cpu_adam_native import native_adam_update
                return native_adam_update(
                    grads, state, params, lr, beta1, beta2, eps, weight_decay,
                    bias_correction=self.bias_correction,
                    adam_w_mode=self.adam_w_mode)
            except Exception as e:
                if self.use_native:
                    raise
                if not getattr(self, "_warned_fallback", False):
                    self._warned_fallback = True
                    from ...utils.logging import logger
                    logger.warning(
                        "DeepSpeedCPUAdam: native host kernel unavailable "
                        "(%s: %s); falling back to the XLA path",
                        type(e).__name__, e)
        return super().update(grads, state, params, lr, beta1, beta2, eps,
                              weight_decay)
