"""LAMB optimizer as a pure pytree transform.

Reference parity: csrc/lamb/fused_lamb_cuda_kernel.cu +
deepspeed/ops/lamb/fused_lamb.py. Per-tensor trust ratio
``||p|| / ||update||`` clamped to [min_coeff, max_coeff]; the reference's
two-stage norm reduction kernel is just jnp.linalg-style reductions under XLA
(sharded norms psum automatically under GSPMD).
"""
import jax
import jax.numpy as jnp


def lamb_init(params, moments_dtype=jnp.float32):
    """``moments_dtype``: storage dtype of exp_avg/exp_avg_sq — bf16
    halves the moment HBM and its per-step traffic (the update math
    always runs fp32); same lever as FusedAdam's (see
    docs/roofline_gpt2_medium_v5e.md)."""
    zeros = lambda p: jnp.zeros(p.shape, dtype=moments_dtype)
    return {
        "step": jnp.zeros((), dtype=jnp.int32),
        "exp_avg": jax.tree_util.tree_map(zeros, params),
        "exp_avg_sq": jax.tree_util.tree_map(zeros, params),
    }


def lamb_update(grads, state, params, lr, beta1, beta2, eps, weight_decay,
                bias_correction=True, max_coeff=10.0, min_coeff=0.01,
                eps_inside_sqrt=False, use_pallas=False, interpret=False):
    """One LAMB step over a pytree; returns (new_params, new_state)."""
    step = state["step"] + 1
    if bias_correction:
        bc1 = 1.0 - jnp.power(beta1, step.astype(jnp.float32))
        bc2 = 1.0 - jnp.power(beta2, step.astype(jnp.float32))
    else:
        bc1 = bc2 = 1.0

    def pallas_leaf(p, g, m, v):
        from .pallas_lamb import fused_lamb_shard
        if m.dtype != jnp.float32:      # pallas kernel is fp32-state
            raise ValueError(
                "pallas LAMB path requires fp32 moments; "
                f"got {m.dtype} (set use_pallas=False)")
        return fused_lamb_shard(p, g, m, v, lr, beta1, beta2, eps,
                                weight_decay, bc1, bc2,
                                max_coeff=max_coeff, min_coeff=min_coeff,
                                eps_inside_sqrt=eps_inside_sqrt,
                                interpret=interpret)

    def leaf(p, g, m, v):
        g = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        m_new = beta1 * m.astype(jnp.float32) + (1.0 - beta1) * g
        v_new = beta2 * v.astype(jnp.float32) + (1.0 - beta2) * (g * g)
        if eps_inside_sqrt:
            denom = jnp.sqrt(v_new / bc2 + eps)
        else:
            denom = jnp.sqrt(v_new / bc2) + eps
        update = (m_new / bc1) / denom + weight_decay * p32
        p_norm = jnp.sqrt(jnp.sum(p32 * p32))
        u_norm = jnp.sqrt(jnp.sum(update * update))
        trust_ratio = jnp.where(
            (p_norm > 0) & (u_norm > 0),
            jnp.clip(p_norm / u_norm, min_coeff, max_coeff), 1.0)
        p_new = p32 - lr * trust_ratio * update
        return (p_new.astype(p.dtype), m_new.astype(m.dtype),
                v_new.astype(v.dtype))

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["exp_avg"])
    flat_v = treedef.flatten_up_to(state["exp_avg_sq"])
    kernel = pallas_leaf if use_pallas else leaf
    out = [kernel(p, g, m, v) for p, g, m, v in
           zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
    return new_params, {"step": step, "exp_avg": new_m, "exp_avg_sq": new_v}


class FusedLamb:
    """Optimizer handle over :func:`lamb_update`
    (reference deepspeed/ops/lamb/fused_lamb.py)."""

    name = "lamb"
    supports_zero = True

    _DTYPES = {"fp32": jnp.float32, "float32": jnp.float32,
               "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16}

    def __init__(self, lr=1e-3, bias_correction=True, betas=(0.9, 0.999),
                 eps=1e-8, eps_inside_sqrt=False, weight_decay=0.0,
                 max_grad_norm=0.0, max_coeff=10.0, min_coeff=0.01,
                 amsgrad=False, use_pallas=None, moments_dtype=None,
                 **kwargs):
        if amsgrad:
            raise RuntimeError("FusedLamb does not support the AMSGrad variant.")
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
                "pallas LAMB kernel is fp32-state); drop one of the two")
        self.lr = lr
        self.bias_correction = bias_correction
        self.betas = tuple(betas)
        self.eps = eps
        self.eps_inside_sqrt = eps_inside_sqrt
        self.weight_decay = weight_decay
        self.max_grad_norm = max_grad_norm
        self.max_coeff = max_coeff
        self.min_coeff = min_coeff

    def init_state(self, params):
        return lamb_init(params, self.moments_dtype)

    def hyperparams(self):
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
        return lamb_update(grads, state, params, lr, beta1, beta2, eps,
                           weight_decay, bias_correction=self.bias_correction,
                           max_coeff=self.max_coeff, min_coeff=self.min_coeff,
                           eps_inside_sqrt=self.eps_inside_sqrt,
                           use_pallas=kernel != "xla",
                           interpret=kernel == "interpret")
