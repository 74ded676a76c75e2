"""A sparse expert layer for any model file: a float32 router over ALL
the model's experts, a range of them held here, no token dropped.

``route`` scores every expert (``sigmoid(x W_g)``, or ``softmax(x W_g)``
over all of them where the model says ``scoring="softmax"``; float32
whatever the activations are), chooses ``top_k`` of them per token by
score PLUS a per-expert selection bias (the bias shifts the choice
only), and weights the chosen by their scores, renormalised over the
chosen where the model says so. ``expert_ffn`` then computes the part of the layer's
result that the experts HELD here give (``experts_held``: a range
``(first, past the last)`` of expert ids; all of them on a chip that
holds the whole layer, a share under expert parallelism): the routed
rows of its own experts sorted by expert (``moe.dispatch``), the gated
MLP of each expert over its group of rows as two grouped matmuls
(ops/pallas/moe.py ``moe_gmm``, or its ``lax.ragged_dot`` oracle off
the TPU), and the weighted sum back per token (``moe.combine``). A row
chosen for an expert held elsewhere contributes nothing here: the
shares of all holders add up to the whole layer's result, and a chip
that holds every expert runs with no exchange at all. There is no
capacity: an expert takes every row routed to it.

Beside the result comes the LOAD, ``(2, experts)`` int32: the rows
each expert got, and 1 where it got any (the matrices of an expert
with no row are never read), which a serving program sums over its
expert layers and returns with its tokens (inference/decoder.py
``counters``).
"""
import jax
import jax.numpy as jnp
import numpy as np

from .pallas import moe as kernels
from .pallas.common import default_interpret


SCORINGS = {"sigmoid": jax.nn.sigmoid,
            "softmax": lambda z: jax.nn.softmax(z, axis=-1)}


def route(x, router_w, expert_bias, top_k, norm_topk_prob=True,
          scaling=1.0, norm_eps=1e-6, scoring="sigmoid"):
    """x (T, d); router_w (d, E) float32; expert_bias (E,) float32 or
    None; ``norm_eps``: what the model's code adds to the chosen
    scores' sum; ``scoring``: each expert's score of its own
    (``"sigmoid"``) or a probability over all experts (``"softmax"``).
    -> (chosen (T, top_k) int32, weights (T, top_k) float32)."""
    with jax.named_scope("moe.route"):
        scores = SCORINGS[scoring](jnp.dot(
            x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        biased = scores if expert_bias is None else \
            scores + expert_bias.astype(jnp.float32)
        _, chosen = jax.lax.top_k(biased, top_k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if norm_topk_prob:
            weights = weights / (weights.sum(-1, keepdims=True) + norm_eps)
        return chosen.astype(jnp.int32), weights * scaling


def load_attrs(load):
    """What a decoder makes of a launch's summed load (host side,
    after the fetch) for its ``moe.load`` span: ``rows`` routed,
    ``experts_hit`` (expert, layer) pairs that got any,
    ``hottest_rows`` of the expert that got most."""
    load = np.asarray(load)
    return {"rows": int(load[0].sum()), "experts_hit": int(load[1].sum()),
            "hottest_rows": int(load[0].max())}


def _use_pallas(kernel):
    if kernel == "auto":
        return not default_interpret()
    return kernel == "pallas"


def expert_ffn(x, chosen, weights, w13, w2, experts_held, num_experts,
               kernel="auto"):
    """The held experts' part of ``sum_j weights[:, j] *
    FFN_chosen[:, j](x)``. x (T, d); chosen, weights (T, k); w13 (held,
    d, 2 ff): each expert's gate and up matrices side by side; w2
    (held, ff, d). -> (out (T, d) in x's dtype, load (2, num_experts)
    int32)."""
    T, d = x.shape
    k = chosen.shape[1]
    first, past = experts_held
    held = past - first
    assert w13.shape[0] == w2.shape[0] == held, \
        "{} experts held, weights of {}".format(held, w13.shape[0])
    ff = w2.shape[1]
    gmm = kernels.moe_gmm if _use_pallas(kernel) else kernels.moe_gmm_xla
    with jax.named_scope("moe.dispatch"):
        local = chosen.reshape(-1) - first                     # (T k,)
        mine = (local >= 0) & (local < held)
        # rows of experts held elsewhere sort past every group
        key = jnp.where(mine, local, held)
        order = jnp.argsort(key, stable=True)
        sizes = (key[:, None] == jnp.arange(held)[None, :]).sum(
            0, dtype=jnp.int32)
        rows = T * k
        tm = kernels.row_tile(rows)
        padded = -(-rows // tm) * tm
        token = jnp.pad(order // k, (0, padded - rows))
        lhs = jnp.take(x, token, axis=0)                       # (m, d)
        extra = {}
        if gmm is kernels.moe_gmm:
            extra["metadata"] = kernels.group_metadata(sizes, padded, tm)
    h = gmm(lhs, w13, sizes, **extra)                          # (m, 2 ff)
    act = (jax.nn.silu(h[:, :ff].astype(jnp.float32)) *
           h[:, ff:].astype(jnp.float32)).astype(x.dtype)
    y = gmm(act, w2, sizes, **extra)                           # (m, d)
    with jax.named_scope("moe.combine"):
        # where each (token, choice) went among the sorted rows
        where = jnp.zeros((rows,), jnp.int32).at[order].set(
            jnp.arange(rows, dtype=jnp.int32))
        y = jnp.take(y, where, axis=0).reshape(T, k, d)
        # a select: a row past the groups holds anything
        part = jnp.where(mine.reshape(T, k, 1),
                         y.astype(jnp.float32) * weights[..., None], 0.0)
        out = part.sum(1).astype(x.dtype)
        load = jnp.zeros((2, num_experts), jnp.int32)
        load = load.at[0, first:past].set(sizes)
        load = load.at[1, first:past].set((sizes > 0).astype(jnp.int32))
    return out, load
