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

A chip that holds a true share works on the rows that land on it: the
sort of the ``T x top_k`` integer keys puts them first, and everything
as wide as a row (the gather, both grouped matmuls, the activation, the
weighted sum) runs on ``share_capacity`` rows, twice the share's even
part of the routed rows, in as many passes over the held rows as it
takes: one unless the router sent this chip more than that, a loop
whose trip count comes from the data, so that the contract above
stands whatever the router does. A pass adds each of its rows, times
its float32 weight, into its token's float32 sum. With every expert
held the capacity is all the rows and the layer is the single pass it
always was: no loop, and the same grouped matmuls.

Beside the result comes the LOAD, ``(2, experts)`` int32: the rows
each expert got, and 1 where it got any (the matrices of an expert
with no row are never read), which a serving program sums over its
expert layers and returns with its tokens (inference/decoder.py
``counters``); a model that holds a share adds ``share_passes`` of each
layer's load (``moe.load[passes]``).
"""
import functools

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


# What a chip that holds a SHARE of the experts works on at a time, over
# its even part of the routed rows. The even part alone would overflow
# every other launch; twice it holds all that the cell measured so far
# has sent one chip (command-a-plus-serve.rag, 16 of 128 held: layer 3's
# hottest expert takes 5.6 times the mean rows, the 16 together 12.5% +/-
# 0.3 of a chunk's; PERF.md section 6, PRs 50 and 52), and a launch past
# it costs a pass more, never a row.
SHARE_HEADROOM = 2


def share_capacity(rows, held, num_experts):
    """The rows ``expert_ffn`` works on at a time where it holds
    ``held`` of ``num_experts``, of ``rows`` (token, choice) pairs
    routed: the even part times ``SHARE_HEADROOM`` in whole row tiles,
    and never more than the (padded) rows there are, which it is where
    every expert is held."""
    tm = kernels.row_tile(rows)
    even = -(-rows * held // num_experts)
    return min(-(-rows // tm), -(-SHARE_HEADROOM * even // tm)) * tm


def _passes(n_held, capacity):
    return jnp.maximum(-(-n_held // capacity), 1).astype(jnp.int32)


def share_passes(load, rows, experts_held, num_experts):
    """The passes ``expert_ffn`` made over the layer whose ``load`` it
    returned, ``rows`` (token, choice) pairs having been routed: 1
    unless more of them landed on the held experts than
    ``share_capacity``. For a model's counters (``moe.load[passes]``)."""
    first, past = experts_held
    return _passes(load[0, first:past].sum(),
                   share_capacity(rows, past - first, num_experts))


@functools.partial(jax.jit, static_argnames=("capacity", "gmm"))
def _share_pass(p, acc, x, weights, w13, w2, order, starts, ends, *,
                capacity, gmm):
    """Pass ``p`` of ``_share_ffn``: the sorted rows ``[p capacity, (p +
    1) capacity)`` through the held experts, each times its weight added
    into its token's row of ``acc`` (T, d) float32. Jitted, so that a
    program's layers share ONE traced and lowered pair of kernels."""
    k = weights.shape[1]
    ff = w2.shape[1]
    lo = p * capacity
    with jax.named_scope("moe.dispatch"):
        at = jax.lax.dynamic_slice_in_dim(order, lo, capacity)
        token = at // k
        # each group's overlap with this pass's rows
        part = jnp.clip(ends, lo, lo + capacity) - \
            jnp.clip(starts, lo, lo + capacity)
        lhs = jnp.take(x, token, axis=0)                       # (C, d)
        extra = {}
        if gmm is kernels.moe_gmm:
            extra["metadata"] = kernels.group_metadata(
                part, capacity, kernels.row_tile(capacity))
    h = gmm(lhs, w13, part, **extra)                           # (C, 2 ff)
    act = (jax.nn.silu(h[:, :ff].astype(jnp.float32)) *
           h[:, ff:].astype(jnp.float32)).astype(x.dtype)
    y = gmm(act, w2, part, **extra)                            # (C, d)
    with jax.named_scope("moe.combine"):
        # a select: a row past the held ones holds anything
        real = (lo + jnp.arange(capacity) < ends[-1])[:, None]
        scaled = y.astype(jnp.float32) * \
            jnp.take(weights.reshape(-1), at)[:, None]
        return acc.at[token].add(jnp.where(real, scaled, 0.0))


def _share_ffn(x, weights, w13, w2, order, sizes, capacity, gmm):
    """``expert_ffn`` behind the sort where the held experts are a true
    share: only the first ``sizes.sum()`` of the sorted rows are this
    chip's, so they are taken ``capacity`` at a time, as many passes as
    it takes (one, unless the router is skewed towards this chip), and
    nothing of a row's width is made for the rows of other chips.
    -> (T, d) float32."""
    T, d = x.shape
    rows = weights.size
    ends = jnp.cumsum(sizes)
    # whole capacities of sorted rows; what pads them is selected out
    order = jnp.pad(order, (0, -(-rows // capacity) * capacity - rows))
    one_pass = functools.partial(
        _share_pass, x=x, weights=weights, w13=w13, w2=w2, order=order,
        starts=ends - sizes, ends=ends, capacity=capacity, gmm=gmm)
    return jax.lax.fori_loop(0, _passes(ends[-1], capacity), one_pass,
                             jnp.zeros((T, d), jnp.float32))


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
        capacity = share_capacity(rows, held, num_experts)
    if held < num_experts:
        out = _share_ffn(x, weights, w13, w2, order, sizes, capacity, gmm)
    else:
        # every routed row is real and the capacity is all of them,
        # padded: ONE pass, made from this frame (a frame more under
        # ``gmm`` moves Mosaic's lowering on the stack, and the size of
        # this one does too: PERF.md section 6, PRs 40 and 44)
        with jax.named_scope("moe.dispatch"):
            token = jnp.pad(order // k, (0, capacity - rows))
            lhs = jnp.take(x, token, axis=0)                   # (m, d)
            extra = {}
            if gmm is kernels.moe_gmm:
                extra["metadata"] = kernels.group_metadata(
                    sizes, capacity, kernels.row_tile(rows))
        h = gmm(lhs, w13, sizes, **extra)                      # (m, 2 ff)
        act = (jax.nn.silu(h[:, :ff].astype(jnp.float32)) *
               h[:, ff:].astype(jnp.float32)).astype(x.dtype)
        y = gmm(act, w2, sizes, **extra)                       # (m, d)
        with jax.named_scope("moe.combine"):
            # where each (token, choice) went among the sorted rows
            where = jnp.zeros((rows,), jnp.int32).at[order].set(
                jnp.arange(rows, dtype=jnp.int32))
            y = jnp.take(y, where, axis=0).reshape(T, k, d)
            # a select: a row past the groups holds anything
            out = jnp.where(mine.reshape(T, k, 1),
                            y.astype(jnp.float32) * weights[..., None],
                            0.0).sum(1)
    with jax.named_scope("moe.combine"):
        out = out.astype(x.dtype)
        load = jnp.zeros((2, num_experts), jnp.int32)
        load = load.at[0, first:past].set(sizes)
        load = load.at[1, first:past].set((sizes > 0).astype(jnp.int32))
    return out, load
