"""GPT-2 as the paper describes it, in plain ``jax.numpy`` and float32.

Radford et al. 2019 (and the released model): learned token and
position embeddings, pre-LayerNorm blocks of causal multi-head
attention and a 4x tanh-GELU MLP, a final LayerNorm, the output head
tied to the token embedding; loss = mean next-token cross-entropy.
No kernel, no cache, no batching tricks, and no call into
``deepspeed_tpu``: the yardstick ``correct`` is decided against.

Weights come from ``--seed`` by the recipe stated in ``draw_weights``
(the one the program documents for itself: normal(0, 0.02), residual
output projections scaled by 1/sqrt(2 L), positions at half that
width, one ``numpy.random.RandomState(seed)`` stream in the stated
order). Nothing the program has made is read.

``rounding`` computes the same mathematics with the operands of every
weight matmul rounded to a lower precision (accumulation stays
float32): "bfloat16" is the precision the configurations state, "fp8"
(e4m3: 4 significant bits) the step below it, the control that
``correct`` has to reject.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5
INIT_STD = 0.02


def draw_weights(model, seed):
    """Stacked float32 numpy weights for ``model`` (a dict with
    n_layer, n_embd, n_head, n_positions, padded_vocab_size)."""
    L, d = model["n_layer"], model["n_embd"]
    v, s = model["padded_vocab_size"], model["n_positions"]
    rng = np.random.RandomState(seed)
    proj_std = INIT_STD / math.sqrt(2.0 * L)

    def normal(std, *shape):
        return (rng.randn(*shape) * std).astype(np.float32)

    per_layer = []
    for _ in range(L):
        per_layer.append({
            "qkv_w": normal(INIT_STD, d, 3 * d),
            "proj_w": normal(proj_std, d, d),
            "fc_w": normal(INIT_STD, d, 4 * d),
            "fc2_w": normal(proj_std, 4 * d, d),
        })
    layers = {k: np.stack([lw[k] for lw in per_layer])
              for k in per_layer[0]}
    for name, width in (("qkv_b", 3 * d), ("proj_b", d), ("fc_b", 4 * d),
                        ("fc2_b", d), ("ln1_b", d), ("ln2_b", d)):
        layers[name] = np.zeros((L, width), np.float32)
    for name in ("ln1_g", "ln2_g"):
        layers[name] = np.ones((L, d), np.float32)
    return {
        "layers": layers,
        "wte": normal(INIT_STD, v, d),
        "wpe": normal(INIT_STD / 2, s, d),
        "lnf_g": np.ones((d,), np.float32),
        "lnf_b": np.zeros((d,), np.float32),
    }


def _round_fp8(x):
    """Round to 4 significant bits (fp8 e4m3's mantissa; its exponent
    range is not modelled), straight-through for the gradient."""
    m, e = jnp.frexp(jax.lax.stop_gradient(x))
    rounded = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    return x + (rounded - jax.lax.stop_gradient(x))


_ROUNDINGS = {
    None: lambda x: x,
    "bfloat16": lambda x: x.astype(jnp.bfloat16).astype(jnp.float32),
    "fp8": _round_fp8,
}


def _layer_norm(x, g, b):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def hidden_states(w, ids, n_head, rounding=None):
    """ids (b, s) int32 -> final hidden states (b, s, d), float32."""
    rnd = _ROUNDINGS[rounding]
    mm = lambda x, m: rnd(x) @ rnd(m)
    b, s = ids.shape
    d = w["wte"].shape[1]
    dh = d // n_head
    causal = jnp.tril(jnp.ones((s, s), bool))
    x = w["wte"][ids] + w["wpe"][:s]

    def block(x, lw):
        h = _layer_norm(x, lw["ln1_g"], lw["ln1_b"])
        qkv = mm(h, lw["qkv_w"]) + lw["qkv_b"]
        q, k, v = (t.reshape(b, s, n_head, dh)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, d)
        x = x + mm(ctx, lw["proj_w"]) + lw["proj_b"]
        h = _layer_norm(x, lw["ln2_g"], lw["ln2_b"])
        x = x + mm(_gelu(mm(h, lw["fc_w"]) + lw["fc_b"]),
                   lw["fc2_w"]) + lw["fc2_b"]
        return x, None

    # checkpointed so that the backward pass keeps one activation per
    # layer, not every layer's attention probabilities
    x, _ = jax.lax.scan(jax.checkpoint(block), x, w["layers"])
    return _layer_norm(x, w["lnf_g"], w["lnf_b"])


@functools.partial(jax.jit, static_argnames=("n_head", "rounding"))
def logits_at(w, ids, positions, n_head, rounding=None):
    """Logits (b, len(positions), V) at the given positions of each
    row; a row's padding after its last compared position changes
    nothing before it, the model being causal."""
    with jax.default_matmul_precision("highest"):
        rnd = _ROUNDINGS[rounding]
        x = hidden_states(w, ids, n_head, rounding)
        x = jnp.take_along_axis(x, positions[..., None], axis=1)
        return rnd(x) @ rnd(w["wte"]).T


def _loss(w, ids, n_head, rounding):
    rnd = _ROUNDINGS[rounding]
    x = hidden_states(w, ids, n_head, rounding)
    logits = rnd(x[:, :-1]) @ rnd(w["wte"]).T
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
    return -ll.sum(), ll.size


@functools.partial(jax.jit, static_argnames=("n_head", "rounding"))
def _loss_and_grad_sum(w, ids, n_head, rounding):
    with jax.default_matmul_precision("highest"):
        (total, count), grads = jax.value_and_grad(
            _loss, has_aux=True)(w, ids, n_head, rounding)
        return total, count, grads


@functools.partial(jax.jit, static_argnames=("n_head", "rounding"))
def _loss_sum(w, ids, n_head, rounding):
    with jax.default_matmul_precision("highest"):
        return _loss(w, ids, n_head, rounding)


@functools.partial(jax.jit, donate_argnums=(0,))
def _adam_first_step(w, grads, lr, beta1, beta2, eps):
    """Adam (Kingma & Ba) from zero moments, bias-corrected, no weight
    decay: the first step's m_hat = g and v_hat = g^2."""
    def update(p, g):
        m_hat = ((1 - beta1) * g) / (1 - beta1)
        v_hat = ((1 - beta2) * g * g) / (1 - beta2)
        return p - lr * m_hat / (jnp.sqrt(v_hat) + eps)
    return jax.tree_util.tree_map(update, w, grads)


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(acc, grads):
    return jax.tree_util.tree_map(jnp.add, acc, grads)


MATRICES = ("qkv_w", "proj_w", "fc_w", "fc2_w", "wte", "wpe")


def strided_matrices(w, stride):
    """Every ``stride``-th row of each weight matrix (the per-layer ones
    stacked on a leading layer axis), as float32 numpy: the sample of
    coordinates on which the first update's direction is compared."""
    out = {k: np.asarray(w["layers"][k][:, ::stride]) for k in MATRICES[:4]}
    out.update({k: np.asarray(w[k][::stride]) for k in MATRICES[4:]})
    return out


def two_steps(model, seed, batches, adam, stride, piece=4, rounding=None):
    """Two training steps from the seed's weights. ``batches`` is two
    (rows, seq) int32 arrays (labels = ids); loss and gradients of the
    first are accumulated over ``piece`` rows at a time (what the chip
    holds), Adam is applied once, the second batch is only evaluated.
    Returns the two mean losses and, on the strided sample of weights,
    the weights before and after the update."""
    n_head = model["n_head"]
    w = jax.tree_util.tree_map(jnp.asarray, draw_weights(model, seed))
    before = strided_matrices(w, stride)
    first, second = (np.asarray(b, np.int32) for b in batches)
    total, count, acc = 0.0, 0, None
    for i in range(0, first.shape[0], piece):
        t, c, g = _loss_and_grad_sum(w, jnp.asarray(first[i:i + piece]),
                                     n_head, rounding)
        acc = g if acc is None else _accumulate(acc, g)
        total, count = total + float(t), count + int(c)
    loss0 = total / count
    grads = jax.tree_util.tree_map(lambda g: g / count, acc)
    w = _adam_first_step(w, grads, adam["lr"], adam["beta1"],
                         adam["beta2"], adam["eps"])
    after = strided_matrices(w, stride)
    total, count = 0.0, 0
    for i in range(0, second.shape[0], piece):
        t, c = _loss_sum(w, jnp.asarray(second[i:i + piece]), n_head,
                         rounding)
        total, count = total + float(t), count + int(c)
    return {"losses": [loss0, total / count], "before": before,
            "after": after}
