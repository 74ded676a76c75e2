"""Olmo Hybrid (Ai2's hybrid of gated-delta-rule linear-attention layers
and full-attention layers, ``model_type: olmo_hybrid``) in plain
``jax.numpy`` and float32, at the sizes of a ``config.json``.

Layer ``i`` is what ``layer_types[i]`` says. The block norms a
sublayer's output (the Olmo family's order): ``h = x +
RMSNorm(Mixer(x))``; ``out = h + RMSNorm(MLP(h))`` with ``MLP(h) =
W_down(silu(W_gate h) * W_up h)``, no biases, eps ``rms_norm_eps``; a
final RMSNorm; logits ``= h W_head`` (untied).

Full layer: ``q = RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)`` over the
WHOLE projection (``hidden_size`` wide, before the heads are split),
``v = x W_v``; ``num_attention_heads`` heads on as many key-value heads;
NO rotation (``rope_parameters.rope_theta: null``); scores ``q k /
sqrt(head width)``, causal, float32 softmax; ``W_o``.

Linear layer (Yang, Kautz, Hatamizadeh 2024, "Gated Delta Networks"),
per token ``t`` and head ``h`` of ``linear_num_value_heads``: ``q~ = x
W_q``, ``k~ = x W_k`` (heads x ``linear_key_head_dim``), ``v~ = x W_v``
(heads x ``linear_value_head_dim``); each stream through a causal
depthwise convolution of ``linear_conv_kernel_dim`` taps (``conv(y)_t[c]
= sum_j w[c, j] y_{t-K+1+j}[c]``, zeros before the first token, no bias)
and ``silu``; ``q = q / sqrt(sum q^2 + 1e-6) / sqrt(d_k)``, ``k = k /
sqrt(sum k^2 + 1e-6)`` a head; ``beta_t = 2 sigmoid(x_t W_b)_h`` (the 2
is ``linear_allow_neg_eigval``: the eigenvalue of ``I - beta k k^T``
along ``k`` lies in (-1, 1)); ``a_t = exp(-exp(A_log_h) softplus((x_t
W_a)_h + dt_bias_h))``; the state ``S`` (``d_k x d_v`` a head, zero at a
request's start): ``S' = a_t S_{t-1}``, ``u_t = beta_t (v_t - S'^T
k_t)``, ``S_t = S' + k_t u_t^T``, ``o_t = S_t^T q_t``; ``o^ =
RMSNorm_dv(o_t; one weight of d_v) * silu((x_t W_g)_h)``; ``Mixer(x)_t =
concat_h(o^) W_o``.

Departures from the released model, each because the source's
``config.json`` gives no number for it (the configuration file lists
them under ``assumed``): the block's norm order, the QK norm's span, no
rotation, the output gate, a convolution without bias with ``silu``
after it, the L2 norm's epsilon and the ``1/sqrt(d_k)`` on ``q``; the
weights are random (``draw_layer``).

No kernel, no cache, no chunking, no batching, the recurrence token by
token under ``lax.scan``, and no call into ``deepspeed_tpu``: the
yardstick ``correct`` is decided against. Weights are drawn ONE LAYER AT
A TIME (``draw_layer``), used on every sequence and dropped.

The controls of the serving check, each a keyword that makes the same
mathematics wrong in one way: ``rounding`` (the operands of every
weight matmul rounded: "bfloat16", what the configuration states;
"fp8", e4m3's 4 significant bits, the step below); ``state_rounding``
(the state rounded after every step); ``beta_two=False`` (beta without
its 2); ``decay=False`` (``a = 1``); ``initial`` (a layer's recurrence
started from a given tail and state instead of zeros);
``reset_state_at`` / ``reset_tail_at`` (the state / the convolution's
tail dropped at one position).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .jamba_reference import ROUNDINGS, _items, rms_norm

INIT_STD = 0.02
DT_MIN, DT_MAX = 1e-3, 1e-1
A_MAX = 16.0
L2_EPS = 1e-6
LINEAR = "linear_attention"


def is_linear(model, i):
    return model["layer_types"][i] == LINEAR


def conv_channels(model):
    return model["linear_num_value_heads"] * (
        2 * model["linear_key_head_dim"] + model["linear_value_head_dim"])


def _key(seed, i):
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), i)


def _std(model):
    """``initializer_range`` where a configuration file gives one (the
    tiny presets of the tests do), else 0.02."""
    return model.get("initializer_range", INIT_STD)


def draw_table(model, seed, stream, *shape):
    """The embedding (stream ``num_hidden_layers``: (vocab, hidden)) or
    the head (the stream after it: (hidden, vocab)), float32,
    normal(0, 0.02)."""
    return _std(model) * jax.random.normal(
        _key(seed, model["num_hidden_layers"] + stream), shape, jnp.float32)


def draw_layer(model, seed, i):
    """Layer ``i``'s float32 weights, from the seed's stream number
    ``i`` split in the order written here. Matrices are (in, out):
    normal(0, 0.02), the convolution's taps (channels, taps) likewise.
    Norm weights 1. ``A_log = log(A)`` with ``A`` uniform in (0, 16) and
    ``dt_bias = softplus^-1(dt)`` with ``dt`` log-uniform in [1e-3,
    1e-1] (the layer's published initialisation): with a decay near 0
    the state would forget within a token, and no check could see a
    state wrongly carried."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    keys = iter(jax.random.split(_key(seed, i), 16))

    def normal(*shape):
        return _std(model) * jax.random.normal(next(keys), shape,
                                               jnp.float32)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    w = {"mixer_norm": ones(d), "mlp_norm": ones(d),
         "gate": normal(d, ff), "up": normal(d, ff), "down": normal(ff, d)}
    if not is_linear(model, i):
        w.update(q=normal(d, d), k=normal(d, d), v=normal(d, d),
                 o=normal(d, d), q_norm=ones(d), k_norm=ones(d))
        return w
    H = model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    w.update(q=normal(d, H * dk), k=normal(d, H * dk), v=normal(d, H * dv),
             g=normal(d, H * dv), o=normal(H * dv, d), b=normal(d, H),
             a=normal(d, H),
             conv_w=normal(conv_channels(model),
                           model["linear_conv_kernel_dim"]))
    A = jax.random.uniform(next(keys), (H,), jnp.float32, 1e-4, A_MAX)
    dt = jnp.exp(jax.random.uniform(next(keys), (H,), jnp.float32) *
                 (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    w.update(A_log=jnp.log(A),
             dt_bias=dt + jnp.log(-jnp.expm1(-dt)),     # inverse softplus
             o_norm=ones(dv))
    return w


def _attention(model, w, x, mm):
    s, d = x.shape
    h = model["num_attention_heads"]
    dh, eps = d // h, model["rms_norm_eps"]
    q = rms_norm(mm(x, w["q"]), w["q_norm"], eps).reshape(s, h, dh)
    k = rms_norm(mm(x, w["k"]), w["k_norm"], eps).reshape(s, h, dh)
    v = mm(x, w["v"]).reshape(s, h, dh)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return mm(ctx.reshape(s, d), w["o"])


def _l2_norm(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def _linear(model, w, x, mm, state_round, initial, reset_state_at,
            reset_tail_at, beta_two, decay):
    """-> (mixer output (s, d), final (conv tail (taps - 1, channels),
    state (H, dk, dv))). ``initial``: the same pair to start from, or
    None for zeros."""
    s = x.shape[0]
    H = model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    kc, ch = model["linear_conv_kernel_dim"], conv_channels(model)
    streams = jnp.concatenate(
        [mm(x, w["q"]), mm(x, w["k"]), mm(x, w["v"])], axis=-1)
    tail0, S0 = initial if initial is not None else (
        jnp.zeros((kc - 1, ch), jnp.float32),
        jnp.zeros((H, dk, dv), jnp.float32))
    padded = jnp.concatenate([tail0, streams], axis=0)   # (s + kc-1, ch)

    def conv(inputs):
        return sum(inputs[j:j + s] * w["conv_w"][:, j] for j in range(kc))

    t = jnp.arange(s)
    convolved = conv(padded)
    if reset_tail_at is not None:
        forgot = jnp.where((jnp.arange(s + kc - 1) < reset_tail_at + kc - 1)
                           [:, None], 0.0, padded)
        convolved = jnp.where((t >= reset_tail_at)[:, None], conv(forgot),
                              convolved)
    xc = jax.nn.silu(convolved)
    q = _l2_norm(xc[:, :H * dk].reshape(s, H, dk)) / math.sqrt(dk)
    k = _l2_norm(xc[:, H * dk:2 * H * dk].reshape(s, H, dk))
    v = xc[:, 2 * H * dk:].reshape(s, H, dv)
    beta = (2.0 if beta_two and model["linear_allow_neg_eigval"] else 1.0) \
        * jax.nn.sigmoid(mm(x, w["b"]))                          # (s, H)
    a = jnp.exp(-jnp.exp(w["A_log"]) *
                jax.nn.softplus(mm(x, w["a"]) + w["dt_bias"]))
    if not decay:
        a = jnp.ones_like(a)
    keep = jnp.ones((s,), bool) if reset_state_at is None \
        else t != reset_state_at

    def step(S, inputs):
        keep_t, q_t, k_t, v_t, a_t, b_t = inputs
        S = jnp.where(keep_t, S, 0.0)
        S = a_t[:, None, None] * S
        u = b_t[:, None] * (v_t - jnp.einsum("hde,hd->he", S, k_t))
        S = state_round(S + k_t[:, :, None] * u[:, None, :])
        return S, jnp.einsum("hde,hd->he", S, q_t)

    S, o = jax.lax.scan(step, S0, (keep, q, k, v, a, beta))
    o = rms_norm(o, w["o_norm"], model["rms_norm_eps"])          # (s, H, dv)
    o = o.reshape(s, H * dv) * jax.nn.silu(mm(x, w["g"]))
    return mm(o, w["o"]), (padded[s:], S)


@functools.partial(jax.jit, static_argnames=(
    "model_items", "linear", "rounding", "state_rounding",
    "reset_state_at", "reset_tail_at", "beta_two", "decay"))
def _layer(w, x, initial, model_items, linear, rounding, state_rounding,
           reset_state_at, reset_tail_at, beta_two, decay):
    model = dict(model_items)
    with jax.default_matmul_precision("highest"):
        rnd = ROUNDINGS[rounding]
        mm = lambda a, m: rnd(a) @ rnd(m)
        eps = model["rms_norm_eps"]
        state = None
        if linear:
            mixed, state = _linear(
                model, w, x, mm, ROUNDINGS[state_rounding], initial,
                reset_state_at, reset_tail_at, beta_two, decay)
        else:
            mixed = _attention(model, w, x, mm)
        x = x + rms_norm(mixed, w["mixer_norm"], eps)
        mlp = mm(jax.nn.silu(mm(x, w["gate"])) * mm(x, w["up"]), w["down"])
        return x + rms_norm(mlp, w["mlp_norm"], eps), state


@functools.partial(jax.jit, static_argnames=("eps", "rounding"))
def _head(head, norm, x, positions, eps, rounding):
    with jax.default_matmul_precision("highest"):
        rnd = ROUNDINGS[rounding]
        x = rms_norm(jnp.take(x, positions, axis=0), norm, eps)
        return rnd(x) @ rnd(head)


def forward_many(model, seed, sequences, positions, rounding=None,
                 state_rounding=None, reset_state_at=None,
                 reset_tail_at=None, beta_two=True, decay=True,
                 initial=None, return_state=False):
    """Logits ``[(len(positions[k]), V)]`` of each sequence
    ``sequences[k]`` (s_k,) at its positions: the full forward, layer
    by layer, each layer's weights drawn once, used on every sequence
    and dropped. ``initial``: per sequence ``{layer: (conv tail,
    state)}`` to start those linear layers from, or None for zeros;
    ``return_state`` also returns per sequence every linear layer's
    final pair (of the sequence as given: pad nothing then)."""
    items = _items(model) + (("linear_allow_neg_eigval",
                              bool(model["linear_allow_neg_eigval"])),)
    emb = draw_table(model, seed, 0, model["vocab_size"],
                     model["hidden_size"])
    xs = [jnp.take(emb, jnp.asarray(ids, jnp.int32), axis=0)
          for ids in sequences]
    del emb
    initial = initial or [None] * len(xs)
    final = [{} for _ in xs]
    for i in range(model["num_hidden_layers"]):
        w = draw_layer(model, seed, i)
        for n, x in enumerate(xs):
            xs[n], state = _layer(
                w, x, (initial[n] or {}).get(i), items, is_linear(model, i),
                rounding, state_rounding, reset_state_at, reset_tail_at,
                beta_two, decay)
            if state is not None:
                final[n][i] = state
        del w
    norm = jnp.ones((model["hidden_size"],), jnp.float32)
    head = draw_table(model, seed, 1, model["hidden_size"],
                      model["vocab_size"])
    logits = [_head(head, norm, x, jnp.asarray(p, jnp.int32),
                    model["rms_norm_eps"], rounding)
              for x, p in zip(xs, positions)]
    return (logits, final) if return_state else logits


def logits_at(model, seed, ids, positions, initial=None, return_state=False,
              **wrong):
    """:func:`forward_many` of one sequence."""
    out = forward_many(model, seed, [ids], [positions],
                       initial=[initial], return_state=return_state,
                       **wrong)
    return (out[0][0], out[1][0]) if return_state else out[0]


def param_count(model):
    """Parameters of the whole model, embedding and head each once."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    H, dv = model["linear_num_value_heads"], model["linear_value_head_dim"]
    ch = conv_channels(model)
    mlp = 3 * d * ff + 2 * d
    linear = (d * ch + 2 * d * H * dv + 2 * d * H +
              ch * model["linear_conv_kernel_dim"] + 2 * H + dv)
    full = 4 * d * d + 2 * d
    n = model["num_hidden_layers"]
    n_linear = sum(is_linear(model, i) for i in range(n))
    return (2 * model["vocab_size"] * d + d + n_linear * (linear + mlp) +
            (n - n_linear) * (full + mlp))


def as_numpy(tree):
    return jax.tree_util.tree_map(np.asarray, tree)
