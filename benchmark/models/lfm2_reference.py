"""LFM2-MoE (LiquidAI's ``model_type: lfm2_moe``: gated short
convolutions, a few rotary grouped-query attention layers, a sparse
expert layer) in plain ``jax.numpy`` and float32, at the sizes of a
``config.json``.

RMSNorm everywhere: ``x * rsqrt(mean(x^2) + norm_eps) * w``. Layer
``l``, of the kind ``layer_types[l]`` says: ``h = x +
Op(RMSNorm(x; operator_norm))``; ``y = h + FFN(RMSNorm(h;
ffn_norm))``. After the last layer one RMSNorm (``embedding_norm``),
then logits ``= h W_emb^T`` (tied).

* ``conv``: ``(B, C, x~) = split(W_in u)`` in that order (d -> 3 d, no
  bias); ``z = B * x~``; ``c_t = sum_j w[:, j] z_{t - (L-1) + j}`` for
  the ``L = conv_L_cache`` taps (depthwise, causal, zeros before the
  sequence); ``Op = W_out(C * c)``.
* ``full_attention``: ``q = W_q u`` (``num_attention_heads`` heads),
  ``k = W_k u``, ``v = W_v u`` (``num_key_value_heads`` heads), no
  biases; RMSNorm over each head of ``q`` and of ``k``; rotary on the
  whole head (``rope_theta``, the rotate-half pairing ``(i, i + dh /
  2)``, not interleaved); causal softmax of ``q.k / sqrt(dh)``, each
  key-value head shared by its group of query heads; ``Op = W_o ctx``.
* FFN of the first ``num_dense_layers`` layers: ``W_2(silu(W_1 x) * W_3
  x)`` at ``intermediate_size``.
* FFN of the others: ``s = sigmoid(W_g x)`` (``num_experts`` scores);
  the ``num_experts_per_tok`` chosen are the top of ``s + b``
  (``use_expert_bias``: the bias shifts the choice only); their weights
  are ``s`` at the chosen over ``(their sum + 1e-6)``
  (``norm_topk_prob``) times ``routed_scaling_factor``; ``FFN = sum_e
  weight_e * W_2e(silu(W_1e x) * W_3e x)`` at ``moe_intermediate_size``.
  No shared expert, no capacity, no dropped token. EVERY expert is
  applied to every token and the result masked by the routing: no sort
  and no gather.

Departures from the released model, each because the source gives no
number for it: the weights are random (``draw_layer``: normal(0, 0.02)
matrices, unit norms); the selection bias is normal(0,
``expert_bias_std``), a stand-in for a trained router's unevenness;
the head is tied to the embedding, as the LFM2 family's is (the catalog
row has no ``tie_word_embeddings``). Where a configuration holds a
share of the experts (``experts_held``) the others' part of the sum is
left out, as on the chip that holds that share.

No kernel, no cache, no batching, no call into ``deepspeed_tpu``: the
yardstick ``correct`` is decided against. Weights are drawn ONE LAYER
AT A TIME (``draw_layer``): the whole configuration in float32 is 15.7
GB.

What makes a control of it (the serving check's): ``rounding``
(operands of every weight matmul rounded: "bfloat16", or "fp8", e4m3's
4 significant bits), ``top_k`` (fewer experts a token than the model
says: mathematics left out), ``use_bias=False`` (the selection bias
ignored), ``renormalise=False`` (the chosen scores used as they are),
``reset_at`` (the convolution's tail dropped at one position),
``rope_restart_at`` (rotary positions counted again from 0 there),
``initial`` (a convolution tail to start from instead of zeros).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
EXPERT_BIAS_STD = 0.04
CONV, ATTENTION = "conv", "full_attention"


def is_attention(model, i):
    return model["layer_types"][i] == ATTENTION


def is_dense(model, i):
    return i < model["num_dense_layers"]


def experts_held(model):
    return tuple(model.get("experts_held") or (0, model["num_experts"]))


def _key(seed, i):
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), i)


def _std(model):
    return model.get("initializer_range", INIT_STD)


def draw_embedding(model, seed):
    """The tied embedding (vocab, hidden), float32: normal(0, 0.02)
    from the seed's stream number ``num_hidden_layers``."""
    return _std(model) * jax.random.normal(
        _key(seed, model["num_hidden_layers"]),
        (model["vocab_size"], model["hidden_size"]), jnp.float32)


def draw_layer(model, seed, i):
    """Layer ``i``'s float32 weights, from the seed's stream number
    ``i`` split in the order written here. Matrices are (in, out):
    normal(0, 0.02). Norm weights 1. The router (hidden, experts); the
    selection bias normal(0, ``expert_bias_std``); each expert's three
    matrices stacked (experts, in, out)."""
    d = model["hidden_size"]
    keys = iter(jax.random.split(_key(seed, i), 16))

    def normal(*shape):
        return _std(model) * jax.random.normal(next(keys), shape,
                                               jnp.float32)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    w = {"operator_norm": ones(d), "ffn_norm": ones(d)}
    if is_attention(model, i):
        dh = d // model["num_attention_heads"]
        kv = model["num_key_value_heads"] * dh
        w.update(q=normal(d, d), k=normal(d, kv), v=normal(d, kv),
                 o=normal(d, d), q_norm=ones(dh), k_norm=ones(dh))
    else:
        w.update(in_proj=normal(d, 3 * d),
                 conv_w=normal(d, model["conv_L_cache"]),
                 out_proj=normal(d, d))
    if is_dense(model, i):
        ff = model["intermediate_size"]
        w.update(w1=normal(d, ff), w3=normal(d, ff), w2=normal(ff, d))
        return w
    E, ff = model["num_experts"], model["moe_intermediate_size"]
    w["router"] = normal(d, E)
    w["expert_bias"] = model.get("expert_bias_std", EXPERT_BIAS_STD) * \
        jax.random.normal(next(keys), (E,), jnp.float32)
    w.update(w1=normal(E, d, ff), w3=normal(E, d, ff), w2=normal(E, ff, d))
    return w


def _round_fp8(x):
    """Round to 4 significant bits (fp8 e4m3's mantissa; its exponent
    range is not modelled)."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)


ROUNDINGS = {
    None: lambda x: x,
    "bfloat16": lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                                   mantissa_bits=7),
    "fp8": _round_fp8,
}


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def rotary(x, positions, theta):
    """x (s, heads, dh); positions (s,). Rotate-half pairing."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _attention(model, w, u, mm, rope_restart_at):
    s, d = u.shape
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    dh, eps = d // h, model["norm_eps"]
    q = rms_norm(mm(u, w["q"]).reshape(s, h, dh), w["q_norm"], eps)
    k = rms_norm(mm(u, w["k"]).reshape(s, kvh, dh), w["k_norm"], eps)
    v = mm(u, w["v"]).reshape(s, kvh, dh)
    positions = jnp.arange(s)
    if rope_restart_at is not None:
        positions = jnp.where(positions >= rope_restart_at,
                              positions - rope_restart_at, positions)
    theta = float(model["rope_theta"])
    q, k = rotary(q, positions, theta), rotary(k, positions, theta)
    k, v = (jnp.repeat(t, h // kvh, axis=1) for t in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return mm(ctx.reshape(s, d), w["o"])


def _conv(model, w, u, mm, initial, reset_at, length):
    """-> (operator output (s, d), the tail (L - 1, d) as it is after
    ``length`` tokens). ``initial``: a tail to start from, or None for
    zeros; ``reset_at``: a position at which the tail is dropped, or
    None."""
    s, d = u.shape
    L = model["conv_L_cache"]
    B, C, x = jnp.split(mm(u, w["in_proj"]), 3, axis=-1)
    z = B * x
    tail0 = initial if initial is not None else \
        jnp.zeros((L - 1, d), jnp.float32)
    padded = jnp.concatenate([tail0, z], axis=0)           # (s + L-1, d)

    def conv(inputs):
        return sum(inputs[j:j + s] * w["conv_w"][:, j] for j in range(L))

    convolved = conv(padded)
    if reset_at is not None:
        forgot = jnp.where((jnp.arange(s + L - 1) < reset_at + L - 1)
                           [:, None], 0.0, padded)
        convolved = jnp.where((jnp.arange(s) >= reset_at)[:, None],
                              conv(forgot), convolved)
    return mm(C * convolved, w["out_proj"]), \
        jax.lax.dynamic_slice_in_dim(padded, length, L - 1, axis=0)


def route(model, w, x, top_k=None, use_bias=True, renormalise=None):
    """-> (chosen (s, k), weights (s, k), scores (s, E))."""
    k = top_k or model["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ w["router"])
    biased = scores + w["expert_bias"] \
        if model["use_expert_bias"] and use_bias else scores
    _, chosen = jax.lax.top_k(biased, k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if model["norm_topk_prob"] if renormalise is None else renormalise:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    return chosen, weights * model["routed_scaling_factor"], scores


def _experts(model, w, x, mm, top_k, use_bias, renormalise):
    """Every held expert applied to every token, masked by the routing.
    -> (FFN output (s, d), chosen (s, k))."""
    chosen, weights, _ = route(model, w, x, top_k, use_bias, renormalise)
    E = model["num_experts"]
    # (s, E): a token's weight for each expert, 0 where not chosen
    dense = jnp.zeros((x.shape[0], E), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(weights)
    first, past = experts_held(model)

    def one(acc, expert):
        w1, w3, w2, weight = expert
        out = mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)
        return acc + weight[:, None] * out, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (w["w1"][first:past], w["w3"][first:past], w["w2"][first:past],
         dense.T[first:past]))
    return out, chosen


@functools.partial(jax.jit, static_argnames=(
    "model_items", "layer_types", "held", "attention", "dense", "rounding",
    "top_k", "use_bias", "renormalise", "reset_at", "rope_restart_at"))
def _layer(w, x, initial, length, model_items, layer_types, held, attention,
           dense, rounding, top_k, use_bias, renormalise, reset_at,
           rope_restart_at):
    model = dict(model_items, layer_types=layer_types, experts_held=held)
    with jax.default_matmul_precision("highest"):
        rnd = ROUNDINGS[rounding]
        mm = lambda a, m: rnd(a) @ rnd(m)
        eps = model["norm_eps"]
        u = rms_norm(x, w["operator_norm"], eps)
        state = chosen = None
        if attention:
            mixed = _attention(model, w, u, mm, rope_restart_at)
        else:
            mixed, state = _conv(model, w, u, mm, initial, reset_at, length)
        x = x + mixed
        u = rms_norm(x, w["ffn_norm"], eps)
        if dense:
            x = x + mm(jax.nn.silu(mm(u, w["w1"])) * mm(u, w["w3"]),
                       w["w2"])
        else:
            out, chosen = _experts(model, w, u, mm, top_k, use_bias,
                                   renormalise)
            x = x + out
        return x, state, chosen


def _items(model):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, bool))))


@functools.partial(jax.jit, static_argnames=("eps", "rounding"))
def _head(emb, norm, x, positions, eps, rounding):
    with jax.default_matmul_precision("highest"):
        rnd = ROUNDINGS[rounding]
        x = rms_norm(jnp.take(x, positions, axis=0), norm, eps)
        return rnd(x) @ rnd(emb).T


def forward_many(model, seed, sequences, positions, rounding=None,
                 top_k=None, use_bias=True, renormalise=None,
                 reset_at=None, rope_restart_at=None, initial=None,
                 lengths=None, return_state=False, return_routing=False):
    """Logits ``[(len(positions[k]), V)]`` of each sequence
    ``sequences[k]`` (s_k,) at its positions: the full forward, layer
    by layer, each layer's weights drawn once, used on every sequence
    and dropped. ``initial``: per sequence ``{layer: tail}`` to start
    those convolution layers from, or None for zeros; ``return_state``
    also returns per sequence every convolution layer's tail as it is
    after ``lengths[k]`` tokens (the whole sequence's where none is
    given: say where the padding starts); ``return_routing`` per
    sequence ``{expert layer: chosen (s, k)}``."""
    items = _items(model)
    layer_types, held = tuple(model["layer_types"]), experts_held(model)
    emb = draw_embedding(model, seed)
    xs = [jnp.take(emb, jnp.asarray(ids, jnp.int32), axis=0)
          for ids in sequences]
    initial = initial or [None] * len(xs)
    lengths = lengths or [len(ids) for ids in sequences]
    final = [{} for _ in xs]
    routing = [{} for _ in xs]
    for i in range(model["num_hidden_layers"]):
        w = draw_layer(model, seed, i)
        for k, x in enumerate(xs):
            xs[k], state, chosen = _layer(
                w, x, (initial[k] or {}).get(i), jnp.int32(lengths[k]),
                items, layer_types, held,
                is_attention(model, i), is_dense(model, i), rounding,
                top_k, use_bias, renormalise, reset_at, rope_restart_at)
            if state is not None:
                final[k][i] = state
            if chosen is not None and return_routing:
                routing[k][i] = np.asarray(chosen)
        del w
    norm = jnp.ones((model["hidden_size"],), jnp.float32)
    logits = [_head(emb, norm, x, jnp.asarray(p, jnp.int32),
                    model["norm_eps"], rounding)
              for x, p in zip(xs, positions)]
    out = (logits,)
    if return_state:
        out += (final,)
    if return_routing:
        out += (routing,)
    return out if len(out) > 1 else logits


def logits_at(model, seed, ids, positions, initial=None, length=None,
              return_state=False, **wrong):
    """:func:`forward_many` of one sequence."""
    out = forward_many(model, seed, [ids], [positions],
                       initial=[initial],
                       lengths=None if length is None else [length],
                       return_state=return_state, **wrong)
    return (out[0][0], out[1][0]) if return_state else out[0]


def param_count(model):
    """Parameters the configuration holds (the experts held, the tied
    embedding once)."""
    d = model["hidden_size"]
    dh = d // model["num_attention_heads"]
    kv = model["num_key_value_heads"] * dh
    attn = 2 * d * d + 2 * d * kv + 2 * dh
    conv = 3 * d * d + d * model["conv_L_cache"] + d * d
    dense = 3 * d * model["intermediate_size"]
    first, past = experts_held(model)
    experts = (past - first) * 3 * d * model["moe_intermediate_size"] + \
        d * model["num_experts"] + model["num_experts"]
    layers = model["num_hidden_layers"]
    n_attn = sum(is_attention(model, i) for i in range(layers))
    n_dense = min(model["num_dense_layers"], layers)
    return (model["vocab_size"] * d + d + layers * 2 * d + n_attn * attn +
            (layers - n_attn) * conv + n_dense * dense +
            (layers - n_dense) * experts)
