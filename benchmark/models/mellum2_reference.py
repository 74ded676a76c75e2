"""The ``mellum`` architecture (Mellum2-12B-A2.5B-Instruct: grouped-query
attention in every layer, sliding-window layers beside full ones, 64
experts behind a softmax router in every layer, an untied head) in plain
``jax.numpy`` and float32, at the sizes of a ``config.json``.

RMSNorm: ``x * rsqrt(mean(x^2) + eps) * w``, ``rms_norm_eps`` 1e-6. Layer
``i`` of ``num_hidden_layers``, ``x`` (T, hidden), every matrix without
bias:

1. ``u = RMSNorm(x; input_layernorm)``; ``q = u W_q`` as (T,
   ``num_attention_heads``, ``head_dim``), ``k = u W_k`` and ``v = u W_v``
   as (T, ``num_key_value_heads``, ``head_dim``); ``q = RMSNorm(q; g_q)``,
   ``k = RMSNorm(k; g_k)`` over the lanes of a head.
2. Rotation over all ``head_dim`` lanes, lane ``j`` paired with ``j +
   head_dim / 2``, at the token's absolute position, by the table of the
   layer's type (``rope_parameters[layer_types[i]]``). ``default``:
   ``inv_freq_j = theta ** (-2j / head_dim)``. ``yarn``: ``corr(n) =
   head_dim ln(original_max / (2 pi n)) / (2 ln theta)``, ``low =
   floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))`` (18 and 35
   at the published numbers), ``ramp_j = clip((j - low) / (high - low),
   0, 1)``, ``inv_freq_j = (1 - ramp_j) theta ** (-2j / head_dim) +
   ramp_j theta ** (-2j / head_dim) / factor``, and cos and sin both
   times ``attention_factor``, at every position.
3. ``scores = q k^T / sqrt(head_dim)``; the query at ``t`` sees the key
   at ``j`` iff ``j <= t`` and, in a ``sliding_attention`` layer, ``t - j
   < sliding_window`` (that many keys with its own); softmax; each
   key-value head serves ``heads / kv_heads`` query heads; ``x = x + ctx
   W_o``.
4. ``h = RMSNorm(x; post_attention_layernorm)``; ``p = softmax(h W_r)``
   over ``num_experts``; the ``num_experts_per_tok`` largest ``p``; ``w =
   p_chosen / sum(p_chosen)`` (``norm_topk_prob``); ``x = x + sum_e w_e
   W2_e (silu(h W1_e) * (h W3_e))`` at ``moe_intermediate_size``. No
   token dropped, no selection bias, no scaling factor, no shared
   expert. EVERY expert is applied to every token and the result masked
   by the routing: no sort and no gather.
5. After the last layer held: RMSNorm, then ``logits = x W_head``
   (``tie_word_embeddings`` false).

Full attention matrices, computed a block of ``QUERY_BLOCK`` queries at a
time so that 8,192 positions fit. No kernel, no cache, no page, no
batching, no call into ``deepspeed_tpu``: the yardstick ``correct`` is
decided against. Weights are drawn ONE LAYER AT A TIME (``draw_layer``:
a layer is 1.67 GB in float32), in the order ``models/mellum.py``
reproduces stream for stream.

Departures from the released model, each because the source gives no
number for it or a seeded stand-in changes nothing: the weights are
random (normal(0, 0.02) matrices, unit norms); the per-head norms of
queries and keys are drawn at ``qk_norm_gain`` (the configuration file's
``assumed.weights`` says what was read at 1 and why it is not 1: queries
and keys are normed, so no spread of ``W_q`` and ``W_k`` moves the
scores); rotate-half pairing (``lfm2_reference``'s note on the
permutation holds).

What makes a control of it (the serving check's): ``rounding`` (operands
of every weight matmul rounded: "bfloat16", or "fp8", e4m3's 4
significant bits), ``kv_rounding`` (the keys, normed and rotated, and
the values rounded likewise: what a token keeps), ``window`` (another
window in the sliding layers; 0: none), ``yarn=False`` (the full layers
rotated by plain rotary at their theta), ``attention_factor`` (another
one), ``sliding_rope_of_full`` (the sliding layers rotated by the full
layers' table), ``scoring`` ("sigmoid" in the softmax's place),
``top_k`` (fewer experts a token), ``renormalise=False`` (the chosen
probabilities as they are), ``qk_norm=False`` (the per-head norms
skipped).
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
QUERY_BLOCK = 512
SLIDING, FULL = "sliding_attention", "full_attention"


def _key(seed, i):
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), i)


def _std(model):
    return model.get("initializer_range", INIT_STD)


def draw_embedding(model, seed):
    """The embedding (vocab, hidden), float32: normal(0, 0.02) from the
    seed's stream number ``num_hidden_layers``."""
    return _std(model) * jax.random.normal(
        _key(seed, model["num_hidden_layers"]),
        (model["vocab_size"], model["hidden_size"]), jnp.float32)


def draw_head(model, seed):
    """The head (hidden, vocab), float32, from stream
    ``num_hidden_layers + 1``."""
    return _std(model) * jax.random.normal(
        _key(seed, model["num_hidden_layers"] + 1),
        (model["hidden_size"], model["vocab_size"]), jnp.float32)


def draw_layer(model, seed, i):
    """Layer ``i``'s float32 weights, from the seed's stream number
    ``i`` split in the order written here. Matrices are (in, out),
    normal(0, 0.02); norm weights 1, the per-head norms of queries and
    keys ``qk_norm_gain``; the router (hidden, experts); each expert's
    three matrices stacked (experts, in, out)."""
    d, dh = model["hidden_size"], model["head_dim"]
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    keys = iter(jax.random.split(_key(seed, i), 16))

    def normal(*shape):
        return _std(model) * jax.random.normal(next(keys), shape,
                                               jnp.float32)

    ones = lambda n, gain=1.0: jnp.full((n,), gain, jnp.float32)
    gain = model.get("qk_norm_gain", 1.0)
    w = {"attn_norm": ones(d), "ffn_norm": ones(d),
         "q": normal(d, h * dh), "k": normal(d, kvh * dh),
         "v": normal(d, kvh * dh), "o": normal(h * dh, d),
         "q_norm": ones(dh, gain), "k_norm": ones(dh, gain)}
    E, ff = model["num_experts"], model["moe_intermediate_size"]
    w["router"] = normal(d, E)
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


def yarn_correction_range(rope, head_dim):
    """``(low, high)`` of a ``yarn`` table: 18 and 35 as published."""
    def corr(rotations):
        return head_dim * math.log(
            rope["original_max_position_embeddings"] /
            (rotations * 2 * math.pi)) / (2 * math.log(rope["rope_theta"]))
    return (max(math.floor(corr(rope["beta_fast"])), 0),
            min(math.ceil(corr(rope["beta_slow"])), head_dim - 1))


def inv_freq(rope, head_dim, yarn=True):
    """The ``head_dim / 2`` frequencies of a ``rope_parameters`` entry
    (float64 numpy); ``yarn=False``: the plain ones at its theta."""
    half = head_dim // 2
    base = float(rope["rope_theta"]) ** (
        -np.arange(half, dtype=np.float64) / half)
    if rope["rope_type"] == "default" or not yarn:
        return base
    low, high = yarn_correction_range(rope, head_dim)
    ramp = np.clip((np.arange(half) - low) / (high - low), 0, 1)
    return (1 - ramp) * base + ramp * base / rope["factor"]


def rotary(x, positions, freq, factor):
    """x (s, heads, head_dim); positions (s,); ``freq`` (head_dim / 2,);
    cos and sin times ``factor``. Rotate-half pairing."""
    half = x.shape[-1] // 2
    angle = positions.astype(jnp.float32)[:, None] * \
        jnp.asarray(freq, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :] * factor
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :] * factor
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _rope_of(model, kind, wrong):
    """-> (frequencies, factor on cos and sin) of a layer of ``kind``."""
    if kind == SLIDING and wrong["sliding_rope_of_full"]:
        kind = FULL
    rope = model["rope_parameters"][kind]
    factor = rope.get("attention_factor", 1.0) \
        if rope["rope_type"] == "yarn" and wrong["yarn"] else 1.0
    if rope["rope_type"] == "yarn" and wrong["attention_factor"] is not None:
        factor = wrong["attention_factor"]
    return inv_freq(rope, model["head_dim"], wrong["yarn"]), factor


def _attention(model, kind, w, u, mm, wrong):
    s, _ = u.shape
    dh = model["head_dim"]
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    eps = model["rms_norm_eps"]
    q = mm(u, w["q"]).reshape(s, h, dh)
    k = mm(u, w["k"]).reshape(s, kvh, dh)
    v = mm(u, w["v"]).reshape(s, kvh, dh)
    if wrong["qk_norm"]:
        q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    positions = jnp.arange(s)
    freq, factor = _rope_of(model, kind, wrong)
    q, k = rotary(q, positions, freq, factor), \
        rotary(k, positions, freq, factor)
    # what a token keeps, in the precision it is kept in
    keep = ROUNDINGS[wrong["kv_rounding"]]
    k, v = keep(k), keep(v)
    window = None
    if kind == SLIDING:
        window = model["sliding_window"] if wrong["window"] is None \
            else wrong["window"]
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, "pad the sequence to a multiple of the block"
    q = q.reshape(s, kvh, h // kvh, dh)

    def one(args):
        qb, q_pos = args                     # (block, kvh, g, dh), (block,)
        scores = jnp.einsum("qkgd,Kkd->kgqK", qb, k) / math.sqrt(dh)
        ahead = q_pos[:, None] - jnp.arange(s)[None, :]
        mask = ahead >= 0
        if window:
            mask = mask & (ahead < window)
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("kgqK,Kkd->qkgd", jax.nn.softmax(scores, axis=-1),
                          v)

    blocks = s // block
    ctx = jax.lax.map(one, (q.reshape(blocks, block, kvh, h // kvh, dh),
                            jnp.arange(s).reshape(blocks, block)))
    return mm(ctx.reshape(s, h * dh), w["o"])


def route(model, w, x, top_k=None, scoring="softmax", renormalise=None):
    """-> (chosen (s, k), weights (s, k), probabilities (s, E))."""
    k = top_k or model["num_experts_per_tok"]
    z = x @ w["router"]
    p = jax.nn.softmax(z, axis=-1) if scoring == "softmax" \
        else jax.nn.sigmoid(z)
    _, chosen = jax.lax.top_k(p, k)
    weights = jnp.take_along_axis(p, chosen, axis=-1)
    if model["norm_topk_prob"] if renormalise is None else renormalise:
        weights = weights / weights.sum(-1, keepdims=True)
    return chosen, weights, p


def _experts(model, w, x, mm, wrong):
    """Every expert applied to every token, masked by the routing. ->
    (the layer's output (s, d), chosen, probabilities)."""
    chosen, weights, p = route(model, w, x, wrong["top_k"],
                               wrong["scoring"], wrong["renormalise"])
    dense = jnp.zeros((x.shape[0], model["num_experts"]), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(weights)

    def one(acc, expert):
        w1, w3, w2, weight = expert
        out = mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)
        return acc + weight[:, None] * out, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (w["w1"], w["w3"], w["w2"], dense.T))
    return out, chosen, p


WRONG = {"rounding": None, "kv_rounding": None, "window": None,
         "yarn": True, "attention_factor": None,
         "sliding_rope_of_full": False, "scoring": "softmax",
         "top_k": None, "renormalise": None, "qk_norm": True}


@functools.partial(jax.jit, static_argnames=("model_json", "kind",
                                             "wrong_items"))
def _layer(w, x, model_json, kind, wrong_items):
    model, wrong = json.loads(model_json), dict(wrong_items)
    with jax.default_matmul_precision("highest"):
        rnd = ROUNDINGS[wrong["rounding"]]
        mm = lambda a, m: rnd(a) @ rnd(m)
        eps = model["rms_norm_eps"]
        x = x + _attention(model, kind, w,
                           rms_norm(x, w["attn_norm"], eps), mm, wrong)
        out, chosen, p = _experts(model, w,
                                  rms_norm(x, w["ffn_norm"], eps), mm, wrong)
        return x + out, chosen, p


@functools.partial(jax.jit, static_argnames=("eps", "rounding"))
def _head(head, norm, x, positions, eps, rounding):
    with jax.default_matmul_precision("highest"):
        rnd = ROUNDINGS[rounding]
        x = rms_norm(jnp.take(x, positions, axis=0), norm, eps)
        return rnd(x) @ rnd(head)


def forward_many(model, seed, sequences, positions, return_routing=False,
                 **wrong):
    """Logits ``[(len(positions[k]), V)]`` of each sequence
    ``sequences[k]`` (s_k,) at its positions: the full forward, layer by
    layer, each layer's weights drawn once, used on every sequence and
    dropped. ``wrong``: the module docstring's controls;
    ``return_routing`` also returns per sequence ``{layer: (chosen (s,
    k), probabilities (s, E))}``."""
    unknown = set(wrong) - set(WRONG)
    assert not unknown, "no such control: {}".format(sorted(unknown))
    wrong_items = tuple(sorted(dict(WRONG, **wrong).items()))
    # the configuration as a hashable static argument
    items = json.dumps(model, sort_keys=True)
    emb = draw_embedding(model, seed)
    xs = [jnp.take(emb, jnp.asarray(ids, jnp.int32), axis=0)
          for ids in sequences]
    del emb
    routing = [{} for _ in xs]
    for i in range(model["num_hidden_layers"]):
        w = draw_layer(model, seed, i)
        for k, x in enumerate(xs):
            xs[k], chosen, p = _layer(w, x, items, model["layer_types"][i],
                                      wrong_items)
            if return_routing:
                routing[k][i] = (np.asarray(chosen), np.asarray(p))
        del w
    head = draw_head(model, seed)
    norm = jnp.ones((model["hidden_size"],), jnp.float32)
    logits = [_head(head, norm, x, jnp.asarray(p, jnp.int32),
                    model["rms_norm_eps"], dict(wrong_items)["rounding"])
              for x, p in zip(xs, positions)]
    return (logits, routing) if return_routing else logits


def logits_at(model, seed, ids, positions, **wrong):
    """:func:`forward_many` of one sequence."""
    return forward_many(model, seed, [ids], [positions], **wrong)[0]


def param_count(model):
    """Parameters the configuration holds (embedding and head each)."""
    d, dh = model["hidden_size"], model["head_dim"]
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    attn = 2 * d * h * dh + 2 * d * kvh * dh + 2 * dh
    experts = model["num_experts"] * 3 * d * model[
        "moe_intermediate_size"] + d * model["num_experts"]
    return 2 * model["vocab_size"] * d + d + \
        model["num_hidden_layers"] * (2 * d + attn + experts)
