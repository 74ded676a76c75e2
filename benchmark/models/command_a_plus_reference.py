"""The ``cohere2_moe`` architecture (command-a-plus-05-2026, "Command A+
218B-A25B": a parallel block, 128 query heads on 8 key-value heads,
sliding-window layers that rotate beside full layers that carry no
position, 128 experts behind a sigmoid router beside 4 shared experts
averaged, a tied head) in plain ``jax.numpy`` and float32, at the sizes
of a ``config.json``.

LayerNorm: ``(x - mean(x)) / sqrt(var(x) + layer_norm_eps) * g`` over the
``hidden_size`` lanes, no bias. Layer ``i`` of ``num_hidden_layers``, ``x``
(T, hidden), every matrix without bias, ONE norm a layer
(``use_parallel_block``):

1. ``h = LayerNorm(x; g_i)``.
2. Attention on ``h``: ``q = h W_q`` as (T, ``num_attention_heads``,
   ``head_dim``), ``k = h W_k`` and ``v = h W_v`` as (T,
   ``num_key_value_heads``, ``head_dim``); no norm on queries and keys
   (``use_qk_norm`` false). A ``sliding_attention`` layer rotates ``q``
   and ``k`` over all ``head_dim`` lanes (``rotary_pct`` 1), lane ``2j``
   paired with lane ``2j + 1`` (``rope_gptj``), by the token's absolute
   position at ``inv_freq_j = rope_theta ** (-2j / head_dim)``, and its
   query at ``t`` sees key ``j`` iff ``0 <= t - j < sliding_window``; a
   ``full_attention`` layer rotates NOTHING ("global NoPE") and sees every
   ``j <= t``. ``scores = q k^T / sqrt(head_dim)``, softmax, query head
   ``n`` reads key-value head ``n // (heads / kv_heads)``; ``a = ctx
   W_o``.
3. Experts on the SAME ``h``: ``s = sigmoid(h W_g)`` over all the
   router's experts; the ``num_experts_per_tok`` largest ``s`` chosen (no
   selection bias, no scaling factor: the config has neither key); ``w =
   s_chosen / sum(s_chosen)`` (``norm_topk_prob``); ``E(h) = W_down
   (silu(W_gate h) * (W_up h))`` at ``intermediate_size``; ``routed =
   sum_e w_e E_e(h)`` over the chosen experts HELD (below); ``shared =
   (1 / num_shared_experts) sum_s S_s(h)``, each shared expert a gated
   MLP of ``intermediate_size``; ``f = routed + shared``.
4. ``x = x + a + f``.
5. After the last layer held: ``LayerNorm(x; g_f)``, then ``logits =
   logit_scale * x E^T`` with the embedding ``E`` (``tie_word_embeddings``).

A chip's SHARE of a layer: ``experts_held`` (a range of the
``router_num_experts`` the router scores; ``num_experts`` then counts
the experts held) and ``padded_vocab_size`` (the embedding's rows held,
the whole vocabulary here). The router keeps its width and its experts a
token; a row chosen for an expert held elsewhere adds nothing, and that
partial result goes on to the next layer. EVERY expert held is applied to
every token and the result masked by the routing: no sort, no gather.

Full attention matrices, computed a block of ``QUERY_BLOCK`` queries at a
time so that 8,192 positions of 128 heads fit. No kernel, no cache, no
page, no batching, no call into ``deepspeed_tpu``: the yardstick
``correct`` is decided against. Weights are drawn ONE LAYER AT A TIME
(``draw_layer``: a layer's share is 4.6 GB in float32), stream for stream
what ``models/cohere2_moe.py`` draws.

Departures from the released model, each because the source gives no
number for it or a seeded stand-in changes nothing: the weights are random
(normal(0, ``initializer_range``) matrices, ``W_q`` and ``W_k`` at
``qk_init_std``: there is no norm on queries and keys, so their spread
sets the softmax, and the configuration file's ``assumed.weights`` says
what was read; unit norms); ``shared_expert_combination_strategy:
"average"`` is read as the mean of the shared experts' results
(``described_as``: "shared experts averaged"), each of
``intermediate_size``, which the family's published 218B / 25B a token
bear out; ``sliding_window`` counts the query's own key; the vision tower
is no part of the row's ``config`` and is left out; ``prefix_dense_*``
are unused (``first_k_dense_replace`` 0) and ``rms_norm_eps`` is null
(LayerNorm's ``layer_norm_eps`` is the one in use).

What makes a control of it (the serving check's): ``rounding`` (operands
of every weight matmul rounded: "bfloat16", or "fp8", e4m3's 4
significant bits), ``kv_rounding`` (the keys, rotated, and the values
rounded likewise: what a token keeps), ``window`` (another window in the
sliding layers; 0: none), ``rotate`` (which layer kinds rotate: both, or
none), ``scoring`` ("softmax" in the sigmoid's place), ``top_k`` (fewer
experts a token), ``renormalise=False`` (the chosen scores as they are),
``shared`` ("sum": not averaged; an int: that many of the shared
experts), ``experts_held`` (another share), ``sequential=True`` (the
experts read ``LayerNorm(x + a)``), ``norm="rms"`` (not mean-centred).
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
QUERY_BLOCK = 256
SLIDING, FULL = "sliding_attention", "full_attention"
# one key a name, split from the layer's stream in this order
_STREAMS = ("q", "k", "v", "o", "router", "shared", "experts")


def _key(seed, i):
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), i)


def _std(model):
    return model.get("initializer_range", INIT_STD)


def router_experts(model):
    """The router's width: every expert of the published layer."""
    return model.get("router_num_experts", model["num_experts"])


def experts_held(model):
    return tuple(model.get("experts_held", (0, router_experts(model))))


def draw_embedding(model, seed):
    """The rows of the embedding held (``padded_vocab_size``, hidden),
    float32: normal(0, 0.02) from the seed's stream number
    ``num_hidden_layers``."""
    rows = model.get("padded_vocab_size", model["vocab_size"])
    return _std(model) * jax.random.normal(
        _key(seed, model["num_hidden_layers"]),
        (rows, model["hidden_size"]), jnp.float32)


@functools.partial(jax.jit, static_argnames=("d", "ff", "std"))
def _draw_experts(key, ids, d, ff, std):
    def one(e):
        gate, up, down = jax.random.split(jax.random.fold_in(key, e), 3)
        draw = lambda k, *shape: std * jax.random.normal(k, shape,
                                                         jnp.float32)
        return draw(gate, d, ff), draw(up, d, ff), draw(down, ff, d)
    return jax.lax.map(one, ids)


def draw_experts(model, key, ids):
    """-> (gate (n, d, ff), up (n, d, ff), down (n, ff, d)) of the
    experts ``ids``: expert ``e``'s three matrices from ``key`` folded
    with ``e``, so a share draws what the whole layer would."""
    return _draw_experts(key, jnp.asarray(ids, jnp.int32),
                         model["hidden_size"], model["intermediate_size"],
                         _std(model))


def draw_layer(model, seed, i, held=None):
    """Layer ``i``'s float32 weights from the seed's stream number ``i``,
    one key a name of ``_STREAMS``. Matrices are (in, out), normal(0,
    0.02), ``W_q`` and ``W_k`` at ``qk_init_std``; the norm 1; the router
    (hidden, router's experts); the routed experts ``held`` (the
    configuration's share unless given) and the shared ones, three
    matrices each, stacked."""
    d, dh = model["hidden_size"], model["head_dim"]
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    keys = dict(zip(_STREAMS, jax.random.split(_key(seed, i),
                                               len(_STREAMS))))
    qk_std = model.get("qk_init_std", _std(model))

    def normal(name, *shape, std=_std(model)):
        return std * jax.random.normal(keys[name], shape, jnp.float32)

    w = {"norm": jnp.ones((d,), jnp.float32),
         "q": normal("q", d, h * dh, std=qk_std),
         "k": normal("k", d, kvh * dh, std=qk_std),
         "v": normal("v", d, kvh * dh), "o": normal("o", h * dh, d),
         "router": normal("router", d, router_experts(model))}
    first, past = held or experts_held(model)
    w["w1"], w["w3"], w["w2"] = draw_experts(model, keys["experts"],
                                             range(first, past))
    w["s1"], w["s3"], w["s2"] = draw_experts(
        model, keys["shared"], range(model["num_shared_experts"]))
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


def layer_norm(x, weight, eps, kind="layer"):
    if kind == "layer":
        x = x - x.mean(-1, keepdims=True)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def rotary(x, positions, theta):
    """x (s, heads, head_dim); positions (s,). Interleaved pairing:
    lanes ``(2j, 2j + 1)`` turn by ``position * theta ** (-2j /
    head_dim)``."""
    half = x.shape[-1] // 2
    freq = jnp.asarray(float(theta) ** (
        -np.arange(half, dtype=np.float64) / half), jnp.float32)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos = jnp.repeat(jnp.cos(angle), 2, axis=-1)[:, None, :]
    sin = jnp.repeat(jnp.sin(angle), 2, axis=-1)[:, None, :]
    pairs = x.reshape(x.shape[:-1] + (half, 2))
    turned = jnp.stack([-pairs[..., 1], pairs[..., 0]], -1).reshape(x.shape)
    return x * cos + turned * sin


def _attention(model, kind, w, u, mm, wrong):
    s, _ = u.shape
    dh = model["head_dim"]
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    q = mm(u, w["q"]).reshape(s, h, dh)
    k = mm(u, w["k"]).reshape(s, kvh, dh)
    v = mm(u, w["v"]).reshape(s, kvh, dh)
    positions = jnp.arange(s)
    if kind in wrong["rotate"]:
        q = rotary(q, positions, model["rope_theta"])
        k = rotary(k, positions, model["rope_theta"])
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


def route(model, w, x, top_k=None, scoring="sigmoid", renormalise=None):
    """-> (chosen (s, k), weights (s, k), scores (s, E)) over ALL the
    router's experts."""
    k = top_k or model["num_experts_per_tok"]
    z = x @ w["router"]
    p = jax.nn.sigmoid(z) if scoring == "sigmoid" \
        else jax.nn.softmax(z, axis=-1)
    _, chosen = jax.lax.top_k(p, k)
    weights = jnp.take_along_axis(p, chosen, axis=-1)
    if model["norm_topk_prob"] if renormalise is None else renormalise:
        weights = weights / weights.sum(-1, keepdims=True)
    return chosen, weights, p


def _gated(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def _experts(model, w, x, mm, wrong, held):
    """The routed experts ``held`` (every one applied to every token,
    masked by the routing) plus the shared experts' mean. -> (the
    layer's ``f`` (s, d), chosen, scores)."""
    chosen, weights, p = route(model, w, x, wrong["top_k"],
                               wrong["scoring"], wrong["renormalise"])
    dense = jnp.zeros((x.shape[0], router_experts(model)),
                      jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(weights)
    first, past = held

    def routed(acc, expert):
        gate, up, down, weight = expert
        return acc + weight[:, None] * _gated(x, gate, up, down, mm), None

    out, _ = jax.lax.scan(routed, jnp.zeros_like(x),
                          (w["w1"], w["w3"], w["w2"], dense.T[first:past]))
    n = model["num_shared_experts"]
    used = n if wrong["shared"] in ("average", "sum") else wrong["shared"]

    def shared(acc, expert):
        return acc + _gated(x, *expert, mm), None

    both, _ = jax.lax.scan(shared, jnp.zeros_like(x),
                           (w["s1"][:used], w["s3"][:used], w["s2"][:used]))
    return out + (both if wrong["shared"] == "sum" else both / n), chosen, p


WRONG = {"rounding": None, "kv_rounding": None, "window": None,
         "rotate": (SLIDING,), "scoring": "sigmoid", "top_k": None,
         "renormalise": None, "shared": "average", "experts_held": None,
         "sequential": False, "norm": "layer"}


@functools.partial(jax.jit, static_argnames=("model_json", "kind",
                                             "wrong_items", "held"))
def _layer(w, x, model_json, kind, wrong_items, held):
    model, wrong = json.loads(model_json), dict(wrong_items)
    with jax.default_matmul_precision("highest"):
        rnd = ROUNDINGS[wrong["rounding"]]
        mm = lambda a, m: rnd(a) @ rnd(m)
        eps = model["layer_norm_eps"]
        h = layer_norm(x, w["norm"], eps, wrong["norm"])
        a = _attention(model, kind, w, h, mm, wrong)
        if wrong["sequential"]:
            h = layer_norm(x + a, w["norm"], eps, wrong["norm"])
        f, chosen, p = _experts(model, w, h, mm, wrong, held)
        return x + a + f, chosen, p


@functools.partial(jax.jit, static_argnames=("eps", "rounding", "norm",
                                             "scale"))
def _head(emb, weight, x, positions, eps, rounding, norm, scale):
    with jax.default_matmul_precision("highest"):
        rnd = ROUNDINGS[rounding]
        x = layer_norm(jnp.take(x, positions, axis=0), weight, eps, norm)
        return scale * (rnd(x) @ rnd(emb).T)


def forward_many(model, seed, sequences, positions, return_routing=False,
                 **wrong):
    """Logits ``[(len(positions[k]), V)]`` of each sequence
    ``sequences[k]`` (s_k,) at its positions: the full forward, layer by
    layer, each layer's weights drawn once, used on every sequence and
    dropped. ``wrong``: the module docstring's controls;
    ``return_routing`` also returns per sequence ``{layer: (chosen (s,
    k), scores (s, E))}``."""
    unknown = set(wrong) - set(WRONG)
    assert not unknown, "no such control: {}".format(sorted(unknown))
    wrong = dict(WRONG, **wrong)
    held = tuple(wrong.pop("experts_held") or experts_held(model))
    wrong["rotate"] = tuple(wrong["rotate"])
    wrong_items = tuple(sorted(wrong.items()))
    # the configuration as a hashable static argument
    items = json.dumps(model, sort_keys=True)
    emb = draw_embedding(model, seed)
    xs = [jnp.take(emb, jnp.asarray(ids, jnp.int32), axis=0)
          for ids in sequences]
    routing = [{} for _ in xs]
    for i in range(model["num_hidden_layers"]):
        w = draw_layer(model, seed, i, held)
        for k, x in enumerate(xs):
            xs[k], chosen, p = _layer(w, x, items, model["layer_types"][i],
                                      wrong_items, held)
            if return_routing:
                routing[k][i] = (np.asarray(chosen), np.asarray(p))
        del w
    norm = jnp.ones((model["hidden_size"],), jnp.float32)
    logits = [_head(emb, norm, x, jnp.asarray(p, jnp.int32),
                    model["layer_norm_eps"], wrong["rounding"],
                    wrong["norm"], float(model["logit_scale"]))
              for x, p in zip(xs, positions)]
    return (logits, routing) if return_routing else logits


def logits_at(model, seed, ids, positions, **wrong):
    """:func:`forward_many` of one sequence."""
    return forward_many(model, seed, [ids], [positions], **wrong)[0]


def param_count(model, held=True):
    """Parameters of the configuration's layers, embedding and final
    norm: those HELD (the share's experts and embedding rows), or with
    ``held=False`` the whole of each layer and of the vocabulary."""
    d, dh = model["hidden_size"], model["head_dim"]
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    first, past = experts_held(model) if held \
        else (0, router_experts(model))
    expert = 3 * d * model["intermediate_size"]
    layer = d + 2 * d * h * dh + 2 * d * kvh * dh + \
        d * router_experts(model) + \
        (model["num_shared_experts"] + past - first) * expert
    rows = model.get("padded_vocab_size", model["vocab_size"]) if held \
        else model["vocab_size"]
    return rows * d + d + model["num_hidden_layers"] * layer
