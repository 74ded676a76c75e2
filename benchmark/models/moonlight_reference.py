"""The ``deepseek_v3`` architecture (Moonlight-16B-A3B: multi-head latent
attention, a leading dense layer, 64 routed experts beside a shared
one, an untied head) in plain ``jax.numpy`` and float32, at the sizes of
a ``config.json``.

RMSNorm: ``x * rsqrt(mean(x^2) + eps) * w``, ``rms_norm_eps``. Layer
``l``: ``h = x + Attn(RMSNorm(x; input_layernorm))``; ``y = h +
FFN_l(RMSNorm(h; post_attention_layernorm))``. After the last layer one
RMSNorm, then logits ``= h W_head`` (a matrix of its own:
``tie_word_embeddings`` false). No bias anywhere.

* Attention, every layer (``q_lora_rank`` null: the query is not
  compressed). With ``u`` the normed input: ``q = W_q u``
  (``num_attention_heads`` heads of ``qk_nope_head_dim +
  qk_rope_head_dim``), each head split ``q_nope | q_pe``; ``W_kva u``
  (hidden -> ``kv_lora_rank + qk_rope_head_dim``) split ``c | k_pe``
  (ONE ``k_pe`` for all heads); ``c~ = RMSNorm(c; kv_a_layernorm)`` with
  its own eps; ``W_kvb c~`` (``kv_lora_rank`` -> heads x
  (``qk_nope_head_dim + v_head_dim``)), each head split ``k_nope | v``;
  rotary (``rope_theta``) over the ``qk_rope_head_dim`` lanes of every
  head's ``q_pe`` and of ``k_pe``; head ``h``: ``k_h = [k_nope_h |
  k_pe]``, scores ``q_h . k_h / sqrt(qk_nope_head_dim +
  qk_rope_head_dim)``, causal softmax, ``ctx_h = sum p v_h``; ``Attn =
  W_o [ctx_1 .. ctx_H]``. The UP-PROJECTED form only: no absorbed form,
  no cache, no kernel.
* FFN of the first ``first_k_dense_replace`` layers: ``W_2(silu(W_1 x)
  * W_3 x)`` at ``intermediate_size``.
* FFN of the others: ``s = sigmoid(W_g x)`` (``n_routed_experts``
  scores); the ``num_experts_per_tok`` chosen are the top of ``s + b``
  (``noaux_tc``: the bias shifts the choice only; ``n_group`` 1, one
  group, nothing masked); their weights are ``s`` at the chosen over
  ``(their sum + 1e-20)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``FFN = sum_e weight_e * expert_e(x) +
  shared(x)``, each routed expert a gated SiLU MLP at
  ``moe_intermediate_size``, ``shared`` one at ``n_shared_experts *
  moe_intermediate_size``. No capacity, no dropped token. EVERY routed
  expert is applied to every token and the result masked by the
  routing: no sort and no gather.

Departures from the released model, each because the source gives no
number for it or a seeded stand-in changes nothing: the weights are
random (``draw_layer``: normal(0, 0.02) matrices, unit norms; ``W_q``
and ``W_kva`` at ``attn_in_scale`` times that spread, ``W_kvb`` and
``W_o`` at ``attn_out_scale`` times, because under normal(0, 0.02) the
softmax is flat and an attention layer's output small beside the
residual, so that nothing attention does wrongly could be seen); the
selection bias is normal(0, ``expert_bias_std``), a stand-in for a
trained router's unevenness; ``kv_a_layernorm``'s eps is 1e-6 (the
architecture's class default; the row gives ``rms_norm_eps`` only); no
``rope_scaling`` (the row has none: plain rotary, no factor in the
softmax scale); rotary pairs lane ``i`` with ``i + rope / 2``
(``lfm2_reference.rotary``'s pairing) where the published code
de-interleaves ``(2i, 2i + 1)`` first: under seeded weights a fixed
permutation of the rope columns of ``W_q`` and ``W_kva`` alike, which
leaves every ``q_pe . k_pe`` as it is. Where a configuration holds a
share of the routed experts (``experts_held``) the others' part of the
sum is left out; the shared expert is whole wherever it is held.

No kernel, no cache, no batching, no call into ``deepspeed_tpu``: the
yardstick ``correct`` is decided against. Weights are drawn ONE LAYER
AT A TIME (``draw_layer``): the five layers are 9.7 GB in float32.

What makes a control of it (the serving check's): ``rounding``
(operands of every weight matmul rounded: "bfloat16", or "fp8", e4m3's
4 significant bits), ``latent_rounding`` (what a token keeps, ``c~`` and
the rotated ``k_pe``, rounded likewise), ``k_pe=False`` (the shared
rope key left out of the scores), ``rope_restart_at`` (rotary
positions counted again from 0 there), ``kv_norm=False``
(``kv_a_layernorm`` skipped), ``scale_width`` (the softmax scaled by
another width's root), ``shared=False`` (the shared expert left out),
``top_k`` (fewer experts a token), ``scaling`` (another
``routed_scaling_factor``), ``use_bias=False`` (the selection bias
ignored).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
EXPERT_BIAS_STD = 0.04
KV_NORM_EPS = 1e-6
ROUTE_NORM_EPS = 1e-20
QUERY_BLOCK = 512


def is_dense(model, i):
    return i < model["first_k_dense_replace"]


def experts_held(model):
    return tuple(model.get("experts_held") or (0, model["n_routed_experts"]))


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
    """The head (hidden, vocab), float32: normal(0, 0.02) from the
    seed's stream number ``num_hidden_layers + 1``."""
    return _std(model) * jax.random.normal(
        _key(seed, model["num_hidden_layers"] + 1),
        (model["hidden_size"], model["vocab_size"]), jnp.float32)


def draw_layer(model, seed, i):
    """Layer ``i``'s float32 weights, from the seed's stream number
    ``i`` split in the order written here. Matrices are (in, out):
    normal(0, 0.02), the four attention matrices at their scales. Norm
    weights 1. The router (hidden, experts); the selection bias
    normal(0, ``expert_bias_std``); the shared expert's three matrices;
    each routed expert's three stacked (experts, in, out)."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, vd = model["kv_lora_rank"], model["v_head_dim"]
    keys = iter(jax.random.split(_key(seed, i), 16))

    def normal(*shape, scale=1.0):
        return _std(model) * scale * jax.random.normal(
            next(keys), shape, jnp.float32)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    a_in = model.get("attn_in_scale", 1.0)
    a_out = model.get("attn_out_scale", 1.0)
    w = {"attn_norm": ones(d), "ffn_norm": ones(d),
         "q": normal(d, h * (nope + rope), scale=a_in),
         "kv_a": normal(d, rank + rope, scale=a_in),
         "kv_norm": ones(rank),
         "kv_b": normal(rank, h * (nope + vd), scale=a_out),
         "o": normal(h * vd, d, scale=a_out)}
    if is_dense(model, i):
        ff = model["intermediate_size"]
        w.update(w1=normal(d, ff), w3=normal(d, ff), w2=normal(ff, d))
        return w
    E, ff = model["n_routed_experts"], model["moe_intermediate_size"]
    sff = model["n_shared_experts"] * ff
    w["router"] = normal(d, E)
    w["expert_bias"] = model.get("expert_bias_std", EXPERT_BIAS_STD) * \
        jax.random.normal(next(keys), (E,), jnp.float32)
    w.update(s1=normal(d, sff), s3=normal(d, sff), s2=normal(sff, d))
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
    """x (s, ..., rope); positions (s,). Rotate-half pairing."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _attention(model, w, u, mm, wrong):
    s, _ = u.shape
    h = model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, vd = model["kv_lora_rank"], model["v_head_dim"]
    q = mm(u, w["q"]).reshape(s, h, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kva = mm(u, w["kv_a"])
    c, k_pe = kva[:, :rank], kva[:, rank:]
    if wrong["kv_norm"]:
        c = rms_norm(c, w["kv_norm"], model.get("kv_norm_eps", KV_NORM_EPS))
    positions = jnp.arange(s)
    restart = wrong["rope_restart_at"]
    if restart is not None:
        positions = jnp.where(positions >= restart, positions - restart,
                              positions)
    theta = float(model["rope_theta"])
    q_pe, k_pe = rotary(q_pe, positions, theta), rotary(k_pe, positions,
                                                       theta)
    # what a token keeps, in the precision it is kept in
    keep = ROUNDINGS[wrong["latent_rounding"]]
    c, k_pe = keep(c), keep(k_pe)
    kv = mm(c, w["kv_b"]).reshape(s, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = 1.0 / math.sqrt(wrong["scale_width"] or (nope + rope))
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, "pad the sequence to a multiple of the block"

    def one(args):
        qn, qp, q_pos = args                  # (block, h, .), (block,)
        scores = jnp.einsum("qhd,khd->hqk", qn, k_nope)
        if wrong["k_pe"]:
            scores = scores + jnp.einsum("qhr,kr->hqk", qp, k_pe)
        scores = jnp.where(jnp.arange(s)[None, :] <= q_pos[:, None],
                           scores * scale, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                          v)

    blocks = s // block
    ctx = jax.lax.map(one, (q_nope.reshape(blocks, block, h, nope),
                            q_pe.reshape(blocks, block, h, rope),
                            jnp.arange(s).reshape(blocks, block)))
    return mm(ctx.reshape(s, h * vd), w["o"])


def route(model, w, x, top_k=None, use_bias=True, scaling=None):
    """-> (chosen (s, k), weights (s, k), scores (s, E))."""
    k = top_k or model["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ w["router"])
    biased = scores + w["expert_bias"] if use_bias else scores
    _, chosen = jax.lax.top_k(biased, k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if model["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) +
                             ROUTE_NORM_EPS)
    factor = model["routed_scaling_factor"] if scaling is None else scaling
    return chosen, weights * factor, scores


def _gated(x, w1, w3, w2, mm):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def _experts(model, w, x, mm, wrong):
    """Every held routed expert applied to every token, masked by the
    routing, plus the shared expert. -> (FFN output (s, d), chosen)."""
    chosen, weights, _ = route(model, w, x, wrong["top_k"],
                               wrong["use_bias"], wrong["scaling"])
    E = model["n_routed_experts"]
    dense = jnp.zeros((x.shape[0], E), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(weights)
    first, past = experts_held(model)

    def one(acc, expert):
        w1, w3, w2, weight = expert
        return acc + weight[:, None] * _gated(x, w1, w3, w2, mm), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (w["w1"][first:past], w["w3"][first:past], w["w2"][first:past],
         dense.T[first:past]))
    if wrong["shared"]:
        out = out + _gated(x, w["s1"], w["s3"], w["s2"], mm)
    return out, chosen


WRONG = {"rounding": None, "latent_rounding": None, "k_pe": True,
         "rope_restart_at": None, "kv_norm": True, "scale_width": None,
         "shared": True, "top_k": None, "scaling": None, "use_bias": True}


@functools.partial(jax.jit, static_argnames=("model_items", "held", "dense",
                                             "wrong_items"))
def _layer(w, x, model_items, held, dense, wrong_items):
    model = dict(model_items, experts_held=held)
    wrong = dict(wrong_items)
    with jax.default_matmul_precision("highest"):
        rnd = ROUNDINGS[wrong["rounding"]]
        mm = lambda a, m: rnd(a) @ rnd(m)
        eps = model["rms_norm_eps"]
        x = x + _attention(model, w, rms_norm(x, w["attn_norm"], eps), mm,
                           wrong)
        u = rms_norm(x, w["ffn_norm"], eps)
        if dense:
            return x + _gated(u, w["w1"], w["w3"], w["w2"], mm), None
        out, chosen = _experts(model, w, u, mm, wrong)
        return x + out, chosen


def _items(model):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, bool))))


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
    ``return_routing`` also returns per sequence ``{expert layer:
    chosen (s, k)}``."""
    unknown = set(wrong) - set(WRONG)
    assert not unknown, "no such control: {}".format(sorted(unknown))
    wrong_items = tuple(sorted(dict(WRONG, **wrong).items()))
    items, held = _items(model), experts_held(model)
    emb = draw_embedding(model, seed)
    xs = [jnp.take(emb, jnp.asarray(ids, jnp.int32), axis=0)
          for ids in sequences]
    del emb
    routing = [{} for _ in xs]
    for i in range(model["num_hidden_layers"]):
        w = draw_layer(model, seed, i)
        for k, x in enumerate(xs):
            xs[k], chosen = _layer(w, x, items, held, is_dense(model, i),
                                   wrong_items)
            if chosen is not None and return_routing:
                routing[k][i] = np.asarray(chosen)
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
    """Parameters the configuration holds (the routed experts held;
    embedding and head each)."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, vd = model["kv_lora_rank"], model["v_head_dim"]
    attn = d * h * (nope + rope) + d * (rank + rope) + rank + \
        rank * h * (nope + vd) + h * vd * d
    ff = model["moe_intermediate_size"]
    first, past = experts_held(model)
    experts = (past - first) * 3 * d * ff + \
        3 * d * model["n_shared_experts"] * ff + \
        d * model["n_routed_experts"] + model["n_routed_experts"]
    layers = model["num_hidden_layers"]
    n_dense = min(model["first_k_dense_replace"], layers)
    return (2 * model["vocab_size"] * d + d + layers * (2 * d + attn) +
            n_dense * 3 * d * model["intermediate_size"] +
            (layers - n_dense) * experts)
