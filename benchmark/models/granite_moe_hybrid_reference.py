"""The ``granitemoehybrid`` architecture (granite-4.0-h-small, "Granite
4.0-H Small 32B-A9B": nine Mamba-2 layers to every grouped-query
attention layer without positions, 72 narrow experts of which 10 a token
AND a shared gated MLP after every mixer, four scalar multipliers, a
tied head) in plain ``jax.numpy`` and float32, at the sizes of a
``config.json``.

RMSNorm everywhere: ``x / sqrt(mean(x^2) + rms_norm_eps) * w``.

* Model: ``x_0 = E[ids] * embedding_multiplier``; the blocks; ``logits
  = RMSNorm(x) E^T / logits_scaling`` over the rows held (``E`` tied).
* Block ``i``, ``r = residual_multiplier``: ``h = x + r *
  Mixer_i(RMSNorm(x))``; ``u = RMSNorm(h)``; ``y = h + r * (Routed(u) +
  Shared(u))``.
* Mamba-2 mixer (``layer_types[i] == "mamba"``), per token ``t``: ``[z |
  xBC | dt~] = u W_in`` split ``d_inner | d_inner + 2 d_state | heads``
  in that order, no bias; ``xBC`` through a causal depthwise convolution
  of ``mamba_d_conv`` taps WITH bias (``conv(v)_t[c] = b[c] + sum_j w[c,
  j] v_{t-K+1+j}[c]``, zeros before the request's first token) and
  ``silu``; split ``x | B | C``; ``x`` as ``mamba_n_heads`` heads of
  ``mamba_d_head``; ``dt_h = softplus(dt~_h + dt_bias_h)``, ``a_h =
  exp(-exp(A_log_h) * dt_h)``; the state ``S_h`` (``mamba_d_head x
  mamba_d_state``, zero at a request's start): ``S_h <- a_h S_h + dt_h *
  x_h (outer) B``, ``y_h = S_h C + D_h x_h`` (``B``, ``C`` the same for
  all heads: ``mamba_n_groups`` 1); ``g = y * silu(z)`` over the whole
  inner width, THEN ``RMSNorm(g)`` (one weight of ``d_inner``: the gate
  comes before the norm, and with one group the norm spans the whole
  inner width); ``Mixer = g W_out``. ``mamba_chunk_size`` is a parameter
  of the published code's algorithm, not of the function.
* Attention mixer: ``q = u W_q`` (heads x head width), ``k, v = u W_k, u
  W_v`` (key-value heads x head width), no bias, NO rotation
  (``position_embedding_type: "nope"``), scores ``q k *
  attention_multiplier`` (0.0078125 = 1/128 at a head of 128: NOT
  ``1/sqrt(128)``), causal, float32 softmax, query head ``j`` reads
  key-value head ``j // (heads / key-value heads)``, ``W_o``.
* Routed: ``z = u W_r`` (all the router's experts, no bias); the
  ``num_experts_per_tok`` largest LOGITS; ``w = softmax`` over those;
  ``Routed(u) = sum over the chosen e HELD of w_e W2_e(silu(W1g_e u) *
  W1u_e u)`` at ``intermediate_size``. Shared: the same gated MLP at
  ``shared_intermediate_size``, whole, on ``u``.

A chip's SHARE of a layer: ``experts_held`` (a range of the
``router_num_experts`` the router scores; ``num_local_experts`` then
counts the experts held) and ``padded_vocab_size`` (the embedding's rows
held, the whole vocabulary here). The router keeps its width and its
experts a token; a row chosen for an expert held elsewhere adds nothing,
and that partial result goes on to the next layer. EVERY expert held is
applied to every token, one expert at a time, and the result masked by
the routing: no sort, no gather.

Attention's scores a block of ``QUERY_BLOCK`` queries at a time so that
8,192 positions fit; the Mamba-2 layer is the recurrence above token by
token under ``lax.scan``. No kernel, no cache, no chunking, no batching,
no call into ``deepspeed_tpu``: the yardstick ``correct`` is decided
against. Weights are drawn ONE LAYER AT A TIME (``draw_layer``: a
layer's share is 1.8 GB in float32), stream for stream what
``models/granite_moe_hybrid.py`` draws, used on every sequence and
dropped.

Departures from the released model, each because the source gives no
number for it or a seeded stand-in changes nothing (the configuration
file lists them under ``assumed``): ``intermediate_size`` is read as ONE
expert's width (the family's 32B / 9B a token bear it out); ``silu``
after the convolution; no clamp of ``dt`` (``time_step_limit`` (0,
inf)); the weights are random (``draw_layer``).

What makes a control of it (the serving check's): ``rounding`` (operands
of every weight matmul rounded: "bfloat16", or "fp8", e4m3's 4
significant bits), ``state_rounding`` (the Mamba-2 state rounded after
every token), ``top_k`` (fewer experts a token), ``renormalise=False``
(the softmax over ALL the router's experts, the chosen ones' as they
are), ``attention_multiplier`` / ``residual_multiplier`` (another
number), ``gate_after_norm=True`` (``RMSNorm(y) * silu(z)``),
``decay=False`` (``a = 1``), ``conv_bias=False``, ``experts_held``
(another share), ``initial`` (a layer's recurrence started from a given
tail and state instead of zeros), ``reset_state_at`` / ``reset_tail_at``
(the state / the convolution's tail dropped at one position).
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from .command_a_plus_reference import (ROUNDINGS, _key, _std, draw_embedding,
                                       draw_experts)
from .jamba_reference import rms_norm

DT_MIN, DT_MAX = 1e-3, 1e-1
A_MIN, A_MAX = 1.0, 16.0
QUERY_BLOCK = 256
MAMBA, ATTENTION = "mamba", "attention"
# one key a name, split from the layer's stream in this order
_STREAMS = ("in_proj", "conv_w", "conv_b", "A", "dt", "out_proj", "q", "k",
            "v", "o", "router", "shared", "experts")


def router_experts(model):
    """The router's width: every expert of the published layer."""
    return model.get("router_num_experts", model["num_local_experts"])


def experts_held(model):
    return tuple(model.get("experts_held", (0, router_experts(model))))


def is_mamba(model, i):
    return model["layer_types"][i] == MAMBA


def d_inner(model):
    return model["mamba_n_heads"] * model["mamba_d_head"]


def conv_channels(model):
    return d_inner(model) + 2 * model["mamba_n_groups"] * \
        model["mamba_d_state"]


def _as_cohere(model, width):
    """The keys ``command_a_plus_reference``'s drawing reads, for one
    expert of ``width``."""
    return {"hidden_size": model["hidden_size"], "intermediate_size": width,
            "initializer_range": _std(model)}


def draw_layer(model, seed, i, held=None):
    """Layer ``i``'s float32 weights from the seed's stream number ``i``,
    one key a name of ``_STREAMS``. Matrices are (in, out), normal(0,
    0.02); norm weights and ``D`` 1; the router (hidden, the router's
    experts); a Mamba-2 layer's convolution (channels, taps) and its
    bias uniform(+-1/2), ``A_log = log(A)`` with ``A`` uniform in (1, 16)
    and ``dt_bias = softplus^-1(dt)`` with ``dt`` log-uniform in [1e-3,
    1e-1] (the layer's published initialiser: with ``A`` up to 128 and
    ``dt`` near 1 the state would forget within a token, and no check
    could see a state wrongly carried); the routed experts ``held`` (the
    configuration's share unless given) and the shared MLP, three
    matrices each, expert ``e``'s from the stream's key folded with
    ``e``."""
    d = model["hidden_size"]
    keys = dict(zip(_STREAMS, jax.random.split(_key(seed, i),
                                               len(_STREAMS))))

    def normal(name, *shape):
        return _std(model) * jax.random.normal(keys[name], shape,
                                               jnp.float32)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    w = {"mixer_norm": ones(d), "moe_norm": ones(d),
         "router": normal("router", d, router_experts(model))}
    if is_mamba(model, i):
        H, di, ch = model["mamba_n_heads"], d_inner(model), \
            conv_channels(model)
        A = jax.random.uniform(keys["A"], (H,), jnp.float32, A_MIN, A_MAX)
        dt = jnp.exp(jax.random.uniform(keys["dt"], (H,), jnp.float32) *
                     (math.log(DT_MAX) - math.log(DT_MIN)) +
                     math.log(DT_MIN))
        half = lambda name, *shape: jax.random.uniform(
            keys[name], shape, jnp.float32, -0.5, 0.5)
        w.update(in_proj=normal("in_proj", d, di + ch + H),
                 conv_w=half("conv_w", ch, model["mamba_d_conv"]),
                 conv_b=half("conv_b", ch), A_log=jnp.log(A),
                 dt_bias=dt + jnp.log(-jnp.expm1(-dt)), D=ones(H),
                 ssd_norm=ones(di), out_proj=normal("out_proj", di, d))
    else:
        h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
        dh = d // h
        w.update(q=normal("q", d, h * dh), k=normal("k", d, kvh * dh),
                 v=normal("v", d, kvh * dh), o=normal("o", h * dh, d))
    first, past = held or experts_held(model)
    w["w1"], w["w3"], w["w2"] = draw_experts(
        _as_cohere(model, model["intermediate_size"]), keys["experts"],
        range(first, past))
    w["s1"], w["s3"], w["s2"] = (m[0] for m in draw_experts(
        _as_cohere(model, model["shared_intermediate_size"]),
        keys["shared"], range(1)))
    return w


def _attention(model, w, u, mm, wrong):
    s, d = u.shape
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    dh = d // h
    q = mm(u, w["q"]).reshape(s, kvh, h // kvh, dh)
    k = mm(u, w["k"]).reshape(s, kvh, dh)
    v = mm(u, w["v"]).reshape(s, kvh, dh)
    scale = model["attention_multiplier"] \
        if wrong["attention_multiplier"] is None \
        else wrong["attention_multiplier"]
    block = min(QUERY_BLOCK, s)
    blocks = -(-s // block)
    # whole blocks of queries; the rows past the sequence are dropped
    q = jnp.pad(q, ((0, blocks * block - s), (0, 0), (0, 0), (0, 0)))

    def one(args):
        qb, q_pos = args                     # (block, kvh, g, dh), (block,)
        scores = jnp.einsum("qkgd,Kkd->kgqK", qb, k) * scale
        scores = jnp.where(q_pos[:, None] >= jnp.arange(s)[None, :], scores,
                           -jnp.inf)
        return jnp.einsum("kgqK,Kkd->qkgd", jax.nn.softmax(scores, axis=-1),
                          v)

    ctx = jax.lax.map(one, (q.reshape(blocks, block, kvh, h // kvh, dh),
                            jnp.arange(blocks * block).reshape(blocks,
                                                               block)))
    return mm(ctx.reshape(blocks * block, h * dh)[:s], w["o"])


def _mamba(model, w, u, mm, wrong, initial):
    """-> (mixer output (s, d), final (conv tail (taps - 1, channels),
    state (heads, head width, state width))). ``initial``: the same pair
    to start from, or None for zeros."""
    s = u.shape[0]
    H, P, N = model["mamba_n_heads"], model["mamba_d_head"], \
        model["mamba_d_state"]
    di, ch, kc = d_inner(model), conv_channels(model), model["mamba_d_conv"]
    z, xbc, dt = jnp.split(mm(u, w["in_proj"]), [di, di + ch], axis=-1)
    tail0, S0 = initial if initial is not None else (
        jnp.zeros((kc - 1, ch), jnp.float32),
        jnp.zeros((H, P, N), jnp.float32))
    padded = jnp.concatenate([tail0, xbc], axis=0)       # (s + kc-1, ch)

    def conv(inputs):
        return sum(inputs[j:j + s] * w["conv_w"][:, j] for j in range(kc))

    t = jnp.arange(s)
    convolved = conv(padded)
    at = wrong["reset_tail_at"]
    if at is not None:
        forgot = jnp.where((jnp.arange(s + kc - 1) < at + kc - 1)[:, None],
                           0.0, padded)
        convolved = jnp.where((t >= at)[:, None], conv(forgot), convolved)
    if wrong["conv_bias"]:
        convolved = convolved + w["conv_b"]
    x, B, C = jnp.split(jax.nn.silu(convolved), [di, di + N], axis=-1)
    x = x.reshape(s, H, P)
    dt = jax.nn.softplus(dt + w["dt_bias"])                      # (s, H)
    a = jnp.exp(-jnp.exp(w["A_log"]) * dt) if wrong["decay"] \
        else jnp.ones_like(dt)
    keep = jnp.ones((s,), bool) if wrong["reset_state_at"] is None \
        else t != wrong["reset_state_at"]
    state_round = ROUNDINGS[wrong["state_rounding"]]

    def step(S, inputs):
        keep_t, x_t, dt_t, a_t, B_t, C_t = inputs
        S = jnp.where(keep_t, S, 0.0)
        S = a_t[:, None, None] * S + \
            (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
        S = state_round(S)
        return S, S @ C_t

    S, y = jax.lax.scan(step, S0, (keep, x, dt, a, B, C))
    y = (y + w["D"][:, None] * x).reshape(s, di)
    gate, eps = jax.nn.silu(z), model["rms_norm_eps"]
    g = rms_norm(y, w["ssd_norm"], eps) * gate if wrong["gate_after_norm"] \
        else rms_norm(y * gate, w["ssd_norm"], eps)
    return mm(g, w["out_proj"]), (padded[s:], S)


def route(model, w, x, top_k=None, renormalise=True):
    """The published form: the ``top_k`` largest LOGITS, softmaxed over
    those; with ``renormalise=False`` the softmax over all the router's
    experts, the chosen ones' probabilities as they are. -> (chosen (s,
    k), weights (s, k), logits (s, E))."""
    k = top_k or model["num_experts_per_tok"]
    z = x @ w["router"]
    top, chosen = jax.lax.top_k(z, k)
    weights = jax.nn.softmax(top, axis=-1) if renormalise else \
        jnp.take_along_axis(jax.nn.softmax(z, axis=-1), chosen, axis=-1)
    return chosen, weights, z


def _gated(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def _experts(model, w, x, mm, wrong, held):
    """The routed experts ``held`` (every one applied to every token,
    one at a time, masked by the routing) plus the shared MLP. -> (the
    layer's ``Routed + Shared`` (s, d), chosen, logits)."""
    chosen, weights, z = route(model, w, x, wrong["top_k"],
                               wrong["renormalise"])
    dense = jnp.zeros((x.shape[0], router_experts(model)),
                      jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(weights)
    first, past = held

    def routed(acc, expert):
        gate, up, down, weight = expert
        return acc + weight[:, None] * _gated(x, gate, up, down, mm), None

    out, _ = jax.lax.scan(routed, jnp.zeros_like(x),
                          (w["w1"], w["w3"], w["w2"], dense.T[first:past]))
    return out + _gated(x, w["s1"], w["s3"], w["s2"], mm), chosen, z


WRONG = {"rounding": None, "state_rounding": None, "top_k": None,
         "renormalise": True, "attention_multiplier": None,
         "residual_multiplier": None, "gate_after_norm": False,
         "decay": True, "conv_bias": True, "experts_held": None,
         "reset_state_at": None, "reset_tail_at": None}


@functools.partial(jax.jit, static_argnames=("model_json", "mamba",
                                             "wrong_items", "held"))
def _layer(w, x, initial, model_json, mamba, wrong_items, held):
    model, wrong = json.loads(model_json), dict(wrong_items)
    with jax.default_matmul_precision("highest"):
        rnd = ROUNDINGS[wrong["rounding"]]
        mm = lambda a, m: rnd(a) @ rnd(m)
        eps = model["rms_norm_eps"]
        r = model["residual_multiplier"] \
            if wrong["residual_multiplier"] is None \
            else wrong["residual_multiplier"]
        u = rms_norm(x, w["mixer_norm"], eps)
        state = None
        if mamba:
            mixed, state = _mamba(model, w, u, mm, wrong, initial)
        else:
            mixed = _attention(model, w, u, mm, wrong)
        h = x + r * mixed
        f, chosen, z = _experts(model, w, rms_norm(h, w["moe_norm"], eps),
                                mm, wrong, held)
        return h + r * f, state, chosen, z


@functools.partial(jax.jit, static_argnames=("eps", "rounding", "scale"))
def _head(emb, weight, x, positions, eps, rounding, scale):
    with jax.default_matmul_precision("highest"):
        rnd = ROUNDINGS[rounding]
        x = rms_norm(jnp.take(x, positions, axis=0), weight, eps)
        return (rnd(x) @ rnd(emb).T) / scale


def forward_many(model, seed, sequences, positions, initial=None,
                 return_state=False, return_routing=False, **wrong):
    """Logits ``[(len(positions[k]), V)]`` of each sequence
    ``sequences[k]`` (s_k,) at its positions: the full forward, layer by
    layer, each layer's weights drawn once, used on every sequence and
    dropped. ``wrong``: the module docstring's controls. ``initial``:
    per sequence ``{layer: (conv tail, state)}`` to start those Mamba-2
    layers from, or None for zeros; ``return_state`` also returns per
    sequence every Mamba-2 layer's final pair (of the sequence as given:
    pad nothing then); ``return_routing`` per sequence ``{layer: (chosen
    (s, k), logits (s, E))}``."""
    unknown = set(wrong) - set(WRONG)
    assert not unknown, "no such control: {}".format(sorted(unknown))
    wrong = dict(WRONG, **wrong)
    held = tuple(wrong.pop("experts_held") or experts_held(model))
    wrong_items = tuple(sorted(wrong.items()))
    # the configuration as a hashable static argument
    items = json.dumps(model, sort_keys=True)
    emb = draw_embedding(model, seed)
    xs = [jnp.take(emb, jnp.asarray(ids, jnp.int32), axis=0) *
          float(model["embedding_multiplier"]) for ids in sequences]
    initial = initial or [None] * len(xs)
    final = [{} for _ in xs]
    routing = [{} for _ in xs]
    for i in range(model["num_hidden_layers"]):
        w = draw_layer(model, seed, i, held)
        for k, x in enumerate(xs):
            xs[k], state, chosen, z = _layer(
                w, x, (initial[k] or {}).get(i), items, is_mamba(model, i),
                wrong_items, held)
            if state is not None:
                final[k][i] = state
            if return_routing:
                routing[k][i] = (np.asarray(chosen), np.asarray(z))
        del w
    norm = jnp.ones((model["hidden_size"],), jnp.float32)
    logits = [_head(emb, norm, x, jnp.asarray(p, jnp.int32),
                    model["rms_norm_eps"], wrong["rounding"],
                    float(model["logits_scaling"]))
              for x, p in zip(xs, positions)]
    out = (logits,) + ((final,) if return_state else ()) + \
        ((routing,) if return_routing else ())
    return out[0] if len(out) == 1 else out


def logits_at(model, seed, ids, positions, initial=None, return_state=False,
              **wrong):
    """:func:`forward_many` of one sequence."""
    out = forward_many(model, seed, [ids], [positions], initial=[initial],
                       return_state=return_state, **wrong)
    return (out[0][0], out[1][0]) if return_state else out[0]


def param_count(model, held=True):
    """Parameters of the configuration's layers, embedding and final
    norm: those HELD (the share's experts and embedding rows), or with
    ``held=False`` the whole of each layer and of the vocabulary."""
    d, H, di = model["hidden_size"], model["mamba_n_heads"], d_inner(model)
    ch = conv_channels(model)
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    dh = d // h
    mamba = d * (di + ch + H) + ch * model["mamba_d_conv"] + ch + 3 * H + \
        di + di * d
    attn = 2 * d * h * dh + 2 * d * kvh * dh
    first, past = experts_held(model) if held \
        else (0, router_experts(model))
    experts = (past - first) * 3 * d * model["intermediate_size"] + \
        3 * d * model["shared_intermediate_size"] + \
        d * router_experts(model) + 2 * d
    n = model["num_hidden_layers"]
    n_mamba = sum(is_mamba(model, i) for i in range(n))
    rows = model.get("padded_vocab_size", model["vocab_size"]) if held \
        else model["vocab_size"]
    return rows * d + d + n_mamba * mamba + (n - n_mamba) * attn + \
        n * experts
