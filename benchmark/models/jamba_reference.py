"""Jamba (AI21's hybrid of Mamba-1 and attention layers) in plain
``jax.numpy`` and float32, as ``JambaForCausalLM`` of the published
``model_type: jamba`` computes it, at the sizes of a ``config.json``.

For layer ``i``: attention if ``i % attn_layer_period ==
attn_layer_offset``, else Mamba (the published
``JambaConfig.layers_block_type`` rule). ``h = x + Mixer(RMSNorm(x))``;
``out = h + MLP(RMSNorm(h))`` with ``MLP(u) = W_down(silu(W_gate u) *
W_up u)``, no biases; a final RMSNorm; logits ``= h W_emb^T`` (tied).
No positional encoding anywhere. Attention: ``num_attention_heads``
query heads on ``num_key_value_heads`` key-value heads, causal softmax,
no biases. Mamba: ``(x, z) = split(W_in u)``; ``x = silu(causal
depthwise conv1d(x) + b)``; ``(dt, B, C) = split(W_x x)``, each
RMS-normed with a learned weight (Jamba's addition to Mamba-1);
``dt = softplus(W_dt dt + b_dt)``; ``A = -exp(A_log)``;
``h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t^T``;
``y_t = h_t C_t + D x_t``; ``Mixer = W_out(y * silu(z))``.

Departures from the released model, each because the source gives no
number for it: the weights are random (``draw_layer``: normal(0, 0.02)
matrices, unit norms, and for ``A_log``, ``b_dt``, ``D`` the Mamba
paper's own initialisation); ``num_experts`` is 1 in this
configuration, so every MLP is the dense one and no router exists.

No kernel, no cache, no batching, a ``lax.scan`` over time for the
recurrence, and no call into ``deepspeed_tpu``: the yardstick
``correct`` is decided against. Weights are drawn ONE LAYER AT A TIME
(``draw_layer``), so the reference never holds more than a layer and
the embedding: the whole model in float32 is 12.1 GB.

``rounding`` computes the same mathematics with the operands of every
weight matmul rounded to a lower precision ("bfloat16": what the
configuration states; "fp8": e4m3's 4 significant bits, the step
below). ``state_rounding`` rounds the SSM state after every step
("bfloat16", or "fp8"); ``initial`` starts a layer's recurrence from a
given state instead of zero; ``reset_at`` drops the recurrent state at
one position (the controls of the serving check).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
DT_MIN, DT_MAX = 1e-3, 1e-1


def is_attention(model, i):
    return i % model["attn_layer_period"] == model["attn_layer_offset"]


def d_inner(model):
    return model["mamba_expand"] * model["hidden_size"]


def _key(seed, i):
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), i)


def _std(model):
    """``initializer_range`` where a configuration file gives one (the
    tiny presets of the tests do, so that a signal passes through
    their narrow layers), else the published default, 0.02."""
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
    normal(0, 0.02). Norm weights 1. ``A_log = log(1..d_state)`` per
    channel, ``D = 1``, ``b_dt = softplus^-1(dt)`` with ``dt``
    log-uniform in [1e-3, 1e-1] (Gu & Dao 2023, section 3.6 and the
    released initialisation): with ``dt`` near 0.7 and ``A = -16`` the
    state would forget within a token, and no check could see a state
    wrongly carried."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    keys = iter(jax.random.split(_key(seed, i), 16))

    def normal(*shape):
        return _std(model) * jax.random.normal(next(keys), shape,
                                               jnp.float32)

    w = {"norm1": jnp.ones((d,), jnp.float32),
         "norm2": jnp.ones((d,), jnp.float32),
         "gate": normal(d, ff), "up": normal(d, ff), "down": normal(ff, d)}
    if is_attention(model, i):
        dh = d // model["num_attention_heads"]
        kv = model["num_key_value_heads"] * dh
        w.update(q=normal(d, d), k=normal(d, kv), v=normal(d, kv),
                 o=normal(d, d))
        return w
    di, n = d_inner(model), model["mamba_d_state"]
    r, kc = model["mamba_dt_rank"], model["mamba_d_conv"]
    dt = jnp.exp(jax.random.uniform(next(keys), (di,), jnp.float32) *
                 (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    w.update(
        in_proj=normal(d, 2 * di), conv_w=normal(di, kc),
        conv_b=jnp.zeros((di,), jnp.float32), x_proj=normal(di, r + 2 * n),
        dt_proj=normal(r, di),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),      # inverse softplus
        A_log=jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                               (di, n)),
        D=jnp.ones((di,), jnp.float32), out_proj=normal(di, d),
        dt_norm=jnp.ones((r,), jnp.float32),
        B_norm=jnp.ones((n,), jnp.float32),
        C_norm=jnp.ones((n,), jnp.float32))
    return w


def _round_fp8(x):
    """Round to 4 significant bits (fp8 e4m3's mantissa; its exponent
    range is not modelled)."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)


ROUNDINGS = {
    None: lambda x: x,
    # reduce_precision, not a pair of casts: the chip's compiler takes
    # a cast to bfloat16 and back out of a scan's body as excess
    # precision it may keep (the state control then read 0.0)
    "bfloat16": lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                                   mantissa_bits=7),
    "fp8": _round_fp8,
}


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def _attention(model, w, u, mm):
    s, d = u.shape
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    dh = d // h
    q = mm(u, w["q"]).reshape(s, h, dh)
    k = mm(u, w["k"]).reshape(s, kvh, dh)
    v = mm(u, w["v"]).reshape(s, kvh, dh)
    k, v = (jnp.repeat(t, h // kvh, axis=1) for t in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return mm(ctx.reshape(s, d), w["o"])


def _mamba(model, w, u, mm, state_round, initial, reset_at):
    """-> (mixer output (s, d), final (conv tail (d_conv-1, di), SSM
    state (di, n))). ``initial``: the same pair to start from, or None
    for zeros. ``reset_at``: a position at which the recurrence starts
    again from zeros (conv tail and SSM state), or None."""
    s = u.shape[0]
    di, n = d_inner(model), model["mamba_d_state"]
    r, kc, eps = (model["mamba_dt_rank"], model["mamba_d_conv"],
                  model["rms_norm_eps"])
    x, z = jnp.split(mm(u, w["in_proj"]), 2, axis=-1)
    tail0, h0 = initial if initial is not None else (
        jnp.zeros((kc - 1, di), jnp.float32), jnp.zeros((di, n), jnp.float32))
    padded = jnp.concatenate([tail0, x], axis=0)          # (s + kc-1, di)

    def conv(inputs):
        return sum(inputs[k:k + s] * w["conv_w"][:, k] for k in range(kc))

    t = jnp.arange(s)
    convolved = conv(padded)
    if reset_at is not None:
        forgot = jnp.where((jnp.arange(s + kc - 1) < reset_at + kc - 1)
                           [:, None], 0.0, padded)
        convolved = jnp.where((t >= reset_at)[:, None], conv(forgot),
                              convolved)
    x = jax.nn.silu(convolved + w["conv_b"])
    dt, B, C = jnp.split(mm(x, w["x_proj"]), [r, r + n], axis=-1)
    dt = rms_norm(dt, w["dt_norm"], eps)
    B = rms_norm(B, w["B_norm"], eps)
    C = rms_norm(C, w["C_norm"], eps)
    dt = jax.nn.softplus(mm(dt, w["dt_proj"]) + w["dt_bias"])   # (s, di)
    A = -jnp.exp(w["A_log"])                                     # (di, n)
    keep = jnp.ones((s,), bool) if reset_at is None else t != reset_at

    def step(h, inputs):
        keep_t, dt_t, x_t, B_t, C_t = inputs
        h = jnp.where(keep_t, h, 0.0)
        h = jnp.exp(dt_t[:, None] * A) * h + \
            (dt_t * x_t)[:, None] * B_t[None, :]
        h = state_round(h)
        return h, h @ C_t

    h, y = jax.lax.scan(step, h0, (keep, dt, x, B, C))
    y = y + w["D"] * x
    return mm(y * jax.nn.silu(z), w["out_proj"]), (padded[s:], h)


@functools.partial(jax.jit, static_argnames=(
    "model_items", "attention", "rounding", "state_rounding", "reset_at"))
def _layer(w, x, initial, model_items, attention, rounding, state_rounding,
           reset_at):
    model = dict(model_items)
    with jax.default_matmul_precision("highest"):
        rnd = ROUNDINGS[rounding]
        mm = lambda a, m: rnd(a) @ rnd(m)
        eps = model["rms_norm_eps"]
        u = rms_norm(x, w["norm1"], eps)
        state = None
        if attention:
            mixed = _attention(model, w, u, mm)
        else:
            mixed, state = _mamba(model, w, u, mm,
                                  ROUNDINGS[state_rounding], initial,
                                  reset_at)
        x = x + mixed
        u = rms_norm(x, w["norm2"], eps)
        x = x + mm(jax.nn.silu(mm(u, w["gate"])) * mm(u, w["up"]),
                   w["down"])
        return x, state


def _items(model):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float)) and
                        not isinstance(v, bool)))


@functools.partial(jax.jit, static_argnames=("eps", "rounding"))
def _head(emb, norm, x, positions, eps, rounding):
    with jax.default_matmul_precision("highest"):
        rnd = ROUNDINGS[rounding]
        x = rms_norm(jnp.take(x, positions, axis=0), norm, eps)
        return rnd(x) @ rnd(emb).T


def forward_many(model, seed, sequences, positions, rounding=None,
                 state_rounding=None, reset_at=None, initial=None,
                 return_state=False):
    """Logits ``[(len(positions[k]), V)]`` of each sequence
    ``sequences[k]`` (s_k,) at its positions: the full forward, layer
    by layer, each layer's weights drawn once, used on every sequence
    and dropped. ``initial``: per sequence ``{layer: (conv tail, SSM
    state)}`` to start those Mamba layers from, or None for zeros;
    ``return_state`` also returns per sequence every Mamba layer's
    final pair (of the sequence as given: pad nothing then)."""
    items = _items(model)
    emb = draw_embedding(model, seed)
    xs = [jnp.take(emb, jnp.asarray(ids, jnp.int32), axis=0)
          for ids in sequences]
    initial = initial or [None] * len(xs)
    final = [{} for _ in xs]
    for i in range(model["num_hidden_layers"]):
        w = draw_layer(model, seed, i)
        for k, x in enumerate(xs):
            xs[k], state = _layer(
                w, x, (initial[k] or {}).get(i), items,
                is_attention(model, i), rounding, state_rounding, reset_at)
            if state is not None:
                final[k][i] = state
        del w
    norm = jnp.ones((model["hidden_size"],), jnp.float32)
    logits = [_head(emb, norm, x, jnp.asarray(p, jnp.int32),
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
    """Parameters of the whole model, the tied embedding once."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    di, n = d_inner(model), model["mamba_d_state"]
    r, kc = model["mamba_dt_rank"], model["mamba_d_conv"]
    dh = d // model["num_attention_heads"]
    kv = model["num_key_value_heads"] * dh
    mlp = 3 * d * ff + 2 * d
    mamba = (d * 2 * di + di * kc + di + di * (r + 2 * n) + r * di + di +
             di * n + di + di * d + r + 2 * n)
    attn = 2 * d * d + 2 * d * kv
    n_attn = sum(is_attention(model, i)
                 for i in range(model["num_hidden_layers"]))
    return (model["vocab_size"] * d + d + n_attn * (attn + mlp) +
            (model["num_hidden_layers"] - n_attn) * (mamba + mlp))


def as_numpy(tree):
    return jax.tree_util.tree_map(np.asarray, tree)
