"""LFM2-MoE (LiquidAI's ``model_type: lfm2_moe``): gated short
convolutions beside a few rotary grouped-query attention layers, a
dense gated MLP in the leading layers and a sparse expert layer in the
rest, for serving through ``init_inference()``.

Layer ``i`` is what ``layer_types[i]`` says (``conv`` |
``full_attention``): ``h = x + Op(RMSNorm(x)); out = h +
FFN(RMSNorm(h))``, no biases, a final RMSNorm and the head tied to the
embedding. The convolution operator is ``W_out(C * conv3(B * x~))``
with ``(B, C, x~) = split(W_in u)`` and a depthwise causal convolution
of ``conv_L`` taps; attention has per-head RMS norms on queries and
keys and rotary positions on the whole head (rotate-half pairing). The
first ``n_dense_layers`` layers have a dense gated MLP; the others an
expert layer (ops/moe.py): ``top_k`` of ``n_experts`` a token, chosen
by sigmoid score plus a per-expert selection bias, weighted by their
renormalised scores, no token dropped. The equations are written out in
``benchmark/models/lfm2_reference.py``, the float32 yardstick; this
module is the program.

Serving keeps TWO kinds of state (``LFM2Decoder.cache_spec``): the
attention layers' keys (normed and rotated) and values in the engine's
page pool, ``(pages + 1, attention layers, page_size, n_kv_heads *
d_head)``; and per slot and convolution layer the last ``conv_L - 1``
gated inputs ``B * x~``, ``conv (conv layers, slots, (conv_L - 1) *
d_model)``, a slot's taps side by side in one row (models/jamba.py
says why). The rules of a recurrent state are Jamba's: the prefill
program of a request's FIRST chunk (``positions == 0``) starts from
zeros whatever the slot holds, a later chunk from the slot's tail, a
padded bucket leaves the tail of the chunk's last real token, and a
decode step advances only the slots the scheduler says are decoding.

The serving programs return, beside the hidden states, the expert
layers' summed load (``counters``: ``moe.load``; inference/decoder.py).

Serving only; a ``model`` mesh axis is refused.
"""
import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..inference.decoder import CacheSpec, StateSpec
from ..inference.kv_cache import read_scope, write_tokens
from ..ops import moe
from .jamba import _attend, _rms_norm

INIT_STD = 0.02
CONV, ATTENTION = "conv", "full_attention"
_FLOAT32_LEAVES = ("router", "expert_bias")


@dataclass
class LFM2Config:
    vocab_size: int = 65536
    d_model: int = 2048
    layer_types: tuple = (CONV, CONV, ATTENTION, CONV) * 3
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 7168                  # the dense layers' MLP
    d_expert: int = 1792              # one expert's MLP
    n_experts: int = 32
    top_k: int = 4
    n_dense_layers: int = 2
    conv_L: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    # spread of the selection bias drawn at init (a trained router is
    # uneven; the published config gives no number)
    expert_bias_std: float = 0.04
    init_std: float = INIT_STD
    max_seq_len: int = 128000
    dtype: object = jnp.bfloat16      # matrices, embedding, activations
    # the expert ids this chip holds, (first, past the last); None: all
    experts_held: object = None
    # "pallas" (ops/pallas/moe.py) | "xla" (lax.ragged_dot) | "auto":
    # pallas on a TPU, xla elsewhere
    moe_kernel: str = "auto"
    # the attention layers' paged read (models/jamba.py)
    paged_attention_kernel: str = "xla"

    @property
    def n_layers(self):
        return len(self.layer_types)

    @property
    def d_head(self):
        return self.d_model // self.n_heads

    @property
    def held(self):
        return tuple(self.experts_held or (0, self.n_experts))

    def is_attention(self, i):
        return self.layer_types[i] == ATTENTION

    def is_dense(self, i):
        return i < self.n_dense_layers

    @property
    def attention_layers(self):
        return [i for i in range(self.n_layers) if self.is_attention(i)]

    @property
    def conv_layers(self):
        return [i for i in range(self.n_layers) if not self.is_attention(i)]

    @property
    def expert_layers(self):
        return [i for i in range(self.n_layers) if not self.is_dense(i)]


def config_from_hf(model, **overrides):
    """An :class:`LFM2Config` from the keys of a published
    ``config.json`` (``model_type: lfm2_moe``)."""
    assert not model["conv_bias"], "a convolution bias is not supported"
    assert len(model["layer_types"]) == model["num_hidden_layers"]
    assert set(model["layer_types"]) <= {CONV, ATTENTION}
    extra = {k: model[k] for k in ("expert_bias_std",) if k in model}
    extra.update(overrides)
    return LFM2Config(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        layer_types=tuple(model["layer_types"]),
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"],
        d_expert=model["moe_intermediate_size"],
        n_experts=model["num_experts"], top_k=model["num_experts_per_tok"],
        n_dense_layers=model["num_dense_layers"],
        conv_L=model["conv_L_cache"], norm_eps=model["norm_eps"],
        rope_theta=float(model["rope_theta"]),
        norm_topk_prob=model["norm_topk_prob"],
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        use_expert_bias=model["use_expert_bias"],
        init_std=model.get("initializer_range", INIT_STD),
        max_seq_len=model["max_position_embeddings"], **extra)


# ------------------------------------------------------------------ init
def _key(seed, i):
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), i)


def init_layer(config, seed, i):
    """Layer ``i``'s weights from the seed's stream ``i``, split in the
    order written here: matrices normal(0, 0.02) as (in, out) in
    ``config.dtype``; norms 1; the router (d, E) float32; the selection
    bias normal(0, ``expert_bias_std``) float32. EVERY expert's
    matrices are drawn, then the held ones kept, so that a share of the
    experts holds the same numbers as the whole layer. ``conv_w`` is
    held (taps, d); an expert's gate and up matrices side by side."""
    d, dtype = config.d_model, config.dtype
    keys = iter(jax.random.split(_key(seed, i), 16))

    def normal(*shape, dtype=dtype):
        return (config.init_std * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    ones = lambda n: jnp.ones((n,), dtype)
    lp = {"operator_norm": ones(d), "ffn_norm": ones(d)}
    if config.is_attention(i):
        dh, kv = config.d_head, config.n_kv_heads * config.d_head
        lp.update(q=normal(d, d), k=normal(d, kv), v=normal(d, kv),
                  o=normal(d, d), q_norm=ones(dh), k_norm=ones(dh))
    else:
        lp.update(in_proj=normal(d, 3 * d),
                  conv_w=normal(d, config.conv_L).T,
                  out_proj=normal(d, d))
    if config.is_dense(i):
        ff = config.d_ff
        lp.update(w1=normal(d, ff), w3=normal(d, ff), w2=normal(ff, d))
        return lp
    E, ff = config.n_experts, config.d_expert
    first, past = config.held
    lp["router"] = normal(d, E, dtype=jnp.float32)
    lp["expert_bias"] = config.expert_bias_std * jax.random.normal(
        next(keys), (E,), jnp.float32)
    w1, w3 = normal(E, d, ff)[first:past], normal(E, d, ff)[first:past]
    lp["w13"] = jnp.concatenate([w1, w3], axis=-1)
    del w1, w3
    lp["w2"] = normal(E, ff, d)[first:past]
    return lp


def init_params(config, seed=0):
    return {
        "layers": [init_layer(config, seed, i)
                   for i in range(config.n_layers)],
        "embed": (config.init_std * jax.random.normal(
            _key(seed, config.n_layers),
            (config.vocab_size, config.d_model),
            jnp.float32)).astype(config.dtype),
        "final_norm": jnp.ones((config.d_model,), config.dtype),
    }


def num_params(config):
    """Parameters held here (the experts held, the tied embedding
    once)."""
    d, dh = config.d_model, config.d_head
    attn = 2 * d * d + 2 * d * config.n_kv_heads * dh + 2 * dh
    conv = 3 * d * d + d * config.conv_L + d * d
    dense = 3 * d * config.d_ff
    first, past = config.held
    experts = (past - first) * 3 * d * config.d_expert + \
        d * config.n_experts + config.n_experts
    n_attn, n_dense = len(config.attention_layers), config.n_dense_layers
    return (config.vocab_size * d + d + config.n_layers * 2 * d +
            n_attn * attn + (config.n_layers - n_attn) * conv +
            n_dense * dense + (config.n_layers - n_dense) * experts)


# --------------------------------------------------------------- layers
def _ffn(u, lp, config):
    """-> (the layer's FFN of ``u`` (.., d), its load (2, E) or None)."""
    if "router" not in lp:
        return (jax.nn.silu(u @ lp["w1"]) * (u @ lp["w3"])) @ lp["w2"], None
    flat = u.reshape(-1, u.shape[-1])
    chosen, weights = moe.route(
        flat, lp["router"],
        lp["expert_bias"] if config.use_expert_bias else None,
        config.top_k, config.norm_topk_prob, config.routed_scaling_factor)
    out, load = moe.expert_ffn(flat, chosen, weights, lp["w13"], lp["w2"],
                               config.held, config.n_experts,
                               kernel=config.moe_kernel)
    return out.reshape(u.shape), load


def _conv_sequence(u, lp, config, tail0, valid_len):
    """The convolution operator over ONE sequence chunk ``u`` (s, d)
    from the tail ``tail0`` (conv_L - 1, d). -> (output (s, d), the
    tail as it is after ``valid_len`` tokens)."""
    with jax.named_scope("short_conv"):
        s, L = u.shape[0], config.conv_L
        B, C, x = jnp.split(u @ lp["in_proj"], 3, axis=-1)
        z = B * x
        padded = jnp.concatenate([tail0.astype(z.dtype), z], axis=0)
        conv = sum(padded[j:j + s].astype(jnp.float32) *
                   lp["conv_w"][j].astype(jnp.float32) for j in range(L))
        # the last conv_L - 1 real inputs (the old tail's, where the
        # chunk is shorter than that)
        tail = jax.lax.dynamic_slice_in_dim(padded, valid_len, L - 1,
                                            axis=0)
        return (C * conv.astype(z.dtype)) @ lp["out_proj"], tail


def _conv_prefill(u, lp, config, state, m, slot, start, valid_len):
    """One slot's chunk against the tail pool (``m``: the layer's index
    among the convolution layers). The first chunk (``start == 0``)
    starts from zeros whatever the slot holds."""
    (conv,) = state
    d = config.d_model
    tail0 = jnp.where(start == 0, 0,
                      conv[m, slot].reshape(config.conv_L - 1, d))
    out, tail = _conv_sequence(u[0], lp, config, tail0, valid_len)
    conv = conv.at[m, slot].set(tail.astype(conv.dtype).reshape(-1))
    return out[None], (conv,)


def _conv_decode(u, lp, config, state, m, advance):
    """One token for every slot (u (slots, 1, d)); a slot outside
    ``advance`` keeps its tail."""
    with jax.named_scope("short_conv"):
        (conv,) = state
        d = config.d_model
        B, C, x = jnp.split(u[:, 0] @ lp["in_proj"], 3, axis=-1)
        z = B * x                                          # (slots, d)
        # a slot's row: its conv_L - 1 last inputs, then the new one
        window = jnp.concatenate([conv[m], z.astype(conv.dtype)], axis=1)
        acc = sum(window[:, j * d:(j + 1) * d].astype(jnp.float32) *
                  lp["conv_w"][j].astype(jnp.float32)
                  for j in range(config.conv_L))
        conv = conv.at[m].set(jnp.where(advance[:, None], window[:, d:],
                                        conv[m]))
        out = (C * acc.astype(z.dtype)) @ lp["out_proj"]
        return out[:, None], (conv,)


def _rotary(x, positions, theta):
    """Rotary embedding of the whole head, rotate-half pairing ``(i, i
    + dh / 2)``. x (b, s, heads, dh); positions (b, s) absolute."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.cos(angle)[:, :, None, :]
    sin = jnp.sin(angle)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _qkv(u, lp, config, tok_pos):
    """-> q (b, s, h, dh), k (b, s, kvh, dh), both normed per head and
    rotated to ``tok_pos`` (b, s), and v (b, s, kvh, dh)."""
    b, s, _ = u.shape
    h, kvh, dh = config.n_heads, config.n_kv_heads, config.d_head
    eps, theta = config.norm_eps, config.rope_theta
    q = _rms_norm((u @ lp["q"]).reshape(b, s, h, dh), lp["q_norm"], eps)
    k = _rms_norm((u @ lp["k"]).reshape(b, s, kvh, dh), lp["k_norm"], eps)
    v = (u @ lp["v"]).reshape(b, s, kvh, dh)
    return _rotary(q, tok_pos, theta), _rotary(k, tok_pos, theta), v


def _attention_paged(u, lp, config, k_cache, v_cache, a, positions,
                     page_tables, valid_lens, page_size):
    """An attention layer against the page pool (``a``: the layer's
    index among the attention layers): ``kv_cache.write_tokens`` and
    the (page, layer) gather of ``models/jamba.py::_attention_paged``,
    the keys stored normed and rotated."""
    b, s, _ = u.shape
    h, kvh, dh = config.n_heads, config.n_kv_heads, config.d_head
    max_pages = page_tables.shape[1]
    tok_pos = positions[:, None] + jnp.arange(s)[None, :]
    q, k, v = _qkv(u, lp, config, tok_pos)
    k_cache, v_cache = write_tokens(
        (k_cache, v_cache), (k.reshape(b, s, -1), v.reshape(b, s, -1)),
        a, page_tables, positions, valid_lens, page_size)

    with jax.named_scope(read_scope(s, page_size)):
        if config.paged_attention_kernel == "pallas":
            from ..ops.pallas.paged_attention import paged_attention
            ctx = paged_attention(q, k_cache, v_cache, page_tables,
                                  positions, valid_lens, layer_idx=a,
                                  page_size=page_size).reshape(b, s, h * dh)
        else:
            def rows_of(cache):
                return cache[page_tables, a].reshape(
                    b, max_pages * page_size, kvh, dh)

            ctx = _attend(q, rows_of(k_cache), rows_of(v_cache), positions,
                          valid_lens, config)
    return ctx.astype(u.dtype) @ lp["o"], k_cache, v_cache


def _attention_dense(u, lp, config):
    b, s, _ = u.shape
    tok_pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q, k, v = _qkv(u, lp, config, tok_pos)
    ctx = _attend(q, k, v, jnp.zeros((b,), jnp.int32), None, config)
    return ctx.astype(u.dtype) @ lp["o"]


def forward_hidden(params, input_ids, config, cache=None, positions=None,
                   page_tables=None, valid_lens=None, page_size=None,
                   state_slot=None, state_advance=None, counters=False):
    """Embedding + the layer stack + the final norm -> hidden states.

    Without ``cache``: the plain forward over whole sequences (b, s),
    every convolution from a zero tail. With ``cache`` = ``(k, v,
    conv)`` returns ``(hidden, cache)``; ``state_slot`` /
    ``state_advance`` as in ``models/jamba.py::forward_hidden``. With
    ``counters`` the last of what is returned is ``(load,)``: the
    expert layers' summed load (ops/moe.py), under
    ``LFM2Decoder.counters``' names."""
    x = jnp.take(params["embed"], input_ids, axis=0)
    eps = config.norm_eps
    if cache is not None:
        assert page_tables is not None, \
            "LFM2 serves from pages only (page_tables=)"
        k_cache, v_cache, *state = cache
        state = tuple(state)
        if state_slot is None:
            assert input_ids.shape[1] == 1, \
                "a recurrent state advances one token a decode step"
            if state_advance is None:
                state_advance = jnp.ones((input_ids.shape[0],), bool)
    load = jnp.zeros((2, config.n_experts), jnp.int32)
    a = m = 0
    for i, lp in enumerate(params["layers"]):
        u = _rms_norm(x, lp["operator_norm"], eps)
        if config.is_attention(i):
            if cache is None:
                mixed = _attention_dense(u, lp, config)
            else:
                mixed, k_cache, v_cache = _attention_paged(
                    u, lp, config, k_cache, v_cache, a, positions,
                    page_tables, valid_lens, page_size)
            a += 1
        else:
            if cache is None:
                zero = jnp.zeros((config.conv_L - 1, config.d_model),
                                 x.dtype)
                mixed = jax.vmap(lambda row: _conv_sequence(
                    row, lp, config, zero, row.shape[0])[0])(u)
            elif state_slot is not None:
                mixed, state = _conv_prefill(
                    u, lp, config, state, m, state_slot, positions[0],
                    valid_lens[0])
            else:
                mixed, state = _conv_decode(u, lp, config, state, m,
                                            state_advance)
            m += 1
        x = x + mixed
        out, layer_load = _ffn(_rms_norm(x, lp["ffn_norm"], eps), lp,
                               config)
        x = x + out
        if layer_load is not None:
            load = load + layer_load
    x = _rms_norm(x, params["final_norm"], eps)
    out = (x,) if cache is None else (x, (k_cache, v_cache) + state)
    if counters:
        out += ((load,),)
    return out[0] if len(out) == 1 else out


def logits(params, hidden):
    """The tied head."""
    with jax.named_scope("head"):
        return hidden @ params["embed"].astype(hidden.dtype).T


def lm_loss(params, input_ids, labels, config):
    hidden = forward_hidden(params, input_ids, config)
    lg = logits(params, hidden).astype(jnp.float32)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)
    return -ll.mean()


# -------------------------------------------------------------- serving
class LFM2Decoder:
    """What ``init_inference()`` asks of a model (inference/decoder.py)."""

    recurrent = True
    # what the serving programs return beside their tokens
    counters = ("moe.load",)

    def __init__(self, config):
        self.config = config

    def cache_spec(self):
        cfg = self.config
        return CacheSpec(
            kv_layers=len(cfg.attention_layers), kv_heads=cfg.n_kv_heads,
            d_head=cfg.d_head,
            state=(StateSpec("conv", (len(cfg.conv_layers),),
                             ((cfg.conv_L - 1) * cfg.d_model,),
                             cfg.dtype),))

    def serving_config(self, mesh):
        from ..parallel.topology import MODEL_AXIS
        if mesh is not None and int(mesh.shape.get(MODEL_AXIS, 1)) > 1:
            raise ValueError(
                "LFM2 has no tensor-parallel layout yet: a mesh with a "
                "'model' axis cannot serve it")
        return dataclasses.replace(self.config,
                                   paged_attention_kernel="xla")

    def decode_config(self, config, paged_attention_kernel):
        return dataclasses.replace(
            config, paged_attention_kernel=paged_attention_kernel)

    def serving_params(self, params, dtype):
        def cast(path, x):
            x = jnp.asarray(x)
            keep = path[-1].key in _FLOAT32_LEAVES or \
                not jnp.issubdtype(x.dtype, jnp.floating)
            return x if keep else x.astype(dtype)
        return jax.tree_util.tree_map_with_path(cast, params)

    @staticmethod
    def counter_attrs(name, value):
        """The attributes of the ``moe.load`` span of one launch, from
        the load its program returned."""
        return moe.load_attrs(value)

    forward_hidden = staticmethod(forward_hidden)
    logits = staticmethod(logits)


def make_lfm2_model(config=None, seed=0, **overrides):
    """A :class:`deepspeed_tpu.runtime.model.Model` for
    ``init_inference()``; weights from ``seed`` (``init_layer``)."""
    from ..runtime.model import Model
    config = dataclasses.replace(config or LFM2Config(), **overrides)
    params = init_params(config, seed=seed)

    def apply_fn(params, input_ids, labels, rng=None, train=True):
        return lm_loss(params, input_ids, labels, config)

    model = Model(apply_fn, params, name="lfm2")
    model.config = config
    model.decoder = LFM2Decoder(config)
    return model
