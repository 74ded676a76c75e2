"""Granite-MoE-Hybrid (IBM's ``model_type: granitemoehybrid``, Granite
4.0-H): Mamba-2 layers beside a few grouped-query attention layers that
carry no position, a sparse expert layer AND a shared gated MLP after
every mixer, four scalar multipliers, for serving through
``init_inference()``.

Layer ``i`` is what ``layer_types[i]`` says (``mamba`` | ``attention``;
published: nine Mamba-2 layers to every attention layer). The block is
pre-norm with a scaled residual: ``h = x + r * Mixer(RMSNorm(x)); out =
h + r * (Routed(u) + Shared(u))`` with ``u = RMSNorm(h)`` and ``r =
residual_multiplier``; the embedding is multiplied by
``embedding_multiplier``; a final RMSNorm, then the TIED head divided by
``logits_scaling``. An attention layer: no bias, no rotation, scores
times ``attention_multiplier`` (which is NOT ``1/sqrt(d_head)``). The
paged kernels fix ``1/sqrt(d_head)`` at their public wrappers, so ``q``
is scaled HERE by ``attention_multiplier * sqrt(d_head)``, in float32
before it is rounded to the compute dtype (on ``q``, not folded into
``W_q``: one rounding, and the weights stay as drawn). A Mamba-2 layer
(ops/pallas/mamba2.py has the recurrence): ONE ``in_proj`` split ``z |
xBC | dt``; ``xBC`` through a causal depthwise convolution of ``d_conv``
taps with bias and SiLU, split ``x | B | C``; ``dt = softplus(dt +
dt_bias)``, the decay ``exp(-exp(A_log) dt)`` a head; the gate BEFORE
the norm: ``RMSNorm(y * silu(z))`` over the whole inner width (one
group), then ``out_proj``. The expert layer (ops/moe.py): ``top_k`` of
``n_experts`` a token by softmax score renormalised over the chosen
(which is the published top-k of the logits, softmaxed: softmax is
monotone), no token dropped; beside it ONE shared gated MLP, added
whole. The equations are written out in
``benchmark/models/granite_moe_hybrid_reference.py``, the float32
yardstick; this module is the program.

A chip may hold a SHARE of each layer (``experts_held``: a range of the
``n_experts`` the router scores; ``vocab_size``: the rows of the tied
embedding held, which are then the whole vocabulary here): the router
keeps its width and its ``top_k``, a row chosen for an expert held
elsewhere adds nothing here, and that partial result goes on to the next
layer. No code stands in for the absent chips or their exchange.

Serving keeps TWO kinds of state (``GraniteMoeHybridDecoder.cache_spec``):

* the attention layers' keys and values in the engine's page pool,
  ``(pages + 1, attention layers, page_size, n_kv_heads * d_head)``, one
  group, written by ``kv_cache.write_tokens``; a decode step reads them
  in the grouped page walk (ops/pallas/paged_attention.py), a prompt
  chunk in ``chunk_attention`` (ops/pallas/chunk_attention.py), both
  under ``paged_attention_kernel: pallas``; elsewhere both in XLA's loop
  (ops/chunk_attention.py), the oracle of the two kernels;
* per slot and Mamba-2 layer a convolution tail, ``conv (mamba layers,
  slots, (d_conv - 1) * conv channels)`` in the compute dtype (a slot's
  last inputs in ONE row, as models/jamba.py holds its own), and the
  recurrence's state, ``ssd (mamba layers, slots, d_state, heads *
  d_head)`` in float32: the state's width second-minor and every head's
  channels side by side in the minor dimension (the reference's ``S_h``
  transposed; 128 x 64 = 8,192 = 64 x 128 lanes at the published
  widths, where a head's 64 alone would be half a tile).
  ``StatePool.nbytes`` then reads 128 x 8,192 x 4 = 4,194,304 B a slot
  and layer for it, and 9 x (4,194,304 + 3 x 8,448 x 2) = 38.2 MB a
  slot at nine Mamba-2 layers.

A recurrent state has no causal mask to hide what a slot held before:
the prefill program that runs a request's FIRST chunk (``positions ==
0``) starts from zeros whatever the slot holds, a later chunk starts
from the slot's state, a padded bucket leaves state and tails as they
were after the chunk's last real token (``valid_lens``), and the decode
program advances only the slots the scheduler says are decoding
(``state_advance``).

The serving programs return, beside the hidden states (``counters``):
``moe.load``, the expert layers' summed load with, in a third row, the
(token, choice) pairs the launch routed ANYWHERE (``routed``) and the
passes its expert layers made over their capacity (``passes``), as
models/cohere2_moe.py returns them; and ``ssd.advanced``, the slots
whose state the launch advanced.

Serving only; a ``model`` mesh axis is refused. ``lm_loss``
differentiates the XLA path (whole sequences, every recurrence from
zero).
"""
import dataclasses
import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..inference.decoder import CacheSpec, StateSpec
from ..inference.kv_cache import write_path, write_tokens
from ..ops import moe
from ..ops.chunk_attention import (block_tokens, blocked_attention,
                                   paged_blocked_attention)
from ..ops.pallas import mamba2
from ..utils.annotate import open_setup_span
from .cohere2_moe import _draw_experts
from .jamba import _rms_norm
from .mellum import INIT_STD, _key

DT_MIN, DT_MAX = 1e-3, 1e-1
A_MIN, A_MAX = 1.0, 16.0
MAMBA, ATTENTION = "mamba", "attention"
_FLOAT32_LEAVES = ("router", "A_log", "dt_bias", "D")
# the streams of a layer's key, in the order
# benchmark/models/granite_moe_hybrid_reference.py::draw_layer splits them
_STREAMS = ("in_proj", "conv_w", "conv_b", "A", "dt", "out_proj", "q", "k",
            "v", "o", "router", "shared", "experts")
_F32 = jnp.float32


@dataclass
class GraniteMoeHybridConfig:
    vocab_size: int = 100352          # the rows of the embedding HELD
    d_model: int = 4096
    layer_types: tuple = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    n_heads: int = 32
    n_kv_heads: int = 8
    mamba_heads: int = 128
    mamba_d_head: int = 64
    d_state: int = 128
    d_conv: int = 4
    d_expert: int = 768
    n_experts: int = 72               # the router's width
    top_k: int = 10
    # (first, past the last) of the experts held here; None: all
    experts_held: object = None
    d_shared: int = 1536
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    init_std: float = INIT_STD
    max_seq_len: int = 131072
    dtype: object = jnp.bfloat16      # matrices, embedding, activations
    # "pallas" (ops/pallas/mamba2.py's step kernel) | "xla" (its oracle)
    # | "auto": pallas on a TPU, xla elsewhere
    ssd_kernel: str = "auto"
    # "pallas" (ops/pallas/moe.py) | "xla" (lax.ragged_dot) | "auto"
    moe_kernel: str = "auto"
    # the attention layers' paged read: "pallas" (a step: the grouped
    # page walk; a chunk: chunk_attention) | "xla" (the blocked loop);
    # the engine sets it on its program families, from
    # inference.paged_attention_kernel
    paged_attention_kernel: str = "xla"

    @property
    def n_layers(self):
        return len(self.layer_types)

    @property
    def d_head(self):
        return self.d_model // self.n_heads

    def is_mamba(self, i):
        return self.layer_types[i] == MAMBA

    @property
    def attention_layers(self):
        return [i for i in range(self.n_layers) if not self.is_mamba(i)]

    @property
    def mamba_layers(self):
        return [i for i in range(self.n_layers) if self.is_mamba(i)]

    @property
    def expert_layers(self):
        return list(range(self.n_layers))

    @property
    def d_inner(self):
        return self.mamba_heads * self.mamba_d_head

    @property
    def conv_channels(self):
        """x, B and C side by side: what the convolution runs over."""
        return self.d_inner + 2 * self.d_state

    @property
    def held(self):
        return tuple(self.experts_held or (0, self.n_experts))


def config_from_hf(model, **overrides):
    """A :class:`GraniteMoeHybridConfig` from the keys of a published
    ``config.json`` (``model_type: granitemoehybrid``). A chip's share
    says so beside them: ``experts_held`` (then ``num_local_experts``
    counts the experts held and ``router_num_experts`` the router's
    width) and ``padded_vocab_size`` (the embedding's rows held)."""
    types = tuple(model["layer_types"])
    assert len(types) == model["num_hidden_layers"] and \
        set(types) <= {MAMBA, ATTENTION}, "layer_types {}".format(types)
    assert model["position_embedding_type"] == "nope", \
        "models/granite_moe_hybrid.py's attention layers have no rotation"
    assert model["mamba_n_groups"] == 1, \
        "models/granite_moe_hybrid.py shares B and C among all heads"
    assert model["mamba_conv_bias"] and not model["mamba_proj_bias"] and \
        not model["attention_bias"] and model["tie_word_embeddings"]
    assert model["normalization_function"] == "rmsnorm" and \
        model["hidden_act"] == "silu"
    assert model["mamba_expand"] * model["hidden_size"] == \
        model["mamba_n_heads"] * model["mamba_d_head"]
    n_experts = model.get("router_num_experts", model["num_local_experts"])
    held = tuple(model.get("experts_held", (0, n_experts)))
    assert held[1] - held[0] == model["num_local_experts"] and \
        0 <= held[0] < held[1] <= n_experts, \
        (held, model["num_local_experts"])
    return GraniteMoeHybridConfig(
        vocab_size=model.get("padded_vocab_size", model["vocab_size"]),
        d_model=model["hidden_size"], layer_types=types,
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        mamba_heads=model["mamba_n_heads"],
        mamba_d_head=model["mamba_d_head"], d_state=model["mamba_d_state"],
        d_conv=model["mamba_d_conv"], d_expert=model["intermediate_size"],
        n_experts=n_experts, top_k=model["num_experts_per_tok"],
        experts_held=held, d_shared=model["shared_intermediate_size"],
        embedding_multiplier=float(model["embedding_multiplier"]),
        residual_multiplier=float(model["residual_multiplier"]),
        attention_multiplier=float(model["attention_multiplier"]),
        logits_scaling=float(model["logits_scaling"]),
        rms_norm_eps=model["rms_norm_eps"],
        init_std=model.get("initializer_range", INIT_STD),
        max_seq_len=model["max_position_embeddings"], **overrides)


# ------------------------------------------------------------------ init
def init_layer(config, seed, i):
    """Layer ``i``'s weights from the seed's stream ``i``, one key a
    name of ``_STREAMS`` (``granite_moe_hybrid_reference.draw_layer``'s):
    matrices normal(0, ``init_std``) as (in, out) in ``config.dtype``,
    norms and ``D`` 1, the router (d, E) float32 over ALL experts; a
    Mamba-2 layer's convolution (taps and bias) uniform(+-1/2),
    ``A_log = log(A)`` with ``A`` uniform in (1, 16) and ``dt_bias`` the
    inverse softplus of ``dt`` log-uniform in [1e-3, 1e-1] (the layer's
    published initialiser), float32; ``conv_w`` is held transposed,
    channels minor. Expert ``e`` draws its gate, up and down matrices
    from its own key (the stream's folded with ``e``), so a share holds
    what the whole layer would hold of those experts; an expert's gate
    and up matrices lie side by side (``input_linear`` chunked in two),
    the shared MLP's likewise."""
    d, dtype = config.d_model, config.dtype
    keys = dict(zip(_STREAMS, jax.random.split(_key(seed, i),
                                               len(_STREAMS))))

    def normal(name, *shape, dtype=dtype):
        return (config.init_std * jax.random.normal(
            keys[name], shape, _F32)).astype(dtype)

    lp = {"mixer_norm": jnp.ones((d,), dtype),
          "moe_norm": jnp.ones((d,), dtype),
          "router": normal("router", d, config.n_experts, dtype=_F32)}
    if config.is_mamba(i):
        H, di, ch = config.mamba_heads, config.d_inner, config.conv_channels
        A = jax.random.uniform(keys["A"], (H,), _F32, A_MIN, A_MAX)
        dt = jnp.exp(jax.random.uniform(keys["dt"], (H,), _F32) *
                     (math.log(DT_MAX) - math.log(DT_MIN)) +
                     math.log(DT_MIN))
        half = lambda name, *shape: jax.random.uniform(
            keys[name], shape, _F32, -0.5, 0.5).astype(dtype)
        lp.update(
            in_proj=normal("in_proj", d, di + ch + H),
            conv_w=half("conv_w", ch, config.d_conv).T,
            conv_b=half("conv_b", ch), A_log=jnp.log(A),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),     # inverse softplus
            D=jnp.ones((H,), _F32), ssd_norm=jnp.ones((di,), dtype),
            out_proj=normal("out_proj", di, d))
    else:
        h, kvh, dh = config.n_heads, config.n_kv_heads, config.d_head
        lp.update(q=normal("q", d, h * dh), k=normal("k", d, kvh * dh),
                  v=normal("v", d, kvh * dh), o=normal("o", h * dh, d))
    draw = functools.partial(_draw_experts, d=d, std=config.init_std,
                             dtype=dtype)
    lp["w13"], lp["w2"] = draw(keys["experts"], jnp.arange(*config.held),
                               ff=config.d_expert)
    s13, s2 = draw(keys["shared"], jnp.arange(1), ff=config.d_shared)
    lp["shared13"], lp["shared2"] = s13[0], s2[0]
    return lp


def init_params(config, seed=0):
    return {
        "layers": [init_layer(config, seed, i)
                   for i in range(config.n_layers)],
        "embed": (config.init_std * jax.random.normal(
            _key(seed, config.n_layers),
            (config.vocab_size, config.d_model), _F32)).astype(
                config.dtype),
        "final_norm": jnp.ones((config.d_model,), config.dtype),
    }


def num_params(config):
    """Parameters HELD: the share's experts and embedding rows."""
    d, H, di = config.d_model, config.mamba_heads, config.d_inner
    ch = config.conv_channels
    mamba = d * (di + ch + H) + ch * config.d_conv + ch + 3 * H + di + \
        di * d
    attn = 2 * d * config.n_heads * config.d_head + \
        2 * d * config.n_kv_heads * config.d_head
    first, past = config.held
    experts = (past - first) * 3 * d * config.d_expert + \
        3 * d * config.d_shared + d * config.n_experts + 2 * d
    n_mamba = len(config.mamba_layers)
    return (config.vocab_size * d + d + n_mamba * mamba +
            (config.n_layers - n_mamba) * attn + config.n_layers * experts)


# --------------------------------------------------------------- layers
def _use_pallas(config):
    if config.ssd_kernel == "auto":
        from ..ops.pallas.common import default_interpret
        return not default_interpret()
    return config.ssd_kernel == "pallas"


def _residual(x, y, config):
    """``x + residual_multiplier * y``, added in float32."""
    return (x.astype(_F32) +
            config.residual_multiplier * y.astype(_F32)).astype(x.dtype)


def _experts(u, lp, config):
    """-> (the routed experts HELD here plus the shared MLP, of ``u``
    (.., d); the load (3, E): ops/moe.py's two rows and, at [2, 0], the
    (token, choice) pairs routed anywhere, at [2, 1] the passes the
    layer made over its share's capacity)."""
    flat = u.reshape(-1, u.shape[-1])
    # softmax over all, the chosen renormalised: the published softmax
    # over the top-k logits
    chosen, weights = moe.route(flat, lp["router"], None, config.top_k,
                                True, norm_eps=0.0, scoring="softmax")
    out, load = moe.expert_ffn(flat, chosen, weights, lp["w13"], lp["w2"],
                               config.held, config.n_experts,
                               kernel=config.moe_kernel)
    with jax.named_scope("moe.shared"):
        hidden = flat @ lp["shared13"]
        width = config.d_shared
        out = out + (jax.nn.silu(hidden[:, :width]) * hidden[:, width:]) @ \
            lp["shared2"]
    routed = jnp.zeros((1, config.n_experts), jnp.int32).at[0, 0].set(
        chosen.size).at[0, 1].set(moe.share_passes(
            load, chosen.size, config.held, config.n_experts))
    return out.reshape(u.shape), jnp.concatenate([load, routed])


def _project(u, lp, config):
    """-> the gate ``z`` (.., d_inner), ``xBC`` before its convolution
    (.., channels), both in the compute dtype, and ``dt`` (.., H) after
    its softplus, float32."""
    with jax.named_scope("ssd.proj"):
        di, ch = config.d_inner, config.conv_channels
        zxbcdt = u @ lp["in_proj"]
        dt = jax.nn.softplus(zxbcdt[..., di + ch:].astype(_F32) +
                             lp["dt_bias"].astype(_F32))
        return zxbcdt[..., :di], zxbcdt[..., di:di + ch], dt


def _split(xc, config):
    """The convolved ``xBC`` (.., channels) -> x (.., d_inner), B, C
    (.., d_state)."""
    di, n = config.d_inner, config.d_state
    return xc[..., :di], xc[..., di:di + n], xc[..., di + n:]


def _gate_norm_project(y, z, lp, config):
    """``W_out(RMSNorm(y * silu(z)))``: the gate BEFORE the norm, which
    spans the whole inner width. y (.., d_inner) float32 as the state
    gave it, z in the compute dtype."""
    with jax.named_scope("ssd.norm"):
        g = y * jax.nn.silu(z.astype(_F32))
        g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) +
                              config.rms_norm_eps)
        g = (g * lp["ssd_norm"].astype(_F32)).astype(z.dtype)
    with jax.named_scope("ssd.proj"):
        return g @ lp["out_proj"]


def _mamba_sequence(u, lp, config, tail0, s0, valid_len):
    """The Mamba-2 mixer over ONE sequence chunk ``u`` (s, d) from the
    convolution tail ``tail0`` (d_conv - 1, channels) and the state
    ``s0`` (d_state, d_inner). -> (mixer output (s, d), the tail and the
    state as they are after ``valid_len`` tokens)."""
    s, kc = u.shape[0], config.d_conv
    z, xbc, dt = _project(u, lp, config)
    with jax.named_scope("ssd.conv"):
        padded = jnp.concatenate([tail0.astype(xbc.dtype), xbc], axis=0)
        conv = lp["conv_b"].astype(_F32) + sum(
            padded[j:j + s].astype(_F32) * lp["conv_w"][j].astype(_F32)
            for j in range(kc))
        xc = jax.nn.silu(conv).astype(xbc.dtype)
        # the last d_conv - 1 real inputs (the old tail's, where the
        # chunk is shorter than that)
        tail = jax.lax.dynamic_slice_in_dim(padded, valid_len, kc - 1,
                                            axis=0)
    x, B, C = _split(xc, config)
    g = -jnp.exp(lp["A_log"].astype(_F32)) * dt
    y, sT = mamba2.ssd_chunk(x, dt, B, C, g, lp["D"], s0, valid_len)
    return _gate_norm_project(y, z, lp, config), tail, sT


def _mamba_prefill(u, lp, config, state, m, slot, start, valid_len):
    """One slot's chunk against the state pools (``m``: the layer's
    index among the Mamba-2 layers). The first chunk (``start == 0``)
    starts from zeros whatever the slot holds."""
    conv, ssd = state
    first = start == 0
    tail0 = jnp.where(first, 0, conv[m, slot].reshape(
        config.d_conv - 1, config.conv_channels))
    s0 = jnp.where(first, 0, ssd[m, slot].astype(_F32))
    out, tail, sT = _mamba_sequence(u[0], lp, config, tail0, s0, valid_len)
    conv = conv.at[m, slot].set(tail.astype(conv.dtype).reshape(-1))
    ssd = ssd.at[m, slot].set(sT.astype(ssd.dtype))
    return out[None], (conv, ssd)


def _mamba_decode(u, lp, config, state, m, advance):
    """One token for every slot (u (slots, 1, d)); a slot outside
    ``advance`` keeps its tail and its state."""
    conv, ssd = state
    ch = config.conv_channels
    z, xbc, dt = _project(u[:, 0], lp, config)
    with jax.named_scope("ssd.conv"):
        # a slot's row: its d_conv - 1 last inputs, then the new one
        window = jnp.concatenate([conv[m], xbc.astype(conv.dtype)], axis=1)
        acc = lp["conv_b"].astype(_F32) + sum(
            window[:, j * ch:(j + 1) * ch].astype(_F32) *
            lp["conv_w"][j].astype(_F32) for j in range(config.d_conv))
        xc = jax.nn.silu(acc).astype(xbc.dtype)
        conv = conv.at[m].set(jnp.where(advance[:, None], window[:, ch:],
                                        conv[m]))
    x, B, C = _split(xc, config)
    # a slot held back keeps its state: no decay and nothing written
    # (selected, not multiplied: its row may hold anything)
    hold = ~advance[:, None]
    a = jnp.where(hold, 1.0, jnp.exp(-jnp.exp(lp["A_log"].astype(_F32)) *
                                     dt))
    dt = jnp.where(hold, 0.0, dt)
    x, B, C = (jnp.where(hold, 0.0, v.astype(_F32)) for v in (x, B, C))
    step = mamba2.ssd_step if _use_pallas(config) else mamba2.ssd_step_xla
    with jax.named_scope("ssd.step"):
        y, ssd = step(ssd, m, x, dt, B, C, a, lp["D"])
    return _gate_norm_project(y, z, lp, config)[:, None], (conv, ssd)


def _qkv(u, lp, config):
    """-> q (b, s, h, dh) times ``attention_multiplier * sqrt(d_head)``
    (the readers below all divide by ``sqrt(d_head)``), k, v (b, s, kvh,
    dh). No rotation."""
    b, s, _ = u.shape
    h, kvh, dh = config.n_heads, config.n_kv_heads, config.d_head
    scale = config.attention_multiplier * math.sqrt(dh)
    q = jnp.dot(u, lp["q"], preferred_element_type=_F32) * scale
    return (q.astype(u.dtype).reshape(b, s, h, dh),
            (u @ lp["k"]).reshape(b, s, kvh, dh),
            (u @ lp["v"]).reshape(b, s, kvh, dh))


def _attention_paged(u, lp, config, pools, a, positions, page_tables,
                     valid_lens, page_size):
    """An attention layer against the page pool (``a``: its index among
    the attention layers): ``kv_cache.write_tokens``, then the read:
    under ``paged_attention_kernel: pallas`` the grouped page walk for a
    launch that wrote rows (a decode step) and ``chunk_attention`` for
    one that wrote pages (a chunk), else the blocked loop."""
    b, s, _ = u.shape
    q, k, v = _qkv(u, lp, config)
    k_pool, v_pool = write_tokens(
        pools, (k.reshape(b, s, -1), v.reshape(b, s, -1)), a, page_tables,
        positions, valid_lens, page_size)
    if config.paged_attention_kernel != "pallas":
        ctx = paged_blocked_attention(q, k_pool, v_pool, a, page_tables,
                                      positions, valid_lens, page_size)
    elif write_path(s, page_size) == "pages":
        from ..ops.pallas.chunk_attention import chunk_attention
        ctx = chunk_attention(q, k_pool, v_pool, a, page_tables, positions,
                              valid_lens, page_size, None)
    else:
        from ..ops.pallas.paged_attention import paged_attention
        ctx = paged_attention(q, k_pool, v_pool, page_tables, positions,
                              valid_lens, layer_idx=a, page_size=page_size)
    return ctx.astype(u.dtype).reshape(b, s, -1) @ lp["o"], (k_pool, v_pool)


def _attention_dense(u, lp, config):
    """Whole sequences from position 0, no cache: the blocked attention
    over the sequence's own keys."""
    b, s, _ = u.shape
    q, k, v = _qkv(u, lp, config)
    block = block_tokens(s)
    n_blocks = -(-s // block)
    pad = ((0, 0), (0, n_blocks * block - s), (0, 0), (0, 0))
    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    ctx = blocked_attention(
        q, lambda c: tuple(jax.lax.dynamic_slice_in_dim(
            x, c * block, block, 1) for x in (k, v)),
        n_blocks, block,
        jnp.broadcast_to(jnp.arange(s)[None, :], (b, s)),
        jnp.full((b,), s - 1, jnp.int32), config.n_kv_heads)
    return ctx.astype(u.dtype).reshape(b, s, -1) @ lp["o"]


def forward_hidden(params, input_ids, config, cache=None, positions=None,
                   page_tables=None, valid_lens=None, page_size=None,
                   state_slot=None, state_advance=None, counters=False):
    """Embedding + the layer stack + the final norm -> hidden states.

    Without ``cache``: the plain forward over whole sequences (b, s),
    every recurrence from zero. With ``cache`` = ``(k, v, conv, ssd)``
    (the page pool and the state pools of the module docstring) returns
    ``(hidden, cache)``: ``state_slot`` (int32 scalar) selects prefill
    of one slot's chunk (b = 1; ``positions[0]`` the chunk's start, 0
    meaning a request's first chunk; ``valid_lens[0]`` its real
    tokens); otherwise decode, one token for every slot,
    ``state_advance`` (slots,) bool marking the slots whose recurrent
    state this step advances. With ``counters`` the last of what is
    returned is ``(load, advanced)``: the expert layers' summed load and
    what they routed anywhere (``_experts``), and int32 (2,), the slots
    whose state the launch advanced and whether it was a decode step (1)
    or a chunk (0), under ``GraniteMoeHybridDecoder.counters``' names."""
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], input_ids, axis=0)
        x = (x.astype(_F32) * config.embedding_multiplier).astype(x.dtype)
    eps = config.rms_norm_eps
    # (slots advanced, 1 for a decode step): a chunk advances its slot
    advanced = jnp.array([input_ids.shape[0], 0], jnp.int32)
    if cache is not None:
        assert page_tables is not None, \
            "Granite-MoE-Hybrid serves from pages only (page_tables=)"
        pools, state = tuple(cache[:2]), tuple(cache[2:])
        if state_slot is None:
            assert input_ids.shape[1] == 1, \
                "a recurrent state advances one token a decode step"
            if state_advance is None:
                state_advance = jnp.ones((input_ids.shape[0],), bool)
            advanced = jnp.stack([state_advance.sum(dtype=jnp.int32),
                                  jnp.int32(1)])
    load = jnp.zeros((3, config.n_experts), jnp.int32)
    a = m = 0
    for i, lp in enumerate(params["layers"]):
        u = _rms_norm(x, lp["mixer_norm"], eps)
        if not config.is_mamba(i):
            with jax.named_scope("attn.full"):
                if cache is None:
                    mixed = _attention_dense(u, lp, config)
                else:
                    mixed, pools = _attention_paged(
                        u, lp, config, pools, a, positions, page_tables,
                        valid_lens, page_size)
            a += 1
        else:
            if cache is None:
                zeros = (jnp.zeros((config.d_conv - 1, config.conv_channels),
                                   x.dtype),
                         jnp.zeros((config.d_state, config.d_inner), _F32))
                mixed = jax.vmap(
                    lambda row: _mamba_sequence(
                        row, lp, config, *zeros, row.shape[0])[0])(u)
            elif state_slot is not None:
                mixed, state = _mamba_prefill(
                    u, lp, config, state, m, state_slot, positions[0],
                    valid_lens[0])
            else:
                mixed, state = _mamba_decode(u, lp, config, state, m,
                                             state_advance)
            m += 1
        x = _residual(x, mixed, config)
        out, layer_load = _experts(_rms_norm(x, lp["moe_norm"], eps), lp,
                                   config)
        x = _residual(x, out, config)
        load = load + layer_load
    x = _rms_norm(x, params["final_norm"], eps)
    out = (x,) if cache is None else (x, pools + state)
    if counters:
        out += ((load, advanced),)
    return out[0] if len(out) == 1 else out


def logits(params, hidden, logits_scaling=1.0):
    """The tied head over the rows held: ``hidden @ embed.T /
    logits_scaling``."""
    with jax.named_scope("head"):
        out = jnp.einsum("...d,vd->...v", hidden,
                         params["embed"].astype(hidden.dtype))
        return out if logits_scaling == 1.0 else out / logits_scaling


def lm_loss(params, input_ids, labels, config):
    hidden = forward_hidden(params, input_ids, config)
    lg = logits(params, hidden, config.logits_scaling).astype(
        _F32)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)
    return -ll.mean()


# -------------------------------------------------------------- serving
class GraniteMoeHybridDecoder:
    """What ``init_inference()`` asks of a model (inference/decoder.py)."""

    recurrent = True
    # what the serving programs return beside their tokens
    counters = ("moe.load", "ssd.advanced")

    def __init__(self, config):
        self.config = config

    def cache_spec(self):
        cfg = self.config
        n_mamba = len(cfg.mamba_layers)
        return CacheSpec(
            kv_layers=len(cfg.attention_layers), kv_heads=cfg.n_kv_heads,
            d_head=cfg.d_head,
            state=(StateSpec("conv", (n_mamba,),
                             ((cfg.d_conv - 1) * cfg.conv_channels,),
                             cfg.dtype),
                   StateSpec("ssd", (n_mamba,),
                             (cfg.d_state, cfg.d_inner), jnp.float32)))

    def serving_config(self, mesh):
        from ..parallel.topology import MODEL_AXIS
        if mesh is not None and int(mesh.shape.get(MODEL_AXIS, 1)) > 1:
            raise ValueError(
                "Granite-MoE-Hybrid has no tensor-parallel layout yet: a "
                "mesh with a 'model' axis cannot serve it")
        return dataclasses.replace(self.config,
                                   paged_attention_kernel="xla")

    def decode_config(self, config, paged_attention_kernel):
        return dataclasses.replace(
            config, paged_attention_kernel=paged_attention_kernel)

    # a chunk has a kernel of its own under the same key
    prefill_config = decode_config

    def serving_params(self, params, dtype):
        # the share, on the start-up record's ``setup.params`` row
        row = open_setup_span()
        if row is not None and row["name"] == "setup.params":
            first, past = self.config.held
            row["attrs"].update(experts_held=past - first,
                                experts=self.config.n_experts)

        def cast(path, x):
            x = jnp.asarray(x)
            keep = path[-1].key in _FLOAT32_LEAVES or \
                not jnp.issubdtype(x.dtype, jnp.floating)
            return x if keep else x.astype(dtype)
        return jax.tree_util.tree_map_with_path(cast, params)

    @staticmethod
    def counter_attrs(name, value):
        """``moe.load``: models/cohere2_moe.py's attributes (``rows``,
        ``experts_hit``, ``hottest_rows``, ``routed``, ``passes``).
        ``ssd.advanced``: ``slots`` whose state the launch advanced;
        ``steps``: 1 for a decode step (the state kernel ran over every
        slot), 0 for a chunk (one slot, the chunked form)."""
        value = np.asarray(value)
        if name == "ssd.advanced":
            return {"slots": int(value[0]), "steps": int(value[1])}
        return dict(moe.load_attrs(value[:2]), routed=int(value[2, 0]),
                    passes=int(value[2, 1]))

    forward_hidden = staticmethod(forward_hidden)

    def logits(self, params, hidden):
        return logits(params, hidden, self.config.logits_scaling)


def make_granite_moe_hybrid_model(config=None, seed=0, **overrides):
    """A :class:`deepspeed_tpu.runtime.model.Model` for
    ``init_inference()``; weights from ``seed`` (``init_layer``)."""
    from ..runtime.model import Model
    config = dataclasses.replace(config or GraniteMoeHybridConfig(),
                                 **overrides)
    params = init_params(config, seed=seed)

    def apply_fn(params, input_ids, labels, rng=None, train=True):
        return lm_loss(params, input_ids, labels, config)

    model = Model(apply_fn, params, name="granite_moe_hybrid")
    model.config = config
    model.decoder = GraniteMoeHybridDecoder(config)
    return model
