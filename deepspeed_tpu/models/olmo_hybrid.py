"""Olmo Hybrid (Ai2's ``model_type: olmo_hybrid``): gated-delta-rule
linear-attention layers beside a few full-attention layers, for serving
through ``init_inference()``.

Layer ``i`` is what ``layer_types[i]`` says (``linear_attention`` |
``full_attention``; published: three linear layers to every full one).
The block norms a sublayer's OUTPUT: ``h = x + RMSNorm(Mixer(x)); out =
h + RMSNorm(MLP(h))`` with a gated SiLU MLP, no biases, a final RMSNorm
and a head of its own (untied). A full layer: RMS norms over the WHOLE
query and key projections before the heads are split, one query head a
key-value head, no positional encoding. A linear layer
(ops/pallas/gated_delta.py has the recurrence): ``q``, ``k``, ``v``
each through a causal depthwise convolution of ``d_conv`` taps and
SiLU; ``q`` and ``k`` L2-normalised a head, ``q`` scaled by
``1/sqrt(d_k)``; ``beta = 2 sigmoid(x W_b)`` (``allow_neg_eigval``;
without it no 2); the decay ``a = exp(-exp(A_log) softplus(x W_a +
dt_bias))``; a per-head RMS norm on what the state gives, gated by
``silu(x W_g)``, then ``W_o``. The equations are written out in
``benchmark/models/olmo_hybrid_reference.py``, the float32 yardstick;
this module is the program.

Serving keeps TWO kinds of state (``OlmoHybridDecoder.cache_spec``):

* the full layers' keys (normed) and values in the engine's page pool,
  ``(pages + 1, full layers, page_size, n_heads * d_head)``, one group,
  written by ``kv_cache.write_tokens``; a decode step reads them in the
  page walk (ops/pallas/paged_attention.py), a prompt chunk in
  ``chunk_attention`` (ops/pallas/chunk_attention.py), both under
  ``paged_attention_kernel: pallas``; elsewhere both in XLA's loop
  (ops/chunk_attention.py), the oracle of the two kernels;
* per slot and linear layer a convolution tail, ``conv (linear layers,
  slots, (d_conv - 1) * conv channels)`` in the compute dtype (the
  three streams' channels side by side, a slot's last inputs in ONE
  row, as models/jamba.py holds its own), and the delta rule's state,
  ``gdn (linear layers, slots, d_k, linear heads * d_v)`` in float32:
  ``d_k`` second-minor and every head's ``d_v`` value lanes side by side
  in the minor dimension, 30 x 192 = 5,760 = 45 x 128 lanes at the
  published widths, so no lane is padding (a head's 192 alone would be
  padded to 256) and a pair of heads is the 384-lane block the step
  kernel takes. ``StatePool.nbytes`` then reads 12 x (96 x 5,760 x 4 +
  3 x 11,520 x 2) = 27.4 MB a slot at 12 linear layers (a padded head
  would read 36).

A recurrent state has no causal mask to hide what a slot held before:
the prefill program that runs a request's FIRST chunk (``positions ==
0``) starts from zeros whatever the slot holds, a later chunk starts
from the slot's state, a padded bucket leaves state and tails as they
were after the chunk's last real token (``valid_lens``), and the decode
program advances only the slots the scheduler says are decoding
(``state_advance``). The serving programs return, beside the hidden
states, the slots whose state the launch advanced (``counters``:
``gdn.advanced``; inference/decoder.py).

Serving only; a ``model`` mesh axis is refused. ``lm_loss``
differentiates the XLA path (whole sequences, every recurrence from
zero).
"""
import dataclasses
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..inference.decoder import CacheSpec, StateSpec
from ..inference.kv_cache import write_path, write_tokens
from ..ops.chunk_attention import (block_tokens, blocked_attention,
                                   paged_blocked_attention)
from ..ops.pallas import gated_delta
from .jamba import _rms_norm

INIT_STD = 0.02
DT_MIN, DT_MAX = 1e-3, 1e-1
A_MAX = 16.0
L2_EPS = 1e-6
LINEAR, FULL = "linear_attention", "full_attention"
_FLOAT32_LEAVES = ("A_log", "dt_bias")


@dataclass
class OlmoHybridConfig:
    vocab_size: int = 100352
    d_model: int = 3840
    layer_types: tuple = (LINEAR, LINEAR, LINEAR, FULL) * 4
    n_heads: int = 30
    n_kv_heads: int = 30
    d_ff: int = 11008
    linear_heads: int = 30            # key heads = value heads
    d_k: int = 96
    d_v: int = 192
    d_conv: int = 4
    allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    init_std: float = INIT_STD
    max_seq_len: int = 65536
    dtype: object = jnp.bfloat16      # matrices, embedding, activations
    # "pallas" (ops/pallas/gated_delta.py's step kernel) | "xla" (its
    # einsum oracle) | "auto": pallas on a TPU, xla elsewhere
    gdn_kernel: str = "auto"
    # the full layers' paged read: "pallas" (a step: the page walk; a
    # chunk: chunk_attention) | "xla" (the blocked loop); the engine
    # sets it on its program families, from
    # inference.paged_attention_kernel
    paged_attention_kernel: str = "xla"

    @property
    def n_layers(self):
        return len(self.layer_types)

    @property
    def d_head(self):
        return self.d_model // self.n_heads

    def is_linear(self, i):
        return self.layer_types[i] == LINEAR

    @property
    def full_layers(self):
        return [i for i in range(self.n_layers) if not self.is_linear(i)]

    @property
    def linear_layers(self):
        return [i for i in range(self.n_layers) if self.is_linear(i)]

    @property
    def conv_channels(self):
        """q, k and v side by side: what the convolution runs over."""
        return self.linear_heads * (2 * self.d_k + self.d_v)


def config_from_hf(model, **overrides):
    """An :class:`OlmoHybridConfig` from the keys of a published
    ``config.json`` (``model_type: olmo_hybrid``)."""
    types = tuple(model["layer_types"])
    assert len(types) == model["num_hidden_layers"] and \
        set(types) <= {LINEAR, FULL}, "layer_types {}".format(types)
    assert model["linear_num_key_heads"] == model["linear_num_value_heads"], \
        "models/olmo_hybrid.py has one key head a value head"
    assert model["num_key_value_heads"] == model["num_attention_heads"], \
        "models/olmo_hybrid.py has one query head a key-value head"
    assert (model.get("rope_parameters") or {}).get("rope_theta") is None, \
        "models/olmo_hybrid.py's full layers have no rotation"
    assert not model.get("attention_bias") and \
        not model.get("tie_word_embeddings")
    return OlmoHybridConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        layer_types=types, n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"],
        linear_heads=model["linear_num_value_heads"],
        d_k=model["linear_key_head_dim"], d_v=model["linear_value_head_dim"],
        d_conv=model["linear_conv_kernel_dim"],
        allow_neg_eigval=model["linear_allow_neg_eigval"],
        rms_norm_eps=model["rms_norm_eps"],
        init_std=model.get("initializer_range", INIT_STD),
        max_seq_len=model["max_position_embeddings"], **overrides)


# ------------------------------------------------------------------ init
def _key(seed, i):
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), i)


def init_layer(config, seed, i):
    """Layer ``i``'s weights from the seed's stream ``i``, drawn in the
    order ``olmo_hybrid_reference.draw_layer`` draws them: matrices
    normal(0, 0.02) as (in, out) in ``config.dtype``; norms 1; the gated
    delta rule's published initialisation for ``A_log`` (log of A
    uniform in (0, 16)) and the dt bias (inverse softplus of a
    log-uniform dt in [1e-3, 1e-1]), which stay float32. A linear
    layer's q, k and v matrices are held as ONE (``qkv``), as are those
    of beta and the decay (``ba``); ``conv_w`` is held transposed,
    channels minor."""
    d, ff, dtype = config.d_model, config.d_ff, config.dtype
    keys = iter(jax.random.split(_key(seed, i), 16))

    def normal(*shape):
        return (config.init_std * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    ones = lambda n: jnp.ones((n,), dtype)
    lp = {"mixer_norm": ones(d), "mlp_norm": ones(d),
          "gate": normal(d, ff), "up": normal(d, ff), "down": normal(ff, d)}
    if not config.is_linear(i):
        lp.update(q=normal(d, d), k=normal(d, d), v=normal(d, d),
                  o=normal(d, d), q_norm=ones(d), k_norm=ones(d))
        return lp
    H, dk, dv = config.linear_heads, config.d_k, config.d_v
    q, k, v = normal(d, H * dk), normal(d, H * dk), normal(d, H * dv)
    g, o = normal(d, H * dv), normal(H * dv, d)
    b, a = normal(d, H), normal(d, H)
    conv_w = normal(config.conv_channels, config.d_conv)
    A = jax.random.uniform(next(keys), (H,), jnp.float32, 1e-4, A_MAX)
    dt = jnp.exp(jax.random.uniform(next(keys), (H,), jnp.float32) *
                 (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    lp.update(
        qkv=jnp.concatenate([q, k, v], axis=1), g=g, o=o,
        ba=jnp.concatenate([b, a], axis=1), conv_w=conv_w.T,
        A_log=jnp.log(A), dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        o_norm=ones(dv))
    return lp


def init_params(config, seed=0):
    def table(stream, *shape):
        return (config.init_std * jax.random.normal(
            _key(seed, stream), shape, jnp.float32)).astype(config.dtype)

    n = config.n_layers
    return {
        "layers": [init_layer(config, seed, i) for i in range(n)],
        "embed": table(n, config.vocab_size, config.d_model),
        "head": table(n + 1, config.d_model, config.vocab_size),
        "final_norm": jnp.ones((config.d_model,), config.dtype),
    }


def num_params(config):
    d, ff, H = config.d_model, config.d_ff, config.linear_heads
    mlp = 3 * d * ff + 2 * d
    linear = (d * config.conv_channels + 2 * d * H * config.d_v +
              2 * d * H + config.conv_channels * config.d_conv + 2 * H +
              config.d_v)
    full = 4 * d * d + 2 * d
    n_full = len(config.full_layers)
    return (2 * config.vocab_size * d + d + n_full * (full + mlp) +
            (config.n_layers - n_full) * (linear + mlp))


# --------------------------------------------------------------- layers
def _mlp(x, lp):
    with jax.named_scope("mlp"):
        return (jax.nn.silu(x @ lp["gate"]) * (x @ lp["up"])) @ lp["down"]


def _use_pallas(config):
    if config.gdn_kernel == "auto":
        from ..ops.pallas.common import default_interpret
        return not default_interpret()
    return config.gdn_kernel == "pallas"


def _l2_norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def _project(u, lp):
    """-> the three streams before their convolution (.., channels),
    the output gate (.., H * dv), beta's and the decay's (.., 2 H)
    pre-activations in float32."""
    with jax.named_scope("gdn.proj"):
        return (u @ lp["qkv"], u @ lp["g"],
                (u @ lp["ba"]).astype(jnp.float32))


def _heads(xc, ba, lp, config):
    """The convolved streams (.., channels) and beta's and the decay's
    pre-activations (.., 2 H) -> q, k (.., H, dk) normalised, q scaled;
    v (.., H, dv); the LOG of the decay and beta (.., H); all
    float32."""
    H, dk, dv = config.linear_heads, config.d_k, config.d_v
    lead = xc.shape[:-1]
    xc = xc.astype(jnp.float32)
    q = _l2_norm(xc[..., :H * dk].reshape(lead + (H, dk))) / math.sqrt(dk)
    k = _l2_norm(xc[..., H * dk:2 * H * dk].reshape(lead + (H, dk)))
    v = xc[..., 2 * H * dk:].reshape(lead + (H, dv))
    beta = jax.nn.sigmoid(ba[..., :H])
    if config.allow_neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., H:] + lp["dt_bias"].astype(jnp.float32))
    return q, k, v, g, beta


def _norm_gate_project(o, gate, lp, config):
    """``W_o(RMSNorm_dv(o) * silu(gate))``: o (.., H * dv) float32 as
    the state gave it, gate (.., H * dv) in the compute dtype."""
    with jax.named_scope("gdn.norm"):
        H, dv = config.linear_heads, config.d_v
        o = o.reshape(o.shape[:-1] + (H, dv))
        o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) +
                              config.rms_norm_eps)
        o = (o * lp["o_norm"].astype(jnp.float32)).reshape(gate.shape)
        o = (o * jax.nn.silu(gate.astype(jnp.float32))).astype(gate.dtype)
        return o @ lp["o"]


def _linear_sequence(u, lp, config, tail0, s0, valid_len):
    """The linear mixer over ONE sequence chunk ``u`` (s, d) from the
    convolution tail ``tail0`` (d_conv - 1, channels) and the state
    ``s0`` (dk, H * dv). -> (mixer output (s, d), the tail and the
    state as they are after ``valid_len`` tokens)."""
    s, kc = u.shape[0], config.d_conv
    x, gate, ba = _project(u, lp)
    with jax.named_scope("gdn.conv"):
        padded = jnp.concatenate([tail0.astype(x.dtype), x], axis=0)
        conv = sum(padded[j:j + s].astype(jnp.float32) *
                   lp["conv_w"][j].astype(jnp.float32) for j in range(kc))
        xc = jax.nn.silu(conv).astype(x.dtype)
        # the last d_conv - 1 real inputs (the old tail's, where the
        # chunk is shorter than that)
        tail = jax.lax.dynamic_slice_in_dim(padded, valid_len, kc - 1,
                                            axis=0)
    q, k, v, g, beta = _heads(xc, ba, lp, config)
    o, sT = gated_delta.gated_delta_chunk(q, k, v, g, beta, s0, valid_len)
    return _norm_gate_project(o, gate, lp, config), tail, sT


def _linear_prefill(u, lp, config, state, m, slot, start, valid_len):
    """One slot's chunk against the state pools (``m``: the layer's
    index among the linear layers). The first chunk (``start == 0``)
    starts from zeros whatever the slot holds."""
    conv, gdn = state
    first = start == 0
    tail0 = jnp.where(first, 0, conv[m, slot].reshape(
        config.d_conv - 1, config.conv_channels))
    s0 = jnp.where(first, 0, gdn[m, slot].astype(jnp.float32))
    out, tail, sT = _linear_sequence(u[0], lp, config, tail0, s0, valid_len)
    conv = conv.at[m, slot].set(tail.astype(conv.dtype).reshape(-1))
    gdn = gdn.at[m, slot].set(sT.astype(gdn.dtype))
    return out[None], (conv, gdn)


def _linear_decode(u, lp, config, state, m, advance):
    """One token for every slot (u (slots, 1, d)); a slot outside
    ``advance`` keeps its tail and its state."""
    conv, gdn = state
    ch = config.conv_channels
    x, gate, ba = _project(u[:, 0], lp)
    with jax.named_scope("gdn.conv"):
        # a slot's row: its d_conv - 1 last inputs, then the new one
        window = jnp.concatenate([conv[m], x.astype(conv.dtype)], axis=1)
        acc = sum(window[:, j * ch:(j + 1) * ch].astype(jnp.float32) *
                  lp["conv_w"][j].astype(jnp.float32)
                  for j in range(config.d_conv))
        xc = jax.nn.silu(acc).astype(x.dtype)
        conv = conv.at[m].set(jnp.where(advance[:, None], window[:, ch:],
                                        conv[m]))
    q, k, v, g, beta = _heads(xc, ba, lp, config)
    # a slot held back keeps its state: no decay and nothing written
    # (selected, not multiplied: its row may hold anything)
    hold = ~advance[:, None]
    a, beta = jnp.where(hold, 1.0, jnp.exp(g)), jnp.where(hold, 0.0, beta)
    q, k, v = (jnp.where(hold[:, :, None], 0.0, x) for x in (q, k, v))
    step = gated_delta.gated_delta_step if _use_pallas(config) \
        else gated_delta.gated_delta_step_xla
    with jax.named_scope("gdn.step"):
        o, gdn = step(gdn, q, k, v, a, beta, m)
    return _norm_gate_project(o, gate, lp, config)[:, None], (conv, gdn)


def _qkv(u, lp, config):
    """-> q, k (b, s, h, dh), each normed over the whole projection
    before the heads are split, and v. No rotation."""
    b, s, _ = u.shape
    h, dh, eps = config.n_heads, config.d_head, config.rms_norm_eps
    q = _rms_norm(u @ lp["q"], lp["q_norm"], eps).reshape(b, s, h, dh)
    k = _rms_norm(u @ lp["k"], lp["k_norm"], eps).reshape(b, s, h, dh)
    return q, k, (u @ lp["v"]).reshape(b, s, h, dh)


def _attention_paged(u, lp, config, pools, a, positions, page_tables,
                     valid_lens, page_size):
    """A full layer against the page pool (``a``: its index among the
    full layers): ``kv_cache.write_tokens``, then the read: under
    ``paged_attention_kernel: pallas`` the page walk for a launch that
    wrote rows (a decode step) and ``chunk_attention`` for one that
    wrote pages (a chunk), else the blocked loop."""
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
    every recurrence from zero. With ``cache`` = ``(k, v, conv, gdn)``
    (the page pool and the state pools of the module docstring) returns
    ``(hidden, cache)``: ``state_slot`` (int32 scalar) selects prefill
    of one slot's chunk (b = 1; ``positions[0]`` the chunk's start, 0
    meaning a request's first chunk; ``valid_lens[0]`` its real
    tokens); otherwise decode, one token for every slot,
    ``state_advance`` (slots,) bool marking the slots whose recurrent
    state this step advances. With ``counters`` the last of what is
    returned is ``(advanced,)``: int32 (2,), the slots whose state the
    launch advanced and whether it was a decode step (1) or a chunk
    (0), under ``OlmoHybridDecoder.counters``' names."""
    x = jnp.take(params["embed"], input_ids, axis=0)
    eps = config.rms_norm_eps
    # (slots advanced, 1 for a decode step): a chunk advances its slot
    advanced = jnp.array([input_ids.shape[0], 0], jnp.int32)
    if cache is not None:
        assert page_tables is not None, \
            "Olmo Hybrid serves from pages only (page_tables=)"
        pools, state = tuple(cache[:2]), tuple(cache[2:])
        if state_slot is None:
            assert input_ids.shape[1] == 1, \
                "a recurrent state advances one token a decode step"
            if state_advance is None:
                state_advance = jnp.ones((input_ids.shape[0],), bool)
            advanced = jnp.stack([state_advance.sum(dtype=jnp.int32),
                                  jnp.int32(1)])
    a = m = 0
    for i, lp in enumerate(params["layers"]):
        if not config.is_linear(i):
            with jax.named_scope("attn.full"):
                if cache is None:
                    mixed = _attention_dense(x, lp, config)
                else:
                    mixed, pools = _attention_paged(
                        x, lp, config, pools, a, positions, page_tables,
                        valid_lens, page_size)
            a += 1
        else:
            if cache is None:
                zeros = (jnp.zeros((config.d_conv - 1, config.conv_channels),
                                   x.dtype),
                         jnp.zeros((config.d_k,
                                    config.linear_heads * config.d_v),
                                   jnp.float32))
                mixed = jax.vmap(
                    lambda row: _linear_sequence(
                        row, lp, config, *zeros, row.shape[0])[0])(x)
            elif state_slot is not None:
                mixed, state = _linear_prefill(
                    x, lp, config, state, m, state_slot, positions[0],
                    valid_lens[0])
            else:
                mixed, state = _linear_decode(x, lp, config, state, m,
                                              state_advance)
            m += 1
        x = x + _rms_norm(mixed, lp["mixer_norm"], eps)
        x = x + _rms_norm(_mlp(x, lp), lp["mlp_norm"], eps)
    x = _rms_norm(x, params["final_norm"], eps)
    out = (x,) if cache is None else (x, pools + state)
    if counters:
        out += ((advanced,),)
    return out[0] if len(out) == 1 else out


def logits(params, hidden):
    """The head, a matrix of its own."""
    with jax.named_scope("head"):
        return hidden @ params["head"].astype(hidden.dtype)


def lm_loss(params, input_ids, labels, config):
    hidden = forward_hidden(params, input_ids, config)
    lg = logits(params, hidden).astype(jnp.float32)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)
    return -ll.mean()


# -------------------------------------------------------------- serving
class OlmoHybridDecoder:
    """What ``init_inference()`` asks of a model (inference/decoder.py)."""

    recurrent = True
    # what the serving programs return beside their tokens
    counters = ("gdn.advanced",)

    def __init__(self, config):
        self.config = config

    def cache_spec(self):
        cfg = self.config
        n_linear = len(cfg.linear_layers)
        return CacheSpec(
            kv_layers=len(cfg.full_layers), kv_heads=cfg.n_kv_heads,
            d_head=cfg.d_head,
            state=(StateSpec("conv", (n_linear,),
                             ((cfg.d_conv - 1) * cfg.conv_channels,),
                             cfg.dtype),
                   StateSpec("gdn", (n_linear,),
                             (cfg.d_k, cfg.linear_heads * cfg.d_v),
                             jnp.float32)))

    def serving_config(self, mesh):
        from ..parallel.topology import MODEL_AXIS
        if mesh is not None and int(mesh.shape.get(MODEL_AXIS, 1)) > 1:
            raise ValueError(
                "Olmo Hybrid has no tensor-parallel layout yet: a mesh "
                "with a 'model' axis cannot serve it")
        return dataclasses.replace(self.config,
                                   paged_attention_kernel="xla")

    def decode_config(self, config, paged_attention_kernel):
        return dataclasses.replace(
            config, paged_attention_kernel=paged_attention_kernel)

    # a chunk has a kernel of its own under the same key
    prefill_config = decode_config

    def serving_params(self, params, dtype):
        def cast(path, x):
            x = jnp.asarray(x)
            keep = path[-1].key in _FLOAT32_LEAVES or \
                not jnp.issubdtype(x.dtype, jnp.floating)
            return x if keep else x.astype(dtype)
        return jax.tree_util.tree_map_with_path(cast, params)

    @staticmethod
    def counter_attrs(name, value):
        """``slots`` whose state the launch advanced; ``steps``: 1 for
        a decode step (the state kernel ran over every slot), 0 for a
        chunk (one slot, the chunked form)."""
        return {"slots": int(value[0]), "steps": int(value[1])}

    forward_hidden = staticmethod(forward_hidden)
    logits = staticmethod(logits)


def make_olmo_hybrid_model(config=None, seed=0, **overrides):
    """A :class:`deepspeed_tpu.runtime.model.Model` for
    ``init_inference()``; weights from ``seed`` (``init_layer``)."""
    from ..runtime.model import Model
    config = dataclasses.replace(config or OlmoHybridConfig(), **overrides)
    params = init_params(config, seed=seed)

    def apply_fn(params, input_ids, labels, rng=None, train=True):
        return lm_loss(params, input_ids, labels, config)

    model = Model(apply_fn, params, name="olmo_hybrid")
    model.config = config
    model.decoder = OlmoHybridDecoder(config)
    return model
