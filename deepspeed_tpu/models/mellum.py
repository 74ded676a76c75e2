"""Mellum (JetBrains' ``model_type: mellum``): rotary grouped-query
attention in every layer, SLIDING-WINDOW layers beside full ones, and a
sparse expert layer behind a softmax router in every layer, for serving
through ``init_inference()``.

Layer ``i`` is what ``layer_types[i]`` says (``sliding_attention`` |
``full_attention``): ``h = x + Attn(RMSNorm(x)); out = h +
Experts(RMSNorm(h))``, no biases, a final RMSNorm and a head of its own
(untied). Attention has per-head RMS norms on queries and keys and
rotary positions on the whole head (rotate-half pairing), by a table a
layer type (``rope``): plain rotary in the sliding layers, YaRN in the
full ones (``yarn_inv_freq``: the slow lanes' frequencies divided by
``factor``, a ramp between, and cos and sin times ``attention_factor``).
A sliding layer's query at ``t`` sees key ``j`` iff ``0 <= t - j <
window``. The expert layer (ops/moe.py): ``top_k`` of ``n_experts`` a
token by softmax probability over all experts, weighted by their
probabilities renormalised over the chosen, no selection bias, no shared
expert, no token dropped. The equations are written out in
``benchmark/models/mellum2_reference.py``, the float32 yardstick; this
module is the program.

Serving keeps the keys (normed and rotated) and values in TWO groups of
pages (``MellumDecoder.cache_spec``; inference/decoder.py ``PageGroup``):
the full layers' first, every page of a request kept until it retires,
and the sliding layers', whose table slides and whose pages go back to
their pool as they leave the window (inference/paging.py
``GroupPages``). ``forward_hidden`` is handed a table a group and each
table's base: a sliding layer reads and writes at ``position - base``,
and rotates by the absolute position. Both read in blocks of keys with a
running softmax, as long a walk as the blocks that hold a visible key:
on the chip (``paged_attention_kernel: pallas``) a decode step in the
grouped page walk (ops/pallas/paged_attention.py, ``window`` in the
sliding layers) and a prompt chunk in ``chunk_attention``
(ops/pallas/chunk_attention.py); elsewhere both in XLA's loop
(ops/chunk_attention.py), the oracle of the two kernels.

The serving programs return, beside the hidden states, the expert
layers' summed load (``counters``: ``moe.load``; inference/decoder.py).

Serving only; a ``model`` mesh axis is refused.
"""
import dataclasses
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..inference.decoder import CacheSpec, PageGroup
from ..inference.kv_cache import write_path, write_tokens
from ..ops import moe
from ..ops.chunk_attention import (block_tokens, blocked_attention,
                                   paged_blocked_attention)
from .jamba import _rms_norm

INIT_STD = 0.02
SLIDING, FULL = "sliding_attention", "full_attention"
_FLOAT32_LEAVES = ("router",)


@dataclass(frozen=True)
class Rope:
    """One layer type's rotary table: ``rope_type`` ``default`` or
    ``yarn`` with YaRN's own parameters."""
    theta: float = 500000.0
    rope_type: str = "default"
    factor: float = 1.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclass
class MellumConfig:
    vocab_size: int = 98304
    d_model: int = 2304
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, FULL) * 2
    n_heads: int = 32
    n_kv_heads: int = 4
    d_head: int = 128
    d_expert: int = 896
    n_experts: int = 64
    top_k: int = 8
    norm_topk_prob: bool = True
    window: int = 1024
    norm_eps: float = 1e-6
    # {layer type: Rope}
    rope: tuple = ((FULL, Rope(rope_type="yarn", factor=16.0,
                               original_max=8192,
                               attention_factor=0.1 * math.log(16.0) + 1)),
                   (SLIDING, Rope()))
    init_std: float = INIT_STD
    # the weight the per-head norms of queries and keys are drawn at
    # (benchmark/models/mellum2_reference.py says why it is not 1)
    qk_norm_gain: float = 1.0
    max_seq_len: int = 131072
    dtype: object = jnp.bfloat16      # matrices, embedding, activations
    # "pallas" (ops/pallas/moe.py) | "xla" (lax.ragged_dot) | "auto"
    moe_kernel: str = "auto"
    # the paged read: "pallas" (a decode or verify step: the grouped
    # page walk; a chunk: chunk_attention) | "xla" (the blocked loop)
    paged_attention_kernel: str = "xla"

    @property
    def n_layers(self):
        return len(self.layer_types)

    def is_sliding(self, i):
        return self.layer_types[i] == SLIDING

    @property
    def full_layers(self):
        return [i for i in range(self.n_layers) if not self.is_sliding(i)]

    @property
    def sliding_layers(self):
        return [i for i in range(self.n_layers) if self.is_sliding(i)]

    @property
    def expert_layers(self):
        return list(range(self.n_layers))

    def rope_of(self, i):
        return dict(self.rope)[self.layer_types[i]]


def config_from_hf(model, **overrides):
    """A :class:`MellumConfig` from the keys of a published
    ``config.json`` (``model_type: mellum``)."""
    assert not model["attention_bias"], "an attention bias is not supported"
    assert len(model["layer_types"]) == model["num_hidden_layers"]
    assert set(model["layer_types"]) <= {SLIDING, FULL}
    assert set(model["mlp_layer_types"]) == {"sparse"}, \
        "every layer's feed-forward part is the expert layer"
    assert model["use_sliding_window"] and not model["tie_word_embeddings"]

    def rope(p):
        if p["rope_type"] == "default":
            return Rope(theta=float(p["rope_theta"]))
        assert p["rope_type"] == "yarn", p["rope_type"]
        return Rope(theta=float(p["rope_theta"]), rope_type="yarn",
                    factor=float(p["factor"]),
                    original_max=p["original_max_position_embeddings"],
                    beta_fast=float(p["beta_fast"]),
                    beta_slow=float(p["beta_slow"]),
                    attention_factor=float(p["attention_factor"]))

    extra = {k: model[k] for k in ("qk_norm_gain",) if k in model}
    extra.update(overrides)
    return MellumConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        layer_types=tuple(model["layer_types"]),
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], d_head=model["head_dim"],
        d_expert=model["moe_intermediate_size"],
        n_experts=model["num_experts"], top_k=model["num_experts_per_tok"],
        norm_topk_prob=model["norm_topk_prob"],
        window=model["sliding_window"], norm_eps=model["rms_norm_eps"],
        rope=tuple(sorted((kind, rope(p)) for kind, p in
                          model["rope_parameters"].items())),
        init_std=model.get("initializer_range", INIT_STD),
        max_seq_len=model["max_position_embeddings"], **extra)


# ---------------------------------------------------------------- rotary
def yarn_correction_range(rope, d_head):
    """YaRN's ``(low, high)``: the lane pairs between which the ramp
    runs, floor and ceil of the correction dimensions of ``beta_fast``
    and ``beta_slow`` rotations over the original context (18 and 35 at
    the published numbers)."""
    def corr(rotations):
        return d_head * math.log(rope.original_max / (
            rotations * 2 * math.pi)) / (2 * math.log(rope.theta))
    return (max(math.floor(corr(rope.beta_fast)), 0),
            min(math.ceil(corr(rope.beta_slow)), d_head - 1))


def inv_freq(rope, d_head):
    """The ``d_head / 2`` frequencies of a layer type, float64 numpy:
    ``theta ** (-2j / d_head)``; under YaRN lane pair ``j`` moves from
    that (``j <= low``) to that over ``factor`` (``j >= high``) along a
    linear ramp."""
    half = d_head // 2
    base = rope.theta ** (-np.arange(half, dtype=np.float64) / half)
    if rope.rope_type == "default":
        return base
    low, high = yarn_correction_range(rope, d_head)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (1 - ramp) * base + ramp * base / rope.factor


def _rotary(x, positions, rope):
    """Rotary embedding of the whole head, rotate-half pairing ``(i, i
    + dh / 2)``, cos and sin times the table's ``attention_factor``. x
    (b, s, heads, dh); positions (b, s) absolute."""
    half = x.shape[-1] // 2
    freq = jnp.asarray(inv_freq(rope, x.shape[-1]), jnp.float32)
    angle = positions.astype(jnp.float32)[..., None] * freq
    cos = (jnp.cos(angle) * rope.attention_factor)[:, :, None, :]
    sin = (jnp.sin(angle) * rope.attention_factor)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


# ------------------------------------------------------------------ init
def _key(seed, i):
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), i)


def init_layer(config, seed, i):
    """Layer ``i``'s weights from the seed's stream ``i``, split in the
    order written here (``mellum2_reference.draw_layer``'s): matrices
    normal(0, ``init_std``) as (in, out) in ``config.dtype``, the
    norms 1, the per-head norms of queries and keys ``qk_norm_gain``;
    the router (d, E) float32; an expert's gate and up matrices side by side."""
    d, dtype = config.d_model, config.dtype
    h, kvh, dh = config.n_heads, config.n_kv_heads, config.d_head
    keys = iter(jax.random.split(_key(seed, i), 16))

    def normal(*shape, dtype=dtype):
        return (config.init_std * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    ones = lambda n, gain=1.0: jnp.full((n,), gain, dtype)
    lp = {"attn_norm": ones(d), "ffn_norm": ones(d),
          "q": normal(d, h * dh), "k": normal(d, kvh * dh),
          "v": normal(d, kvh * dh), "o": normal(h * dh, d),
          "q_norm": ones(dh, config.qk_norm_gain),
          "k_norm": ones(dh, config.qk_norm_gain)}
    E, ff = config.n_experts, config.d_expert
    lp["router"] = normal(d, E, dtype=jnp.float32)
    w1, w3 = normal(E, d, ff), normal(E, d, ff)
    lp["w13"] = jnp.concatenate([w1, w3], axis=-1)
    del w1, w3
    lp["w2"] = normal(E, ff, d)
    return lp


def init_params(config, seed=0):
    def table(stream, *shape):
        return (config.init_std * jax.random.normal(
            _key(seed, stream), shape, jnp.float32)).astype(config.dtype)
    return {
        "layers": [init_layer(config, seed, i)
                   for i in range(config.n_layers)],
        "embed": table(config.n_layers, config.vocab_size, config.d_model),
        "head": table(config.n_layers + 1, config.d_model,
                      config.vocab_size),
        "final_norm": jnp.ones((config.d_model,), config.dtype),
    }


def num_params(config):
    d, dh = config.d_model, config.d_head
    attn = 2 * d * config.n_heads * dh + 2 * d * config.n_kv_heads * dh + \
        2 * dh
    experts = config.n_experts * 3 * d * config.d_expert + \
        d * config.n_experts
    return 2 * config.vocab_size * d + d + \
        config.n_layers * (2 * d + attn + experts)


# --------------------------------------------------------------- layers
def _experts(u, lp, config):
    """-> (the expert layer of ``u`` (.., d), its load (2, E))."""
    flat = u.reshape(-1, u.shape[-1])
    chosen, weights = moe.route(
        flat, lp["router"], None, config.top_k, config.norm_topk_prob,
        norm_eps=0.0, scoring="softmax")
    out, load = moe.expert_ffn(flat, chosen, weights, lp["w13"], lp["w2"],
                               (0, config.n_experts), config.n_experts,
                               kernel=config.moe_kernel)
    return out.reshape(u.shape), load


def _qkv(u, lp, config, tok_pos, rope):
    """-> q (b, s, h, dh), k (b, s, kvh, dh), both normed per head and
    rotated to ``tok_pos`` (b, s) by the layer type's table, and v."""
    b, s, _ = u.shape
    h, kvh, dh = config.n_heads, config.n_kv_heads, config.d_head
    eps = config.norm_eps
    q = _rms_norm((u @ lp["q"]).reshape(b, s, h, dh), lp["q_norm"], eps)
    k = _rms_norm((u @ lp["k"]).reshape(b, s, kvh, dh), lp["k_norm"], eps)
    v = (u @ lp["v"]).reshape(b, s, kvh, dh)
    return _rotary(q, tok_pos, rope), _rotary(k, tok_pos, rope), v


def _attention_paged(u, lp, config, i, pools, a, positions, page_tables,
                     base, valid_lens, page_size):
    """Layer ``i`` against its group's pages (``a``: its index among the
    group's layers; ``base`` (b,): the absolute position of the table's
    first token): ``kv_cache.write_tokens``, then the read at
    ``positions - base``: under ``paged_attention_kernel: pallas`` the
    page walk for a launch that wrote rows (a decode or verify step) and
    ``chunk_attention`` for one that wrote pages (a chunk), else the
    blocked loop."""
    b, s, _ = u.shape
    window = config.window if config.is_sliding(i) else None
    tok_pos = positions[:, None] + jnp.arange(s)[None, :]
    q, k, v = _qkv(u, lp, config, tok_pos, config.rope_of(i))
    at = positions - base
    k_pool, v_pool = write_tokens(
        pools, (k.reshape(b, s, -1), v.reshape(b, s, -1)), a, page_tables,
        at, valid_lens, page_size)
    if config.paged_attention_kernel != "pallas":
        ctx = paged_blocked_attention(q, k_pool, v_pool, a, page_tables, at,
                                      valid_lens, page_size, window)
    elif write_path(s, page_size) == "pages":
        from ..ops.pallas.chunk_attention import chunk_attention
        ctx = chunk_attention(q, k_pool, v_pool, a, page_tables, at,
                              valid_lens, page_size, window)
    else:
        from ..ops.pallas.paged_attention import paged_attention
        ctx = paged_attention(q, k_pool, v_pool, page_tables, at,
                              valid_lens, layer_idx=a, page_size=page_size,
                              window=window)
    return ctx.astype(u.dtype).reshape(b, s, -1) @ lp["o"], (k_pool, v_pool)


def _attention_dense(u, lp, config, i):
    """Whole sequences from position 0, no cache: the blocked attention
    over the sequence's own keys."""
    b, s, _ = u.shape
    tok_pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q, k, v = _qkv(u, lp, config, tok_pos, config.rope_of(i))
    block = block_tokens(s)
    n_blocks = -(-s // block)
    pad = ((0, 0), (0, n_blocks * block - s), (0, 0), (0, 0))
    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    ctx = blocked_attention(
        q, lambda c: tuple(jax.lax.dynamic_slice_in_dim(
            x, c * block, block, 1) for x in (k, v)),
        n_blocks, block, tok_pos, jnp.full((b,), s - 1, jnp.int32),
        config.n_kv_heads, config.window if config.is_sliding(i) else None)
    return ctx.astype(u.dtype).reshape(b, s, -1) @ lp["o"]


def forward_hidden(params, input_ids, config, cache=None, positions=None,
                   page_tables=None, page_bases=None, valid_lens=None,
                   page_size=None, counters=False):
    """Embedding + the layer stack + the final norm -> hidden states.

    Without ``cache``: the plain forward over whole sequences (b, s).
    With ``cache`` = ``(k, v)`` of the full layers' group then ``(k,
    v)`` of the sliding layers', ``page_tables`` and ``page_bases`` a
    pair each, in that order: returns ``(hidden, cache)``. With
    ``counters`` the last of what is returned is ``(load,)``: the
    expert layers' summed load (ops/moe.py), under
    ``MellumDecoder.counters``' names."""
    x = jnp.take(params["embed"], input_ids, axis=0)
    eps = config.norm_eps
    if cache is not None:
        assert page_tables is not None and page_bases is not None, \
            "Mellum serves from the paged layout, a table a page group"
        groups = [tuple(cache[:2]), tuple(cache[2:4])]
    load = jnp.zeros((2, config.n_experts), jnp.int32)
    at = [0, 0]                  # the next layer's index in its group
    for i, lp in enumerate(params["layers"]):
        u = _rms_norm(x, lp["attn_norm"], eps)
        g = int(config.is_sliding(i))
        with jax.named_scope("attn.window" if g else "attn.full"):
            if cache is None:
                mixed = _attention_dense(u, lp, config, i)
            else:
                mixed, groups[g] = _attention_paged(
                    u, lp, config, i, groups[g], at[g], positions,
                    page_tables[g], page_bases[g], valid_lens, page_size)
        at[g] += 1
        x = x + mixed
        out, layer_load = _experts(_rms_norm(x, lp["ffn_norm"], eps), lp,
                                   config)
        x = x + out
        load = load + layer_load
    x = _rms_norm(x, params["final_norm"], eps)
    out = (x,) if cache is None else (x, groups[0] + groups[1])
    if counters:
        out += ((load,),)
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
class MellumDecoder:
    """What ``init_inference()`` asks of a model (inference/decoder.py)."""

    # what the serving programs return beside their tokens
    counters = ("moe.load",)

    def __init__(self, config):
        self.config = config

    def cache_spec(self):
        cfg = self.config
        full, sliding = len(cfg.full_layers), len(cfg.sliding_layers)
        return CacheSpec(
            kv_layers=full + sliding, kv_heads=cfg.n_kv_heads,
            d_head=cfg.d_head,
            groups=(PageGroup(full), PageGroup(sliding, window=cfg.window)))

    def serving_config(self, mesh):
        from ..parallel.topology import MODEL_AXIS
        if mesh is not None and int(mesh.shape.get(MODEL_AXIS, 1)) > 1:
            raise ValueError(
                "Mellum has no tensor-parallel layout yet: a mesh with a "
                "'model' axis cannot serve it")
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
        return moe.load_attrs(value)

    forward_hidden = staticmethod(forward_hidden)
    logits = staticmethod(logits)


def make_mellum_model(config=None, seed=0, **overrides):
    """A :class:`deepspeed_tpu.runtime.model.Model` for
    ``init_inference()``; weights from ``seed`` (``init_layer``)."""
    from ..runtime.model import Model
    config = dataclasses.replace(config or MellumConfig(), **overrides)
    params = init_params(config, seed=seed)

    def apply_fn(params, input_ids, labels, rng=None, train=True):
        return lm_loss(params, input_ids, labels, config)

    model = Model(apply_fn, params, name="mellum")
    model.config = config
    model.decoder = MellumDecoder(config)
    return model
