"""Cohere2-MoE (Cohere's ``model_type: cohere2_moe``, Command A+): a
PARALLEL block, grouped-query attention whose SLIDING-WINDOW layers are
rotated and whose full layers carry no position at all, and a sparse
expert layer behind a sigmoid router beside several SHARED experts whose
results are averaged, for serving through ``init_inference()``.

Layer ``i`` is what ``layer_types[i]`` says (``sliding_attention`` |
``full_attention``) and has ONE norm: ``h = LayerNorm(x); out = x +
Attn(h) + Experts(h)``, no biases; LayerNorm is mean-centred
(``(x - mean) / sqrt(var + eps) * g``, no bias). A final LayerNorm, then
the TIED head: ``logit_scale * hidden @ embed.T``. Attention has no
norms on queries and keys. A sliding layer rotates them over the whole
head, lanes paired ``(2j, 2j + 1)`` (``rope_gptj``, the interleaved
pairing), and its query at ``t`` sees key ``j`` iff ``0 <= t - j <
window``; a full layer rotates nothing and sees every ``j <= t``. The
expert layer (ops/moe.py): ``top_k`` of ``n_experts`` a token by sigmoid
score, weighted by their scores renormalised over the chosen, no
selection bias, no scaling factor, no token dropped; beside it
``n_shared`` gated MLPs on the same ``h``, their MEAN added. The shared
experts are held as ONE gated MLP of width ``n_shared * d_shared`` whose
result is divided by ``n_shared``: the same sum, one pair of matmuls.
The equations are written out in
``benchmark/models/command_a_plus_reference.py``, the float32 yardstick;
this module is the program.

A chip may hold a SHARE of each layer (``experts_held``: a range of the
``n_experts`` the router scores; ``vocab_size``: the rows of the tied
embedding held, which are then the whole vocabulary here): the router
keeps its width and its ``top_k``, a row chosen for an expert held
elsewhere adds nothing here, and that partial result goes on to the next
layer. No code stands in for the absent chips or their exchange.

Serving keeps keys (rotated where the layer rotates) and values in TWO
groups of pages (``Cohere2MoeDecoder.cache_spec``; inference/decoder.py
``PageGroup``): the full layers' first, then the sliding layers', whose
table slides and whose pages go back to their pool as they leave the
window. ``forward_hidden`` is handed a table a group and each table's
base: a sliding layer reads and writes at ``position - base`` and rotates
by the absolute position. On the chip (``paged_attention_kernel:
pallas``) a decode step reads in the grouped page walk
(ops/pallas/paged_attention.py, ``window`` in the sliding layers) and a
prompt chunk in ``chunk_attention`` (ops/pallas/chunk_attention.py);
elsewhere both in XLA's loop (ops/chunk_attention.py), the oracle of the
two kernels.

The serving programs return, beside the hidden states, the expert
layers' summed load (``counters``: ``moe.load``) with, in a third row,
the (token, choice) pairs the launch routed ANYWHERE (``routed``), so
that the share that landed on the experts held here can be read, and
the passes its expert layers made over their capacity (``passes``: one
a layer unless the router sent this chip more than twice its even
share; ops/moe.py ``share_capacity``).

Serving only; a ``model`` mesh axis is refused.
"""
import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..inference.decoder import CacheSpec, PageGroup
from ..inference.kv_cache import write_path, write_tokens
from ..ops import moe
from ..ops.chunk_attention import (block_tokens, blocked_attention,
                                   paged_blocked_attention)
from ..utils.annotate import open_setup_span
from .mellum import FULL, INIT_STD, SLIDING, _key

_FLOAT32_LEAVES = ("router",)
# the streams of a layer's key, in the order
# benchmark/models/command_a_plus_reference.py::draw_layer splits them
_STREAMS = ("q", "k", "v", "o", "router", "shared", "experts")


@dataclass
class Cohere2MoeConfig:
    vocab_size: int = 262144          # the rows of the embedding HELD
    d_model: int = 4096
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, FULL)
    n_heads: int = 128
    n_kv_heads: int = 8
    d_head: int = 128
    d_expert: int = 4096
    n_experts: int = 128              # the router's width
    top_k: int = 8
    # (first, past the last) of the experts held here; None: all
    experts_held: object = None
    n_shared: int = 4
    d_shared: int = 4096
    norm_topk_prob: bool = True
    window: int = 4096
    norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    logit_scale: float = 1.0
    init_std: float = INIT_STD
    # the spread W_q and W_k are drawn at (there are no norms on queries
    # and keys, so it sets the softmax; the benchmark's configuration
    # says what was read); None: ``init_std``
    qk_init_std: object = None
    max_seq_len: int = 200000
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

    @property
    def held(self):
        return tuple(self.experts_held or (0, self.n_experts))


def config_from_hf(model, **overrides):
    """A :class:`Cohere2MoeConfig` from the keys of a published
    ``config.json`` (``model_type: cohere2_moe``). A chip's share says
    so beside them: ``experts_held`` (then ``num_experts`` counts the
    experts held and ``router_num_experts`` the router's width),
    ``padded_vocab_size`` (the embedding's rows held) and
    ``qk_init_std``."""
    assert not model["attention_bias"], "an attention bias is not supported"
    assert len(model["layer_types"]) == model["num_hidden_layers"]
    assert set(model["layer_types"]) <= {SLIDING, FULL}
    assert model["use_parallel_block"] and not model["use_qk_norm"]
    assert model["first_k_dense_replace"] == 0, "no leading dense layer"
    assert model["expert_selection_fn"] == "sigmoid"
    assert model["shared_expert_combination_strategy"] == "average"
    assert model["position_embedding_type"] == "rope_gptj" and \
        model["rotary_pct"] == 1
    assert model["tie_word_embeddings"] and model["use_gated_activation"]
    n_experts = model.get("router_num_experts", model["num_experts"])
    held = tuple(model.get("experts_held", (0, n_experts)))
    assert held[1] - held[0] == model["num_experts"] and \
        0 <= held[0] < held[1] <= n_experts, (held, model["num_experts"])
    extra = {k: model[k] for k in ("qk_init_std",) if k in model}
    extra.update(overrides)
    return Cohere2MoeConfig(
        vocab_size=model.get("padded_vocab_size", model["vocab_size"]),
        d_model=model["hidden_size"],
        layer_types=tuple(model["layer_types"]),
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], d_head=model["head_dim"],
        d_expert=model["intermediate_size"], n_experts=n_experts,
        top_k=model["num_experts_per_tok"], experts_held=held,
        n_shared=model["num_shared_experts"],
        d_shared=model["intermediate_size"],
        norm_topk_prob=model["norm_topk_prob"],
        window=model["sliding_window"], norm_eps=model["layer_norm_eps"],
        rope_theta=float(model["rope_theta"]),
        logit_scale=float(model["logit_scale"]),
        init_std=model.get("initializer_range", INIT_STD),
        max_seq_len=model["max_position_embeddings"], **extra)


# ------------------------------------------------------------------ init
@functools.partial(jax.jit, static_argnames=("d", "ff", "std", "dtype"))
def _draw_experts(key, ids, d, ff, std, dtype):
    """-> (w13 (n, d, 2 ff), w2 (n, ff, d)) of the experts ``ids``, one
    at a time: expert ``e``'s gate, up and down matrices from ``key``
    folded with ``e``."""
    def one(e):
        gate, up, down = jax.random.split(jax.random.fold_in(key, e), 3)
        normal = lambda k, *shape: (std * jax.random.normal(
            k, shape, jnp.float32)).astype(dtype)
        return (jnp.concatenate([normal(gate, d, ff), normal(up, d, ff)],
                                axis=-1), normal(down, ff, d))
    return jax.lax.map(one, ids)


def init_layer(config, seed, i):
    """Layer ``i``'s weights from the seed's stream ``i``, one key a
    name of ``_STREAMS`` (``command_a_plus_reference.draw_layer``'s):
    matrices normal(0, ``init_std``) as (in, out) in ``config.dtype``,
    ``W_q`` and ``W_k`` at ``qk_init_std``, the norm 1, the router (d,
    E) float32 over ALL experts. Expert ``e``, routed or shared, draws
    its gate, up and down matrices from its own key (the stream's
    folded with ``e``), so a share holds what the whole layer would
    hold of those experts; an expert's gate and up matrices lie side by
    side, and the shared experts side by side as one MLP."""
    d, dtype = config.d_model, config.dtype
    h, kvh, dh = config.n_heads, config.n_kv_heads, config.d_head
    keys = dict(zip(_STREAMS, jax.random.split(_key(seed, i),
                                               len(_STREAMS))))
    qk_std = config.init_std if config.qk_init_std is None \
        else config.qk_init_std

    def normal(name, *shape, std=config.init_std, dtype=dtype):
        return (std * jax.random.normal(keys[name], shape,
                                        jnp.float32)).astype(dtype)

    lp = {"norm": jnp.ones((d,), dtype),
          "q": normal("q", d, h * dh, std=qk_std),
          "k": normal("k", d, kvh * dh, std=qk_std),
          "v": normal("v", d, kvh * dh), "o": normal("o", h * dh, d),
          "router": normal("router", d, config.n_experts,
                           dtype=jnp.float32)}
    draw = functools.partial(_draw_experts, d=d, std=config.init_std,
                             dtype=dtype)
    lp["w13"], lp["w2"] = draw(keys["experts"], jnp.arange(*config.held),
                               ff=config.d_expert)
    n, ff = config.n_shared, config.d_shared
    s13, s2 = draw(keys["shared"], jnp.arange(n), ff=ff)
    # one MLP of width n ff: every gate, then every up; the downs stacked
    lp["shared13"] = jnp.concatenate(
        [s13[:, :, :ff].transpose(1, 0, 2).reshape(d, n * ff),
         s13[:, :, ff:].transpose(1, 0, 2).reshape(d, n * ff)], axis=-1)
    lp["shared2"] = s2.reshape(n * ff, d)
    return lp


def init_params(config, seed=0):
    return {
        "layers": [init_layer(config, seed, i)
                   for i in range(config.n_layers)],
        "embed": (config.init_std * jax.random.normal(
            _key(seed, config.n_layers),
            (config.vocab_size, config.d_model), jnp.float32)).astype(
                config.dtype),
        "final_norm": jnp.ones((config.d_model,), config.dtype),
    }


def num_params(config):
    """Parameters HELD: the share's experts and embedding rows."""
    d, dh = config.d_model, config.d_head
    attn = 2 * d * config.n_heads * dh + 2 * d * config.n_kv_heads * dh
    first, past = config.held
    experts = (past - first) * 3 * d * config.d_expert + \
        config.n_shared * 3 * d * config.d_shared + d * config.n_experts
    return config.vocab_size * d + d + config.n_layers * (d + attn + experts)


# --------------------------------------------------------------- layers
def _layer_norm(x, weight, eps):
    """Mean-centred, no bias; float32 inside."""
    xf = x.astype(jnp.float32)
    xf = xf - xf.mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps) *
            weight.astype(jnp.float32)).astype(x.dtype)


def _rotary(x, positions, theta):
    """Rotary embedding of the whole head, INTERLEAVED pairing ``(2j, 2j
    + 1)``, ``inv_freq_j = theta ** (-2j / dh)``. x (b, s, heads, dh);
    positions (b, s) absolute. A lane's partner (lane ``2j + 1`` negated
    for lane ``2j``, lane ``2j`` for lane ``2j + 1``) comes from one small
    matmul with a signed permutation, exact in any dtype: splitting the
    minor dimension into pairs costs the chip a relayout of every head
    (0.32 s of a 4.45 s busy window; PERF.md section 6, PR 50)."""
    dh = x.shape[-1]
    half = dh // 2
    freq = jnp.asarray(theta ** (-np.arange(half, dtype=np.float64) / half),
                       jnp.float32)
    angle = positions.astype(jnp.float32)[..., None] * freq
    cos = jnp.repeat(jnp.cos(angle), 2, axis=-1)[:, :, None, :]
    sin = jnp.repeat(jnp.sin(angle), 2, axis=-1)[:, :, None, :]
    swap = np.zeros((dh, dh), np.float32)
    swap[np.arange(1, dh, 2), np.arange(0, dh, 2)] = -1.0
    swap[np.arange(0, dh, 2), np.arange(1, dh, 2)] = 1.0
    partner = jnp.einsum("bshd,de->bshe", x, jnp.asarray(swap, x.dtype),
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos + partner * sin).astype(x.dtype)


def _experts(u, lp, config):
    """-> (the routed experts HELD here plus the shared experts' mean, of
    ``u`` (.., d); the load (3, E): ops/moe.py's two rows and, at [2,
    0], the (token, choice) pairs routed anywhere, at [2, 1] the passes
    the layer made over its share's capacity)."""
    flat = u.reshape(-1, u.shape[-1])
    chosen, weights = moe.route(
        flat, lp["router"], None, config.top_k, config.norm_topk_prob,
        norm_eps=0.0, scoring="sigmoid")
    out, load = moe.expert_ffn(flat, chosen, weights, lp["w13"], lp["w2"],
                               config.held, config.n_experts,
                               kernel=config.moe_kernel)
    with jax.named_scope("moe.shared"):
        width = lp["shared2"].shape[0]
        hidden = flat @ lp["shared13"]
        shared = (jax.nn.silu(hidden[:, :width]) * hidden[:, width:]) @ \
            lp["shared2"]
        out = out + (shared.astype(jnp.float32) /
                     config.n_shared).astype(out.dtype)
    routed = jnp.zeros((1, config.n_experts), jnp.int32).at[0, 0].set(
        chosen.size).at[0, 1].set(moe.share_passes(
            load, chosen.size, config.held, config.n_experts))
    return out.reshape(u.shape), jnp.concatenate([load, routed])


def _qkv(u, lp, config, tok_pos, sliding):
    """-> q (b, s, h, dh), k (b, s, kvh, dh), rotated to ``tok_pos`` (b,
    s) in a sliding layer and as they are in a full one, and v."""
    b, s, _ = u.shape
    h, kvh, dh = config.n_heads, config.n_kv_heads, config.d_head
    q = (u @ lp["q"]).reshape(b, s, h, dh)
    k = (u @ lp["k"]).reshape(b, s, kvh, dh)
    v = (u @ lp["v"]).reshape(b, s, kvh, dh)
    if sliding:
        q = _rotary(q, tok_pos, config.rope_theta)
        k = _rotary(k, tok_pos, config.rope_theta)
    return q, k, v


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
    sliding = config.is_sliding(i)
    window = config.window if sliding else None
    tok_pos = positions[:, None] + jnp.arange(s)[None, :]
    q, k, v = _qkv(u, lp, config, tok_pos, sliding)
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
    sliding = config.is_sliding(i)
    tok_pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q, k, v = _qkv(u, lp, config, tok_pos, sliding)
    block = block_tokens(s)
    n_blocks = -(-s // block)
    pad = ((0, 0), (0, n_blocks * block - s), (0, 0), (0, 0))
    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    ctx = blocked_attention(
        q, lambda c: tuple(jax.lax.dynamic_slice_in_dim(
            x, c * block, block, 1) for x in (k, v)),
        n_blocks, block, tok_pos, jnp.full((b,), s - 1, jnp.int32),
        config.n_kv_heads, config.window if sliding else None)
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
    expert layers' summed load and what they routed anywhere
    (``_experts``), under ``Cohere2MoeDecoder.counters``' names."""
    x = jnp.take(params["embed"], input_ids, axis=0)
    eps = config.norm_eps
    if cache is not None:
        assert page_tables is not None and page_bases is not None, \
            "Cohere2-MoE serves from the paged layout, a table a page group"
        groups = [tuple(cache[:2]), tuple(cache[2:4])]
    load = jnp.zeros((3, config.n_experts), jnp.int32)
    at = [0, 0]                  # the next layer's index in its group
    for i, lp in enumerate(params["layers"]):
        u = _layer_norm(x, lp["norm"], eps)
        g = int(config.is_sliding(i))
        with jax.named_scope("attn.window" if g else "attn.full"):
            if cache is None:
                mixed = _attention_dense(u, lp, config, i)
            else:
                mixed, groups[g] = _attention_paged(
                    u, lp, config, i, groups[g], at[g], positions,
                    page_tables[g], page_bases[g], valid_lens, page_size)
        at[g] += 1
        # the parallel block: attention and experts read the same norm
        out, layer_load = _experts(u, lp, config)
        x = x + mixed + out
        load = load + layer_load
    x = _layer_norm(x, params["final_norm"], eps)
    out = (x,) if cache is None else (x, groups[0] + groups[1])
    if counters:
        out += ((load,),)
    return out[0] if len(out) == 1 else out


def logits(params, hidden, logit_scale=1.0):
    """The tied head: ``logit_scale * hidden @ embed.T``."""
    with jax.named_scope("head"):
        out = jnp.einsum("...d,vd->...v", hidden,
                         params["embed"].astype(hidden.dtype))
        return out if logit_scale == 1.0 else out * logit_scale


def lm_loss(params, input_ids, labels, config):
    hidden = forward_hidden(params, input_ids, config)
    lg = logits(params, hidden, config.logit_scale).astype(
        jnp.float32)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)
    return -ll.mean()


# -------------------------------------------------------------- serving
class Cohere2MoeDecoder:
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
                "Cohere2-MoE has no tensor-parallel layout yet: a mesh "
                "with a 'model' axis cannot serve it")
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
        value = np.asarray(value)
        return dict(moe.load_attrs(value[:2]), routed=int(value[2, 0]),
                    passes=int(value[2, 1]))

    forward_hidden = staticmethod(forward_hidden)

    def logits(self, params, hidden):
        return logits(params, hidden, self.config.logit_scale)


def make_cohere2_moe_model(config=None, seed=0, **overrides):
    """A :class:`deepspeed_tpu.runtime.model.Model` for
    ``init_inference()``; weights from ``seed`` (``init_layer``)."""
    from ..runtime.model import Model
    config = dataclasses.replace(config or Cohere2MoeConfig(), **overrides)
    params = init_params(config, seed=seed)

    def apply_fn(params, input_ids, labels, rng=None, train=True):
        return lm_loss(params, input_ids, labels, config)

    model = Model(apply_fn, params, name="cohere2_moe")
    model.config = config
    model.decoder = Cohere2MoeDecoder(config)
    return model
