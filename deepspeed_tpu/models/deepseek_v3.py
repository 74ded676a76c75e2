"""The ``deepseek_v3`` architecture (``model_type: deepseek_v3``;
Moonlight-16B-A3B is one): multi-head latent attention in every layer, a
dense gated MLP in the leading layers and, in the rest, a sparse expert
layer beside a SHARED expert that every token takes, for serving through
``init_inference()``.

Layer ``l``: ``h = x + Attn(RMSNorm(x)); y = h + FFN_l(RMSNorm(h))``, no
bias anywhere, a final RMSNorm and a head of its own (untied).
Attention is ops/mla.py's: the query is not compressed (``q_lora_rank``
null), keys and values come from a latent of ``kv_rank`` values and one
rotated ``qk_rope``-wide key that all heads share. The first
``n_dense_layers`` layers have a gated SiLU MLP of ``d_ff``; the others
``top_k`` of ``n_experts`` routed experts a token (ops/moe.py: sigmoid
scores, the choice shifted by a selection bias, weights renormalised
over the chosen and times ``routed_scaling_factor``, no token dropped)
PLUS one gated MLP of ``n_shared_experts * d_expert`` applied to every
token (``moe.shared``). The equations are written out in
``benchmark/models/moonlight_reference.py``, the float32 yardstick;
this module is the program.

Serving keeps ONE kind of state, latent pages
(``DeepseekV3Decoder.cache_spec``): per token and layer the row ``[c~ |
rotated k_pe | 0]``, ``kv_rank + qk_rope`` values padded to whole lanes
(576 -> 640), in one pool ``(pages + 1, layers, page_size, 640)`` and no
second: one scatter a layer and one page DMA a block where a ``(k, v)``
pair makes two, and the decode kernel's values are a lane slice of the
block it fetched for the keys. The pages are the whole of a request's
state, so prefix sharing works as it is; speculative decoding and the
fleet's hand-off take a page for keys and values and are refused at
construction (inference/decoder.py ``refuse``). A prompt chunk
attends in the up-projected form, a decode step in the absorbed one
(ops/mla.py says why they are the same function).

The serving programs return, beside the hidden states, the expert
layers' summed load (``counters``: ``moe.load``; inference/decoder.py).

Serving only; a ``model`` mesh axis is refused.
"""
import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..inference.decoder import CacheSpec
from ..ops import mla, moe

INIT_STD = 0.02
_FLOAT32_LEAVES = ("router", "expert_bias")
# what this architecture's code adds to the sum of the chosen scores
ROUTE_NORM_EPS = 1e-20


@dataclass
class DeepseekV3Config:
    vocab_size: int = 163840
    d_model: int = 2048
    n_layers: int = 27
    n_heads: int = 16
    qk_nope: int = 128
    qk_rope: int = 64
    v_head: int = 128
    kv_rank: int = 512
    d_ff: int = 11264                 # the dense layers' MLP
    d_expert: int = 1408              # one routed expert's MLP
    n_experts: int = 64
    top_k: int = 6
    n_shared_experts: int = 2
    n_dense_layers: int = 1
    norm_eps: float = 1e-5
    kv_norm_eps: float = 1e-6
    rope_theta: float = 50000.0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    # spread of the selection bias drawn at init (a trained router is
    # uneven; the published config gives no number)
    expert_bias_std: float = 0.04
    init_std: float = INIT_STD
    # W_q and W_kva are drawn at ``attn_in_scale`` times ``init_std``,
    # W_kvb and W_o at ``attn_out_scale`` times: under normal(0, 0.02)
    # the softmax is flat and the layer's output small beside the
    # residual, and what attention does wrongly cannot be seen
    attn_in_scale: float = 1.0
    attn_out_scale: float = 1.0
    max_seq_len: int = 8192
    dtype: object = jnp.bfloat16      # matrices, embedding, activations
    # the expert ids this chip holds, (first, past the last); None: all
    experts_held: object = None
    # "pallas" (ops/pallas/moe.py) | "xla" (lax.ragged_dot) | "auto"
    moe_kernel: str = "auto"
    # a decode step's read of the latent pages: "pallas"
    # (ops/pallas/paged_attention.py mla_decode) | "xla" (its oracle)
    paged_attention_kernel: str = "xla"

    @property
    def held(self):
        return tuple(self.experts_held or (0, self.n_experts))

    @property
    def d_shared(self):
        return self.n_shared_experts * self.d_expert

    def is_dense(self, i):
        return i < self.n_dense_layers

    @property
    def expert_layers(self):
        return [i for i in range(self.n_layers) if not self.is_dense(i)]

    @property
    def mla(self):
        return mla.MLADims(
            heads=self.n_heads, nope=self.qk_nope, rope=self.qk_rope,
            v=self.v_head, rank=self.kv_rank, rope_theta=self.rope_theta,
            kv_norm_eps=self.kv_norm_eps)


def config_from_hf(model, **overrides):
    """A :class:`DeepseekV3Config` from the keys of a published
    ``config.json`` (``model_type: deepseek_v3``)."""
    assert model.get("q_lora_rank") is None, \
        "a compressed query (q_lora_rank) is not supported"
    assert not model.get("attention_bias", False), "no biases"
    assert model["n_group"] == 1 and model["topk_group"] == 1, \
        "group-limited routing is not supported"
    assert model["scoring_func"] == "sigmoid" and \
        model["topk_method"] == "noaux_tc" and model["moe_layer_freq"] == 1
    assert not model.get("tie_word_embeddings", False), "the head is untied"
    assert "rope_scaling" not in model or model["rope_scaling"] is None
    extra = {k: model[k] for k in ("expert_bias_std", "attn_in_scale",
                                   "attn_out_scale", "kv_norm_eps")
             if k in model}
    extra.update(overrides)
    return DeepseekV3Config(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        qk_nope=model["qk_nope_head_dim"], qk_rope=model["qk_rope_head_dim"],
        v_head=model["v_head_dim"], kv_rank=model["kv_lora_rank"],
        d_ff=model["intermediate_size"],
        d_expert=model["moe_intermediate_size"],
        n_experts=model["n_routed_experts"],
        top_k=model["num_experts_per_tok"],
        n_shared_experts=model["n_shared_experts"],
        n_dense_layers=model["first_k_dense_replace"],
        norm_eps=model["rms_norm_eps"],
        rope_theta=float(model["rope_theta"]),
        norm_topk_prob=model["norm_topk_prob"],
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        init_std=model.get("initializer_range", INIT_STD),
        max_seq_len=model["max_position_embeddings"], **extra)


# ------------------------------------------------------------------ init
def _key(seed, i):
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), i)


def init_layer(config, seed, i):
    """Layer ``i``'s weights from the seed's stream ``i``, split in the
    order written here: matrices normal(0, ``init_std``) as (in, out) in
    ``config.dtype`` (the four attention matrices at their scales);
    norms 1; the router (d, E) float32; the selection bias normal(0,
    ``expert_bias_std``) float32. EVERY routed expert's matrices are
    drawn, then the held ones kept. A gated MLP's gate and up matrices
    are held side by side (``*13``)."""
    d, dtype = config.d_model, config.dtype
    h = config.n_heads
    keys = iter(jax.random.split(_key(seed, i), 16))

    def normal(*shape, scale=1.0, dtype=dtype):
        return (config.init_std * scale * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    ones = lambda n: jnp.ones((n,), dtype)
    a_in, a_out = config.attn_in_scale, config.attn_out_scale
    lp = {
        "attn_norm": ones(d), "ffn_norm": ones(d),
        "q": normal(d, h * (config.qk_nope + config.qk_rope), scale=a_in),
        "kv_a": normal(d, config.kv_rank + config.qk_rope, scale=a_in),
        "kv_norm": ones(config.kv_rank),
        "kv_b": normal(config.kv_rank, h * (config.qk_nope + config.v_head),
                       scale=a_out),
        "o": normal(h * config.v_head, d, scale=a_out),
    }
    if config.is_dense(i):
        ff = config.d_ff
        lp["w13"] = jnp.concatenate([normal(d, ff), normal(d, ff)], axis=-1)
        lp["w2"] = normal(ff, d)
        return lp
    E, ff, sff = config.n_experts, config.d_expert, config.d_shared
    first, past = config.held
    lp["router"] = normal(d, E, dtype=jnp.float32)
    lp["expert_bias"] = config.expert_bias_std * jax.random.normal(
        next(keys), (E,), jnp.float32)
    lp["shared13"] = jnp.concatenate([normal(d, sff), normal(d, sff)],
                                     axis=-1)
    lp["shared2"] = normal(sff, d)
    w1, w3 = normal(E, d, ff)[first:past], normal(E, d, ff)[first:past]
    lp["w13"] = jnp.concatenate([w1, w3], axis=-1)
    del w1, w3
    lp["w2"] = normal(E, ff, d)[first:past]
    return lp


def init_params(config, seed=0):
    def table(stream, *shape):
        return (config.init_std * jax.random.normal(
            _key(seed, stream), shape, jnp.float32)).astype(config.dtype)

    L = config.n_layers
    return {
        "layers": [init_layer(config, seed, i) for i in range(L)],
        "embed": table(L, config.vocab_size, config.d_model),
        "head": table(L + 1, config.d_model, config.vocab_size),
        "final_norm": jnp.ones((config.d_model,), config.dtype),
    }


def num_params(config):
    """Parameters held here (the experts held; embedding and head
    each)."""
    d, h = config.d_model, config.n_heads
    attn = d * h * (config.qk_nope + config.qk_rope) + \
        d * (config.kv_rank + config.qk_rope) + config.kv_rank + \
        config.kv_rank * h * (config.qk_nope + config.v_head) + \
        h * config.v_head * d
    dense = 3 * d * config.d_ff
    first, past = config.held
    experts = (past - first) * 3 * d * config.d_expert + \
        3 * d * config.d_shared + d * config.n_experts + config.n_experts
    n_dense = min(config.n_dense_layers, config.n_layers)
    return (2 * config.vocab_size * d + d +
            config.n_layers * (2 * d + attn) + n_dense * dense +
            (config.n_layers - n_dense) * experts)


# --------------------------------------------------------------- layers
def _gated_mlp(u, w13, w2):
    ff = w2.shape[0]
    h = u @ w13
    return (jax.nn.silu(h[..., :ff]) * h[..., ff:]) @ w2


def _ffn(u, lp, config):
    """-> (the layer's FFN of ``u`` (.., d), its load (2, E) or None)."""
    if "router" not in lp:
        return _gated_mlp(u, lp["w13"], lp["w2"]), None
    flat = u.reshape(-1, u.shape[-1])
    chosen, weights = moe.route(
        flat, lp["router"], lp["expert_bias"], config.top_k,
        config.norm_topk_prob, config.routed_scaling_factor,
        norm_eps=ROUTE_NORM_EPS)
    out, load = moe.expert_ffn(flat, chosen, weights, lp["w13"], lp["w2"],
                               config.held, config.n_experts,
                               kernel=config.moe_kernel)
    with jax.named_scope("moe.shared"):
        shared = _gated_mlp(flat, lp["shared13"], lp["shared2"])
    return (out + shared).reshape(u.shape), load


def forward_hidden(params, input_ids, config, cache=None, positions=None,
                   page_tables=None, valid_lens=None, page_size=None,
                   counters=False):
    """Embedding + the layer stack + the final norm -> hidden states.

    Without ``cache``: the plain forward over whole sequences (b, s).
    With ``cache`` = ``(latent pool,)`` returns ``(hidden, cache)``.
    With ``counters`` the last of what is returned is ``(load,)``: the
    expert layers' summed load (ops/moe.py), under
    ``DeepseekV3Decoder.counters``' names."""
    x = jnp.take(params["embed"], input_ids, axis=0)
    eps, dims = config.norm_eps, config.mla
    if cache is not None:
        assert page_tables is not None, \
            "latent pages are served through page tables only"
        (pool,) = cache
    load = jnp.zeros((2, config.n_experts), jnp.int32)
    for i, lp in enumerate(params["layers"]):
        u = mla.rms_norm(x, lp["attn_norm"], eps)
        if cache is None:
            ctx = mla.attention_dense(u, lp, dims)
        else:
            ctx, pool = mla.attention_paged(
                u, lp, dims, pool, i, positions, page_tables, valid_lens,
                page_size, kernel=config.paged_attention_kernel)
        x = x + ctx @ lp["o"]
        out, layer_load = _ffn(mla.rms_norm(x, lp["ffn_norm"], eps), lp,
                               config)
        x = x + out
        if layer_load is not None:
            load = load + layer_load
    x = mla.rms_norm(x, params["final_norm"], eps)
    out = (x,) if cache is None else (x, (pool,))
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
class DeepseekV3Decoder:
    """What ``init_inference()`` asks of a model (inference/decoder.py)."""

    recurrent = False
    # what the serving programs return beside their tokens
    counters = ("moe.load",)

    def __init__(self, config):
        self.config = config

    def cache_spec(self):
        cfg = self.config
        return CacheSpec(kv_layers=cfg.n_layers, kv_heads=1,
                         d_head=cfg.kv_rank + cfg.qk_rope,
                         page_lanes=cfg.mla.lanes)

    def serving_config(self, mesh):
        from ..parallel.topology import MODEL_AXIS
        if mesh is not None and int(mesh.shape.get(MODEL_AXIS, 1)) > 1:
            raise ValueError(
                "deepseek_v3 has no tensor-parallel layout yet: a mesh "
                "with a 'model' axis cannot serve it")
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


def make_deepseek_v3_model(config=None, seed=0, **overrides):
    """A :class:`deepspeed_tpu.runtime.model.Model` for
    ``init_inference()``; weights from ``seed`` (``init_layer``)."""
    from ..runtime.model import Model
    config = dataclasses.replace(config or DeepseekV3Config(), **overrides)
    params = init_params(config, seed=seed)

    def apply_fn(params, input_ids, labels, rng=None, train=True):
        return lm_loss(params, input_ids, labels, config)

    model = Model(apply_fn, params, name="deepseek_v3")
    model.config = config
    model.decoder = DeepseekV3Decoder(config)
    return model
