"""Jamba: Mamba-1 layers beside a few attention layers (AI21's
``model_type: jamba``), for serving through ``init_inference()``.

Layer ``i`` is an attention layer if ``i % attn_layer_period ==
attn_layer_offset``, else a Mamba layer; every layer is
``h = x + Mixer(RMSNorm(x)); out = h + MLP(RMSNorm(h))`` with a gated
SiLU MLP, no biases, no positional encoding, a final RMSNorm and the
head tied to the embedding. The attention layers are grouped-query
(``n_kv_heads`` key-value heads under ``n_heads`` query heads). The
equations are written out in ``benchmark/models/jamba_reference.py``,
the float32 yardstick; this module is the program.

Serving keeps TWO kinds of state (``JambaDecoder.cache_spec``):

* the attention layers' keys and values in the engine's page pool,
  ``(pages + 1, attention layers, page_size, n_kv_heads * d_head)``,
  written by ``kv_cache.write_tokens`` and read by one gather on
  (page, layer)
  as GPT-2's are;
* per slot and Mamba layer a convolution tail, ``conv (mamba layers,
  slots, (d_conv - 1) * d_inner)`` (a slot's three last inputs side by
  side in ONE row: a second-minor dimension of 3 made the chip's
  compiler re-tile the whole pool on the way into and out of every
  decode step), and an SSM state, ``ssm (mamba layers, slots, d_state,
  d_inner)``: ``d_inner`` minor (a minor dimension of ``d_state`` = 16
  would be padded to the chip's 128 lanes) and layer-major, so that a
  program reads and writes one layer's own region and never copies a
  slab to slice it.

A recurrent state has no causal mask to hide what a slot held before:
the prefill program that runs a request's FIRST chunk (``positions ==
0``) starts from zeros whatever the slot holds, a later chunk starts
from the slot's state, a padded bucket leaves the state as it was after
the chunk's last real token (``valid_lens``), and the decode program
advances only the slots the scheduler says are decoding
(``state_advance``).

Serving only; a ``model`` mesh axis is refused.
"""
import dataclasses
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..inference.decoder import CacheSpec, StateSpec
from ..inference.kv_cache import read_scope, write_tokens

INIT_STD = 0.02
DT_MIN, DT_MAX = 1e-3, 1e-1


@dataclass
class JambaConfig:
    vocab_size: int = 65536
    d_model: int = 2560
    n_layers: int = 28
    n_heads: int = 20
    n_kv_heads: int = 1
    d_ff: int = 8192
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    expand: int = 2
    rms_norm_eps: float = 1e-6
    init_std: float = INIT_STD        # the published initializer_range
    max_seq_len: int = 262144
    dtype: object = jnp.bfloat16      # matrices, embedding, activations
    # the SSM state's dtype in the serving pool (float32 | bfloat16)
    state_dtype: object = jnp.float32
    # "pallas" (ops/pallas/mamba.py) | "xla" (lax.scan / einsum oracles)
    # | "auto": pallas on a TPU, xla elsewhere
    scan_kernel: str = "auto"
    # the attention layers' paged read: "xla" (the gather, the oracle)
    # or "pallas" (the page-table walk); the engine sets it on the
    # decode program family only, from inference.paged_attention_kernel
    paged_attention_kernel: str = "xla"
    kernel_mesh: object = None

    @property
    def d_head(self):
        return self.d_model // self.n_heads

    @property
    def d_inner(self):
        return self.expand * self.d_model

    def is_attention(self, i):
        return i % self.attn_layer_period == self.attn_layer_offset

    @property
    def attention_layers(self):
        return [i for i in range(self.n_layers) if self.is_attention(i)]

    @property
    def mamba_layers(self):
        return [i for i in range(self.n_layers) if not self.is_attention(i)]


def config_from_hf(model, **overrides):
    """A :class:`JambaConfig` from the keys of a published
    ``config.json`` (``model_type: jamba``)."""
    assert model.get("num_experts", 1) == 1, \
        "models/jamba.py has no expert MLP (ops/moe.py has the layer)"
    return JambaConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"],
        attn_layer_period=model["attn_layer_period"],
        attn_layer_offset=model["attn_layer_offset"],
        d_state=model["mamba_d_state"], d_conv=model["mamba_d_conv"],
        dt_rank=model["mamba_dt_rank"], expand=model["mamba_expand"],
        rms_norm_eps=model["rms_norm_eps"],
        init_std=model.get("initializer_range", INIT_STD),
        max_seq_len=model["max_position_embeddings"], **overrides)


# ------------------------------------------------------------------ init
def _key(seed, i):
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), i)


def init_layer(config, seed, i):
    """Layer ``i``'s weights from the seed's stream ``i``: matrices
    normal(0, 0.02) as (in, out) in ``config.dtype``; norms 1; the
    Mamba paper's initialisation for ``A_log`` (log 1..d_state),
    ``D`` (1) and the dt bias (inverse softplus of a log-uniform dt in
    [1e-3, 1e-1]), which stay float32 as the released kernels read
    them. ``conv_w`` and ``A_log`` are held transposed, ``d_inner``
    minor."""
    d, ff, dtype = config.d_model, config.d_ff, config.dtype
    keys = iter(jax.random.split(_key(seed, i), 16))

    def normal(*shape):
        return (config.init_std * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    ones = lambda n: jnp.ones((n,), dtype)
    lp = {"norm1": ones(d), "norm2": ones(d),
          "gate": normal(d, ff), "up": normal(d, ff),
          "down": normal(ff, d)}
    if config.is_attention(i):
        kv = config.n_kv_heads * config.d_head
        lp.update(q=normal(d, d), k=normal(d, kv), v=normal(d, kv),
                  o=normal(d, d))
        return lp
    di, n, r, kc = (config.d_inner, config.d_state, config.dt_rank,
                    config.d_conv)
    dt = jnp.exp(jax.random.uniform(next(keys), (di,), jnp.float32) *
                 (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    in_proj, conv_w = normal(d, 2 * di), normal(di, kc)
    x_proj, dt_proj = normal(di, r + 2 * n), normal(r, di)
    lp.update(
        in_proj=in_proj, conv_w=conv_w.T, conv_b=jnp.zeros((di,), dtype),
        x_proj=x_proj, dt_proj=dt_proj,
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        A_log=jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
            (n, di)),
        D=jnp.ones((di,), jnp.float32), out_proj=normal(di, d),
        dt_norm=ones(r), B_norm=ones(n), C_norm=ones(n))
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


_FLOAT32_LEAVES = ("dt_bias", "A_log", "D")


def num_params(config):
    d, ff, di, n, r, kc = (config.d_model, config.d_ff, config.d_inner,
                           config.d_state, config.dt_rank, config.d_conv)
    mlp = 3 * d * ff + 2 * d
    mamba = (d * 2 * di + di * kc + di + di * (r + 2 * n) + r * di + di +
             di * n + di + di * d + r + 2 * n)
    attn = 2 * d * d + 2 * d * config.n_kv_heads * config.d_head
    n_attn = len(config.attention_layers)
    return (config.vocab_size * d + d + n_attn * (attn + mlp) +
            (config.n_layers - n_attn) * (mamba + mlp))


# --------------------------------------------------------------- layers
def _rms_norm(x, weight, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)).astype(x.dtype)


def _mlp(x, lp, config):
    with jax.named_scope("mlp"):
        u = _rms_norm(x, lp["norm2"], config.rms_norm_eps)
        return (jax.nn.silu(u @ lp["gate"]) * (u @ lp["up"])) @ lp["down"]


def _use_pallas(config):
    if config.scan_kernel == "auto":
        from ..ops.pallas.common import default_interpret
        return not default_interpret()
    return config.scan_kernel == "pallas"


def _dt_b_c(xc, lp, config):
    """-> dt (.., d_inner) f32 after softplus, B, C (.., d_state) f32."""
    r, n, eps = config.dt_rank, config.d_state, config.rms_norm_eps
    with jax.named_scope("mamba.proj"):
        dt, B, C = jnp.split(xc @ lp["x_proj"], [r, r + n], axis=-1)
        dt = _rms_norm(dt, lp["dt_norm"], eps)
        B = _rms_norm(B, lp["B_norm"], eps).astype(jnp.float32)
        C = _rms_norm(C, lp["C_norm"], eps).astype(jnp.float32)
        dt = jax.nn.softplus((dt @ lp["dt_proj"]).astype(jnp.float32) +
                             lp["dt_bias"].astype(jnp.float32))
    return dt, B, C


def _mamba_sequence(u, lp, config, tail0, h0, valid_len):
    """The Mamba mixer over ONE sequence chunk ``u`` (s, d) from the
    convolution tail ``tail0`` (d_conv-1, d_inner) and SSM state ``h0``
    (d_state, d_inner). -> (mixer output (s, d), the tail and the state
    as they are after ``valid_len`` tokens)."""
    from ..ops.pallas import mamba as kernels
    s, kc = u.shape[0], config.d_conv
    with jax.named_scope("mamba.proj"):
        x, z = jnp.split(u @ lp["in_proj"], 2, axis=-1)
    padded = jnp.concatenate([tail0.astype(x.dtype), x], axis=0)
    conv = sum(padded[k:k + s].astype(jnp.float32) *
               lp["conv_w"][k].astype(jnp.float32) for k in range(kc))
    xc = jax.nn.silu(conv + lp["conv_b"].astype(jnp.float32)).astype(x.dtype)
    # the last d_conv-1 real inputs (the old tail's, where the chunk
    # is shorter than that)
    tail = jax.lax.dynamic_slice_in_dim(padded, valid_len, kc - 1, axis=0)
    dt, B, C = _dt_b_c(xc, lp, config)
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))
    scan = kernels.mamba_scan if _use_pallas(config) and s % 8 == 0 \
        else kernels.mamba_scan_xla
    with jax.named_scope("mamba.scan"):
        y, h = scan(xc, dt, B, C, A, h0, valid_len)
    return _gate_and_project(y, xc, z, lp), tail, h


def _gate_and_project(y, xc, z, lp):
    """``W_out((y + D x) * silu(z))``: the scan's output, the skip, the
    gate, the output projection (y float32; xc, z in compute dtype)."""
    with jax.named_scope("mamba.proj"):
        y = y + lp["D"].astype(jnp.float32) * xc.astype(jnp.float32)
        y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(xc.dtype)
        return y @ lp["out_proj"]


def _mamba_prefill(u, lp, config, state, m, slot, start, valid_len):
    """One slot's chunk against the state pools (``m``: the layer's
    index among the Mamba layers). The first chunk (``start == 0``)
    starts from zeros whatever the slot holds."""
    conv, ssm = state
    first = start == 0
    tail0 = jnp.where(first, 0, conv[m, slot].reshape(config.d_conv - 1,
                                                     config.d_inner))
    h0 = jnp.where(first, 0, ssm[m, slot].astype(jnp.float32))
    out, tail, h = _mamba_sequence(u[0], lp, config, tail0, h0, valid_len)
    conv = conv.at[m, slot].set(tail.astype(conv.dtype).reshape(-1))
    ssm = ssm.at[m, slot].set(h.astype(ssm.dtype))
    return out[None], (conv, ssm)


def _mamba_decode(u, lp, config, state, m, advance):
    """One token for every slot (u (slots, 1, d)); a slot outside
    ``advance`` keeps its tail and its state."""
    from ..ops.pallas import mamba as kernels
    conv, ssm = state
    di = config.d_inner
    with jax.named_scope("mamba.proj"):
        x, z = jnp.split(u[:, 0] @ lp["in_proj"], 2, axis=-1)  # (slots, di)
    # a slot's row: its d_conv - 1 last inputs, then the new one
    window = jnp.concatenate([conv[m], x.astype(conv.dtype)], axis=1)
    acc = sum(window[:, k * di:(k + 1) * di].astype(jnp.float32) *
              lp["conv_w"][k].astype(jnp.float32)
              for k in range(config.d_conv))
    xc = jax.nn.silu(acc + lp["conv_b"].astype(jnp.float32)).astype(x.dtype)
    conv = conv.at[m].set(jnp.where(advance[:, None], window[:, di:],
                                    conv[m]))
    dt, B, C = _dt_b_c(xc, lp, config)
    # a slot held back keeps its state: dt 0 and a zero input term
    # (selected, not multiplied: its row may hold anything)
    hold = ~advance[:, None]
    dt, B = jnp.where(hold, 0.0, dt), jnp.where(hold, 0.0, B)
    xs = jnp.where(hold, 0, xc)
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))
    step = kernels.mamba_step if _use_pallas(config) \
        else kernels.mamba_step_xla
    with jax.named_scope("mamba.step"):
        y, ssm = step(ssm, m, xs, dt, B, C, A)
    return _gate_and_project(y, xc, z, lp)[:, None], (conv, ssm)


def _attend(q, k_rows, v_rows, positions, valid_lens, config):
    """Causal grouped-query attention of ``s`` new queries (b, s, h,
    dh) over rows (b, S, kvh, dh) under the absolute-position mask
    ``k_pos <= q_pos``; V is zeroed past the live window (a masked
    weight is exactly 0, but 0 * NaN is NaN: recycled pages are reused
    without clearing)."""
    b, s, h, dh = q.shape
    S, kvh = k_rows.shape[1], k_rows.shape[2]
    q = q.reshape(b, s, kvh, h // kvh, dh)
    scores = jnp.einsum("bskgd,bSkd->bkgsS", q, k_rows,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / math.sqrt(dh))
    k_pos = jnp.arange(S)[None, None, :]
    q_pos = positions[:, None, None] + jnp.arange(s)[None, :, None]
    scores = jnp.where((k_pos <= q_pos)[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    live = positions + (valid_lens if valid_lens is not None else s) - 1
    v_rows = jnp.where((jnp.arange(S)[None, :] <= live[:, None])
                       [:, :, None, None], v_rows, 0)
    ctx = jnp.einsum("bkgsS,bSkd->bskgd", probs.astype(v_rows.dtype),
                     v_rows, preferred_element_type=jnp.float32)
    return ctx.reshape(b, s, h * dh)


def _attention_paged(u, lp, config, k_cache, v_cache, a, positions,
                     page_tables, valid_lens, page_size):
    """An attention layer against the page pool (``a``: the layer's
    index among the attention layers): ``kv_cache.write_tokens`` and
    the (page, layer) gather of ``models/gpt2.py::_paged_attn_ctx``."""
    b, s, _ = u.shape
    h, kvh, dh = config.n_heads, config.n_kv_heads, config.d_head
    max_pages = page_tables.shape[1]
    with jax.named_scope("attn.proj"):
        q = (u @ lp["q"]).reshape(b, s, h, dh)
        k, v = u @ lp["k"], u @ lp["v"]                # (b, s, kvh*dh)
    k_cache, v_cache = write_tokens(
        (k_cache, v_cache), (k.reshape(b, s, -1), v.reshape(b, s, -1)),
        a, page_tables, positions, valid_lens, page_size,
        mesh=config.kernel_mesh)

    with jax.named_scope(read_scope(s, page_size)):
        if config.paged_attention_kernel == "pallas":
            # the page-table walk in the kernel: the live pages and no
            # others (ops/pallas/paged_attention.py, the grouped kernel)
            from ..ops.pallas.paged_attention import paged_attention
            ctx = paged_attention(q, k_cache, v_cache, page_tables,
                                  positions, valid_lens, layer_idx=a,
                                  page_size=page_size).reshape(b, s, h * dh)
        else:
            def rows_of(cache):
                # one gather on (page, layer): the slot's whole logical
                # window, max_pages pages, live or not
                return cache[page_tables, a].reshape(
                    b, max_pages * page_size, kvh, dh)

            ctx = _attend(q, rows_of(k_cache), rows_of(v_cache), positions,
                          valid_lens, config)
    with jax.named_scope("attn.proj"):
        return ctx.astype(u.dtype) @ lp["o"], k_cache, v_cache


def _attention_dense(u, lp, config):
    b, s, _ = u.shape
    h, kvh, dh = config.n_heads, config.n_kv_heads, config.d_head
    q = (u @ lp["q"]).reshape(b, s, h, dh)
    k = (u @ lp["k"]).reshape(b, s, kvh, dh)
    v = (u @ lp["v"]).reshape(b, s, kvh, dh)
    ctx = _attend(q, k, v, jnp.zeros((b,), jnp.int32), None, config)
    return ctx.astype(u.dtype) @ lp["o"]


def forward_hidden(params, input_ids, config, cache=None, positions=None,
                   page_tables=None, valid_lens=None, page_size=None,
                   state_slot=None, state_advance=None):
    """Embedding + the layer stack + the final norm -> hidden states.

    Without ``cache``: the plain forward over whole sequences (b, s),
    every recurrence from zero. With ``cache`` = ``(k, v, conv, ssm)``
    (the page pool and the state pools of the module docstring)
    returns ``(hidden, cache)``: ``state_slot`` (int32 scalar) selects
    prefill of one slot's chunk (b = 1; ``positions[0]`` the chunk's
    start, 0 meaning a request's first chunk; ``valid_lens[0]`` its
    real tokens); otherwise decode, one token for every slot,
    ``state_advance`` (slots,) bool marking the slots whose recurrent
    state this step advances."""
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], input_ids, axis=0)
    eps = config.rms_norm_eps
    if cache is not None:
        assert page_tables is not None, \
            "Jamba serves from pages only (page_tables=)"
        k_cache, v_cache, *state = cache
        state = tuple(state)
        if state_slot is None:
            assert input_ids.shape[1] == 1, \
                "a recurrent state advances one token a decode step"
            if state_advance is None:
                state_advance = jnp.ones((input_ids.shape[0],), bool)
    a = m = 0
    for i, lp in enumerate(params["layers"]):
        u = _rms_norm(x, lp["norm1"], eps)
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
                zeros = (jnp.zeros((config.d_conv - 1, config.d_inner),
                                   x.dtype),
                         jnp.zeros((config.d_state, config.d_inner),
                                   jnp.float32))
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
        x = x + mixed
        x = x + _mlp(x, lp, config)
    x = _rms_norm(x, params["final_norm"], eps)
    if cache is None:
        return x
    return x, (k_cache, v_cache) + state


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
class JambaDecoder:
    """What ``init_inference()`` asks of a model (inference/decoder.py)."""

    recurrent = True

    def __init__(self, config):
        self.config = config

    def cache_spec(self):
        cfg = self.config
        n_mamba = len(cfg.mamba_layers)
        return CacheSpec(
            kv_layers=len(cfg.attention_layers), kv_heads=cfg.n_kv_heads,
            d_head=cfg.d_head,
            state=(StateSpec("conv", (n_mamba,),
                             ((cfg.d_conv - 1) * cfg.d_inner,), cfg.dtype),
                   StateSpec("ssm", (n_mamba,),
                             (cfg.d_state, cfg.d_inner), cfg.state_dtype)))

    def serving_config(self, mesh):
        from ..parallel.topology import MODEL_AXIS
        if mesh is not None and int(mesh.shape.get(MODEL_AXIS, 1)) > 1:
            raise ValueError(
                "Jamba has no tensor-parallel layout yet: a mesh with a "
                "'model' axis cannot serve it")
        return dataclasses.replace(self.config, kernel_mesh=mesh,
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

    forward_hidden = staticmethod(forward_hidden)
    logits = staticmethod(logits)


def make_jamba_model(config=None, seed=0, **overrides):
    """A :class:`deepspeed_tpu.runtime.model.Model` for
    ``init_inference()``; weights from ``seed`` (``init_layer``)."""
    from ..runtime.model import Model
    config = dataclasses.replace(config or JambaConfig(), **overrides)
    params = init_params(config, seed=seed)

    def apply_fn(params, input_ids, labels, rng=None, train=True):
        return lm_loss(params, input_ids, labels, config)

    model = Model(apply_fn, params, name="jamba")
    model.config = config
    model.decoder = JambaDecoder(config)
    return model
