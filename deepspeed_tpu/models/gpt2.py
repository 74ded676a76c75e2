"""Megatron-style GPT-2 — the flagship pretraining model family.

Reference parity: the DeepSpeedExamples Megatron-GPT2 workload (BASELINE
configs 2/4/5; reference tests/model/Megatron_GPT2). TPU-first design:

  * pure-functional transformer over a params pytree; one jitted step;
  * Megatron tensor parallelism expressed as PartitionSpecs on the ``model``
    mesh axis (QKV/MLP-in column-parallel, proj/MLP-out row-parallel,
    vocab-parallel embedding) — XLA inserts the TP collectives that
    Megatron's ColumnParallelLinear/RowParallelLinear do by hand;
  * activation checkpointing via jax.checkpoint per block;
  * attention routed through ops.transformer (Pallas flash attention on TPU,
    reference csrc/transformer fused kernels).

Model size table matches GPT-2 family: 125M/350M/760M/1.5B (gpt2_small..xl).
"""
import math
import dataclasses
from dataclasses import dataclass, field
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..inference.kv_cache import read_scope, write_path, write_tokens
from ..parallel.topology import MODEL_AXIS


@dataclass
class GPT2Config:
    vocab_size: int = 50304        # 50257 padded to a multiple of 128
    max_seq_len: int = 1024
    n_layers: int = 12
    n_heads: int = 12
    d_model: int = 768
    dropout: float = 0.0
    remat: bool = True             # activation checkpointing per block
    remat_policy: str = "full"     # "full" | "dots" (save MXU outputs)
    loss_chunk: int = 128          # CE seq-chunking (0 = dense logits)
    # lax.scan over stacked block params: one compiled block body instead
    # of n_layers unrolled copies — compile time O(1) in depth. Off by
    # default: the pipeline path owns its own stacking.
    scan_blocks: bool = False
    use_flash_attention: bool = True
    # Resolved transformer.flash_attention tri-state
    # ("pallas"|"interpret"|"xla", ops.transformer.attention.
    # resolve_flash_backend). None keeps the legacy use_flash_attention
    # bool dispatch; the engine sets this from ds_config so a forced
    # "pallas" off-TPU runs the interpreter instead of silently going
    # dense.
    flash_attention_backend: object = None
    # The mesh the engine's step programs span (Model.bind_mesh /
    # InferenceEngine set it on their own copy of the config). GSPMD
    # cannot partition a Mosaic kernel, so the kernel dispatch sites
    # hand it on and the kernels run under a shard_map over it
    # (ops/pallas/common.py shard_kernel).
    kernel_mesh: object = None
    dtype: object = jnp.float32    # param dtype at init (engine recasts)
    # Sequence/context parallelism: "ring" | "ulysses" | None. When set,
    # attention runs via shard_map over sp_mesh's ``sequence`` axis
    # (parallel/ring_attention.py) so activations shard over sequence.
    sequence_parallel: object = None
    sp_mesh: object = None
    # Sparse embedding-gradient exchange (ds_config "sparse_gradients" /
    # reference CSR allreduce): backward ships (ids, rows) over the data
    # axis instead of the dense (vocab, d) cotangent. Needs the engine's
    # global mesh (same contract as sp_mesh).
    sparse_embedding_grads: bool = False
    embedding_grad_mesh: object = None
    # Collective matmul (comm.collective_matmul): a
    # parallel.collective_matmul.CollectiveMatmulBinding attached by the
    # engine when fusion is enabled and the mesh carries a >1 ``model``
    # axis. The TP matmul sites (qkv/fc column-parallel gathers,
    # attn-proj/fc2 row-parallel scatters) then run the ring-decomposed
    # fused GEMMs; None (default) keeps the plain XLA matmuls — the
    # numerics oracle.
    collective_matmul: object = None
    # Block-sparse attention: the parsed ds_config "sparse_attention"
    # dict (mode/block/...), e.g. engine.sparse_attention_config().
    # When set, _attn_ctx runs the Pallas block-sparse kernels
    # (ops/sparse_attention) instead of dense flash — the reference's
    # "10x longer sequences" path (tests/perf/longseq_model.py measures
    # the model-level capability). Causal; incompatible with
    # sequence_parallel.
    sparse_attention: object = None
    # Paged-attention read path: "xla" (the jnp.take gather-back — the
    # numerics oracle and default) or "pallas" (ops/pallas/
    # paged_attention: in-kernel page-table walk with double-buffered
    # page fetches and online softmax; for a prompt chunk ops/pallas/
    # chunk_attention). The serving engine resolves the
    # inference.paged_attention_kernel tri-state into this field on the
    # DECODE program family and, on one chip, the PREFILL family
    # (docs/pallas_kernels.md); training never reads it.
    paged_attention_kernel: str = "xla"

    @property
    def d_head(self):
        return self.d_model // self.n_heads


SIZES = {
    "gpt2_small": dict(n_layers=12, n_heads=12, d_model=768),      # 125M
    "gpt2_medium": dict(n_layers=24, n_heads=16, d_model=1024),    # 350M
    "gpt2_large": dict(n_layers=36, n_heads=20, d_model=1280),     # 760M
    "gpt2_xl": dict(n_layers=48, n_heads=25, d_model=1600),        # 1.5B
}


def config_for(name, **overrides):
    base = dict(SIZES[name])
    base.update(overrides)
    return GPT2Config(**base)


def init_block_params(config, rng):
    """One transformer block, Megatron init: normal(0, 0.02) with the
    residual output projections scaled by 1/sqrt(2*n_layers) — n_layers is
    the FULL model depth (also used by the pipeline's per-layer init)."""
    std = 0.02
    proj_std = std / math.sqrt(2.0 * config.n_layers)
    d = config.d_model
    norm = lambda *shape, sd=std: jnp.asarray(
        rng.randn(*shape) * sd, dtype=config.dtype)
    zeros = lambda *shape: jnp.zeros(shape, dtype=config.dtype)
    ones = lambda *shape: jnp.ones(shape, dtype=config.dtype)
    return {
        "ln1": {"scale": ones(d), "bias": zeros(d)},
        "attn": {
            "qkv_kernel": norm(d, 3 * d),
            "qkv_bias": zeros(3 * d),
            "proj_kernel": norm(d, d, sd=proj_std),
            "proj_bias": zeros(d),
        },
        "ln2": {"scale": ones(d), "bias": zeros(d)},
        "mlp": {
            "fc_kernel": norm(d, 4 * d),
            "fc_bias": zeros(4 * d),
            "proj_kernel": norm(4 * d, d, sd=proj_std),
            "proj_bias": zeros(d),
        },
    }


def init_params(config, seed=0):
    """Megatron-style init: normal(0, 0.02), output projections scaled by
    1/sqrt(2*n_layers)."""
    rng = np.random.RandomState(seed)
    std = 0.02
    d, v, s = config.d_model, config.vocab_size, config.max_seq_len
    norm = lambda *shape, sd=std: jnp.asarray(
        rng.randn(*shape) * sd, dtype=config.dtype)
    zeros = lambda *shape: jnp.zeros(shape, dtype=config.dtype)
    ones = lambda *shape: jnp.ones(shape, dtype=config.dtype)

    blocks = [init_block_params(config, rng) for _ in range(config.n_layers)]
    if config.scan_blocks:
        blocks = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    return {
        "wte": norm(v, d),
        "wpe": norm(s, d, sd=std / 2),
        "blocks": blocks,
        "ln_f": {"scale": ones(d), "bias": zeros(d)},
    }


def partition_spec_fn(path, shape):
    """Megatron TP layout on the ``model`` mesh axis. Handles both the
    per-layer list layout and the stacked scan_blocks layout (leading
    (n_layers,) dim -> leading None in the spec)."""
    if path.endswith("wte"):
        return P(MODEL_AXIS, None)               # vocab-parallel embedding
    spec = None
    if "qkv_kernel" in path or "fc_kernel" in path:
        spec = P(None, MODEL_AXIS)               # column parallel
    elif "qkv_bias" in path or "fc_bias" in path:
        spec = P(MODEL_AXIS)
    elif "attn" in path and "proj_kernel" in path:
        spec = P(MODEL_AXIS, None)               # row parallel
    elif "mlp" in path and "proj_kernel" in path:
        spec = P(MODEL_AXIS, None)
    if spec is not None and len(shape) == len(spec) + 1:
        spec = P(None, *spec)                    # stacked layer dim
    return spec                                   # None: LN, wpe, biases


def _layer_norm(x, scale, bias, eps=1e-5):
    from ..ops.transformer.fused_ops import fused_layer_norm
    return fused_layer_norm(x, scale, bias, eps)


def _column_matmul(x, w, config):
    """x @ w at a column-parallel site (qkv/fc): the ring-fused
    allgather-matmul when the engine attached a collective_matmul
    binding, the plain matmul otherwise."""
    if config.collective_matmul is not None:
        from ..parallel.collective_matmul import tp_column_matmul
        return tp_column_matmul(x, w, config.collective_matmul)
    return x @ w


def _row_matmul(x, w, config):
    """x @ w at a row-parallel site (attn proj/fc2): the ring-fused
    matmul-reducescatter when the binding is live (the partial-sum
    reduction hides inside the GEMM; only the consumer's gather stays
    exposed), the plain matmul otherwise."""
    if config.collective_matmul is not None:
        from ..parallel.collective_matmul import tp_row_matmul
        return tp_row_matmul(x, w, config.collective_matmul)
    return x @ w


def _attn_ctx(x, block, config, train):
    """QKV projection + attention mixing -> (b, s, d) context, BEFORE the
    output projection (which lives in _block_rest so the fused and unfused
    paths share one copy of everything downstream of the context)."""
    b, s, d = x.shape
    h, dh = config.n_heads, config.d_head
    with jax.named_scope("attn.proj"):
        qkv = _column_matmul(x, block["qkv_kernel"].astype(x.dtype),
                             config) + block["qkv_bias"].astype(x.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    reshape = lambda t: t.reshape(b, s, h, dh)
    q, k, v = reshape(q), reshape(k), reshape(v)

    from ..ops.transformer.attention import (causal_attention,
                                             causal_attention_fn)
    if config.sparse_attention:
        if config.sequence_parallel:
            raise ValueError(
                "GPT2Config.sparse_attention is incompatible with "
                "sequence_parallel — pick one long-sequence strategy")
        attn = _sparse_attn_fn(config, s)
        perm = lambda t: t.transpose(0, 2, 1, 3)    # (b,s,h,d)->(b,h,s,d)
        ctx = perm(attn(perm(q), perm(k), perm(v), None, None))
        return ctx.reshape(b, s, d)
    if config.sequence_parallel:
        from ..parallel.ring_attention import sequence_parallel_attention
        if config.sp_mesh is None or not hasattr(config.sp_mesh, "shape"):
            raise ValueError(
                "GPT2Config.sequence_parallel={!r} requires sp_mesh to be "
                "the engine's global jax.sharding.Mesh carrying a "
                "'sequence' axis (e.g. build_mesh(data=2, sequence=4))"
                .format(config.sequence_parallel))
        # attn_fn feeds the ulysses impl's local kernel (flash-capable);
        # the ring impl uses its own online-softmax accumulation, so pass
        # None there to keep _make_sharded's jit cache key stable across
        # use_flash_attention values.
        attn_fn = (causal_attention_fn(config.use_flash_attention,
                                       config.flash_attention_backend)
                   if config.sequence_parallel == "ulysses" else None)
        ctx = sequence_parallel_attention(
            q, k, v, config.sp_mesh, impl=config.sequence_parallel,
            attn_fn=attn_fn)
    else:
        # no scope around the flash kernels: an unnamed ``pallas_call``
        # takes its trace event's name from the innermost one
        # (docs/telemetry.md, "Device scopes")
        ctx = causal_attention(q, k, v,
                               use_flash=config.use_flash_attention,
                               backend=config.flash_attention_backend,
                               mesh=config.kernel_mesh)
    return ctx.reshape(b, s, d)


def _mlp(x, block, config, rng, train):
    from ..ops.transformer.fused_ops import fused_bias_gelu
    with jax.named_scope("mlp"):
        h = fused_bias_gelu(
            _column_matmul(x, block["fc_kernel"].astype(x.dtype), config),
            block["fc_bias"].astype(x.dtype))
        out = _row_matmul(h, block["proj_kernel"].astype(x.dtype),
                          config) + block["proj_bias"].astype(x.dtype)
    if train and config.dropout > 0.0 and rng is not None:
        keep = jax.random.bernoulli(rng, 1.0 - config.dropout, out.shape)
        out = jnp.where(keep, out / (1.0 - config.dropout), 0.0)
    return out


def _block(x, block_params, config, rng, train):
    """Unfused block: LN1 + attention context, then the shared
    _block_rest tail (proj/residual/MLP — one copy for both paths)."""
    ln1 = _layer_norm(x, block_params["ln1"]["scale"],
                      block_params["ln1"]["bias"])
    ctx = _attn_ctx(ln1, block_params["attn"], config, train)
    return _block_rest(x, ctx, block_params, config, rng, train)


_SPARSE_ATTN_CACHE = {}          # (config key) -> SparseSelfAttention
_SPARSE_ATTN_CACHE_MAX = 4       # module instances hold layout + packed
                                 # index arrays (~tens of MB at 64k), so
                                 # the cache is bounded LRU-style


def _sparse_attn_fn(config, seq):
    """Cached block-sparse attention for (config, seq), built on the
    module-level SparseSelfAttention (one shared implementation of
    layout construction, seq%block validation, cpu-interpret fallback
    and per-seq kernel caching). The layout is trace-time static, so a
    stable module instance per sparsity config keeps jit cache keys
    stable across blocks/steps."""
    from ..ops.sparse_attention import SparseSelfAttention
    from ..ops.sparse_attention.sparsity_config import (
        sparsity_config_from_dict)
    key = (tuple(sorted((k, str(v))
                        for k, v in dict(config.sparse_attention).items())),
           config.n_heads)
    sa = _SPARSE_ATTN_CACHE.pop(key, None)
    if sa is None or sa.max_seq_length < seq:
        sa = SparseSelfAttention(
            sparsity_config=sparsity_config_from_dict(
                dict(config.sparse_attention), config.n_heads),
            max_seq_length=seq, causal=True)
    _SPARSE_ATTN_CACHE[key] = sa                   # re-insert = LRU touch
    while len(_SPARSE_ATTN_CACHE) > _SPARSE_ATTN_CACHE_MAX:
        _SPARSE_ATTN_CACHE.pop(next(iter(_SPARSE_ATTN_CACHE)))
    return sa._kernel(seq, False, False)


def _use_fused_attn(config):
    """The fused LN+QKV+flash op applies on the plain flash path (the
    sequence-parallel and block-sparse impls own their attention; the
    reference jnp path keeps gradients for CPU tests). Runs compiled on
    TPU; a forced "interpret" backend (flash_attention: pallas off-TPU)
    takes it too, under the Pallas interpreter."""
    if config.sequence_parallel or config.sparse_attention:
        return False
    mesh = config.kernel_mesh
    if mesh is not None and int(mesh.shape.get(MODEL_AXIS, 1)) > 1:
        # the fused op takes the whole (d, 3d) QKV weight; under tensor
        # parallelism the unfused path splits heads over ``model``
        return False
    if config.flash_attention_backend is not None:
        return config.flash_attention_backend in ("pallas", "interpret")
    return (config.use_flash_attention
            and jax.default_backend() == "tpu")


def _block_rest(x, ctx, block_params, config, rng, train):
    """Everything after the attention context: proj + residual + MLP. Split
    out so per-block remat can wrap THIS while the fused attention op stays
    outside (its custom_vjp saves out/lse and recomputes LN+QKV in the
    backward — re-running the flash forward kernel inside the remat rebuild
    is the single biggest avoidable cost at bench shapes)."""
    r1, r2 = (None, None) if rng is None else jax.random.split(rng)
    attn = block_params["attn"]
    with jax.named_scope("attn.proj"):
        out = _row_matmul(ctx, attn["proj_kernel"].astype(x.dtype),
                          config) + attn["proj_bias"].astype(x.dtype)
    if train and config.dropout > 0.0 and r1 is not None:
        keep = jax.random.bernoulli(r1, 1.0 - config.dropout, out.shape)
        out = jnp.where(keep, out / (1.0 - config.dropout), 0.0)
    x = x + out
    ln2 = _layer_norm(x, block_params["ln2"]["scale"],
                      block_params["ln2"]["bias"])
    x = x + _mlp(ln2, block_params["mlp"], config, r2, train)
    return x


def _fused_attn_ctx(x, block_params, config):
    from ..ops.transformer.attention import fused_causal_attention
    # block sizes resolve by width inside the op (auto_blocks)
    return fused_causal_attention(
        x, block_params["ln1"]["scale"], block_params["ln1"]["bias"],
        block_params["attn"]["qkv_kernel"],
        block_params["attn"]["qkv_bias"], config.n_heads,
        interpret=(config.flash_attention_backend == "interpret"),
        mesh=config.kernel_mesh)


def _qkv_rows(x, block):
    """Shared QKV projection for the cached (serving) attention paths:
    -> q, k, v (b, s, h * dh), one packed row a token: what the paged
    pool holds and ``write_tokens`` takes."""
    with jax.named_scope("attn.proj"):
        qkv = x @ block["qkv_kernel"].astype(x.dtype) + \
            block["qkv_bias"].astype(x.dtype)
    return jnp.split(qkv, 3, axis=-1)


def _attend_cache_rows(q, k_rows, v_rows, positions, dh, valid_lens=None):
    """Absolute-position causal attention of ``s`` new queries over the
    full per-slot cache rows (b, h, S, dh). The ``k_pos <= q_pos`` mask
    makes every entry past a slot's live length unreachable — stale K/V
    from slot/page reuse and padded/garbage writes never contribute
    (NaN-poison pinned by tests/unit/test_serving.py). Shared verbatim
    by the paged read's XLA path and the model drafter's contiguous
    cache. ``valid_lens`` (b,) is how many of the ``s`` input tokens
    are real per row (default: all — the drafter's padded-bucket write
    overwrites the whole span)."""
    s = q.shape[1]
    S = k_rows.shape[2]
    qf = q.astype(jnp.float32) * (1.0 / math.sqrt(dh))
    scores = jnp.einsum("bqhd,bhkd->bhqk", qf, k_rows.astype(jnp.float32))
    k_pos = jnp.arange(S)[None, None, None, :]
    q_pos = (positions[:, None] + jnp.arange(s)[None, :])[:, None, :, None]
    scores = jnp.where(k_pos <= q_pos, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    # Zero V beyond the LIVE window — the last REAL token's position,
    # not the padded bucket width: paged prefill redirects pad writes
    # to the garbage page, so the row's own tail inside the bucket span
    # keeps recycled-page content. Those lanes carry softmax weight
    # exactly 0.0 for every real query, but 0 * NaN = NaN — non-finite
    # stale V would contaminate the weighted sum despite the mask.
    # Reachable positions are untouched, so finite-garbage numerics are
    # bitwise unchanged (the K side needs no such guard: jnp.where
    # REPLACES masked scores, it does not multiply them).
    live = (positions + (valid_lens if valid_lens is not None else s) - 1)
    live_v = jnp.arange(S)[None, :] <= live[:, None]
    v_rows = jnp.where(live_v[:, None, :, None], v_rows, 0)
    ctx = jnp.einsum("bhqk,bhkd->bqhd", probs, v_rows.astype(jnp.float32))
    return ctx


def _cached_attn_ctx(x, block, config, k_cache, v_cache, layer_idx,
                     positions):
    """Incremental attention against the model drafter's contiguous
    cache (inference/speculative.py: no engine serves from it; the
    serving path is :func:`_paged_attn_ctx`).

    ``x`` is the LN'd input for ``s`` NEW tokens per slot (batch row i IS
    cache slot i); the new K/V are written into the cache at
    ``positions[i] .. positions[i]+s`` and the query attends over the whole
    cache row under the absolute-position causal mask ``k_pos <= q_pos``
    (stale entries past a slot's live length are masked out, so slot reuse
    needs no explicit cache clearing). One code path serves prefill
    (s = bucket, positions = chunk start), decode (s = 1, positions =
    length) and speculative verify (s = k+1, positions = length).
    Returns ``(ctx, k_cache, v_cache)`` — caches are functionally updated.
    """
    b, s, d = x.shape
    h, dh = config.n_heads, config.d_head
    q, k, v = _qkv_rows(x, block)
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, s, h, dh).transpose(0, 2, 1, 3)     # (b, h, s, dh)
    v = v.reshape(b, s, h, dh).transpose(0, 2, 1, 3)

    def write_row(row, new, pos):
        # row (h, S, dh), new (h, s, dh): in-place update at seq offset pos
        return jax.lax.dynamic_update_slice(row, new, (0, pos, 0))

    with jax.named_scope("kv.write"):
        k_rows = jax.vmap(write_row)(k_cache[:, layer_idx],
                                     k.astype(k_cache.dtype), positions)
        v_rows = jax.vmap(write_row)(v_cache[:, layer_idx],
                                     v.astype(v_cache.dtype), positions)
        k_cache = k_cache.at[:, layer_idx].set(k_rows)
        v_cache = v_cache.at[:, layer_idx].set(v_rows)
    ctx = _attend_cache_rows(q, k_rows, v_rows, positions, dh)
    return ctx.astype(x.dtype).reshape(b, s, d), k_cache, v_cache


def _gather_pages(cache, page_tables, layer_idx):
    """Layer ``layer_idx`` of the rows' physical pages, straight from
    the 4-D pool: ``(pages, layers, page_size, heads * d_head)``
    indexed by ``page_tables`` (b, max_pages) -> ``(b, max_pages,
    page_size, heads * d_head)``. ONE gather on (page, layer), so only
    the rows' own pages are read. Slicing the layer out first
    (``cache[:, layer_idx]``, a strided slice) makes XLA copy that
    layer's whole slab before every gather: 279 MB a layer and cache
    at 8,500 pages of GPT-2 medium, for 2 MB of pages
    (tests/unit/test_tpu_compile.py pins the compiled programs)."""
    return cache[page_tables, layer_idx]


def _paged_attn_ctx(x, block, config, k_cache, v_cache, layer_idx,
                    positions, page_tables, valid_lens, page_size):
    """Incremental attention against the PAGED KV cache.

    The cache is a global pool ``(pages, layers, page_size, heads *
    d_head)`` — heads packed in the lane-aligned minor dimension
    (inference/kv_cache.py); ``page_tables`` (b, max_pages) int32 maps
    each slot's logical page j to a physical page (entry 0 = the
    reserved garbage page). The new keys and values are written first,
    by ``kv_cache.write_tokens`` (the masked write and its contract: a
    bucket-padded prefill can never touch another sequence's pages),
    which takes the projection's packed rows as they are.
    Reads: the default "xla" path
    gathers the slot's full logical window back into contiguous (b, h,
    max_pages*page_size, d_head) rows and runs the masked attention
    of :func:`_attend_cache_rows` over them — the values a contiguous
    cache would hold, in the same order. That
    gather touches the rows' own pages of this layer and nothing else
    of the pool (:func:`_gather_pages`); it is the CPU path, the oracle
    and the drafter's read, and a chunk's read on a mesh. With
    ``config.paged_attention_kernel == "pallas"`` the read side is a
    kernel, picked as the write picks its own (``kv_cache.write_path``):
    a launch that wrote rows (a decode or verify step) runs the
    ops/pallas/paged_attention page walk (a block of pages fetched and
    every head folded a loop turn), one that wrote pages (a prompt
    chunk, wherever it starts) ops/pallas/chunk_attention (tiles of
    queries against blocks of keys: the causal triangle's upper half and
    the bucket's padding are not visited; q goes in and ctx comes out as
    packed rows, nothing is transposed). Both: K, V and the softmax
    weights on the MXU in the pool's dtype, the softmax in float32 (a
    running one over blocks; plain over GPT-2's one-block table), the
    same masking contract, ctx within 1e-5 of the gather path under
    a float32 pool, greedy streams byte-identical
    (docs/pallas_kernels.md). The chunk kernel has no ``shard_map``
    wrapper: on a mesh the prefill family keeps the gather
    (``GPT2Decoder.prefill_config``) and a verify step of a page or more
    the walk. The WRITE is shared by every path, so the cache bits never
    diverge.
    """
    b, s, d = x.shape
    h, dh = config.n_heads, config.d_head
    max_pages = page_tables.shape[1]
    q, k, v = _qkv_rows(x, block)
    k_cache, v_cache = write_tokens(
        (k_cache, v_cache), (k, v), layer_idx, page_tables, positions,
        valid_lens, page_size, mesh=config.kernel_mesh)
    q = q.reshape(b, s, h, dh)

    # a prompt chunk's read or a decode step's, as the write tells them
    # apart (docs/telemetry.md, "Device scopes")
    with jax.named_scope(read_scope(s, page_size)):
        if config.paged_attention_kernel != "pallas":
            def rows_of(cache):
                # (P, L, ps, h*dh) --gather--> (b, max_pages, ps, h*dh)
                # -> contiguous logical rows (b, h, max_pages*ps, dh)
                gathered = _gather_pages(cache, page_tables, layer_idx)
                return gathered.reshape(
                    b, max_pages * page_size, -1, dh).transpose(0, 2, 1, 3)

            ctx = _attend_cache_rows(q, rows_of(k_cache), rows_of(v_cache),
                                     positions, dh, valid_lens=valid_lens)
        elif write_path(s, page_size) == "pages" \
                and config.kernel_mesh is None:
            from ..ops.pallas.chunk_attention import chunk_attention
            ctx = chunk_attention(q, k_cache, v_cache, layer_idx,
                                  page_tables, positions, valid_lens,
                                  page_size, out_dtype=x.dtype)
        else:
            from ..ops.pallas.paged_attention import paged_attention
            ctx = paged_attention(q, k_cache, v_cache, page_tables,
                                  positions, valid_lens,
                                  layer_idx=layer_idx, page_size=page_size,
                                  mesh=config.kernel_mesh)
    return ctx.astype(x.dtype).reshape(b, s, d), k_cache, v_cache


def _forward_hidden_cached(params, input_ids, config, cache, positions,
                           page_tables=None, valid_lens=None,
                           page_size=None):
    """Cache-threaded variant of :func:`forward_hidden` for serving.

    ``cache`` is ``(k, v)``: the paged pool (pages, layers, page_size,
    heads * d_head) indexed per slot through ``page_tables`` (b,
    max_pages) with ``valid_lens`` (b,) masking padded writes
    (inference/kv_cache.py) — what every engine passes; without
    ``page_tables``, the model drafter's contiguous cache (slots,
    layers, heads, max_seq, d_head). ``positions``
    (b,) int32 is the absolute position of input_ids[:, 0] per slot.
    Returns ``(hidden, (k, v))``.
    """
    if config.scan_blocks or config.sequence_parallel or \
            config.sparse_attention:
        raise ValueError(
            "KV-cache decode supports the plain dense GPT-2 path only "
            "(scan_blocks / sequence_parallel / sparse_attention must be "
            "off in the inference model config)")
    b, s = input_ids.shape
    k_cache, v_cache = cache
    compute_dtype = params["ln_f"]["scale"].dtype
    with jax.named_scope("embed"):
        tok = jnp.take(params["wte"], input_ids, axis=0)
        pos_ids = positions[:, None] + jnp.arange(s)[None, :]
        pos = jnp.take(params["wpe"], pos_ids, axis=0)
        x = tok.astype(compute_dtype) + pos.astype(compute_dtype)
    for i, bp in enumerate(params["blocks"]):
        ln1 = _layer_norm(x, bp["ln1"]["scale"], bp["ln1"]["bias"])
        if page_tables is not None:
            ctx, k_cache, v_cache = _paged_attn_ctx(
                ln1, bp["attn"], config, k_cache, v_cache, i, positions,
                page_tables, valid_lens, page_size)
        else:
            ctx, k_cache, v_cache = _cached_attn_ctx(
                ln1, bp["attn"], config, k_cache, v_cache, i, positions)
        x = _block_rest(x, ctx, bp, config, rng=None, train=False)
    x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    return x, (k_cache, v_cache)


def make_block_fn(config, train):
    """One transformer block as ``block_fn(x, block_params, rng) -> x``,
    with the config's remat/fused-attention choices applied. Shared by
    the monolithic forward (forward_hidden) and the streamed-offload
    segments (stream_spec_for) so both run identical per-block math.

    "full": recompute everything in bwd (min memory, ~4/3 flops);
    "dots": save matmul outputs, recompute elementwise only — the usual
    MFU sweet spot on TPU (HBM traffic for ln/gelu recompute is cheaper
    than re-running the gemms on the MXU). Under scan the CSE-prevention
    barriers are unnecessary and inhibit fusion."""
    policy = (jax.checkpoint_policies.nothing_saveable
              if config.remat_policy == "full" else
              jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if _use_fused_attn(config):
        # attention runs OUTSIDE the remat region via its own custom_vjp
        # (saves ctx+lse, recomputes LN+QKV in bwd, never re-runs the flash
        # forward); only the proj/MLP remainder is rematerialized, under
        # the same remat_policy as the unfused path.
        rest_fn = partial(_block_rest, config=config, train=train)
        if config.remat:
            rest_fn = jax.checkpoint(rest_fn, policy=policy,
                                     prevent_cse=not config.scan_blocks)
        return lambda x, bp, rng: rest_fn(
            x, _fused_attn_ctx(x, bp, config), bp, rng=rng)
    block_fn = partial(_block, config=config, train=train)
    if config.remat:
        block_fn = jax.checkpoint(block_fn, policy=policy,
                                  prevent_cse=not config.scan_blocks)
    return block_fn


def forward_hidden(params, input_ids, config, rng=None, train=False,
                   cache=None, positions=None, page_tables=None,
                   valid_lens=None, page_size=None):
    """Embedding + transformer stack -> final hidden states.

    With ``cache`` (a ``(k, v)`` KV-cache buffer pair) and ``positions``
    (per-row absolute offset of the first token) the stack runs the
    incremental serving path and returns ``(hidden, cache)`` instead:
    with ``page_tables``/``valid_lens``/``page_size`` over the paged
    pool (see ``_paged_attn_ctx``), as every engine calls it; without
    them over the model drafter's contiguous cache.
    """
    if cache is not None:
        if positions is None:
            positions = jnp.zeros((input_ids.shape[0],), jnp.int32)
        return _forward_hidden_cached(params, input_ids, config, cache,
                                      positions, page_tables=page_tables,
                                      valid_lens=valid_lens,
                                      page_size=page_size)
    b, s = input_ids.shape
    compute_dtype = params["ln_f"]["scale"].dtype
    with jax.named_scope("embed"):
        if config.sparse_embedding_grads:
            from ..ops.sparse_grads import sparse_embedding_lookup
            tok = sparse_embedding_lookup(params["wte"], input_ids,
                                          mesh=config.embedding_grad_mesh)
        else:
            tok = jnp.take(params["wte"], input_ids, axis=0)
        x = tok.astype(compute_dtype) + \
            params["wpe"][:s].astype(compute_dtype)

    block_fn = make_block_fn(config, train)

    if config.scan_blocks:
        n = config.n_layers
        keys = (jax.random.split(rng, n) if rng is not None
                else jnp.zeros((n, 2), dtype=jnp.uint32))

        def scan_body(carry, layer):
            bp, key = layer
            out = block_fn(carry, bp, rng=key if rng is not None else None)
            return out, None

        x, _ = jax.lax.scan(scan_body, x, (params["blocks"], keys))
    else:
        rngs = (jax.random.split(rng, config.n_layers)
                if rng is not None else [None] * config.n_layers)
        for i, bp in enumerate(params["blocks"]):
            x = block_fn(x, bp, rng=rngs[i])
    x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    return x


def causal_lm_cross_entropy(logits, labels):
    """Shifted masked CE shared by the dense and pipeline GPT-2 paths.
    ``labels`` may equal ``input_ids`` (shift happens internally); -100
    positions are masked."""
    shift_logits = logits[:, :-1].astype(jnp.float32)
    shift_labels = labels[:, 1:]
    mask = (shift_labels != -100).astype(jnp.float32)
    safe_labels = jnp.where(shift_labels == -100, 0, shift_labels)
    logp = jax.nn.log_softmax(shift_logits, axis=-1)
    token_ll = jnp.take_along_axis(logp, safe_labels[..., None],
                                   axis=-1)[..., 0]
    return -(token_ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def chunked_causal_lm_loss(hidden, wte, labels, chunk):
    """Shifted masked CE without materializing the full (b, s, V) logits.

    At GPT-2 vocab (50k) the dense fp32 logits are the single largest
    activation (b=32, s=1024 -> 6.6 GB) and the reference's CUDA path never
    holds them either (fused softmax-xent). A lax.scan over sequence chunks
    computes each chunk's logits -> log-softmax -> gathered token ll and
    drops them; jax.checkpoint on the body recomputes chunk logits in the
    backward instead of saving them. Peak logits memory falls by s/chunk.
    """
    b, s, d = hidden.shape
    shift_labels = jnp.concatenate(
        [labels[:, 1:], jnp.full((b, 1), -100, labels.dtype)], axis=1)
    n_chunks = s // chunk
    h = hidden.reshape(b, n_chunks, chunk, d).transpose(1, 0, 2, 3)
    lab = shift_labels.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    wte_c = wte.astype(hidden.dtype)

    def body(carry, xs):
        hc, lc = xs
        logits = (hc @ wte_c.T).astype(jnp.float32)
        mask = (lc != -100)
        safe = jnp.where(mask, lc, 0)
        # lse + one gathered logit instead of log_softmax: the full
        # (rows, V) logp array never materializes (only reductions over
        # the logits survive), halving the chunk's HBM traffic
        m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
        lse = m[..., 0] + jnp.log(
            jnp.exp(logits - m).sum(axis=-1))
        ll = jnp.take_along_axis(logits, safe[..., None],
                                 axis=-1)[..., 0] - lse
        tot, cnt = carry
        return (tot + (ll * mask).sum(),
                cnt + mask.sum().astype(jnp.float32)), None

    with jax.named_scope("head.loss"):
        (tot, cnt), _ = jax.lax.scan(
            jax.checkpoint(body), (jnp.float32(0), jnp.float32(0)),
            (h, lab))
    return -tot / jnp.maximum(cnt, 1.0)


def lm_loss(params, input_ids, labels, config, rng=None, train=True):
    """Causal LM cross-entropy (mean over tokens)."""
    hidden = forward_hidden(params, input_ids, config, rng=rng, train=train)
    chunk = config.loss_chunk
    if chunk and hidden.shape[1] % chunk == 0 and hidden.shape[1] > chunk:
        return chunked_causal_lm_loss(hidden, params["wte"], labels, chunk)
    with jax.named_scope("head.loss"):
        # tied embedding
        logits = hidden @ params["wte"].astype(hidden.dtype).T
        return causal_lm_cross_entropy(logits, labels)


def stream_spec_for(config):
    """:class:`runtime.model.StreamSpec` for GPT-2 — the layer-group
    decomposition the streamed-offload runner (cpu_offload_params)
    drives. Composition equals ``lm_loss`` segment for segment: embed
    (wte gather + wpe add), per-layer ``make_block_fn`` blocks, head
    (ln_f + tied-wte CE). ``wte`` is shared between the embed and head
    segments — ``split`` returns the SAME object in both so the runner
    sums the two gradient contributions."""
    from ..runtime.model import StreamSpec
    if config.sequence_parallel or config.sparse_embedding_grads:
        raise ValueError(
            "streamed parameter offload does not compose with "
            "sequence_parallel or sparse_embedding_grads")

    def split(params):
        blocks = params["blocks"]
        if isinstance(blocks, dict):
            # scan_blocks stacked layout: per-layer views (no copy)
            n = np.shape(jax.tree_util.tree_leaves(blocks)[0])[0]
            blocks = [jax.tree_util.tree_map(lambda t: t[i], blocks)
                      for i in range(n)]
        else:
            blocks = list(blocks)
        return ({"wte": params["wte"], "wpe": params["wpe"]},
                blocks,
                {"ln_f": params["ln_f"], "wte": params["wte"]})

    def embed_apply(embed, batch, rng, train):
        input_ids = batch[0]
        s = input_ids.shape[1]
        compute_dtype = embed["wte"].dtype
        tok = jnp.take(embed["wte"], input_ids, axis=0)
        return tok.astype(compute_dtype) + \
            embed["wpe"][:s].astype(compute_dtype)

    def block_apply(bp, x, rng, train):
        return make_block_fn(config, train)(x, bp, rng=rng)

    def head_apply(head, x, batch, rng, train):
        labels = batch[1]
        x = _layer_norm(x, head["ln_f"]["scale"], head["ln_f"]["bias"])
        chunk = config.loss_chunk
        if chunk and x.shape[1] % chunk == 0 and x.shape[1] > chunk:
            return chunked_causal_lm_loss(x, head["wte"], labels, chunk)
        logits = x @ head["wte"].astype(x.dtype).T
        return causal_lm_cross_entropy(logits, labels)

    return StreamSpec(split, embed_apply, block_apply, head_apply)


def profile_spec(config, batch_size, seq=None, seed=0):
    """Module-tree spec for the per-module flops profiler
    (profiling/flops_profiler: profile_module_tree/format_module_profile —
    the reference's per-module aggregated table, profiler.py:515-677).
    Each node prices one forward sub-function via XLA cost_analysis.
    ``seq`` should be the ACTUAL training sequence length (attention is
    quadratic in it); defaults to config.max_seq_len."""
    import jax
    # per-module pricing stays on the dense math (cost_analysis cannot
    # attribute flops inside a shard_map'd fused collective-matmul)
    config = dataclasses.replace(config, collective_matmul=None)
    s, d, v, L = (seq or config.max_seq_len, config.d_model,
                  config.vocab_size, config.n_layers)
    dt = jnp.bfloat16
    rng = np.random.RandomState(seed)
    bp = jax.tree_util.tree_map(lambda t: jnp.asarray(t, dt),
                                init_block_params(config, rng))
    wte = jnp.asarray(rng.randn(v, d) * 0.02, dt)
    wpe = jnp.asarray(rng.randn(s, d) * 0.01, dt)
    ln_f = {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)}
    x = jax.ShapeDtypeStruct((batch_size, s, d), dt)
    ids = jax.ShapeDtypeStruct((batch_size, s), jnp.int32)

    def embed(ids):
        return jnp.take(wte, ids, axis=0) + wpe[None]

    def attn(xv):
        ln1 = _layer_norm(xv, bp["ln1"]["scale"], bp["ln1"]["bias"])
        # jnp reference attention: cost_analysis cannot see inside a
        # pallas custom call, and the dense math IS the flop count
        # (collective_matmul already stripped at function entry)
        cfg_ref = dataclasses.replace(config, use_flash_attention=False,
                                      sequence_parallel=None,
                                      sparse_attention=None)
        ctx = _attn_ctx(ln1, bp["attn"], cfg_ref, train=False)
        return xv + ctx @ bp["attn"]["proj_kernel"] + bp["attn"]["proj_bias"]

    def mlp(xv):
        ln2 = _layer_norm(xv, bp["ln2"]["scale"], bp["ln2"]["bias"])
        return xv + _mlp(ln2, bp["mlp"], config, None, False)

    def block_fn(xv):
        return mlp(attn(xv))

    def head_loss(hidden, labels):
        if config.loss_chunk and s % config.loss_chunk == 0 \
                and s > config.loss_chunk:
            return chunked_causal_lm_loss(hidden, wte, labels,
                                          config.loss_chunk)
        logits = hidden @ wte.T
        return causal_lm_cross_entropy(logits, labels)

    per_block = 12 * d * d + 13 * d
    return {
        "name": "gpt2(fwd, b={} s={})".format(batch_size, s),
        "params": num_params(config),
        "children": [
            {"name": "embedding", "fn": embed, "args": (ids,),
             "params": v * d + s * d},
            {"name": "block", "fn": block_fn, "args": (x,),
             "count": L, "params": per_block,
             "children": [
                 {"name": "attention", "fn": attn, "args": (x,),
                  "params": 4 * d * d + 5 * d},
                 {"name": "mlp", "fn": mlp, "args": (x,),
                  "params": 8 * d * d + 7 * d},
             ]},
            {"name": "final_norm",
             "fn": lambda xv: _layer_norm(xv, ln_f["scale"], ln_f["bias"]),
             "args": (x,), "params": 2 * d},
            {"name": "lm_head+ce", "fn": head_loss, "args": (x, ids),
             "params": 0},
        ],
    }


def make_gpt2_model(config=None, size="gpt2_small", seed=0, **overrides):
    """Build a :class:`deepspeed_tpu.runtime.model.Model` for the engine."""
    from ..runtime.model import Model
    if config is None:
        config = config_for(size, **overrides)
    # the model owns its config: what an engine resolves onto it (flash
    # backend, kernel mesh, collective-matmul binding) never reaches the
    # caller's object, nor another model built from it
    config = dataclasses.replace(config)
    params = init_params(config, seed=seed)

    def apply_fn(params, input_ids, labels, rng=None, train=True):
        return lm_loss(params, input_ids, labels, config, rng=rng, train=train)

    model = Model(apply_fn, params, partition_spec_fn=partition_spec_fn,
                  name="gpt2")
    model.config = config
    model.decoder = GPT2Decoder(config)
    model.bind_mesh = partial(setattr, config, "kernel_mesh")
    model.profile_spec_fn = lambda batch_size, seq=None: profile_spec(
        config, batch_size, seq=seq)
    if not (config.sequence_parallel or config.sparse_embedding_grads):
        # streamed-offload decomposition (cpu_offload_params); the
        # incompatible configs simply don't attach one and the engine
        # rejects the combination loudly
        model.stream_spec = stream_spec_for(config)
    return model


class GPT2Decoder:
    """GPT-2 as ``init_inference()`` sees it (inference/decoder.py):
    every layer keeps keys and values, nothing else: the pages (or the
    slot rows) are the whole of a request's state."""

    recurrent = False

    def __init__(self, config):
        self.config = config

    def cache_spec(self):
        from ..inference.decoder import CacheSpec
        cfg = self.config
        return CacheSpec(kv_layers=cfg.n_layers, kv_heads=cfg.n_heads,
                         d_head=cfg.d_head)

    def serving_config(self, mesh):
        # deterministic, dense path: the cached attention owns masking;
        # flash / scan / SP are training-path levers
        return dataclasses.replace(
            self.config, dropout=0.0, scan_blocks=False,
            sequence_parallel=None, sp_mesh=None, sparse_attention=None,
            sparse_embedding_grads=False, embedding_grad_mesh=None,
            paged_attention_kernel="xla", kernel_mesh=mesh)

    def decode_config(self, config, paged_attention_kernel):
        # the decode and prefill families may read the pages with a
        # Pallas kernel (the page walk, a chunk's chunk_attention:
        # docs/pallas_kernels.md dispatch rules); the serving config
        # keeps "xla" so every oracle comparison stays on the gather path
        return dataclasses.replace(
            config, paged_attention_kernel=paged_attention_kernel)

    def prefill_config(self, config, paged_attention_kernel):
        # a prompt chunk's read as the engine resolved decode's; on a
        # mesh the gather (chunk_attention has no shard_map wrapper)
        return config if config.kernel_mesh is not None else \
            self.decode_config(config, paged_attention_kernel)

    def serving_params(self, params, dtype):
        if self.config.scan_blocks:
            # serving iterates blocks as a python list; unstack the
            # scan-trained (L, ...) layout once at engine build
            blocks = params["blocks"]
            params = dict(params)
            params["blocks"] = [
                jax.tree_util.tree_map(lambda t, i=i: t[i], blocks)
                for i in range(self.config.n_layers)]

        def cast(x):
            x = jnp.asarray(x)
            return x.astype(dtype) if jnp.issubdtype(x.dtype,
                                                     jnp.floating) else x
        return jax.tree_util.tree_map(cast, params)

    forward_hidden = staticmethod(forward_hidden)

    @staticmethod
    def logits(params, hidden):
        # tied-embedding LM head (lm_loss's convention)
        with jax.named_scope("head"):
            return hidden @ params["wte"].astype(hidden.dtype).T


def num_params(config):
    d, v, s, L = (config.d_model, config.vocab_size, config.max_seq_len,
                  config.n_layers)
    per_block = 12 * d * d + 13 * d
    return v * d + s * d + L * per_block + 2 * d
