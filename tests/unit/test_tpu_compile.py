"""The chip's compiler on the main path's kernels, without the chip.

libtpu compiles for a TPU that is DESCRIBED (``v5e:2x2``), not attached:
each case lowers a kernel at real GPT-2 widths with ``interpret=False``
and compiles it, so a Mosaic refusal (a slice not aligned to the tiling,
a scoped-VMEM overflow, an unsupported vector cast) fails a tier-1 test
instead of a chip run. Nothing executes — a compile that passes says
nothing about results or times.

All of these live in THIS ONE file, and the topology is described inside
module-scoped non-autouse fixtures: only the xdist worker that is handed
this file loads libtpu, and every worker collects the same tests
(/opt/skills/guides/on-chip-measurement, section 2). Nothing at import,
in a ``skipif`` or in a ``parametrize`` argument touches the topology or
the backend.
"""
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

pytestmark = pytest.mark.pallas

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# (batch, seq, heads, d_head) at the widths the repo trains
GPT2_MEDIUM = (4, 1024, 16, 64)
GPT2_XL = (2, 1024, 25, 64)
# ... and at the batch the benchmark's training cell runs (micro-batch 20)
TRAIN_CELL = (20, 1024, 16, 64)


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("model",))


@pytest.fixture(scope="module", autouse=False)
def no_persistent_cache():
    """An executable compiled for a described chip is written to the
    persistent cache but cannot be read back without one (the next
    compile warns) — keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Lower ``fn`` at ``(shape, dtype)`` args placed by ``sharding`` and
    run the chip's compiler; returns the number of Mosaic kernels in the
    compiled program."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text().count("tpu_custom_call")


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("shape", [GPT2_MEDIUM, GPT2_XL, TRAIN_CELL],
                         ids=["gpt2_medium", "gpt2_xl", "train_cell"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_attention_bshd_compiles(one_chip, no_persistent_cache,
                                       shape, grad):
    """``train_cell`` is the benchmark's training shape, batch 20: at the
    batch of 4 XLA holds the kernels' whole operands in VMEM (``S(1)``),
    and the scoped limit a call meets there is not the one the cell
    meets (a backward at (512, 512) compiles at 4 and fails at 20 inside
    XLA's default 16 MiB)."""
    from deepspeed_tpu.ops.transformer.flash_attention import \
        flash_attention_bshd

    def fwd(q, k, v):
        return flash_attention_bshd(q, k, v, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(F32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    assert _compile(fn, one_chip, *[(shape, BF16)] * 3) >= 1


@pytest.mark.parametrize("shape", [GPT2_MEDIUM, GPT2_XL, TRAIN_CELL],
                         ids=["gpt2_medium", "gpt2_xl", "train_cell"])
def test_fused_ln_qkv_attention_grad_compiles(one_chip,
                                              no_persistent_cache, shape):
    """``train_cell``: the program `gpt2-350m-train.seq1024` runs a layer
    (see `test_flash_attention_bshd_compiles` on why batch 4 is not it)."""
    from deepspeed_tpu.ops.transformer.flash_attention import \
        fused_ln_qkv_attention
    b, s, h, dh = shape
    d = h * dh

    def loss(x, ln_s, ln_b, w, bias):
        return fused_ln_qkv_attention(x, ln_s, ln_b, w, bias, h,
                                      interpret=False).astype(F32).sum()

    n = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), one_chip,
                 ((b, s, d), BF16), ((d,), BF16), ((d,), BF16),
                 ((d, 3 * d), BF16), ((3 * d,), BF16))
    assert n >= 2                       # forward + backward kernels


# ------------------------------------------------------- fused optimizers
_OPT_SHAPES = [(1024, 3072), (50257, 1024), (1024,), (30522, 1024)]


@pytest.mark.parametrize("shape", _OPT_SHAPES, ids=str)
def test_fused_adam_compiles(one_chip, no_persistent_cache, shape):
    from deepspeed_tpu.ops.adam.pallas_adam import fused_adam_shard

    def step(p, g, m, v):
        return fused_adam_shard(p, g, m, v, 1e-4, 0.9, 0.999, 1e-8, 0.01,
                                0.1, 0.001, interpret=False)

    assert _compile(step, one_chip, *[(shape, F32)] * 4) == 1


@pytest.mark.parametrize("shape", _OPT_SHAPES, ids=str)
def test_fused_lamb_compiles(one_chip, no_persistent_cache, shape):
    from deepspeed_tpu.ops.lamb.pallas_lamb import fused_lamb_shard

    def step(p, g, m, v):
        return fused_lamb_shard(p, g, m, v, 1e-4, 0.9, 0.999, 1e-8, 0.01,
                                0.1, 0.001, interpret=False)

    assert _compile(step, one_chip, *[(shape, F32)] * 4) == 1


# ------------------------------------------------------- paged attention
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("width", [1, 5], ids=["decode", "spec_verify"])
def test_paged_attention_compiles_at_d_head_64(one_chip,
                                               no_persistent_cache, dtype,
                                               width):
    """gpt2_medium serving shapes: 16 slots, 24 layers, 1024 usable
    pages of 16 tokens, heads packed in the pool's minor dimension (the
    (.., page, d_head 64) layout was refused: "Slice shape along
    dimension 4 must be aligned to tiling (128), but is 64")."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention
    b, h, dh, ps = 16, 16, 64, 16
    pool = ((1025, 24, ps, h * dh), dtype)
    fn = functools.partial(paged_attention, layer_idx=3, page_size=ps,
                           interpret=False)
    assert _compile(fn, one_chip, ((b, width, h, dh), dtype), pool, pool,
                    ((b, 64), I32), ((b,), I32), ((b,), I32)) == 1


# (slots, pages a row, pages + 1, layers, heads, d_head, pages a block)
# of the decode programs that run ``_kernel``: the two GPT-2 serving
# cells' (64 slots x 768 tokens, 128 slots x 1008 tokens, pages of 16)
# and Olmo-Hybrid's four full layers (64 slots x 3,072 tokens)
_DECODE_CELLS = {"chat": (64, 48, 3073, 24, 16, 64, 32),
                 "docs": (128, 63, 8501, 24, 16, 64, 32),
                 "evals": (64, 192, 4001, 4, 30, 128, 16)}


@pytest.mark.parametrize("width", [1, 5], ids=["decode", "spec_verify"])
@pytest.mark.parametrize("cell", sorted(_DECODE_CELLS))
def test_paged_attention_block_walk_compiles_at_the_cells_shapes(
        one_chip, no_persistent_cache, cell, width):
    """The block walk at the shapes the benchmark runs it at: 16 heads
    of 64 packed in 1,024 bf16 lanes, 24 layers, 32 pages (512 tokens) a
    block, a row of 63 pages not a multiple of it; 30 heads of 128 in
    3,840 lanes, 16 pages a block (7.5 MiB of K and V buffers: the call
    asks for its ``vmem_limit_bytes``)."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        _pages_per_block, paged_attention)
    b, row, pages, layers, h, dh, block = _DECODE_CELLS[cell]
    ps = 16
    assert _pages_per_block(row, ps, h * dh, 2) == block
    pool = ((pages, layers, ps, h * dh), BF16)
    fn = functools.partial(paged_attention, layer_idx=layers - 1,
                           page_size=ps, interpret=False)
    assert _compile(fn, one_chip, ((b, width, h, dh), BF16), pool, pool,
                    ((b, row), I32), ((b,), I32), ((b,), I32)) == 1


# --------------------------------------- paged attention, grouped walk
# cell: slots, query heads, key-value heads, d_head, pages a row, pages
# + 1, layers, window, pages a block: the decode programs that run the
# grouped walk (Mellum's full and sliding layers, LFM2's, Jamba's)
_GROUPED_CELLS = {
    "ide_full": (128, 32, 4, 128, 2048, 70001, 2, None, 32),
    "ide_window": (128, 32, 4, 128, 193, 8577, 6, 1024, 24),
    "extract": (384, 32, 8, 64, 192, 30001, 3, None, 32),
    "rollouts": (384, 20, 1, 128, 192, 27001, 2, None, 32),
}


def _compile_grouped_walk(one_chip, cell, width):
    from deepspeed_tpu.ops.pallas.paged_attention import (
        _grouped_block, paged_attention)
    b, h, kvh, dh, row, pages, layers, window, block = _GROUPED_CELLS[cell]
    assert _grouped_block(row, 16, 1, window) == block

    def fn(q, k_pool, v_pool, page_tables, positions, valid_lens):
        return paged_attention(q, k_pool, v_pool, page_tables, positions,
                               valid_lens, layer_idx=layers - 1,
                               page_size=16, interpret=False, window=window)

    pool = ((pages, layers, 16, kvh * dh), BF16)
    return _compile(fn, one_chip, ((b, width, h, dh), BF16), pool, pool,
                    ((b, row), I32), ((b,), I32), ((b,), I32))


def test_paged_attention_compiles_at_one_kv_head_of_128(
        one_chip, no_persistent_cache):
    """The grouped kernel at AI21-Jamba2-3B's attention layers: 20
    query heads of 128 on one key-value head, the rollouts cell's 384
    slots, a row of 192 pages of 16 tokens; a page is a (16, 128) bf16
    tile, a block 32 of them."""
    assert _compile_grouped_walk(one_chip, "rollouts", 1) == 1


@pytest.mark.parametrize("width", [1, 2], ids=["decode", "verify_width_2"])
@pytest.mark.parametrize("cell", sorted(_GROUPED_CELLS))
def test_grouped_walk_compiles_at_the_cells_shapes(
        one_chip, no_persistent_cache, cell, width):
    """The grouped walk with bfloat16 pools at each cell's slots, table
    width and window: a block of 512 tokens (384 with Mellum's window:
    its 65 pages in three even turns) fetched whole into a double
    buffer, the slot's row of the table and the next slot's in scalar
    memory, every head's scores one matmul over the packed lanes (at
    LFM2's heads of 64 no half-tile slice of a page)."""
    assert _compile_grouped_walk(one_chip, cell, width) == 1


# ------------------------------------------------- kernels on a mesh
def _mesh_compile(fn, mesh, *args):
    """``args``: (shape, dtype, PartitionSpec) placed on ``mesh``."""
    sds = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, spec))
           for s, d, spec in args]
    return jax.jit(fn).lower(*sds).compile().as_text() \
        .count("tpu_custom_call")


@pytest.mark.parametrize("axes", [(("data", 4),),
                                  (("data", 2), ("model", 2))],
                         ids=["data4", "data2_model2"])
def test_flash_grad_compiles_on_a_mesh(topo, no_persistent_cache, axes):
    """GSPMD refuses a bare Mosaic kernel in a program of several
    devices ("Mosaic kernels cannot be automatically partitioned");
    handed the mesh, the dispatch layer shard_maps it. data=4 is the
    ZeRO path (fused op), data x model the tensor-parallel one."""
    from deepspeed_tpu.ops.transformer.attention import (
        causal_attention, fused_causal_attention)
    names, dims = zip(*axes)
    mesh = Mesh(np.array(topo.devices).reshape(dims), names)
    b, s, h, dh = GPT2_MEDIUM
    d = h * dh
    if "model" in names:
        def loss(q, k, v):
            return causal_attention(q, k, v, backend="pallas",
                                    mesh=mesh).astype(F32).sum()
        spec = P("data", None, "model", None)
        n = _mesh_compile(jax.grad(loss, argnums=(0, 1, 2)), mesh,
                          *[((b, s, h, dh), BF16, spec)] * 3)
    else:
        def loss(x, ln_s, ln_b, w, bias):
            return fused_causal_attention(x, ln_s, ln_b, w, bias, h,
                                          mesh=mesh).astype(F32).sum()
        n = _mesh_compile(
            jax.grad(loss, argnums=(0, 1, 2, 3, 4)), mesh,
            ((b, s, d), BF16, P("data")), ((d,), BF16, P()),
            ((d,), BF16, P()), ((d, 3 * d), BF16, P()),
            ((3 * d,), BF16, P()))
    assert n >= 2


def test_paged_attention_compiles_on_a_tensor_parallel_mesh(
        four_chips, no_persistent_cache):
    """Heads split over ``model`` like the pool's packed minor dim: four
    of gpt2_medium's 16 heads per chip (a 256-lane local row)."""
    from deepspeed_tpu.inference.kv_cache import PAGED_KV_CACHE_SPEC
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention
    b, h, dh, ps = 16, 16, 64, 16
    fn = functools.partial(paged_attention, layer_idx=3, page_size=ps,
                           interpret=False, mesh=four_chips)
    pool = ((1025, 24, ps, h * dh), BF16, PAGED_KV_CACHE_SPEC)
    assert _mesh_compile(
        fn, four_chips, ((b, 1, h, dh), BF16, P(None, None, "model")),
        pool, pool, ((b, 64), I32, P()), ((b,), I32, P()),
        ((b,), I32, P())) == 1


@pytest.mark.parametrize("width", [1, 5], ids=["decode", "spec_verify"])
@pytest.mark.parametrize("cell", ["chat", "docs"])
def test_paged_attention_block_walk_compiles_on_a_tensor_parallel_mesh(
        four_chips, no_persistent_cache, cell, width):
    """The GPT-2 cells' shapes with the heads split four ways: a shard's
    4 heads are 256 lanes, its block the same 32 pages (512 tokens: the
    cap in tokens binds, not the buffers' bytes) and its score rows 4 a
    query."""
    from deepspeed_tpu.inference.kv_cache import PAGED_KV_CACHE_SPEC
    from deepspeed_tpu.ops.pallas.paged_attention import (
        _pages_per_block, paged_attention)
    b, row, pages, layers, h, dh, _ = _DECODE_CELLS[cell]
    ps = 16
    assert _pages_per_block(row, ps, h * dh // 4, 2) == 32
    fn = functools.partial(paged_attention, layer_idx=layers - 1,
                           page_size=ps, interpret=False, mesh=four_chips)
    pool = ((pages, layers, ps, h * dh), BF16, PAGED_KV_CACHE_SPEC)
    assert _mesh_compile(
        fn, four_chips, ((b, width, h, dh), BF16, P(None, None, "model")),
        pool, pool, ((b, row), I32, P()), ((b,), I32, P()),
        ((b,), I32, P())) == 1


# ----------------------------------------------- the page write (prefill)
# a cell's pool (pages + 1, layers, lanes), how many pools, its largest
# prefill bucket and a row's pages (benchmark/configs/*.json)
_WRITE_CELLS = {
    "chat": ((3073, 24, 1024), 2, 512, 64),
    "docs": ((8501, 24, 1024), 2, 1024, 64),
    "rollouts": ((18001, 2, 128), 2, 512, 192),
    "extract": ((30001, 3, 512), 2, 256, 192),
    "reasoning": ((75001, 5, 640), 1, 512, 512),
}


def _write_args(cell, sharding_of):
    """(pools, news, page_tables, positions, valid_lens) of one slot's
    largest chunk in ``cell``; ``sharding_of(lanes_axis)`` places an
    array whose dimension ``lanes_axis`` is the lanes (None: none)."""
    (pages, layers, lanes), n_pools, bucket, row = _WRITE_CELLS[cell]

    def sds(shape, dtype, lanes_axis=None):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=sharding_of(lanes_axis))

    return ((sds((pages, layers, 16, lanes), BF16, 3),) * n_pools,
            (sds((1, bucket, lanes), BF16, 2),) * n_pools,
            sds((1, row), I32), sds((1,), I32), sds((1,), I32))


@pytest.mark.parametrize("cell", sorted(_WRITE_CELLS))
def test_kv_page_write_compiles_at_the_cells_shapes(
        one_chip, no_persistent_cache, monkeypatch, cell):
    """``kv_cache.write_tokens`` of a prefill chunk at every serving
    cell's pool and largest bucket: one kernel for the layer's pools
    (pages of 1,024, 128, 512 and 640 bf16 lanes; the select over the
    two partly filled pages included), the pools aliased input to
    output and never copied."""
    from deepspeed_tpu.inference import kv_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pools, *rest = _write_args(cell, lambda lanes_axis: one_chip)
    layer = pools[0].shape[1] - 1
    compiled = jax.jit(
        lambda pools, *rest: kv_cache.write_tokens(pools, *rest[:1], layer,
                                                   *rest[1:], 16),
        donate_argnums=0).lower(pools, *rest).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "kv_page_write" in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == sum(
        int(np.prod(p.shape)) * 2 for p in pools)
    assert memory.temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("cell", ["chat", "docs"])
def test_kv_page_write_compiles_on_a_tensor_parallel_mesh(
        four_chips, no_persistent_cache, monkeypatch, cell):
    """The lanes split over ``model`` like the pool's (a shard moves
    pages of 256 lanes): the kernel under its shard_map, no collective
    beside it."""
    from deepspeed_tpu.inference import kv_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sharding_of(lanes_axis):
        spec = [None] * (lanes_axis or 0) + \
            (["model"] if lanes_axis else [])
        return NamedSharding(four_chips, P(*spec))

    pools, *rest = _write_args(cell, sharding_of)
    compiled = jax.jit(
        lambda pools, *rest: kv_cache.write_tokens(
            pools, *rest[:1], 3, *rest[1:], 16, mesh=four_chips),
        donate_argnums=0).lower(pools, *rest).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "kv_page_write" in text
    assert not re.search(r"all-gather|all-to-all|all-reduce|"
                         r"collective-permute", text)
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        int(np.prod(p.shape)) * 2 // 4 for p in pools)


# ------------------------------------- the serving engine's own programs
def _page_writes(text, program, pool_shape):
    """How a serving program writes its new cache rows
    (``kv_cache.write_tokens``), read off its compiled text: the number
    of ``kv_page_write`` kernels. A prefill program holds no scatter on
    the pool, neither in the pool's own shape nor over the pool
    flattened to rows, where XLA put a bucket's row updates (docs'
    ``fusion bf16[3264384,1024]``, 1.43 s of a 4.94 s window, ledger
    PR 38): whole pages move by DMA. A decode or verify program keeps
    its row scatter and holds no page write."""
    pages, layers, ps, lanes = pool_shape
    shapes = ["bf16[{},{},{},{}]".format(*pool_shape),
              "bf16[{},{}]".format(pages * layers * ps, lanes)]
    scatters = [line.strip()[:160] for line in text.splitlines()
                if " scatter(" in line and any(s in line for s in shapes)]
    writes = sum("tpu_custom_call" in line and "kv_page_write" in line
                 for line in text.splitlines())
    if program == "prefill":
        assert not scatters[:3]
        assert shapes[1] not in text
    else:
        assert scatters and not writes
    return writes


@pytest.fixture(scope="module")
def serving_engine():
    """Two layers at gpt2_medium widths behind ``init_inference`` on the
    CPU; the pool is tiny here — the programs are lowered at the
    benchmark cells' pool shapes, which are arguments."""
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import gpt2
    cfg = gpt2.GPT2Config(vocab_size=50257, max_seq_len=1024, n_layers=2,
                          n_heads=16, d_model=1024,
                          use_flash_attention=False, remat=False)
    return deepspeed.init_inference(
        model=gpt2.make_gpt2_model(config=cfg, seed=0),
        config={"inference": {
            "max_batch_size": 2, "dtype": "bf16",
            "kv_block_size": 16, "num_pages": 64, "greedy": True,
            "paged_attention_kernel": "pallas",
            "prefill_buckets": [128, 1024]}})


# (pages, a prefill bucket, slots) of the two serving cells (benchmark/
# configs/gpt2-350m-serve.json, -serve-batch.json); a row of either has
# the model's 1024 positions / 16 = 64 pages, as the fixture's engine
_SERVING_CELLS = {"chat": (3072, 128, 64), "docs": (8500, 1024, 128)}


@pytest.mark.parametrize("cell", sorted(_SERVING_CELLS))
@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_serving_programs_copy_no_layer_slab(
        one_chip, no_persistent_cache, serving_engine, monkeypatch,
        program, cell):
    """``jit_prefill`` (one row: the page write and ``chunk_attention``
    a layer) and ``jit_decode`` (the page walk, every slot) at the
    cells' pool shapes: no instruction makes a ``[pages + 1, page, heads
    * d_head]`` array — a layer's whole slab of the pool, 101 MB in chat
    and 279 MB in docs, which prefill copied twice a layer while its
    read sliced the layer out before gathering (a row's 64 pages, 2 MB,
    were then taken from the copy; 42-44% of docs' busy device time,
    ledger PR 26) — both donated pools come back in place, and the new
    rows are written as :func:`_page_writes` says. Since PR 57 a chunk
    reads its keys in the kernel: the float32 scores of the bucket
    against the row's 1,024 keys (``f32[16,1024,1024]`` in docs, 67 MB
    a layer), the gather of the row's 64 pages and the transposes
    between the packed rows and ``[1, bucket, 16, 64]`` are gone from
    ``jit_prefill``."""
    eng = serving_engine
    (pages, bucket, slots), row = _SERVING_CELLS[cell], eng.max_pages
    cfg = eng.model_config
    layers, ps, hd = cfg.n_layers, eng.page_size, cfg.n_heads * cfg.d_head
    # the kernel dispatch asks the backend whether to interpret
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                    eng.params)
    pool = sds((pages + 1, layers, ps, hd), BF16)
    rng = jax.random.PRNGKey(0)
    tail = (sds(rng.shape, rng.dtype), sds((), F32), sds((), F32))
    if program == "prefill":
        fn = eng._get_prefill_fn(bucket, True, 0)
        args = (sds((1, bucket), I32), sds((row,), I32), sds((), I32),
                sds((), I32))
    else:
        fn = eng._get_decode_fn(True, 0)
        args = (sds((slots, 1), I32), sds((slots,), I32),
                sds((slots, row), I32))
    text = fn.lower(params, pool, pool, *args, *tail).compile().as_text()

    assert text.startswith("HloModule jit_" + program)
    slab = "bf16[{},{},{}]".format(pages + 1, ps, hd)
    assert not [line.strip()[:160] for line in text.splitlines()
                if slab in line][:3]
    # decode: the page walk a layer; prefill: the page write (both
    # pools in one call) and the chunk's read
    reads = len(re.findall(
        r"%chunk_attention(?:\.\d+)? = .*tpu_custom_call", text))
    assert (text.count("tpu_custom_call"), reads) == \
        ((2 * layers, layers) if program == "prefill" else (layers, 0))
    if program == "prefill":
        gone = ["f32[{},{},{}]".format(cfg.n_heads, bucket, row * ps),
                "bf16[{},{},{},{}]".format(1, row, ps, hd),
                "bf16[{},{},{}]".format(row, ps, hd),
                "bf16[1,{},{},{}]".format(bucket, cfg.n_heads, cfg.d_head),
                "bf16[1,{},{},{}]".format(cfg.n_heads, bucket, cfg.d_head)]
        assert not [line.strip()[:160] for line in text.splitlines()
                    if any(shape in line for shape in gone)][:3]
    assert _page_writes(text, program, (pages + 1, layers, ps, hd)) == \
        (layers if program == "prefill" else 0)
    aliased = {int(out): int(arg) for out, arg in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
        text.split("\n", 1)[0])}
    n_params = len(jax.tree_util.tree_leaves(params))
    assert aliased == {0: n_params, 1: n_params + 1}


# ------------------------------------------------------- compiler params
# ------------------------------------------------- Jamba: scan, step, pools
JAMBA = dict(d_inner=5120, d_state=16, mamba_layers=26)


@pytest.mark.parametrize("chunk", [128, 256, 512])
def test_mamba_scan_compiles_at_the_published_widths(
        one_chip, no_persistent_cache, chunk):
    from deepspeed_tpu.ops.pallas.mamba import mamba_scan
    di, n = JAMBA["d_inner"], JAMBA["d_state"]

    def fn(x, dt, B, C, A, h0, valid):
        return mamba_scan(x, dt, B, C, A, h0, valid, interpret=False)

    assert _compile(fn, one_chip, ((chunk, di), F32), ((chunk, di), F32),
                    ((chunk, n), F32), ((chunk, n), F32), ((n, di), F32),
                    ((n, di), F32), ((), I32)) == 1


@pytest.mark.parametrize("state", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("slots", [256, 512])
def test_mamba_step_compiles_in_place_on_the_pool(
        one_chip, no_persistent_cache, slots, state):
    from deepspeed_tpu.ops.pallas.mamba import mamba_step
    di, n, layers = (JAMBA["d_inner"], JAMBA["d_state"],
                     JAMBA["mamba_layers"])

    def fn(pool, x, dt, B, C, A):
        return mamba_step(pool, 3, x, dt, B, C, A, interpret=False)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((layers, slots, n, di), state), ((slots, di), F32),
        ((slots, di), F32), ((slots, n), F32), ((slots, n), F32),
        ((n, di), F32))]
    compiled = jax.jit(fn, donate_argnums=0).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    # the pool comes back in place, and no layer's slab is made of it
    assert re.search(r"\{1\}: \(0, \{\}, (?:may|must)-alias\)",
                     text.split("\n", 1)[0])
    slab = "{}[{},{},{}]".format("f32" if state == F32 else "bf16",
                                 slots, n, di)
    assert slab not in text


@pytest.fixture(scope="module")
def jamba_engine():
    """A tiny Jamba engine on the CPU whose programs are lowered at the
    published widths: the model config, the weights and both pools are
    what the programs close over or take as arguments."""
    import json
    import os
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import jamba
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "jamba2-3b-serve.json")) as f:
        cell = json.load(f)
    tiny = dict(cell["model"], hidden_size=64, intermediate_size=128,
                num_attention_heads=4, vocab_size=128, mamba_dt_rank=8,
                num_hidden_layers=8)
    eng = deepspeed.init_inference(
        model=jamba.make_jamba_model(jamba.config_from_hf(tiny), seed=0),
        config={"inference": dict(cell["inference"], max_batch_size=2,
                                  num_pages=256,
                                  paged_attention_kernel="pallas")})
    eng.model_config = jamba.config_from_hf(
        cell["model"], scan_kernel="pallas",
        state_dtype=jnp.dtype(cell["precision_state"]))
    return eng, cell


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_jamba_programs_copy_no_state_slab_and_alias_both_pools(
        one_chip, no_persistent_cache, jamba_engine, monkeypatch, program):
    """``jit_prefill`` (the largest bucket, one slot) and ``jit_decode``
    (every slot) of AI21-Jamba2-3B at the cell's pool shapes: no
    instruction makes an array of a layer's whole SSM-state slab
    (``[slots, 16, 5120]``, 84 MB of float32 at 256 slots) nor, in
    prefill, of its convolution-tail slab or of a layer's pages; the
    page pool AND the state pool, four donated buffers, come back in
    place; and each Mamba layer runs one kernel."""
    from deepspeed_tpu.models import jamba
    eng, cell = jamba_engine
    cfg = eng.model_config
    inference = cell["inference"]
    slots, pages = inference["max_batch_size"], inference["num_pages"]
    bucket, ps = inference["prefill_buckets"][-1], eng.page_size
    row = inference["max_seq_len"] // ps
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(lambda: jamba.JambaDecoder(cfg).serving_params(
        jamba.init_params(cfg, 0), BF16))
    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), params)
    n_mamba, n_attn = len(cfg.mamba_layers), len(cfg.attention_layers)
    pool = sds((pages + 1, n_attn, ps, cfg.n_kv_heads * cfg.d_head), BF16)
    conv = sds((n_mamba, slots, (cfg.d_conv - 1) * cfg.d_inner), BF16)
    ssm = sds((n_mamba, slots, cfg.d_state, cfg.d_inner), cfg.state_dtype)
    rng = jax.random.PRNGKey(0)
    tail = (sds(rng.shape, rng.dtype), sds((), F32), sds((), F32))
    if program == "prefill":
        fn = eng._get_prefill_fn(bucket, True, 0)
        args = (sds((), I32), sds((1, bucket), I32), sds((row,), I32),
                sds((), I32), sds((), I32))
    else:
        fn = eng._get_decode_fn(True, 0)
        args = (sds((slots,), jnp.bool_), sds((slots, 1), I32),
                sds((slots,), I32), sds((slots, row), I32))
    compiled = fn.lower(params, pool, pool, conv, ssm, *args,
                        *tail).compile()
    text = compiled.as_text()

    assert text.startswith("HloModule jit_" + program)
    state_name = {"float32": "f32", "bfloat16": "bf16"}[
        jnp.dtype(cfg.state_dtype).name]
    slabs = ["{}[{},{},{}]".format(state_name, slots, cfg.d_state,
                                   cfg.d_inner)]
    if program == "prefill":
        # decode reads and writes every slot's tail: there the layer's
        # region IS the operand, 7.9 MB, and no copy made to slice it
        slabs += ["bf16[{},{}]".format(slots,
                                       (cfg.d_conv - 1) * cfg.d_inner)]
    # and the whole pools are never copied or re-tiled
    slabs += ["bf16[{},{},{}]".format(n_mamba, slots,
                                      (cfg.d_conv - 1) * cfg.d_inner) +
              "{2,1,0:T(8,128)(2,1)} copy("]
    # nor of a layer's pages, nor (decode) of every slot's whole window
    slabs += ["bf16[{},{},{}]".format(pages + 1, ps,
                                      cfg.n_kv_heads * cfg.d_head),
              "bf16[{},{},{}]".format(slots * row, ps,
                                      cfg.n_kv_heads * cfg.d_head)]
    for slab in slabs:
        assert not [line.strip()[:160] for line in text.splitlines()
                    if slab in line][:3], slab
    # a kernel a Mamba layer, and one an attention layer: in decode the
    # walk of its pages in the grouped paged kernel, in prefill (which
    # gathers its row) the chunk's page write
    assert text.count("tpu_custom_call") == n_mamba + n_attn
    assert _page_writes(
        text, program,
        (pages + 1, n_attn, ps, cfg.n_kv_heads * cfg.d_head)) == \
        (n_attn if program == "prefill" else 0)
    aliased = {int(out): int(arg) for out, arg in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
        text.split("\n", 1)[0])}
    n_params = len(jax.tree_util.tree_leaves(params))
    assert aliased == {i: n_params + i for i in range(4)}
    # the whole of it fits the chip with room to spare
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < \
        12 * 2 ** 30


# ------------------------------------- LFM2: grouped matmul, expert layer
@pytest.mark.parametrize("rows", [1024, 1536, 4096],
                         ids=["chunk256", "decode384", "chunk1024"])
@pytest.mark.parametrize("k, n", [(2048, 3584), (1792, 2048)],
                         ids=["gate_up", "down"])
def test_moe_gmm_compiles_at_the_published_widths(
        one_chip, no_persistent_cache, rows, k, n):
    """The grouped matmul over LFM2-8B-A1B's 32 experts at the rows of
    a decode step of 384 slots and of prefill chunks, 4 experts a
    token: gate and up side by side (2048 -> 2 x 1792), then down."""
    from deepspeed_tpu.ops.pallas.moe import moe_gmm

    def fn(lhs, rhs, sizes):
        return moe_gmm(lhs, rhs, sizes, interpret=False)

    assert _compile(fn, one_chip, ((rows, k), BF16), ((32, k, n), BF16),
                    ((32,), I32)) == 1


@pytest.fixture(scope="module")
def lfm2_engine():
    """A tiny LFM2 engine on the CPU whose programs are lowered at the
    published widths (``jamba_engine`` says how)."""
    import json
    import os
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import lfm2
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2-8b-a1b-serve.json")) as f:
        cell = json.load(f)
    tiny = dict(cell["model"], hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_attention_heads=4,
                num_key_value_heads=2, vocab_size=128, num_experts=8)
    eng = deepspeed.init_inference(
        model=lfm2.make_lfm2_model(lfm2.config_from_hf(tiny), seed=0),
        config={"inference": dict(cell["inference"], max_batch_size=2,
                                  num_pages=256,
                                  paged_attention_kernel="pallas")})
    eng.model_config = lfm2.config_from_hf(cell["model"],
                                           moe_kernel="pallas")
    return eng, cell


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_lfm2_programs_run_the_kernels_and_alias_both_pools(
        one_chip, no_persistent_cache, lfm2_engine, monkeypatch, program):
    """``jit_prefill`` (the largest bucket) and ``jit_decode`` (every
    slot) of LFM2-8B-A1B's first stage at the cell's pool shapes: two
    grouped matmuls an expert layer; in decode the three attention
    layers walk their pages in the grouped paged kernel at its second
    shape (32 query heads on 8 key-value heads of 64); the page pool
    and the tail pool, three donated buffers, come back in place; the
    tail pool is never copied whole; and it fits the chip."""
    from deepspeed_tpu.models import lfm2
    eng, cell = lfm2_engine
    cfg = eng.model_config
    inference = cell["inference"]
    slots, pages = inference["max_batch_size"], inference["num_pages"]
    bucket, ps = inference["prefill_buckets"][-1], eng.page_size
    row = inference["max_seq_len"] // ps
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(lambda: lfm2.LFM2Decoder(cfg).serving_params(
        lfm2.init_params(cfg, 0), BF16))
    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), params)
    n_conv, n_attn = len(cfg.conv_layers), len(cfg.attention_layers)
    pool = sds((pages + 1, n_attn, ps, cfg.n_kv_heads * cfg.d_head), BF16)
    conv = sds((n_conv, slots, (cfg.conv_L - 1) * cfg.d_model), BF16)
    rng = jax.random.PRNGKey(0)
    tail = (sds(rng.shape, rng.dtype), sds((), F32), sds((), F32))
    if program == "prefill":
        fn = eng._get_prefill_fn(bucket, True, 0)
        args = (sds((), I32), sds((1, bucket), I32), sds((row,), I32),
                sds((), I32), sds((), I32))
    else:
        fn = eng._get_decode_fn(True, 0)
        args = (sds((slots,), jnp.bool_), sds((slots, 1), I32),
                sds((slots,), I32), sds((slots, row), I32))
    compiled = fn.lower(params, pool, pool, conv, *args, *tail).compile()
    text = compiled.as_text()

    assert text.startswith("HloModule jit_" + program)
    # and a kernel an attention layer: decode's page walk, prefill's
    # page write
    assert text.count("tpu_custom_call") == \
        2 * len(cfg.expert_layers) + n_attn
    assert _page_writes(
        text, program,
        (pages + 1, n_attn, ps, cfg.n_kv_heads * cfg.d_head)) == \
        (n_attn if program == "prefill" else 0)
    whole = "bf16[{},{},{}]".format(n_conv, slots,
                                    (cfg.conv_L - 1) * cfg.d_model) + \
        "{2,1,0:T(8,128)(2,1)} copy("
    assert whole not in text
    aliased = {int(out): int(arg) for out, arg in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
        text.split("\n", 1)[0])}
    n_params = len(jax.tree_util.tree_leaves(params))
    assert aliased == {i: n_params + i for i in range(3)}
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < \
        14 * 2 ** 30


@pytest.mark.parametrize("seq", [1, 2], ids=["decode", "verify_2"])
def test_mla_decode_compiles_at_the_published_widths(
        one_chip, no_persistent_cache, seq):
    """The latent page walk at Moonlight-16B-A3B's attention: 16 heads'
    absorbed queries of 640 lanes (512 latent + 64 rope + 64 of
    padding) against one pool of 640-lane rows, 320 slots, a row of 512
    pages of 16 tokens: a page is a (16, 640) bf16 slab, the values its
    first 512 lanes. The lane rule that PR 22 paid for: 576 lanes, the
    row without its padding, is not a multiple of 128. A block's 32
    page copies are straight-line starts behind one wait (PR 47); two
    queries a slot are 32 rows of the same two matmuls."""
    from deepspeed_tpu.ops.pallas.paged_attention import mla_decode

    def fn(q, pool, page_tables, positions, valid_lens):
        return mla_decode(q, pool, page_tables, positions, valid_lens,
                          layer_idx=3, page_size=16, rank=512,
                          sm_scale=192 ** -0.5, interpret=False)

    b = 320
    assert _compile(fn, one_chip, ((b, seq, 16, 640), BF16),
                    ((75001, 5, 16, 640), BF16), ((b, 512), I32),
                    ((b,), I32), ((b,), I32)) == 1


@pytest.fixture(scope="module")
def moonlight_engine():
    """A tiny deepseek_v3 engine on the CPU whose programs are lowered
    at the published widths (``jamba_engine`` says how)."""
    import json
    import os
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import deepseek_v3
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "moonlight-16b-a3b-serve.json")) as f:
        cell = json.load(f)
    tiny = dict(cell["model"], hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_attention_heads=4,
                qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                kv_lora_rank=128, vocab_size=128, n_routed_experts=8)
    eng = deepspeed.init_inference(
        model=deepseek_v3.make_deepseek_v3_model(
            deepseek_v3.config_from_hf(tiny), seed=0),
        config={"inference": dict(cell["inference"], max_batch_size=2,
                                  num_pages=1024,
                                  paged_attention_kernel="pallas")})
    eng.model_config = deepseek_v3.config_from_hf(cell["model"],
                                                  moe_kernel="pallas")
    return eng, cell


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_moonlight_programs_run_the_kernels_and_alias_the_latent_pool(
        one_chip, no_persistent_cache, moonlight_engine, monkeypatch,
        program):
    """``jit_prefill`` (the largest bucket) and ``jit_decode`` (every
    slot) of Moonlight-16B-A3B's first stage at the cell's pool shape:
    two grouped matmuls an expert layer; in decode every layer walks
    its latent pages in ``mla_decode``; the ONE pool of 640-lane rows is
    donated and comes back in place, never copied whole (the prefill's
    loop over key blocks reads it where it lies); and it fits the
    chip."""
    from deepspeed_tpu.models import deepseek_v3
    eng, cell = moonlight_engine
    cfg = eng.model_config
    inference = cell["inference"]
    slots, pages = inference["max_batch_size"], inference["num_pages"]
    bucket, ps = inference["prefill_buckets"][-1], eng.page_size
    row = inference["max_seq_len"] // ps
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda: deepseek_v3.DeepseekV3Decoder(cfg).serving_params(
            deepseek_v3.init_params(cfg, 0), BF16))
    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), params)
    pool = sds((pages + 1, cfg.n_layers, ps, cfg.mla.lanes), BF16)
    assert cfg.mla.lanes == 640
    rng = jax.random.PRNGKey(0)
    tail = (sds(rng.shape, rng.dtype), sds((), F32), sds((), F32))
    if program == "prefill":
        fn = eng._get_prefill_fn(bucket, True, 0)
        args = (sds((1, bucket), I32), sds((row,), I32), sds((), I32),
                sds((), I32))
    else:
        fn = eng._get_decode_fn(True, 0)
        args = (sds((slots, 1), I32), sds((slots,), I32),
                sds((slots, row), I32))
    compiled = fn.lower(params, pool, *args, *tail).compile()
    text = compiled.as_text()

    assert text.startswith("HloModule jit_" + program)
    # and a kernel a layer: decode's latent page walk, prefill's page
    # write (the one pool)
    assert text.count("tpu_custom_call") == \
        2 * len(cfg.expert_layers) + cfg.n_layers
    assert ("mla_decode" in text) == (program == "decode")
    assert _page_writes(text, program,
                        (pages + 1, cfg.n_layers, ps, 640)) == \
        (cfg.n_layers if program == "prefill" else 0)
    whole = "bf16[{},{},{},{}]".format(pages + 1, cfg.n_layers, ps, 640)
    assert not re.search(re.escape(whole) + r"\S* copy\(", text)
    aliased = {int(out): int(arg) for out, arg in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
        text.split("\n", 1)[0])}
    n_params = len(jax.tree_util.tree_leaves(params))
    assert aliased == {0: n_params}
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < \
        14.5 * 2 ** 30


@pytest.mark.parametrize("window, row", [(None, 2048), (1024, 193)],
                         ids=["full", "window"])
def test_grouped_walk_compiles_at_mellum_widths(
        one_chip, no_persistent_cache, window, row):
    """The grouped page walk at Mellum2-12B-A2.5B's attention, 32 query
    heads on 4 key-value heads of 128, 128 slots: a full layer over a
    row of 2,048 pages (32,768 positions; the table goes to the kernel
    a slot's row at a time: whole it is all of scalar memory's 1 MB,
    which the compiler refused) and a sliding layer over its sliding
    table of 193 columns with ``window=1024``."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

    def fn(q, k_pool, v_pool, page_tables, positions, valid_lens):
        return paged_attention(q, k_pool, v_pool, page_tables, positions,
                               valid_lens, layer_idx=1, page_size=16,
                               interpret=False, window=window)

    b, pages = 128, 65001 if window is None else 8577
    layers = 2 if window is None else 6
    pool = ((pages, layers, 16, 512), BF16)
    assert _compile(fn, one_chip, ((b, 1, 32, 128), BF16), pool, pool,
                    ((b, row), I32), ((b,), I32), ((b,), I32)) == 1


@pytest.mark.parametrize("bucket", [512, 1024, 2048])
@pytest.mark.parametrize("window, row", [(None, 2048), (1024, 193)],
                         ids=["full", "window"])
def test_chunk_attention_compiles_at_mellum_widths(
        one_chip, no_persistent_cache, window, row, bucket):
    """A prefill chunk's attention over its pages at Mellum2-12B-A2.5B's
    attention (32 query heads on 4 key-value heads of 128, pages of 16)
    for every bucket of the ide cell: a full layer over its slot's row
    of 2,048 pages in scalar memory, a sliding layer over its sliding
    table of 193 columns with ``window=1024``; the layer is an operand.
    The tiles come from these shapes and ``vmem_limit_bytes`` with them
    (the scoped default of 16 MB would refuse the tile)."""
    from deepspeed_tpu.ops.pallas.chunk_attention import chunk_attention

    def fn(q, k_pool, v_pool, layer, page_tables, positions, valid_lens):
        return chunk_attention(q, k_pool, v_pool, layer, page_tables,
                               positions, valid_lens, 16, window,
                               interpret=False)

    pages, layers = (70001, 2) if window is None else (8577, 6)
    pool = ((pages, layers, 16, 512), BF16)
    assert _compile(fn, one_chip, ((1, bucket, 32, 128), BF16), pool, pool,
                    ((), I32), ((1, row), I32), ((1,), I32),
                    ((1,), I32)) == 1


@pytest.mark.parametrize("bucket", [512, 1024, 2048])
@pytest.mark.parametrize("window, row", [(None, 2048), (4096, 385)],
                         ids=["full", "window"])
def test_chunk_attention_compiles_at_command_a_plus_widths(
        one_chip, no_persistent_cache, window, row, bucket):
    """The same kernel at command-a-plus-05-2026's attention (128 query
    heads on 8 key-value heads of 128: 16 heads a key-value head over
    pools of 1,024 lanes, pages of 16) for every bucket of the rag cell:
    the full layer over its slot's row of 2,048 pages, a sliding layer
    over its sliding table of 385 columns with ``window=4096``. The tile
    the shapes give is 128 queries against blocks of 1,024 keys with a
    short last block of 256 (three bodies), and what it asks of VMEM
    (the 100 MiB a call may) is granted."""
    from deepspeed_tpu.ops.pallas.chunk_attention import chunk_attention

    def fn(q, k_pool, v_pool, layer, page_tables, positions, valid_lens):
        return chunk_attention(q, k_pool, v_pool, layer, page_tables,
                               positions, valid_lens, 16, window,
                               interpret=False)

    pages, layers = (36001, 1) if window is None else (10409, 3)
    pool = ((pages, layers, 16, 1024), BF16)
    assert _compile(fn, one_chip, ((1, bucket, 128, 128), BF16), pool, pool,
                    ((), I32), ((1, row), I32), ((1,), I32),
                    ((1,), I32)) == 1


@pytest.mark.parametrize("engine", ["serving_engine", "jamba_engine",
                                    "lfm2_engine", "moonlight_engine"])
def test_the_other_families_prefill_closes_over_what_it_did(request, engine):
    """Jamba, LFM2 and Moonlight under ``paged_attention_kernel:
    pallas``: their decoders have no prefill variant of the config, so
    ``_get_prefill_fn`` closes over ``model_config`` itself, the XLA
    path, and their prefill programs are the programs they were (the
    cases above that count each program's kernels hold the text).
    GPT-2's has one since PR 57 (a chunk reads its keys in
    ``chunk_attention``); the serving config stays the gather."""
    eng = request.getfixturevalue(engine)
    eng = eng[0] if isinstance(eng, tuple) else eng
    assert eng.paged_attention_kernel == "pallas"
    assert eng.model_config.paged_attention_kernel == "xla"
    if engine == "serving_engine":
        assert eng.prefill_attention_kernel == "pallas"
        assert eng._prefill_config() == eng.decoder.decode_config(
            eng.model_config, "pallas")
        return
    assert eng._prefill_config() is eng.model_config
    assert eng.prefill_attention_kernel == "xla"


@pytest.fixture(scope="module")
def mellum_engine():
    """A tiny Mellum engine on the CPU whose programs are lowered at
    the published widths (``jamba_engine`` says how)."""
    import json
    import os
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import mellum
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "mellum2-12b-a2.5b-serve.json")) as f:
        cell = json.load(f)
    tiny = dict(cell["model"], hidden_size=64, moe_intermediate_size=32,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                vocab_size=128, num_experts=8, num_experts_per_tok=2)
    eng = deepspeed.init_inference(
        model=mellum.make_mellum_model(mellum.config_from_hf(tiny), seed=0),
        config={"inference": dict(cell["inference"], max_batch_size=2,
                                  num_pages=[4096, 512],
                                  paged_attention_kernel="pallas")})
    eng.model_config = mellum.config_from_hf(cell["model"],
                                             moe_kernel="pallas")
    return eng, cell


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_mellum_programs_run_the_kernels_and_alias_both_groups(
        one_chip, no_persistent_cache, mellum_engine, monkeypatch, program):
    """``jit_prefill`` (the largest bucket) and ``jit_decode`` (every
    slot) of Mellum2-12B-A2.5B's first stage at the cell's pool shapes,
    a table and a base a page group: two grouped matmuls a layer; in
    decode every layer walks its group's pages in the grouped paged
    kernel (the sliding layers over their 193 columns); in prefill a
    page write a layer and the chunk's attention in the
    ``chunk_attention`` kernel in all 8 layers (no loop that carries a
    float32 accumulator through HBM, no array of a block's scores); both
    groups' pool pairs, four donated buffers, come back in place; and
    it fits the chip."""
    from deepspeed_tpu.models import mellum
    eng, cell = mellum_engine
    cfg = eng.model_config
    inference = cell["inference"]
    slots, pages = inference["max_batch_size"], inference["num_pages"]
    bucket, ps = inference["prefill_buckets"][-1], eng.page_size
    rows = (inference["max_seq_len"] // ps, eng.page_groups[1].max_pages)
    assert rows[1] == (cfg.window + bucket) // ps + 1 == 193
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(lambda: mellum.MellumDecoder(cfg).serving_params(
        mellum.init_params(cfg, 0), BF16))
    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), params)
    lanes = cfg.n_kv_heads * cfg.d_head
    layers = (len(cfg.full_layers), len(cfg.sliding_layers))
    pools = [sds((n + 1, l, ps, lanes), BF16)
             for n, l in zip(pages, layers) for _ in "kv"]
    rng = jax.random.PRNGKey(0)
    tail = (sds(rng.shape, rng.dtype), sds((), F32), sds((), F32))
    if program == "prefill":
        fn = eng._get_prefill_fn(bucket, True, 0)
        args = (sds((1, bucket), I32),
                (tuple(sds((r,), I32) for r in rows),
                 (sds((), I32), sds((), I32))), sds((), I32), sds((), I32))
    else:
        fn = eng._get_decode_fn(True, 0)
        args = (sds((slots, 1), I32), sds((slots,), I32),
                (tuple(sds((slots, r), I32) for r in rows),
                 (sds((slots,), I32), sds((slots,), I32))))
    compiled = fn.lower(params, *pools, *args, *tail).compile()
    text = compiled.as_text()

    assert text.startswith("HloModule jit_" + program)
    assert eng.prefill_attention_kernel == "pallas"
    chunk_calls = len(re.findall(
        r"%chunk_attention(?:\.\d+)? = .*tpu_custom_call", text))
    assert chunk_calls == (cfg.n_layers if program == "prefill" else 0)
    assert text.count("tpu_custom_call") == 3 * cfg.n_layers + chunk_calls
    # every expert held: the grouped matmuls take every routed row
    gmm_rows = {int(m) for m in re.findall(
        r"%moe_gmm(?:\.\d+)? = bf16\[(\d+),\d+\]\S* custom-call", text)}
    assert gmm_rows == {(bucket if program == "prefill" else slots) *
                        cfg.top_k}
    assert ("paged_attention_grouped" in text) == (program == "decode")
    # XLA's loop over key blocks carried f32[1,4,8,bucket,128] and made
    # f32[1,4,8,bucket,512] scores a turn
    assert not re.search(r"f32\[1,4,8,\d+,(128|512)\]", text)
    for n, l in zip(pages, layers):
        assert _page_writes(text, program, (n + 1, l, ps, lanes)) == \
            (cfg.n_layers if program == "prefill" else 0)
    # the scores of a chunk against the whole context: never an array
    assert "f32[1,4,8,{},{}]".format(bucket, rows[0] * ps) not in text
    aliased = {int(out): int(arg) for out, arg in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
        text.split("\n", 1)[0])}
    n_params = len(jax.tree_util.tree_leaves(params))
    assert aliased == {i: n_params + i for i in range(4)}
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < \
        14.5 * 2 ** 30


@pytest.fixture(scope="module")
def command_a_plus_engine():
    """A tiny Cohere2-MoE engine on the CPU whose programs are lowered
    at the published widths and the cell's share (``jamba_engine`` says
    how)."""
    import json
    import os
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import cohere2_moe
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "command-a-plus-serve.json")) as f:
        cell = json.load(f)
    tiny = dict(cell["model"], hidden_size=64, intermediate_size=32,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                padded_vocab_size=128, num_experts=2, router_num_experts=8,
                experts_held=[0, 2], num_experts_per_tok=2,
                num_shared_experts=2)
    eng = deepspeed.init_inference(
        model=cohere2_moe.make_cohere2_moe_model(
            cohere2_moe.config_from_hf(tiny), seed=0),
        config={"inference": dict(cell["inference"], max_batch_size=2,
                                  num_pages=[4096, 512],
                                  paged_attention_kernel="pallas")})
    eng.model_config = cohere2_moe.config_from_hf(cell["model"],
                                                  moe_kernel="pallas")
    return eng, cell


# -------------------------- Olmo Hybrid: the gated delta rule's state pool
OLMO = {"heads": 30, "d_k": 96, "d_v": 192, "linear_layers": 12}


@pytest.mark.parametrize("slots", [64, 80])
def test_gated_delta_step_compiles_in_place_on_the_pool(
        one_chip, no_persistent_cache, slots):
    """One decode step of one linear layer of Olmo-Hybrid-7B over every
    slot at the published widths (30 heads, a float32 state of 96 x 192
    a head, held 96 x 5,760 a slot): the pool of 12 layers comes back
    in place and no layer's slab (141 MB at 64 slots) is made of it."""
    from deepspeed_tpu.ops.pallas.gated_delta import gated_delta_step
    H, dk, dv, layers = (OLMO["heads"], OLMO["d_k"], OLMO["d_v"],
                         OLMO["linear_layers"])

    def fn(pool, q, k, v, a, beta):
        return gated_delta_step(pool, q, k, v, a, beta, 3, interpret=False)

    args = [jax.ShapeDtypeStruct(s, F32, sharding=one_chip) for s in (
        (layers, slots, dk, H * dv), (slots, H, dk), (slots, H, dk),
        (slots, H, dv), (slots, H), (slots, H))]
    compiled = jax.jit(fn, donate_argnums=0).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert re.search(r"\{1\}: \(0, \{\}, (?:may|must)-alias\)",
                     text.split("\n", 1)[0])
    assert "f32[{},{},{}]".format(slots, dk, H * dv) not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_paged_attention_compiles_at_30_heads_of_128(
        one_chip, no_persistent_cache):
    """The page walk at Olmo-Hybrid-7B's full layers: one query head a
    key-value head, 30 of 128, 3,840 packed lanes a row (GPT-2 medium:
    16 of 64, 1,024), 64 slots over rows of 192 pages of 16."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

    def fn(q, k_pool, v_pool, page_tables, positions, valid_lens):
        return paged_attention(q, k_pool, v_pool, page_tables, positions,
                               valid_lens, layer_idx=1, page_size=16,
                               interpret=False)

    pool = ((4001, 4, 16, 3840), BF16)
    assert _compile(fn, one_chip, ((64, 1, 30, 128), BF16), pool, pool,
                    ((64, 192), I32), ((64,), I32), ((64,), I32)) == 1


@pytest.mark.parametrize("bucket", [128, 256, 512])
def test_chunk_attention_compiles_at_30_heads_of_128(
        one_chip, no_persistent_cache, bucket):
    """A prompt chunk's attention at Olmo-Hybrid-7B's full layers (30
    heads on 30 key-value heads of 128: a group of ONE over 3,840
    lanes) for every bucket of the evals cell, over a slot's row of 192
    pages."""
    from deepspeed_tpu.ops.pallas.chunk_attention import chunk_attention

    def fn(q, k_pool, v_pool, layer, page_tables, positions, valid_lens):
        return chunk_attention(q, k_pool, v_pool, layer, page_tables,
                               positions, valid_lens, 16, None,
                               interpret=False)

    pool = ((4001, 4, 16, 3840), BF16)
    assert _compile(fn, one_chip, ((1, bucket, 30, 128), BF16), pool, pool,
                    ((), I32), ((1, 192), I32), ((1,), I32),
                    ((1,), I32)) == 1


# (pool pages + 1, the buckets) of GPT-2's two serving cells; a row is
# the model's 1,024 positions: 64 columns, as ``_SERVING_CELLS`` says
_GPT2_CHUNKS = [(cell, pages, bucket)
                for cell, pages, buckets in (("docs", 8501, (512, 1024)),
                                             ("chat", 3073, (128, 256, 512)))
                for bucket in buckets]


@pytest.mark.parametrize("cell, pages, bucket", _GPT2_CHUNKS,
                         ids=["{}-{}".format(c, b)
                              for c, _, b in _GPT2_CHUNKS])
def test_chunk_attention_compiles_at_16_heads_of_64(
        one_chip, no_persistent_cache, cell, pages, bucket):
    """A prompt chunk's attention at GPT-2 medium's heads (16 of 64
    lanes, one a key-value head, 1,024 lanes a pool row) for every
    bucket of docs and chat over the row its engine has (64 columns):
    two heads share a lane tile and are folded together over its 128
    lanes, where a head's own 64 at a traced ``h * d_head`` were refused
    ("cannot statically prove that index in dimension 2 is a multiple
    of 128", PR 57). q goes in and the result comes out as the
    projection's packed rows in bfloat16: nothing but the kernel and
    its scalar operands is left of the call."""
    from deepspeed_tpu.ops.pallas.chunk_attention import chunk_attention

    def fn(q, k_pool, v_pool, layer, page_tables, positions, valid_lens):
        return chunk_attention(
            q.reshape(1, bucket, 16, 64), k_pool, v_pool, layer,
            page_tables, positions, valid_lens, 16, out_dtype=BF16,
            interpret=False).reshape(q.shape)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((pages, 24, 16, 1024), BF16)
    text = jax.jit(fn).lower(
        sds((1, bucket, 1024), BF16), pool, pool, sds((), I32),
        sds((1, 64), I32), sds((1,), I32), sds((1,), I32)) \
        .compile().as_text()
    assert text.count("tpu_custom_call") == 1
    # the packed rows are the kernel's own layout: no copy of them
    assert not [line.strip()[:160] for line in text.splitlines()
                if re.search(r" (copy|transpose)\(", line)
                and "bf16[1,{},".format(bucket) in line][:3]


def test_chunk_attention_compiles_at_8_heads_of_64_in_groups_of_4(
        one_chip, no_persistent_cache):
    """The same fold at LFM2-8B-A1B's attention (32 query heads on 8
    key-value heads of 64: 4 heads a group over 512 lanes), a bucket of
    512 over a slot's row of 192 pages: the shape its chunk can opt in
    with (its read is still the XLA loop)."""
    from deepspeed_tpu.ops.pallas.chunk_attention import chunk_attention

    def fn(q, k_pool, v_pool, layer, page_tables, positions, valid_lens):
        return chunk_attention(q, k_pool, v_pool, layer, page_tables,
                               positions, valid_lens, 16, None,
                               interpret=False)

    pool = ((8001, 3, 16, 512), BF16)
    assert _compile(fn, one_chip, ((1, 512, 32, 64), BF16), pool, pool,
                    ((), I32), ((1, 192), I32), ((1,), I32),
                    ((1,), I32)) == 1


@pytest.fixture(scope="module")
def olmo_engine():
    """A tiny Olmo Hybrid engine on the CPU whose programs are lowered
    at the published widths (``jamba_engine`` says how)."""
    import json
    import os
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import olmo_hybrid
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "olmo-hybrid-7b-serve.json")) as f:
        cell = json.load(f)
    tiny = dict(cell["model"], hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=4,
                vocab_size=128, linear_num_key_heads=4,
                linear_num_value_heads=4, linear_key_head_dim=8,
                linear_value_head_dim=32, num_hidden_layers=4,
                layer_types=cell["model"]["layer_types"][:4])
    eng = deepspeed.init_inference(
        model=olmo_hybrid.make_olmo_hybrid_model(
            olmo_hybrid.config_from_hf(tiny), seed=0),
        config={"inference": dict(cell["inference"], max_batch_size=2,
                                  num_pages=256,
                                  paged_attention_kernel="pallas")})
    eng.model_config = olmo_hybrid.config_from_hf(cell["model"],
                                                  gdn_kernel="pallas")
    return eng, cell


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_olmo_programs_copy_no_state_slab_and_alias_both_pools(
        one_chip, no_persistent_cache, olmo_engine, monkeypatch, program):
    """``jit_prefill`` (the largest bucket, one slot) and ``jit_decode``
    (every slot) of Olmo-Hybrid-7B at the cell's pool shapes and depth
    (16 layers: 12 linear, 4 full): no instruction makes an array of a
    layer's whole state slab (``[slots, 96, 5760]``, 141 MB of float32
    at 64 slots), of a layer's pages or (decode) of every slot's whole
    window; the page pool AND the state pool, four donated buffers,
    come back in place; a decode step runs one state kernel a linear
    layer and one page walk a full layer, a chunk one page write and
    one ``chunk_attention`` a full layer (its delta rule is XLA); and
    the whole of it fits the chip."""
    from deepspeed_tpu.models import olmo_hybrid
    eng, cell = olmo_engine
    cfg = eng.model_config
    inference = cell["inference"]
    slots, pages = inference["max_batch_size"], inference["num_pages"]
    bucket, ps = inference["prefill_buckets"][-1], eng.page_size
    row = inference["max_seq_len"] // ps
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    decoder = olmo_hybrid.OlmoHybridDecoder(cfg)
    params = jax.eval_shape(lambda: decoder.serving_params(
        olmo_hybrid.init_params(cfg, 0), BF16))
    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), params)
    n_linear, n_full = len(cfg.linear_layers), len(cfg.full_layers)
    lanes = cfg.n_kv_heads * cfg.d_head
    pool = sds((pages + 1, n_full, ps, lanes), BF16)
    conv = sds((n_linear, slots, (cfg.d_conv - 1) * cfg.conv_channels), BF16)
    gdn = sds((n_linear, slots, cfg.d_k, cfg.linear_heads * cfg.d_v), F32)
    assert [(s.name, s.dtype) for s in decoder.cache_spec().state] == \
        [("conv", BF16), ("gdn", F32)]
    # a slot's state is 27.4 MB with no lane of padding
    assert (conv.size * 2 + gdn.size * 4) // slots == 27371520
    rng = jax.random.PRNGKey(0)
    tail = (sds(rng.shape, rng.dtype), sds((), F32), sds((), F32))
    if program == "prefill":
        fn = eng._get_prefill_fn(bucket, True, 0)
        args = (sds((), I32), sds((1, bucket), I32), sds((row,), I32),
                sds((), I32), sds((), I32))
    else:
        fn = eng._get_decode_fn(True, 0)
        args = (sds((slots,), jnp.bool_), sds((slots, 1), I32),
                sds((slots,), I32), sds((slots, row), I32))
    compiled = fn.lower(params, pool, pool, conv, gdn, *args,
                        *tail).compile()
    text = compiled.as_text()

    assert text.startswith("HloModule jit_" + program)
    slabs = ["f32[{},{},{}]".format(slots, cfg.d_k,
                                    cfg.linear_heads * cfg.d_v),
             "bf16[{},{},{}]".format(pages + 1, ps, lanes),
             "bf16[{},{},{}]".format(slots * row, ps, lanes)]
    if program == "prefill":
        slabs += ["bf16[{},{}]".format(
            slots, (cfg.d_conv - 1) * cfg.conv_channels)]
    for slab in slabs:
        assert not [line.strip()[:160] for line in text.splitlines()
                    if slab in line][:3], slab
    kernels = {name: len(re.findall(
        r"%" + name + r"(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)) for name in ("gated_delta_step", "paged_attention",
                            "chunk_attention")}
    if program == "decode":
        assert kernels == {"gated_delta_step": n_linear,
                           "paged_attention": n_full, "chunk_attention": 0}
        assert text.count("tpu_custom_call") == n_linear + n_full
    else:
        assert kernels == {"gated_delta_step": 0, "paged_attention": 0,
                           "chunk_attention": n_full}
        assert _page_writes(text, program,
                            (pages + 1, n_full, ps, lanes)) == n_full
    aliased = {int(out): int(arg) for out, arg in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
        text.split("\n", 1)[0])}
    n_params = len(jax.tree_util.tree_leaves(params))
    assert aliased == {i: n_params + i for i in range(4)}
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < \
        15.2 * 2 ** 30


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_command_a_plus_programs_run_the_kernels_at_the_cells_share(
        one_chip, no_persistent_cache, command_a_plus_engine, monkeypatch,
        program):
    """``jit_prefill`` (the largest bucket) and ``jit_decode`` (every
    slot) of command-a-plus-05-2026's first four layers at the cell's
    share (16 of 128 experts, 32,768 rows of the tied embedding) and pool
    shapes: shapes no other cell has. 16 query heads a key-value head:
    a decode query is 128 rows over 1,024 packed lanes in the grouped
    walk, the sliding layers over their table of 385 columns with
    ``window=4096``; a chunk's attention in ``chunk_attention`` in all 4
    layers; the grouped matmuls of a layer over the share's CAPACITY of
    rows (4,096 of a chunk's 16,384 routed, 128 of a decode step's 320:
    twice the part of 16 experts of 128), in a loop that a launch past
    its capacity goes round again; both groups' pool pairs come back in
    place; and it fits the chip. Temporaries (the chip's compiler, PR
    52): prefill 0.455 GB, decode 0.143 (0.454 / 0.143 at PR 50, when
    every routed row was gathered: the largest live set is not the
    expert layer's)."""
    from deepspeed_tpu.models import cohere2_moe
    from deepspeed_tpu.ops import moe
    eng, cell = command_a_plus_engine
    cfg = eng.model_config
    inference = cell["inference"]
    slots, pages = inference["max_batch_size"], inference["num_pages"]
    bucket, ps = inference["prefill_buckets"][-1], eng.page_size
    rows = (inference["max_seq_len"] // ps, eng.page_groups[1].max_pages)
    assert rows[1] == (cfg.window + bucket) // ps + 1 == 385
    assert cfg.held == (0, 16) and cfg.n_experts == 128
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda: cohere2_moe.Cohere2MoeDecoder(cfg).serving_params(
            cohere2_moe.init_params(cfg, 0), BF16))
    assert sum(int(np.prod(x.shape)) for x in
               jax.tree_util.tree_leaves(params)) == 4_733_292_544
    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), params)
    lanes = cfg.n_kv_heads * cfg.d_head
    layers = (len(cfg.full_layers), len(cfg.sliding_layers))
    pools = [sds((n + 1, l, ps, lanes), BF16)
             for n, l in zip(pages, layers) for _ in "kv"]
    rng = jax.random.PRNGKey(0)
    tail = (sds(rng.shape, rng.dtype), sds((), F32), sds((), F32))
    if program == "prefill":
        fn = eng._get_prefill_fn(bucket, True, 0)
        args = (sds((1, bucket), I32),
                (tuple(sds((r,), I32) for r in rows),
                 (sds((), I32), sds((), I32))), sds((), I32), sds((), I32))
    else:
        fn = eng._get_decode_fn(True, 0)
        args = (sds((slots, 1), I32), sds((slots,), I32),
                (tuple(sds((slots, r), I32) for r in rows),
                 (sds((slots,), I32), sds((slots,), I32))))
    compiled = fn.lower(params, *pools, *args, *tail).compile()
    text = compiled.as_text()

    assert text.startswith("HloModule jit_" + program)
    assert eng.prefill_attention_kernel == "pallas"
    chunk_calls = len(re.findall(
        r"%chunk_attention(?:\.\d+)? = .*tpu_custom_call", text))
    assert chunk_calls == (cfg.n_layers if program == "prefill" else 0)
    assert text.count("tpu_custom_call") == 3 * cfg.n_layers + chunk_calls
    # the share's grouped matmuls, in the loop over its capacity's rows
    gmm_rows = {int(m) for m in re.findall(
        r"%moe_gmm(?:\.\d+)? = bf16\[(\d+),\d+\]\S* custom-call", text)}
    assert gmm_rows == {moe.share_capacity(
        (bucket if program == "prefill" else slots) * cfg.top_k,
        cfg.held[1] - cfg.held[0], cfg.n_experts)}
    assert ("paged_attention_grouped" in text) == (program == "decode")
    # the sliding group's pool by its shape (XLA drops the one-layer
    # full group's unit dimension); the page writes are counted over all
    assert _page_writes(text, program, (pages[1] + 1, layers[1], ps,
                                        lanes)) == \
        (cfg.n_layers if program == "prefill" else 0)
    aliased = {int(out): int(arg) for out, arg in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
        text.split("\n", 1)[0])}
    n_params = len(jax.tree_util.tree_leaves(params))
    assert aliased == {i: n_params + i for i in range(4)}
    memory = compiled.memory_analysis()
    print("command_a_plus {}: arguments {:.3f} GB, temporaries {:.3f} GB"
          .format(program, memory.argument_size_in_bytes / 1e9,
                  memory.temp_size_in_bytes / 1e9))
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < \
        14.6 * 2 ** 30


# ------------------------------------------------ granite-4.0-h-small
GRANITE = {"heads": 128, "d_head": 64, "d_state": 128, "mamba_layers": 9,
           "slots": 96}


def test_ssd_step_compiles_in_place_on_the_pool(one_chip,
                                                no_persistent_cache):
    """One decode step of one Mamba-2 layer of granite-4.0-h-small over
    every slot at the published widths (128 heads of 64, a float32 state
    of 128 x 8,192 a slot: 4.19 MB) and the cell's 96 slots: the pool of
    9 layers (3.6 GB) comes back in place and no layer's slab (403 MB)
    is made of it."""
    from deepspeed_tpu.ops.pallas.mamba2 import ssd_step
    H, p, n = GRANITE["heads"], GRANITE["d_head"], GRANITE["d_state"]
    layers, slots = GRANITE["mamba_layers"], GRANITE["slots"]

    def fn(pool, x, dt, B, C, a, D):
        return ssd_step(pool, 4, x, dt, B, C, a, D, interpret=False)

    args = [jax.ShapeDtypeStruct(s, F32, sharding=one_chip) for s in (
        (layers, slots, n, H * p), (slots, H * p), (slots, H), (slots, n),
        (slots, n), (slots, H), (H,))]
    compiled = jax.jit(fn, donate_argnums=0).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert re.search(r"%ssd_step(?:\.\d+)? = ", text)
    assert re.search(r"\{1\}: \(0, \{\}, (?:may|must)-alias\)",
                     text.split("\n", 1)[0])
    assert "f32[{},{},{}]".format(slots, n, H * p) not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_paged_attention_compiles_at_32_on_8_heads_of_128(
        one_chip, no_persistent_cache):
    """The grouped page walk at granite-4.0-h-small's attention layer:
    4 query heads a key-value head, 32 rows a slot over 1,024 packed
    lanes, 96 slots over rows of 640 pages of 16 (10,240 positions), a
    pool of ONE layer."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

    def fn(q, k_pool, v_pool, page_tables, positions, valid_lens):
        return paged_attention(q, k_pool, v_pool, page_tables, positions,
                               valid_lens, layer_idx=0, page_size=16,
                               interpret=False)

    pool = ((16001, 1, 16, 1024), BF16)
    compiled = jax.jit(fn).lower(*[
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
            ((96, 1, 32, 128), BF16), pool, pool, ((96, 640), I32),
            ((96,), I32), ((96,), I32))]).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "%paged_attention_grouped" in text


@pytest.mark.parametrize("bucket", [256, 512, 1024, 2048])
def test_chunk_attention_compiles_at_32_on_8_heads_of_128(
        one_chip, no_persistent_cache, bucket):
    """A prompt chunk's attention at granite-4.0-h-small's attention
    layer (32 heads on 8 key-value heads of 128: a group of 4 over 1,024
    lanes) for every bucket of the support cell, over a slot's row of
    640 pages."""
    from deepspeed_tpu.ops.pallas.chunk_attention import chunk_attention

    def fn(q, k_pool, v_pool, layer, page_tables, positions, valid_lens):
        return chunk_attention(q, k_pool, v_pool, layer, page_tables,
                               positions, valid_lens, 16, None,
                               interpret=False)

    pool = ((16001, 1, 16, 1024), BF16)
    assert _compile(fn, one_chip, ((1, bucket, 32, 128), BF16), pool, pool,
                    ((), I32), ((1, 640), I32), ((1,), I32),
                    ((1,), I32)) == 1


@pytest.mark.parametrize("rows, k, n", [
    (1024, 4096, 1536), (1024, 768, 4096),
    (20480, 4096, 1536), (20480, 768, 4096)],
    ids=["decode_w13", "decode_w2", "chunk_w13", "chunk_w2"])
def test_moe_gmm_compiles_at_36_groups_of_width_768(
        one_chip, no_persistent_cache, rows, k, n):
    """The grouped matmul over granite-4.0-h-small's 36 held experts of
    width 768 at the rows of a decode step of 96 slots and of the
    largest chunk, 10 experts a token (a half share: the capacity is all
    the rows, 960 in whole tiles of 128): gate and up side by side (4,096 -> 2 x 768), then down."""
    from deepspeed_tpu.ops.pallas.moe import moe_gmm

    def fn(lhs, rhs, sizes):
        return moe_gmm(lhs, rhs, sizes, interpret=False)

    assert _compile(fn, one_chip, ((rows, k), BF16), ((36, k, n), BF16),
                    ((36,), I32)) == 1


@pytest.fixture(scope="module")
def granite_engine():
    """A tiny Granite-MoE-Hybrid engine on the CPU whose programs are
    lowered at the published widths (``jamba_engine`` says how)."""
    import json
    import os
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import granite_moe_hybrid as granite
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite-4.0-h-small-serve.json")) as f:
        cell = json.load(f)
    tiny = dict(cell["model"], hidden_size=64, intermediate_size=32,
                shared_intermediate_size=48, num_attention_heads=4,
                num_key_value_heads=2, vocab_size=128,
                padded_vocab_size=128, mamba_n_heads=8, mamba_d_head=16,
                mamba_d_state=16, num_local_experts=4,
                router_num_experts=8, experts_held=[0, 4],
                num_experts_per_tok=3, num_hidden_layers=3,
                layer_types=["mamba", "attention", "mamba"])
    eng = deepspeed.init_inference(
        model=granite.make_granite_moe_hybrid_model(
            granite.config_from_hf(tiny), seed=0),
        config={"inference": dict(cell["inference"], max_batch_size=2,
                                  num_pages=1300,
                                  paged_attention_kernel="pallas")})
    eng.model_config = granite.config_from_hf(
        cell["model"], ssd_kernel="pallas", moe_kernel="pallas")
    return eng, cell


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_granite_programs_copy_no_state_slab_and_alias_both_pools(
        one_chip, no_persistent_cache, granite_engine, monkeypatch,
        program):
    """``jit_prefill`` (the largest bucket, one slot) and ``jit_decode``
    (every slot) of granite-4.0-h-small at the cell's share (36 of 72
    experts, 50,176 rows of the tied embedding), pool shapes and depth
    (10 layers: 9 Mamba-2, 1 attention): no instruction makes an array
    of a layer's whole state slab (``[96, 128, 8192]``, 403 MB of
    float32) or of every slot's whole window; the page pool AND the state pool,
    four donated buffers, come back in place; a decode step runs one
    state kernel a Mamba-2 layer, one grouped page walk and two grouped
    matmuls a layer over ALL its routed rows (a half share: the capacity
    is all the rows); a chunk one page write and one ``chunk_attention``
    (its recurrence is XLA); and the whole of it fits the chip."""
    from deepspeed_tpu.models import granite_moe_hybrid as granite
    from deepspeed_tpu.ops import moe
    eng, cell = granite_engine
    cfg = eng.model_config
    inference = cell["inference"]
    slots, pages = inference["max_batch_size"], inference["num_pages"]
    bucket, ps = inference["prefill_buckets"][-1], eng.page_size
    row = inference["max_seq_len"] // ps
    assert cfg.held == (0, 36) and cfg.n_experts == 72
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    decoder = granite.GraniteMoeHybridDecoder(cfg)
    params = jax.eval_shape(lambda: decoder.serving_params(
        granite.init_params(cfg, 0), BF16))
    assert sum(int(np.prod(x.shape)) for x in
               jax.tree_util.tree_leaves(params)) == 4_757_211_776
    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), params)
    n_mamba, n_attn = len(cfg.mamba_layers), len(cfg.attention_layers)
    lanes = cfg.n_kv_heads * cfg.d_head
    pool = sds((pages + 1, n_attn, ps, lanes), BF16)
    conv = sds((n_mamba, slots, (cfg.d_conv - 1) * cfg.conv_channels), BF16)
    ssd = sds((n_mamba, slots, cfg.d_state, cfg.d_inner), F32)
    assert [(s.name, s.dtype) for s in decoder.cache_spec().state] == \
        [("conv", BF16), ("ssd", F32)]
    assert (conv.size * 2 + ssd.size * 4) // slots == 38204928
    rng = jax.random.PRNGKey(0)
    tail = (sds(rng.shape, rng.dtype), sds((), F32), sds((), F32))
    if program == "prefill":
        fn = eng._get_prefill_fn(bucket, True, 0)
        args = (sds((), I32), sds((1, bucket), I32), sds((row,), I32),
                sds((), I32), sds((), I32))
    else:
        fn = eng._get_decode_fn(True, 0)
        args = (sds((slots,), jnp.bool_), sds((slots, 1), I32),
                sds((slots,), I32), sds((slots, row), I32))
    compiled = fn.lower(params, pool, pool, conv, ssd, *args,
                        *tail).compile()
    text = compiled.as_text()

    assert text.startswith("HloModule jit_" + program)
    # (a layer's pages are the whole pool here: XLA drops the one-layer
    # pool's unit dimension, and a decode step scatters its rows into it
    # in place)
    slabs = ["f32[{},{},{}]".format(slots, cfg.d_state, cfg.d_inner),
             "bf16[{},{},{}]".format(slots * row, ps, lanes)]
    if program == "prefill":
        slabs += ["bf16[{},{}]".format(
            slots, (cfg.d_conv - 1) * cfg.conv_channels)]
    for slab in slabs:
        assert not [line.strip()[:160] for line in text.splitlines()
                    if slab in line][:3], slab
    kernels = {name: len(re.findall(
        r"%" + name + r"(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)) for name in ("ssd_step", "paged_attention_grouped",
                            "chunk_attention", "moe_gmm")}
    if program == "decode":
        assert kernels == {"ssd_step": n_mamba,
                           "paged_attention_grouped": n_attn,
                           "chunk_attention": 0,
                           "moe_gmm": 2 * cfg.n_layers}
    else:
        assert kernels == {"ssd_step": 0, "paged_attention_grouped": 0,
                           "chunk_attention": n_attn,
                           "moe_gmm": 2 * cfg.n_layers}
        assert _page_writes(text, program,
                            (pages + 1, n_attn, ps, lanes)) == n_attn
    # the half share's grouped matmuls run over ALL the routed rows
    tokens = bucket if program == "prefill" else slots
    gmm_rows = {int(m) for m in re.findall(
        r"%moe_gmm(?:\.\d+)? = bf16\[(\d+),\d+\]\S* custom-call", text)}
    assert gmm_rows == {moe.share_capacity(tokens * cfg.top_k, 36, 72)}
    assert gmm_rows.pop() >= tokens * cfg.top_k
    aliased = {int(out): int(arg) for out, arg in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
        text.split("\n", 1)[0])}
    n_params = len(jax.tree_util.tree_leaves(params))
    assert aliased == {i: n_params + i for i in range(4)}
    memory = compiled.memory_analysis()
    print("granite {}: arguments {:.3f} GB, temporaries {:.3f} GB"
          .format(program, memory.argument_size_in_bytes / 1e9,
                  memory.temp_size_in_bytes / 1e9))
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < \
        15.2 * 2 ** 30


def test_pallas_compiler_params_construct():
    """Every ``compiler_params`` a pallas_call site passes must construct
    under the installed jax — the sites are only reached with
    ``interpret=False``, so no interpreter test ever runs them
    (``pltpu.TPUCompilerParams`` was such a name)."""
    from jax.experimental.pallas import tpu as pltpu
    from deepspeed_tpu.ops.pallas import ring_gemm
    for cid in ring_gemm.COLLECTIVE_IDS:
        kw = ring_gemm._compiler_kwargs(cid, interpret=False)
        assert isinstance(kw["compiler_params"], pltpu.CompilerParams)
        assert kw["compiler_params"].collective_id == cid
        assert ring_gemm._compiler_kwargs(cid, interpret=True) == {}


# ------------------------------------------- known-refused, off main path
class CompilerRefused(Exception):
    """The chip's compiler refused the kernel with the recorded message."""


def _expect_refusal(compile_fn, message):
    """Run ``compile_fn``: the compiler's refusal with ``message`` in it
    becomes :class:`CompilerRefused` (what the strict xfail expects);
    anything else — an import error, a renamed API, a different refusal
    — propagates and fails the test. A compile that passes makes the
    strict xfail fail: the kernel is no longer refused, drop the mark."""
    try:
        compile_fn()
    except Exception as err:  # noqa: BLE001 - re-raised unless it matches
        if message in str(err):
            raise CompilerRefused(str(err)[-400:]) from err
        raise


@pytest.mark.xfail(strict=True, raises=CompilerRefused, reason=(
    "ring GEMMs hold whole operands in VMEM with no grid: at TP4 "
    "b4 s1024 d1024 f4096 bf16 the chip's compiler refuses them — "
    "scoped VMEM over the 16 MiB limit"))
def test_ring_gemm_compiles_at_tp4(four_chips, no_persistent_cache):
    from deepspeed_tpu.ops.pallas.ring_gemm import ag_matmul_pallas
    from deepspeed_tpu.parallel.topology import shard_map_compat
    fn = shard_map_compat(
        lambda x, w: ag_matmul_pallas(x, w, "model", interpret=False),
        mesh=four_chips, in_specs=(P(None, "model", None),
                                   P(None, "model")),
        out_specs=P(None, None, "model"))
    x = jax.ShapeDtypeStruct((4, 1024, 1024), BF16, sharding=NamedSharding(
        four_chips, P(None, "model", None)))
    w = jax.ShapeDtypeStruct((1024, 4096), BF16, sharding=NamedSharding(
        four_chips, P(None, "model")))
    _expect_refusal(lambda: jax.jit(fn).lower(x, w).compile(),
                    "exceeded scoped vmem limit")


@pytest.mark.xfail(strict=True, raises=CompilerRefused, reason=(
    "block-sparse kernels cast a boolean mask inside the kernel: "
    "'Invalid vector register cast ... tpu.bitcast_vreg "
    "(vector<8x128xi1>) -> vector<8x128xi32>' on today's compiler"))
def test_block_sparse_attention_compiles(one_chip, no_persistent_cache):
    from deepspeed_tpu.ops.sparse_attention import (FixedSparsityConfig,
                                                    SparseSelfAttention)
    heads, seq, dh = 16, 4096, 64
    attn = SparseSelfAttention(
        FixedSparsityConfig(num_heads=heads, block=64),
        max_seq_length=seq, causal=True, interpret=False)
    _expect_refusal(
        lambda: _compile(lambda q, k, v: attn(q, k, v), one_chip,
                         *[((1, heads, seq, dh), BF16)] * 3),
        "tpu.bitcast_vreg")
