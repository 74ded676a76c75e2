"""Pallas kernel tier (ISSUE 11): paged attention + ring GEMMs vs their
XLA oracles, in interpreter mode on CPU.

Contracts pinned here (docs/pallas_kernels.md):

* the paged-attention page-walk kernel matches the slot/gather oracle
  within 1e-5 across page-boundary-crossing mixed lengths, NaN-poisoned
  recycled pools and garbage-page redirects, and greedy serving streams
  are BYTE-identical with the kernel on vs off;
* the ring-GEMM pallas backend matches the ppermute oracle at the PR 6
  tolerances (column bitwise fp32, row <= 5e-6, grads 1e-4) across
  world sizes 1/2/4, forward and backward;
* both tri-state config keys validate, resolve, and fall back LOUDLY
  (never silently);
* the shard-lint IR walker classifies ``pallas_call`` into the segment
  lattice (compute for the page walk, collective for the remote-copy
  ring) and ``engine.audit()`` stays clean with the kernels enabled;
* ``bin/ds_lint.py`` DSL005 flags ``pl.pallas_call`` sites outside
  ``deepspeed_tpu/ops/``.
"""
import contextlib
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu as deepspeed
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.models.gpt2 import _attend_cache_rows, _paged_attn_ctx
from deepspeed_tpu.ops.pallas.paged_attention import paged_attention
from deepspeed_tpu.parallel.collective_matmul import (
    CollectiveMatmulBinding, tp_column_matmul, tp_row_matmul)
from deepspeed_tpu.utils.logging import logger as ds_logger

pytestmark = pytest.mark.pallas


@contextlib.contextmanager
def _capture_warnings():
    """The DS logger has propagate=False, so caplog can't see it; attach
    a handler directly (the repo's test_telemetry idiom)."""
    messages = []

    class _Cap(logging.Handler):
        def emit(self, record):
            messages.append(record.getMessage())

    cap = _Cap(level=logging.WARNING)
    ds_logger.addHandler(cap)
    try:
        yield messages
    finally:
        ds_logger.removeHandler(cap)

_MESHES = {}


def _model_mesh(n):
    if n not in _MESHES:
        _MESHES[n] = Mesh(np.array(jax.devices()[:n]).reshape(n),
                          ("model",))
    return _MESHES[n]


def _binding(n, **kw):
    return CollectiveMatmulBinding(mesh=_model_mesh(n), axis="model", **kw)


# ===================================================== paged attention

def _paged_setup(seed=0, b=3, s=2, h=2, dh=8, ps=4, max_pages=8,
                 layers=2, usable_pages=12, poison=True):
    """A hand-built paged pool: NaN garbage page 0, NaN unallocated
    tail pages, random live content, slots at mixed lengths whose live
    windows CROSS page boundaries."""
    rng = np.random.RandomState(seed)
    k_pool = rng.randn(usable_pages + 1, layers, ps, h * dh) \
        .astype(np.float32)
    v_pool = rng.randn(usable_pages + 1, layers, ps, h * dh) \
        .astype(np.float32)
    if poison:
        k_pool[0] = np.nan
        v_pool[0] = np.nan
        k_pool[9:] = np.nan
        v_pool[9:] = np.nan
    # pos 5: mid-page; pos 13: crosses into page 3 with the 2 new
    # tokens landing on a page boundary (13 % 4 = 1 .. 14 % 4 = 2);
    # pos 3: the new tokens straddle pages 0 -> 1
    positions = np.array([5, 13, 3], np.int32)
    valid_lens = np.full((b,), s, np.int32)
    page_tables = np.zeros((b, max_pages), np.int32)
    page_tables[0, :2] = [3, 4]
    page_tables[1, :4] = [1, 2, 5, 6]
    page_tables[2, :2] = [7, 8]
    q = jnp.asarray(rng.randn(b, s, h, dh).astype(np.float32))
    return (q, jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(page_tables), jnp.asarray(positions),
            jnp.asarray(valid_lens), ps, max_pages)


def _gather_oracle(q, k_pool, v_pool, page_tables, positions, valid_lens,
                   ps, max_pages, layer):
    b, _, h, dh = q.shape

    def rows_of(cache):
        g = jnp.take(cache[:, layer], page_tables, axis=0)
        return g.reshape(b, max_pages * ps, h, dh).transpose(0, 2, 1, 3)

    return _attend_cache_rows(q, rows_of(k_pool), rows_of(v_pool),
                              positions, dh, valid_lens=valid_lens)


@pytest.mark.parametrize("layer", [0, 1])
def test_paged_attention_matches_gather_oracle(layer):
    # mixed lengths crossing page boundaries, NaN-poisoned garbage page
    # AND NaN unallocated pages: every live row within atol 1e-5
    (q, kp, vp, pt, pos, vl, ps, mp) = _paged_setup()
    got = paged_attention(q, kp, vp, pt, pos, vl, layer_idx=layer,
                          page_size=ps)
    want = _gather_oracle(q, kp, vp, pt, pos, vl, ps, mp, layer)
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_paged_attention_padded_valid_lens_stay_clean():
    # prefill-shaped call: only valid_lens tokens of the s-wide chunk
    # are real; VALID rows must match the oracle and stay finite even
    # with every stale lane NaN-poisoned (the V-zero guard)
    (q, kp, vp, pt, pos, vl, ps, mp) = _paged_setup(s=4)
    vl = jnp.asarray(np.array([2, 3, 1], np.int32))
    got = np.asarray(paged_attention(q, kp, vp, pt, pos, vl,
                                     layer_idx=0, page_size=ps))
    want = np.asarray(_gather_oracle(q, kp, vp, pt, pos, vl, ps, mp, 0))
    for i, n in enumerate([2, 3, 1]):
        assert np.isfinite(got[i, :n]).all()
        np.testing.assert_allclose(got[i, :n], want[i, :n], atol=1e-5,
                                   rtol=1e-5)


# The block walk: h 8 x dh 64 = 512 packed lanes and pages of 16 tokens;
# the kernel takes _BLOCK_TOKENS (512: 32 pages) a block whatever the
# pool's dtype, so a row of 20 pages is ONE block, never whole unless the
# slot fills the row, a row of 40 is walked as 32, 8 and one of 72 as 32,
# 32, 8. A full block comes whole behind one wait, a slot's last by its
# live pages, a dead slot (no query, or a row of the garbage page)
# fetches nothing and writes zeros.
_WALK_H, _WALK_DH, _WALK_PS = 8, 64, 16
# a slot the scheduler has not filled, as the DECODE program hands it:
# position 0, the launch's width of queries, a row of the garbage page
_UNFILLED = (0, 1, "unfilled")
# name: ((position, valid_len) per slot, s, max_pages, pool dtype[,
# (heads, d_head)])
_WALK_CASES = {
    "ends_in_first_page_of_second_block":
        ([(131, 1), (140, 1)], 1, 20, np.float32),
    "ends_on_a_blocks_last_token":
        ([(127, 1), (255, 1), (126, 1)], 1, 20, np.float32),
    "ends_in_third_block":
        ([(296, 1), (40, 1), (257, 1)], 1, 20, np.float32),
    "row_not_a_multiple_of_the_block":
        ([(319, 1), (300, 1)], 1, 20, np.float32),
    "row_smaller_than_the_block":
        ([(79, 1), (3, 1), (64, 1)], 1, 5, np.float32),
    "one_page_slots":
        ([(0, 1), (15, 1), (7, 1)], 1, 20, np.float32),
    "dead_slot_between_live_slots":
        ([(100, 1), (0, 0), (200, 1)], 1, 20, np.float32),
    "dead_slot_last":
        ([(130, 1), (37, 1), (0, 0)], 1, 20, np.float32),
    "two_queries_across_a_block_edge":
        ([(127, 2), (15, 2), (130, 1)], 2, 20, np.float32),
    "verify_width_padded":
        ([(126, 3), (250, 5), (9, 0), (14, 1)], 5, 20, np.float32),
    "verify_width_dead_slot_last":
        ([(255, 5), (60, 4), (0, 0)], 5, 20, np.float32),
    "bf16_pool":
        ([(296, 1), (127, 1), (0, 0), (31, 1)], 1, 20, jnp.bfloat16),
    "bf16_pool_verify_width":
        ([(126, 3), (250, 5), (14, 1)], 5, 20, jnp.bfloat16),
    # the prefetch is handed ACROSS dead slots: first, last, between
    "dead_slots_first":
        ([(0, 0), (0, 0), (600, 1), (17, 1)], 1, 40, np.float32),
    "dead_slots_first_last_and_between":
        ([(0, 0), (530, 1), (0, 0), _UNFILLED, (511, 1), (40, 1), (0, 0)],
         1, 40, np.float32),
    "unfilled_slots_of_a_decode_launch":
        ([_UNFILLED, (700, 1), _UNFILLED, _UNFILLED, (90, 1), _UNFILLED],
         1, 48, np.float32),
    "unfilled_slots_of_a_verify_launch":
        ([(508, 5), (0, 5, "unfilled"), (30, 2), (0, 5, "unfilled")],
         5, 40, np.float32),
    "every_slot_dead":
        ([(0, 0), _UNFILLED, (0, 0)], 1, 20, np.float32),
    "one_live_slot_alone":
        ([(1000, 1)], 1, 72, np.float32),
    # 512 tokens a block: the last live position 511 ends the first
    # block, 512 opens the second, 1,023 / 1,024 likewise a block on
    "ends_on_a_block_edge_and_a_token_past_it":
        ([(511, 1), (512, 1), (1023, 1), (1024, 1)], 1, 72, np.float32),
    "verify_width_across_a_block_edge":
        ([(510, 5), (1020, 5), (507, 5)], 5, 72, np.float32),
    "one_page_beside_a_full_row":
        ([(3, 1), (1151, 1), (15, 1), (1151, 1)], 1, 72, np.float32),
    "full_rows_not_a_multiple_of_the_block":
        ([(639, 1), (639, 1)], 1, 40, np.float32),
    # slot 0's last page leaves NaN rows 131.. in a buffer half that slot
    # 2's shorter block does not overwrite
    "stale_nan_rows_left_in_a_buffer_half":
        ([(130, 1), (20, 1), (99, 1)], 1, 20, np.float32),
    # Olmo-Hybrid's full layers: 30 heads of 128 over 3,840 lanes, where
    # the buffers' bytes give 8 float32 pages a block
    "30_heads_over_3840_lanes":
        ([(300, 1), (127, 1), _UNFILLED, (128, 1)], 1, 24, np.float32,
         (30, 128)),
}
# A bf16 pool against the float32 oracle: the weights enter the second
# matmul rounded to bf16, as the one-page float32 kernel's did on the
# chip (its matmuls ran at the chip's default precision). The tolerance
# is that kernel's own max abs error on these inputs on a TPU v5e
# (8.564e-4 and 1.73086e-3; PR 33's kernel bench, CHANGES.md); this one
# read 8.346e-4 and 1.73086e-3 there.
_WALK_BF16_ATOL = {"bf16_pool": 8.56e-4, "bf16_pool_verify_width": 1.7308e-3}


def _walk_setup(slots, s, max_pages, dtype, seed=0, heads=(_WALK_H, _WALK_DH)):
    """A pool where everything a slot must not see is NaN: the garbage
    page, every page no slot owns, and the tail of a slot's last page
    past its live window (a recycled page's stale content). Pages are
    dealt out of order, so a walk that trusts anything but the table
    fails; what a live slot's row holds PAST its pages names poisoned
    pages no slot owns (a recycled row's stale entries), an unfilled
    slot's row the garbage page."""
    rng = np.random.RandomState(seed)
    b, (h, dh), ps = len(slots), heads, _WALK_PS
    owned = [0 if len(slot) == 3 else -(-(slot[0] + slot[1]) // ps)
             for slot in slots]
    pages = sum(owned) + 3
    k_pool = np.full((pages + 1, 2, ps, h * dh), np.nan, np.float32)
    v_pool = np.full((pages + 1, 2, ps, h * dh), np.nan, np.float32)
    free = list(rng.permutation(np.arange(1, pages + 1)))
    page_tables = np.zeros((b, max_pages), np.int32)
    for i, (pos, n, *_) in enumerate(slots):
        for j in range(owned[i]):
            page = page_tables[i, j] = free.pop()
            live = min(ps, pos + n - j * ps)
            k_pool[page, :, :live] = rng.randn(2, live, h * dh)
            v_pool[page, :, :live] = rng.randn(2, live, h * dh)
    for i in range(b):
        if owned[i]:
            page_tables[i, owned[i]:] = free[i % 3]
    q = rng.randn(b, s, h, dh).astype(np.float32)
    positions, valid_lens = np.array(
        [slot[:2] for slot in slots], np.int32).reshape(b, 2).T
    return (jnp.asarray(q, dtype), jnp.asarray(k_pool, dtype),
            jnp.asarray(v_pool, dtype), jnp.asarray(page_tables),
            jnp.asarray(positions), jnp.asarray(valid_lens))


@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_paged_attention_block_walk(case):
    from deepspeed_tpu.ops.pallas.paged_attention import _pages_per_block
    slots, s, max_pages, dtype, *heads = _WALK_CASES[case]
    h, dh = heads[0] if heads else (_WALK_H, _WALK_DH)
    ps, itemsize = _WALK_PS, jnp.dtype(dtype).itemsize
    # 512 tokens, or what 8 MiB hold of K and V double-buffered
    assert _pages_per_block(max_pages, ps, h * dh, itemsize) == min(
        max_pages, 8 if h * dh * itemsize > 4096 else 32)
    q, kp, vp, pt, pos, vl = _walk_setup(slots, s, max_pages, dtype,
                                         heads=(h, dh))
    got = np.asarray(paged_attention(q, kp, vp, pt, pos, vl, layer_idx=1,
                                     page_size=ps))
    # no NaN of a dead page, a dead tail or a dead slot reaches a row
    assert np.isfinite(got).all()
    f32 = [a.astype(jnp.float32) for a in (q, kp, vp)]
    want = np.asarray(_gather_oracle(*f32, pt, pos, vl, ps, max_pages, 1))
    atol, rtol = (1e-5, 1e-5) if dtype == np.float32 else \
        (_WALK_BF16_ATOL[case], 0.0)
    for i, (_, n, *unfilled) in enumerate(slots):
        if unfilled or n == 0:
            assert not got[i].any()            # a dead slot writes zeros
            continue
        np.testing.assert_allclose(got[i, :n], want[i, :n], atol=atol,
                                   rtol=rtol)


def test_paged_attention_knows_the_garbage_page():
    """The walk sees an unfilled slot by the page its row begins on."""
    from deepspeed_tpu.inference.paging import GARBAGE_PAGE
    from deepspeed_tpu.ops.pallas.paged_attention import _GARBAGE_PAGE
    assert _GARBAGE_PAGE == GARBAGE_PAGE


def test_paged_attn_ctx_dispatch_parity_and_shared_writes():
    # the model-level dispatch: ctx within 1e-5 AND the cache WRITES
    # bitwise identical (the scatter is shared by both read paths)
    import dataclasses
    cfg = gpt2.GPT2Config(vocab_size=64, max_seq_len=32, n_layers=2,
                          n_heads=2, d_model=16,
                          use_flash_attention=False, remat=False,
                          loss_chunk=0)
    rng = np.random.RandomState(1)
    b, s, ps, mp = 2, 2, 4, 8
    block = jax.tree_util.tree_map(
        jnp.asarray, {
            "qkv_kernel": rng.randn(16, 48).astype(np.float32),
            "qkv_bias": rng.randn(48).astype(np.float32),
            "proj_kernel": rng.randn(16, 16).astype(np.float32),
            "proj_bias": rng.randn(16).astype(np.float32),
        })
    x = jnp.asarray(rng.randn(b, s, 16).astype(np.float32))
    k_pool = jnp.asarray(rng.randn(9, 2, ps, 2 * 8).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(9, 2, ps, 2 * 8).astype(np.float32))
    pt = np.zeros((b, mp), np.int32)
    pt[0, :2] = [1, 2]
    pt[1, :3] = [3, 4, 5]
    pos = jnp.asarray(np.array([5, 9], np.int32))
    vl = jnp.asarray(np.array([s, s], np.int32))
    outs = {}
    for kernel in ("xla", "pallas"):
        c = dataclasses.replace(cfg, paged_attention_kernel=kernel)
        outs[kernel] = _paged_attn_ctx(
            x, block, c, k_pool, v_pool, 1, pos, jnp.asarray(pt), vl, ps)
    ctx_x, kx, vx = outs["xla"]
    ctx_p, kp2, vp2 = outs["pallas"]
    np.testing.assert_allclose(np.asarray(ctx_p), np.asarray(ctx_x),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(kx), np.asarray(kp2))
    np.testing.assert_array_equal(np.asarray(vx), np.asarray(vp2))


def _tiny_model():
    cfg = gpt2.GPT2Config(vocab_size=128, max_seq_len=48, n_layers=2,
                          n_heads=2, d_model=32,
                          use_flash_attention=False, remat=False,
                          loss_chunk=0)
    return gpt2.make_gpt2_model(config=cfg)


_PAGED_BASE = {"max_batch_size": 2, "prefill_buckets": [8, 16],
               "dtype": "fp32", "greedy": True, "max_new_tokens": 4,
               "kv_block_size": 4}


def test_engine_greedy_streams_byte_identical():
    # the acceptance bit: greedy serving streams equal with the kernel
    # on vs off (and both equal the dense chain: the uncached forward
    # over the whole sequence, argmax, no serving path at all)
    model = _tiny_model()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 128, size=n).tolist() for n in (5, 9, 3)]
    streams = {}
    for name, kernel in (("paged_xla", "xla"), ("paged_pallas", "pallas")):
        eng = deepspeed.init_inference(model=model, config={
            "inference": dict(_PAGED_BASE, paged_attention_kernel=kernel)})
        streams[name] = eng.generate(prompts)

    @jax.jit
    def dense(ids):        # one program: the sequence rides zero-padded
        return gpt2.forward_hidden(model.params, ids, model.config,
                                   train=False)[0] @ model.params["wte"].T

    def dense_chain(prompt):
        seq = list(prompt)
        for _ in range(_PAGED_BASE["max_new_tokens"]):
            ids = np.zeros((1, model.config.max_seq_len), np.int32)
            ids[0, :len(seq)] = seq
            seq.append(int(np.asarray(dense(ids))[len(seq) - 1].argmax()))
        return seq[len(prompt):]

    assert streams["paged_pallas"] == streams["paged_xla"]
    assert streams["paged_pallas"] == [dense_chain(p) for p in prompts]


def test_paged_attention_kernel_config_gate():
    model = _tiny_model()
    # invalid value raises at config parse
    from deepspeed_tpu.inference.config import (
        DeepSpeedInferenceConfig, DeepSpeedInferenceConfigError)
    with pytest.raises(DeepSpeedInferenceConfigError):
        DeepSpeedInferenceConfig(
            {"inference": {"paged_attention_kernel": "cuda"}})
    # auto resolves to the XLA gather path off-TPU
    eng = deepspeed.init_inference(
        model=model, config={"inference": dict(_PAGED_BASE)})
    assert eng.paged_attention_kernel == "xla"
    # explicit pallas resolves pallas (interpreter mode)...
    eng = deepspeed.init_inference(
        model=model,
        config={"inference": dict(_PAGED_BASE,
                                  paged_attention_kernel="pallas")})
    assert eng.paged_attention_kernel == "pallas"
    # ...for a prompt chunk's read too (chunk_attention); the serving
    # config, which every oracle comparison closes over, stays the gather
    assert eng.prefill_attention_kernel == "pallas"
    assert eng.model_config.paged_attention_kernel == "xla"
    # ...with no kv_* key set too: every engine has page tables to walk
    eng = deepspeed.init_inference(
        model=model,
        config={"inference": {"max_batch_size": 2, "dtype": "fp32",
                              "paged_attention_kernel": "pallas"}})
    assert eng.paged_attention_kernel == "pallas"


def test_decode_program_carries_pallas_and_audits_clean():
    # the decode family runs the page walk, a kernel a layer; prefill
    # two: the page write (kv_cache.write_tokens) and the chunk's read
    # (chunk_attention); the IR walker classifies the calls as compute
    # segments; audit is clean
    from deepspeed_tpu.analysis.ir import walk
    from deepspeed_tpu.analysis.programs import collect_inference_programs
    eng = deepspeed.init_inference(
        model=_tiny_model(),
        config={"inference": dict(_PAGED_BASE,
                                  paged_attention_kernel="pallas")})
    specs = {s.name: s for s in collect_inference_programs(eng)}
    decode = walk(jax.make_jaxpr(specs["decode"].build())
                  (*specs["decode"].args))
    calls = [e for e in decode.eqns if e.prim == "pallas_call"]
    assert len(calls) == eng.model_config.n_layers
    assert all(e.kind == "compute" for e in calls)
    prefill = walk(jax.make_jaxpr(specs["prefill/b8"].build())
                   (*specs["prefill/b8"].args))
    calls = [e for e in prefill.eqns if e.prim == "pallas_call"]
    assert len(calls) == 2 * eng.model_config.n_layers
    assert all(e.kind == "compute" for e in calls)
    assert [name for e in calls for name in ("kv_page_write",
                                             "chunk_attention")
            if name in str(e.eqn)] == \
        ["kv_page_write", "chunk_attention"] * eng.model_config.n_layers
    report = eng.audit()
    assert report.findings == [], [f.key for f in report.findings]


# ========================================================== ring GEMMs

TOL_ROW = dict(atol=5e-6, rtol=5e-6)
TOL_GRAD = dict(atol=1e-4, rtol=1e-4)


def _xw(rng, b, s, d, f, dtype=np.float32):
    return (jnp.asarray(rng.randn(b, s, d).astype(dtype)),
            jnp.asarray(rng.randn(d, f).astype(dtype)))


@pytest.mark.parametrize("n", [1, 2, 4])
def test_ring_column_forward_bitwise(n):
    rng = np.random.RandomState(3)
    x, w = _xw(rng, 2, 8, 16, 8 * max(n, 1))
    got = tp_column_matmul(x, w, _binding(n, backend="pallas"))
    want = tp_column_matmul(x, w, _binding(n, backend="ppermute"))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [1, 2, 4])
def test_ring_row_forward(n):
    rng = np.random.RandomState(4)
    f = 8 * max(n, 1)
    x, w = _xw(rng, 2, 8, f, 16)
    got = tp_row_matmul(x, w, _binding(n, backend="pallas"))
    want = tp_row_matmul(x, w, _binding(n, backend="ppermute"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **TOL_ROW)


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("kind", ["column", "row"])
def test_ring_backward_matches_ppermute(n, kind):
    rng = np.random.RandomState(5)
    if kind == "column":
        x, w = _xw(rng, 1, 8, 8, 8 * n)
        op = tp_column_matmul
    else:
        x, w = _xw(rng, 1, 8, 8 * n, 8)
        op = tp_row_matmul
    gp = jax.grad(lambda x, w: jnp.sum(
        op(x, w, _binding(n, backend="pallas")) ** 2),
        argnums=(0, 1))(x, w)
    go = jax.grad(lambda x, w: jnp.sum(
        op(x, w, _binding(n, backend="ppermute")) ** 2),
        argnums=(0, 1))(x, w)
    for a, b in zip(gp, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **TOL_GRAD)


def test_ring_bf16_wire_policy():
    # the lossy half-width hop: pallas matches the ppermute bf16 wire
    # closely (same cast points: rotated payloads only)
    rng = np.random.RandomState(6)
    x, w = _xw(rng, 2, 8, 16, 16)
    got = tp_column_matmul(x, w, _binding(4, backend="pallas",
                                          dtype="bf16"))
    want = tp_column_matmul(x, w, _binding(4, backend="ppermute",
                                           dtype="bf16"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # and stays a bf16-grade approximation of the exact product
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               atol=0.3, rtol=0.05)


def test_ring_backend_config_validation():
    from deepspeed_tpu.runtime.comm.config import CollectiveMatmulConfig
    assert CollectiveMatmulConfig({"backend": "pallas"}).backend == \
        "pallas"
    assert CollectiveMatmulConfig({}).backend == "ppermute"
    with pytest.raises(ValueError):
        CollectiveMatmulConfig({"backend": "nccl"})
    # backend=pallas with TP fusion off is fully inert (the zero3 ring
    # gather deliberately stays ppermute): loud no-op, raise under strict
    with pytest.raises(ValueError):
        CollectiveMatmulConfig({"enabled": True, "backend": "pallas",
                                "tensor_parallel": False,
                                "strict": True})
    # chunks stays honored on every ppermute path (the zero gather and
    # the loud-fallback loops) — accepted under the pallas backend
    assert CollectiveMatmulConfig({"backend": "pallas",
                                   "chunks": 2}).chunks == 2


def test_ring_multi_axis_mesh_falls_back_loudly_off_tpu():
    # DP x TP mesh: the interpreter's remote-copy simulation addresses
    # one named axis, so off-TPU the dispatch warns and runs the
    # ppermute loop — outputs stay bitwise the oracle's
    import deepspeed_tpu.parallel.collective_matmul as cm
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    bind = CollectiveMatmulBinding(mesh=mesh, axis="model",
                                   backend="pallas")
    rng = np.random.RandomState(7)
    x, w = _xw(rng, 2, 8, 16, 16)
    cm._warn_fallback_once.cache_clear()
    with _capture_warnings() as messages:
        got = tp_column_matmul(x, w, bind)
    want = tp_column_matmul(
        x, w, CollectiveMatmulBinding(mesh=mesh, axis="model",
                                      backend="ppermute"))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert any("multi-axis mesh" in m for m in messages)


def test_ring_walker_classifies_collective():
    from deepspeed_tpu.analysis.ir import walk
    from deepspeed_tpu.ops.pallas.ring_gemm import ag_matmul_pallas
    from deepspeed_tpu.parallel.topology import shard_map_compat
    mesh = _model_mesh(2)
    fn = shard_map_compat(
        lambda x, w: ag_matmul_pallas(x, w, "model"), mesh=mesh,
        in_specs=(P(None, "model", None), P(None, "model")),
        out_specs=P(None, None, "model"))
    res = walk(jax.make_jaxpr(fn)(jnp.zeros((2, 8, 16)),
                                  jnp.zeros((16, 16))))
    calls = [e for e in res.eqns if e.prim == "pallas_call"]
    assert calls and all(e.kind == "collective" for e in calls)


def test_ring_engine_training_matches_ppermute(tmp_path):
    # single-axis (pure TP) mesh so the kernels run for real on CPU:
    # fused-vs-fused losses match across 3 steps, the comm_overlap
    # telemetry reports the allgather class fused on BOTH backends, and
    # the shard-lint audit stays green with the kernels in the program
    from deepspeed_tpu.parallel.topology import build_mesh
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    def run(backend):
        cfg = gpt2.GPT2Config(vocab_size=128, max_seq_len=32, n_layers=2,
                              n_heads=2, d_model=64,
                              use_flash_attention=False, remat=False,
                              loss_chunk=0)
        eng = DeepSpeedEngine(
            model=gpt2.make_gpt2_model(config=cfg),
            mesh=build_mesh(model=2),
            config_params={
                "train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "steps_per_print": 10 ** 9,
                "telemetry": {"enabled": True,
                              "output_path": str(tmp_path / backend)},
                "comm": {"collective_matmul": {
                    "enabled": True, "backend": backend}}})
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 128, size=(1, 2, 32)).astype(np.int32)
        losses = [float(eng.train_batch(batch=(ids, ids.copy())))
                  for _ in range(3)]
        return eng, losses

    eng_p, lp = run("pallas")
    eng_o, lo = run("ppermute")
    np.testing.assert_allclose(lp, lo, atol=1e-5, rtol=1e-6)
    # comm_overlap is backend-INVARIANT: wire bytes and fused classes
    # depend on the decomposition, not on who constructs the overlap
    over_p = eng_p.telemetry_snapshot()["comm_overlap_last"]
    over_o = eng_o.telemetry_snapshot()["comm_overlap_last"]
    assert over_p is not None and set(over_p) == {"allgather", "reduce"}
    for cls in ("allgather", "reduce"):
        assert over_p[cls]["bytes"] == over_o[cls]["bytes"]
        assert over_p[cls]["fused"] == over_o[cls]["fused"]
    assert eng_p._cm_tp
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, size=(2, 32)).astype(np.int32)
    report = eng_p.audit(batch=(ids, ids.copy()))
    assert report.findings == [], [f.key for f in report.findings]


# ============================================================= DSL005

def test_dsl005_flags_pallas_call_outside_ops(tmp_path):
    from deepspeed_tpu.analysis import astlint
    pkg = tmp_path / "deepspeed_tpu"
    (pkg / "ops" / "pallas").mkdir(parents=True)
    (pkg / "models").mkdir(parents=True)
    body = ("from jax.experimental import pallas as pl\n"
            "def f(x):\n"
            "    return pl.pallas_call(lambda i, o: None,\n"
            "                          out_shape=None)(x)\n")
    (pkg / "ops" / "pallas" / "good.py").write_text(body)
    (pkg / "models" / "bad.py").write_text(body)
    findings = astlint.lint_paths([str(pkg)], base=str(tmp_path))
    keys = [k for k in findings if k.startswith("DSL005")]
    assert keys == ["DSL005:deepspeed_tpu/models/bad.py::f"], findings
