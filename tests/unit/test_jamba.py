"""Jamba through ``init_inference()`` at a tiny size on the CPU: one
period of 14 layers with one attention layer, widths cut (only here),
against the float32 reference ``benchmark/models/jamba_reference.py``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as deepspeed
from benchmark.models import jamba_reference as reference
from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.models import gpt2, jamba
from deepspeed_tpu.ops.pallas import mamba as kernels

MODEL = {
    "attn_layer_offset": 3, "attn_layer_period": 14, "hidden_size": 64,
    "intermediate_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 256,
    "num_attention_heads": 4, "num_experts": 1, "num_hidden_layers": 14,
    "num_key_value_heads": 1, "rms_norm_eps": 1e-6, "vocab_size": 128,
    # 1 / sqrt(hidden_size): a signal passes through the narrow layers
    # as it does through the published widths at 0.02, so a state
    # wrongly carried changes the tokens
    "initializer_range": 0.125}
SEED = 5
VOCAB = MODEL["vocab_size"]


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _config(**overrides):
    return jamba.config_from_hf(MODEL, dtype=jnp.float32, **overrides)


def _engine(slots=3, buckets=(8, 16), num_pages=40,
            paged_attention_kernel="xla", **overrides):
    return deepspeed.init_inference(
        model=jamba.make_jamba_model(_config(**overrides), seed=SEED),
        config={"inference": {
            "max_batch_size": slots, "dtype": "fp32",
            "kv_block_size": 4, "num_pages": num_pages, "max_seq_len": 64,
            "paged_attention_kernel": paged_attention_kernel,
            "prefill_buckets": list(buckets), "greedy": True,
            "max_new_tokens": 8}})


def _ids(n, salt=0):
    return np.random.default_rng([SEED, salt]).integers(0, VOCAB, n)


def _ref_logits(ids, positions=None):
    """The reference's logits at ``positions`` of ``ids``; padded to
    one length (the model is causal: what follows changes nothing), so
    the reference compiles once."""
    positions = np.arange(len(ids)) if positions is None else positions
    padded = np.zeros((64,), np.int64)
    padded[:len(ids)] = ids
    return np.asarray(reference.logits_at(MODEL, SEED, padded, positions))


def _greedy_chain(prompt, n):
    """The reference's greedy continuation: n tokens after prompt."""
    ids = list(prompt)
    for _ in range(n):
        ids.append(int(_ref_logits(ids, [len(ids) - 1])[0].argmax()))
    return ids[len(prompt):]


class _Tap:
    """The logits the engine's programs return last."""

    def __init__(self, engine):
        self.engine, self.last, self.all = engine, None, []
        for name in ("_get_prefill_fn", "_get_decode_fn"):
            self._wrap(name, getattr(engine, name))

    def _wrap(self, name, make):
        def tapped_make(*args, **kwargs):
            program = make(*args, **kwargs)

            def tapped(*a, **k):
                out = program(*a, **k)
                self.last = np.asarray(out[-1])
                self.all.append(self.last.reshape(-1, VOCAB))
                return out
            return tapped
        setattr(self.engine, name, tapped_make)


def test_param_count_at_the_published_sizes():
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "hidden_size": 2560, "intermediate_size": 8192,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "num_attention_heads": 20,
        "num_hidden_layers": 28, "num_key_value_heads": 1,
        "vocab_size": 65536, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 262144}
    assert reference.param_count(published) == 3029337472
    assert jamba.num_params(jamba.config_from_hf(published)) == 3029337472
    assert [i for i in range(28) if reference.is_attention(published, i)] \
        == [7, 21]


def test_model_without_cache_matches_the_reference():
    cfg = _config()
    model = jamba.make_jamba_model(cfg, seed=SEED)
    assert jamba.num_params(cfg) == reference.param_count(MODEL) == sum(
        x.size for x in jax.tree_util.tree_leaves(model.params))
    ids = _ids(40)
    hidden = jamba.forward_hidden(model.params, jnp.asarray(ids)[None], cfg)
    got = np.asarray(jamba.logits(model.params, hidden))[0]
    np.testing.assert_allclose(got, _ref_logits(ids), atol=2e-5)


@pytest.mark.parametrize("n", [8, 11, 16, 23],
                         ids=["full_bucket", "padded_bucket",
                              "largest_bucket", "two_chunks"])
def test_prefill_then_decode_through_the_scheduler(n):
    """One chunk = two chunks = a padded bucket = the reference's full
    forward: the prompt's last logits, and then every decode step's."""
    engine = _engine()
    tap = _Tap(engine)
    prompt = _ids(n, salt=n).tolist()
    sched = ContinuousBatchingScheduler(engine)
    uid = sched.submit(prompt, max_new_tokens=6, eos_token_id=None)
    sched.run()
    tokens = sched.results[uid]
    assert tokens == _greedy_chain(prompt, 6)
    chunks = 2 if n > 16 else 1
    # the programs that ran: each chunk's prefill, then five decodes
    assert len(tap.all) == chunks + 5
    ref = _ref_logits(prompt + tokens, np.arange(n - 1, n + 5))
    got = np.stack([rows[0] for rows in tap.all[chunks - 1:]])
    np.testing.assert_allclose(got, ref, atol=3e-5)


def test_prefill_logits_one_chunk_two_chunks_and_padding():
    """``engine.prefill_chunk`` directly: a 23-token prompt as chunks
    of 16 + 7 (the second padded to 8) leaves the slot as one forward
    over 23 tokens would; the decode after it agrees with the
    reference."""
    engine = _engine(buckets=(8, 16, 32))
    tap = _Tap(engine)
    ids = _ids(24, salt=1).tolist()
    ref = _ref_logits(ids, [22, 23])
    assert engine.try_admit(0, ids[:23])
    engine.prefill_chunk(0, ids[:16], 0)
    engine.prefill_chunk(0, ids[16:23], 16)
    np.testing.assert_allclose(tap.last, ref[0], atol=3e-5)
    assert engine.try_admit(1, ids[:23])
    engine.prefill_chunk(1, ids[:23], 0)                  # bucket 32
    np.testing.assert_allclose(tap.last, ref[0], atol=3e-5)
    for slot in (0, 1):
        assert engine.ensure_pages(slot, 24)
    tokens = np.zeros((engine.num_slots,), np.int32)
    tokens[:2] = ids[23]
    engine.decode_step(tokens, active=[0, 1])
    got = tap.last.reshape(engine.num_slots, VOCAB)
    np.testing.assert_allclose(got[0], ref[1], atol=3e-5)
    np.testing.assert_allclose(got[1], ref[1], atol=3e-5)
    # both ways leave the same state behind
    conv, ssm = (np.asarray(a) for a in engine.state.arrays)
    np.testing.assert_allclose(conv[:, 0], conv[:, 1], atol=1e-5)
    np.testing.assert_allclose(ssm[:, 0], ssm[:, 1], atol=1e-5)


def _poison(engine):
    engine.state.update(tuple(jnp.full_like(a, jnp.nan)
                              for a in engine.state.arrays))
    engine.kv.update(tuple(jnp.full_like(a, jnp.nan)
                           for a in engine.kv.buffers()))


def test_a_reused_slot_starts_from_zero_state_under_nan_poison():
    """Every slot's state and every page NaN beforehand, one slot, four
    requests through it one after the other: each stream is the
    reference's, so the first chunk's program reset the state and no
    idle or retired slot's NaN reached a live one."""
    engine = _engine(slots=2, num_pages=24)
    _poison(engine)
    sched = ContinuousBatchingScheduler(engine)
    prompts = [_ids(n, salt=100 + n).tolist() for n in (5, 19, 9, 12)]
    uids = [sched.submit(p, max_new_tokens=4, eos_token_id=None)
            for p in prompts]
    results = sched.run()
    for uid, prompt in zip(uids, prompts):
        assert results[uid] == _greedy_chain(prompt, 4)
    snap = sched.metrics.snapshot()["state_pool"]
    assert snap["resets"] == 4 and snap["slots"] == 2
    assert snap["bytes"] == engine.state.nbytes


def test_a_slot_between_two_chunks_keeps_its_state_through_a_decode():
    """A decode step runs for every slot while slot 1 is between the
    two chunks of its prompt: its state must be what chunk one left."""
    engine = _engine(slots=2, buckets=(8, 16))
    sched = ContinuousBatchingScheduler(engine)
    short, long_ = _ids(6, salt=7).tolist(), _ids(23, salt=8).tolist()
    a = sched.submit(short, max_new_tokens=8, eos_token_id=None)
    sched.step()                        # a decodes from here on
    b = sched.submit(long_, max_new_tokens=4, eos_token_id=None)
    results = sched.run()
    assert results[a] == _greedy_chain(short, 8)
    assert results[b] == _greedy_chain(long_, 4)


def test_preemption_and_resume_give_the_same_tokens():
    """A pool too small for both answers: the younger request is
    preempted, re-prefills prompt + tokens so far (which rebuilds its
    state from zeros) and ends with the tokens it would have had."""
    engine = _engine(slots=2, num_pages=16)
    sched = ContinuousBatchingScheduler(engine)
    prompts = [_ids(9, salt=21).tolist(), _ids(10, salt=22).tolist()]
    uids = [sched.submit(p, max_new_tokens=30, eos_token_id=None)
            for p in prompts]
    results = sched.run()
    assert sched.preemptions >= 1
    for uid, prompt in zip(uids, prompts):
        assert results[uid] == _greedy_chain(prompt, 30)


# ---------------------------------------------------------------- kernels
def _scan_inputs(T, di, n, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (f(T, di), jnp.abs(f(T, di)) * 0.1, f(T, n), f(T, n),
            -jnp.abs(f(n, di)) - 0.1, f(n, di))


@pytest.mark.pallas
@pytest.mark.parametrize("valid", [16, 11, 3])
def test_scan_kernel_in_interpret_mode_matches_lax_scan(valid):
    x, dt, B, C, A, h0 = _scan_inputs(16, 256, 16)
    want_y, want_h = kernels.mamba_scan_xla(x, dt, B, C, A, h0, valid)
    y, h = kernels.mamba_scan(x, dt, B, C, A, h0, jnp.int32(valid),
                              interpret=True, lane_block=128, time_tile=8)
    np.testing.assert_allclose(y[:valid], want_y[:valid], atol=1e-5)
    np.testing.assert_allclose(h, want_h, atol=1e-5)
    # positions past valid changed nothing: the state is the state
    # after `valid` tokens of the unpadded chunk
    _, short = kernels.mamba_scan_xla(x[:valid], dt[:valid], B[:valid],
                                      C[:valid], A, h0, valid)
    np.testing.assert_allclose(h, short, atol=1e-5)


@pytest.mark.pallas
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_step_kernel_in_interpret_mode_matches_xla(dtype):
    rng = np.random.default_rng(3)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    pool = f(3, 16, 16, 256).astype(dtype)
    x, dt, B, C = f(16, 256), jnp.abs(f(16, 256)) * 0.1, f(16, 16), f(16, 16)
    dt = dt.at[5].set(0.0)                         # a slot held back
    A = -jnp.abs(f(16, 256)) - 0.1
    want_y, want_pool = kernels.mamba_step_xla(pool, 1, x, dt, B, C, A)
    y, got = kernels.mamba_step(pool, 1, x, dt, B, C, A, interpret=True,
                                lane_block=128)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(y, want_y, atol=tol, rtol=tol)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want_pool.astype(jnp.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_array_equal(got[1, 5], pool[1, 5])
    np.testing.assert_array_equal(got[0], pool[0])
    np.testing.assert_array_equal(got[2], pool[2])


@pytest.mark.pallas
@pytest.mark.parametrize("b, s, h, kvh, max_pages", [
    (3, 1, 4, 1, 20), (2, 3, 4, 2, 9), (2, 1, 20, 1, 5)],
    ids=["multi_query", "grouped_verify_width", "twenty_on_one"])
def test_grouped_paged_attention_matches_the_gather(b, s, h, kvh,
                                                    max_pages):
    """The paged kernel for fewer key-value heads than query heads
    against the XLA gather path, the garbage page NaN: the same live
    entries, nothing of a page past the live window."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention
    rng = np.random.default_rng(b * 100 + s)
    dh, ps, pages = 128, 16, 40
    pools = [jnp.asarray(rng.standard_normal((pages + 1, 2, ps, kvh * dh)),
                         jnp.float32).at[0].set(jnp.nan) for _ in range(2)]
    positions = rng.integers(0, max_pages * ps - s - 1, b)
    positions[0] = 0
    tables = np.zeros((b, max_pages), np.int32)
    for i in range(b):
        n = (int(positions[i]) + s - 1) // ps + 1
        tables[i, :n] = rng.choice(np.arange(1, pages + 1), n,
                                   replace=False)
    positions = jnp.asarray(positions, jnp.int32)
    valid = jnp.full((b,), s, jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
    cfg = jamba.JambaConfig(n_heads=h, n_kv_heads=kvh, d_model=h * dh)

    def rows_of(cache):
        return cache[jnp.asarray(tables), 1].reshape(b, max_pages * ps,
                                                     kvh, dh)

    want = jamba._attend(q, rows_of(pools[0]), rows_of(pools[1]),
                         positions, valid, cfg).reshape(b, s, h, dh)
    got = paged_attention(q, pools[0], pools[1], jnp.asarray(tables),
                          positions, valid, layer_idx=1, page_size=ps,
                          interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.pallas
def test_the_engine_with_the_kernels_interpreted_matches_the_oracles():
    prompt = _ids(19, salt=31).tolist()
    streams = []
    for kernel in ("xla", "pallas"):
        engine = _engine(scan_kernel=kernel,
                         paged_attention_kernel=kernel)
        assert engine.paged_attention_kernel == kernel
        streams.append(engine.generate([prompt], max_new_tokens=5,
                                       eos_token_id=None)[0])
    assert streams[0] == streams[1] == _greedy_chain(prompt, 5)


# --------------------------------------------------------------- refusals
def _refused(match, **inference):
    config = {"max_batch_size": 2, "dtype": "fp32",
              "kv_block_size": 4, "num_pages": 16, "max_seq_len": 64,
              "prefill_buckets": [8]}
    config.update(inference)
    with pytest.raises(ValueError, match=match):
        deepspeed.init_inference(
            model=jamba.make_jamba_model(_config(), seed=SEED),
            config={"inference": config},
            draft_model=inference.pop("_draft", None))


def test_prefix_cache_refuses_recurrent_layers():
    _refused("prefix caching .* recurrent layers", prefix_caching=True)


def test_drafter_refuses_recurrent_layers():
    _refused("speculative decoding .* recurrent layers",
             speculative={"enabled": True, "method": "ngram"})


def test_a_recurrent_draft_model_is_refused():
    from deepspeed_tpu.inference.speculative import ModelDrafter
    with pytest.raises(ValueError, match="draft model.* recurrent"):
        ModelDrafter(jamba.make_jamba_model(_config(), seed=SEED), 2, 64,
                     jnp.float32)


@pytest.mark.parametrize("role", ["PrefillRole", "DecodeRole"])
def test_fleet_hand_off_refuses_recurrent_layers(role):
    from deepspeed_tpu.inference.fleet import roles
    with pytest.raises(ValueError, match="hand-off .* recurrent layers"):
        getattr(roles, role)(_engine())


def test_a_model_mesh_axis_refuses_the_family():
    from deepspeed_tpu.parallel.topology import build_mesh
    mesh = build_mesh(data=jax.device_count() // 2, model=2)
    with pytest.raises(ValueError, match="no tensor-parallel layout"):
        deepspeed.init_inference(
            model=jamba.make_jamba_model(_config(), seed=SEED), mesh=mesh,
            config={"inference": {"dtype": "fp32"}})


def test_the_engine_imports_no_model_module():
    import ast
    import os
    root = os.path.dirname(deepspeed.__file__)
    for name in ("engine.py", "kv_cache.py", "decoder.py"):
        tree = ast.parse(open(os.path.join(root, "inference", name)).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                assert "models" not in (node.module or "").split("."), name
                assert not any(a.name in ("models", "gpt2", "jamba")
                               for a in node.names), name


def test_gpt2_goes_through_the_same_protocol():
    model = gpt2.make_gpt2_model(
        config=gpt2.GPT2Config(vocab_size=64, max_seq_len=32, n_layers=2,
                               n_heads=2, d_model=16,
                               use_flash_attention=False, remat=False))
    spec = model.decoder.cache_spec()
    assert (spec.kv_layers, spec.kv_heads, spec.d_head, spec.state) == \
        (2, 2, 8, ())
    engine = deepspeed.init_inference(model=model, config={"inference": {
        "max_batch_size": 2, "dtype": "fp32",
        "kv_block_size": 4, "prefill_buckets": [8]}})
    assert engine.state is None and not getattr(
        engine.decoder, "recurrent", False)
    assert "state_pool" not in engine.serving_metrics.snapshot()


def test_the_audit_lowers_the_programs_with_their_state_pool():
    """``engine.audit()`` (the AOT shard-lint) builds each serving
    program's arguments itself: with the state arrays, the slot and the
    advance mask in their places, every program traces and every
    donated buffer (pages AND state) is aliased."""
    engine = _engine()
    from deepspeed_tpu.analysis.auditor import engine_program_specs
    specs = engine_program_specs(engine)
    assert sorted(s.name for s in specs) == ["decode", "prefill/b16",
                                             "prefill/b8"]
    for spec in specs:
        assert spec.donate == (1, 2, 3, 4)
        out = jax.eval_shape(spec.build(), *spec.args)
        assert [o.shape for o in out[:4]] == \
            [a.shape for a in spec.args[1:5]]
    report = engine.audit()
    assert not [f for f in report.findings if "donat" in str(f).lower()]


def test_the_allocator_counts_its_shared_pages():
    """``shared_pages`` is what lets a decode step skip the copy-on-
    write walk: the number of pages held more than once, through ref,
    free and fork."""
    from deepspeed_tpu.inference.paging import PageAllocator
    alloc = PageAllocator(4)
    a, b = alloc.alloc(), alloc.alloc()
    assert alloc.shared_pages == 0
    alloc.ref(a)
    alloc.ref(a)
    alloc.ref(b)
    assert alloc.shared_pages == 2
    alloc.free(a)                       # 3 -> 2: still shared
    assert alloc.shared_pages == 2
    new, forked = alloc.fork(b)         # 2 -> 1 and a fresh page
    assert forked and alloc.shared_pages == 1
    alloc.free(a)
    assert alloc.shared_pages == 0
    for page in (a, b, new):
        alloc.free(page)
    assert alloc.shared_pages == 0 and alloc.free_pages == 4
