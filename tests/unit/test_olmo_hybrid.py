"""Olmo Hybrid through ``init_inference()`` at a tiny size on the CPU:
two periods of three linear layers and a full one, widths cut (only
here), against the float32 reference
``benchmark/models/olmo_hybrid_reference.py``."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as deepspeed
from benchmark.models import olmo_hybrid_reference as reference
from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.models import olmo_hybrid

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
MODEL = {
    "model_type": "olmo_hybrid", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "max_position_embeddings": 256, "attention_bias": False,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "layer_types": PERIOD * 2, "linear_num_key_heads": 4,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 32, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
    # 1 / sqrt(hidden_size): a signal passes through the narrow layers
    "initializer_range": 0.125}
SEED = 5
VOCAB = MODEL["vocab_size"]


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _config(**overrides):
    return olmo_hybrid.config_from_hf(MODEL, dtype=jnp.float32, **overrides)


def _engine(slots=3, buckets=(8, 16), num_pages=40,
            paged_attention_kernel="xla", **overrides):
    return deepspeed.init_inference(
        model=olmo_hybrid.make_olmo_hybrid_model(_config(**overrides),
                                                 seed=SEED),
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
    one length (the model is causal), so the reference compiles once."""
    positions = np.arange(len(ids)) if positions is None else positions
    padded = np.zeros((64,), np.int64)
    padded[:len(ids)] = ids
    return np.asarray(reference.logits_at(MODEL, SEED, padded, positions))


def _greedy_chain(prompt, n):
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
    published = dict(
        MODEL, vocab_size=100352, hidden_size=3840,
        intermediate_size=11008, num_hidden_layers=32,
        num_attention_heads=30, num_key_value_heads=30,
        layer_types=PERIOD * 8, linear_num_key_heads=30,
        linear_num_value_heads=30, linear_key_head_dim=96,
        linear_value_head_dim=192, max_position_embeddings=65536)
    del published["initializer_range"]
    assert reference.param_count(published) == 7430870688
    cfg = olmo_hybrid.config_from_hf(published)
    assert olmo_hybrid.num_params(cfg) == 7430870688
    assert cfg.conv_channels == 11520 and len(cfg.full_layers) == 8
    # the cell's cut: layers 0-15
    cut = dict(published, num_hidden_layers=16, layer_types=PERIOD * 4)
    assert reference.param_count(cut) == 4100788944
    # a slot's state in the pool: 27.4 MB, no lane of padding
    spec = olmo_hybrid.OlmoHybridDecoder(
        olmo_hybrid.config_from_hf(cut)).cache_spec()
    assert (spec.kv_layers, spec.kv_heads, spec.d_head) == (4, 30, 128)
    conv, gdn = spec.state
    assert conv.lead == gdn.lead == (12,) and gdn.tail == (96, 5760)
    assert gdn.tail[1] % 128 == 0 and gdn.dtype == jnp.float32
    assert 12 * (96 * 5760 * 4 + conv.tail[0] * 2) == 27371520


def test_model_without_cache_matches_the_reference():
    cfg = _config()
    model = olmo_hybrid.make_olmo_hybrid_model(cfg, seed=SEED)
    assert olmo_hybrid.num_params(cfg) == reference.param_count(MODEL) == \
        sum(x.size for x in jax.tree_util.tree_leaves(model.params))
    ids = _ids(40)
    hidden = olmo_hybrid.forward_hidden(model.params,
                                        jnp.asarray(ids)[None], cfg)
    got = np.asarray(olmo_hybrid.logits(model.params, hidden))[0]
    np.testing.assert_allclose(got, _ref_logits(ids), atol=5e-4)


def test_the_loss_differentiates_the_xla_path():
    cfg = _config()
    model = olmo_hybrid.make_olmo_hybrid_model(cfg, seed=SEED)
    ids = jnp.asarray(_ids(24, salt=3))[None]
    loss, grads = jax.value_and_grad(olmo_hybrid.lm_loss)(
        model.params, ids, ids, cfg)
    assert np.isfinite(float(loss))
    norms = [float(jnp.abs(g).max())
             for g in jax.tree_util.tree_leaves(grads)]
    assert all(np.isfinite(norms)) and max(norms) > 0


@pytest.mark.parametrize("n", [8, 11, 16, 23, 37],
                         ids=["full_bucket", "padded_bucket",
                              "largest_bucket", "two_chunks",
                              "three_chunks"])
def test_prefill_then_decode_through_the_scheduler(n):
    """One chunk = two or three chunks = a padded bucket = the
    reference's full forward: the prompt's last logits, and then every
    decode step's."""
    engine = _engine()
    tap = _Tap(engine)
    prompt = _ids(n, salt=n).tolist()
    sched = ContinuousBatchingScheduler(engine)
    uid = sched.submit(prompt, max_new_tokens=6, eos_token_id=None)
    sched.run()
    tokens = sched.results[uid]
    assert tokens == _greedy_chain(prompt, 6)
    chunks = -(-n // 16)
    assert len(tap.all) == chunks + 5
    ref = _ref_logits(prompt + tokens, np.arange(n - 1, n + 5))
    got = np.stack([rows[0] for rows in tap.all[chunks - 1:]])
    np.testing.assert_allclose(got, ref, atol=5e-4)
    # what the programs counted: a slot a chunk, a slot a decode step
    counted = sched.metrics.program_counters["gdn.advanced"]
    assert counted == {"launches": chunks + 5, "slots": chunks + 5,
                       "steps": 5}


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
    np.testing.assert_allclose(tap.last, ref[0], atol=5e-4)
    assert engine.try_admit(1, ids[:23])
    engine.prefill_chunk(1, ids[:23], 0)                  # bucket 32
    np.testing.assert_allclose(tap.last, ref[0], atol=5e-4)
    for slot in (0, 1):
        assert engine.ensure_pages(slot, 24)
    tokens = np.zeros((engine.num_slots,), np.int32)
    tokens[:2] = ids[23]
    before = [np.asarray(a) for a in engine.state.arrays]
    engine.decode_step(tokens, active=[0, 1])
    got = tap.last.reshape(engine.num_slots, VOCAB)
    np.testing.assert_allclose(got[0], ref[1], atol=5e-4)
    np.testing.assert_allclose(got[1], ref[1], atol=5e-4)
    # both ways leave the same state behind, and the slot held back
    # keeps its own to the bit
    conv, gdn = (np.asarray(a) for a in engine.state.arrays)
    # (float32 in another order, through eight post-normed layers)
    for pool in (conv, gdn):
        np.testing.assert_allclose(pool[:, 0], pool[:, 1],
                                   atol=2e-4 * np.abs(pool[:, 0]).max())
    np.testing.assert_array_equal(conv[:, 2], before[0][:, 2])
    np.testing.assert_array_equal(gdn[:, 2], before[1][:, 2])
    assert not np.array_equal(gdn[:, 0], before[1][:, 0])


def _poison(engine):
    engine.state.update(tuple(jnp.full_like(a, jnp.nan)
                              for a in engine.state.arrays))
    engine.kv.update(tuple(jnp.full_like(a, jnp.nan)
                           for a in engine.kv.buffers()))


@pytest.mark.parametrize("gdn_kernel", ["xla", "pallas"])
def test_a_reused_slot_starts_from_zero_state_under_nan_poison(gdn_kernel):
    """Every slot's state and every page NaN beforehand, one slot, four
    requests through it one after the other: each stream is the
    reference's, so the first chunk's program reset the state and no
    idle or retired slot's NaN reached a live one."""
    engine = _engine(slots=2, num_pages=24, gdn_kernel=gdn_kernel)
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


@pytest.mark.pallas
def test_the_engine_with_the_kernels_interpreted_matches_the_oracles():
    prompt = _ids(19, salt=31).tolist()
    streams = []
    for kernel in ("xla", "pallas"):
        engine = _engine(gdn_kernel=kernel, paged_attention_kernel=kernel)
        assert engine.paged_attention_kernel == kernel
        assert engine.prefill_attention_kernel == kernel
        streams.append(engine.generate([prompt], max_new_tokens=5,
                                       eos_token_id=None)[0])
    assert streams[0] == streams[1] == _greedy_chain(prompt, 5)


def test_allow_neg_eigval_is_the_two():
    plain = dict(MODEL, linear_allow_neg_eigval=False)
    cfg = olmo_hybrid.config_from_hf(plain, dtype=jnp.float32)
    model = olmo_hybrid.make_olmo_hybrid_model(cfg, seed=SEED)
    ids = _ids(20, salt=2)
    hidden = olmo_hybrid.forward_hidden(model.params,
                                        jnp.asarray(ids)[None], cfg)
    got = np.asarray(olmo_hybrid.logits(model.params, hidden))[0]
    want = np.asarray(reference.logits_at(plain, SEED, ids, np.arange(20)))
    np.testing.assert_allclose(got, want, atol=5e-4)
    assert np.abs(want - _ref_logits(ids)).max() > 1e-2


# --------------------------------------------------------------- refusals
def _refused(match, **inference):
    config = {"max_batch_size": 2, "dtype": "fp32",
              "kv_block_size": 4, "num_pages": 16, "max_seq_len": 64,
              "prefill_buckets": [8]}
    config.update(inference)
    with pytest.raises(ValueError, match=match):
        deepspeed.init_inference(
            model=olmo_hybrid.make_olmo_hybrid_model(_config(), seed=SEED),
            config={"inference": config})


def test_prefix_cache_refuses_recurrent_layers():
    _refused("prefix caching .* recurrent layers", prefix_caching=True)


def test_drafter_refuses_recurrent_layers():
    _refused("speculative decoding .* recurrent layers",
             speculative={"enabled": True, "method": "ngram"})


def test_a_recurrent_draft_model_is_refused():
    from deepspeed_tpu.inference.speculative import ModelDrafter
    with pytest.raises(ValueError, match="draft model.* recurrent"):
        ModelDrafter(olmo_hybrid.make_olmo_hybrid_model(_config(),
                                                        seed=SEED),
                     2, 64, jnp.float32)


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
            model=olmo_hybrid.make_olmo_hybrid_model(_config(), seed=SEED),
            mesh=mesh, config={"inference": {"dtype": "fp32"}})


def test_the_decoder_is_named_where_a_model_lacks_one():
    from deepspeed_tpu.inference import decoder
    with pytest.raises(AssertionError, match="make_olmo_hybrid_model"):
        decoder.decoder_of(object())
    assert "make_olmo_hybrid_model" in decoder.__doc__


def test_the_audit_lowers_the_programs_with_their_state_pool():
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
