"""Granite-MoE-Hybrid through ``init_inference()`` at a tiny size on the
CPU: two periods of Mamba-2 layers around an attention layer, experts
and a shared MLP after every mixer, widths cut (only here), against the
float32 reference
``benchmark/models/granite_moe_hybrid_reference.py``."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as deepspeed
from benchmark.models import granite_moe_hybrid_reference as reference
from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.models import granite_moe_hybrid as granite
from deepspeed_tpu.ops import moe

PERIOD = ["mamba", "mamba", "attention", "mamba"]
MODEL = {
    "model_type": "granitemoehybrid", "attention_bias": False,
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 32,
    "layer_types": PERIOD * 2, "logits_scaling": 16,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 16, "mamba_d_state": 16, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 8, "mamba_proj_bias": False,
    "max_position_embeddings": 256, "normalization_function": "rmsnorm",
    "num_attention_heads": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 8, "num_key_value_heads": 2,
    "num_local_experts": 8, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-5,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 48, "tie_word_embeddings": True,
    "vocab_size": 128,
    # 1 / sqrt(hidden_size): a signal passes through the narrow layers
    "initializer_range": 0.125}
# the catalog row's `config`, as read from the model's public config.json
PUBLISHED = dict(
    MODEL, attention_multiplier=0.0078125, hidden_size=4096,
    intermediate_size=768,
    layer_types=(["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    mamba_d_head=64, mamba_d_state=128, mamba_n_heads=128,
    max_position_embeddings=131072, num_attention_heads=32,
    num_experts_per_tok=10, num_hidden_layers=40, num_key_value_heads=8,
    num_local_experts=72, shared_intermediate_size=1536, vocab_size=100352)
del PUBLISHED["initializer_range"]
SEED = 5
VOCAB = MODEL["vocab_size"]


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _config(model=MODEL, **overrides):
    return granite.config_from_hf(model, dtype=jnp.float32, **overrides)


def _engine(slots=3, buckets=(8, 16), num_pages=40,
            paged_attention_kernel="xla", model=MODEL, **overrides):
    return deepspeed.init_inference(
        model=granite.make_granite_moe_hybrid_model(
            _config(model, **overrides), seed=SEED),
        config={"inference": {
            "max_batch_size": slots, "dtype": "fp32",
            "kv_block_size": 4, "num_pages": num_pages, "max_seq_len": 64,
            "paged_attention_kernel": paged_attention_kernel,
            "prefill_buckets": list(buckets), "greedy": True,
            "max_new_tokens": 8}})


def _ids(n, salt=0):
    return np.random.default_rng([SEED, salt]).integers(0, VOCAB, n)


def _ref_logits(ids, positions=None, model=MODEL, **wrong):
    """The reference's logits at ``positions`` of ``ids``; padded to
    one length (the model is causal), so the reference compiles once."""
    positions = np.arange(len(ids)) if positions is None else positions
    padded = np.zeros((64,), np.int64)
    padded[:len(ids)] = ids
    return np.asarray(reference.logits_at(model, SEED, padded, positions,
                                          **wrong))


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
                self.all.append(self.last.reshape(-1, self.last.shape[-1]))
                return out
            return tapped
        setattr(self.engine, name, tapped_make)


def test_param_count_and_the_state_at_the_published_sizes():
    whole = dict(PUBLISHED, router_num_experts=72)
    assert reference.param_count(whole, held=False) == 32207337984
    assert 32.0e9 < granite.num_params(granite.config_from_hf(PUBLISHED)) \
        == 32207337984 < 32.5e9
    # with 10 of 72 experts a token: the family's "A9B"
    active = 32207337984 - 40 * 62 * 3 * 4096 * 768
    assert 8.7e9 < active < 9.0e9
    # the cell's cut: layers 0-9, experts 0-35, rows 0-50,175
    cut = dict(PUBLISHED, num_hidden_layers=10,
               layer_types=PUBLISHED["layer_types"][:10],
               num_local_experts=36, router_num_experts=72,
               experts_held=[0, 36], padded_vocab_size=50176)
    cfg = granite.config_from_hf(cut)
    assert granite.num_params(cfg) == reference.param_count(cut) == \
        4757211776
    assert (cfg.d_inner, cfg.conv_channels, cfg.held) == \
        (8192, 8448, (0, 36))
    assert len(cfg.mamba_layers) == 9 and cfg.attention_layers == [5]
    spec = granite.GraniteMoeHybridDecoder(cfg).cache_spec()
    assert (spec.kv_layers, spec.kv_heads, spec.d_head) == (1, 8, 128)
    conv, ssd = spec.state
    assert conv.lead == ssd.lead == (9,) and ssd.tail == (128, 8192)
    assert conv.tail == (3 * 8448,) and conv.dtype == jnp.bfloat16
    # inner width minor, whole lane tiles, float32: 4,194,304 B a slot
    # and layer, 38.2 MB a slot
    assert ssd.tail[1] % 128 == 0 and ssd.dtype == jnp.float32
    assert ssd.tail[0] * ssd.tail[1] * 4 == 4194304
    assert 9 * (4194304 + conv.tail[0] * 2) == 38204928


def test_the_state_pool_reads_the_slots_bytes():
    engine = _engine()
    n_mamba = 6
    assert engine.state.nbytes == 3 * n_mamba * (16 * 128 * 4 + 3 * 160 * 4)


def test_model_without_cache_matches_the_reference():
    cfg = _config()
    model = granite.make_granite_moe_hybrid_model(cfg, seed=SEED)
    assert granite.num_params(cfg) == reference.param_count(MODEL) == \
        sum(x.size for x in jax.tree_util.tree_leaves(model.params))
    ids = _ids(40)
    hidden = granite.forward_hidden(model.params, jnp.asarray(ids)[None],
                                    cfg)
    got = np.asarray(model.decoder.logits(model.params, hidden))[0]
    np.testing.assert_allclose(got, _ref_logits(ids), atol=2e-5)


@pytest.mark.parametrize("wrong", [
    {"attention_multiplier": 0.25}, {"residual_multiplier": 1.0},
    {"gate_after_norm": True}, {"conv_bias": False}, {"decay": False},
    {"renormalise": False}, {"top_k": 2}],
    ids=lambda w: next(iter(w)))
def test_the_reference_made_wrong_is_another_function(wrong):
    """Each of the block's particulars moves the logits by far more
    than the program lies from the sound reference."""
    ids = _ids(40)
    assert np.abs(_ref_logits(ids, **wrong) - _ref_logits(ids)).max() > 2e-3


def test_the_loss_differentiates_the_xla_path():
    cfg = _config()
    model = granite.make_granite_moe_hybrid_model(cfg, seed=SEED)
    ids = jnp.asarray(_ids(24, salt=3))[None]
    loss, grads = jax.value_and_grad(granite.lm_loss)(
        model.params, ids, ids, cfg)
    assert np.isfinite(float(loss))
    norms = [float(jnp.abs(g).max())
             for g in jax.tree_util.tree_leaves(grads)]
    assert all(np.isfinite(norms)) and max(norms) > 0


def test_routes_softmax_renormalised_is_the_published_top_k_then_softmax():
    """``ops/moe.py::route`` as it stands (softmax over all, the top-k,
    renormalised, eps 0) against the reference's published form (the
    top-k LOGITS, softmaxed): the same experts, the same weights."""
    w = reference.draw_layer(MODEL, SEED, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (50, 64))
    chosen, weights = moe.route(x, w["router"], None, 3, True,
                                norm_eps=0.0, scoring="softmax")
    ref_chosen, ref_weights, _ = reference.route(MODEL, w, x)
    np.testing.assert_array_equal(np.asarray(chosen),
                                  np.asarray(ref_chosen))
    np.testing.assert_allclose(np.asarray(weights), np.asarray(ref_weights),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-5)


def test_the_two_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The lower and the upper half of a layer's experts, each as the
    program computes its share, the shared MLP counted once, add up to
    what the uncut reference gives for the whole layer."""
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(2), (24, 64))
    w = reference.draw_layer(MODEL, SEED, 1)
    mm = lambda a, m: a @ m
    whole, _, _ = reference._experts(MODEL, w, x, mm, reference.WRONG,
                                     (0, 8))
    shared = reference._gated(x, w["s1"], w["s3"], w["s2"], mm)
    parts = []
    for held in ((0, 4), (4, 8)):
        share = dict(MODEL, num_local_experts=4, router_num_experts=8,
                     experts_held=list(held))
        cfg = _config(share)
        lp = granite.init_layer(cfg, SEED, 1)
        assert lp["w13"].shape == (4, 64, 64)
        out, load = granite._experts(x, lp, cfg)
        assert int(load[2, 0]) == 24 * 3          # routed anywhere
        assert int(load[0, :held[0]].sum()) == 0 == \
            int(load[0, held[1]:].sum())
        parts.append((out, int(load[0].sum())))
    assert parts[0][1] + parts[1][1] == 24 * 3
    total = parts[0][0] + parts[1][0] - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-6)
    assert np.abs(np.asarray(whole - shared)).max() > 1e-3


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
    chunks = -(-n // 16)
    assert len(tap.all) == chunks + 5
    ref = _ref_logits(prompt + tokens, np.arange(n - 1, n + 5))
    got = np.stack([rows[0] for rows in tap.all[chunks - 1:]])
    np.testing.assert_allclose(got, ref, atol=5e-5)
    assert tokens == [int(r.argmax()) for r in ref]
    # what the programs counted: a slot a chunk, a slot a decode step;
    # 3 experts a token in 8 layers, all of them held
    counted = sched.metrics.program_counters
    assert counted["ssd.advanced"] == {
        "launches": chunks + 5, "slots": chunks + 5, "steps": 5}
    load = counted["moe.load"]
    assert load["launches"] == chunks + 5 and load["passes"] == 8 * (
        chunks + 5)
    assert load["rows"] == load["routed"]


def test_prefill_logits_one_chunk_two_chunks_and_padding():
    """``engine.prefill_chunk`` directly: a 23-token prompt as chunks
    of 16 + 7 (the second padded to 8) leaves the slot as one forward
    over 23 tokens would; the decode after it agrees with the
    reference, and a slot held back keeps its state to the bit."""
    engine = _engine(buckets=(8, 16, 32))
    tap = _Tap(engine)
    ids = _ids(24, salt=1).tolist()
    ref = _ref_logits(ids, [22, 23])
    assert engine.try_admit(0, ids[:23])
    engine.prefill_chunk(0, ids[:16], 0)
    engine.prefill_chunk(0, ids[16:23], 16)
    np.testing.assert_allclose(tap.last, ref[0], atol=5e-5)
    assert engine.try_admit(1, ids[:23])
    engine.prefill_chunk(1, ids[:23], 0)                  # bucket 32
    np.testing.assert_allclose(tap.last, ref[0], atol=5e-5)
    for slot in (0, 1):
        assert engine.ensure_pages(slot, 24)
    tokens = np.zeros((engine.num_slots,), np.int32)
    tokens[:2] = ids[23]
    before = [np.asarray(a) for a in engine.state.arrays]
    engine.decode_step(tokens, active=[0, 1])
    got = tap.last.reshape(engine.num_slots, VOCAB)
    np.testing.assert_allclose(got[0], ref[1], atol=5e-5)
    np.testing.assert_allclose(got[1], ref[1], atol=5e-5)
    conv, ssd = (np.asarray(a) for a in engine.state.arrays)
    for pool in (conv, ssd):
        np.testing.assert_allclose(pool[:, 0], pool[:, 1],
                                   atol=1e-4 * np.abs(pool[:, 0]).max())
    np.testing.assert_array_equal(conv[:, 2], before[0][:, 2])
    np.testing.assert_array_equal(ssd[:, 2], before[1][:, 2])
    assert not np.array_equal(ssd[:, 0], before[1][:, 0])


def _poison(engine):
    engine.state.update(tuple(jnp.full_like(a, jnp.nan)
                              for a in engine.state.arrays))
    engine.kv.update(tuple(jnp.full_like(a, jnp.nan)
                           for a in engine.kv.buffers()))


@pytest.mark.pallas
@pytest.mark.parametrize("ssd_kernel", ["xla", "pallas"])
def test_a_reused_slot_starts_from_zero_state_under_nan_poison(ssd_kernel):
    """Every slot's state and every page NaN beforehand, one slot, four
    requests through it one after the other: each stream is the
    reference's, so the first chunk's program reset the state and no
    idle or retired slot's NaN reached a live one."""
    engine = _engine(slots=2, num_pages=24, ssd_kernel=ssd_kernel)
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


def test_a_share_serves_its_part_and_counts_what_landed():
    """Experts 0-3 of 8 held: the engine's logits are the reference's
    given the same share, and ``moe.load`` says what share of the routed
    rows landed here."""
    share = dict(MODEL, num_local_experts=4, router_num_experts=8,
                 experts_held=[0, 4], padded_vocab_size=64)
    engine = _engine(model=share)
    tap = _Tap(engine)
    prompt = np.random.default_rng(9).integers(0, 64, 19).tolist()
    sched = ContinuousBatchingScheduler(engine)
    uid = sched.submit(prompt, max_new_tokens=3, eos_token_id=None)
    sched.run()
    ids = prompt + sched.results[uid]
    ref = _ref_logits(ids, np.arange(18, 21), model=share)
    assert ref.shape == (3, 64)
    got = np.stack([rows[0] for rows in tap.all[1:]])
    np.testing.assert_allclose(got, ref, atol=5e-5)
    load = sched.metrics.program_counters["moe.load"]
    # every row a launch holds is routed, a bucket's padding and the
    # idle slots' with it: chunks of 16 and 8, two steps of three slots
    assert load["routed"] == (16 + 8 + 2 * 3) * 3 * 8
    assert 0.25 * load["routed"] < load["rows"] < 0.75 * load["routed"]
    whole = _ref_logits(ids, np.arange(18, 21),
                        model=dict(share, num_local_experts=8,
                                   experts_held=[0, 8]))
    assert np.abs(whole - ref).max() > 1e-3


@pytest.mark.pallas
def test_the_engine_with_the_kernels_interpreted_matches_the_oracles():
    prompt = _ids(19, salt=31).tolist()
    streams = []
    for kernel in ("xla", "pallas"):
        engine = _engine(ssd_kernel=kernel, moe_kernel=kernel,
                         paged_attention_kernel=kernel)
        assert engine.paged_attention_kernel == kernel
        assert engine.prefill_attention_kernel == kernel
        streams.append(engine.generate([prompt], max_new_tokens=5,
                                       eos_token_id=None)[0])
    assert streams[0] == streams[1] == _greedy_chain(prompt, 5)


def test_the_programs_scopes_are_found():
    """``engine.program_scopes()`` (the scope map of every compiled
    program) names the new mechanisms: the chunked form in the prefill
    program, the step in the decode program."""
    import re
    from deepspeed_tpu.utils import annotate
    engine = _engine()
    engine.generate([_ids(9, salt=41).tolist()], max_new_tokens=2,
                    eos_token_id=None)
    found = {}
    for entry in engine.program_scopes():
        assert "error" not in entry
        for row in entry["instructions"].values():
            path = re.sub(r"[A-Za-z_][\w.]*\(|\)", "", row[0])
            found.setdefault(entry["program"], set()).update(
                set(path.split("/")).intersection(annotate.DEVICE_SCOPES))
    everywhere = {"embed", "ssd.proj", "ssd.conv", "ssd.norm", "attn.full",
                  "moe.route", "moe.dispatch", "moe.combine", "moe.shared",
                  "kv.write", "head"}
    assert everywhere | {"ssd.chunk"} <= found["prefill"]
    assert everywhere | {"ssd.step"} <= found["decode"]
    assert "ssd.step" not in found["prefill"]
    assert "ssd.chunk" not in found["decode"]


# --------------------------------------------------------------- refusals
def _refused(match, **inference):
    config = {"max_batch_size": 2, "dtype": "fp32",
              "kv_block_size": 4, "num_pages": 16, "max_seq_len": 64,
              "prefill_buckets": [8]}
    config.update(inference)
    with pytest.raises(ValueError, match=match):
        deepspeed.init_inference(
            model=granite.make_granite_moe_hybrid_model(_config(),
                                                        seed=SEED),
            config={"inference": config})


def test_prefix_cache_refuses_recurrent_layers():
    _refused("prefix caching .* recurrent layers", prefix_caching=True)


def test_drafter_refuses_recurrent_layers():
    _refused("speculative decoding .* recurrent layers",
             speculative={"enabled": True, "method": "ngram"})


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
            model=granite.make_granite_moe_hybrid_model(_config(),
                                                        seed=SEED),
            mesh=mesh, config={"inference": {"dtype": "fp32"}})


def test_the_decoder_is_named_where_a_model_lacks_one():
    from deepspeed_tpu.inference import decoder
    with pytest.raises(AssertionError,
                       match="make_granite_moe_hybrid_model"):
        decoder.decoder_of(object())
    assert "make_granite_moe_hybrid_model" in decoder.__doc__


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
