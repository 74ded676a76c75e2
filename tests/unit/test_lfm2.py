"""LFM2-MoE through ``init_inference()`` at a tiny size on the CPU: six
layers (conv, attention; one dense MLP, five expert layers), widths cut
(only here), against the float32 reference
``benchmark/models/lfm2_reference.py``; the expert layer and its
grouped matmul on their own.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as deepspeed
from benchmark.models import lfm2_reference as reference
from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.models import lfm2
from deepspeed_tpu.ops import moe
from deepspeed_tpu.ops.pallas import moe as kernels

MODEL = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 64,
    "intermediate_size": 128,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "full_attention"],
    "max_position_embeddings": 256, "model_type": "lfm2_moe",
    "moe_intermediate_size": 32, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_dense_layers": 1, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 6,
    "num_key_value_heads": 2, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 128,
    # 1 / sqrt(hidden_size): a signal passes through the narrow layers
    # as it does through the published widths at 0.02
    "initializer_range": 0.125, "expert_bias_std": 0.04}
SEED = 5
VOCAB = MODEL["vocab_size"]


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _config(**overrides):
    return lfm2.config_from_hf(MODEL, dtype=jnp.float32, **overrides)


def _engine(slots=3, buckets=(8, 16), num_pages=40,
            paged_attention_kernel="xla", **overrides):
    return deepspeed.init_inference(
        model=lfm2.make_lfm2_model(_config(**overrides), seed=SEED),
        config={"inference": {
            "max_batch_size": slots, "dtype": "fp32",
            "kv_block_size": 4, "num_pages": num_pages, "max_seq_len": 64,
            "paged_attention_kernel": paged_attention_kernel,
            "prefill_buckets": list(buckets), "greedy": True,
            "max_new_tokens": 8}})


def _ids(n, salt=0):
    return np.random.default_rng([SEED, salt]).integers(0, VOCAB, n)


def _ref_logits(ids, positions=None):
    """The reference's logits at ``positions`` of ``ids``, padded to
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


# ------------------------------------------------------------------ model
def test_param_count_at_the_published_sizes():
    published = dict(
        MODEL, hidden_size=2048, intermediate_size=7168,
        moe_intermediate_size=1792, num_attention_heads=32,
        num_key_value_heads=8, num_experts=32, num_experts_per_tok=4,
        num_dense_layers=2, vocab_size=65536, num_hidden_layers=24,
        layer_types=["conv", "conv", "full_attention", "conv", "conv",
                     "conv", "full_attention", "conv", "conv", "conv",
                     "full_attention", "conv", "conv", "conv",
                     "full_attention", "conv", "conv", "conv",
                     "full_attention", "conv", "conv", "full_attention",
                     "conv", "conv"])
    published.pop("initializer_range")
    # the released model: 8.3B, 1.5B of them active a token
    assert 8.30e9 < reference.param_count(published) < 8.40e9
    first_stage = dict(published, num_hidden_layers=12,
                       layer_types=published["layer_types"][:12])
    count = reference.param_count(first_stage)
    assert lfm2.num_params(lfm2.config_from_hf(first_stage)) == count
    assert 3.92e9 < count < 3.94e9
    cfg = lfm2.config_from_hf(first_stage)
    assert cfg.attention_layers == [2, 6, 10]
    assert cfg.expert_layers == list(range(2, 12)) and cfg.d_head == 64


def test_model_without_cache_matches_the_reference():
    cfg = _config()
    model = lfm2.make_lfm2_model(cfg, seed=SEED)
    assert lfm2.num_params(cfg) == reference.param_count(MODEL) == sum(
        x.size for x in jax.tree_util.tree_leaves(model.params))
    ids = _ids(40)
    hidden, (load,) = lfm2.forward_hidden(
        model.params, jnp.asarray(ids)[None], cfg, counters=True)
    got = np.asarray(lfm2.logits(model.params, hidden))[0]
    np.testing.assert_allclose(got, _ref_logits(ids), atol=2e-5)
    # every token's two experts, in each of the five expert layers
    assert int(load[0].sum()) == 40 * 2 * 5
    assert int(load[1].sum()) <= 8 * 5 and int(load[0].max()) > 0


@pytest.mark.parametrize("n", [8, 11, 16, 23],
                         ids=["full_bucket", "padded_bucket",
                              "largest_bucket", "two_chunks"])
def test_prefill_then_decode_through_the_scheduler(n):
    """One chunk = two chunks = a padded bucket = the reference's full
    forward: the prompt's last logits, and then every decode step's
    (rotary positions of a second chunk and of each decode step)."""
    engine = _engine()
    tap = _Tap(engine)
    prompt = _ids(n, salt=n).tolist()
    sched = ContinuousBatchingScheduler(engine)
    uid = sched.submit(prompt, max_new_tokens=6, eos_token_id=None)
    sched.run()
    tokens = sched.results[uid]
    assert tokens == _greedy_chain(prompt, 6)
    chunks = 2 if n > 16 else 1
    assert len(tap.all) == chunks + 5
    ref = _ref_logits(prompt + tokens, np.arange(n - 1, n + 5))
    got = np.stack([rows[0] for rows in tap.all[chunks - 1:]])
    np.testing.assert_allclose(got, ref, atol=3e-5)
    # what the programs counted reached the scheduler's metrics
    counted = sched.metrics.program_counters["moe.load"]
    assert counted["launches"] == chunks + 5
    padded = 8 if n <= 8 else 16 if n <= 16 else 16 + 8
    assert counted["rows"] == (padded + 5 * engine.num_slots) * 2 * 5


def test_prefill_logits_one_chunk_two_chunks_and_padding():
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
    # both ways leave the same tails behind
    (conv,) = (np.asarray(a) for a in engine.state.arrays)
    np.testing.assert_allclose(conv[:, 0], conv[:, 1], atol=1e-5)


def _poison(engine):
    engine.state.update(tuple(jnp.full_like(a, jnp.nan)
                              for a in engine.state.arrays))
    engine.kv.update(tuple(jnp.full_like(a, jnp.nan)
                           for a in engine.kv.buffers()))


def test_a_reused_slot_starts_from_a_zero_tail_under_nan_poison():
    """Every slot's tail and every page NaN beforehand, one slot, four
    requests through it one after the other (one of two tokens: the
    tail reaches that far): each stream is the reference's. The idle
    slot's NaN rows go through the expert layer with the live ones and
    reach none of them."""
    engine = _engine(slots=2, num_pages=24)
    _poison(engine)
    sched = ContinuousBatchingScheduler(engine)
    prompts = [_ids(n, salt=100 + n).tolist() for n in (2, 19, 9, 3)]
    uids = [sched.submit(p, max_new_tokens=4, eos_token_id=None)
            for p in prompts]
    results = sched.run()
    for uid, prompt in zip(uids, prompts):
        assert results[uid] == _greedy_chain(prompt, 4)
    assert sched.metrics.snapshot()["state_pool"]["resets"] == 4


def test_a_slot_between_two_chunks_keeps_its_tail_through_a_decode():
    engine = _engine(slots=2, buckets=(8, 16))
    sched = ContinuousBatchingScheduler(engine)
    # 17 tokens: the second chunk is one token, right behind the tail
    short, long_ = _ids(6, salt=7).tolist(), _ids(17, salt=8).tolist()
    a = sched.submit(short, max_new_tokens=8, eos_token_id=None)
    sched.step()                        # a decodes from here on
    b = sched.submit(long_, max_new_tokens=4, eos_token_id=None)
    results = sched.run()
    assert results[a] == _greedy_chain(short, 8)
    assert results[b] == _greedy_chain(long_, 4)


def test_preemption_and_resume_give_the_same_tokens():
    """A pool too small for both answers: the younger request is
    preempted, re-prefills prompt + tokens so far (tails from zero,
    rotary positions from 0 again) and ends with the tokens it would
    have had."""
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
        engine = _engine(moe_kernel=kernel, paged_attention_kernel=kernel)
        assert engine.paged_attention_kernel == kernel
        streams.append(engine.generate([prompt], max_new_tokens=5,
                                       eos_token_id=None)[0])
    assert streams[0] == streams[1] == _greedy_chain(prompt, 5)


def test_the_load_is_a_span_a_launch_with_the_decoders_attributes(
        monkeypatch):
    """Engine and scheduler know the counter's name only: the decoder
    makes the span's attributes, one span a launch, after the fetch."""
    from deepspeed_tpu.inference import engine as engine_module
    seen = []

    class _Span:
        def __init__(self, name, **attrs):
            seen.append((name, attrs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(engine_module, "annotate", _Span)
    engine = _engine()
    engine.generate([_ids(11, salt=3).tolist()], max_new_tokens=3,
                    eos_token_id=None)
    loads = [attrs for name, attrs in seen if name == "moe.load"]
    assert len(loads) == 3              # one prefill, two decode steps
    assert set(loads[0]) == {"rows", "experts_hit", "hottest_rows"}
    assert loads[0]["rows"] == 16 * 2 * 5
    assert loads[1]["rows"] == engine.num_slots * 2 * 5
    assert 0 < loads[1]["experts_hit"] <= 8 * 5
    names = [name for name, _ in seen]
    assert names.index("moe.load") > names.index("engine.prefill.fetch")
    source = open(engine_module.__file__).read()
    assert "moe" not in source and "expert" not in source


# ----------------------------------------------------------- expert layer
def _layer_inputs(T=24, d=32, E=8, ff=16, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (f(T, d), 0.2 * f(d, E), 0.1 * f(E), 0.2 * f(E, d, 2 * ff),
            0.2 * f(E, ff, d))


def _dense_layer(x, router, bias, w13, w2, top_k, **routing):
    """Every expert on every token, masked by the routing."""
    chosen, weights = moe.route(x, router, bias, top_k, **routing)
    ff = w2.shape[1]
    out = jnp.zeros_like(x)
    for e in range(w13.shape[0]):
        h = x @ w13[e]
        y = (jax.nn.silu(h[:, :ff]) * h[:, ff:]) @ w2[e]
        out += ((chosen == e) * weights).sum(-1, keepdims=True) * y
    return out, chosen


def test_router_bias_shifts_the_choice_only():
    x, router, _, _, _ = _layer_inputs()
    bias = jnp.zeros((8,)).at[3].set(10.0)
    chosen, weights = moe.route(x, router, bias, 2)
    plain, plain_w = moe.route(x, router, None, 2)
    assert (chosen == 3).any(-1).all()          # always chosen
    scores = jax.nn.sigmoid(x @ router)
    picked = jnp.take_along_axis(scores, chosen, -1)
    np.testing.assert_allclose(weights, picked / (picked.sum(-1, keepdims=True)
                                                  + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-5)
    _, raw = moe.route(x, router, None, 2, norm_topk_prob=False)
    np.testing.assert_allclose(
        raw, jnp.take_along_axis(scores, plain, -1), rtol=1e-6)


@pytest.mark.pallas
@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_expert_layer_matches_every_expert_applied_densely(kernel):
    x, router, bias, w13, w2 = _layer_inputs()
    want, chosen = _dense_layer(x, router, bias, w13, w2, 2)
    c, w = moe.route(x, router, bias, 2)
    got, load = moe.expert_ffn(x, c, w, w13, w2, (0, 8), 8, kernel=kernel)
    np.testing.assert_allclose(got, want, atol=2e-5)
    rows = np.bincount(np.asarray(chosen).ravel(), minlength=8)
    np.testing.assert_array_equal(load[0], rows)
    np.testing.assert_array_equal(load[1], rows > 0)


@pytest.mark.pallas
@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_four_shares_of_the_experts_add_up_to_the_whole_layer(kernel,
                                                              scoring):
    """The guide's share test: the router over all 8 experts (by either
    scoring), each share computing its own two experts' part; the parts
    add up to the uncut layer's result, and the loads to its load."""
    x, router, bias, w13, w2 = _layer_inputs(seed=2)
    want, _ = _dense_layer(x, router, bias, w13, w2, 2, scoring=scoring)
    c, w = moe.route(x, router, bias, 2, scoring=scoring)
    whole, whole_load = moe.expert_ffn(x, c, w, w13, w2, (0, 8), 8,
                                       kernel=kernel)
    parts = [moe.expert_ffn(x, c, w, w13[a:a + 2], w2[a:a + 2],
                            (a, a + 2), 8, kernel=kernel)
             for a in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(p for p, _ in parts), want, atol=3e-5)
    np.testing.assert_allclose(sum(p for p, _ in parts), whole, atol=3e-5)
    np.testing.assert_array_equal(sum(l for _, l in parts), whole_load)
    # a share alone is not the layer
    assert float(jnp.abs(parts[0][0] - want).max()) > 1e-2


def test_a_share_of_the_model_holds_the_whole_models_numbers():
    """``experts_held`` in the model: two shares' expert layers hold
    the same matrices as the whole model's, and their outputs add up."""
    whole = lfm2.init_layer(_config(), SEED, 2)
    lo = lfm2.init_layer(_config(experts_held=(0, 4)), SEED, 2)
    hi = lfm2.init_layer(_config(experts_held=(4, 8)), SEED, 2)
    np.testing.assert_array_equal(
        jnp.concatenate([lo["w13"], hi["w13"]]), whole["w13"])
    np.testing.assert_array_equal(
        jnp.concatenate([lo["w2"], hi["w2"]]), whole["w2"])
    np.testing.assert_array_equal(lo["router"], whole["router"])
    u = jnp.asarray(np.random.default_rng(1).standard_normal((1, 12, 64)),
                    jnp.float32)
    outs = [lfm2._ffn(u, lp, cfg)[0] for lp, cfg in (
        (lo, _config(experts_held=(0, 4))),
        (hi, _config(experts_held=(4, 8))), (whole, _config()))]
    np.testing.assert_allclose(outs[0] + outs[1], outs[2], atol=2e-5)
    # the reference, given the same share, leaves the same part out
    share = dict(MODEL, experts_held=[0, 4])
    w = reference.draw_layer(MODEL, SEED, 2)
    with jax.default_matmul_precision("highest"):
        want, _ = reference._experts(share, w, u[0], jnp.matmul, None, True,
                                     None)
    np.testing.assert_allclose(outs[0][0], want, atol=2e-5)


# ----------------------------------------------------------------- kernel
@pytest.mark.pallas
@pytest.mark.parametrize("sizes", [
    [3, 0, 9, 4], [0, 16, 0, 0], [1, 1, 1, 1], [0, 0, 0, 0], [5, 5, 5, 1]],
    ids=["ragged_with_an_empty_expert", "one_expert_takes_every_row",
         "one_row_each", "no_row_at_all", "full"])
def test_moe_gmm_in_interpret_mode_matches_ragged_dot(sizes):
    rng = np.random.default_rng(sum(sizes))
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    lhs, rhs = f(16, 24), f(4, 24, 40)
    sizes = jnp.asarray(sizes, jnp.int32)
    total = int(sizes.sum())
    got = kernels.moe_gmm(lhs, rhs, sizes, interpret=True)
    want = kernels.moe_gmm_xla(lhs, rhs, sizes)
    np.testing.assert_allclose(got[:total], want[:total], atol=1e-5)
    group = np.repeat(np.arange(4), np.asarray(sizes))
    for r in range(total):
        np.testing.assert_allclose(got[r], lhs[r] @ rhs[group[r]],
                                   atol=1e-5)


@pytest.mark.pallas
def test_moe_gmm_groups_across_row_tiles_and_nan_rows_past_them():
    """384 rows in tiles of 128: groups that start inside a tile, one
    spanning two tiles, an empty expert between them; the rows past
    the groups' total are NaN and reach no row of a group."""
    rng = np.random.default_rng(9)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    sizes = jnp.asarray([100, 0, 130, 29, 50], jnp.int32)
    total = int(sizes.sum())                     # 309 of 384
    lhs = f(384, 64).at[total:].set(jnp.nan)
    rhs = f(5, 64, 256)
    got = kernels.moe_gmm(lhs, rhs, sizes, interpret=True)
    want = kernels.moe_gmm_xla(lhs[:total], rhs, sizes)
    np.testing.assert_allclose(got[:total], want, atol=1e-4)
    assert np.isfinite(np.asarray(got[:total])).all()


def test_group_metadata_gives_an_empty_expert_no_item():
    sizes = jnp.asarray([100, 0, 130, 29, 50], jnp.int32)
    group, tile, starts, ends, num = kernels.group_metadata(sizes, 384, 128)
    n = int(num[0])
    items = list(zip(np.asarray(group)[:n], np.asarray(tile)[:n]))
    # expert 0: tile 0; expert 2: tiles 0, 1; 3: tile 1, 2; 4: tile 2
    assert items == [(0, 0), (2, 0), (2, 1), (3, 1), (3, 2), (4, 2)]
    assert 1 not in np.asarray(group)           # its matrix is never named
    # items past the real ones repeat the last: no block is fetched
    assert set(zip(np.asarray(group)[n:], np.asarray(tile)[n:])) == {(4, 2)}
    assert len(group) == 384 // 128 + 5 - 1


# --------------------------------------------------------------- refusals
def test_a_model_mesh_axis_refuses_the_family():
    from deepspeed_tpu.parallel.topology import build_mesh
    mesh = build_mesh(data=jax.device_count() // 2, model=2)
    with pytest.raises(ValueError, match="no tensor-parallel layout"):
        deepspeed.init_inference(
            model=lfm2.make_lfm2_model(_config(), seed=SEED), mesh=mesh,
            config={"inference": {"dtype": "fp32"}})


def test_prefix_cache_refuses_the_family():
    with pytest.raises(ValueError, match="prefix caching .* recurrent"):
        deepspeed.init_inference(
            model=lfm2.make_lfm2_model(_config(), seed=SEED),
            config={"inference": {
                "max_batch_size": 2, "dtype": "fp32",
                "kv_block_size": 4, "num_pages": 16, "max_seq_len": 64,
                "prefill_buckets": [8], "prefix_caching": True}})
