"""Cohere2-MoE (a parallel block, rotated sliding-window layers beside
full layers without positions, a share of the experts behind a sigmoid
router, shared experts averaged, a tied head) at a tiny size on the CPU:
the program against the float32 reference, whole and through the engine;
the share tied to the model (the eight shares of a 16-expert layer add up
to the uncut reference's layer); what the share writes on the spans and
the start-up record; and the refusals."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as deepspeed
from benchmark.models import command_a_plus_reference as reference
from deepspeed_tpu.inference.decoder import decoder_of
from deepspeed_tpu.models import cohere2_moe
from deepspeed_tpu.ops import moe

SLIDING, FULL = cohere2_moe.SLIDING, cohere2_moe.FULL
WINDOW, PAGE, SEQ = 12, 4, 96
# the keys of the published config.json that the program reads, at a tiny
# size: 16 experts of which this chip holds two, 4 a token, 4 shared
MODEL = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 32, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 32, "layer_norm_eps": 1e-05,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL], "logit_scale": 1,
    "max_position_embeddings": 256, "model_type": "cohere2_moe",
    "norm_topk_prob": True, "num_attention_heads": 8, "num_experts": 2,
    "num_experts_per_tok": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "num_shared_experts": 4,
    "position_embedding_type": "rope_gptj", "rope_theta": 50000,
    "rotary_pct": 1, "shared_expert_combination_strategy": "average",
    "sliding_window": WINDOW, "tie_word_embeddings": True,
    "use_gated_activation": True, "use_parallel_block": True,
    "use_qk_norm": False, "vocab_size": 1024,
    "router_num_experts": 16, "experts_held": [4, 6],
    "padded_vocab_size": 128,
    # 1 / sqrt(hidden_size): a signal passes through the narrow layers
    # as it does through the published widths at 0.02
    "initializer_range": 0.125, "qk_init_std": 0.25}
SEED = 5
VOCAB = MODEL["padded_vocab_size"]


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _config(model=MODEL, **overrides):
    return cohere2_moe.config_from_hf(model, dtype=jnp.float32, **overrides)


def _engine(slots=3, buckets=(8, 16), num_pages=(72, 30), inference=None,
            **overrides):
    return deepspeed.init_inference(
        model=cohere2_moe.make_cohere2_moe_model(_config(**overrides),
                                                 seed=SEED),
        config={"inference": dict({
            "max_batch_size": slots, "dtype": "fp32",
            "kv_block_size": PAGE, "num_pages": list(num_pages),
            "max_seq_len": SEQ, "prefill_buckets": list(buckets),
            "greedy": True, "max_new_tokens": 8}, **(inference or {}))})


def _ids(n, salt=0):
    return np.random.default_rng([SEED, salt]).integers(0, VOCAB, n)


def _ref_logits(ids, positions=None, **wrong):
    positions = np.arange(len(ids)) if positions is None else positions
    padded = np.zeros((SEQ,), np.int64)
    padded[:len(ids)] = ids
    return np.asarray(reference.logits_at(MODEL, SEED, padded, positions,
                                          **wrong))


def _greedy_chain(prompt, n):
    ids = list(prompt)
    for _ in range(n):
        ids.append(int(_ref_logits(ids, [len(ids) - 1])[0].argmax()))
    return ids[len(prompt):]


class _Tap:
    """The logits the engine's programs return last."""

    def __init__(self, engine):
        self.engine, self.last = engine, None
        for name in ("_get_prefill_fn", "_get_decode_fn"):
            self._wrap(name, getattr(engine, name))

    def _wrap(self, name, make):
        def tapped_make(*args, **kwargs):
            program = make(*args, **kwargs)

            def tapped(*a, **k):
                out = program(*a, **k)
                self.last = np.asarray(out[-1]).reshape(-1, VOCAB)
                return out
            return tapped
        setattr(self.engine, name, tapped_make)


# ------------------------------------------------------------------ model
def test_param_count_at_the_published_share():
    """One of eight chips' share of four layers: 4,733,292,544."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "..", "benchmark", "configs",
                           "command-a-plus-serve.json")) as f:
        cell = json.load(f)
    cfg = cohere2_moe.config_from_hf(cell["model"])
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.d_head) == (128, 8, 128)
    assert (cfg.n_experts, cfg.held, cfg.top_k) == (128, (0, 16), 8)
    assert (cfg.n_shared, cfg.d_shared, cfg.d_expert) == (4, 4096, 4096)
    assert (cfg.window, cfg.vocab_size) == (4096, 32768)
    assert cfg.qk_init_std == 0.025 and cfg.norm_eps == 1e-5
    assert cohere2_moe.num_params(cfg) == 4_733_292_544 == \
        reference.param_count(cell["model"])
    spec = cohere2_moe.Cohere2MoeDecoder(cfg).cache_spec()
    assert [(g.layers, g.window) for g in spec.groups] == \
        [(1, None), (3, 4096)]
    assert spec.kv_heads * spec.d_head == 1024


def test_model_without_cache_matches_the_reference():
    """Whole sequences four windows long, the plain forward."""
    cfg = _config()
    params = cohere2_moe.init_params(cfg, SEED)
    ids = _ids(50)
    hidden = cohere2_moe.forward_hidden(params, jnp.asarray(ids)[None], cfg)
    got = np.asarray(cohere2_moe.logits(params, hidden))[0]
    np.testing.assert_allclose(got, _ref_logits(ids), atol=2e-5)
    # and each part of the block is seen: made wrong, the logits are others
    for wrong in ({"window": 0}, {"rotate": (FULL,)}, {"shared": "sum"},
                  {"experts_held": (6, 8)}):
        assert np.abs(got - _ref_logits(ids, **wrong)).max() > 1e-2, wrong


def test_the_tied_head_is_scaled():
    cfg = _config(dict(MODEL, logit_scale=0.25))
    params = cohere2_moe.init_params(cfg, SEED)
    hidden = jnp.asarray(np.random.default_rng(0).standard_normal((3, 64)),
                         jnp.float32)
    decoder = cohere2_moe.Cohere2MoeDecoder(cfg)
    np.testing.assert_allclose(
        decoder.logits(params, hidden),
        0.25 * np.asarray(hidden) @ np.asarray(params["embed"]).T,
        rtol=1e-5, atol=1e-6)
    assert "head" not in params


def test_weights_are_the_references_own_recipe():
    cfg = _config()
    lp, w = cohere2_moe.init_layer(cfg, SEED, 1), reference.draw_layer(
        MODEL, SEED, 1)
    for name in ("q", "k", "v", "o", "router", "norm", "w2"):
        np.testing.assert_array_equal(lp[name], w[name])
    np.testing.assert_array_equal(
        lp["w13"], np.concatenate([w["w1"], w["w3"]], axis=-1))
    # the four shared experts side by side as one MLP of width 4 x 32
    assert lp["shared13"].shape == (64, 2 * 4 * 32)
    assert lp["shared2"].shape == (4 * 32, 64)
    for s in range(4):
        np.testing.assert_array_equal(
            lp["shared13"][:, 32 * s:32 * (s + 1)], w["s1"][s])
        np.testing.assert_array_equal(
            lp["shared13"][:, 128 + 32 * s:128 + 32 * (s + 1)], w["s3"][s])
        np.testing.assert_array_equal(
            lp["shared2"][32 * s:32 * (s + 1)], w["s2"][s])
    # queries and keys at their own spread
    assert 0.2 < float(np.std(lp["q"])) < 0.3 and \
        0.1 < float(np.std(lp["v"])) < 0.15
    params = cohere2_moe.init_params(cfg, SEED)
    np.testing.assert_array_equal(params["embed"],
                                  reference.draw_embedding(MODEL, SEED))


def test_the_rotation_pairs_neighbouring_lanes():
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 5, 2, 8)),
                    jnp.float32)
    pos = jnp.asarray([[0, 1, 7, 30, 200]])
    got = np.asarray(cohere2_moe._rotary(x, pos, 50000.0))
    np.testing.assert_allclose(got[0, 0], x[0, 0], atol=1e-6)
    for j in range(4):
        angle = np.asarray(pos[0], np.float64) * 50000.0 ** (-2 * j / 8)
        a, b = np.asarray(x[0, :, :, 2 * j]), np.asarray(x[0, :, :, 2 * j + 1])
        c, s = np.cos(angle)[:, None], np.sin(angle)[:, None]
        np.testing.assert_allclose(got[0, :, :, 2 * j], a * c - b * s,
                                   atol=1e-5)
        np.testing.assert_allclose(got[0, :, :, 2 * j + 1], b * c + a * s,
                                   atol=1e-5)
    np.testing.assert_allclose(
        got[0], reference.rotary(x[0], pos[0], 50000), atol=1e-5)


def test_the_norm_is_mean_centred():
    x = jnp.asarray(np.random.default_rng(3).standard_normal((4, 64)) + 2.0,
                    jnp.float32)
    got = np.asarray(cohere2_moe._layer_norm(x, jnp.ones((64,)), 1e-5))
    np.testing.assert_allclose(got.mean(-1), 0.0, atol=1e-5)
    np.testing.assert_allclose(got.std(-1), 1.0, atol=1e-3)
    np.testing.assert_allclose(
        got, reference.layer_norm(x, jnp.ones((64,)), 1e-5), atol=1e-5)


# ------------------------------------------------- the share and the model
def test_the_eight_shares_add_up_to_the_uncut_references_layer():
    """The PROGRAM's expert layer under each of the eight shares ``(0, 2)
    ... (14, 16)`` of a 16-expert layer, the shared experts' mean
    (computed by every chip alike) counted once, against the reference's
    layer with every expert held."""
    x = jnp.asarray(np.random.default_rng(2).standard_normal((40, 64)),
                    jnp.float32)
    u = cohere2_moe._layer_norm(x, jnp.ones((64,)), 1e-5)
    uncut = dict(MODEL, experts_held=[0, 16], num_experts=16)
    w = reference.draw_layer(uncut, SEED, 2)
    wrong = dict(reference.WRONG)
    wrong.pop("experts_held")
    mm = lambda a, m: a @ m
    whole = np.asarray(reference._experts(uncut, w, u, mm, wrong,
                                          (0, 16))[0])
    alike = np.asarray(reference._experts(
        uncut, dict(w, w2=jnp.zeros_like(w["w2"])), u, mm, wrong,
        (0, 16))[0])
    total, landed, routed = 0.0, 0, []
    for first in range(0, 16, 2):
        share = dict(MODEL, experts_held=[first, first + 2])
        cfg = _config(share)
        lp = cohere2_moe.init_layer(cfg, SEED, 2)
        out, load = cohere2_moe._experts(u, lp, cfg)
        total = total + (np.asarray(out) - alike)
        load = np.asarray(load)
        # its own experts' rows only, and what was routed anywhere
        assert load[0, :first].sum() == load[0, first + 2:].sum() == 0
        landed += int(load[0].sum())
        routed.append(int(load[2, 0]))
        # one pass over the share's capacity, and nothing else there
        assert load[2, 1] == 1 and not load[2, 2:].any()
    np.testing.assert_allclose(alike + total, whole, atol=2e-5)
    # every (token, choice) pair landed on exactly one of the eight
    assert routed == [40 * 4] * 8 and landed == 40 * 4
    assert np.abs(total).max() > 1e-2


# ----------------------------------------------------------------- engine
@pytest.mark.parametrize("n", [7, 16, 41, 70],
                         ids=["one_chunk", "a_bucket", "three_chunks",
                              "five_chunks"])
def test_prefill_in_chunks_then_decode_against_the_reference(n):
    """A window (12) shorter than the prompt and than a chunk (16): the
    prompt in chunks of the largest bucket (the second chunk on starts
    past the window's end), then 14 forced tokens through
    ``decode_step`` (across three pages' release); logits at the
    prompt's last position and after each fed token."""
    eng = _engine()
    tap = _Tap(eng)
    seq = _ids(n + 14, salt=n)
    assert eng.try_admit(1, seq[:n].tolist())
    for start in range(0, n, 16):
        eng.prefill_chunk(1, seq[start:min(n, start + 16)], start)
    got = [tap.last[0]]
    freed = eng.page_groups[1].freed
    for step in range(14):
        tokens = np.zeros((3,), np.int32)
        tokens[1] = seq[n + step]
        assert eng.ensure_pages(1, n + step + 1)
        eng.decode_step(tokens, active=[1])
        eng.advance(1)
        got.append(tap.last[1])
    want = _ref_logits(seq, np.arange(n - 1, n + 14))
    np.testing.assert_allclose(np.stack(got), want, atol=3e-5)
    if n + 14 > WINDOW + PAGE:
        assert eng.page_groups[1].freed > freed   # decode released pages


@pytest.mark.parametrize("n", [5, 23, 40])
def test_prefill_then_decode_through_the_scheduler(n):
    eng = _engine()
    prompt = _ids(n, salt=n).tolist()
    assert eng.generate([prompt], max_new_tokens=9)[0] == \
        _greedy_chain(prompt, 9)


def test_a_recycled_windowed_page_full_of_nan_reaches_no_request():
    eng = _engine()
    poison = tuple(jnp.full(p.shape, jnp.nan, p.dtype) for p in eng._pools())
    eng._update_cache(poison)
    prompt = _ids(37, salt=4).tolist()
    assert eng.generate([prompt], max_new_tokens=10)[0] == \
        _greedy_chain(prompt, 10)


@pytest.mark.pallas
def test_the_engine_with_the_kernels_interpreted_matches_the_oracles():
    """The grouped walk, ``chunk_attention`` and the grouped matmul under
    the interpreter at four query heads a key-value head, a share of the
    experts held."""
    prompts = [_ids(n, salt=n).tolist() for n in (29, 9)]
    want = _engine().generate(prompts, max_new_tokens=10)
    eng = _engine(inference={"paged_attention_kernel": "pallas"},
                  moe_kernel="pallas")
    assert (eng.paged_attention_kernel, eng.prefill_attention_kernel) == \
        ("pallas", "pallas")
    assert eng.generate(prompts, max_new_tokens=10) == want


def test_the_audit_lowers_the_programs_with_a_table_a_group():
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
        # beside the pools the tokens, the load with `routed`, the logits
        assert out[-2].shape == (3, 16) and out[-2].dtype == jnp.int32


# --------------------------------------------------- spans and the record
def test_the_spans_say_where_a_chunk_starts_and_what_landed_here(tmp_path):
    """``sched.prefill.chunk`` carries ``start``; ``moe.load`` of a model
    that holds a share carries ``routed`` and ``passes`` beside ``rows``."""
    eng = _engine()
    jax.profiler.start_trace(str(tmp_path))
    eng.generate([_ids(40, salt=7).tolist()], max_new_tokens=12)
    jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(
        [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
         for f in fs if f.endswith(".xplane.pb")][0])
    found = {"moe.load": [], "sched.prefill.chunk": []}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in found:
                    found[ev.name].append(dict(ev.stats))
    chunks = found["sched.prefill.chunk"]
    assert [(c["start"], c["tokens"], c["padded"]) for c in chunks] == \
        [(0, 16, 16), (16, 16, 16), (32, 8, 8)]
    assert [c["window_freed"] for c in chunks] == [1, 4, 2]
    loads = found["moe.load"]
    # three chunks, then eleven decode steps of three slots
    assert [s["routed"] for s in loads] == \
        [16 * 4 * 4] * 2 + [8 * 4 * 4] + [3 * 4 * 4] * 11
    assert all(0 <= s["rows"] <= s["routed"] for s in loads)
    assert all(set(s) >= {"rows", "experts_hit", "hottest_rows", "routed"}
               for s in loads)
    # every expert layer of every launch stayed inside its capacity
    assert [s["passes"] for s in loads] == [4] * 14
    # two of sixteen experts: about an eighth of what was routed
    assert 0.02 < sum(s["rows"] for s in loads) / \
        sum(s["routed"] for s in loads) < 0.35


def test_the_passes_are_summed_into_the_schedulers_metrics():
    """``passes`` takes the road ``routed`` took: the scheduler sums a
    launch's attributes into ``program_counters`` by name."""
    from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
    sched = ContinuousBatchingScheduler(_engine())
    sched.submit(_ids(20, salt=2).tolist(), max_new_tokens=4,
                 eos_token_id=None)
    sched.run()
    counted = sched.metrics.program_counters["moe.load"]
    assert counted["launches"] == 2 + 3          # two chunks, three steps
    assert counted["passes"] == 4 * counted["launches"]
    assert counted["routed"] == (16 + 8 + 3 * 3) * 4 * 4
    shown = sched.metrics.snapshot()["program_counters"]["moe.load"]
    assert shown["passes"] == counted["passes"]


def test_the_counter_attrs_of_a_fetched_load():
    load = np.zeros((3, 16), np.int32)
    load[0, 4:6], load[1, 4:6], load[2, :2] = (5, 0), (1, 0), (64, 4)
    assert cohere2_moe.Cohere2MoeDecoder.counter_attrs("moe.load", load) == \
        {"rows": 5, "experts_hit": 1, "hottest_rows": 5, "routed": 64,
         "passes": 4}
    # ops/moe.py's own attributes are what they were
    assert moe.load_attrs(load[:2]) == {"rows": 5, "experts_hit": 1,
                                        "hottest_rows": 5}


def test_the_start_up_record_says_which_share_is_held():
    eng = _engine()
    rows = [row for row in eng.startup_report()["rows"]
            if row["name"] == "setup.params"]
    assert len(rows) == 1
    attrs = rows[0]["attrs"]
    assert (attrs["experts_held"], attrs["experts"]) == (2, 16)
    assert attrs["leaves"] > 0 and attrs["bytes"] > 0
    # outside an engine's construction the decoder writes nowhere
    decoder = decoder_of(cohere2_moe.make_cohere2_moe_model(_config(),
                                                            seed=SEED))
    decoder.serving_params({"router": jnp.ones((2, 2))}, jnp.bfloat16)


def test_the_router_stays_float32_as_served():
    decoder = cohere2_moe.Cohere2MoeDecoder(_config())
    served = decoder.serving_params(
        cohere2_moe.init_params(_config(), SEED), jnp.bfloat16)
    assert served["layers"][0]["router"].dtype == jnp.float32
    assert served["layers"][0]["router"].shape == (64, 16)
    assert served["layers"][0]["w13"].shape == (2, 64, 64)
    assert {x.dtype for lp in served["layers"] for k, x in lp.items()
            if k != "router"} == {jnp.dtype(jnp.bfloat16)}
    assert served["embed"].dtype == jnp.bfloat16


# --------------------------------------------------------------- refusals
def test_a_model_mesh_axis_refuses_the_family():
    from deepspeed_tpu.parallel.topology import build_mesh
    mesh = build_mesh(data=jax.device_count() // 2, model=2)
    with pytest.raises(ValueError, match="no tensor-parallel layout"):
        deepspeed.init_inference(
            model=cohere2_moe.make_cohere2_moe_model(_config(), seed=SEED),
            mesh=mesh, config={"inference": {"dtype": "fp32"}})


@pytest.mark.parametrize("what, more", [
    ("prefix caching", {"prefix_caching": True}),
    ("speculative decoding", {"speculative": {"enabled": True,
                                              "method": "ngram",
                                              "num_draft_tokens": 2}}),
    ("the fleet's page hand-off", {"fleet": {"role": "prefill"}}),
])
def test_what_takes_a_page_for_a_positions_whole_state_refuses_a_window(
        what, more):
    with pytest.raises(ValueError, match=what + ".* cannot serve a model "
                       "with sliding-window layers or several page groups"):
        _engine(inference=more)


@pytest.mark.parametrize("key, value, why", [
    ("use_parallel_block", False, None),
    ("use_qk_norm", True, None),
    ("first_k_dense_replace", 1, "no leading dense layer"),
    ("expert_selection_fn", "softmax", None),
    ("shared_expert_combination_strategy", "sum", None),
    ("position_embedding_type", "rope", None),
    ("experts_held", [4, 7], None),
])
def test_a_config_the_program_does_not_compute_is_refused(key, value, why):
    with pytest.raises(AssertionError, match=why):
        cohere2_moe.config_from_hf(dict(MODEL, **{key: value}))


def test_the_decoder_protocol_names_the_family():
    from deepspeed_tpu.inference import decoder
    assert "make_cohere2_moe_model" in decoder.__doc__
    with pytest.raises(AssertionError, match="make_cohere2_moe_model"):
        decoder.decoder_of(object())
