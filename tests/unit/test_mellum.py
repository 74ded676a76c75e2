"""Mellum (sliding-window layers beside full ones over two page groups, a
softmax router) at a tiny size on the CPU: the program against the
float32 reference, the two groups' page accounting (a windowed table
holds exactly the pages with a visible key), the windowed page walk
against its oracle, YaRN's table, the router's two scorings, the three
refusals, and the one table that a one-group family's programs still
take."""
import importlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as deepspeed
from benchmark import manifest
from benchmark.models import mellum2_reference as reference
from deepspeed_tpu.inference.decoder import CacheSpec, PageGroup
from deepspeed_tpu.inference.paging import GARBAGE_PAGE, GroupPages
from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.models import mellum
from deepspeed_tpu.models.jamba import _attend
from deepspeed_tpu.ops import moe

paged_attention = importlib.import_module(
    "deepspeed_tpu.ops.pallas.paged_attention")

SLIDING, FULL = mellum.SLIDING, mellum.FULL
# the catalog row Mellum2-12B-A2.5B-Instruct's `config`
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
               "original_max_position_embeddings": 8192, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782},
        SLIDING: {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
WINDOW, PAGE, SEQ = 12, 4, 96
MODEL = dict(
    PUBLISHED, hidden_size=64, head_dim=32, num_attention_heads=4,
    num_key_value_heads=2, moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=3, num_hidden_layers=4,
    layer_types=[SLIDING, SLIDING, SLIDING, FULL],
    mlp_layer_types=["sparse"] * 4, max_position_embeddings=256,
    sliding_window=WINDOW, vocab_size=128,
    rope_parameters={
        FULL: dict(PUBLISHED["rope_parameters"][FULL], factor=4,
                   original_max_position_embeddings=32,
                   attention_factor=0.1 * np.log(4.0) + 1),
        SLIDING: PUBLISHED["rope_parameters"][SLIDING]},
    # 1 / sqrt(hidden_size): a signal passes through the narrow layers
    # as it does through the published widths at 0.02
    initializer_range=0.125, qk_norm_gain=1.5)
SEED = 5
VOCAB = MODEL["vocab_size"]


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _config(**overrides):
    return mellum.config_from_hf(MODEL, dtype=jnp.float32, **overrides)


def _engine(slots=3, buckets=(8, 16), num_pages=(72, 30), inference=None,
            **overrides):
    return deepspeed.init_inference(
        model=mellum.make_mellum_model(_config(**overrides), seed=SEED),
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
def test_param_count_at_the_published_sizes():
    whole = mellum.config_from_hf(PUBLISHED)
    assert mellum.num_params(whole) == reference.param_count(PUBLISHED) \
        == 12_149_923_072
    cut = dict(PUBLISHED, num_hidden_layers=8,
               layer_types=PUBLISHED["layer_types"][:8],
               mlp_layer_types=["sparse"] * 8)
    assert mellum.num_params(mellum.config_from_hf(cut)) == \
        reference.param_count(cut) == 3_794_968_832
    assert (len(whole.full_layers), len(whole.sliding_layers)) == (7, 21)
    spec = mellum.MellumDecoder(mellum.config_from_hf(cut)).cache_spec()
    assert spec.groups == (PageGroup(2), PageGroup(6, window=1024))
    assert spec.windowed and spec.kv_layers == 8


def test_yarn_table_against_the_closed_form():
    """``low`` 18 and ``high`` 35 at the published numbers; the lanes
    below ``low`` keep their frequency, those from ``high`` on have it
    over 16, the ramp between; plain rotary in the sliding layers."""
    cfg = mellum.config_from_hf(PUBLISHED)
    full, sliding = cfg.rope_of(3), cfg.rope_of(0)
    assert mellum.yarn_correction_range(full, 128) == (18, 35)
    assert reference.yarn_correction_range(
        PUBLISHED["rope_parameters"][FULL], 128) == (18, 35)
    assert full.attention_factor == pytest.approx(0.1 * np.log(16.0) + 1)
    j = np.arange(64)
    base = 500000.0 ** (-2.0 * j / 128)
    ramp = np.clip((j - 18) / 17.0, 0, 1)
    want = (1 - ramp) * base + ramp * base / 16
    np.testing.assert_allclose(mellum.inv_freq(full, 128), want, rtol=1e-12)
    np.testing.assert_allclose(
        reference.inv_freq(PUBLISHED["rope_parameters"][FULL], 128), want,
        rtol=1e-12)
    np.testing.assert_array_equal(mellum.inv_freq(full, 128)[:19], base[:19])
    np.testing.assert_allclose(mellum.inv_freq(full, 128)[35:],
                               base[35:] / 16, rtol=1e-12)
    np.testing.assert_allclose(mellum.inv_freq(sliding, 128), base,
                               rtol=1e-12)
    assert sliding.attention_factor == 1.0


def test_model_without_cache_matches_the_reference():
    """Whole sequences four windows long, the plain forward."""
    cfg = _config()
    params = mellum.init_params(cfg, SEED)
    ids = _ids(50)
    hidden = mellum.forward_hidden(params, jnp.asarray(ids)[None], cfg)
    got = np.asarray(mellum.logits(params, hidden))[0]
    np.testing.assert_allclose(got, _ref_logits(ids), atol=2e-5)
    # and the window is seen: without it the logits are others
    assert np.abs(got - _ref_logits(ids, window=0)).max() > 1e-2


def test_weights_are_the_references_own_recipe():
    cfg = _config()
    lp, w = mellum.init_layer(cfg, SEED, 1), reference.draw_layer(
        MODEL, SEED, 1)
    for name in ("q", "k", "v", "o", "router", "q_norm", "k_norm",
                 "attn_norm", "ffn_norm", "w2"):
        np.testing.assert_array_equal(lp[name], w[name])
    np.testing.assert_array_equal(
        lp["w13"], np.concatenate([w["w1"], w["w3"]], axis=-1))
    assert float(lp["q_norm"][0]) == 1.5
    params = mellum.init_params(cfg, SEED)
    np.testing.assert_array_equal(params["embed"],
                                  reference.draw_embedding(MODEL, SEED))
    np.testing.assert_array_equal(params["head"],
                                  reference.draw_head(MODEL, SEED))


# ----------------------------------------------------------------- engine
@pytest.mark.parametrize("n", [7, 16, 41, 70],
                         ids=["one_chunk", "a_bucket", "three_chunks",
                              "five_chunks"])
def test_prefill_in_chunks_then_decode_against_the_reference(n):
    """A window (12) shorter than the prompt and than a chunk (16): the
    prompt in chunks of the largest bucket, then 14 forced tokens
    through ``decode_step`` (across three pages' release); logits at
    the prompt's last position and after each fed token."""
    eng = _engine()
    tap = _Tap(eng)
    seq = _ids(n + 14, salt=n)
    assert eng.try_admit(1, seq[:n].tolist())
    for start in range(0, n, 16):
        eng.prefill_chunk(1, seq[start:min(n, start + 16)], start)
    got = [tap.last[0]]
    for step in range(14):
        tokens = np.zeros((3,), np.int32)
        tokens[1] = seq[n + step]
        assert eng.ensure_pages(1, n + step + 1)
        eng.decode_step(tokens, active=[1])
        eng.advance(1)
        got.append(tap.last[1])
    want = _ref_logits(seq, np.arange(n - 1, n + 14))
    np.testing.assert_allclose(np.stack(got), want, atol=3e-5)


@pytest.mark.parametrize("n", [5, 23, 40])
def test_prefill_then_decode_through_the_scheduler(n):
    eng = _engine()
    prompt = _ids(n, salt=n).tolist()
    assert eng.generate([prompt], max_new_tokens=9)[0] == \
        _greedy_chain(prompt, 9)


def _visible_pages(length):
    """Logical pages that hold a key the query at ``length`` sees."""
    first = max(0, length - WINDOW + 1) // PAGE
    return first, length // PAGE


def test_a_windowed_table_holds_exactly_the_pages_with_a_visible_key():
    """Through chunks and decode steps: after every launch the windowed
    group's row is the pages of the next query's window and nothing
    else, what left is free again, the table's width is bounded by the
    window and the largest chunk, and the full group keeps every page."""
    eng = _engine()
    full, window = eng.page_groups
    assert window.max_pages == (WINDOW + 16 - 2) // PAGE + 2 == 8
    assert full.max_pages == SEQ // PAGE and window.steady == 4
    n = 45
    seq = _ids(n + 20, salt=9)
    assert eng.try_admit(0, seq[:n].tolist())
    assert window.reserved == window.steady and window.counts[0] == 0
    assert full.counts[0] == -(-n // PAGE)

    def holds_the_window(length, written):
        # pages of positions [first visible to `length`, written)
        first = max(0, length - WINDOW + 1) // PAGE
        assert window.base[0] == first
        assert window.counts[0] == -(-written // PAGE) - first
        held = window.tables[0, :window.counts[0]]
        assert (held != GARBAGE_PAGE).all() and len(set(held)) == len(held)
        assert (window.tables[0, window.counts[0]:] == GARBAGE_PAGE).all()
        assert window.allocator.pages_in_use == window.counts[0]
        assert all(window.allocator.refcount(int(p)) == 1 for p in held)

    for start in range(0, n, 16):
        eng.prefill_chunk(0, seq[start:min(n, start + 16)], start)
        done = min(n, start + 16)
        holds_the_window(done, done)
    for step in range(20):
        at = n + step
        assert eng.ensure_pages(0, at + 1)
        holds_the_window(at, at + 1)
        first, last = _visible_pages(at)
        assert window.counts[0] == last - first + 1 <= window.steady
        tokens = np.zeros((3,), np.int32)
        tokens[0] = seq[at]
        eng.decode_step(tokens, active=[0])
        eng.advance(0)
    assert full.counts[0] == -(-(n + 20) // PAGE)
    assert window.freed == window.base[0] > 0
    eng.free_slot(0)
    for group in eng.page_groups:
        assert group.allocator.pages_in_use == 0 and group.counts[0] == 0
        assert (group.tables == GARBAGE_PAGE).all() and group.base[0] == 0
    assert window.reserved == 0


def test_a_request_is_admitted_only_when_both_pools_have_room():
    # the full pool has room for one prompt of 60; the window's for all
    eng = _engine(num_pages=(24, 30))
    a, b = _ids(60, 1).tolist(), _ids(60, 2).tolist()
    assert eng.try_admit(0, a) and not eng.try_admit(1, b)
    assert eng.page_groups[1].reserved == eng.page_groups[1].steady
    eng.free_slot(0)
    # the windowed pool promises two slots' steady pages and a chunk's:
    # 2 x 4 + 4 = 12 of 13; a third promise does not fit
    eng = _engine(num_pages=(72, 13))
    assert eng.try_admit(0, a) and eng.try_admit(1, b)
    full = eng.page_groups[0]
    before = full.allocator.pages_in_use
    assert not eng.try_admit(2, _ids(8, 3).tolist())
    # and the full group gave back what it had taken for it
    assert full.allocator.pages_in_use == before and full.counts[2] == 0
    eng.free_slot(1)
    assert eng.try_admit(2, _ids(8, 3).tolist())


def test_retire_preemption_and_resume_return_every_page_of_both_pools():
    """A full pool too small for three long requests to finish side by
    side: the youngest is preempted, resumes, and every answer is the
    reference's; afterwards both pools are empty."""
    prompts = [_ids(n, salt=n).tolist() for n in (30, 26, 22)]
    want = [_greedy_chain(p, 12) for p in prompts]
    eng = _engine(num_pages=(26, 30))
    sched = ContinuousBatchingScheduler(eng)
    uids = [sched.submit(p, max_new_tokens=12) for p in prompts]
    results = sched.run()
    assert sched.preemptions > 0
    assert [results[u] for u in uids] == want
    for group in eng.page_groups:
        assert group.allocator.pages_in_use == 0
        assert (group.tables == GARBAGE_PAGE).all()
        assert not group.counts.any() and not group.base.any()
    assert eng.page_groups[1].reserved == 0
    stats = eng.page_pool_stats()
    assert stats["groups"][1]["pages_freed_sliding"] > 0
    assert stats["groups"][1]["table_width"] == 8
    snap = sched.metrics.snapshot()["page_groups"]
    assert len(snap["live"]) == 2 and snap["freed"][0] == 0 < snap["freed"][1]


def test_a_recycled_windowed_page_full_of_nan_reaches_no_request():
    eng = _engine()
    poison = tuple(jnp.full(p.shape, jnp.nan, p.dtype) for p in eng._pools())
    eng._update_cache(poison)
    prompt = _ids(37, salt=4).tolist()
    assert eng.generate([prompt], max_new_tokens=10)[0] == \
        _greedy_chain(prompt, 10)


@pytest.mark.pallas
def test_the_engine_with_the_kernels_interpreted_matches_the_oracles():
    prompts = [_ids(n, salt=n).tolist() for n in (29, 9)]
    want = _engine().generate(prompts, max_new_tokens=10)
    eng = _engine(inference={"paged_attention_kernel": "pallas"},
                  moe_kernel="pallas")
    assert eng.generate(prompts, max_new_tokens=10) == want


@pytest.mark.pallas
def test_the_engine_says_which_read_path_each_family_holds():
    """Under ``pallas`` a chunk (a page of tokens or more) goes to
    ``chunk_attention`` and a decode step to the page walk; the start-up
    record's ``setup.kernels`` row and the engine say which path the
    prefill family holds. ``auto`` off the chip keeps both in XLA's
    loop, the oracle."""
    from deepspeed_tpu.analysis.auditor import engine_program_specs
    eng = _engine(inference={"paged_attention_kernel": "pallas"})
    assert (eng.paged_attention_kernel, eng.prefill_attention_kernel) == \
        ("pallas", "pallas")
    kernels = [row for row in eng.startup_report()["rows"]
               if row["name"] == "setup.kernels"]
    assert [row["attrs"]["prefill_attn"] for row in kernels] == ["pallas"]
    for spec in engine_program_specs(eng):
        text = str(jax.make_jaxpr(spec.build())(*spec.args))
        chunk, walk = ("name=chunk_attention" in text,
                       "name=paged_attention_grouped" in text)
        assert (chunk, walk) == ((True, False) if "prefill" in spec.name
                                 else (False, True)), spec.name
    auto = _engine()
    assert (auto.paged_attention_kernel, auto.prefill_attention_kernel) == \
        ("xla", "xla")
    for spec in engine_program_specs(auto):
        text = str(jax.make_jaxpr(spec.build())(*spec.args))
        assert "name=chunk_attention" not in text and \
            "name=paged_attention_grouped" not in text


def test_the_audit_lowers_the_programs_with_a_table_a_group():
    """``engine.audit()`` (the AOT shard-lint) builds each serving
    program's arguments itself: with a table and a base a page group in
    the one table's place every program traces, and both groups' pool
    pairs are donated and come back."""
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


def test_the_spans_say_what_the_window_gave_back(tmp_path):
    """``sched.decode.pages`` and ``sched.prefill.chunk`` carry
    ``window_freed``; the former also the decoding slots' live pages a
    group and the windowed pool's size."""
    eng = _engine()
    jax.profiler.start_trace(str(tmp_path))
    eng.generate([_ids(40, salt=7).tolist()], max_new_tokens=12)
    jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(
        [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
         for f in fs if f.endswith(".xplane.pb")][0])
    found = {"sched.decode.pages": [], "sched.prefill.chunk": []}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in found:
                    found[ev.name].append(dict(ev.stats))
    chunks = found["sched.prefill.chunk"]
    # a step with no slot decoding yet has no pages to count
    steps = [s for s in found["sched.decode.pages"] if s]
    assert len(chunks) == 3 and len(steps) == 11
    # the chunks ending at 16, 32 and 40 slid 1, 4 and 2 pages out
    assert [c["window_freed"] for c in chunks] == [1, 4, 2]
    assert sum(s["window_freed"] for s in steps) == 2
    assert all(s["window_pool"] == 30 for s in steps)
    assert [s["full_live"] for s in steps] == \
        [-(-(41 + i) // PAGE) for i in range(11)]
    assert all(3 <= s["window_live"] <= 4 for s in steps)


# ------------------------------------------------------------ the page walk
def _walk_inputs(rng, b=3, s=1, h=4, kvh=2, dh=32, pages=40, max_pages=12):
    pools = [jnp.asarray(rng.normal(size=(pages + 1, 2, PAGE, kvh * dh)),
                         jnp.float32) for _ in range(2)]
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    tables = rng.permutation(np.arange(1, pages + 1))[:b * max_pages] \
        .reshape(b, max_pages).astype(np.int32)
    return q, pools, tables


@pytest.mark.pallas
@pytest.mark.parametrize("seq", [1, 2])
@pytest.mark.parametrize("max_pages", [12, 3], ids=["wide", "three_pages"])
def test_the_windowed_walk_interpreted_matches_its_oracle(seq, max_pages):
    """Against ``ops/chunk_attention.py``'s blocked attention (the XLA
    path, itself held to the reference above), with the table absolute
    (the walk starts at the first page with a visible key) and slots of
    every length: inside one page, inside the window, past it (and in
    a table narrower than the walk's chunk of pages, its last page)."""
    from deepspeed_tpu.ops.chunk_attention import paged_blocked_attention
    rng = np.random.default_rng(3)
    q, pools, tables = _walk_inputs(rng, s=seq, max_pages=max_pages)
    last = max_pages * PAGE - seq
    positions = jnp.asarray([2, 9, min(43, last)], jnp.int32)
    valid = jnp.full((3,), seq, jnp.int32)
    for window in (None, 12, 5):
        got = paged_attention.paged_attention(
            q, *pools, tables, positions, valid, layer_idx=1,
            page_size=PAGE, interpret=True, window=window)
        want = paged_blocked_attention(q, *pools, 1, jnp.asarray(tables),
                                       positions, valid, PAGE, window)
        np.testing.assert_allclose(got, want, atol=2e-5)
    # a window that no key is older than: the program without one, bit
    # for bit
    none = paged_attention.paged_attention(
        q, *pools, tables, positions, valid, layer_idx=1, page_size=PAGE,
        interpret=True)
    wide = paged_attention.paged_attention(
        q, *pools, tables, positions, valid, layer_idx=1, page_size=PAGE,
        interpret=True, window=4096)
    np.testing.assert_array_equal(none, wide)


@pytest.mark.pallas
def test_without_a_window_the_walk_is_the_program_it_was():
    """``window=None`` traces the kernel there was before the argument:
    the same equations, name for name, as a call that does not pass it
    (LFM2 and Jamba run it), and its output against their oracle."""
    rng = np.random.default_rng(4)
    q, pools, tables = _walk_inputs(rng)
    positions = jnp.asarray([2, 9, 43], jnp.int32)
    valid = jnp.ones((3,), jnp.int32)

    def walk(**kwargs):
        return lambda *a: paged_attention.paged_attention(
            *a, layer_idx=1, page_size=PAGE, interpret=True, **kwargs)

    args = (q, *pools, tables, positions, valid)
    assert str(jax.make_jaxpr(walk())(*args)) == \
        str(jax.make_jaxpr(walk(window=None))(*args))
    assert str(jax.make_jaxpr(walk())(*args)) != \
        str(jax.make_jaxpr(walk(window=12))(*args))
    b, _, h, dh = q.shape
    rows = [p[tables, 1].reshape(b, -1, p.shape[-1] // dh, dh)
            for p in pools]
    want = _attend(q, *rows, positions, valid, None).reshape(q.shape)
    np.testing.assert_allclose(walk()(*args), want, atol=2e-5)


def test_a_window_is_the_grouped_walks_alone():
    rng = np.random.default_rng(5)
    q, pools, tables = _walk_inputs(rng, h=2, kvh=2)
    with pytest.raises(ValueError, match="only the grouped page walk"):
        paged_attention.paged_attention(
            q, *pools, tables, jnp.zeros((3,), jnp.int32),
            jnp.ones((3,), jnp.int32), layer_idx=0, page_size=PAGE,
            interpret=True, window=8)


# --------------------------------------------------------------- the router
def _router_inputs(seed=0, tokens=24, d=16, experts=8):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(tokens, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(d, experts)), jnp.float32))


def test_softmax_routing_is_the_references():
    """Float32 softmax over all experts, the choice by probability, the
    chosen renormalised (no eps); without renormalising, as they are."""
    x, router = _router_inputs()
    model = {"num_experts_per_tok": 3, "norm_topk_prob": True}
    want_c, want_w, p = reference.route(model, {"router": router}, x)
    chosen, weights = moe.route(x, router, None, 3, norm_eps=0.0,
                                scoring="softmax")
    np.testing.assert_array_equal(chosen, want_c)
    np.testing.assert_allclose(weights, want_w, rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-6)
    _, raw = moe.route(x, router, None, 3, norm_topk_prob=False,
                       scoring="softmax")
    np.testing.assert_allclose(raw, jnp.take_along_axis(p, chosen, -1),
                               rtol=1e-6)
    assert float(raw.sum(-1).max()) < 1.0


def test_the_sigmoid_path_is_unchanged():
    x, router = _router_inputs(seed=1)
    bias = jnp.linspace(-0.1, 0.1, 8)
    chosen, weights = moe.route(x, router, bias, 2, scaling=2.5)
    again = moe.route(x, router, bias, 2, scaling=2.5, scoring="sigmoid")
    np.testing.assert_array_equal(chosen, again[0])
    np.testing.assert_array_equal(weights, again[1])
    scores = jax.nn.sigmoid(jnp.dot(x, router,
                                    precision=jax.lax.Precision.HIGHEST))
    _, want_c = jax.lax.top_k(scores + bias, 2)
    picked = jnp.take_along_axis(scores, want_c, -1)
    np.testing.assert_array_equal(chosen, want_c)
    np.testing.assert_allclose(
        weights, 2.5 * picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    with pytest.raises(KeyError):
        moe.route(x, router, None, 2, scoring="tanh")


# ------------------------------------------------------------ page groups
def test_a_spec_without_groups_is_the_one_group_it_was():
    spec = CacheSpec(kv_layers=3, kv_heads=8, d_head=64)
    assert spec.groups == () and not spec.windowed
    assert spec.page_groups == (PageGroup(3),)
    with pytest.raises(AssertionError, match="kv_layers says"):
        CacheSpec(kv_layers=3, kv_heads=8, d_head=64,
                  groups=(PageGroup(2), PageGroup(2, window=8)))
    mixed = CacheSpec(kv_layers=4, kv_heads=8, d_head=64, groups=(
        PageGroup(1), PageGroup(3, window=8)))
    assert mixed.page_groups == mixed.groups and mixed.windowed
    assert mixed.page_groups[1] == PageGroup(3, 8)


def test_group_pages_slide_and_bound_the_table():
    """At the cell's numbers: a window of 1,024, pages of 16, chunks of
    2,048: 65 pages for a decode step and a table of (1,024 + 2,048) /
    16 + 1 columns, whatever ``max_seq_len`` is."""
    assert GroupPages.spans(1024, 16, 2048, 2048) == (65, 193)
    assert GroupPages.spans(1024, 16, 2048, 8192) == (65, 193)
    group = GroupPages(400, 2, 2048, 16, window=1024, chunk_tokens=2048)
    assert group.tables.shape == (2, 193)
    assert group.admit(0, 30000) and group.counts[0] == 0
    # a prompt's chunks, then decode: never more than the table holds
    at = 0
    while at < 30000:
        n = min(2048, 30000 - at)
        group.slide(0, at)
        assert group.grow(0, at + n)
        assert group.counts[0] <= 193
        at += n
        group.slide(0, at)
        assert group.counts[0] <= 65
    assert group.base[0] == (30000 - 1023) // 16
    assert group.allocator.pages_in_use == group.counts[0]
    # without a window: nothing slides, admission takes the pages
    plain = GroupPages(40, 2, 64, 16)
    assert plain.admit(1, 100) and plain.counts[1] == 7
    assert plain.slide(1, 90) == 0 and not plain.admit(0, 600)
    plain.release(1)
    assert plain.allocator.pages_in_use == 0


# --------------------------------------------------------------- refusals
def test_a_model_mesh_axis_refuses_the_family():
    from deepspeed_tpu.parallel.topology import build_mesh
    mesh = build_mesh(data=jax.device_count() // 2, model=2)
    with pytest.raises(ValueError, match="no tensor-parallel layout"):
        deepspeed.init_inference(
            model=mellum.make_mellum_model(_config(), seed=SEED), mesh=mesh,
            config={"inference": {"dtype": "fp32"}})


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
                       "with sliding-window layers or several page groups: "
                       "a page is not the whole of a position's state"):
        _engine(inference=more)


def test_the_refusals_say_one_sentence():
    import re
    from deepspeed_tpu.inference import decoder
    plain = CacheSpec(kv_layers=2, kv_heads=1, d_head=64)
    keeps_all = type("D", (), {})
    what = decoder.FEATURES["speculative"][0]
    for subject, spec, model in (
            (keeps_all, CacheSpec(kv_layers=2, kv_heads=1, d_head=576,
                                  page_lanes=640), "latent pages"),
            (type("D", (), {"recurrent": True}), plain, "recurrent layers"),
            (keeps_all, mellum.MellumDecoder(_config()).cache_spec(),
             "sliding-window layers")):
        with pytest.raises(ValueError, match="^" + re.escape(what) +
                           " cannot serve a model with " + model):
            decoder.refuse(subject, spec, "speculative")
    decoder.refuse(keeps_all, plain, "speculative")
    # two groups without a window have a table each all the same
    with pytest.raises(ValueError, match="several page groups"):
        decoder.refuse(keeps_all, CacheSpec(
            kv_layers=2, kv_heads=1, d_head=64,
            groups=(PageGroup(1), PageGroup(1))), "speculative")
    with pytest.raises(AssertionError, match="make_mellum_model.*groups"):
        decoder.decoder_of(object())


# ------------------------------------------- what a one-group family takes
class _Seen(Exception):
    pass


@pytest.mark.parametrize("tiny, pools, state", [
    ("tiny/configs/tiny-serve.json", 2, 0),
    ("tiny_jamba/configs/tiny-jamba.json", 2, 2),
    ("tiny_lfm2/configs/tiny-lfm2.json", 2, 1),
    ("tiny_moonlight/configs/tiny-moonlight.json", 1, 0),
], ids=["gpt2", "jamba", "lfm2", "moonlight"])
def test_a_one_group_family_uploads_and_walks_one_table(tiny, pools, state):
    """The decode program of GPT-2, Jamba, LFM2 and Moonlight is handed
    what it was before there were groups: the pools, the state arrays
    and the mask of slots that advance, tokens, lengths, ONE table
    (slots, max_pages) int32, key, temperature, top_p; and the prefill
    program one row."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "unit_benchmark", tiny)
    with open(path) as f:
        config = json.load(f)
    eng = manifest.plugin("models", config["family"]).build_serve_engine(
        config, 1)
    assert not eng._grouped and len(eng.page_groups) == 1
    assert eng.page_groups[0].tables is eng.page_tables
    assert eng.page_groups[0].allocator is eng.allocator

    def refusing(*args, **kwargs):
        def program(params, *rest):
            raise _Seen(rest)
        return program

    eng._get_decode_fn = eng._get_prefill_fn = refusing
    with pytest.raises(_Seen) as seen:
        eng.decode_step(np.zeros((eng.num_slots,), np.int32))
    rest = seen.value.args[0]
    extra = 1 if state else 0              # the slots that advance
    assert len(rest) == pools + state + extra + 6
    tokens, lengths, tables = rest[pools + state + extra:][:3]
    assert tokens.shape == (eng.num_slots, 1)
    assert lengths.shape == (eng.num_slots,)
    assert isinstance(tables, np.ndarray) and tables.dtype == np.int32
    assert tables.shape == (eng.num_slots, eng.max_pages)
    assert eng.try_admit(0, [1, 2, 3])
    with pytest.raises(_Seen) as seen:
        eng.prefill_chunk(0, [1, 2, 3], 0)
    rest = seen.value.args[0]
    row = rest[pools + state + extra + 1]
    assert isinstance(row, np.ndarray) and row.shape == (eng.max_pages,)
    assert len(rest) == pools + state + extra + 7
