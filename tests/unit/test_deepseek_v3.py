"""The ``deepseek_v3`` architecture (Moonlight-16B-A3B) through
``init_inference()`` at a tiny size on the CPU: three layers (one dense
MLP, two expert layers with a shared expert), widths cut (only here),
against the float32 reference ``benchmark/models/moonlight_reference.py``;
latent attention's two forms, the latent page pool and its decode
kernel on their own.
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as deepspeed
from benchmark.models import moonlight_reference as reference
from deepspeed_tpu.inference.decoder import CacheSpec, StateSpec
from deepspeed_tpu.inference.kv_cache import PagedKVCache
from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.models import deepseek_v3
from deepspeed_tpu.ops import mla, moe

kernels = importlib.import_module(
    "deepspeed_tpu.ops.pallas.paged_attention")

PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
MODEL = dict(
    PUBLISHED, hidden_size=64, intermediate_size=128, kv_lora_rank=128,
    max_position_embeddings=256, moe_intermediate_size=32,
    n_routed_experts=8, num_attention_heads=4, num_experts_per_tok=3,
    num_hidden_layers=3, num_key_value_heads=4, qk_nope_head_dim=32,
    qk_rope_head_dim=16, v_head_dim=32, vocab_size=128,
    # 1 / sqrt(hidden_size): a signal passes through the narrow layers
    # as it does through the published widths at 0.02
    initializer_range=0.125, expert_bias_std=0.04, attn_in_scale=2.0,
    attn_out_scale=2.0)
SEED = 5
VOCAB = MODEL["vocab_size"]
WINDOW = 64


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _config(**overrides):
    return deepseek_v3.config_from_hf(MODEL, dtype=jnp.float32, **overrides)


def _inference(slots=3, buckets=(8, 16), num_pages=48, **more):
    return {"inference": dict({
        "max_batch_size": slots, "dtype": "fp32",
        "kv_block_size": 4, "num_pages": num_pages, "max_seq_len": WINDOW,
        "prefill_buckets": list(buckets), "greedy": True,
        "max_new_tokens": 8}, **more)}


def _engine(slots=3, buckets=(8, 16), num_pages=48, inference=None,
            **overrides):
    return deepspeed.init_inference(
        model=deepseek_v3.make_deepseek_v3_model(_config(**overrides),
                                                 seed=SEED),
        config=_inference(slots, buckets, num_pages, **(inference or {})))


def _ids(n, salt=0):
    return np.random.default_rng([SEED, salt]).integers(0, VOCAB, n)


def _ref_logits(ids, positions=None):
    """The reference's logits at ``positions`` of ``ids``, padded to
    one length (the model is causal), so the reference compiles once."""
    positions = np.arange(len(ids)) if positions is None else positions
    padded = np.zeros((WINDOW,), np.int64)
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
    # the released model: 15.96B, 27 layers
    assert 15.95e9 < reference.param_count(PUBLISHED) < 15.97e9
    first_stage = dict(PUBLISHED, num_hidden_layers=5)
    count = reference.param_count(first_stage)
    cfg = deepseek_v3.config_from_hf(first_stage)
    assert deepseek_v3.num_params(cfg) == count
    assert 3.092e9 < count < 3.094e9              # 6.19 GB in bfloat16
    assert cfg.expert_layers == [1, 2, 3, 4] and cfg.d_shared == 2816
    assert cfg.mla.lanes == 640 and cfg.mla.rank + cfg.mla.rope == 576
    spec = deepseek_v3.DeepseekV3Decoder(cfg).cache_spec()
    assert spec.page_lanes == 640 and spec.kv_layers == 5 and not spec.state


def test_model_without_cache_matches_the_reference():
    cfg = _config()
    model = deepseek_v3.make_deepseek_v3_model(cfg, seed=SEED)
    assert deepseek_v3.num_params(cfg) == reference.param_count(MODEL) == \
        sum(x.size for x in jax.tree_util.tree_leaves(model.params))
    ids = _ids(40, salt=9)
    hidden = deepseek_v3.forward_hidden(model.params,
                                        jnp.asarray(ids)[None], cfg)
    got = np.asarray(deepseek_v3.logits(model.params, hidden))[0]
    np.testing.assert_allclose(got, _ref_logits(ids), atol=3e-5)
    labels = jnp.asarray(_ids(40, salt=10))[None]
    loss = deepseek_v3.lm_loss(model.params, jnp.asarray(ids)[None], labels,
                               cfg)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("layer", [0, 1], ids=["dense", "experts"])
def test_weights_are_the_references_own_recipe(layer):
    ref = reference.draw_layer(MODEL, 9, layer)
    got = deepseek_v3.init_layer(_config(), 9, layer)
    for pair, (a, b) in (("w13", ("w1", "w3")), ("shared13", ("s1", "s3"))):
        if a in ref and (pair != "shared13" or "s1" in ref):
            ref[pair] = jnp.concatenate([ref.pop(a), ref.pop(b)], -1)
    if "s2" in ref:
        ref["shared2"] = ref.pop("s2")
    assert set(ref) == set(got)
    for name in ref:
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(ref[name]))
    params = deepseek_v3.init_params(_config(), 9)
    np.testing.assert_array_equal(np.asarray(params["embed"]),
                                  np.asarray(reference.draw_embedding(MODEL,
                                                                      9)))
    np.testing.assert_array_equal(np.asarray(params["head"]),
                                  np.asarray(reference.draw_head(MODEL, 9)))


def test_absorbed_and_up_projected_attention_give_the_same_logits():
    """The logits at one position, read once from a decode step (the
    absorbed form over the pages) and once from the last token of a
    prompt chunk (the up-projected form over the same latents): each
    other's, and the reference's."""
    ids = _ids(30, salt=2).tolist()
    ref = _ref_logits(ids, [29])[0]

    engine = _engine()
    tap = _Tap(engine)
    assert engine.try_admit(0, ids[:29])
    engine.prefill_chunk(0, ids[:16], 0)
    engine.prefill_chunk(0, ids[16:29], 16)
    assert engine.ensure_pages(0, 30)
    tokens = np.zeros((engine.num_slots,), np.int32)
    tokens[0] = ids[29]
    engine.decode_step(tokens, active=[0])
    absorbed = tap.last.reshape(engine.num_slots, VOCAB)[0]

    engine = _engine()
    tap = _Tap(engine)
    assert engine.try_admit(0, ids)
    engine.prefill_chunk(0, ids[:16], 0)
    engine.prefill_chunk(0, ids[16:], 16)
    up_projected = tap.last.reshape(-1, VOCAB)[-1]

    np.testing.assert_allclose(absorbed, up_projected, atol=3e-5)
    np.testing.assert_allclose(absorbed, ref, atol=3e-5)


@pytest.mark.parametrize("n", [8, 11, 16, 23, 37],
                         ids=["full_bucket", "padded_bucket",
                              "largest_bucket", "two_chunks",
                              "three_chunks"])
def test_prefill_then_decode_through_the_scheduler(n):
    """One chunk = two = three = a padded bucket = the reference's full
    forward: the prompt's last logits, and then every decode step's
    (rotary positions of a later chunk and of each decode step; a later
    chunk reads the earlier ones' latents from the pages)."""
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
    np.testing.assert_allclose(got, ref, atol=3e-5)
    counted = sched.metrics.program_counters["moe.load"]
    assert counted["launches"] == chunks + 5
    last = n - 16 * (chunks - 1)
    padded = 16 * (chunks - 1) + (8 if last <= 8 else 16)
    assert counted["rows"] == (padded + 5 * engine.num_slots) * 3 * 2
    # what a cached token costs: 3 layers x 256 lanes x 4 bytes
    assert sched.metrics.snapshot()["kv_token_bytes"] == 3072 == \
        engine.kv_token_bytes


def test_a_recycled_page_full_of_nan_reaches_no_request():
    """Every page NaN beforehand (pad lanes too), one slot, three
    requests through it one after the other: each stream is the
    reference's; and the pad lanes of every live row are zero."""
    engine = _engine(slots=2, num_pages=24)
    engine.kv.update(tuple(jnp.full_like(a, jnp.nan)
                           for a in engine.kv.buffers()))
    sched = ContinuousBatchingScheduler(engine)
    prompts = [_ids(n, salt=100 + n).tolist() for n in (3, 19, 9)]
    uids = [sched.submit(p, max_new_tokens=4, eos_token_id=None)
            for p in prompts]
    results = sched.run()
    for uid, prompt in zip(uids, prompts):
        assert results[uid] == _greedy_chain(prompt, 4)
    pool = np.asarray(engine.kv.k)
    written = ~np.isnan(pool[1:, :, :, 0])
    assert written.any() and engine.kv.v is None
    assert pool.shape[-1] == 256              # 128 + 16, whole lanes
    assert (pool[1:][written][:, 144:] == 0).all()
    assert (pool[1:][written][:, :144] != 0).any()


def test_preemption_and_resume_give_the_same_tokens():
    engine = _engine(slots=2, num_pages=16)
    sched = ContinuousBatchingScheduler(engine)
    prompts = [_ids(9, salt=21).tolist(), _ids(10, salt=22).tolist()]
    uids = [sched.submit(p, max_new_tokens=30, eos_token_id=None)
            for p in prompts]
    results = sched.run()
    assert sched.preemptions >= 1
    for uid, prompt in zip(uids, prompts):
        assert results[uid] == _greedy_chain(prompt, 30)


def test_prefix_caching_on_and_off_give_the_same_tokens():
    """The pages are the whole of a request's state: two prompts that
    share their first 20 tokens, one after the other; the second maps
    the first's pages and both streams are the reference's."""
    shared = _ids(20, salt=40).tolist()
    prompts = [shared + _ids(5, salt=41).tolist(),
               shared + _ids(7, salt=42).tolist()]
    streams = {}
    for caching in (False, True):
        engine = _engine(inference={"prefix_caching": caching})
        streams[caching] = [
            engine.generate([p], max_new_tokens=5, eos_token_id=None)[0]
            for p in prompts]
        if caching:
            assert engine.prefix_stats()["tokens_saved"] >= 20
    assert streams[True] == streams[False] == \
        [_greedy_chain(p, 5) for p in prompts]


@pytest.mark.pallas
def test_the_engine_with_the_kernels_interpreted_matches_the_oracles():
    prompt = _ids(19, salt=31).tolist()
    streams = []
    for kernel in ("xla", "pallas"):
        engine = _engine(moe_kernel=kernel,
                         inference={"paged_attention_kernel": kernel})
        assert engine.paged_attention_kernel == kernel
        streams.append(engine.generate([prompt], max_new_tokens=5,
                                       eos_token_id=None)[0])
    assert streams[0] == streams[1] == _greedy_chain(prompt, 5)


# -------------------------------------------------------- latent attention
DIMS = mla.MLADims(heads=4, nope=32, rope=64, v=32, rank=128,
                   rope_theta=1e4)


def _latent_pool(rng, pages, layers, page_size):
    pool = jnp.asarray(rng.normal(size=(pages, layers, page_size,
                                        DIMS.lanes)), jnp.float32)
    return pool.at[..., DIMS.rank + DIMS.rope:].set(0)


@pytest.mark.pallas
@pytest.mark.parametrize("seq", [1, 2])
@pytest.mark.parametrize("block_tokens", [512, 8],
                         ids=["one_block", "many_blocks"])
def test_mla_decode_interpreted_matches_its_oracle(monkeypatch, seq,
                                                   block_tokens):
    """Slots of 0, 17 and 45 live tokens over a poisoned pool (the
    garbage page and every page no slot holds are NaN, pad lanes
    included), walked in one block and in blocks of two pages."""
    monkeypatch.setattr(kernels, "_MLA_BLOCK_TOKENS", block_tokens)
    rng = np.random.default_rng(0)
    page_size, max_pages, b = 4, 12, 3
    pool = _latent_pool(rng, 40, 2, page_size)
    lens = np.array([0, 17, 45], np.int32)
    tables, nxt = np.zeros((b, max_pages), np.int32), 1
    for i in range(b):
        for j in range(-(-(lens[i] + 1) // page_size)):
            tables[i, j], nxt = nxt, nxt + 1
    pool = pool.at[0].set(jnp.nan).at[nxt:].set(jnp.nan)
    q = jnp.asarray(rng.normal(size=(b, seq, 4, DIMS.lanes)), jnp.float32)
    q = q.at[..., DIMS.rank + DIMS.rope:].set(0)
    positions = jnp.maximum(jnp.asarray(lens) - (seq - 1), 0)
    valid = jnp.full((b,), seq, jnp.int32)
    got = kernels.mla_decode(q, pool, jnp.asarray(tables), positions, valid,
                             layer_idx=1, page_size=page_size,
                             rank=DIMS.rank, sm_scale=DIMS.scale,
                             interpret=True)
    rows = pool[jnp.asarray(tables), 1].reshape(b, max_pages * page_size,
                                                DIMS.lanes)
    want = mla.absorbed_attention_rows(q, rows, positions, valid, DIMS)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_mla_decode_refuses_a_row_that_is_not_whole_lanes():
    q = jnp.zeros((1, 1, 4, 576), jnp.float32)
    pool = jnp.zeros((4, 1, 4, 576), jnp.float32)
    with pytest.raises(ValueError, match="whole-lane"):
        kernels.mla_decode(q, pool, jnp.zeros((1, 2), jnp.int32),
                           jnp.zeros((1,), jnp.int32),
                           jnp.ones((1,), jnp.int32), layer_idx=0,
                           page_size=4, rank=512, sm_scale=1.0,
                           interpret=True)


def test_the_two_forms_are_the_same_function_on_their_own():
    """Queries against cached rows, once up-projected with a running
    softmax over blocks, once absorbed over the rows themselves."""
    rng = np.random.default_rng(3)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    b, s, K = 2, 5, 24
    rows = f(b, K, DIMS.lanes).at[..., DIMS.rank + DIMS.rope:].set(0)
    w_kvb = 0.1 * f(DIMS.rank, DIMS.heads * (DIMS.nope + DIMS.v))
    q_nope, q_pe = f(b, s, DIMS.heads, DIMS.nope), f(b, s, DIMS.heads,
                                                     DIMS.rope)
    positions = jnp.asarray([7, 19])
    q_pos = positions[:, None] + jnp.arange(s)[None]
    valid = jnp.full((b,), s)
    up = mla.prefill_attention(
        q_nope, q_pe,
        lambda c: jax.lax.dynamic_slice_in_dim(rows, c * 8, 8, 1), 3, 8,
        w_kvb, DIMS, q_pos, positions + s - 1)
    lat = mla.absorbed_attention_rows(
        mla.absorb(q_nope, q_pe, w_kvb, DIMS), rows, positions, valid, DIMS)
    np.testing.assert_allclose(up, mla.unabsorb(lat, w_kvb, DIMS),
                               atol=2e-5)


# ----------------------------------------------------------- expert layer
def test_route_at_6_of_64_with_the_scaling_factor():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((40, 32)), jnp.float32)
    router = jnp.asarray(0.3 * rng.standard_normal((32, 64)), jnp.float32)
    bias = jnp.asarray(0.2 * rng.standard_normal((64,)), jnp.float32)
    chosen, weights = moe.route(x, router, bias, 6, True, 2.446,
                                norm_eps=1e-20)
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    want = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :6]
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(want, -1))
    picked = np.take_along_axis(scores, np.asarray(chosen), -1)
    np.testing.assert_allclose(
        weights, 2.446 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.446,
                               rtol=1e-6)
    # the bias shifts the choice only
    plain, _ = moe.route(x, router, None, 6, True, 2.446)
    assert (np.sort(plain, -1) != np.sort(chosen, -1)).any()


def test_four_shares_of_the_experts_add_up_with_the_shared_expert_once():
    """``experts_held`` in the model: four shares' expert layers hold
    the same routed matrices as the whole model's; the routed parts add
    up to the whole layer's, and the shared expert, which every share
    holds whole, is counted ONCE."""
    whole_cfg = _config()
    lp = deepseek_v3.init_layer(whole_cfg, SEED, 1)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((24, 64)),
                    jnp.float32)
    want, load = deepseek_v3._ffn(x, lp, whole_cfg)
    shared = deepseek_v3._gated_mlp(x, lp["shared13"], lp["shared2"])
    routed, loads = 0.0, 0
    for first in (0, 2, 4, 6):
        cfg = _config(experts_held=(first, first + 2))
        part = deepseek_v3.init_layer(cfg, SEED, 1)
        np.testing.assert_array_equal(part["w13"],
                                      lp["w13"][first:first + 2])
        np.testing.assert_array_equal(part["shared2"], lp["shared2"])
        out, part_load = deepseek_v3._ffn(x, part, cfg)
        routed, loads = routed + (out - shared), loads + part_load
    np.testing.assert_allclose(routed + shared, want, atol=3e-5)
    np.testing.assert_array_equal(loads, load)
    assert float(jnp.abs(shared).max()) > 1e-2


# ------------------------------------------------------------ the caches
@pytest.mark.parametrize("family, spec, arrays", [
    ("gpt2", CacheSpec(kv_layers=24, kv_heads=16, d_head=64),
     [(9, 24, 16, 1024)] * 2),
    ("jamba", CacheSpec(kv_layers=2, kv_heads=1, d_head=128, state=(
        StateSpec("ssm", (26,), (5120, 16), jnp.float32),)),
     [(9, 2, 16, 128)] * 2),
    ("lfm2", CacheSpec(kv_layers=3, kv_heads=8, d_head=64),
     [(9, 3, 16, 512)] * 2),
    ("moonlight", CacheSpec(kv_layers=5, kv_heads=1, d_head=576,
                            page_lanes=640), [(9, 5, 16, 640)]),
])
def test_each_familys_pages_are_the_arrays_and_bytes_they_were(
        family, spec, arrays):
    """The ``(k, v)`` families allocate the pair they always did; latent
    pages ONE pool and no ``v``."""
    kv = PagedKVCache.allocate(8, spec.kv_layers, spec.kv_heads, 16,
                               spec.d_head, jnp.bfloat16,
                               lanes=spec.page_lanes)
    assert [a.shape for a in kv.buffers()] == arrays
    assert all(a.dtype == jnp.bfloat16 for a in kv.buffers())
    assert kv.nbytes == sum(2 * int(np.prod(s)) for s in arrays)
    assert kv.token_bytes == sum(2 * s[1] * s[3] for s in arrays)
    assert kv.k is kv.buffers()[0] and kv.num_pages == 8
    assert (kv.v is None) == (family == "moonlight")
    # what allocate() made before it took `lanes`
    if family != "moonlight":
        old = PagedKVCache.allocate(8, spec.kv_layers, spec.kv_heads, 16,
                                    spec.d_head, jnp.bfloat16)
        assert [a.shape for a in old.buffers()] == arrays
        assert old.nbytes == kv.nbytes
    kv.update(tuple(a + 1 for a in kv.buffers()))
    assert len(kv.buffers()) == len(arrays)


# --------------------------------------------------------------- refusals
def test_a_model_mesh_axis_refuses_the_family():
    from deepspeed_tpu.parallel.topology import build_mesh
    mesh = build_mesh(data=jax.device_count() // 2, model=2)
    with pytest.raises(ValueError, match="no tensor-parallel layout"):
        deepspeed.init_inference(
            model=deepseek_v3.make_deepseek_v3_model(_config(), seed=SEED),
            mesh=mesh,
            config={"inference": {"dtype": "fp32"}})


@pytest.mark.parametrize("what, more", [
    ("speculative decoding", {"speculative": {"enabled": True,
                                              "method": "ngram",
                                              "num_draft_tokens": 2}}),
    ("the fleet's page hand-off", {"fleet": {"role": "prefill"}}),
])
def test_what_takes_a_page_for_keys_and_values_refuses_latent_pages(
        what, more):
    with pytest.raises(ValueError, match=what + ".* latent pages"):
        _engine(inference=more)
