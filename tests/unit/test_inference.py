"""Inference serving: init_inference, KV-cache decode, continuous batching.

The acceptance spec for the subsystem (ISSUE 2): incremental decode
logits match the full forward within 1e-5 (fp32, CPU), continuous
batching returns exactly what sequential generation returns, and prefill
bucketing bounds the number of jit traces.

Most tests share one module-level engine: slot reuse needs no cache
clearing (itself pinned below), so serving state never leaks between
requests — and the shared jit caches keep the file tier-1-fast.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as deepspeed
from deepspeed_tpu.models import gpt2

pytestmark = pytest.mark.inference

TINY = dict(vocab_size=128, max_seq_len=64, n_layers=2, n_heads=2,
            d_model=32, use_flash_attention=False, remat=False)


def tiny_model(seed=0, **over):
    cfg = gpt2.GPT2Config(**{**TINY, **over})
    return gpt2.make_gpt2_model(config=cfg, seed=seed)


def make_engine(model=None, **inference):
    inference.setdefault("max_batch_size", 2)
    inference.setdefault("prefill_buckets", [8, 16, 32])
    inference.setdefault("dtype", "fp32")
    inference.setdefault("greedy", True)
    return deepspeed.init_inference(model=model or tiny_model(),
                                    config={"inference": inference})


@pytest.fixture(scope="module")
def shared():
    """(model, engine) reused across tests — exercises slot reuse for free."""
    model = tiny_model()
    return model, make_engine(model)


def full_forward_logits(model, seq):
    """Dense full-forward logits for the whole sequence — the parity spec
    for decode. Causality makes row i valid for every prefix >= i+1, so
    ONE call at the final length checks every decode step."""
    ids = jnp.asarray(np.asarray(seq, np.int32)[None])
    hidden = gpt2.forward_hidden(model.params, ids, model.config,
                                 train=False)
    return np.asarray(hidden[0] @ model.params["wte"].T)


def greedy_chain(model, prompt, n):
    """Reference generation: n greedy tokens via repeated full forwards."""
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(full_forward_logits(model, seq)[-1].argmax()))
    return seq[len(prompt):]


# --------------------------------------------------------------- parity


def tap_logits(monkeypatch, eng):
    """Every launch's last output, its logits, in launch order: the
    program makers wrapped by name, as the benchmark's check wraps
    them."""
    seen = []
    for name in ("_get_prefill_fn", "_get_decode_fn"):
        def tapped(*args, _make=getattr(eng, name), **kwargs):
            fn = _make(*args, **kwargs)

            def run(*operands):
                out = fn(*operands)
                seen.append(out[-1])
                return out
            return run
        monkeypatch.setattr(eng, name, tapped)
    return seen


def test_decode_logits_match_full_forward(shared, monkeypatch):
    """Prefill + 6 greedy decode steps through the public ``prefill`` /
    ``decode_step`` produce, at every step, the same next-token logits
    as the full forward over the final sequence (fp32, atol 1e-5)."""
    model, eng = shared
    rs = np.random.RandomState(0)
    prompt = rs.randint(0, 128, size=11).tolist()
    n = len(prompt)
    seen = tap_logits(monkeypatch, eng)

    seq = prompt + [eng.prefill(0, prompt)]
    step_logits = [np.asarray(seen[-1])]
    for _ in range(6):
        assert eng.ensure_pages(0, int(eng.lengths[0]) + 1)
        tokens = np.zeros((eng.num_slots,), np.int32)
        tokens[0] = seq[-1]
        nxt = eng.decode_step(tokens)
        eng.advance(0)
        step_logits.append(np.asarray(seen[-1][0, 0]))
        seq.append(int(nxt[0]))
    eng.free_slot(0)
    assert len(seen) == 7 and eng.allocator.pages_in_use == 0

    ref = full_forward_logits(model, seq)      # one dense pass at the end
    for t, got in enumerate(step_logits):
        np.testing.assert_allclose(got, ref[n - 1 + t], atol=1e-5)
    # greedy sampling == argmax of those logits
    assert seq[n:] == [int(ref[n - 1 + t].argmax()) for t in range(7)]


# --------------------------------------------- continuous batching


def test_continuous_batching_matches_sequential(shared):
    """Scheduler output == one-request-at-a-time generation (greedy), with
    prompts spanning buckets."""
    _, eng = shared
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, 128, size=sz).tolist() for sz in (3, 9, 14, 5)]
    batched = eng.generate(prompts, max_new_tokens=5)
    sequential = [eng.generate([p], max_new_tokens=5)[0] for p in prompts]
    assert batched == sequential
    assert all(len(o) == 5 for o in batched)


def test_scheduler_overlaps_and_retires(shared):
    """Heterogeneous lengths don't serialize: with 2 slots and 3 requests
    of very different budgets, the short ones retire and free their slot
    while the long one keeps decoding."""
    from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
    from deepspeed_tpu.utils.monitor import ServingMetrics
    _, eng = shared
    metrics = ServingMetrics()
    sched = ContinuousBatchingScheduler(eng, metrics=metrics)
    long_uid = sched.submit([1, 2, 3], max_new_tokens=20)
    s1 = sched.submit([4, 5], max_new_tokens=2)
    s2 = sched.submit([6], max_new_tokens=2)
    results = sched.run()
    assert len(results[long_uid]) == 20
    assert len(results[s1]) == 2 and len(results[s2]) == 2
    # total decode steps must be near the LONG request's budget, not the
    # sum of all three (continuous batching, not sequential batches)
    assert sched.steps <= 22, sched.steps
    snap = metrics.snapshot()
    assert snap["prefill_tokens"] == 6
    assert snap["decode_tokens"] >= 20
    assert snap["peak_queue_depth"] >= 1


def test_eos_retires_slot(shared):
    _, eng = shared
    prompt = [7, 7, 7]
    free_run = eng.generate([prompt], max_new_tokens=8)[0]
    eos = free_run[2]
    out = eng.generate([prompt], max_new_tokens=8, eos_token_id=eos)[0]
    # generation stops at the FIRST occurrence of eos (inclusive)
    assert out == free_run[:free_run.index(eos) + 1]
    assert eng.lengths.tolist() == [0] * eng.num_slots  # all slots freed


def test_config_eos_token_id_is_honored(shared):
    """inference.eos_token_id from ds_config applies through generate();
    an explicit eos_token_id=None disables it."""
    model, eng0 = shared                     # no config-level eos
    free = eng0.generate([[7, 7, 7]], max_new_tokens=6)[0]
    eos = free[1]
    eng = make_engine(model, eos_token_id=int(eos))
    out = eng.generate([[7, 7, 7]], max_new_tokens=6)[0]
    assert out == free[:free.index(eos) + 1]
    assert eng.generate([[7, 7, 7]], max_new_tokens=6,
                        eos_token_id=None)[0] == free


def test_slot_reuse_is_clean(shared):
    """A slot reused by a later request must not see the earlier
    request's cache entries (stale tail is masked, prefix overwritten)."""
    model, eng = shared
    rs = np.random.RandomState(2)
    long_p = rs.randint(0, 128, size=14).tolist()
    short_p = rs.randint(0, 128, size=4).tolist()
    eng.generate([long_p], max_new_tokens=6)       # fills slot 0 deep
    out = eng.generate([short_p], max_new_tokens=3)[0]   # reuses it shallow
    assert out == greedy_chain(model, short_p, 3)


def test_scan_blocks_model_serves_after_unstack():
    """A scan_blocks-trained model (stacked (L, ...) block params) is
    unstacked at engine build and serves with exact parity to its own
    full forward."""
    model = tiny_model(scan_blocks=True)
    eng = make_engine(model)
    prompt = [5, 80, 13, 2]
    out = eng.generate([prompt], max_new_tokens=3)[0]
    seq = list(prompt)
    for _ in range(3):   # greedy chain via the scan forward
        ids = jnp.asarray(np.asarray(seq, np.int32)[None])
        hidden = gpt2.forward_hidden(model.params, ids, model.config,
                                     train=False)
        seq.append(int(np.asarray(hidden[0, -1] @ model.params["wte"].T)
                       .argmax()))
    assert out == seq[len(prompt):]


def test_max_seq_len_caps_generation(shared):
    _, eng = shared
    prompt = list(range(30))           # max_seq_len 64 -> at most 35 new
    out = eng.generate([prompt], max_new_tokens=100)[0]
    assert len(out) == 64 - 30 + 1     # decode until the cache is full


# ---------------------------------------------------- recompile bounds


def test_prefill_bucketing_caps_jit_traces():
    """7 distinct prompt lengths, 3 buckets -> at most 3 prefill traces
    and exactly 1 decode trace (fresh engine so the count is exact)."""
    eng = make_engine(max_new_tokens=2)
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 128, size=sz).tolist()
               for sz in range(2, 30, 4)]
    eng.generate(prompts)
    assert eng.compile_stats["prefill_traces"] <= 3
    assert eng.compile_stats["decode_traces"] == 1


def test_bucket_for_rejects_oversized_prompt(shared):
    _, eng = shared
    with pytest.raises(ValueError, match="prefill bucket"):
        eng.bucket_for(33)


def test_bad_request_params_rejected_at_submit(shared):
    _, eng = shared
    with pytest.raises(AssertionError, match="max_new_tokens"):
        eng.generate([[1, 2]], max_new_tokens=0)
    # oversized top_k clamps to vocab instead of a trace-time error
    out = eng.generate([[1, 2]], max_new_tokens=2,
                       sampling={"greedy": False, "top_k": 10 ** 6})
    assert len(out[0]) == 2


# ----------------------------------------------------------- sampling


def test_sampler_greedy_is_argmax():
    from deepspeed_tpu.inference.sampling import make_sampler
    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.randn(3, 50).astype(np.float32))
    out = make_sampler(True)(logits, jax.random.PRNGKey(0), 1.0, 1.0)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(logits).argmax(-1))


def test_sampler_top_k_masks_tail():
    from deepspeed_tpu.inference.sampling import make_sampler
    sample = make_sampler(False, top_k=2)
    logits = jnp.asarray([[0.0, 5.0, 4.0, -1.0, 1.0]] * 64,
                         dtype=jnp.float32)
    toks = np.asarray(sample(logits, jax.random.PRNGKey(1),
                             jnp.float32(1.0), jnp.float32(1.0)))
    assert set(toks.tolist()) <= {1, 2}


def test_sampler_top_p_keeps_nucleus():
    from deepspeed_tpu.inference.sampling import make_sampler
    sample = make_sampler(False, top_k=0)
    # token 0 has ~98% mass: top_p=0.5 nucleus is exactly {0}
    logits = jnp.asarray([[8.0, 4.0, 3.0, 2.0, 1.0]] * 64,
                         dtype=jnp.float32)
    toks = np.asarray(sample(logits, jax.random.PRNGKey(2),
                             jnp.float32(1.0), jnp.float32(0.5)))
    assert set(toks.tolist()) == {0}


def test_sampler_temperature_flattens():
    from deepspeed_tpu.inference.sampling import make_sampler
    sample = make_sampler(False, top_k=0)
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]] * 512, dtype=jnp.float32)
    cold = np.asarray(sample(logits, jax.random.PRNGKey(3),
                             jnp.float32(0.05), jnp.float32(1.0)))
    hot = np.asarray(sample(logits, jax.random.PRNGKey(3),
                            jnp.float32(20.0), jnp.float32(1.0)))
    assert (cold == 0).all()                  # ~argmax at low temperature
    assert len(np.unique(hot)) >= 3           # near-uniform at high temp


def test_sampled_generation_is_reproducible():
    model = tiny_model()
    kw = dict(max_batch_size=1, prefill_buckets=[8], greedy=False,
              top_k=8, temperature=0.9)
    a = make_engine(model, **kw)
    b = make_engine(model, **kw)
    prompt = [3, 1, 4, 1, 5]
    assert a.generate([prompt], max_new_tokens=5) == \
        b.generate([prompt], max_new_tokens=5)   # same seed -> same keys


# ------------------------------------------------------ config surface


def test_inference_config_parses_and_validates():
    from deepspeed_tpu.inference.config import (DeepSpeedInferenceConfig,
                                                DeepSpeedInferenceConfigError)
    ic = DeepSpeedInferenceConfig({"inference": {
        "max_batch_size": 16, "max_seq_len": 256,
        "prefill_buckets": [128, 32], "dtype": "bf16",
        "max_new_tokens": 10, "eos_token_id": 50256,
        "greedy": False, "temperature": 0.7, "top_k": 40, "top_p": 0.9}})
    assert ic.max_batch_size == 16
    assert ic.prefill_buckets == [32, 128]      # sorted, deduped
    assert ic.dtype == jnp.bfloat16
    assert ic.resolve_buckets(256) == [32, 128]
    # a configured bucket beyond max_seq_len is a config error, not a
    # silently-dropped entry
    with pytest.raises(DeepSpeedInferenceConfigError, match="exceed"):
        ic.resolve_buckets(64)
    # defaults: power-of-two ladder capped by max_seq_len
    assert DeepSpeedInferenceConfig({}).resolve_buckets(256) == [64, 128, 256]
    for bad in ({"max_batch_size": 0}, {"dtype": "int8"},
                {"temperature": 0.0}, {"top_p": 0.0},
                {"prefill_buckets": []}, {"top_k": -1}):
        with pytest.raises(DeepSpeedInferenceConfigError):
            DeepSpeedInferenceConfig({"inference": bad})


def test_inference_only_ds_config_needs_no_batch_triple():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    cfg = DeepSpeedConfig(None, param_dict={
        "inference": {"max_batch_size": 2}}, inference_only=True)
    assert cfg.inference_config.max_batch_size == 2
    assert cfg.train_micro_batch_size_per_gpu == 1
    # the TRAINING parse still demands its batch triple even when an
    # inference section is present (one config may drive both entry points)
    with pytest.raises(AssertionError, match="train_batch_size"):
        DeepSpeedConfig(None, param_dict={"inference": {}})
    # and init_inference works from an empty dict (all defaults)
    eng = deepspeed.init_inference(model=tiny_model(), config={})
    assert eng.num_slots == 8


def test_unknown_inference_key_strict_raises():
    from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                              DeepSpeedConfigError)
    with pytest.raises(DeepSpeedConfigError, match="inference"):
        DeepSpeedConfig(None, param_dict={
            "config_validation": "strict",
            "inference": {"max_batch_sizes": 4}}, inference_only=True)


# ----------------------------------------------------------- sharding


def test_kv_cache_sharded_over_heads_and_decode_parity():
    """TP mesh: params placed with Megatron specs; an engine built with
    NO ``inference.kv_*`` key shards its pages' packed heads, and decode
    still matches the unsharded full forward."""
    from deepspeed_tpu.parallel.topology import build_mesh
    from deepspeed_tpu.inference.kv_cache import PAGED_KV_CACHE_SPEC
    mesh = build_mesh(data=4, model=2)
    model = tiny_model()
    eng = deepspeed.init_inference(model=model, mesh=mesh, config={
        "inference": {"max_batch_size": 2, "prefill_buckets": [16],
                      "dtype": "fp32", "greedy": True}})
    assert eng.kv.k.sharding.spec == eng.kv.v.sharding.spec == \
        PAGED_KV_CACHE_SPEC
    assert eng.page_pool_stats()["num_pages"] * eng.page_size == \
        2 * TINY["max_seq_len"]
    assert "model" in str(
        eng.params["blocks"][0]["attn"]["qkv_kernel"].sharding.spec)
    prompt = [11, 3, 9, 60, 2]
    out = eng.generate([prompt], max_new_tokens=3)[0]
    assert out == greedy_chain(model, prompt, 3)


def test_init_inference_mp_size_builds_mesh():
    eng = deepspeed.init_inference(model=tiny_model(), mp_size=2, config={
        "inference": {"max_batch_size": 2, "dtype": "fp32"}})
    assert eng.mesh is not None and eng.mesh.shape["model"] == 2
