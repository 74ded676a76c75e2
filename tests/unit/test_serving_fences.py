"""A serving launch puts one program on the device. The scheduler's
timers fence on the engine's own cache buffers (``InferenceEngine.wait``)
and send the device no op of their own; a greedy launch splits no key.
Training's timers, built with no fence, keep the device op."""
import glob
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as deepspeed
from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.models import gpt2, jamba
from deepspeed_tpu.utils import timer as timer_mod
from deepspeed_tpu.utils.monitor import ServingMetrics
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer

pytestmark = pytest.mark.inference

PROMPTS = [[5, 9, 2, 7, 1, 3, 8], list(range(1, 21)), [4, 4, 6]]
NEW_TOKENS = 6
JAMBA = {
    "attn_layer_offset": 1, "attn_layer_period": 2, "hidden_size": 64,
    "intermediate_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 256,
    "num_attention_heads": 4, "num_experts": 1, "num_hidden_layers": 2,
    "num_key_value_heads": 1, "rms_norm_eps": 1e-6, "vocab_size": 128,
    "initializer_range": 0.125}
# what the sampled engine below (seed 11) yields for these prompts at
# the commit before the fence and the greedy launch changed (f5da228)
SAMPLED_STREAM_AT_PARENT = [
    [67, 58, 97, 45, 122, 83], [99, 39, 5, 15, 57, 127],
    [69, 69, 73, 124, 5, 53]]


def _tiny_gpt2(seed=0, **over):
    cfg = gpt2.GPT2Config(**{
        "vocab_size": 128, "max_seq_len": 64, "n_layers": 2, "n_heads": 2,
        "d_model": 32, "use_flash_attention": False, "remat": False,
        **over})
    return gpt2.make_gpt2_model(config=cfg, seed=seed)


def _gpt2_engine(seed=0, draft_model=None, **inference):
    return deepspeed.init_inference(
        model=_tiny_gpt2(), seed=seed, draft_model=draft_model,
        config={"inference": {
            "max_batch_size": 2, "prefill_buckets": [8, 16, 32],
            "dtype": "fp32", "greedy": True,
            "kv_block_size": 8, "prefill_chunk_tokens": 16,
            **inference}})


def _jamba_engine():
    """One Mamba layer and one attention layer: a recurrent state pool
    beside the pages, both returned by every launch."""
    cfg = jamba.config_from_hf(JAMBA, dtype=jnp.float32)
    return deepspeed.init_inference(
        model=jamba.make_jamba_model(cfg, seed=5),
        config={"inference": {
            "max_batch_size": 2, "dtype": "fp32",
            "kv_block_size": 4, "num_pages": 40, "max_seq_len": 64,
            "paged_attention_kernel": "xla", "greedy": True,
            "prefill_buckets": [8, 16]}})


def _speculative_engine():
    return _gpt2_engine(
        draft_model=_tiny_gpt2(seed=123, n_layers=1),
        speculative={"enabled": True, "method": "model",
                     "num_draft_tokens": 3})


ENGINES = {"gpt2_paged": _gpt2_engine, "jamba_state_pool": _jamba_engine,
           "gpt2_model_drafter": _speculative_engine}


def _serve(engine, fence="engine"):
    """-> (tokens in prompt order, metrics, launches, wall seconds).
    ``fence="device"`` gives the scheduler the timers the parent commit
    built: no fence of the engine's, the op on the device."""
    launches = []
    launch = engine._launch
    engine._launch = lambda fn, args: (launches.append(fn),
                                       launch(fn, args))[1]
    try:
        metrics = ServingMetrics()
        sched = ContinuousBatchingScheduler(engine, metrics=metrics)
        if fence == "device":
            sched.timers = SynchronizedWallClockTimer()
        uids = [sched.submit(p, max_new_tokens=NEW_TOKENS,
                             eos_token_id=None) for p in PROMPTS]
        t0 = time.time()
        results = sched.run()
        wall = time.time() - t0
    finally:
        del engine._launch
    return [results[u] for u in uids], metrics, len(launches), wall


def _refuse(name):
    def refused(*args, **kwargs):
        raise AssertionError("a greedy serving launch called " + name)
    return refused


def _timer_sync_spans(trace_dir):
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    return sum(ev.name == "timer.sync"
               for plane in jax.profiler.ProfileData.from_file(path).planes
               for line in plane.lines for ev in line.events)


@pytest.mark.parametrize("family", sorted(ENGINES))
def test_greedy_serving_sends_the_device_no_fence_and_no_split(
        family, monkeypatch, tmp_path):
    """With the device fence and ``jax.random.split`` refused, greedy
    serving yields what the parent's path (device fences) yields; the
    stored key is the same array afterwards; and a traced run still
    holds two ``timer.sync`` spans a launch."""
    engine = ENGINES[family]()
    want, _, _, _ = _serve(engine, fence="device")
    rng = engine._rng
    monkeypatch.setattr(timer_mod, "_device_synchronize",
                        _refuse("_device_synchronize"))
    monkeypatch.setattr(jax.random, "split", _refuse("jax.random.split"))
    got, metrics, launches, wall = _serve(engine)
    assert got == want and all(len(t) == NEW_TOKENS for t in got)
    assert launches > len(PROMPTS)
    assert engine._rng is rng
    # host clock from the call to the fetched token: the device's work
    # is inside, the fetch waits for it
    assert metrics.prefill_seconds > 0 and metrics.decode_seconds > 0
    assert metrics.prefill_seconds + metrics.decode_seconds <= wall
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced, _, launches, _ = _serve(engine)
    finally:
        jax.profiler.stop_trace()
    assert traced == want
    assert _timer_sync_spans(str(tmp_path)) == 2 * launches


class _Buffer:
    """Stands where a cache array stood: notes that it was waited on."""

    def __init__(self, name, waited):
        self.name, self.waited = name, waited

    def block_until_ready(self):
        self.waited.append(self.name)


def test_wait_blocks_on_every_buffer_a_launch_returns():
    waited = []
    engine = _jamba_engine()
    state = ["state{}".format(i) for i in range(len(engine.state.arrays))]
    engine.kv.update((_Buffer("k", waited), _Buffer("v", waited)))
    engine.state.update([_Buffer(name, waited) for name in state])
    engine.wait()
    assert waited == ["k", "v"] + state and len(state) >= 2
    del waited[:]
    engine = _speculative_engine()
    engine.kv.update((_Buffer("k", waited), _Buffer("v", waited)))
    engine.drafter.kv.update((_Buffer("draft_k", waited),
                              _Buffer("draft_v", waited)))
    engine.wait()
    assert waited == ["k", "v", "draft_k", "draft_v"]


def test_sampled_stream_is_the_parents_and_splits_once_a_launch(
        monkeypatch):
    """``greedy: false`` with a fixed seed: the stream recorded at the
    parent commit, one split a launch."""
    engine = _gpt2_engine(seed=11, greedy=False, temperature=0.9,
                          top_k=20, top_p=0.95)
    splits = []
    split = jax.random.split
    monkeypatch.setattr(
        jax.random, "split",
        lambda *a, **k: (splits.append(1), split(*a, **k))[1])
    got, _, launches, _ = _serve(engine)
    assert got == SAMPLED_STREAM_AT_PARENT
    assert len(splits) == launches
    assert not np.array_equal(np.asarray(engine._rng),
                              np.asarray(jax.random.PRNGKey(11)))


@pytest.mark.parametrize("fenced", [False, True],
                         ids=["default_device_op", "callers_fence"])
def test_timer_fences_on_what_it_was_built_with(fenced, monkeypatch):
    """Training's contract: a timer built with no argument fences with
    ``_device_synchronize`` on start and on stop. One built with a
    fence calls that, and not the default."""
    default, own = [], []
    monkeypatch.setattr(timer_mod, "_device_synchronize",
                        lambda: default.append(1))
    timers = SynchronizedWallClockTimer(fence=lambda: own.append(1)) \
        if fenced else SynchronizedWallClockTimer()
    t = timers("phase")
    t.start()
    assert (len(default), len(own)) == ((0, 1) if fenced else (1, 0))
    t.stop()
    assert (len(default), len(own)) == ((0, 2) if fenced else (2, 0))
    assert t.elapsed(reset=True) >= 0.0
    assert timers("phase") is t and timers("other").fence_ is t.fence_
