"""TPU-native inference serving: ``deepspeed_tpu.init_inference()``.

Subsystem layout:
  config.py      — the ds_config ``inference`` section
  kv_cache.py    — the page-pool KV cache every engine serves from
                   (heads-sharded), the per-slot state pool, and the
                   model drafter's contiguous cache
  decoder.py     — what the engine asks of a model, and what each
                   serving feature needs of a cache (``refuse``)
  paging.py      — host-side page allocator / prefix cache / chunk plans
  engine.py      — InferenceEngine: jitted prefill + fused decode/verify
  sampling.py    — jit-compatible greedy/temperature/top-k/top-p
  speculative.py — ngram + small-model drafters
  scheduler.py   — continuous batching at decode-step granularity with
                   chunked-prefill admission and preemption

``runtime/config.py`` imports ``.config`` while it is itself still
initializing, so the engine/scheduler classes (which import DeepSpeedConfig
back) are re-exported lazily.
"""
from .config import DeepSpeedInferenceConfig, DeepSpeedInferenceConfigError

__all__ = ["DeepSpeedInferenceConfig", "DeepSpeedInferenceConfigError",
           "InferenceEngine", "ContinuousBatchingScheduler",
           "InferenceRequest", "KVCache", "PagedKVCache", "PageAllocator",
           "PrefixCache", "NGramDrafter", "ModelDrafter"]

_LAZY = {
    "InferenceEngine": "engine",
    "ContinuousBatchingScheduler": "scheduler",
    "InferenceRequest": "scheduler",
    "KVCache": "kv_cache",
    "PagedKVCache": "kv_cache",
    "PageAllocator": "paging",
    "PrefixCache": "paging",
    "NGramDrafter": "speculative",
    "ModelDrafter": "speculative",
}


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(name)
    import importlib
    return getattr(importlib.import_module("." + mod, __name__), name)
