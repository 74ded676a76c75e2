"""Benchmark: GPT-2 (350M) causal-LM pretraining throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

vs_baseline = measured MFU / 0.45 — the repo's north-star target
(BASELINE.json: Megatron-GPT2 ZeRO-2 at >=45% MFU).
"""
import json
import sys
import time

import numpy as np


def peak_for(device):
    """Peak bf16 flops/s per chip — the table lives with the telemetry
    subsystem now (deepspeed_tpu/telemetry/mfu.py) so the per-step
    StepRecords and this bench price MFU identically."""
    from deepspeed_tpu.telemetry.mfu import peak_flops_for
    return peak_flops_for(device)


def scratch_telemetry_dir(prefix):
    """Disposable telemetry output dir: the rolling snapshot rides the
    bench JSON line, so the JSONL dir is scratch — removed at process
    exit (atexit runs LIFO, so the collector's own exit handler closes
    the JSONL handle first). Shared by bench_inference.py and the
    telemetry-overhead bench; __graft_entry__._tele_cfg inlines the same
    pattern to stay importable without the repo root on sys.path.
    Without this every run leaked a /tmp directory."""
    import atexit
    import shutil
    import tempfile
    d = tempfile.mkdtemp(prefix=prefix)
    atexit.register(shutil.rmtree, d, ignore_errors=True)
    return d


def require_tpu():
    """The bench measures the chip: without one it exits non-zero with a
    one-line reason — no retry, no CPU client, no stand-in model."""
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit("bench: needs a TPU, jax found platform {!r}".format(
            platform))


# GPT-2 medium (350M) at seq 1024, the one configuration: d_model 1024
# tiles the MXU better than 125M's 768 (sweep: tests/perf/
# sweep_gpt2_mfu.py). bf16 Adam moments + bf16 grad-accum (lossless at
# gas=1) free ~2.8 GB of optimizer-state HBM, which buys REMAT OFF at
# micro_batch 20 (docs/roofline_gpt2_medium_v5e.md has the builder-timed
# grid of rounds 1-5). A compiler refusal of this configuration is the
# run's result, not a cue to try a smaller one.
SEQ = 1024
MICRO_BATCH = 20
WARMUP_STEPS = 3
STEPS = 20


def main():
    import jax
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    require_tpu()
    seq, steps, micro_batch = SEQ, STEPS, MICRO_BATCH
    cfg = gpt2.config_for("gpt2_medium", max_seq_len=seq, remat=False,
                          loss_chunk=128)
    n_params = gpt2.num_params(cfg)
    model = gpt2.make_gpt2_model(config=cfg)
    ds_config = {
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {
            "lr": 1e-4, "moments_dtype": "bf16"}},
        "data_types": {"grad_accum_dtype": "bf16"},
        "runtime": {"executor": "on", "executor_rewrites": {
            "passes": ["hoist", "fuse", "widen"]}},
        "steps_per_print": 10 ** 9,
        # per-step StepRecords; the final rolling snapshot lands in
        # the JSON line below so bench records carry MFU/phase/comm
        # trajectories
        "telemetry": {"enabled": True,
                      "output_path": scratch_telemetry_dir(
                          "bench_telemetry_"),
                      # fleet export plane (docs/fleet.md): the
                      # final /metrics scrape is embedded under
                      # extra.metrics (port 0 = ephemeral)
                      "metrics": {"enabled": True, "port": 0}},
    }
    engine, _, _, _ = deepspeed.initialize(model=model,
                                           config_params=ds_config)

    rng = np.random.RandomState(0)
    global_batch = micro_batch * engine.dp_world_size
    ids = rng.randint(0, cfg.vocab_size, size=(1, global_batch, seq)) \
        .astype(np.int32)
    batch = (ids, ids.copy())

    # compile + warmup; steps chain through the donated state, so
    # blocking on the last loss fences the whole loop
    for _ in range(WARMUP_STEPS):
        loss = engine.train_batch(batch=batch)
    jax.block_until_ready(loss)

    t0 = time.time()
    for _ in range(steps):
        loss = engine.train_batch(batch=batch)
    jax.block_until_ready(loss)
    dt = time.time() - t0

    # per-step collective bytes-on-wire for the run's ZeRO config vs the
    # flat-fp32 baseline
    from deepspeed_tpu.runtime.comm.wire import estimate_engine_comm_bytes
    comm = estimate_engine_comm_bytes(engine)

    tokens_per_step = global_batch * seq
    tokens_per_sec = tokens_per_step * steps / dt
    # flops/token: 6N for the dense path + 12*L*d*s for attention scores/ctx
    flops_per_token = 6.0 * n_params + 12.0 * cfg.n_layers * cfg.d_model * seq
    achieved = tokens_per_sec * flops_per_token / jax.device_count()
    mfu = achieved / peak_for(jax.devices()[0])

    print(json.dumps({
        "metric": "gpt2_350m_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec / jax.device_count(), 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "loss": round(float(loss), 4),
            "seq_len": seq,
            "global_batch": global_batch,
            "params": n_params,
            "device": jax.devices()[0].device_kind,
            "device_count": jax.device_count(),
            "backend": jax.devices()[0].platform,
            "rung": {"micro_batch": micro_batch, "remat": False,
                     "bf16_state": True},
            "comm": comm,
            # segment-executor accounting (docs/executor.md): plan
            # size and per-kind walls of the step plans this run
            # executed (the fused path is a one-segment plan; the
            # offload microbench reports the multi-segment plans)
            "executor": engine.executor_snapshot(),
            # omitted (not {}) on non-writer processes: the schema
            # checker rejects an empty snapshot (bin/check_bench_schema)
            **({"telemetry": engine.telemetry_snapshot()}
               if engine.telemetry is not None else {}),
            # final Prometheus scrape of the fleet metrics plane
            # (series count + exposition text; None-safe when the
            # metrics section is off or this is a non-writer process)
            **({"metrics": engine.telemetry.metrics_scrape()}
               if engine.telemetry is not None and
               engine.telemetry.metrics is not None else {}),
        },
    }))


def emit_error_json(metric, err):
    """Last-resort bench output: one parseable JSON line naming the
    failure (shared by bench.py and bench_inference.py)."""
    print(json.dumps({
        "metric": metric,
        "value": None, "unit": "tokens/s/chip", "vs_baseline": None,
        "error": "{}: {}".format(type(err).__name__, str(err)[:400]),
    }))


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as err:  # noqa: BLE001 - emit parseable JSON, not a trace
        emit_error_json("gpt2_350m_pretrain_tokens_per_sec_per_chip", err)
        sys.exit(1)
