"""Benchmark: GPT-2 serving throughput through the inference subsystem.

Default mode prints ONE JSON line in bench.py's shape:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extra": {...}}

value = decode tokens/s/chip through the continuous-batching scheduler
(the serving steady state). vs_baseline = decode model-flops utilization
(2N flops/token, forward only) against a 5% target — decode is
HBM-bandwidth bound, so single-digit MFU is the healthy regime and 0.05
is the modest north star this harness tracks.

``--serving-trace [--out PATH]`` runs the HEAVY-TRAFFIC synthetic trace
instead (ISSUE 7): Zipf-distributed prompt/output lengths, bursty
Poisson arrivals, a shared system prompt on part of the traffic — three
engine configs at EQUAL KV HBM budget (slot baseline; paged; paged +
prefix sharing + ngram speculative decoding + chunked prefill), run
INTERLEAVED per the PR 5/6 microbench discipline, reporting p50/p95
TTFT, p50/p95 per-output-token latency, and goodput (completed-request
tokens/s). The artifact (default tests/perf/BENCH_SERVING.json) is
validated by bin/check_bench_schema.py.

``--disagg [--out PATH]`` runs the DISAGGREGATED rung (ISSUE 17): the
same Zipf/Poisson trace at 10x the load (560 requests) against two
configs at EQUAL aggregate KV budget — ``single`` (one paged chunked-
prefill monolith owning the whole page budget) vs ``disagg`` (a
DisaggServer fleet: 1 prefill host + 2 decode hosts on the simulated
multi-host CPU mesh, KV moving over the serialized page-slice wire,
placement through the SLO router). The artifact (default
tests/perf/BENCH_SERVING_r17.json) carries the handoff/router evidence
in ``extra.serving_trace.disagg`` and feeds bin/ds_scoreboard.py's
serving trajectory gate.
"""
import json
import sys
import time

import numpy as np

from bench import (emit_error_json, peak_for, require_tpu,
                   scratch_telemetry_dir)


def main():
    import jax
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from deepspeed_tpu.utils.monitor import ServingMetrics

    enable_compile_cache()
    require_tpu()
    cfg = gpt2.config_for("gpt2_medium", max_seq_len=1024, remat=False)
    inference = {"max_batch_size": 16, "dtype": "bf16",
                 "prefill_buckets": [128, 256, 512],
                 "max_new_tokens": 64, "greedy": True}
    n_requests, prompt_lens = 48, (64, 180, 400)

    n_params = gpt2.num_params(cfg)
    model = gpt2.make_gpt2_model(config=cfg)
    engine = deepspeed.init_inference(
        model=model,
        config={"inference": inference,
                # per-decode-step serving records; the final rolling
                # snapshot rides extra.telemetry below
                "telemetry": {"enabled": True,
                              "output_path": scratch_telemetry_dir(
                                  "bench_inf_telemetry_")}})

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size,
                           size=prompt_lens[i % len(prompt_lens)]).tolist()
               for i in range(n_requests)]

    # warmup: compile every prefill bucket + the decode fn off the clock
    engine.generate(prompts[:len(inference["prefill_buckets"])],
                    max_new_tokens=2)

    metrics = ServingMetrics()
    t0 = time.time()
    outs = engine.generate(prompts, metrics=metrics)
    wall = time.time() - t0
    assert len(outs) == n_requests and all(len(o) > 0 for o in outs)

    snap = metrics.snapshot()
    chips = jax.device_count()
    decode_tps = snap["decode_tokens_per_sec"]
    # decode flops/token: forward-only dense path ~ 2N
    flops_per_token = 2.0 * n_params
    mfu = (decode_tps * flops_per_token / chips) / peak_for(jax.devices()[0])

    print(json.dumps({
        "metric": "gpt2_inference_decode_tokens_per_sec_per_chip",
        "value": round(decode_tps / chips, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.05, 4),
        "extra": {
            "prefill_tokens_per_sec": snap["prefill_tokens_per_sec"],
            "decode_tokens_per_sec": decode_tps,
            "decode_mfu": round(mfu, 4),
            "mean_slot_occupancy": snap["mean_slot_occupancy"],
            "peak_queue_depth": snap["peak_queue_depth"],
            "requests": n_requests,
            "slots": engine.num_slots,
            "prefill_buckets": engine.prefill_buckets,
            "prefill_traces": engine.compile_stats["prefill_traces"],
            "wall_seconds": round(wall, 2),
            "params": n_params,
            "kv_cache_mb": round(engine.kv.nbytes / 2 ** 20, 1),
            "device": jax.devices()[0].device_kind,
            "device_count": jax.device_count(),
            "backend": jax.devices()[0].platform,
            # omitted (not {}) on non-writer processes: the schema
            # checker rejects an empty snapshot (bin/check_bench_schema)
            **({"telemetry": engine.telemetry_snapshot()}
               if engine.telemetry is not None else {}),
        },
    }))


# ---------------------------------------------------------------------
# heavy-traffic synthetic trace (ISSUE 7): slot vs paged vs paged+spec
# ---------------------------------------------------------------------

TRACE_SEED = 17
HBM_BUDGET_TOKENS = 1024          # slot baseline: 4 slots x 256 max_seq
TRACE_MAX_SEQ = 256
TRACE_PAGE = 16


def _zipf_clipped(rng, a, lo, hi, size):
    vals = rng.zipf(a, size=size) + lo - 1
    return np.clip(vals, lo, hi)


def build_trace(vocab, n_requests=56):
    """One fixed workload every config replays: Zipf prompt/output
    lengths, Poisson-burst arrival offsets (seconds), a shared system
    prompt on ~half the traffic, and document-sliced prompt bodies (so
    prompt-lookup drafting sees the repetitive structure real text
    has). Arrivals are deliberately faster than the slot baseline can
    drain — goodput must measure CAPACITY under backlog, not offered
    load."""
    rng = np.random.RandomState(TRACE_SEED)
    prompt_lens = _zipf_clipped(rng, 1.4, 4, 160, n_requests)
    output_lens = _zipf_clipped(rng, 1.3, 12, 96, n_requests)
    # "document": patterned token stream — windows of it repeat n-grams
    doc = np.tile(rng.randint(0, vocab, size=192), 4)
    system = rng.randint(0, vocab, size=48).tolist()
    requests, t = [], 0.0
    i = 0
    while i < n_requests:
        t += rng.exponential(0.06)                 # burst inter-arrival
        for _ in range(min(1 + rng.poisson(2.0), n_requests - i)):
            n = int(prompt_lens[i])
            if i % 2 == 0 and n > 16:
                body_n = max(n - len(system), 4)
                start = rng.randint(0, len(doc) - body_n)
                prompt = system + doc[start:start + body_n].tolist()
            else:
                start = rng.randint(0, len(doc) - n)
                prompt = doc[start:start + n].tolist()
            requests.append({"arrival_s": t, "prompt": prompt,
                             "max_new_tokens": int(output_lens[i])})
            i += 1
    return requests


def _trace_configs():
    """Three engine configs at EQUAL KV HBM budget. The slot baseline
    spends it as 4 contiguous max_seq rows; the paged configs spend the
    same bytes as a 64-page pool and raise CONCURRENCY instead (mixed
    Zipf lengths leave contiguous rows mostly empty)."""
    # minus one: the paged pool carries a reserved garbage page, and it
    # pays for it INSIDE the budget (usable 63 + garbage 1 = 64 pages =
    # exactly the slot layout's 1024 token-slots)
    pages = HBM_BUDGET_TOKENS // TRACE_PAGE - 1
    base = {"max_seq_len": TRACE_MAX_SEQ, "dtype": "fp32", "greedy": True,
            "prefill_buckets": [32, 64, 128, 256]}
    slot = dict(base, max_batch_size=HBM_BUDGET_TOKENS // TRACE_MAX_SEQ)
    paged = dict(base, max_batch_size=12, kv_layout="paged",
                 kv_block_size=TRACE_PAGE, num_pages=pages)
    paged_spec = dict(paged, prefix_caching=True, prefill_chunk_tokens=64,
                      speculative={"enabled": True, "method": "ngram",
                                   "num_draft_tokens": 6})
    return {"slot": slot, "paged": paged, "paged_spec": paged_spec}


def run_trace(engine, requests):
    """Replay the trace against one engine: submit each request when its
    arrival offset elapses, stepping the scheduler continuously. Returns
    the per-run metrics summary."""
    from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
    from deepspeed_tpu.utils.monitor import ServingMetrics
    if engine.prefix_cache is not None:
        # every round starts COLD: a warm prefix cache from the prior
        # round would hand the treatment config an advantage the slot
        # baseline has no analog of
        engine.prefix_cache.clear()
    metrics = ServingMetrics()
    sched = ContinuousBatchingScheduler(engine, metrics=metrics)
    pending = sorted(requests, key=lambda r: r["arrival_s"])
    t0 = time.perf_counter()
    idx = 0
    while idx < len(pending) or sched.has_work:
        now = time.perf_counter() - t0
        while idx < len(pending) and pending[idx]["arrival_s"] <= now:
            req = pending[idx]
            sched.submit(req["prompt"],
                         max_new_tokens=req["max_new_tokens"])
            # anchor TTFT at the TRACE arrival, not the (slightly
            # later) submit poll — queueing delay is the trace's point
            sched.queue[-1].arrival_t = t0 + req["arrival_s"]
            idx += 1
        if sched.has_work:
            sched.step()
        elif idx < len(pending):
            time.sleep(min(0.005, pending[idx]["arrival_s"] - now))
    wall = time.perf_counter() - t0
    snap = metrics.snapshot()
    out = {
        "wall_seconds": round(wall, 3),
        "goodput_tokens_per_sec": round(snap["completed_tokens"] / wall, 2),
        "completed_requests": snap["completed_requests"],
        "completed_tokens": snap["completed_tokens"],
        "decode_tokens_per_sec": snap["decode_tokens_per_sec"],
        "decode_steps": snap["decode_steps"],
        "ttft_p50_s": snap["ttft"]["p50_s"],
        "ttft_p95_s": snap["ttft"]["p95_s"],
        "tpot_p50_s": snap["tpot"]["p50_s"],
        "tpot_p95_s": snap["tpot"]["p95_s"],
        "mean_slot_occupancy": snap["mean_slot_occupancy"],
        "peak_queue_depth": snap["peak_queue_depth"],
        "preemptions": sched.preemptions,
    }
    if snap.get("speculative"):
        out["spec_acceptance_rate"] = snap["speculative"]["acceptance_rate"]
        out["tokens_per_decode_step"] = round(
            snap["decode_tokens"] / max(snap["decode_steps"], 1), 3)
    if engine.prefix_stats() is not None:
        out["prefix_hit_rate"] = engine.prefix_stats()["hit_rate"]
    return out


def serving_trace_main(out_path):
    import jax
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import gpt2

    cfg = gpt2.GPT2Config(vocab_size=512, max_seq_len=TRACE_MAX_SEQ,
                          n_layers=2, n_heads=4, d_model=128,
                          use_flash_attention=False, remat=False)
    model = gpt2.make_gpt2_model(config=cfg)
    requests = build_trace(cfg.vocab_size)
    engines = {}
    for name, inf in _trace_configs().items():
        engines[name] = deepspeed.init_inference(
            model=model, config={"inference": inf})
        # KV budget really is equal across configs
        assert engines[name].kv.nbytes == \
            engines["slot"].kv.nbytes, (name, engines[name].kv.nbytes)
        # warmup: compile every bucket + decode/verify off the clock
        engines[name].generate(
            [r["prompt"] for r in requests[:len(inf["prefill_buckets"])]],
            max_new_tokens=8)

    rounds = 3                  # odd: the middle of the sort IS a median
    results = {name: [] for name in engines}
    for _ in range(rounds):
        # interleaved rounds: machine drift hits every config equally
        for name, engine in engines.items():
            results[name].append(run_trace(engine, requests))

    def median_run(runs):
        return sorted(runs,
                      key=lambda r: r["goodput_tokens_per_sec"])[
                          len(runs) // 2]

    configs = {name: median_run(runs) for name, runs in results.items()}
    ratio = (configs["paged_spec"]["goodput_tokens_per_sec"] /
             configs["slot"]["goodput_tokens_per_sec"])
    payload = {
        "metric": "gpt2_serving_goodput_ratio_paged_spec_vs_slot",
        "value": round(ratio, 3),
        "unit": "x",
        # acceptance floor: >= 1.5x goodput at equal HBM budget
        "vs_baseline": round(ratio / 1.5, 4),
        "extra": {
            "serving_trace": {
                "trace": {"requests": len(requests), "seed": TRACE_SEED,
                          "prompt_len_max": max(len(r["prompt"])
                                                for r in requests),
                          "output_len_max": max(r["max_new_tokens"]
                                                for r in requests),
                          "span_s": round(requests[-1]["arrival_s"], 2)},
                "hbm_budget_tokens": HBM_BUDGET_TOKENS,
                "kv_bytes_per_config": engines["slot"].kv.nbytes,
                "rounds": rounds,
                "configs": configs,
            },
            "goodput_ratio_paged_vs_slot": round(
                configs["paged"]["goodput_tokens_per_sec"] /
                configs["slot"]["goodput_tokens_per_sec"], 3),
            "device": getattr(jax.devices()[0], "device_kind", "cpu"),
            "backend": jax.default_backend(),
        },
    }
    line = json.dumps(payload)
    print(line)
    if out_path:
        with open(out_path, "w") as fh:
            fh.write(line + "\n")
    return 0


# ---------------------------------------------------------------------
# disaggregated rung (ISSUE 17): single paged monolith vs a 1-prefill +
# 2-decode DisaggServer fleet at equal AGGREGATE page budget, 10x load
# ---------------------------------------------------------------------

DISAGG_REQUESTS = 560             # 10x the ISSUE 7 trace
DISAGG_DECODE_HOSTS = 2


def run_disagg_trace(server_factory, requests):
    """Replay the trace against a fresh DisaggServer, mirroring
    run_trace's arrival-anchored discipline: submit each request when
    its offset elapses (TTFT anchored at the TRACE arrival), pump
    ``server.step()`` continuously. Returns (metrics summary, server)."""
    server = server_factory()
    pending = sorted(requests, key=lambda r: r["arrival_s"])
    t0 = time.perf_counter()
    idx = 0
    while idx < len(pending) or server.has_work:
        now = time.perf_counter() - t0
        while idx < len(pending) and pending[idx]["arrival_s"] <= now:
            req = pending[idx]
            server.submit(req["prompt"],
                          max_new_tokens=req["max_new_tokens"],
                          arrival_t=t0 + req["arrival_s"])
            idx += 1
        if server.has_work:
            server.step()
        elif idx < len(pending):
            time.sleep(min(0.005, pending[idx]["arrival_s"] - now))
    wall = time.perf_counter() - t0
    snap = server.metrics.snapshot()
    return {
        "wall_seconds": round(wall, 3),
        "goodput_tokens_per_sec": round(snap["completed_tokens"] / wall, 2),
        "completed_requests": snap["completed_requests"],
        "completed_tokens": snap["completed_tokens"],
        "decode_tokens_per_sec": snap["decode_tokens_per_sec"],
        "decode_steps": snap["decode_steps"],
        "ttft_p50_s": snap["ttft"]["p50_s"],
        "ttft_p95_s": snap["ttft"]["p95_s"],
        "tpot_p50_s": snap["tpot"]["p50_s"],
        "tpot_p95_s": snap["tpot"]["p95_s"],
        "mean_slot_occupancy": snap["mean_slot_occupancy"],
        "peak_queue_depth": snap["peak_queue_depth"],
        "preemptions": server.preemptions,
    }, server


def disagg_trace_main(out_path):
    import jax
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.inference.fleet import DisaggServer
    from deepspeed_tpu.utils.monitor import ServingMetrics

    cfg = gpt2.GPT2Config(vocab_size=512, max_seq_len=TRACE_MAX_SEQ,
                          n_layers=2, n_heads=4, d_model=128,
                          use_flash_attention=False, remat=False)
    model = gpt2.make_gpt2_model(config=cfg)
    requests = build_trace(cfg.vocab_size, n_requests=DISAGG_REQUESTS)

    # equal AGGREGATE budget: each fleet host owns a 64-page pool
    # (63 usable + 1 garbage); the monolith owns the fleet's whole
    # page count in one pool (191 usable + 1 garbage = 3 x 64)
    per_host = HBM_BUDGET_TOKENS // TRACE_PAGE - 1
    n_hosts = 1 + DISAGG_DECODE_HOSTS
    base = {"max_seq_len": TRACE_MAX_SEQ, "dtype": "fp32", "greedy": True,
            "prefill_buckets": [32, 64, 128, 256], "kv_layout": "paged",
            "kv_block_size": TRACE_PAGE, "prefill_chunk_tokens": 64}
    mono = deepspeed.init_inference(model=model, config={"inference": dict(
        base, max_batch_size=12 * DISAGG_DECODE_HOSTS,
        num_pages=n_hosts * (per_host + 1) - 1)})
    pre = deepspeed.init_inference(model=model, config={"inference": dict(
        base, max_batch_size=4, num_pages=per_host,
        fleet={"enabled": True, "role": "prefill"})})
    decs = [deepspeed.init_inference(model=model, config={"inference": dict(
        base, max_batch_size=12, num_pages=per_host,
        fleet={"enabled": True, "role": "decode"})})
        for _ in range(DISAGG_DECODE_HOSTS)]
    fleet_nbytes = pre.kv.nbytes + sum(d.kv.nbytes for d in decs)
    assert mono.kv.nbytes == fleet_nbytes, (mono.kv.nbytes, fleet_nbytes)

    def make_server():
        return DisaggServer(
            {"prefill0": pre},
            {"decode{}".format(i): d for i, d in enumerate(decs)},
            metrics=ServingMetrics())

    # warmup: compile every bucket + the decode fns off the clock, on
    # the monolith AND through the fleet wire
    warm = requests[:len(base["prefill_buckets"])]
    mono.generate([r["prompt"] for r in warm], max_new_tokens=8)
    warm_server = make_server()
    for req in warm:
        warm_server.submit(req["prompt"], max_new_tokens=8)
    warm_server.run()

    rounds = 3                  # odd: the middle of the sort IS a median
    singles, disaggs, servers = [], [], []
    for _ in range(rounds):
        # interleaved rounds: machine drift hits every config equally
        singles.append(run_trace(mono, requests))
        result, server = run_disagg_trace(make_server, requests)
        disaggs.append(result)
        servers.append(server)

    def median_i(runs):
        order = sorted(range(len(runs)),
                       key=lambda i: runs[i]["goodput_tokens_per_sec"])
        return order[len(runs) // 2]

    mi = median_i(disaggs)
    configs = {"single": singles[median_i(singles)], "disagg": disaggs[mi]}
    server = servers[mi]
    stats = server.handoff_stats()
    ratio = (configs["disagg"]["goodput_tokens_per_sec"] /
             configs["single"]["goodput_tokens_per_sec"])
    payload = {
        "metric": "gpt2_serving_disagg_goodput_ratio_vs_single",
        "value": round(ratio, 3),
        "unit": "x",
        # acceptance floor: the fleet holds >= 0.8x the monolith's
        # goodput at equal aggregate budget while paying the real
        # serialized-handoff wire cost (its win is TTFT isolation)
        "vs_baseline": round(ratio / 0.8, 4),
        "extra": {
            "serving_trace": {
                "trace": {"requests": len(requests), "seed": TRACE_SEED,
                          "prompt_len_max": max(len(r["prompt"])
                                                for r in requests),
                          "output_len_max": max(r["max_new_tokens"]
                                                for r in requests),
                          "span_s": round(requests[-1]["arrival_s"], 2)},
                "hbm_budget_tokens": n_hosts * HBM_BUDGET_TOKENS,
                "kv_bytes_per_config": mono.kv.nbytes,
                "rounds": rounds,
                "configs": configs,
                "disagg": {
                    "prefill_hosts": 1,
                    "decode_hosts": DISAGG_DECODE_HOSTS,
                    "handoff": {"handoffs": stats["handoffs"],
                                "payload_bytes": stats["payload_bytes"]},
                    "router_decisions": server.router.decision_counts(),
                },
            },
            "ttft_p95_ratio_single_vs_disagg": round(
                configs["single"]["ttft_p95_s"] /
                max(configs["disagg"]["ttft_p95_s"], 1e-9), 3),
            "device": getattr(jax.devices()[0], "device_kind", "cpu"),
            "backend": jax.default_backend(),
        },
    }
    line = json.dumps(payload)
    print(line)
    if out_path:
        with open(out_path, "w") as fh:
            fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    if "--disagg" in sys.argv:
        out = "tests/perf/BENCH_SERVING_r17.json"
        if "--out" in sys.argv:
            idx = sys.argv.index("--out") + 1
            if idx >= len(sys.argv):
                emit_error_json(
                    "gpt2_serving_disagg_goodput_ratio_vs_single",
                    ValueError("--out needs a path argument"))
                sys.exit(1)
            out = sys.argv[idx]
        try:
            sys.exit(disagg_trace_main(out))
        except Exception as err:  # noqa: BLE001 - parseable JSON always
            emit_error_json("gpt2_serving_disagg_goodput_ratio_vs_single",
                            err)
            sys.exit(1)
    if "--serving-trace" in sys.argv:
        out = "tests/perf/BENCH_SERVING.json"
        if "--out" in sys.argv:
            idx = sys.argv.index("--out") + 1
            if idx >= len(sys.argv):
                emit_error_json(
                    "gpt2_serving_goodput_ratio_paged_spec_vs_slot",
                    ValueError("--out needs a path argument"))
                sys.exit(1)
            out = sys.argv[idx]
        try:
            sys.exit(serving_trace_main(out))
        except Exception as err:  # noqa: BLE001 - parseable JSON always
            emit_error_json("gpt2_serving_goodput_ratio_paged_spec_vs_slot",
                            err)
            sys.exit(1)
    try:
        sys.exit(main())
    except Exception as err:  # noqa: BLE001 - emit parseable JSON, not a trace
        emit_error_json("gpt2_inference_decode_tokens_per_sec_per_chip", err)
        sys.exit(1)
