#!/usr/bin/env python3
"""Validate telemetry/diagnostics artifact shapes.

Three artifact families, dispatched by shape:

* **BENCH_*.json** — ONE parseable JSON object with metric (str), value
  (number|null), unit (str), vs_baseline (number|null); "error" (str)
  required whenever value is null; optional extra (dict). When
  ``extra.telemetry`` is present it must be a telemetry snapshot:
  ``steps``/``serving_steps`` ints, and — when steps > 0 —
  ``step_time_s``/``mfu``/``tokens_per_sec_per_chip`` dists with
  last/mean/p50/p95 numbers (docs/telemetry.md).
* **crash bundles** (``kind: "crash_bundle"``, flight recorder —
  docs/diagnostics.md): reason/wall, record+span+log rings, env report,
  program registry.
* **analysis reports** (``kind: "analysis_report"``, the shard-lint
  auditor / ``bin/ds_lint.py --json`` — docs/analysis.md): programs
  map, findings/suppressed lists with rule/check/key/severity, summary
  counters.
* **bench scoreboards** (``kind: "bench_scoreboard"``,
  ``bin/ds_scoreboard.py --json`` — docs/fleet.md): non-empty
  trajectory rows with rung/mfu/regression fields.
* **fleet reports** (``kind: "fleet_report"``, ``bin/ds_fleet.py
  --json`` — docs/fleet.md): hosts/offsets/records/straggler plus the
  ISSUE 15 ``divergence`` section (published/digests/mismatch/
  divergent_hosts — docs/concurrency.md).
* **host manifests** (``kind: "host_manifest"``, the collector's
  discovery seam): required keys plus the optional
  ``program_fingerprint`` extension (version/digest/families).
* **Chrome trace-event files** (a JSON array, telemetry.spans'
  trace_events.json and ``bin/ds_fleet.py --trace``'s merged form):
  parsed leniently (a crashed run may leave the Perfetto-tolerated
  trailing-comma/unclosed-array form) and each event checked for
  name/ph/ts/pid/tid.

BENCH ``extra.metrics`` (the embedded final /metrics scrape of the
fleet export plane) is validated for series count + exposition text.
``extra.longctx`` (tests/perf/bench_longctx.py, the long-context
sparse-attention rung) is validated for its rows and for the INTERNAL
CONSISTENCY of its analytic dense-OOM accounting — the published
fits booleans must match their own published operands.

Usage: check_bench_schema.py [FILE...]; with no args, validates every
BENCH_*.json in the repo root and tests/perf/. Exit 1 on any failure.
"""
import glob
import json
import os
import sys

_NUM = (int, float)

# Local copy of telemetry/record.py SERVING_SUBDICT_KEYS: this checker
# must stay runnable as a bare stdlib script (no deepspeed_tpu/jax
# import from bin/). tests/unit/test_serving.py pins the two tables
# equal so they cannot drift.
SERVING_SUBDICT_KEYS = {
    "ttft": ("count", "mean_s", "p50_s", "p95_s"),
    "tpot": ("count", "mean_s", "p50_s", "p95_s"),
    "page_pool": ("num_pages", "pages_in_use", "occupancy"),
    "prefix": ("lookups", "hits", "hit_rate"),
    "speculative": ("proposed", "accepted", "acceptance_rate"),
}

# Local copy of telemetry/record.py SERVING_ROLES (ISSUE 17): the
# closed role vocabulary a serving_step record / fleet host summary may
# carry. Pinned equal by tests/unit/test_serving_fleet.py.
SERVING_ROLES = ("monolith", "prefill", "decode", "router")

# Local copies of inference/fleet/events.py ROUTER_EVENT_KEYS /
# ROUTER_DECISIONS (same stdlib-only constraint; pinned equal by
# tests/unit/test_serving_fleet.py).
ROUTER_EVENT_KEYS = (
    "kind", "wall", "decision", "request_uid", "host", "reason",
    "predicted_cost_s", "detail",
)
ROUTER_DECISIONS = ("admit", "deny", "route_away", "preempt_migrate",
                    "enroll", "enroll_refusal")

# Local copy of telemetry/record.py SEGMENT_KEYS /
# SEGMENT_KIND_KEYS / SEGMENT_OPTIONAL_KEYS (same stdlib-only
# constraint; pinned equal by tests/unit/test_executor.py): the
# unified per-segment stats schema of the executor-lowered offload
# paths' ``offload`` record sub-dict and the benches'
# ``extra.executor`` payload.
SEGMENT_KEYS = (
    "plan_segments", "per_kind", "overlap_efficiency",
    "upload_batches", "upload_elems", "upload_bytes",
    "bucket_elems", "bucket_occupancy",
)
SEGMENT_KIND_KEYS = ("segments", "run_s", "wait_s")
SEGMENT_OPTIONAL_KEYS = (
    "segment_upload_bytes_peak", "groups", "collective_matmul",
    "work_chunks", "mode", "plans_executed", "segments_executed",
    "last_plan_segments", "rewrites",
)

# Local copy of telemetry/record.py REWRITE_KEYS / REWRITE_PASS_KEYS
# (PR 19 plan-rewrite stats; same stdlib-only constraint; pinned equal
# by tests/unit/test_executor.py).
REWRITE_KEYS = ("enabled", "passes", "segments_moved",
                "predicted_exposed_wait_delta_s",
                "measured_exposed_wait_delta_s")
REWRITE_PASS_KEYS = ("name", "segments_moved",
                     "predicted_exposed_wait_delta_s")


def check_rewrite_stats(stats, where):
    """-> list of problems with one REWRITE_KEYS stats dict (a stdlib
    re-statement of telemetry/record.py validate_rewrite_stats)."""
    problems = []
    if not isinstance(stats, dict):
        return ["{} is not a dict".format(where)]
    for key in REWRITE_KEYS:
        if key not in stats:
            problems.append("{} missing key {!r}".format(where, key))
    extra = sorted(set(stats) - set(REWRITE_KEYS))
    if extra:
        problems.append("{} has unexpected key(s) {}".format(
            where, extra))
    if problems:
        return problems
    if not isinstance(stats["enabled"], bool):
        problems.append("{}.enabled is not a bool".format(where))
    if not _is_num(stats["segments_moved"]) or \
            stats["segments_moved"] < 0:
        problems.append("{}.segments_moved is not a nonnegative "
                        "number".format(where))
    for key in ("predicted_exposed_wait_delta_s",
                "measured_exposed_wait_delta_s"):
        val = stats[key]
        if val is not None and not _is_num(val):
            problems.append("{}.{} is neither null nor a number".format(
                where, key))
    passes = stats["passes"]
    if not isinstance(passes, list):
        return problems + ["{}.passes is not a list".format(where)]
    for i, entry in enumerate(passes):
        if not isinstance(entry, dict) or \
                sorted(entry) != sorted(REWRITE_PASS_KEYS):
            problems.append(
                "{}.passes[{}] does not carry exactly {}".format(
                    where, i, sorted(REWRITE_PASS_KEYS)))
            break
    return problems


def check_segment_stats(stats, where):
    """-> list of problems with one SEGMENT_KEYS stats dict (a stdlib
    re-statement of telemetry/record.py validate_segment_stats —
    executor dicts carry the lifetime counter extras; record dicts the
    path extras)."""
    problems = []
    if not isinstance(stats, dict):
        return ["{} is not a dict".format(where)]
    # dispatch marker: dicts without plan_segments are pre-executor
    # artifacts (older BENCH records) — validated only for shape above
    if "plan_segments" not in stats:
        return []
    for key in SEGMENT_KEYS:
        if key not in stats and not (
                where.endswith("executor") and key.startswith(
                    ("upload_", "bucket_"))):
            problems.append("{} missing key {!r}".format(where, key))
    extra = sorted(set(stats) - set(SEGMENT_KEYS)
                   - set(SEGMENT_OPTIONAL_KEYS))
    if extra:
        problems.append("{} has unexpected key(s) {}".format(
            where, extra))
    per_kind = stats.get("per_kind")
    if not isinstance(per_kind, dict):
        problems.append("{}.per_kind is not a dict".format(where))
    else:
        for kind, slot in per_kind.items():
            if not isinstance(slot, dict):
                problems.append(
                    "{}.per_kind.{} is not a dict".format(where, kind))
                continue
            for key in SEGMENT_KIND_KEYS:
                if not _is_num(slot.get(key)):
                    problems.append(
                        "{}.per_kind.{}.{} is not a number".format(
                            where, kind, key))
    if stats.get("rewrites") is not None:
        problems.extend(check_rewrite_stats(
            stats["rewrites"], where + ".rewrites"))
    return problems


# Local copy of telemetry/recorder.py CRASH_BUNDLE_KEYS (same stdlib-
# only constraint; pinned equal by tests/unit/test_diagnostics.py).
CRASH_BUNDLE_KEYS = (
    "kind", "reason", "wall", "job_name", "exception",
    "records", "spans", "open_spans", "log_events",
    "ds_config", "env", "programs", "watchdog", "topology", "state",
)


def _is_num(val):
    return isinstance(val, _NUM) and not isinstance(val, bool)


def _check_dist(d, name, problems):
    if not isinstance(d, dict):
        problems.append("telemetry.{} is not a dict".format(name))
        return
    for key in ("last", "mean", "p50", "p95"):
        if not _is_num(d.get(key)):
            problems.append(
                "telemetry.{}.{} is not a number: {!r}".format(
                    name, key, d.get(key)))


def check_telemetry_snapshot(snap):
    """-> list of problems with one ``extra.telemetry`` payload."""
    problems = []
    if not isinstance(snap, dict):
        return ["extra.telemetry is not a dict"]
    if not snap:
        return ["extra.telemetry is empty (telemetry was disabled — "
                "drop the key instead)"]
    steps = snap.get("steps", 0)
    serving = snap.get("serving_steps", 0)
    for key, val in (("steps", steps), ("serving_steps", serving)):
        if not isinstance(val, int) or isinstance(val, bool) or val < 0:
            problems.append(
                "telemetry.{} is not an int >= 0: {!r}".format(key, val))
            return problems
    if steps == 0 and serving == 0:
        problems.append("telemetry carries neither train nor serving steps")
    if steps > 0:
        for name in ("step_time_s", "mfu", "tokens_per_sec_per_chip"):
            _check_dist(snap.get(name), name, problems)
        if not isinstance(snap.get("phases_mean_s"), dict):
            problems.append("telemetry.phases_mean_s is not a dict")
        if isinstance(snap.get("offload_last"), dict):
            problems.extend(check_segment_stats(
                snap["offload_last"], "telemetry.offload_last"))
    if serving > 0:
        srv = snap.get("serving")
        if not isinstance(srv, dict):
            problems.append("telemetry.serving is not a dict")
        else:
            # serving-memory/latency gauges (ISSUE 7): optional (null
            # until the feature that produces them has fired) — but
            # when present they must carry their numeric fields
            for key, want in SERVING_SUBDICT_KEYS.items():
                sub = srv.get(key)
                if sub is None:
                    continue
                if not isinstance(sub, dict):
                    problems.append(
                        "telemetry.serving.{} is not a dict".format(key))
                    continue
                for sub_key in want:
                    if not _is_num(sub.get(sub_key)):
                        problems.append(
                            "telemetry.serving.{}.{} is not a number: "
                            "{!r}".format(key, sub_key, sub.get(sub_key)))
    return problems


def check_metrics_payload(payload):
    """-> list of problems with one ``extra.metrics`` payload (the
    bench-embedded final /metrics scrape; docs/fleet.md)."""
    problems = []
    if not isinstance(payload, dict):
        return ["extra.metrics is not a dict"]
    series = payload.get("series")
    if not isinstance(series, int) or isinstance(series, bool) or \
            series < 1:
        problems.append("metrics.series is not an int >= 1: "
                        "{!r}".format(series))
    scrape = payload.get("scrape")
    if not isinstance(scrape, str) or "# TYPE " not in scrape:
        problems.append("metrics.scrape is not Prometheus exposition "
                        "text (no '# TYPE ' line)")
    return problems


# Local copy of bin/ds_scoreboard.py SCOREBOARD_ROW_KEYS (same stdlib-
# only constraint; pinned equal by tests/unit/test_fleet.py).
SCOREBOARD_ROW_KEYS = (
    "rung", "file", "rc", "metric", "value", "unit", "mfu",
    "tokens_per_sec_per_chip", "goodput_tokens_per_sec", "reduction_x",
    "overlap_efficiency", "device", "error",
)


def check_scoreboard(payload):
    """-> list of problems with one bench_scoreboard artifact
    (bin/ds_scoreboard.py --json)."""
    problems = []
    rows = payload.get("rows")
    if not isinstance(rows, list) or not rows:
        return ["scoreboard rows is not a non-empty list"]
    for i, row in enumerate(rows):
        if not isinstance(row, dict):
            problems.append("rows[{}] is not an object".format(i))
            break
        for key in SCOREBOARD_ROW_KEYS:
            if key not in row:
                problems.append("rows[{}] missing {!r}".format(i, key))
        if not isinstance(row.get("rung"), int):
            problems.append("rows[{}].rung is not an int".format(i))
        if row.get("mfu") is not None and not _is_num(row["mfu"]):
            problems.append("rows[{}].mfu is neither null nor a "
                            "number".format(i))
        if problems:
            break
    if not isinstance(payload.get("regression"), bool):
        problems.append("regression is not a bool")
    for key in ("latest_mfu", "best_prior_mfu"):
        val = payload.get(key)
        if val is not None and not _is_num(val):
            problems.append("{} is neither null nor a number".format(key))
    serving = payload.get("serving")
    if serving is not None:
        # disaggregated-serving trajectory (ISSUE 17): goodput/p95-TTFT
        # rungs over BENCH_SERVING*.json with the same >10% gate
        if not isinstance(serving, dict):
            problems.append("serving is neither null nor a dict")
            return problems
        srows = serving.get("rows")
        if not isinstance(srows, list):
            problems.append("serving.rows is not a list")
        else:
            for i, row in enumerate(srows):
                if not isinstance(row, dict):
                    problems.append(
                        "serving.rows[{}] is not an object".format(i))
                    break
                for key in ("rung", "file", "config", "device",
                            "goodput_tokens_per_sec", "ttft_p95_s"):
                    if key not in row:
                        problems.append(
                            "serving.rows[{}] missing {!r}".format(
                                i, key))
                if problems:
                    break
        if not isinstance(serving.get("regression"), bool):
            problems.append("serving.regression is not a bool")
    longctx = payload.get("longctx")
    if longctx is not None:
        # long-context trajectory (ISSUE 18): tokens/s rungs over
        # BENCH_LONGCTX*.json with the same >10% gate
        if not isinstance(longctx, dict):
            problems.append("longctx is neither null nor a dict")
            return problems
        lrows = longctx.get("rows")
        if not isinstance(lrows, list):
            problems.append("longctx.rows is not a list")
        else:
            for i, row in enumerate(lrows):
                if not isinstance(row, dict):
                    problems.append(
                        "longctx.rows[{}] is not an object".format(i))
                    break
                for key in ("rung", "file", "seq", "mode", "device",
                            "tokens_per_sec"):
                    if key not in row:
                        problems.append(
                            "longctx.rows[{}] missing {!r}".format(
                                i, key))
                if problems:
                    break
        if not isinstance(longctx.get("regression"), bool):
            problems.append("longctx.regression is not a bool")
    return problems


def check_longctx(payload):
    """-> list of problems with one ``extra.longctx`` payload
    (tests/perf/bench_longctx.py — the ISSUE 18 long-context rung).
    The dense-OOM claim is ANALYTIC (live-bytes arithmetic at the
    declared shape), so the checker re-derives the fits booleans from
    the published operands — a row that says "dense doesn't fit" with
    numbers that say otherwise is a schema failure, not an opinion."""
    problems = []
    if not isinstance(payload, dict):
        return ["extra.longctx is not a dict"]
    rows = payload.get("rows")
    if not isinstance(rows, list) or not rows:
        return ["longctx.rows is not a non-empty list"]
    timed = 0
    for i, row in enumerate(rows):
        if not isinstance(row, dict):
            problems.append("longctx.rows[{}] is not an object".format(i))
            break
        for key in ("seq", "mode", "fits", "timed"):
            if key not in row:
                problems.append(
                    "longctx.rows[{}] missing {!r}".format(i, key))
        if row.get("mode") not in ("dense", "sparse"):
            problems.append("longctx.rows[{}] has unknown mode "
                            "{!r}".format(i, row.get("mode")))
        if row.get("timed"):
            timed += 1
            if row.get("fits") and \
                    not _is_num(row.get("tokens_per_sec")):
                problems.append(
                    "longctx.rows[{}] is timed but tokens_per_sec is "
                    "not a number".format(i))
        if problems:
            break
    if not timed:
        problems.append("longctx has no timed row (accounting alone is "
                        "not a rung)")
    oom = payload.get("dense_oom")
    if not isinstance(oom, dict):
        problems.append("longctx.dense_oom is not a dict")
        return problems
    for key in ("hbm_budget_bytes", "dense_bwd_live_bytes",
                "sparse_bwd_live_bytes"):
        if not _is_num(oom.get(key)):
            problems.append(
                "longctx.dense_oom.{} is not a number".format(key))
    if problems:
        return problems
    budget = oom["hbm_budget_bytes"]
    for mode in ("dense", "sparse"):
        fits = oom.get("{}_fits".format(mode))
        derived = oom["{}_bwd_live_bytes".format(mode)] <= budget
        if not isinstance(fits, bool):
            problems.append(
                "longctx.dense_oom.{}_fits is not a bool".format(mode))
        elif fits != derived:
            problems.append(
                "longctx.dense_oom.{}_fits={} contradicts its own "
                "operands ({} bytes vs budget {})".format(
                    mode, fits,
                    oom["{}_bwd_live_bytes".format(mode)], budget))
    if oom.get("dense_fits") is True:
        problems.append("longctx.dense_oom claims dense FITS — the "
                        "rung's shape no longer demonstrates the "
                        "long-context memory wall")
    return problems


# per-config metrics every serving-trace artifact row must report
SERVING_TRACE_CONFIG_KEYS = (
    "goodput_tokens_per_sec", "completed_requests", "completed_tokens",
    "wall_seconds", "ttft_p50_s", "ttft_p95_s", "tpot_p50_s", "tpot_p95_s",
)


def check_serving_trace(trace):
    """-> list of problems with one ``extra.serving_trace`` payload
    (bench_inference.py --serving-trace / tests/perf/BENCH_SERVING.json)."""
    problems = []
    if not isinstance(trace, dict):
        return ["extra.serving_trace is not a dict"]
    configs = trace.get("configs")
    if not isinstance(configs, dict) or not configs:
        return ["serving_trace.configs is not a non-empty dict"]
    # 'slot' is the single-engine trace's baseline; the disaggregated
    # trace (ISSUE 17) compares against the 'single' paged monolith
    if "slot" not in configs and "single" not in configs:
        problems.append("serving_trace.configs lacks a baseline "
                        "('slot' or 'single')")
    for name, cfg in configs.items():
        if not isinstance(cfg, dict):
            problems.append(
                "serving_trace.configs.{} is not a dict".format(name))
            continue
        for key in SERVING_TRACE_CONFIG_KEYS:
            if not _is_num(cfg.get(key)):
                problems.append(
                    "serving_trace.configs.{}.{} is not a number: "
                    "{!r}".format(name, key, cfg.get(key)))
    if not _is_num(trace.get("hbm_budget_tokens")):
        problems.append("serving_trace.hbm_budget_tokens is not a number")
    disagg = trace.get("disagg")
    if disagg is not None:
        # the disaggregated rung's router/handoff evidence (ISSUE 17)
        if not isinstance(disagg, dict):
            problems.append("serving_trace.disagg is not a dict")
            return problems
        handoff = disagg.get("handoff")
        if not isinstance(handoff, dict):
            problems.append("serving_trace.disagg.handoff is not a dict")
        else:
            for key in ("handoffs", "payload_bytes"):
                if not _is_num(handoff.get(key)):
                    problems.append(
                        "serving_trace.disagg.handoff.{} is not a "
                        "number".format(key))
        decisions = disagg.get("router_decisions")
        if not isinstance(decisions, dict):
            problems.append(
                "serving_trace.disagg.router_decisions is not a dict")
        else:
            unknown = sorted(set(decisions) - set(ROUTER_DECISIONS))
            if unknown:
                problems.append(
                    "serving_trace.disagg.router_decisions has unknown "
                    "decision(s) {}".format(unknown))
    return problems


def _unwrap_driver_record(payload):
    """Repo-root BENCH_r*.json are DRIVER run records ({"cmd", "rc",
    "tail"}): the bench's own JSON line is the last {"metric": ...} line
    of the captured tail. Returns (inner_payload, problems)."""
    tail = payload.get("tail", "")
    inner = None
    for line in tail.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                cand = json.loads(line)
            except ValueError:
                continue
            if isinstance(cand, dict) and "metric" in cand:
                inner = cand
    if inner is None:
        if payload.get("rc") != 0:
            # historical failed run: the record honestly carries rc + the
            # traceback tail; nothing further to validate
            return None, []
        return None, ["driver record has rc=0 but no bench JSON line "
                      "in its tail"]
    return inner, []


def check_bench_payload(payload):
    """-> list of problems with one parsed BENCH_*.json object. Accepts
    the three artifact shapes in the repo: bench.py's single JSON line,
    perf-table artifacts (metric + rows), and driver run records
    (cmd/rc/tail with the bench line embedded)."""
    problems = []
    if not isinstance(payload, dict):
        return ["top level is not a JSON object"]
    if "rc" in payload and "cmd" in payload:
        payload, problems = _unwrap_driver_record(payload)
        if payload is None:
            return problems
    if "rows" in payload:
        # perf-table shape (e.g. BENCH_BERT_*): non-empty rows; metric
        # is a string when present (earliest artifacts predate it)
        if "metric" in payload and not isinstance(payload["metric"], str):
            problems.append("metric is not a string")
        rows = payload.get("rows")
        if not isinstance(rows, list) or not rows:
            problems.append("rows is not a non-empty list")
        return problems
    if not isinstance(payload.get("metric"), str):
        problems.append("metric is not a string")
    if not isinstance(payload.get("unit"), str):
        problems.append("unit is not a string")
    value = payload.get("value")
    if value is not None and not _is_num(value):
        problems.append("value is neither a number nor null")
    vs = payload.get("vs_baseline")
    if vs is not None and not _is_num(vs):
        problems.append("vs_baseline is neither a number nor null")
    if value is None and not isinstance(payload.get("error"), str):
        problems.append("value is null but no 'error' string names why")
    extra = payload.get("extra")
    if extra is not None:
        if not isinstance(extra, dict):
            problems.append("extra is not a dict")
        else:
            if "telemetry" in extra:
                problems.extend(
                    check_telemetry_snapshot(extra["telemetry"]))
            if "serving_trace" in extra:
                problems.extend(check_serving_trace(extra["serving_trace"]))
            if "longctx" in extra:
                problems.extend(check_longctx(extra["longctx"]))
            if "executor" in extra:
                problems.extend(check_segment_stats(
                    extra["executor"], "extra.executor"))
            if "metrics" in extra:
                problems.extend(check_metrics_payload(extra["metrics"]))
    return problems


def check_crash_bundle(bundle):
    """-> list of problems with one flight-recorder crash bundle. A
    stdlib re-statement of telemetry/recorder.py's
    ``validate_crash_bundle`` (the bundle writer's own checker is the
    source of truth; test_diagnostics.py pins the key table equal)."""
    problems = []
    if not isinstance(bundle, dict):
        return ["bundle is not a dict"]
    for key in CRASH_BUNDLE_KEYS:
        if key not in bundle:
            problems.append("missing key {!r}".format(key))
    if problems:
        return problems
    if not isinstance(bundle.get("reason"), str) or not bundle["reason"]:
        problems.append("reason is not a non-empty string")
    if not _is_num(bundle.get("wall")):
        problems.append("wall is not a number")
    for key in ("records", "spans", "open_spans", "log_events"):
        val = bundle[key]
        if not isinstance(val, list) or \
                not all(isinstance(item, dict) for item in val):
            problems.append("{} is not a list of objects".format(key))
    for rec in bundle.get("records") or []:
        if rec.get("kind") not in ("train_step", "serving_step"):
            problems.append(
                "records entry of kind {!r}".format(rec.get("kind")))
            break
    for key in ("env", "programs", "state"):
        if not isinstance(bundle[key], dict):
            problems.append("{} is not a dict".format(key))
    for key in ("exception", "ds_config", "watchdog", "topology"):
        if bundle[key] is not None and not isinstance(bundle[key], dict):
            problems.append("{} is neither null nor a dict".format(key))
    if isinstance(bundle.get("programs"), dict) and \
            "programs" not in bundle["programs"]:
        problems.append("programs is not a registry snapshot "
                        "(no 'programs' table)")
    return problems


# Local copy of analysis/findings.py ANALYSIS_REPORT_KEYS /
# FINDING_KEYS / SEVERITIES (same stdlib-only constraint; pinned equal
# by tests/unit/test_analysis.py).
ANALYSIS_REPORT_KEYS = (
    "kind", "version", "job", "programs", "findings", "suppressed",
    "summary",
)
ANALYSIS_FINDING_KEYS = ("rule", "check", "program", "severity",
                         "message", "key")
ANALYSIS_SEVERITIES = ("error", "warn", "info")


def check_analysis_report(payload):
    """-> list of problems with one shard-lint analysis report. A
    stdlib re-statement of analysis/findings.py's
    ``validate_analysis_report`` (the writer-side checker is the source
    of truth; test_analysis.py pins the key tables equal)."""
    problems = []
    if not isinstance(payload, dict):
        return ["report is not a dict"]
    for key in ANALYSIS_REPORT_KEYS:
        if key not in payload:
            problems.append("missing key {!r}".format(key))
    if problems:
        return problems
    if not isinstance(payload.get("programs"), dict):
        problems.append("programs is not a dict")
    for section in ("findings", "suppressed"):
        entries = payload.get(section)
        if not isinstance(entries, list):
            problems.append("{} is not a list".format(section))
            continue
        for i, ent in enumerate(entries):
            if not isinstance(ent, dict):
                problems.append(
                    "{}[{}] is not an object".format(section, i))
                break
            for key in ANALYSIS_FINDING_KEYS:
                if not isinstance(ent.get(key), str):
                    problems.append("{}[{}].{} is not a string".format(
                        section, i, key))
            if ent.get("severity") not in ANALYSIS_SEVERITIES:
                problems.append("{}[{}] has unknown severity "
                                "{!r}".format(section, i,
                                              ent.get("severity")))
            if section == "suppressed" and \
                    not ent.get("suppressed_reason"):
                problems.append(
                    "suppressed[{}] lacks a suppressed_reason".format(i))
            if problems:
                break
    summary = payload.get("summary")
    if not isinstance(summary, dict):
        problems.append("summary is not a dict")
    else:
        for key in ("programs_audited", "findings", "suppressed"):
            val = summary.get(key)
            if not isinstance(val, int) or isinstance(val, bool) or \
                    val < 0:
                problems.append(
                    "summary.{} is not an int >= 0".format(key))
    return problems


# Local copies of telemetry/fleet/aggregate.py FLEET_REPORT_KEYS /
# HOST_MANIFEST_KEYS / FINGERPRINT_KEYS (same stdlib-only constraint;
# pinned equal by tests/unit/test_concurrency.py).
FLEET_REPORT_KEYS = (
    "kind", "run_dir", "n_hosts", "hosts", "offsets", "records", "gaps",
    "straggler", "ici_health", "trace", "divergence", "rescale",
    "router",
)
# Local copy of runtime/elastic/events.py RESCALE_EVENT_KEYS (same
# stdlib-only constraint; pinned equal by
# tests/unit/test_elastic_rescale.py).
RESCALE_EVENT_KEYS = (
    "kind", "event", "wall", "reason", "attempt",
    "old_world", "new_world", "old_mesh", "new_mesh",
    "outcome", "detail",
)
HOST_MANIFEST_KEYS = (
    "kind", "job_name", "host", "pid", "process_index", "wall_start",
    "files", "metrics_port",
)
FINGERPRINT_KEYS = ("version", "digest", "families")


def _check_fingerprint(fp, where, problems):
    if not isinstance(fp, dict):
        problems.append("{} is not a dict".format(where))
        return
    for key in FINGERPRINT_KEYS:
        if key not in fp:
            problems.append("{} missing {!r}".format(where, key))
    if not isinstance(fp.get("digest", ""), str):
        problems.append("{}.digest is not a string".format(where))
    fams = fp.get("families")
    if fams is not None and not isinstance(fams, dict):
        problems.append("{}.families is not a dict".format(where))


def check_host_manifest(payload):
    """-> list of problems with one host_manifest.json (the fleet
    merger's discovery seam; the optional ``program_fingerprint``
    extension is ISSUE 15's divergence-auditor seam)."""
    problems = []
    for key in HOST_MANIFEST_KEYS:
        if key not in payload:
            problems.append("missing key {!r}".format(key))
    if not problems and not isinstance(payload.get("files"), dict):
        problems.append("files is not a dict")
    fp = payload.get("program_fingerprint")
    if fp is not None:
        _check_fingerprint(fp, "program_fingerprint", problems)
    return problems


def check_fleet_report(payload):
    """-> list of problems with one fleet_report artifact
    (``bin/ds_fleet.py --json``), including the ISSUE 15 ``divergence``
    section."""
    problems = []
    for key in FLEET_REPORT_KEYS:
        if key not in payload:
            problems.append("missing key {!r}".format(key))
    if problems:
        return problems
    if not isinstance(payload.get("n_hosts"), int) or \
            isinstance(payload.get("n_hosts"), bool):
        problems.append("n_hosts is not an int")
    for key in ("hosts", "records", "gaps"):
        if not isinstance(payload.get(key), list):
            problems.append("{} is not a list".format(key))
    for key in ("offsets", "straggler", "ici_health"):
        if not isinstance(payload.get(key), dict):
            problems.append("{} is not a dict".format(key))
    for i, rec in enumerate(payload.get("records") or []):
        if not isinstance(rec, dict) or rec.get("kind") != "fleet_step":
            problems.append(
                "records[{}] is not a fleet_step record".format(i))
            break
    straggler = payload.get("straggler")
    if isinstance(straggler, dict) and \
            not isinstance(straggler.get("flags"), list):
        problems.append("straggler.flags is not a list")
    div = payload.get("divergence")
    if not isinstance(div, dict):
        problems.append("divergence is not a dict")
    else:
        if not isinstance(div.get("mismatch"), bool):
            problems.append("divergence.mismatch is not a bool")
        if not isinstance(div.get("published"), int) or \
                isinstance(div.get("published"), bool):
            problems.append("divergence.published is not an int")
        for key in ("digests", "families"):
            if not isinstance(div.get(key), dict):
                problems.append(
                    "divergence.{} is not a dict".format(key))
        if not isinstance(div.get("divergent_hosts"), list):
            problems.append("divergence.divergent_hosts is not a list")
        if div.get("mismatch") and not div.get("divergent_hosts"):
            problems.append(
                "divergence.mismatch set with no divergent_hosts")
    rescale = payload.get("rescale")
    if not isinstance(rescale, dict):
        problems.append("rescale is not a dict")
    else:
        for key in ("count", "completed"):
            if not isinstance(rescale.get(key), int) or \
                    isinstance(rescale.get(key), bool):
                problems.append(
                    "rescale.{} is not an int".format(key))
        events = rescale.get("events")
        if not isinstance(events, list):
            problems.append("rescale.events is not a list")
        else:
            for i, ev in enumerate(events):
                if not isinstance(ev, dict) or \
                        ev.get("kind") != "rescale_event":
                    problems.append(
                        "rescale.events[{}] is not a rescale_event"
                        .format(i))
                    break
                missing = [k for k in RESCALE_EVENT_KEYS if k not in ev]
                if missing:
                    problems.append(
                        "rescale.events[{}] missing {}".format(
                            i, missing))
                    break
    router = payload.get("router")
    if not isinstance(router, dict):
        problems.append("router is not a dict")
    else:
        if not isinstance(router.get("count"), int) or \
                isinstance(router.get("count"), bool):
            problems.append("router.count is not an int")
        decisions = router.get("decisions")
        if not isinstance(decisions, dict):
            problems.append("router.decisions is not a dict")
        else:
            unknown = sorted(set(decisions) - set(ROUTER_DECISIONS))
            if unknown:
                problems.append(
                    "router.decisions has unknown decision(s) "
                    "{}".format(unknown))
        events = router.get("events")
        if not isinstance(events, list):
            problems.append("router.events is not a list")
        else:
            for i, ev in enumerate(events):
                if not isinstance(ev, dict) or \
                        ev.get("kind") != "router_event":
                    problems.append(
                        "router.events[{}] is not a router_event"
                        .format(i))
                    break
                missing = [k for k in ROUTER_EVENT_KEYS if k not in ev]
                if missing:
                    problems.append(
                        "router.events[{}] missing {}".format(
                            i, missing))
                    break
                if ev.get("decision") not in ROUTER_DECISIONS:
                    problems.append(
                        "router.events[{}] has unknown decision "
                        "{!r}".format(i, ev.get("decision")))
                    break
    return problems


# every Chrome trace event must carry these fields
TRACE_EVENT_KEYS = ("name", "ph", "ts", "pid", "tid")


def parse_trace_events(text):
    """Parse a trace-event file LENIENTLY: a live/crashed run's file is
    the Perfetto-tolerated array form with a trailing comma and no
    closing bracket. Returns (events, problems)."""
    text = text.strip()
    try:
        payload = json.loads(text)
    except ValueError:
        try:
            payload = json.loads(text.rstrip(",\n\t ") + "]")
        except ValueError as err:
            return None, ["unparseable trace-event file: {}".format(err)]
    if isinstance(payload, dict):
        payload = payload.get("traceEvents")
    if not isinstance(payload, list):
        return None, ["trace-event payload is not an array"]
    return payload, []


def check_trace_events(text):
    """-> list of problems with one Chrome trace-event file's text."""
    events, problems = parse_trace_events(text)
    if problems:
        return problems
    if not events:
        return ["trace-event file holds no events"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append("event {} is not an object".format(i))
            continue
        for key in TRACE_EVENT_KEYS:
            if key not in ev:
                problems.append(
                    "event {} is missing {!r}".format(i, key))
        if not isinstance(ev.get("name"), str):
            problems.append("event {} name is not a string".format(i))
        if ev.get("ph") not in ("X", "i", "B", "E", "M"):
            problems.append(
                "event {} has unknown phase {!r}".format(i, ev.get("ph")))
        if not _is_num(ev.get("ts")):
            problems.append("event {} ts is not a number".format(i))
        if ev.get("ph") == "X" and not _is_num(ev.get("dur")):
            problems.append(
                "event {} is complete ('X') without a dur".format(i))
        if problems:
            break                       # first bad event names the file
    return problems


def check_file(path):
    try:
        with open(path) as fh:
            text = fh.read()
    except OSError as err:
        return ["unreadable: {}".format(err)]
    if text.lstrip().startswith("["):
        # only the span tracer's Chrome trace files are arrays
        return check_trace_events(text)
    try:
        payload = json.loads(text)
    except ValueError as err:
        return ["unparseable: {}".format(err)]
    if isinstance(payload, dict) and payload.get("kind") == "crash_bundle":
        return check_crash_bundle(payload)
    if isinstance(payload, dict) and \
            payload.get("kind") == "analysis_report":
        return check_analysis_report(payload)
    if isinstance(payload, dict) and \
            payload.get("kind") == "bench_scoreboard":
        return check_scoreboard(payload)
    if isinstance(payload, dict) and \
            payload.get("kind") == "fleet_report":
        return check_fleet_report(payload)
    if isinstance(payload, dict) and \
            payload.get("kind") == "host_manifest":
        return check_host_manifest(payload)
    if isinstance(payload, dict) and "traceEvents" in payload:
        return check_trace_events(text)
    return check_bench_payload(payload)


def main(argv):
    paths = argv[1:]
    if not paths:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(root, "BENCH_*.json")) +
                       glob.glob(os.path.join(root, "tests", "perf",
                                              "BENCH_*.json")))
    if not paths:
        print("check_bench_schema: no BENCH_*.json files found")
        return 1
    failed = 0
    for path in paths:
        problems = check_file(path)
        if problems:
            failed += 1
            print("FAIL {}".format(path))
            for problem in problems:
                print("  - {}".format(problem))
        else:
            print("OK   {}".format(path))
    print("check_bench_schema: {}/{} files valid".format(
        len(paths) - failed, len(paths)))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
