"""Fleet observatory tests (ISSUE 14; docs/fleet.md): the metrics /
export plane, the multi-host merger under TORN inputs (mid-line crash,
missing manifest, skewed clock), straggler/ICI attribution, the
scoreboard, and the schema/constant pins that keep the stdlib-only
fleet package honest against the jax-side modules it mirrors.

Marker: ``fleet`` (tier-1 — fast, CPU-only, no engine builds except
the two collector-integration tests which build bare collectors)."""
import importlib.util
import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from deepspeed_tpu.telemetry import collector as collector_mod
from deepspeed_tpu.telemetry import record as record_mod
from deepspeed_tpu.telemetry.collector import TelemetryCollector
from deepspeed_tpu.telemetry.config import DeepSpeedTelemetryConfig
from deepspeed_tpu.telemetry.fleet import aggregate, export, metrics, \
    straggler
from deepspeed_tpu.telemetry.fleet.aggregate import (
    estimate_offsets, load_host, merge_chrome_traces, merge_records,
    merge_run, read_jsonl_tolerant, validate_fleet_record,
    validate_host_manifest, write_host_manifest)
from deepspeed_tpu.telemetry.fleet.metrics import (
    Metric, MetricsRegistry, MetricsSink, parse_prometheus_text)
from deepspeed_tpu.telemetry.fleet.straggler import (
    StragglerDetector, detect_stragglers, ici_health_from_record)
from deepspeed_tpu.telemetry.watchdog import Watchdog

pytestmark = pytest.mark.fleet

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load_bin(name):
    path = os.path.join(_REPO, "bin", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- helpers
def _train_rec(step=0, wall=None, step_time_s=0.01, loss=2.0,
               per_kind=None, comm_overlap=None, overflow=False,
               hbm=None):
    """A schema-valid train StepRecord (validate_step_record == [])."""
    rec = {
        "kind": "train_step", "step": step,
        "wall": time.time() if wall is None else wall,
        "step_time_s": step_time_s, "loss": loss, "grad_norm": 1.0,
        "loss_scale": 1.0, "overflow": overflow, "skipped_steps": 0,
        "micro_steps": 1, "tokens_per_step": 256,
        "tokens_per_sec_per_chip": 256.0 / max(step_time_s, 1e-9),
        "model_flops_per_step": 1e9, "mfu": 0.4,
        "peak_flops_per_chip": 1e12, "device": "cpu", "n_devices": 1,
        "phases": {"fwd": step_time_s / 2, "bwd": step_time_s / 2},
        "phase_total_s": step_time_s,
        "hbm": hbm or {"available": False, "bytes_in_use": None,
                       "peak_bytes_in_use": None},
        "wire": None, "comm_overlap": comm_overlap, "offload": None,
        "pipe": None,
    }
    if per_kind is not None:
        rec["offload"] = {"plan_segments": sum(1 for _ in per_kind),
                          "per_kind": per_kind,
                          "overlap_efficiency": 0.5}
    return rec


def _serving_rec(step=0):
    return {
        "kind": "serving_step", "step": step, "wall": time.time(),
        "slot_occupancy": 0.5, "queue_depth": 2, "active_slots": 2,
        "prefill_tokens": 100 + step, "prefill_tokens_per_sec": 50.0,
        "decode_tokens": 10 + step, "decode_steps": step + 1,
        "decode_tokens_per_sec": 20.0,
        "ttft": {"count": 1, "mean_s": 0.1, "p50_s": 0.1, "p95_s": 0.2},
        "tpot": {"count": 1, "mean_s": 0.01, "p50_s": 0.01,
                 "p95_s": 0.02},
        "page_pool": None, "prefix": None, "speculative": None,
    }


def _write_host(root, name, steps, step_time=0.01, skew=0.0,
                manifest=True, torn=False, per_kind=None,
                straggle_from=None, straggle_time=None):
    """Write one synthetic host directory: manifest + telemetry.jsonl
    of schema-valid train records with controlled walls."""
    d = os.path.join(str(root), name)
    os.makedirs(d, exist_ok=True)
    if manifest:
        write_host_manifest(d, job_name=name)
    lines = []
    base = 1000.0 + skew
    wall = base
    for step in range(steps):
        st = step_time
        if straggle_from is not None and step >= straggle_from:
            st = straggle_time
        wall += st
        rec = _train_rec(step=step, wall=wall, step_time_s=st,
                         per_kind=per_kind)
        assert record_mod.validate_step_record(rec) == [], rec
        lines.append(json.dumps(rec))
    body = "\n".join(lines) + "\n"
    if torn:
        body = body[:-len(lines[-1]) // 2 - 1]    # last line cut mid-JSON
    with open(os.path.join(d, aggregate.JSONL_NAME), "w") as fh:
        fh.write(body)
    return d


def _tc(tmp_path, **extra):
    return DeepSpeedTelemetryConfig({"telemetry": dict(
        {"enabled": True, "output_path": str(tmp_path)}, **extra)})


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.status, resp.read().decode()


# ------------------------------------------------------------------- pins
def test_fleet_constants_pinned_to_jax_side_modules():
    """The stdlib-only fleet package duplicates a handful of constants
    from the jax-importing telemetry modules; they must stay equal."""
    assert metrics.KIND_TRAIN == record_mod.KIND_TRAIN
    assert metrics.KIND_SERVING == record_mod.KIND_SERVING
    assert aggregate.JSONL_NAME == collector_mod.JSONL_NAME
    assert aggregate.SPANS_JSONL_NAME == collector_mod.SPANS_JSONL_NAME
    assert aggregate.CHROME_TRACE_NAME == collector_mod.CHROME_TRACE_NAME
    assert straggler.STRAGGLER_DEFAULTS == \
        __import__("deepspeed_tpu.telemetry.watchdog",
                   fromlist=["STRAGGLER_DEFAULTS"]).STRAGGLER_DEFAULTS


def test_scoreboard_row_keys_pinned_to_checker():
    scoreboard = _load_bin("ds_scoreboard")
    checker = _load_bin("check_bench_schema")
    assert tuple(scoreboard.SCOREBOARD_ROW_KEYS) == \
        tuple(checker.SCOREBOARD_ROW_KEYS)


def test_fleet_clis_run_without_jax(tmp_path):
    """bin/ds_fleet.py must doctor a run directory on a box without
    jax: run it in a subprocess where importing jax raises."""
    import subprocess
    import sys
    _write_host(tmp_path, "host0", steps=3)
    _write_host(tmp_path, "host1", steps=3)
    poison = tmp_path / "poison"
    poison.mkdir()
    (poison / "jax.py").write_text(
        "raise ImportError('no jax on this box (test_fleet)')\n")
    env = dict(os.environ, PYTHONPATH=str(poison))
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bin", "ds_fleet.py"),
         str(tmp_path)], capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr
    assert "fleet report: 2 host(s), 3 merged step(s)" in out.stdout


# ------------------------------------------------------ metric primitives
def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry(namespace="t")
    c = reg.counter("reqs_total")
    c.inc()
    c.inc(2.5, route="a")
    assert c.value() == 1.0 and c.value(route="a") == 2.5
    g = reg.gauge("depth")
    g.set(3)
    g.set(7)
    assert g.value() == 7.0
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    state = h.value()
    assert state["count"] == 3 and state["sum"] == pytest.approx(5.55)
    assert state["buckets"] == [1, 2]        # le=0.1 -> 1, le=1.0 -> 2


def test_counter_set_to_is_monotone():
    reg = MetricsRegistry()
    c = reg.counter("tokens_total")
    c.set_to(100)
    c.set_to(40)             # a lower cumulative source value is kept
    assert c.value() == 100.0
    c.set_to(150)
    assert c.value() == 150.0


def test_metric_validation():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="invalid metric name"):
        Metric("bad-name", "gauge")
    with pytest.raises(ValueError, match="kind"):
        Metric("ok_name", "summary")
    reg.counter("dual")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("dual")
    with pytest.raises(ValueError, match="namespace"):
        MetricsRegistry(namespace="bad ns")


def test_render_parse_roundtrip():
    reg = MetricsRegistry(namespace="ds",
                          const_labels={"job": "t", "host": "h1"})
    reg.counter("steps_total", "steps").inc(3)
    reg.gauge("mfu").set(0.42)
    g = reg.gauge("wire_bytes")
    g.set(10, **{"class": "allgather"})
    g.set(20, **{"class": 'wei"rd\\cls'})     # label escaping
    h = reg.histogram("step_seconds", buckets=(0.5, 2.0))
    h.observe(0.1)
    h.observe(1.0)
    text = reg.render_text()
    families, problems = parse_prometheus_text(text)
    assert problems == []
    assert set(families) == {"ds_steps_total", "ds_mfu",
                             "ds_wire_bytes", "ds_step_seconds"}
    flat = {(name, labels.get("class"), labels.get("le")): val
            for name, labels, val
            in families["ds_wire_bytes"]["samples"]}
    assert flat[("ds_wire_bytes", "allgather", None)] == 10.0
    assert flat[("ds_wire_bytes", 'wei"rd\\cls', None)] == 20.0
    hist = families["ds_step_seconds"]["samples"]
    by_le = {labels["le"]: val for name, labels, val in hist
             if name.endswith("_bucket")}
    assert by_le["0.5"] == 1 and by_le["2.0"] == 2
    assert by_le["+Inf"] == 2                 # +Inf bucket == count
    # const labels ride every sample
    for fam in families.values():
        for _, labels, _ in fam["samples"]:
            assert labels["job"] == "t" and labels["host"] == "h1"


def test_parse_prometheus_text_flags_problems():
    families, problems = parse_prometheus_text(
        "# TYPE ds_x gauge\nds_x 1.0\nds_orphan 2\nds_x nan_ish_X\n")
    assert len(problems) == 2
    assert any("no preceding TYPE" in p for p in problems)
    assert any("non-numeric" in p for p in problems)
    assert families["ds_x"]["samples"][0][2] == 1.0


# ------------------------------------------------------------ MetricsSink
def test_sink_folds_train_record_into_families():
    reg = MetricsRegistry()
    sink = MetricsSink(reg, nominal_bytes_per_s=1e9)
    per_kind = {"host": {"run_s": 0.004, "wait_s": 0.0},
                "transfer": {"run_s": 0.001, "wait_s": 0.002}}
    co = {"allgather": {"bytes": 4_000_000, "fused": False,
                        "est_collective_s": 1e-3, "exposed_s": 2e-3,
                        "overlap_efficiency": 0.5}}
    sink.emit(_train_rec(step=0, per_kind=per_kind, comm_overlap=co))
    sink.emit(_train_rec(step=1, per_kind=per_kind, comm_overlap=co,
                         overflow=True))
    assert sink._train_steps.value() == 2.0
    assert sink._overflow.value() == 1.0
    assert sink._mfu.value() == 0.4
    assert sink._phase.value(phase="fwd") == pytest.approx(0.01)
    assert sink._seg_wait.value(kind="transfer") == pytest.approx(0.004)
    assert sink._seg_eff.value() == 0.5
    # ici_health: 4 MB over the 2 ms measured transfer wait = 2e9 B/s
    # against the 1e9 nominal -> 2.0
    assert sink._ici.value(**{"class": "allgather"}) == \
        pytest.approx(2.0, rel=1e-3)
    st = sink._step_time.value()
    assert st["count"] == 2


def test_sink_ici_health_unset_without_measured_waits():
    """micro/fused records (no offload per_kind walls) must leave the
    ici_health gauge honestly unset, never report the analytic 1.0."""
    reg = MetricsRegistry()
    sink = MetricsSink(reg, nominal_bytes_per_s=1e9)
    co = {"allgather": {"bytes": 1000, "fused": False,
                        "est_collective_s": 1e-4, "exposed_s": 1e-4,
                        "overlap_efficiency": 0.0}}
    sink.emit(_train_rec(step=0, comm_overlap=co))
    assert sink._ici.value(**{"class": "allgather"}) is None
    health = ici_health_from_record(
        _train_rec(comm_overlap=co), nominal_bytes_per_s=1e9)
    assert health == {"allgather": None}
    assert ici_health_from_record(_train_rec()) == {}


def test_sink_folds_serving_record_and_watchdog_trips():
    wd = Watchdog({"ttft_slo": {"slo_s": 0.05, "every": 1,
                                "action": "warn"},
                   "straggler": dict(straggler.STRAGGLER_DEFAULTS)})
    reg = MetricsRegistry()
    sink = MetricsSink(reg, watchdog=wd)
    wd.observe_ttft(0.01)
    wd.observe_ttft(0.2)                      # violation -> trip
    sink.emit(_serving_rec(step=0))
    assert sink._serving_steps.value() == 1.0
    assert sink._prefill_tokens.value() == 100.0
    assert sink._ttft_p95.value() == 0.2
    assert sink._slo_burn.value() == pytest.approx(0.5)
    assert sink._trips.value(watchdog="ttft_slo") == 1.0
    assert wd.ttft_burn_rate() == pytest.approx(0.5)


# --------------------------------------------------------------- straggler
def test_ici_health_from_record_hand_computed():
    per_kind = {"collective": {"run_s": 0.0, "wait_s": 0.001},
                "transfer": {"run_s": 0.0, "wait_s": 0.003}}
    co = {"allgather": {"bytes": 3_000_000, "fused": True,
                        "est_collective_s": 0.0, "exposed_s": 0.0,
                        "overlap_efficiency": 1.0},
          "reduce": {"bytes": 1_000_000, "fused": False,
                     "est_collective_s": 0.0, "exposed_s": 0.0,
                     "overlap_efficiency": 1.0}}
    health = ici_health_from_record(
        _train_rec(per_kind=per_kind, comm_overlap=co),
        nominal_bytes_per_s=1e9)
    # total wait 4 ms apportioned by byte share: allgather gets 3 ms,
    # reduce 1 ms -> both achieve 1e9 B/s == nominal -> health 1.0
    assert health["allgather"] == pytest.approx(1.0)
    assert health["reduce"] == pytest.approx(1.0)


def _fleet_steps(walls_by_host, per_kind_by_host=None, ici_by_host=None):
    """Build merged fleet_step records from {host: [step walls...]}."""
    n = len(next(iter(walls_by_host.values())))
    out = []
    for step in range(n):
        hosts = {}
        for name, walls in walls_by_host.items():
            hosts[name] = {
                "wall": 1000.0 + step, "wall_corrected": 1000.0 + step,
                "offset_s": 0.0, "step_time_s": walls[step],
                "loss": 2.0, "mfu": 0.4, "phases": {},
                "per_kind": (per_kind_by_host or {}).get(name),
                "hbm_peak": None,
                "ici_health": (ici_by_host or {}).get(name),
            }
        out.append({"kind": "fleet_step", "step": step,
                    "n_hosts": len(hosts), "wall": 1000.0 + step,
                    "hosts": hosts, "step_time": None,
                    "missing_hosts": []})
    return out


def test_straggler_flags_after_k_consecutive_steps_only():
    clean = [0.010, 0.011, 0.009, 0.010, 0.010, 0.011]
    spike = [0.010, 0.050, 0.009, 0.010, 0.010, 0.011]  # one-off spike
    slow = [0.010, 0.030, 0.031, 0.032, 0.030, 0.031]   # sick from 1
    report = detect_stragglers(_fleet_steps(
        {"h0": clean, "h1": clean, "h2": spike, "h3": slow}), k=3)
    assert report["flagged_hosts"] == ["h3"]
    flag = report["flags"][0]
    # step 1's median is inflated by the spike host (4 hosts, upper
    # median), so h3's streak honestly starts at step 2
    assert flag["metric"] == "step_wall" and flag["first_step"] == 2
    assert flag["steps"] == 4 and flag["last_step"] == 5
    assert flag["worst_ratio"] == pytest.approx(0.031 / 0.009, rel=0.01)


def test_straggler_streak_broken_by_clean_step():
    slow = [0.030, 0.031, 0.010, 0.030, 0.031]    # never 3 consecutive
    clean = [0.010] * 5
    report = detect_stragglers(_fleet_steps(
        {"h0": clean, "h1": clean, "h2": slow}), k=3)
    assert report["flags"] == []


def test_straggler_flagged_in_two_host_fleet():
    """Even-count medians average the middle pair: with the naive
    upper-middle pick a 2-host fleet's slow host would be its own
    median and never flag (regression)."""
    report = detect_stragglers(
        _fleet_steps({"h0": [0.010] * 4, "h1": [0.035] * 4}), k=3)
    assert report["flagged_hosts"] == ["h1"]
    assert straggler.true_median([1.0, 3.0]) == 2.0
    assert straggler.true_median([1.0, 2.0, 4.0]) == 2.0


def test_straggler_min_hosts_gate():
    report = detect_stragglers(
        _fleet_steps({"h0": [0.01] * 4, "h1": [0.05] * 4}),
        k=2, min_hosts=3)
    assert report["flags"] == []


def test_straggler_per_kind_segment_walls_and_min_wall_floor():
    slow_pk = {"host": {"run_s": 0.030, "wait_s": 0.0},
               "transfer": {"run_s": 50e-6, "wait_s": 0.0}}
    ok_pk = {"host": {"run_s": 0.010, "wait_s": 0.0},
             "transfer": {"run_s": 20e-6, "wait_s": 0.0}}
    # equal step walls: only the per-kind channel can flag; the sub-ms
    # transfer walls (2.5x over median!) are jitter, not signal
    report = detect_stragglers(_fleet_steps(
        {"h0": [0.03] * 4, "h1": [0.03] * 4, "h2": [0.03] * 4},
        per_kind_by_host={"h0": ok_pk, "h1": ok_pk, "h2": slow_pk}), k=3)
    assert [f["metric"] for f in report["flags"]] == ["segment:host"]
    assert report["flagged_hosts"] == ["h2"]


def test_straggler_null_run_s_degrades_not_crashes():
    """A degraded/adopted record (crash-bundle ring, _jsonable
    fallback) can carry ``per_kind: {..., run_s: null}`` — the detector
    must read it as 0, never TypeError on exactly the post-mortem
    inputs the merger promises to tolerate (regression)."""
    null_pk = {"host": {"run_s": None, "wait_s": None}}
    ok_pk = {"host": {"run_s": 0.010, "wait_s": 0.0}}
    report = detect_stragglers(_fleet_steps(
        {"h0": [0.01] * 4, "h1": [0.01] * 4, "h2": [0.01] * 4},
        per_kind_by_host={"h0": ok_pk, "h1": ok_pk, "h2": null_pk}), k=3)
    assert report["flagged_hosts"] == []


def test_describe_flag_ratio_wording():
    """Wall ratios are fleet-median deviations; ici:<class> ratios are
    INVERTED achieved/nominal bandwidth — the trip/log wording must not
    claim median semantics for a bandwidth number."""
    assert "over the fleet median" in straggler.describe_flag_ratio(
        "step_wall", 2.5)
    ici = straggler.describe_flag_ratio("ici:allgather", 4.0)
    assert "25%" in ici and "median" not in ici


def test_ici_degraded_link_flagged():
    ok = {"allgather": 1.0}
    bad = {"allgather": 0.3}       # below 1/factor = 1/1.5
    report = detect_stragglers(_fleet_steps(
        {"h0": [0.01] * 4, "h1": [0.01] * 4, "h2": [0.01] * 4},
        ici_by_host={"h0": ok, "h1": ok, "h2": bad}), k=3)
    assert [f["metric"] for f in report["flags"]] == ["ici:allgather"]
    assert report["flagged_hosts"] == ["h2"]


def test_straggler_flag_tracks_live_streak():
    det = StragglerDetector(k=2)
    for rec in _fleet_steps({"h0": [0.01] * 5, "h1": [0.01] * 5,
                             "h2": [0.03, 0.03, 0.04, 0.05, 0.05]}):
        det.observe(rec)
    assert len(det.flags) == 1                # ONE flag for the streak
    assert det.flags[0]["steps"] == 5
    assert det.flags[0]["worst_ratio"] == pytest.approx(5.0, rel=0.05)
    assert det.flags[0]["last_step"] == 4


# --------------------------------------------------------------- aggregate
def test_manifest_roundtrip_and_validation(tmp_path):
    path = write_host_manifest(str(tmp_path), job_name="train",
                               metrics_port=9400, process_index=3,
                               process_count=8)
    with open(path) as fh:
        manifest = json.load(fh)
    assert validate_host_manifest(manifest) == []
    assert manifest["process_index"] == 3
    assert manifest["files"]["telemetry"] == aggregate.JSONL_NAME
    bad = dict(manifest)
    bad.pop("pid")
    assert validate_host_manifest(bad) == ["missing key 'pid'"]
    assert validate_host_manifest({"kind": "nope"}) \
        == ["unknown manifest kind 'nope'"]


def test_read_jsonl_tolerant_torn_tail_vs_interior_corruption(tmp_path):
    p = tmp_path / "t.jsonl"
    p.write_text('{"a": 1}\n{"bro\n{"b": 2}\n{"torn": tr')
    records, problems = read_jsonl_tolerant(str(p))
    assert records == [{"a": 1}, {"b": 2}]
    assert len(problems) == 2
    assert any("corrupt line at t.jsonl:2" in x for x in problems)
    assert any("torn tail" in x and "t.jsonl:4" in x for x in problems)


def test_load_host_missing_manifest_flags_gap(tmp_path):
    d = _write_host(tmp_path, "h0", steps=3, manifest=False)
    host = load_host(d)
    assert host.manifest is None
    assert "missing host manifest" in host.gaps
    assert len(host.records) == 3             # steps stay merged


def test_load_host_adopts_crash_bundle_records(tmp_path):
    d = _write_host(tmp_path, "h0", steps=2, torn=True)
    crash = os.path.join(d, "crash")
    os.makedirs(crash)
    lost = _train_rec(step=1, wall=1000.03)
    with open(os.path.join(crash, "bundle_000.json"), "w") as fh:
        json.dump({"reason": "watchdog:step_deadline",
                   "records": [lost]}, fh)
    host = load_host(d)
    assert host.crashed and host.crash_reason == "watchdog:step_deadline"
    assert [r["step"] for r in host.records] == [0, 1]
    assert any("torn tail" in g for g in host.gaps)
    assert any("adopted from the crash bundle" in g for g in host.gaps)


def test_estimate_offsets_recovers_deliberate_skew(tmp_path):
    _write_host(tmp_path, "h0", steps=8)
    _write_host(tmp_path, "h1", steps=8, skew=5.0)
    hosts = [load_host(os.path.join(str(tmp_path), n))
             for n in ("h0", "h1")]
    offsets = estimate_offsets(hosts)
    assert offsets["h0"] == 0.0
    assert offsets["h1"] == pytest.approx(5.0, abs=0.01)
    merged = merge_records(hosts, offsets)
    for rec in merged:
        slots = rec["hosts"]
        assert abs(slots["h1"]["wall_corrected"]
                   - slots["h0"]["wall_corrected"]) < 0.05


def test_merge_records_flags_missing_host_steps(tmp_path):
    _write_host(tmp_path, "h0", steps=5)
    _write_host(tmp_path, "h1", steps=3)      # stream stops early
    hosts = [load_host(os.path.join(str(tmp_path), n))
             for n in ("h0", "h1")]
    merged = merge_records(hosts)
    assert len(merged) == 5
    for rec in merged:
        assert validate_fleet_record(rec) == [], rec
    assert merged[2]["missing_hosts"] == []
    assert merged[3]["missing_hosts"] == ["h1"]
    assert merged[3]["n_hosts"] == 1
    assert merged[0]["step_time"]["max_host"] in ("h0", "h1")


def test_validate_fleet_record_rejects_bad_shapes():
    assert validate_fleet_record([]) == ["record is not a dict"]
    assert validate_fleet_record({"kind": "nope"}) \
        == ["unknown record kind 'nope'"]
    good = _fleet_steps({"h0": [0.01]})[0]
    assert validate_fleet_record(good) == []
    extra = dict(good, surprise=1)
    assert any("unexpected key" in p
               for p in validate_fleet_record(extra))
    bad_host = dict(good, hosts={"h0": {"wall": "late"}})
    assert any("missing" in p for p in validate_fleet_record(bad_host))


def test_merge_run_end_to_end_torn_missing_skewed(tmp_path):
    """The satellite contract: torn JSONL + missing manifest + skewed
    clock in one run — merged output schema-valid, every gap flagged,
    no host silently dropped."""
    _write_host(tmp_path, "h0", steps=6)
    _write_host(tmp_path, "h1", steps=6, torn=True)
    _write_host(tmp_path, "h2", steps=6, manifest=False)
    _write_host(tmp_path, "h3", steps=6, skew=3600.0)
    report = merge_run(str(tmp_path))
    assert report["kind"] == "fleet_report"
    assert report["n_hosts"] == 4
    for rec in report["records"]:
        assert validate_fleet_record(rec) == [], rec
    assert len(report["records"]) == 6
    gaps = "\n".join(report["gaps"])
    assert "h1: torn tail" in gaps
    assert "h2: missing host manifest" in gaps
    assert report["offsets"]["h3"] == pytest.approx(3600.0, abs=0.01)
    # the torn host lost ONLY its final step; steps 0..4 stay merged
    by_host = {h["name"]: h for h in report["hosts"]}
    assert by_host["h1"]["steps"] == 5
    assert report["records"][-1]["missing_hosts"] == ["h1"]
    # equal per-step sleeps, no straggler: zero false positives
    assert report["straggler"]["flags"] == []


def test_merge_run_passes_over_an_old_controller_ledger(tmp_path):
    """A run directory written before PR 31 may hold the removed
    run-time controller's ``controller_events.jsonl``: the merge reads
    the host as if the file were not there, and the report has no
    ``controller`` section and passes the stdlib checker."""
    for name in ("h0", "h1"):
        d = _write_host(tmp_path, name, steps=4)
        with open(os.path.join(d, "controller_events.jsonl"), "w") as fh:
            fh.write(json.dumps({
                "kind": "controller_event", "wall": 1001.0, "seq": 0,
                "event": "decision", "decision_id": name + "-0000",
                "policy": "speculation", "knob": "spec_k", "target": None,
                "old": 3, "new": 8, "signal": {"step": 3},
                "predicted_win_s": 0.01, "measured_win_s": None,
                "reason": "acceptance rate 0.9"}) + "\n")
    report = merge_run(str(tmp_path))
    assert "controller" not in report
    assert report["n_hosts"] == 2 and report["gaps"] == []
    assert len(report["records"]) == 4
    path = tmp_path / "fleet_report.json"
    path.write_text(json.dumps(report))
    assert _load_bin("check_bench_schema").check_file(str(path)) == []


def test_merge_chrome_traces_lanes_and_offsets(tmp_path):
    d0 = _write_host(tmp_path, "h0", steps=2)
    d1 = _write_host(tmp_path, "h1", steps=2, skew=2.0)
    ev = {"name": "train_step", "ph": "X", "ts": 1000.0, "dur": 5.0,
          "pid": 777, "tid": 1}
    with open(os.path.join(d0, aggregate.CHROME_TRACE_NAME), "w") as fh:
        json.dump([ev], fh)
    with open(os.path.join(d1, aggregate.CHROME_TRACE_NAME), "w") as fh:
        # the live/crashed lenient form: unclosed array
        fh.write('[{"name": "train_step", "ph": "X", "ts": 2001000.0, '
                 '"dur": 5.0, "pid": 888, "tid": 1},')
    hosts = [load_host(d) for d in (d0, d1)]
    out = os.path.join(str(tmp_path), "merged.json")
    path, events, merged_hosts = merge_chrome_traces(
        hosts, estimate_offsets(hosts), out)
    assert merged_hosts == 2
    with open(path) as fh:
        merged = json.load(fh)                # strict JSON: loadable
    assert len(merged) == events == 4         # 2 metadata + 2 events
    lanes = {e["pid"] for e in merged}
    assert lanes == {0, 1}                    # host-index lanes, not 777
    names = {e["args"]["name"] for e in merged if e["ph"] == "M"}
    assert names == {"h0", "h1"}
    ts = {e["pid"]: e["ts"] for e in merged if e["ph"] == "X"}
    # h1's 2 s clock skew corrected away (both events ~1000 us apart
    # of each other instead of 2e6 us)
    assert abs(ts[1] - ts[0]) < 2e6


# -------------------------------------------------- export + collector
def test_exporter_serves_metrics_and_healthz(tmp_path):
    reg = MetricsRegistry(namespace="ds")
    reg.gauge("mfu").set(0.5)
    state = {"status": "ok"}
    exp = export.MetricsExporter(reg, port=0, healthz=lambda: dict(state))
    try:
        code, text = _get("http://127.0.0.1:{}/metrics".format(exp.port))
        assert code == 200
        families, problems = parse_prometheus_text(text)
        assert problems == []
        assert "ds_mfu" in families
        assert "ds_metrics_scrapes_total" in families
        code, body = _get("http://127.0.0.1:{}/healthz".format(exp.port))
        assert code == 200 and json.loads(body)["status"] == "ok"
        state["status"] = "degraded"
        with pytest.raises(urllib.error.HTTPError) as err:
            _get("http://127.0.0.1:{}/healthz".format(exp.port))
        assert err.value.code == 503
        with pytest.raises(urllib.error.HTTPError) as err:
            _get("http://127.0.0.1:{}/nope".format(exp.port))
        assert err.value.code == 404
        assert exp.snapshot()["live"] is True
        assert exp.snapshot()["scrapes"] == 1
    finally:
        exp.close()
        exp.close()                            # idempotent
    assert exp.snapshot()["live"] is False


def test_collector_metrics_off_structurally_absent(tmp_path):
    before = {t.name for t in threading.enumerate()}
    col = TelemetryCollector(_tc(tmp_path), job_name="off")
    try:
        assert col.metrics is None and col.exporter is None
        assert col.fleet is None
        assert "fleet" not in col.snapshot()
        after = {t.name for t in threading.enumerate()} - before
        assert not any(n.startswith("ds-metrics") for n in after)
        # the manifest is written for EVERY live collector (metrics on
        # or off) so any telemetry run is mergeable post-mortem
        manifest = os.path.join(col.output_dir, aggregate.MANIFEST_NAME)
        with open(manifest) as fh:
            payload = json.load(fh)
        assert validate_host_manifest(payload) == []
        assert payload["metrics_port"] is None
    finally:
        col.close()


def test_collector_metrics_on_full_plane(tmp_path):
    col = TelemetryCollector(
        _tc(tmp_path, metrics={"enabled": True, "port": 0},
            watchdog={"straggler": True}),
        job_name="on")
    try:
        col.sinks.emit(_train_rec(step=0))
        port = col.exporter.port
        code, text = _get("http://127.0.0.1:{}/metrics".format(port))
        families, problems = parse_prometheus_text(text)
        assert problems == [] and "ds_train_steps_total" in families
        # const labels carry job + host
        _, labels, val = families["ds_train_steps_total"]["samples"][0]
        assert labels == {"job": "on", "host": socket.gethostname()}
        assert val == 1.0
        code, body = _get("http://127.0.0.1:{}/healthz".format(port))
        hz = json.loads(body)
        assert code == 200 and hz["status"] == "ok" and hz["steps"] == 1
        assert hz["fleet"]["metrics_export"]["port"] == port
        snap = col.snapshot()
        assert snap["fleet"]["metrics_export"]["live"] is True
        scrape = col.metrics_scrape()
        assert scrape["series"] >= 1 and "# TYPE " in scrape["scrape"]
        # manifest advertises the live port
        with open(os.path.join(col.output_dir,
                               aggregate.MANIFEST_NAME)) as fh:
            assert json.load(fh)["metrics_port"] == port
    finally:
        col.close()
    assert col.metrics_scrape()["series"] >= 1   # registry survives close


def test_collector_survives_bound_metrics_port(tmp_path):
    """A fixed port already bound (two engines sharing one ds_config,
    two processes on a host) must not kill engine construction: the
    sink stays live, only the HTTP plane is absent — loudly."""
    first = TelemetryCollector(
        _tc(tmp_path, metrics={"enabled": True, "port": 0}),
        job_name="a")
    try:
        taken = first.exporter.port
        second = TelemetryCollector(
            _tc(tmp_path, metrics={"enabled": True, "port": taken}),
            job_name="b")
        try:
            assert second.exporter is None
            assert second.metrics is not None      # sink still folds
            second.sinks.emit(_train_rec(step=0))
            assert second.metrics_scrape()["series"] >= 1
            assert second.snapshot()["fleet"]["metrics_export"] is None
        finally:
            second.close()
    finally:
        first.close()


def test_merge_run_trace_out_single_load(tmp_path):
    """merge_run(trace_out=) merges the Chrome traces from the hosts
    it already loaded — the report carries the trace sub-dict and an
    unparseable per-host trace lands in the gaps, not on throwaway
    HostViews."""
    d0 = _write_host(tmp_path, "h0", steps=2)
    d1 = _write_host(tmp_path, "h1", steps=2)
    with open(os.path.join(d0, aggregate.CHROME_TRACE_NAME), "w") as fh:
        json.dump([{"name": "s", "ph": "X", "ts": 1.0, "dur": 1.0,
                    "pid": 1, "tid": 1}], fh)
    with open(os.path.join(d1, aggregate.CHROME_TRACE_NAME), "w") as fh:
        fh.write("not json at all {{{")
    out = os.path.join(str(tmp_path), "merged.json")
    report = merge_run(str(tmp_path), trace_out=out)
    assert report["trace"]["hosts_merged"] == 1
    assert report["trace"]["path"] == os.path.abspath(out)
    with open(out) as fh:
        json.load(fh)                             # loadable
    assert any("unparseable trace_events.json" in g
               for g in report["gaps"])
    assert merge_run(str(tmp_path))["trace"] is None


def test_sink_fleet_ici_keys_are_host_qualified():
    """FleetLocalState.ici_health keys are '<host>:<class>' from BOTH
    sources (local sink measurements and ingest_fleet) — one schema."""
    from deepspeed_tpu.telemetry.fleet.metrics import FleetLocalState
    fleet = FleetLocalState()
    sink = MetricsSink(MetricsRegistry(), fleet=fleet,
                       nominal_bytes_per_s=1e9, host="me")
    per_kind = {"transfer": {"run_s": 0.0, "wait_s": 0.002}}
    co = {"allgather": {"bytes": 2_000_000, "fused": False,
                        "est_collective_s": 0.0, "exposed_s": 0.0,
                        "overlap_efficiency": 0.0}}
    sink.emit(_train_rec(per_kind=per_kind, comm_overlap=co))
    assert fleet.ici_health == {"me:allgather": pytest.approx(1.0)}


def test_ingest_fleet_trips_straggler_watchdog_once(tmp_path):
    col = TelemetryCollector(
        _tc(tmp_path, metrics={"enabled": True, "port": 0},
            watchdog={"straggler": True}),
        job_name="ingest")
    flag = {"host": "h3", "metric": "step_wall", "worst_ratio": 3.0,
            "steps": 4, "first_step": 2, "last_step": 5}
    report = {"straggler": {"flags": [flag]},
              "ici_health": {"h3": {"allgather": 0.4}}}
    try:
        col.ingest_fleet(report)
        col.ingest_fleet(report)               # same flag: ONE trip
        trips = [t for t in col.watchdog.trips
                 if t["watchdog"] == "straggler"]
        assert len(trips) == 1
        snap = col.snapshot()["fleet"]
        assert snap["straggler_flags"] == [flag]
        assert snap["ici_health"] == {"h3:allgather": 0.4}
        assert snap["ingests"] == 2
        hz = col.healthz()
        assert hz["status"] == "degraded"
        assert hz["watchdog"]["trips"] == 1
        with pytest.raises(urllib.error.HTTPError) as err:
            _get("http://127.0.0.1:{}/healthz".format(col.exporter.port))
        assert err.value.code == 503
    finally:
        col.close()


# ----------------------------------------------------------------- config
def test_metrics_config_matrix():
    base = {"enabled": True, "output_path": "/tmp/x"}

    def cfg(**over):
        return DeepSpeedTelemetryConfig(
            {"telemetry": dict(base, **over)})

    off = cfg()
    assert off.metrics_enabled is False and off.metrics_port == 0
    on = cfg(metrics={"enabled": True, "port": 9400, "namespace": "acme"})
    assert on.metrics_enabled and on.metrics_port == 9400
    assert on.metrics_namespace == "acme"
    assert cfg(metrics={}).metrics_enabled is True     # presence = on
    assert cfg(metrics={"enabled": False}).metrics_enabled is False
    with pytest.raises(ValueError, match="telemetry.metrics.port"):
        cfg(metrics={"port": -1})
    with pytest.raises(ValueError, match="telemetry.metrics.port"):
        cfg(metrics={"port": True})
    with pytest.raises(ValueError, match="telemetry.metrics.port"):
        cfg(metrics={"port": 70000})
    with pytest.raises(ValueError, match="namespace"):
        cfg(metrics={"namespace": ""})
    # unknown keys warn (the PR 4 policy); raise under telemetry.strict
    assert cfg(metrics={"prots": 1}).metrics_enabled is True
    with pytest.raises(ValueError, match="unknown key"):
        cfg(strict=True, metrics={"prots": 1})
    # straggler watchdog sub-config rides the PR 8 matrix
    wd = cfg(watchdog={"straggler": {"factor": 2.0, "k": 5,
                                     "action": "dump"}}).watchdog
    assert wd["straggler"]["factor"] == 2.0
    assert wd["straggler"]["k"] == 5
    assert cfg(watchdog={"straggler": True}).watchdog["straggler"] \
        == straggler.STRAGGLER_DEFAULTS
    assert cfg(watchdog={"straggler": False}).watchdog["straggler"] \
        is None
    with pytest.raises(ValueError, match="action"):
        cfg(watchdog={"straggler": {"action": "page_me"}})


# -------------------------------------------------------------- scoreboard
def _bench_file(tmp_path, rung, mfu, device="tpu", rc=0, wrapped=False):
    inner = {"metric": "train_tokens_per_sec_per_chip",
             "value": 1000.0 * (mfu or 0), "unit": "tokens/s/chip",
             "extra": {"mfu": mfu, "device": device}}
    path = tmp_path / "BENCH_r{:02d}.json".format(rung)
    if wrapped:
        payload = {"n": rung, "cmd": "python bench.py", "rc": rc,
                   "tail": "noise\n" + json.dumps(inner) + "\n"}
    elif rc != 0:
        payload = {"n": rung, "cmd": "python bench.py", "rc": rc,
                   "tail": "Traceback ...\n"}
    else:
        payload = inner
    path.write_text(json.dumps(payload))
    return str(path)


def test_scoreboard_regression_gate_and_unwrap(tmp_path):
    scoreboard = _load_bin("ds_scoreboard")
    paths = [
        _bench_file(tmp_path, 1, 0.50, wrapped=True),
        _bench_file(tmp_path, 2, 0.52),
        _bench_file(tmp_path, 3, None, rc=1),      # failed rung, kept
        _bench_file(tmp_path, 4, 0.51),
    ]
    board = scoreboard.build_scoreboard(paths)
    assert [r["mfu"] for r in board["rows"]] == [0.50, 0.52, None, 0.51]
    assert board["rows"][2]["error"] is not None
    assert board["regression"] is False and board["gate"] == "passed"
    assert board["best_prior_mfu"] == 0.52
    # >10% drop trips
    paths.append(_bench_file(tmp_path, 5, 0.40))
    tripped = scoreboard.build_scoreboard(paths)
    assert tripped["regression"] is True and tripped["gate"] == "tripped"
    md = scoreboard.render_markdown(tripped)
    assert "REGRESSION" in md and "| 5 |" in md


def test_scoreboard_device_gating(tmp_path):
    scoreboard = _load_bin("ds_scoreboard")
    paths = [_bench_file(tmp_path, 1, 0.50, device="tpu"),
             _bench_file(tmp_path, 2, 0.003, device="cpu")]
    board = scoreboard.build_scoreboard(paths)
    assert board["regression"] is False
    assert board["gate"].startswith("skipped: latest rung is a cpu")
    # gate-cpu still finds no same-device prior -> skipped, not tripped
    board = scoreboard.build_scoreboard(paths, gate_cpu=True)
    assert board["regression"] is False
    assert board["gate"].startswith("skipped: no prior rung")
    # a genuine same-device cpu regression trips under --gate-cpu
    paths.append(_bench_file(tmp_path, 3, 0.001, device="cpu"))
    board = scoreboard.build_scoreboard(paths, gate_cpu=True)
    assert board["regression"] is True


def test_check_bench_schema_validates_scoreboard_and_metrics(tmp_path):
    scoreboard = _load_bin("ds_scoreboard")
    checker = _load_bin("check_bench_schema")
    paths = [_bench_file(tmp_path, 1, 0.5), _bench_file(tmp_path, 2, 0.6)]
    board = scoreboard.build_scoreboard(paths)
    good = tmp_path / "scoreboard.json"
    good.write_text(json.dumps(board))
    assert checker.check_file(str(good)) == []
    bad = tmp_path / "bad_scoreboard.json"
    bad.write_text(json.dumps(dict(board, rows=[])))
    assert checker.check_file(str(bad)) != []
    # extra.metrics payloads
    assert checker.check_metrics_payload(
        {"series": 5, "port": 1234,
         "scrape": "# TYPE ds_mfu gauge\nds_mfu 0.5\n"}) == []
    assert checker.check_metrics_payload({"series": 0, "scrape": ""}) \
        != []
    assert checker.check_metrics_payload("nope") != []


# ------------------------------------------------------------------ DSL007
def test_dsl007_metric_name_outside_catalog(tmp_path):
    from deepspeed_tpu.analysis import astlint
    src = tmp_path / "mod.py"
    src.write_text(
        "def build(r):\n"
        "    a = r.counter('documented_series_total')\n"
        "    b = r.gauge('undocumented_series')\n"
        "    c = r.histogram('NotAMetricName')\n"   # shape-mismatch: skip
        "    return a, b, c\n")
    catalog = "| `ds_documented_series_total` | counter | | ok |\n"
    findings = astlint.lint_paths([str(tmp_path)], base=str(tmp_path),
                                  metric_catalog=catalog)
    keys = [k for k in findings if k.startswith("DSL007")]
    assert len(keys) == 1
    assert "undocumented_series" in keys[0] or \
        "undocumented_series" in findings[keys[0]][0].message
    # catalog absent -> the rule is inert (partial checkouts)
    assert astlint.lint_paths([str(tmp_path)],
                              base=str(tmp_path)) == {} or \
        not any(k.startswith("DSL007")
                for k in astlint.lint_paths([str(tmp_path)],
                                            base=str(tmp_path)))


def test_dsl007_repo_metrics_all_documented():
    """Every metric name metrics.py exports is in docs/fleet.md —
    the repo's own DSL007 self-check stays green."""
    from deepspeed_tpu.analysis import astlint
    findings = astlint.lint_paths(
        [os.path.join(_REPO, "deepspeed_tpu", "telemetry", "fleet")],
        base=_REPO)
    assert not any(k.startswith("DSL007") for k in findings), \
        sorted(k for k in findings if k.startswith("DSL007"))
