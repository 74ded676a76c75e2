"""Flight recorder + run doctor (ISSUE 8): span-tree shape per engine
path, the watchdog trip/action matrix, crash-bundle round-trip under the
PR 1 fault-injection harness, recompile-storm detection, JSONL rotation,
and the off-is-zero-overhead structural contract."""
import contextlib
import importlib.util
import json
import logging
import os
import signal
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.runtime.model import Model
from deepspeed_tpu.telemetry.config import DeepSpeedTelemetryConfig
from deepspeed_tpu.telemetry.recorder import (CRASH_BUNDLE_KEYS,
                                              validate_crash_bundle)
from deepspeed_tpu.telemetry.spans import SpanTracer, validate_span
from deepspeed_tpu.telemetry.watchdog import Watchdog, WatchdogError
from deepspeed_tpu.utils.fault_injection import SimulatedKill
from deepspeed_tpu.utils.logging import logger as ds_logger

pytestmark = pytest.mark.diagnostics


@contextlib.contextmanager
def _capture_warnings():
    """The DS logger has propagate=False, so caplog can't see it; attach
    a handler directly (the repo's test_telemetry idiom)."""
    messages = []

    class _Cap(logging.Handler):
        def emit(self, record):
            messages.append(record.getMessage())

    cap = _Cap(level=logging.WARNING)
    ds_logger.addHandler(cap)
    try:
        yield messages
    finally:
        ds_logger.removeHandler(cap)


def _toy_model():
    return Model(lambda p, x, y: jnp.mean((x @ p["w"] - y) ** 2),
                 {"w": jnp.zeros((4, 2))})


def _diag_telemetry(tmp_path, **extra):
    tele = {"enabled": True, "output_path": str(tmp_path),
            "spans": {}, "flight_recorder": {}}
    tele.update(extra)
    return tele


def _engine(tmp_path, telemetry=None, extra=None):
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "wall_clock_breakdown": True,
    }
    if telemetry is not None:
        config["telemetry"] = telemetry
    config.update(extra or {})
    engine, _, _, _ = deepspeed_tpu.initialize(model=_toy_model(),
                                               config_params=config)
    return engine


def _batch():
    return jnp.ones((8, 4)), jnp.ones((8, 2))


def _train_steps(engine, n):
    x, y = _batch()
    for _ in range(n):
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()


def _spans_of(engine):
    path = os.path.join(engine.telemetry.output_dir, "spans.jsonl")
    return [json.loads(line) for line in open(path)]


def _crash_dir(engine):
    return os.path.join(engine.telemetry.output_dir, "crash")


def _bundles(engine):
    d = _crash_dir(engine)
    if not os.path.isdir(d):
        return []
    return [os.path.join(d, name) for name in sorted(os.listdir(d))
            if name.endswith(".json")]


def _serve_engine(tmp_path, paged=True, telemetry=None, max_new_tokens=3):
    from deepspeed_tpu.models import gpt2
    cfg = gpt2.GPT2Config(vocab_size=64, max_seq_len=32, n_layers=1,
                          n_heads=2, d_model=16, use_flash_attention=False,
                          remat=False)
    inf = {"max_batch_size": 2, "prefill_buckets": [8, 16], "dtype": "fp32",
           "greedy": True, "max_new_tokens": max_new_tokens}
    if paged:
        inf.update(kv_block_size=4, prefix_caching=True)
    config = {"inference": inf}
    if telemetry is not None:
        config["telemetry"] = telemetry
    return deepspeed_tpu.init_inference(
        model=gpt2.make_gpt2_model(config=cfg), config=config)


# ------------------------------------------------------------ span tracer

def test_span_tracer_tree_export_and_schema():
    exported = []

    class Sink:
        def emit(self, rec):
            exported.append(rec)

        def close(self):
            pass

    tracer = SpanTracer([Sink()], max_events=4)
    root = tracer.begin("serving_request", uid=7)
    root.event("admit", slot=0)
    child = root.child("prefill_chunk", tokens=8)
    child.end()
    root.timed_child("decode", 1.0, 2.0, step=3)
    root.end()
    assert len(exported) == 3                      # depth-first, root first
    assert exported[0]["name"] == "serving_request"
    assert exported[0]["parent_id"] is None
    for rec in exported:
        assert validate_span(rec) == []
        assert rec["trace_id"] == exported[0]["trace_id"]
    assert {rec["parent_id"] for rec in exported[1:]} == \
        {exported[0]["span_id"]}
    assert exported[2]["dur_s"] == pytest.approx(1.0)
    assert exported[0]["events"][0]["name"] == "admit"
    assert tracer.trees_exported == 1 and not tracer._open_roots


def test_span_event_cap_bounds_long_requests():
    tracer = SpanTracer([], max_events=3)
    root = tracer.begin("serving_request")
    for i in range(10):
        root.event("decode", step=i)
    assert len(root.events) == 3
    root.end()
    assert root.to_dict()["attrs"]["dropped_events"] == 7


def test_open_spans_snapshot_for_crash_bundles():
    tracer = SpanTracer([])
    root = tracer.begin("serving_request", uid=1)
    root.child("prefill_chunk")
    open_spans = tracer.open_snapshot()
    assert len(open_spans) == 2
    for rec in open_spans:
        assert rec["end_s"] is None and validate_span(rec) == []
    root.end()
    assert tracer.open_snapshot() == []


# ------------------------------------------------------- train span trees

def test_train_step_span_tree_matches_phases(tmp_path):
    engine = _engine(tmp_path, telemetry=_diag_telemetry(tmp_path))
    _train_steps(engine, 2)
    spans = _spans_of(engine)
    roots = [s for s in spans if s["parent_id"] is None]
    assert len(roots) == 2
    assert {r["name"] for r in roots} == {"train_step"}
    assert [r["attrs"]["step"] for r in roots] == [0, 1]
    assert roots[0]["trace_id"] != roots[1]["trace_id"]
    recs = [json.loads(line) for line in open(engine.telemetry.jsonl_path)]
    for root, rec in zip(roots, recs):
        assert root["attrs"]["path"] == "micro"
        kids = [s for s in spans if s["parent_id"] == root["span_id"]]
        # one child per phase clock, durations EQUAL to the record's
        assert {k["name"] for k in kids} == set(rec["phases"])
        for kid in kids:
            assert kid["dur_s"] == pytest.approx(
                rec["phases"][kid["name"]])
            assert root["start_s"] - 1e-6 <= kid["start_s"] and \
                kid["end_s"] <= root["end_s"] + 1e-6
        assert root["dur_s"] == pytest.approx(rec["step_time_s"])
    for s in spans:
        assert validate_span(s) == []


def test_offload_span_tree_is_the_executed_segment_plan(tmp_path):
    """ISSUE 13: on the executor-lowered paths the step's span tree IS
    the executed segment plan — one child per segment, named by its
    plan node with its kind attr — so trace durations and plan nodes
    cannot drift (phase-derived trees remain the micro/fused
    fallback)."""
    from deepspeed_tpu.runtime.executor import plan_for_engine
    engine = _engine(tmp_path, telemetry=_diag_telemetry(tmp_path),
                     extra={"zero_optimization": {
                         "stage": 2, "cpu_offload": True},
                         "bf16": {"enabled": True}})
    plan_names = [s.name for s in plan_for_engine(engine).segments]
    plan_kinds = {s.name: s.kind
                  for s in plan_for_engine(engine).segments}
    _train_steps(engine, 2)
    spans = _spans_of(engine)
    roots = [s for s in spans if s["parent_id"] is None]
    assert len(roots) == 2
    for root in roots:
        assert root["attrs"]["path"] == "offload"
        kids = [s for s in spans if s["parent_id"] == root["span_id"]]
        # tree == plan: same node names (the async launch order may
        # permute the record order, never the node set)
        assert sorted(k["name"] for k in kids) == sorted(plan_names)
        for kid in kids:
            assert kid["attrs"]["kind"] == plan_kinds[kid["name"]]
            assert kid["dur_s"] is not None and kid["dur_s"] >= 0
    for s in spans:
        assert validate_span(s) == []


def test_fused_path_span_labeled(tmp_path):
    engine = _engine(tmp_path, telemetry=_diag_telemetry(tmp_path),
                     extra={"train_batch_size": 8})
    x, y = np.ones((1, 8, 4), np.float32), np.ones((1, 8, 2), np.float32)
    engine.train_batch(batch=(x, y))
    roots = [s for s in _spans_of(engine) if s["parent_id"] is None]
    assert roots and roots[0]["attrs"]["path"] == "fused"


def test_chrome_trace_file_valid(tmp_path):
    engine = _engine(tmp_path, telemetry=_diag_telemetry(tmp_path))
    _train_steps(engine, 2)
    engine.telemetry.close()
    path = os.path.join(engine.telemetry.output_dir, "trace_events.json")
    events = json.load(open(path))              # closed file: strict JSON
    assert events
    checker = _load_checker()
    assert checker.check_trace_events(open(path).read()) == []
    # truncated mid-write (a crashed run): still validates leniently
    text = open(path).read()
    cut = text.rindex("},") + 2
    assert checker.check_trace_events(text[:cut]) == []


# ---------------------------------------------------- serving span trees

def test_serving_request_span_tree(tmp_path):
    engine = _serve_engine(tmp_path,
                           telemetry=_diag_telemetry(tmp_path))
    system = list(range(1, 13))                  # 3 full 4-token pages
    engine.generate([system + [20, 21, 22]])
    engine.generate([system + [30, 31]])        # prefix hit on pages
    spans = _spans_of(engine)
    roots = [s for s in spans if s["parent_id"] is None]
    assert len(roots) == 2
    for root in roots:
        assert root["name"] == "serving_request"
        events = [e["name"] for e in root["events"]]
        assert events[0] == "admit" and events[-1] == "retire"
        assert "page_alloc" in events
        kids = [s["name"] for s in spans
                if s["parent_id"] == root["span_id"]]
        assert "prefill_chunk" in kids and "decode" in kids
        # 3 new tokens => first from prefill + 2 decode steps
        assert kids.count("decode") == 2
    assert any("prefix_hit" in [e["name"] for e in r["events"]]
               for r in roots)
    for s in spans:
        assert validate_span(s) == []


def test_preemption_event_rides_request_span(tmp_path):
    """A pool-exhaustion preemption lands as an event on the victim's
    span, and the resumed request keeps ONE trace (second admit event)."""
    from deepspeed_tpu.models import gpt2
    cfg = gpt2.GPT2Config(vocab_size=128, max_seq_len=64, n_layers=1,
                          n_heads=2, d_model=16, use_flash_attention=False,
                          remat=False)
    # 3 slots x up to ~40 tokens each, but only 9 pages (72 tokens):
    # the shapes of test_serving's preemption test
    engine = deepspeed_tpu.init_inference(
        model=gpt2.make_gpt2_model(config=cfg),
        config={"inference": {
            "max_batch_size": 3, "prefill_buckets": [8, 16, 32],
            "dtype": "fp32", "greedy": True,
            "kv_block_size": 8, "num_pages": 9},
            "telemetry": _diag_telemetry(tmp_path, watchdog={
                "pool_exhaustion": {"every": 1, "action": "warn"}})})
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, 128, size=n).tolist() for n in (12, 14, 10)]
    with _capture_warnings() as messages:
        engine.generate(prompts, max_new_tokens=24)
    spans = _spans_of(engine)
    roots = [s for s in spans if s["parent_id"] is None]
    preempted = [r for r in roots
                 if "preempted" in [e["name"] for e in r["events"]]]
    assert preempted, [r["events"] for r in roots]
    events = [e["name"] for e in preempted[0]["events"]]
    assert events.count("admit") == 2            # admitted, then resumed
    assert any("pool_exhaustion" in m for m in messages)
    assert engine.telemetry.watchdog.snapshot()["pool_events"] >= 1
    engine.telemetry.close()                     # stops the watchdog thread


# ------------------------------------------------------ watchdog matrix

def _rec(step, loss, overflow=False):
    return {"kind": "train_step", "step": step, "loss": loss,
            "overflow": overflow}


class _FakeRecorder:
    def __init__(self):
        self.dumps = []

    def dump(self, reason, exc=None):
        self.dumps.append(reason)
        return "/dev/null"


def test_watchdog_nan_streak_actions():
    for action, dumps, raises in (("warn", 0, False), ("dump", 1, False),
                                  ("raise", 1, True)):
        rec = _FakeRecorder()
        wd = Watchdog({"nan_streak": {"threshold": 2, "action": action}},
                      recorder=rec)
        with _capture_warnings() as messages:
            wd.observe_train(_rec(0, float("nan")))
            assert not wd.trips                   # streak of 1: no trip
            if raises:
                with pytest.raises(WatchdogError, match="nan_streak"):
                    wd.observe_train(_rec(1, float("nan")))
            else:
                wd.observe_train(_rec(1, float("nan")))
            # the streak trips ONCE, not on every further bad step
            wd.observe_train(_rec(2, float("nan")))
        assert len(wd.trips) == 1
        assert len(rec.dumps) == dumps
        assert any("nan_streak" in m and "TRIPPED" in m for m in messages)
        # a finite step resets the streak; a fresh streak re-trips
        wd.observe_train(_rec(3, 1.0))
        if raises:
            with pytest.raises(WatchdogError):
                wd.observe_train(_rec(4, float("nan")))
                wd.observe_train(_rec(5, float("nan")))
        else:
            wd.observe_train(_rec(4, float("nan")))
            wd.observe_train(_rec(5, float("nan")))
        assert len(wd.trips) == 2
        wd.close()


def test_watchdog_overflow_counts_toward_streak():
    wd = Watchdog({"nan_streak": {"threshold": 2, "action": "warn"}})
    wd.observe_train(_rec(0, 1.0, overflow=True))
    wd.observe_train(_rec(1, 1.0, overflow=True))
    assert len(wd.trips) == 1
    wd.close()


def test_watchdog_loss_spike_zscore():
    wd = Watchdog({"loss_spike": {"zscore": 4.0, "window": 16,
                                  "min_steps": 4, "action": "warn"}})
    for i in range(8):
        wd.observe_train(_rec(i, 1.0 + 0.01 * (i % 2)))
    assert not wd.trips
    wd.observe_train(_rec(8, 50.0))              # >> 4 sigma
    assert len(wd.trips) == 1
    assert wd.trips[0]["watchdog"] == "loss_spike"
    # cooldown: the window refills before another trip can fire
    wd.observe_train(_rec(9, 60.0))
    assert len(wd.trips) == 1
    wd.close()


def test_watchdog_ttft_slo_and_pool_events():
    rec = _FakeRecorder()
    wd = Watchdog({"ttft_slo": {"slo_s": 0.5, "every": 2,
                                "action": "dump"},
                   "pool_exhaustion": {"every": 1, "action": "warn"}},
                  recorder=rec)
    wd.observe_ttft(0.1)
    assert not wd.trips
    wd.observe_ttft(0.9)                         # violation 1 -> trip
    wd.observe_ttft(0.9)                         # violation 2 (every=2)
    wd.observe_ttft(0.9)                         # violation 3 -> trip
    assert len([t for t in wd.trips
                if t["watchdog"] == "ttft_slo"]) == 2
    assert rec.dumps == ["watchdog:ttft_slo"] * 2
    wd.observe_pool_event("admission_blocked")
    assert wd.trips[-1]["watchdog"] == "pool_exhaustion"
    snap = wd.snapshot()
    assert snap["ttft_violations"] == 3 and snap["pool_events"] == 1
    wd.close()


def test_watchdog_step_deadline_thread_trips_on_hang():
    before = {id(t) for t in threading.enumerate()}
    rec = _FakeRecorder()
    wd = Watchdog({"step_deadline": {
        "factor": 2.0, "min_steps": 3, "floor_s": 0.2, "poll_s": 0.02,
        "action": "dump"}}, recorder=rec)
    for step in range(3):                        # build the median
        wd.step_begin(step)
        time.sleep(0.01)
        wd.step_end()
    with _capture_warnings() as messages:
        wd.step_begin(3)                         # armed now
        deadline = time.monotonic() + 2.0
        while not rec.dumps and time.monotonic() < deadline:
            time.sleep(0.02)                     # the "hang"
        wd.step_end()
    assert wd.trips and wd.trips[0]["watchdog"] == "step_deadline"
    assert rec.dumps == ["watchdog:step_deadline"]
    assert any("has not completed" in m for m in messages)
    wd.close()
    # close() joined THIS watchdog's thread (other tests' daemon
    # threads, from engines whose collectors outlive their test, are
    # not this test's concern)
    assert not any(t.name.startswith("ds-watchdog")
                   for t in threading.enumerate()
                   if t.is_alive() and id(t) not in before)


def test_watchdog_step_deadline_clean_steps_no_trip():
    wd = Watchdog({"step_deadline": {
        "factor": 50.0, "min_steps": 2, "floor_s": 5.0, "poll_s": 0.02,
        "action": "warn"}})
    for step in range(6):
        wd.step_begin(step)
        time.sleep(0.005)
        wd.step_end()
    time.sleep(0.1)                              # let the thread poll
    assert not wd.trips
    wd.close()


def test_watchdog_dump_action_without_recorder_warns():
    wd = Watchdog({"nan_streak": {"threshold": 1, "action": "dump"}},
                  recorder=None)
    with _capture_warnings() as messages:
        wd.observe_train(_rec(0, float("nan")))
    assert any("flight_recorder" in m for m in messages)
    wd.close()


# ------------------------------------------------------- crash bundles

def test_mid_step_kill_yields_schema_valid_bundle(tmp_path, monkeypatch):
    """PR 1 fault-injection harness: a SimulatedKill (BaseException,
    like a real preemption) mid-step must leave a schema-valid crash
    bundle with >= 1 StepRecord, the span tree, and the program
    registry — then re-raise untouched."""
    engine = _engine(tmp_path, telemetry=_diag_telemetry(tmp_path))
    _train_steps(engine, 2)                      # ring holds 2 records

    def boom(lr_kwargs=None):
        raise SimulatedKill("injected mid-step kill")

    monkeypatch.setattr(engine, "_take_model_step", boom)
    x, y = _batch()
    with pytest.raises(SimulatedKill):
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
    paths = _bundles(engine)
    assert len(paths) == 1
    bundle = json.load(open(paths[0]))
    assert validate_crash_bundle(bundle) == []
    assert bundle["reason"] == "exception:train_step"
    assert bundle["exception"]["type"] == "SimulatedKill"
    assert "injected mid-step kill" in bundle["exception"]["traceback"]
    assert len(bundle["records"]) >= 1
    assert all(r["kind"] == "train_step" for r in bundle["records"])
    assert any(s["name"] == "train_step" for s in bundle["spans"])
    assert "micro" in bundle["programs"]["programs"]
    assert bundle["env"]["jax_version"] == jax.__version__
    assert bundle["ds_config"]["train_micro_batch_size_per_gpu"] == 1
    assert bundle["state"]["engine"]["global_steps"] == 2
    # the stdlib checker in bin/ accepts the same bundle
    assert _load_checker().check_crash_bundle(bundle) == []


def test_nested_step_path_wrappers_dump_once(tmp_path, monkeypatch):
    """forward() raising inside train-path code that an outer wrapper
    also guards must produce ONE bundle, not one per wrapper."""
    engine = _engine(tmp_path, telemetry=_diag_telemetry(tmp_path))
    _train_steps(engine, 1)
    err = RuntimeError("boom")

    def boom(*args, **kwargs):
        raise err

    monkeypatch.setattr(engine, "_forward_impl", boom)
    x, y = _batch()
    with pytest.raises(RuntimeError):
        engine(x, y)
    with pytest.raises(RuntimeError):
        engine(x, y)                             # same exception object
    assert len(_bundles(engine)) == 1


def test_debug_dump_and_bundle_retention(tmp_path):
    tele = _diag_telemetry(tmp_path)
    tele["flight_recorder"] = {"max_bundles": 2, "capacity": 3}
    engine = _engine(tmp_path, telemetry=tele)
    _train_steps(engine, 5)
    for i in range(3):
        assert engine.debug_dump("probe{}".format(i)) is not None
    paths = _bundles(engine)
    assert len(paths) == 2                       # retention pruned oldest
    assert "probe1" in paths[0] and "probe2" in paths[1]
    bundle = json.load(open(paths[-1]))
    assert validate_crash_bundle(bundle) == []
    assert len(bundle["records"]) == 3           # ring capacity bound


def test_debug_dump_without_recorder_is_loud_noop(tmp_path):
    engine = _engine(tmp_path, telemetry={"enabled": True,
                                          "output_path": str(tmp_path)})
    with _capture_warnings() as messages:
        assert engine.debug_dump() is None
    assert any("flight_recorder" in m for m in messages)


def test_bundle_counter_survives_process_restart(tmp_path):
    """A crash-looping job restarts with a fresh recorder every time;
    it must neither overwrite the previous crash's bundle nor grow the
    directory past max_bundles."""
    from deepspeed_tpu.telemetry.recorder import FlightRecorder
    crash = str(tmp_path / "crash")
    first = FlightRecorder(crash, max_bundles=2)
    p0 = first.dump("crash")
    first.close()
    second = FlightRecorder(crash, max_bundles=2)   # "restarted" process
    p1 = second.dump("crash")
    assert p1 != p0 and os.path.exists(p0) and os.path.exists(p1)
    second.dump("crash")                            # retention: 2 kept
    second.close()
    kept = sorted(os.listdir(crash))
    assert len(kept) == 2 and os.path.basename(p0) not in kept


def test_watchdog_thread_raise_covers_induced_interrupt(tmp_path):
    """A raise-trip from the deadline thread dumps ONCE: the induced
    KeyboardInterrupt reaching the step-path hook must not write a
    second bundle for the same trip."""
    from deepspeed_tpu.telemetry.recorder import FlightRecorder
    rec = FlightRecorder(str(tmp_path / "crash"))
    assert rec.dump("watchdog:step_deadline") is not None
    rec.cover_interrupt()
    assert rec.dump("exception:forward", exc=KeyboardInterrupt()) is None
    # a LATER real interrupt (window expired) still dumps
    rec._interrupt_covered_until = 0.0
    assert rec.dump("exception:forward",
                    exc=KeyboardInterrupt()) is not None
    rec.close()


def test_warn_log_events_ride_the_bundle(tmp_path):
    engine = _engine(tmp_path, telemetry=_diag_telemetry(tmp_path))
    _train_steps(engine, 1)
    ds_logger.warning("synthetic warning for the ring %d", 7)
    bundle = json.load(open(engine.debug_dump()))
    assert any("synthetic warning for the ring 7" == e["message"]
               for e in bundle["log_events"])


def test_sigterm_handler_dumps_and_chains(tmp_path):
    tele = _diag_telemetry(tmp_path)
    tele["flight_recorder"] = {"on_sigterm": True}
    chained = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: chained.append(s))
    try:
        engine = _engine(tmp_path, telemetry=tele)
        _train_steps(engine, 1)
        handler = signal.getsignal(signal.SIGTERM)
        assert handler == engine.telemetry.recorder._on_sigterm
        handler(signal.SIGTERM, None)
        assert len(_bundles(engine)) == 1
        assert "sigterm" in _bundles(engine)[0]
        assert chained == [signal.SIGTERM]       # previous handler ran
        engine.telemetry.close()                 # uninstalls the handler
        assert signal.getsignal(signal.SIGTERM) not in \
            (handler, signal.SIG_DFL) or \
            signal.getsignal(signal.SIGTERM) != handler
    finally:
        signal.signal(signal.SIGTERM, prev)


# -------------------------------------------------- compile observatory

def test_program_registry_prices_engine_programs(tmp_path):
    engine = _engine(tmp_path, telemetry=_diag_telemetry(tmp_path))
    _train_steps(engine, 3)
    snap = engine.telemetry.programs.snapshot()
    assert set(snap["programs"]) >= {"micro", "apply"}
    micro = snap["programs"]["micro"]
    assert micro["calls"] == 3
    assert micro["flops"] > 0 and micro["cost_analysis"]["flops"] > 0
    # what the program's making cost is the start-up record's to say:
    # one row for the micro program, with JAX's own seconds
    rows = [row["attrs"] for row in engine.startup_report()["rows"]
            if row["name"] == "setup.program"]
    assert sorted(r["program"] for r in rows) == ["apply", "micro"]
    row = next(r for r in rows if r["program"] == "micro")
    assert row["step"] == 0 and row["compile_s"] > 0
    # the first call's fresh-state signature may legitimately differ
    # from the steady state's (one extra executable); a STABLE loop must
    # not keep recompiling
    assert micro["recompiles"] <= 1
    assert not snap["flags"]


def test_recompile_storm_flagged_on_prefill_bucket_explosion(tmp_path):
    tele = _diag_telemetry(tmp_path)
    tele["programs"] = {"recompile_storm_threshold": 2}
    engine = _serve_engine(tmp_path, paged=False, telemetry=tele,
                           max_new_tokens=1)
    with _capture_warnings() as messages:
        # 8- and 16-token buckets at two sampling configs -> 3 distinct
        # prefill traces: past the tiny threshold
        engine.generate([[1, 2, 3]])
        engine.generate([list(range(1, 11))])
        engine.generate([[4, 5]], sampling={"greedy": False, "top_k": 2})
    snap = engine.telemetry.programs.snapshot()
    assert snap["families"]["prefill"]["count"] >= 3
    assert snap["families"]["prefill"]["storm"] is True
    assert any(f["key"] == "recompile_storm:prefill"
               for f in snap["flags"])
    assert any("recompile storm" in m for m in messages)
    assert "program_flags" in engine.telemetry_snapshot()


def test_replicated_leaf_audit_flags_large_replicated_inputs():
    from deepspeed_tpu.telemetry.programs import ProgramRegistry
    reg = ProgramRegistry(replicated_leaf_bytes=1024)
    big = jax.device_put(jnp.ones((64, 64), jnp.float32))  # replicated
    fn = jax.jit(lambda x: x * 2)
    fn(big)
    with _capture_warnings() as messages:
        reg.observe_call("grow", fn, (big,))
    if jax.device_count() > 1:
        assert any(f["key"].startswith("replicated_leaf")
                   for f in reg.flags)
        assert any("REPLICATED" in m for m in messages)
    small = jnp.ones((2,), jnp.float32)
    reg.observe_call("ok", fn, (small,))
    assert not any(f["key"].startswith("replicated_leaf:ok")
                   for f in reg.flags)


def test_registry_counts_recompiles_via_jit_cache():
    from deepspeed_tpu.telemetry.programs import ProgramRegistry
    reg = ProgramRegistry(storm_threshold=4)
    fn = jax.jit(lambda x: x + 1)
    fn(jnp.ones((2,)))
    reg.observe_call("k", fn, None)
    assert reg.programs["k"]["recompiles"] == 0
    for n in range(3, 9):                        # 6 new shapes
        fn(jnp.ones((n,)))
        reg.observe_call("k", fn, None)
    entry = reg.programs["k"]
    assert entry["executables"] == 7 and entry["recompiles"] == 6
    assert any(f["key"] == "recompile_storm:k" for f in reg.flags)


# ----------------------------------------------------- bounded JSONL

def test_jsonl_rotation_keeps_schema_valid_files(tmp_path):
    from deepspeed_tpu.telemetry.record import validate_step_record
    tele = _diag_telemetry(tmp_path, jsonl_max_bytes=4096)
    engine = _engine(tmp_path, telemetry=tele)
    _train_steps(engine, 12)                     # records ~> 1 KB each
    main_path = engine.telemetry.jsonl_path
    rotated = main_path + ".1"
    assert os.path.exists(rotated)
    assert os.path.getsize(main_path) <= 4096
    assert os.path.getsize(rotated) <= 4096
    n = 0
    for path in (main_path, rotated):
        for line in open(path):
            assert validate_step_record(json.loads(line)) == []
            n += 1
    assert 0 < n <= 12                           # oldest rotation dropped
    with pytest.raises(ValueError, match="jsonl_max_bytes"):
        DeepSpeedTelemetryConfig({"telemetry": {"jsonl_max_bytes": 10}})


# ------------------------------------------------- config validation

def test_diagnostics_config_unknown_keys_warn_and_strict_raises():
    base = {"enabled": True, "output_path": "x"}
    for section in ("spans", "flight_recorder", "watchdog", "programs"):
        with _capture_warnings() as messages:
            DeepSpeedTelemetryConfig({"telemetry": dict(
                base, **{section: {"bogus": 1}})})
        assert any("bogus" in m for m in messages), section
        with pytest.raises(ValueError, match="bogus"):
            DeepSpeedTelemetryConfig({"telemetry": dict(
                base, strict=True, **{section: {"bogus": 1}})})
    with pytest.raises(ValueError, match="action"):
        DeepSpeedTelemetryConfig({"telemetry": dict(base, watchdog={
            "nan_streak": {"action": "explode"}})})
    with pytest.raises(ValueError, match="threshold"):
        DeepSpeedTelemetryConfig({"telemetry": dict(base, watchdog={
            "nan_streak": {"threshold": -1}})})
    cfg = DeepSpeedTelemetryConfig({"telemetry": dict(base, watchdog={
        "step_deadline": False, "ttft_slo": {"slo_s": 2.0}})})
    assert cfg.watchdog["step_deadline"] is None
    assert cfg.watchdog["ttft_slo"]["slo_s"] == 2.0
    # ttft_slo without an slo_s can never trip: parsed away
    cfg = DeepSpeedTelemetryConfig({"telemetry": dict(base,
                                                      watchdog={})})
    assert cfg.watchdog["ttft_slo"] is None
    assert cfg.watchdog["nan_streak"]["threshold"] == 3


# --------------------------------------------- off-is-zero-overhead

def test_diagnostics_off_is_structurally_absent(tmp_path):
    from deepspeed_tpu.inference.scheduler import \
        ContinuousBatchingScheduler
    before_threads = {t.name for t in threading.enumerate()}
    before_handler = signal.getsignal(signal.SIGTERM)
    n_handlers = len(ds_logger.handlers)
    # telemetry ON but no diagnostics sections: registry only
    engine = _engine(tmp_path, telemetry={"enabled": True,
                                          "output_path": str(tmp_path)})
    tel = engine.telemetry
    assert tel.spans is None and tel.recorder is None and \
        tel.watchdog is None
    assert tel.programs is not None              # observatory rides along
    _train_steps(engine, 1)
    assert not os.path.exists(os.path.join(tel.output_dir, "spans.jsonl"))
    assert not os.path.exists(os.path.join(tel.output_dir, "crash"))
    serve = _serve_engine(tmp_path / "srv", paged=False,
                          telemetry=None)
    sched = ContinuousBatchingScheduler(serve)
    assert sched._spans is None and sched._watchdog is None
    assert len(ds_logger.handlers) == n_handlers
    assert signal.getsignal(signal.SIGTERM) == before_handler
    assert {t.name for t in threading.enumerate()
            if t.name.startswith("ds-watchdog")} - before_threads == set()
    # telemetry fully OFF keeps the one-is-not-None contract
    off = _engine(tmp_path / "off", telemetry=None)
    assert off.telemetry is None


# ------------------------------------------------ checker pinned copies

def _load_checker():
    path = os.path.join(os.path.dirname(__file__), "..", "..", "bin",
                        "check_bench_schema.py")
    spec = importlib.util.spec_from_file_location("check_bench_schema",
                                                  os.path.abspath(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_checker_local_copies_pinned_to_source_of_truth():
    checker = _load_checker()
    assert tuple(checker.CRASH_BUNDLE_KEYS) == tuple(CRASH_BUNDLE_KEYS)


def test_checker_rejects_malformed_diagnostics_artifacts():
    checker = _load_checker()
    assert checker.check_crash_bundle({"kind": "crash_bundle"})
    assert checker.check_trace_events("not json at all [")
    assert checker.check_trace_events("[]")      # no events
    bad_event = json.dumps([{"name": "x", "ph": "X", "ts": 1.0,
                             "pid": 0}])         # no tid/dur
    assert checker.check_trace_events(bad_event)
    good = json.dumps([{"name": "x", "ph": "X", "ts": 1.0, "dur": 2.0,
                        "pid": 0, "tid": 1}])
    assert checker.check_trace_events(good) == []


# ------------------------------------------------- env report satellite

def test_collect_env_is_bundle_ready():
    from deepspeed_tpu.env_report import collect_env, main
    env = collect_env()
    json.dumps(env)                              # JSON-serializable
    assert env["jax_version"] == jax.__version__
    assert env["device_count"] == jax.device_count()
    assert env["devices"][0]["kind"]
    assert "python_version" in env and "platform" in env
    import io
    out = io.StringIO()
    assert main(out) == 0
    text = out.getvalue()
    assert "jax version" in text and "HBM per device" in text


# --------------------------------------------- flops profiler satellite

def test_flops_profiler_loud_when_costs_missing(tmp_path, monkeypatch):
    from deepspeed_tpu.profiling.flops_profiler import profiler as prof_mod
    engine = _engine(tmp_path, telemetry={"enabled": True,
                                          "output_path": str(tmp_path)})
    prof = prof_mod.FlopsProfiler(engine)
    with _capture_warnings() as messages:
        assert prof.profile_engine_step() == {}
        assert prof.get_total_flops() is None
    assert sum("flops_profiler" in m and "cost_analysis" in m
               for m in messages) == 2
    # under telemetry.strict the same no-ops raise
    engine._config.telemetry_config.strict = True
    with pytest.raises(ValueError, match="flops_profiler"):
        prof.profile_engine_step()
    engine._config.telemetry_config.strict = False

    # pricing delegates to telemetry's costs_of_compiled (one home)
    calls = []
    real = prof_mod.cost_analysis_of
    from deepspeed_tpu.telemetry import collector as coll_mod

    def spy(fn, *args):
        calls.append("delegated")
        return {"flops": 7.0}

    monkeypatch.setattr(coll_mod, "costs_of_compiled", spy)
    costs = real(lambda x: x * 2, jnp.ones((2,)))
    assert calls == ["delegated"] and costs["flops"] == 7.0
