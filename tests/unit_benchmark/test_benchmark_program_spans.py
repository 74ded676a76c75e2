"""The readers of the program's own spans: the thread laid out by its
innermost open span, the device's idle gaps cut at span edges, the
host's milliseconds a step, and the identity that holds the three idle
shares to the idle share."""
import os

import pytest

from benchmark import manifest, trace
from benchmark.layer_metrics import (program_span_host_ms,
                                     program_span_idle_share, program_spans)

HERE = os.path.dirname(os.path.abspath(__file__))
QUANTITIES = ["step_idle_before_dispatch", "step_idle_in_flight",
              "step_idle_after_fetch"]

# one scheduler step of 10 s on the scheduler's thread, a second one of
# 2 s, and nothing of the program's between 10 and 20
STEP = [
    ("sched.step", 0.0, 10.0, {"step": 0}),
    ("sched.plan", 0.0, 1.0, {}),
    ("sched.decode", 2.0, 9.0, {}),
    ("sched.decode.pages", 2.0, 3.0, {}),
    ("engine.decode.prepare", 3.0, 4.0, {}),
    ("engine.decode.dispatch", 4.0, 5.0, {}),
    ("engine.decode.fetch", 5.0, 7.0, {}),
    ("sched.decode.commit", 7.5, 8.5, {}),
    ("sched.retire", 9.0, 9.5, {}),
    ("sched.step", 20.0, 22.0, {"step": 1}),
    ("engine.decode.fetch", 20.5, 21.0, {}),
]


class FakeRun:
    def __init__(self, events, device=None, host=()):
        self.reduction = trace.Reduction(
            {"/device:TPU:0": device} if device else {}, list(host))
        self.program_spans = program_spans.ProgramSpans(events,
                                                        self.reduction)
        self.trace_dir = None
        self.logged = []

    def log(self, message):
        self.logged.append(message)


def test_innermost_tiles_the_open_time_and_adds_up_to_self_times():
    pieces = program_spans.innermost(STEP)
    assert pieces[:5] == [(0.0, 1.0, "sched.plan"),
                          (1.0, 2.0, "sched.step"),
                          (2.0, 3.0, "sched.decode.pages"),
                          (3.0, 4.0, "engine.decode.prepare"),
                          (4.0, 5.0, "engine.decode.dispatch")]
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:])
               if b[0] != 20.0)
    self_s = {}
    for start, end, name in pieces:
        self_s[name] = self_s.get(name, 0.0) + end - start
    # sched.decode: 7 s less its five children's 6 s; sched.step: 12 s
    # less 1 + 7 + 0.5 + 0.5
    assert self_s["sched.decode"] == pytest.approx(1.0)
    assert self_s["sched.step"] == pytest.approx(3.0)
    assert sum(self_s.values()) == pytest.approx(12.0)


def test_a_child_that_outlives_its_parent_by_rounding_is_clamped():
    pieces = program_spans.innermost([("a", 0.0, 1.0), ("b", 0.5, 1.001),
                                      ("c", 1.0005, 2.0)])
    assert pieces == [(0.0, 0.5, "a"), (0.5, 1.0, "b"), (1.0005, 2.0, "c")]


@pytest.mark.parametrize("gap, booked", [
    # inside one span
    ((5.2, 6.2), {"engine.decode.fetch": 1.0}),
    # across two: cut at the edge between them
    ((3.5, 4.25), {"engine.decode.prepare": 0.5,
                   "engine.decode.dispatch": 0.25}),
    # under a container's self time, between two of its children
    ((7.0, 7.5), {"sched.decode": 0.5}),
    # across a child, its container's self time and the next sibling
    ((6.5, 8.0), {"engine.decode.fetch": 0.5, "sched.decode": 0.5,
                  "sched.decode.commit": 0.5}),
    # outside every span
    ((12.0, 15.0), {None: 3.0}),
    # from the end of a step into the time between steps
    ((9.25, 11.0), {"sched.retire": 0.25, "sched.step": 0.5, None: 1.0}),
])
def test_a_gap_is_cut_at_span_edges(gap, booked):
    got = program_spans.book([gap], program_spans.innermost(STEP))
    got = {k: v for k, v in got.items() if v > 1e-12}
    assert got == pytest.approx(booked)


def _shares(run):
    return {q: program_span_idle_share.read(
        run, manifest.load_layer_metric(q + ".chat")) for q in QUANTITIES}


def test_three_shares_and_idle_outside_add_up_to_the_idle_share():
    # the benchmark's own span lies around each step, and between the
    # steps the device works on something else
    device = [("op", "op", 0.5, 1.5), ("op", "op", 4.2, 5.8),
              ("op", "op", 6.9, 7.6), ("op", "op", 10.2, 19.8),
              ("op", "op", 20.0, 22.0)]
    host = [("scheduler.step", -0.5, 10.5), ("scheduler.step", 19.5, 22.5)]
    run = FakeRun(STEP, device, host)
    red = run.reduction
    shares = _shares(run)
    window = 23.0
    assert red.window_s == window
    # idle gaps: -0.5..0.5, 1.5..4.2, 5.8..6.9, 7.6..10.2, 19.8..20,
    # 22..22.5. Before: plan 0.5, step self 0.5, pages 1, prepare 1,
    # decode self 0.5 (8.5..9), step self 0.5 (9.5..10)
    assert shares["step_idle_before_dispatch"] == pytest.approx(
        100 * (0.5 + 0.5 + 1.0 + 1.0 + 0.5 + 0.5) / window)
    # dispatch 4..4.2, fetch 5.8..6.9
    assert shares["step_idle_in_flight"] == pytest.approx(
        100 * (0.2 + 1.1) / window)
    # commit 7.6..8.5, retire 9..9.5
    assert shares["step_idle_after_fetch"] == pytest.approx(
        100 * (0.9 + 0.5) / window)
    outside = run.program_spans.idle[None]
    assert outside == pytest.approx(0.5 + 0.2 + 0.2 + 0.5)
    idle_share = 100 * (1 - red.busy_s / window)
    assert sum(shares.values()) + 100 * outside / window == \
        pytest.approx(idle_share)
    # and against the breakdown, which gives whole gaps to the
    # benchmark's span around the step: the same seconds plus what lies
    # between that span's edges and the program's
    assert red.gap_seconds() == {"scheduler.step": pytest.approx(
        window * sum(shares.values()) / 100 + outside)}


def test_no_span_is_listed_in_two_shares():
    listed = [name for q in QUANTITIES
              for name in manifest.load_layer_metric(q + ".docs")["innermost"]]
    assert len(listed) == len(set(listed))


def test_host_ms_is_the_step_less_its_waits():
    run = FakeRun(STEP)
    params = manifest.load_layer_metric("sched_host_ms_mean.chat")
    # (10 - 2) and (2 - 0.5) seconds
    assert program_span_host_ms.read(run, params) == pytest.approx(
        1000 * (8.0 + 1.5) / 2)
    # no device plane: the host's milliseconds read, the shares do not
    assert _shares(run) == dict.fromkeys(QUANTITIES)
    # a window that the second step outlasts: the first step alone
    cut = FakeRun(STEP, host=[("scheduler.step", -0.5, 21.5)])
    assert program_span_host_ms.read(cut, params) == pytest.approx(8000)


def test_table_counts_seconds_self_and_idle():
    run = FakeRun(STEP, [("op", "op", 4.0, 5.5)],
                  [("scheduler.step", 0.0, 22.0)])
    table = run.program_spans.table()
    assert table["engine.decode.fetch"] == [2, 2.5, 2.5,
                                            pytest.approx(2.0)]
    assert table["sched.decode"][:3] == [1, 7.0, pytest.approx(1.0)]
    assert table["engine.decode.dispatch"][3] == 0.0
    assert FakeRun(STEP).program_spans.table()["sched.plan"] == \
        [1, 1.0, 1.0, None]


def test_a_trace_with_no_program_span_reads_nothing():
    """The parent's program writes none: every reader returns None and
    the metrics are left out of the line."""

    class Run(FakeRun):
        def __init__(self):
            self.trace_dir = os.path.join(HERE, "fixtures",
                                          "train_step_head.xplane.pb")
            self.reduction = trace.reduce_trace(self.trace_dir, [])
            self.logged = []

    run = Run()
    assert run.reduction.device_events
    assert _shares(run) == dict.fromkeys(QUANTITIES)
    assert program_span_host_ms.read(
        run, manifest.load_layer_metric("sched_host_ms_mean.docs")) is None
    assert run.program_spans.events == [] and run.logged == []


def test_recorded_serving_steps_from_the_chip():
    """Two consecutive scheduler steps of a traced chat window on a TPU
    v5e (PR 26's first chip run), the first with a prefill chunk: the
    device's `XLA Ops` events and the host's spans of that stretch, cut
    from the profiler's own .xplane.pb; each device event is named by
    `trace.short_name`'s label, its statistics dropped."""

    class Run(FakeRun):
        def __init__(self):
            self.trace_dir = os.path.join(HERE, "fixtures_program_spans",
                                          "serve_chat_steps.xplane.pb")
            self.reduction = trace.reduce_trace(
                self.trace_dir, ["scheduler.step", "loadgen.wait"])
            self.logged = []

    run = Run()
    red = run.reduction
    assert len(red.device_events["/device:TPU:0"]) == 4300
    assert red.window_s == pytest.approx(0.056260655, rel=1e-6)
    shares = _shares(run)
    spans = run.program_spans
    assert len(spans.events) == 34
    assert {ev[0] for ev in spans.events} == {
        name for q in QUANTITIES
        for name in manifest.load_layer_metric(q + ".chat")["innermost"]}
    assert shares == {
        "step_idle_before_dispatch": pytest.approx(20.818833, rel=1e-6),
        "step_idle_in_flight": pytest.approx(19.493998, rel=1e-6),
        "step_idle_after_fetch": pytest.approx(1.0549842, rel=1e-6)}
    # the identity: what the readers book and what lies outside every
    # program span is all the idle there is ...
    outside = spans.idle[None]
    assert outside == pytest.approx(0.0002252, rel=1e-3)
    assert red.window_s * sum(shares.values()) / 100 + outside == \
        pytest.approx(red.window_s - red.busy_s, rel=1e-9)
    # ... and the same seconds the breakdown puts under the benchmark's
    # own span around the step
    assert red.gap_seconds() == {"scheduler.step": pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)}
    assert program_span_host_ms.read(
        run, manifest.load_layer_metric("sched_host_ms_mean.chat")) == \
        pytest.approx(10.003869, rel=1e-6)
    # the fences: two a prefill chunk, two a decode
    assert spans.table()["timer.sync"][0] == 6
    assert spans.table()["timer.sync"][3] == pytest.approx(0.005492069,
                                                           rel=1e-6)
    # (recorded when the step still wrote `active` and `queued`)
    assert spans.named(["sched.step"])[0][3]["step"] == 441
    assert spans.named(["sched.admit.request"])[0][3]["queue_wait_us"] == 141
    assert any("queue wait of 1 admitted" in m for m in run.logged)


def test_host_ms_is_read_from_a_cpu_trace_through_the_command(tmp_path):
    """A tiny paged engine served inside one profiler session, here on
    the CPU, and the command's own `layer_metrics` over BENCHMARK.json's
    `program_span` entries of the chat cell: the host's milliseconds
    read, and with no device plane the three shares are left out. (The
    tiny rehearsal manifest is a file the benchmark has, so it gains no
    entry; this stands in for its run, in this process.)"""
    import jax

    import deepspeed_tpu
    from benchmark import run as command, spans as bench_spans
    from deepspeed_tpu.inference.scheduler import \
        ContinuousBatchingScheduler
    from deepspeed_tpu.models import gpt2

    engine = deepspeed_tpu.init_inference(
        model=gpt2.make_gpt2_model(config=gpt2.GPT2Config(
            vocab_size=128, max_seq_len=64, n_layers=1, n_heads=2,
            d_model=32, use_flash_attention=False, remat=False), seed=0),
        config={"inference": {
            "max_batch_size": 2, "prefill_buckets": [16], "dtype": "fp32",
            "greedy": True, "kv_layout": "paged", "kv_block_size": 8}})

    def serve():
        sched = ContinuousBatchingScheduler(engine)
        for prompt in ([5, 9, 2, 7], [4, 4, 6]):
            sched.submit(prompt, max_new_tokens=3, eos_token_id=None)
        sched.run()

    serve()                                 # compiles both programs
    jax.profiler.start_trace(str(tmp_path))
    try:
        serve()
    finally:
        jax.profiler.stop_trace()

    class Run(FakeRun):
        def __init__(self):
            whole = manifest.load_manifest()
            self.manifest = dict(whole, per_layer=[
                m for m in whole["per_layer"]
                if m["source"] == "program_span"])
            self.cell = manifest.find_cell(whole, "gpt2-350m-serve.chat")
            self.trace_dir = str(tmp_path)
            self.spans = bench_spans.SpanRecorder()
            self.logged = []

    run = Run()
    values = command.layer_metrics(run, {"end_to_end": {}})
    assert list(values) == ["sched_host_ms_mean.chat"]
    assert values["sched_host_ms_mean.chat"]["unit"] == "ms"
    assert values["sched_host_ms_mean.chat"]["value"] > 0
    assert not run.reduction.device_events
    # every span the program writes is booked by one of the shares
    assert {ev[0] for ev in run.program_spans.events} == {
        name for q in QUANTITIES
        for name in manifest.load_layer_metric(q + ".chat")["innermost"]}
    assert any(m.startswith("program spans {") for m in run.logged)
    assert any("queue wait of 2 admitted" in m for m in run.logged)
