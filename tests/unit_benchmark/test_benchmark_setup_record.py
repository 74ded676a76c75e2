"""The reader of the program's start-up record: sums over the rows that
ended before the window opened, the six ``setup_*`` metrics' parameter
files, and the same numbers from a real tiny engine on the CPU (set-up
needs no trace, so the rehearsal reads what the chip run reads)."""
import json

import pytest

from benchmark import manifest
from benchmark.layer_metrics import setup_record

MANIFEST = manifest.load_manifest()
CELLS = [cell["name"] for cell in MANIFEST["workloads"]][:6]
METRICS = {"setup_import_s": ("s", "program_span"),
           "setup_engine_s": ("s", "program_span"),
           "setup_trace_lower_s": ("s", "program_span"),
           "setup_compile_load_s": ("s", "program_span"),
           "setup_first_run_s": ("s", "program_span"),
           "setup_programs_compiled": ("programs", "program_counter")}


def _row(name, start, end, **attrs):
    return {"name": name, "start_s": start, "end_s": end, "parent": None,
            "attrs": attrs}


def _program(start, end, cache, **seconds):
    attrs = dict({"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
                  "first_run_s": 0.0, "cache_load_s": 0.0}, **seconds)
    return _row("setup.program", start, end, program="prefill", key="8",
                engine="inference-1", step=0, cache=cache, **attrs)


# the window opens at 100: the last two rows are not set-up
ROWS = [
    _row("setup.import", 1.0, 3.5),
    _row("setup.programs.other", 4.0, 9.0, programs=7, compiled=2,
         trace_s=0.5, lower_s=0.25, compile_s=1.0, names={"add": [3, 1.75]}),
    _row("setup.params", 10.0, 14.0, engine="inference-1", bytes=8),
    _row("setup.engine", 10.0, 16.0, engine="inference-1",
         kind="inference"),
    _program(20.0, 30.0, "hit", trace_s=4.0, lower_s=3.0, compile_s=2.0,
             first_run_s=1.0, cache_load_s=1.5),
    _program(30.0, 36.0, "miss", trace_s=1.0, lower_s=1.0, compile_s=3.5,
             first_run_s=0.5),
    _program(36.0, 37.0, None, first_run_s=1.0),
    _program(99.0, 101.0, "off", trace_s=1.0, compile_s=1.0),
    _row("setup.programs.other", 95.0, 100.5, programs=1, compiled=1,
         trace_s=64.0, lower_s=64.0, compile_s=64.0, names={}),
]
EXPECTED = {"setup_import_s": 2.5, "setup_engine_s": 6.0,
            "setup_trace_lower_s": 0.75 + 7.0 + 2.0,
            "setup_compile_load_s": 1.0 + 2.0 + 3.5,
            "setup_first_run_s": 2.5, "setup_programs_compiled": 2 + 1}


class FakeRun:
    t_open = 100.0

    def __init__(self, rows):
        self.setup_rows = None if rows is None else [
            row for row in rows if row["end_s"] <= self.t_open]
        self.logged = []

    def log(self, message):
        self.logged.append(message)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_sums_the_rows_that_ended_before_the_window(name):
    params = manifest.load_layer_metric(name)
    assert params["reader"] == "setup_record"
    assert setup_record.read(FakeRun(ROWS), params) == \
        pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(METRICS))
def test_no_rows_or_no_record_reads_none(name):
    params = manifest.load_layer_metric(name)
    assert setup_record.read(FakeRun([]), params) is None
    # a program without the record (the parent of the PR that brought it)
    assert setup_record.read(FakeRun(None), params) is None
    # rows, but none of the metric's spans
    assert setup_record.read(FakeRun([_row("setup.cache", 1.0, 2.0)]),
                             params) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_manifest_entry_lists_the_six_cells(name):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    unit, source = METRICS[name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": "engine start-up",
                     "moves": "setup_s", "workloads": CELLS}
    assert len(CELLS) == 6
    for cell in CELLS:
        assert entry in manifest.cell_metrics(MANIFEST, cell, "per_layer")


# what test_lfm2_reference.py and test_moonlight_reference.py pinned as
# the whole set of their cell's per-layer metrics when they were written
ISSUE_NAMED = {
    "lfm2-8b-a1b-serve.extract": {
        "batch_occupancy.extract", "kv_pool_live_share.extract",
        "device_idle_share.extract", "sched_host_ms_mean.extract",
        "step_idle_before_dispatch.extract", "step_idle_in_flight.extract",
        "step_idle_after_fetch.extract", "prefill_padding_share.extract",
        "serve_mfu.extract", "moe_gmm_roofline",
        "moe_gmm_busy_share.extract"},
    "moonlight-16b-a3b-serve.reasoning": {
        "batch_occupancy.reasoning", "kv_pool_live_share.reasoning",
        "device_idle_share.reasoning", "sched_host_ms_mean.reasoning",
        "step_idle_before_dispatch.reasoning",
        "step_idle_in_flight.reasoning", "step_idle_after_fetch.reasoning",
        "prefill_padding_share.reasoning", "serve_mfu.reasoning",
        "moe_gmm_roofline.reasoning", "moe_gmm_busy_share.reasoning",
        "mla_decode_roofline", "mla_decode_busy_share.reasoning"},
}


@pytest.mark.parametrize("cell", sorted(ISSUE_NAMED))
def test_a_cell_reports_what_its_issue_named_and_the_start_up_metrics(cell):
    names = {m["name"] for m in manifest.cell_metrics(MANIFEST, cell,
                                                      "per_layer")}
    assert set(METRICS) <= names
    assert names - set(METRICS) == ISSUE_NAMED[cell]
    assert [m["name"] for m in manifest.cell_metrics(
        MANIFEST, cell, "end_to_end")] == ["serve_tokens_per_s", "setup_s"]


def test_a_run_that_opened_no_window_reads_none():
    class NoWindow:
        log = print
    for name in METRICS:
        assert setup_record.read(NoWindow(),
                                 manifest.load_layer_metric(name)) is None


def test_the_rows_come_from_the_program_and_are_logged_once():
    """A real tiny engine on the CPU: the reader takes the program's
    accessor, keeps what ended before the window, and says where the
    set-up went in two log lines."""
    import time
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2

    class Spans:
        spans = []

    class Run(FakeRun):
        def __init__(self):
            self.logged, self.spans = [], Spans()

    run = Run()
    start = time.perf_counter()
    cfg = gpt2.GPT2Config(vocab_size=128, max_seq_len=64, n_layers=2,
                          n_heads=2, d_model=32,
                          use_flash_attention=False, remat=False)
    engine = deepspeed_tpu.init_inference(
        model=gpt2.make_gpt2_model(config=cfg, seed=0),
        config={"inference": {
            "max_batch_size": 2, "prefill_buckets": [8, 32],
            "dtype": "fp32", "greedy": True, "kv_layout": "paged",
            "kv_block_size": 8}})
    engine.generate([[5, 9, 2]], max_new_tokens=2)
    run.spans.spans.append(("engine.build", start, time.perf_counter()))
    run.t_open = time.perf_counter()
    engine.generate([list(range(1, 21))], max_new_tokens=2)   # too late
    values = {name: setup_record.read(run, manifest.load_layer_metric(
        name)) for name in METRICS}
    assert all(v is not None and v >= 0 for v in values.values())
    mine = [row for row in run.setup_rows
            if row["attrs"].get("engine") == engine.startup_tag]
    programs = [row["attrs"] for row in mine
                if row["name"] == "setup.program"]
    assert sorted(p["key"] for p in programs) == ["1/True/0", "8/True/0"]
    assert values["setup_first_run_s"] >= sum(
        p["first_run_s"] for p in programs) > 0
    assert values["setup_trace_lower_s"] >= sum(
        p["trace_s"] + p["lower_s"] for p in programs) > 0
    assert values["setup_engine_s"] >= [
        row["end_s"] - row["start_s"] for row in mine
        if row["name"] == "setup.engine"][0] > 0
    assert [m.split(":")[0] for m in run.logged] == [
        "start-up record [name, starts at (s, window opens at 0), "
        "seconds, attributes]",
        "benchmark spans before the window [name, starts at, seconds, "
        "spans]"]
    record = json.loads(run.logged[0].split("]: ", 1)[1])
    assert all(at < 0 for _, at, _, _ in record)
    assert {name for name, _, _, _ in record} >= {
        "setup.import", "setup.engine", "setup.params", "setup.cache",
        "setup.kernels", "setup.program"}
    assert json.loads(run.logged[1].split("]: ", 1)[1])[0][0] == \
        "engine.build"
