"""The reduction from a profiler trace to busy time, operation times
and labelled idle gaps."""
import os
import re
import types

import pytest

from benchmark import manifest, trace
from benchmark.layer_metrics import (flash_attention_roofline,
                                     mamba_step_roofline,
                                     paged_attention_roofline, serve_mfu)

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")


FUSION = ("%fusion.1 = bf16[20,128]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8]{0} "
          "%p.1), kind=kOutput, calls=%fused_computation.1")
KERNEL = ("%jvp__.48 = (bf16[20,1024,1024]{2,1,0:T(8,128)(2,1)}, f32[20,1024,"
          "16]{2,1,0:T(8,128)}) custom-call(bf16[20,1024,1024]{2,1,0} %g.1), "
          'custom_call_target="tpu_custom_call"')
WHILE = ("%while.3 = (s32[]{:T(128)}, f32[]{:T(128)}) while((s32[]{:T(128)}, "
         "f32[]{:T(128)}) %tuple.1), condition=%c.1, body=%b.1")
CALL = (" = bf16[64,1024]{1,0:T(8,128)(2,1)} custom-call(s32[64,48]{1,0} "
        '%tables.1, bf16[3073,24,16,1024]{3,2,1,0} %cache.1), '
        'custom_call_target="tpu_custom_call"')
GPT2_MEDIUM = {"n_layer": 24, "n_embd": 1024, "n_head": 16,
               "n_positions": 1024, "padded_vocab_size": 50304}


def test_short_names():
    assert trace.short_name(FUSION) == ("fusion bf16[20,128]", "fusion")
    assert trace.short_name(KERNEL) == ("jvp__ kernel", "custom-call")
    assert trace.short_name(WHILE) == ("while (s32[], f32[])", "while")
    assert trace.short_name("plain") == ("plain", "")


def test_union_counts_overlap_once():
    assert trace.union_seconds([]) == 0
    assert trace.union_seconds([(0, 1), (2, 3)]) == 2
    assert trace.union_seconds([(0, 2), (1, 3)]) == 3
    assert trace.union_seconds([(0, 5), (1, 2), (3, 4)]) == 5
    assert trace.union_seconds([(2, 3), (0, 1), (0.5, 2.5)]) == 3


def test_idle_gaps_are_what_no_interval_covers():
    busy = [(1, 2), (1.5, 3), (5, 6)]
    assert trace.idle_gaps(busy, 0, 7) == [(0, 1), (3, 5), (6, 7)]
    assert trace.idle_gaps(busy, 1, 6) == [(3, 5)]
    assert trace.idle_gaps([], 0, 2) == [(0, 2)]
    assert trace.idle_gaps([(0, 9)], 1, 2) == []


def test_a_gap_is_labelled_by_the_span_that_covers_most_of_it():
    spans = [("scheduler.step", 0.0, 1.0), ("loadgen.wait", 1.0, 4.0)]
    assert trace.label_gap((0.5, 1.2), spans) == "scheduler.step"
    assert trace.label_gap((0.9, 3.0), spans) == "loadgen.wait"
    assert trace.label_gap((5.0, 6.0), spans) == "unattributed"


def test_reduction_busy_window_ops_and_breakdown():
    device = {"/device:TPU:0": [
        (FUSION, FUSION, 1.0, 2.0), (KERNEL, KERNEL, 2.0, 2.5),
        (WHILE, WHILE, 4.0, 5.0), (FUSION, FUSION, 4.0, 5.0)]}
    host = [("train_batch", 0.0, 0.1), ("fence", 0.1, 6.0)]
    red = trace.Reduction(device, host, {"/device:TPU:0": [
        ("jit_fused(1)", 0.9, 2.6), ("jit_fused(1)", 3.9, 5.1)]})
    assert (red.start, red.end, red.window_s) == (0.0, 6.0, 6.0)
    assert red.busy_s == pytest.approx(2.5)
    # the while only contains the second fusion: busy once, no operation
    assert red.op_seconds() == {"fusion bf16[20,128]": 2.0, "jvp__ kernel": 0.5}
    assert red.whole_launches(
        [r"^%jvp__\.\d+ = .*tpu_custom_call"]) == (1, 0, 0.5)
    assert red.whole_launches(["paged"]) == (0, 0, 0.0)
    gaps = red.gap_seconds()
    # the first gap (0, 1) lies under both spans; fence covers most
    assert gaps == {"fence": pytest.approx(3.5)}
    assert red.breakdown()["device_ops"][0] == ["fusion bf16[20,128]", 2.0]


@pytest.mark.parametrize("event, read", [
    ("%decode.7" + CALL, True),           # today: the decode program's
    ("%decode" + CALL, True),
    ("%paged_decode.12" + CALL, True),    # a name= on the pallas_call
    ("%paged_attention_grouped" + CALL, True),
    ("%mamba_step.3" + CALL, False),
    ("%prefill.5" + CALL, False),
    ("%decode_tail.2" + CALL, False),
    ("%paged_gather.4 = bf16[64,1024]{1,0} fusion(bf16[8]{0} %p.1), "
     "kind=kLoop, calls=%fused_computation.9", False)])
@pytest.mark.parametrize("metric", ["paged_attention_roofline",
                                    "paged_attention_roofline.docs"])
def test_paged_kernel_is_read_by_either_name(metric, event, read):
    """The decode program's Mosaic calls, or a paged kernel with a name
    of its own: the bytes come from the live pages the runner counted,
    so the share prices the same work whatever implements it."""
    red = trace.Reduction({"/device:TPU:0": [(event, event, 1.0, 1.5),
                                            (FUSION, FUSION, 2.0, 3.0)]},
                          [], {"/device:TPU:0": [
                              ("jit_decode(77)", 0.9, 1.6),
                              ("jit_decode(77)", 1.9, 3.1)]})
    run = types.SimpleNamespace(
        reduction=red, log=lambda m: None,
        counters={"live_kv_pages_read": 1000, "steps": 1},
        config={"family": "gpt2", "model": GPT2_MEDIUM,
                "inference": {"kv_block_size": 16}},
        peaks={"hbm_bytes_per_s": 819e9})
    value = paged_attention_roofline.read(
        run, manifest.load_layer_metric(metric))
    if read:
        # 1000 pages x 16 tokens x K and V x 24 layers x 1024 x 2 bytes
        assert value == pytest.approx(
            100 * (1000 * 1572864 / 819e9) / 0.5)
    else:
        assert value is None            # left out of the line, never 0


def test_whole_launches_are_the_runs_that_hold_as_many_as_most_do():
    """Three runs of one program hold two kernel events each, a fourth
    lost one, a run of another program holds its own one event, and one
    event lies outside every run."""
    k = lambda at: ("%mamba_step.1" + CALL, "%mamba_step.1" + CALL,
                    at, at + 0.25)
    device = {"/device:TPU:0": [
        k(1.0), k(1.5), k(3.0), k(3.5), k(5.0), k(5.5), k(7.0), k(9.0),
        k(11.0), (FUSION, FUSION, 1.25, 1.5)]}
    runs = {"/device:TPU:0": [
        ("jit_decode(5)", 0.9, 2.0), ("jit_decode(5)", 2.9, 4.0),
        ("jit_decode(5)", 4.9, 6.0), ("jit_decode(5)", 6.9, 8.0),
        ("jit_verify(6)", 8.9, 10.0)]}
    red = trace.Reduction(device, [], runs)
    assert red.whole_launches(["^%mamba_step"]) == (4, 1, 1.75)
    assert red.whole_launches(["paged"]) == (0, 0, 0.0)
    # a trace with no module line holds no run to price
    assert trace.Reduction(device, []).whole_launches(
        ["^%mamba_step"]) == (0, 0, 0.0)
    assert trace.program_of("jit_decode(1234567890)") == "jit_decode"
    assert trace.program_of("jit_decode") == "jit_decode"


JAMBA_3B = {"hidden_size": 2560, "num_hidden_layers": 28,
            "mamba_d_state": 16, "mamba_expand": 2, "mamba_d_conv": 4,
            "attn_layer_period": 14, "attn_layer_offset": 7}
ROOFLINES = {
    # metric: (reader, an event of the kernel, events a program run,
    #          the run's configuration, the host's counters for 10 steps)
    "mamba_step_roofline": (
        mamba_step_roofline, "%mamba_step.3" + CALL, 26,
        {"family": "jamba", "model": JAMBA_3B,
         "precision_state": "bfloat16"},
        {"steps": 10, "active_slot_steps": 3800}),
    "paged_attention_roofline.docs": (
        paged_attention_roofline, "%decode.7" + CALL, 24,
        {"family": "gpt2", "model": GPT2_MEDIUM,
         "inference": {"kv_block_size": 16}},
        {"steps": 10, "live_kv_pages_read": 50000}),
    "flash_attention_roofline": (
        flash_attention_roofline, KERNEL, 72,
        {"family": "gpt2", "model": GPT2_MEDIUM},
        {"steps": 10, "rows": 20, "seq_len": 1024}),
}


def _traced(event, per_run, runs, lost_runs=(), lost_events=0):
    """A device plane of ``runs`` program runs, 1 s apart, each with
    ``per_run`` kernel events of 1 ms; the runs in ``lost_runs`` are
    missing with all their events, and run 0 lost its first
    ``lost_events`` events."""
    events, modules = [], []
    for r in range(runs):
        if r in lost_runs:
            continue
        modules.append(("jit_step(9)", r + 0.0, r + 0.9))
        for i in range(lost_events if r == 0 else 0, per_run):
            at = r + 0.01 + 0.002 * i
            events.append((event, event, at, at + 0.001))
    return trace.Reduction({"/device:TPU:0": events}, [],
                           {"/device:TPU:0": modules})


@pytest.mark.parametrize("lost", ["nothing", "runs", "events"])
@pytest.mark.parametrize("metric", sorted(ROOFLINES))
def test_a_trace_that_lost_operations_reads_the_same_share(metric, lost):
    """The host counted ten steps. A trace that holds all ten, one that
    lost four of them whole (the check of PR 32 read 142.8% so, where
    every whole trace reads 88.9%), and one in which a run lost some of
    its events read the same share of the roofline: the work priced is
    that of the runs the trace holds whole."""
    reader, event, per_run, config, counters = ROOFLINES[metric]
    params = manifest.load_layer_metric(metric)

    def read(reduction):
        return reader.read(types.SimpleNamespace(
            reduction=reduction, counters=counters, config=config,
            log=lambda m: None,
            peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}),
            params)

    whole = read(_traced(event, per_run, 10))
    assert 0 < whole
    got = read(_traced(event, per_run, 10,
                       lost_runs=(3, 4, 5, 6) if lost == "runs" else (),
                       lost_events=5 if lost == "events" else 0))
    assert got == pytest.approx(whole, rel=1e-9)
    if metric == "mamba_step_roofline":
        # 380 live slots a step x 26 layers x 389,248 bytes, over 26 ms
        assert whole == pytest.approx(
            100 * (380 * 26 * 389248 / 819e9) / 0.026)


def test_serve_mfu_prices_the_rate_every_serving_run_measures():
    run = types.SimpleNamespace(
        end_to_end={"serve_tokens_per_s": 14289.0, "tpot_p95_ms": 19.9},
        config={"family": "gpt2", "model": GPT2_MEDIUM},
        peaks={"bf16_flops_per_s": 197e12})
    for metric in ("serve_mfu.chat", "serve_mfu.docs"):
        params = manifest.load_layer_metric(metric)
        # 2 x 24 layers x 12 d^2 = 603,979,776 operations a token
        assert serve_mfu.read(run, params) == pytest.approx(
            100 * 14289.0 * 603979776 / 197e12)
    run.end_to_end = {}
    assert serve_mfu.read(run, params) is None


def test_reduction_of_nothing_reads_nothing():
    red = trace.Reduction({}, [])
    assert red.busy_s == 0 and red.window_s == 0
    assert red.breakdown() == {"device_ops": [], "idle_gaps": []}


def test_no_trace_file_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(str(tmp_path))


def test_recorded_tpu_trace():
    """The first 400 device operations of a traced training window on a
    TPU v5e (PR 25's first chip run), with the benchmark's host spans
    beside them: cut from the profiler's own .xplane.pb, names kept to
    their first 400 characters."""
    spans = ["fence", "train_batch", "input.make_batch"]
    red = trace.reduce_trace(
        os.path.join(FIXTURE, "train_step_head.xplane.pb"), spans)
    assert list(red.device_events) == ["/device:TPU:0"]
    assert len(red.device_events["/device:TPU:0"]) == 400   # no module line
    assert [n for n, _, _ in red.host_spans] == [
        "input.make_batch", "train_batch", "input.make_batch", "train_batch"]
    assert red.busy_s == pytest.approx(0.016526628, rel=1e-6)
    assert red.window_s == pytest.approx(0.022548224, rel=1e-6)
    # the flash forward kernel of the first three layers, by the pattern
    # the metric's data file holds
    from benchmark import manifest
    patterns = manifest.load_layer_metric(
        "flash_attention_roofline")["patterns"]
    found = [e - s for _, text, s, e in red.device_events["/device:TPU:0"]
             if any(re.search(p, text) for p in patterns)]
    assert len(found) == 3
    assert sum(found) == pytest.approx(0.004860346, rel=1e-6)
    # the cut holds no module line, so no run of the program to price
    assert red.whole_launches(patterns) == (0, 0, 0.0)
    breakdown = red.breakdown()
    assert breakdown["device_ops"][0][0] == "jvp__ kernel"
    assert breakdown["device_ops"][2][0] == "fusion bf16[20,1024,3072]"
    # the device waits for the window's first dispatch, under train_batch
    assert breakdown["idle_gaps"] == [
        ["train_batch", pytest.approx(0.006021596, rel=1e-6)]]
    assert sum(v for _, v in breakdown["idle_gaps"]) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
