"""The reduction from a profiler trace to busy time, operation times
and labelled idle gaps."""
import os

import pytest

from benchmark import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")


FUSION = ("%fusion.1 = bf16[20,128]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8]{0} "
          "%p.1), kind=kOutput, calls=%fused_computation.1")
KERNEL = ("%jvp__.48 = (bf16[20,1024,1024]{2,1,0:T(8,128)(2,1)}, f32[20,1024,"
          "16]{2,1,0:T(8,128)}) custom-call(bf16[20,1024,1024]{2,1,0} %g.1), "
          'custom_call_target="tpu_custom_call"')
WHILE = ("%while.3 = (s32[]{:T(128)}, f32[]{:T(128)}) while((s32[]{:T(128)}, "
         "f32[]{:T(128)}) %tuple.1), condition=%c.1, body=%b.1")


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
    red = trace.Reduction(device, host)
    assert (red.start, red.end, red.window_s) == (0.0, 6.0, 6.0)
    assert red.busy_s == pytest.approx(2.5)
    # the while only contains the second fusion: busy once, no operation
    assert red.op_seconds() == {"fusion bf16[20,128]": 2.0, "jvp__ kernel": 0.5}
    assert red.matching([r"^%jvp__\.\d+ = .*tpu_custom_call"]) == (1, 0.5)
    assert red.matching(["paged"]) == (0, 0.0)
    gaps = red.gap_seconds()
    # the first gap (0, 1) lies under both spans; fence covers most
    assert gaps == {"fence": pytest.approx(3.5)}
    assert red.breakdown()["device_ops"][0] == ["fusion bf16[20,128]", 2.0]


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
    count, seconds = red.matching(patterns)
    assert count == 3 and seconds == pytest.approx(0.004860346, rel=1e-6)
    breakdown = red.breakdown()
    assert breakdown["device_ops"][0][0] == "jvp__ kernel"
    assert breakdown["device_ops"][2][0] == "fusion bf16[20,1024,3072]"
    # the device waits for the window's first dispatch, under train_batch
    assert breakdown["idle_gaps"] == [
        ["train_batch", pytest.approx(0.006021596, rel=1e-6)]]
    assert sum(v for _, v in breakdown["idle_gaps"]) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
