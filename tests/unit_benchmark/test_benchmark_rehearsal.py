"""All three runners end to end at a tiny size on the CPU, kernels
interpreted, from a temporary COPY of the benchmark to which one more
configuration, workload and per-layer metric file were added for each
runner: they run with no edit to any file of the benchmark."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import benchmark_rehearsal

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
RUNS = ["tiny-train.seq64:0", "tiny-train.seq64:1", "tiny-serve.chat:0",
        "tiny-serve.chat:1", "tiny-serve.docs:0"]
DRAINED = "tiny-serve.drained:0"


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    copy = tmp_path_factory.mktemp("benchmark_copy")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), copy)
    shutil.copytree(os.path.join(REPO, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    benchmark_rehearsal.add_tiny_files(str(copy),
                                       os.path.join(HERE, "tiny"))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(copy), REPO]),
               TMPDIR=str(copy))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "benchmark_rehearsal.py")] +
        RUNS + [DRAINED], cwd=str(copy), env=env, capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = {}
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            r = json.loads(line)
            out["{}:{}".format(r["cell"], r["trace"])] = r
    return out


@pytest.mark.parametrize("spec", RUNS)
def test_runner_rehearsal(results, spec):
    r = results[spec]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert r["checks"] and all(v <= limit
                               for v, limit in r["checks"].values())
    assert all(v > 0 for v in r["end_to_end"].values())
    if spec.startswith("tiny-train"):
        assert {"train_batch", "fence", "input.make_batch",
                "train_step"} <= set(r["spans"])
        assert r["counters"]["steps"] == r["attempted"]
    else:
        assert "scheduler.step" in r["spans"]
        assert 0 < r["counters"]["active_slot_steps"] <= \
            r["counters"]["slot_steps"]
        assert r["counters"]["live_kv_pages_read"] > 0
    if spec.endswith(".chat:0"):
        assert {"ttft_p90_ms", "tpot_p90_ms"} <= set(r["end_to_end"])
        assert "loadgen.wait" in r["spans"]
        assert "backlog_left" not in r["counters"]
    if spec.endswith(".docs:0"):
        # a backlog that outlasts the window says how much of it is left
        assert 0 < r["counters"]["backlog_left"] < 6000


def test_a_backlog_that_runs_dry_refuses_its_run(results):
    """6 requests are gone long before the window closes: no result,
    and the error names the rate that would have kept the queue full."""
    error = results[DRAINED]["error"]
    assert error.startswith("the backlog ran dry: 0 of 6 requests")
    assert "tokens/s: raise arrivals.queued" in error


@pytest.mark.parametrize("spec, metric", [
    ("tiny-train.seq64:1", "tiny_dispatch_ms_p90"),
    ("tiny-serve.chat:1", "tiny_occupancy")])
def test_dropped_in_layer_metric_is_read(results, spec, metric):
    value = results[spec]["per_layer"][metric]
    assert value["value"] > 0 and value["unit"] in ("ms", "%")
    # no TPU plane in a CPU trace: the device readers find nothing to
    # read and their metrics are left out, not reported as 0
    assert results[spec]["breakdown"] == {"device_ops": [],
                                          "idle_gaps": []}
