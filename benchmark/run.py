"""``python -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one cell, once, in this process, on the chips this
machine holds. Prints each number the output check compared beside its
limit, then one JSON line (see BENCHMARK.json's contract): with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, the device's busy seconds and a breakdown.
"""
import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from . import manifest, peaks, spans  # noqa: E402

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
MISS = 1e12      # reported for a percentile that fell on a missed request


def require_devices(chips):
    """The TPU devices of this machine, or exit non-zero with one line:
    the benchmark measures the chip and has no other way to run."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit("benchmark: needs a TPU, jax found platform {!r}".format(
            platform))
    if len(devices) < chips:
        sys.exit("benchmark: the cell needs {} chips, jax found {}".format(
            chips, len(devices)))
    try:
        return devices, peaks.peaks_for(devices[0].device_kind)
    except KeyError as err:
        sys.exit("benchmark: {}".format(err.args[0]))


class Run:
    """What one run carries from the command line to the runner, the
    readers and the result line."""

    def __init__(self, args, manifest_, cell, config, workload,
                 peaks_=None):
        self.seed, self.trace = args.seed, bool(args.trace)
        self.seconds, self.trace_dir = args.seconds, None
        self.manifest, self.cell = manifest_, cell
        self.config, self.workload = config, workload
        self.chips = cell["chips"]
        self.peaks = peaks_        # the device's row of peaks.json
        self.spans = spans.SpanRecorder(annotate=self.trace)
        self.counters = {}
        self.compile_times, self.cache_misses = [], 0
        self.t_open = self.t_close = None
        self.memory_peak_bytes = None

    def log(self, message):
        print("benchmark: " + message, flush=True)

    def window_seconds(self):
        """A traced run measures a short window (traces are large and
        tracing slows the host); its numbers are per-layer only."""
        if self.trace:
            traced = min(self.seconds, self.workload["trace_seconds"])
            self.log("--trace 1 measures {:g} s of the {:g} s asked for "
                     "(the workload's trace_seconds)".format(
                         traced, self.seconds))
            return traced
        return self.seconds

    def open_window(self):
        if self.trace:
            import jax
            self.trace_dir = tempfile.mkdtemp(prefix="benchmark_trace_")
            jax.profiler.start_trace(self.trace_dir)
        self.t_open = time.perf_counter()

    def close_window(self):
        self.t_close = time.perf_counter()
        if self.trace:
            import jax
            jax.profiler.stop_trace()

    def note_memory(self):
        import jax
        self.memory_peak_bytes = max(
            d.memory_stats()["peak_bytes_in_use"]
            for d in jax.devices()[:self.chips])

    def compiles_in_window(self):
        return sum(self.t_open <= t <= self.t_close
                   for t in self.compile_times)

    def on_duration(self, event, duration, **kwargs):
        if event == _COMPILE_EVENT:
            self.compile_times.append(time.perf_counter())
            if duration >= 1.0:
                self.log("compiled or loaded {} in {:.1f} s".format(
                    kwargs.get("fun_name"), duration))

    def on_event(self, event, **_):
        if event == _CACHE_MISS_EVENT:
            self.cache_misses += 1

    def discard_trace(self):
        shutil.rmtree(self.trace_dir, ignore_errors=True)


def layer_metrics(run, outcome):
    """The cell's per-layer metrics, each by its own reader; a reader
    that finds nothing to read returns None and the metric is left
    out."""
    from . import trace
    red = run.reduction = trace.reduce_trace(run.trace_dir,
                                             run.spans.names())
    run.log("trace: {} device operations and {} program runs over "
            "{:.6f} s, {} host spans".format(
                sum(map(len, red.device_events.values())),
                sum(map(len, red.device_modules.values())),
                red.window_s, len(red.host_spans)))
    run.end_to_end = outcome["end_to_end"]
    values = {}
    for metric in manifest.cell_metrics(run.manifest, run.cell["name"],
                                        "per_layer"):
        params = manifest.load_layer_metric(metric["name"])
        reader = manifest.plugin("layer_metrics", params["reader"])
        value = reader.read(run, params)
        if value is not None:
            values[metric["name"]] = {"value": value,
                                      "unit": metric["unit"]}
    return values


def main(argv=None):
    parser = argparse.ArgumentParser(prog="benchmark.run")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    manifest_ = manifest.load_manifest()
    cell = manifest.find_cell(manifest_, args.workload)
    config = manifest.load_config(manifest_, cell["config"])
    workload = manifest.load_workload(cell["name"])
    devices, device_peaks = require_devices(cell["chips"])

    import jax
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    run = Run(args, manifest_, cell, config, workload, device_peaks)
    run.log("compile cache at {}".format(enable_compile_cache()))
    jax.monitoring.register_event_duration_secs_listener(run.on_duration)
    jax.monitoring.register_event_listener(run.on_event)

    outcome = manifest.plugin("runners", workload["runner"]).run(run)
    gc.collect()

    setup_s = run.t_open - _PROCESS_START
    in_window = run.compiles_in_window()
    run.log("setup_s={:.3f} window_s={:.3f} programs compiled or loaded="
            "{} (cache misses {}) of them inside the window={} spans={}"
            .format(setup_s, run.t_close - run.t_open,
                    len(run.compile_times), run.cache_misses, in_window,
                    {n: len(run.spans.durations(n))
                     for n in run.spans.names()}))
    before = {}
    for name, start, end in run.spans.spans:
        if end <= run.t_open:
            before[name] = round(before.get(name, 0.0) + end - start, 2)
    run.log("set-up spans (s): " + json.dumps(before))
    run.log("counters: " + json.dumps(run.counters))
    correct = in_window == 0
    for name, (value, limit) in sorted(outcome["checks"].items()):
        ok = value <= limit        # false for a NaN
        correct = correct and ok
        run.log("check {}: value={!r} limit={!r} {}".format(
            name, value, limit, "ok" if ok else "NOT CORRECT"))
    run.log("check compiles_in_window: value={} limit=0 {}".format(
        in_window, "ok" if in_window == 0 else "NOT CORRECT"))

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": bool(correct), "attempted": outcome["attempted"],
              "failed": outcome["failed"]}
    if run.trace:
        result["metrics"] = layer_metrics(run, outcome)
        device["busy_s"] = run.reduction.busy_s
        device["window_s"] = run.reduction.window_s
        result["breakdown"] = run.reduction.breakdown()
        run.discard_trace()
    else:
        reported = dict(outcome["end_to_end"], setup_s=setup_s)
        # a tail that ends in a miss is infinite; JSON has no infinity
        result["metrics"] = {
            m["name"]: {"value": min(reported[m["name"]], MISS),
                        "unit": m["unit"]}
            for m in manifest.cell_metrics(manifest_, cell["name"],
                                           "end_to_end")}
    result["device"] = device
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
