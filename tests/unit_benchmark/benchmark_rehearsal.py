"""CPU rehearsal of the runners at a tiny size, kernels interpreted.

Run as a script from a COPY of the benchmark (``BENCHMARK.json`` +
``benchmark/``) to which ``tiny/`` has added one more configuration,
workload and per-layer metric file for each runner: nothing of the
benchmark is edited, the new cells are found by name. It calls the
runners' own functions on a ``Run`` that differs from the command's in
one thing, the device's memory statistics, which the CPU does not keep.
Prints one JSON line per run. Not a way to run the benchmark: the
command itself refuses anything but a TPU.
"""
import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")


def add_tiny_files(copy_dir, tiny_dir):
    """Drop the tiny cells' files into the copy and name them in its
    BENCHMARK.json."""
    import shutil
    with open(os.path.join(tiny_dir, "manifest_entries.json")) as f:
        extra = json.load(f)
    path = os.path.join(copy_dir, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    for section, entries in extra.items():
        manifest[section].extend(entries)
    with open(path, "w") as f:
        json.dump(manifest, f)
    for kind in ("configs", "workloads", "layer_metrics"):
        src = os.path.join(tiny_dir, kind)
        for name in os.listdir(src):
            shutil.copy(os.path.join(src, name),
                        os.path.join(copy_dir, "benchmark", kind, name))


def rehearse(cell_name, seed, seconds, trace):
    from benchmark import manifest, run as command

    class CpuRun(command.Run):
        def note_memory(self):
            self.memory_peak_bytes = 0

    manifest_ = manifest.load_manifest()
    cell = manifest.find_cell(manifest_, cell_name)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    run = CpuRun(args, manifest_, cell,
                 manifest.load_config(manifest_, cell["config"]),
                 manifest.load_workload(cell_name))
    import jax
    jax.monitoring.register_event_duration_secs_listener(run.on_duration)
    outcome = manifest.plugin("runners", run.workload["runner"]).run(run)
    result = {"cell": cell_name, "trace": trace,
              "attempted": outcome["attempted"],
              "failed": outcome["failed"],
              "end_to_end": outcome["end_to_end"],
              "checks": {k: list(v) for k, v in outcome["checks"].items()},
              "compiles_in_window": run.compiles_in_window(),
              "counters": run.counters, "spans": run.spans.names()}
    if trace:
        result["per_layer"] = command.layer_metrics(run, outcome)
        result["breakdown"] = run.reduction.breakdown()
        run.discard_trace()
    return result


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    for spec in sys.argv[1:]:
        cell, trace = spec.rsplit(":", 1)
        try:
            result = rehearse(cell, 7, 2.0, int(trace))
        except RuntimeError as err:     # a runner that refuses its run
            result = {"cell": cell, "trace": int(trace),
                      "error": str(err)}
        print(json.dumps(result), flush=True)
