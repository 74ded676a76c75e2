"""BENCHMARK.json and the files it names, found by name.

A cell names a config and a traffic mix; the config file names its model
family, the workload file its runner and traffic generator, a per-layer
metric file its reader. Each is a module or data file under this
package, looked up by that name, so a later PR adds files and entries
and edits none.
"""
import importlib
import json
import os

PACKAGE_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(PACKAGE_DIR)


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest():
    return _read_json(os.path.join(REPO_DIR, "BENCHMARK.json"))


def find_cell(manifest, name):
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError("no workload {!r} in BENCHMARK.json (has: {})".format(
        name, ", ".join(c["name"] for c in manifest["workloads"])))


def load_config(manifest, name):
    for entry in manifest["configs"]:
        if entry["name"] == name:
            return _read_json(os.path.join(REPO_DIR, entry["file"]))
    raise KeyError("no config {!r} in BENCHMARK.json".format(name))


def load_workload(cell_name):
    return _read_json(os.path.join(PACKAGE_DIR, "workloads",
                                   cell_name + ".json"))


def load_layer_metric(metric_name):
    """The metric's parameter file: ``layer_metrics/<name>.json``, or,
    for a metric split by cell (``<quantity>.<suffix>``), the
    quantity's one file ``layer_metrics/<quantity>.json``."""
    directory = os.path.join(PACKAGE_DIR, "layer_metrics")
    path = os.path.join(directory, metric_name + ".json")
    if not os.path.exists(path) and "." in metric_name:
        path = os.path.join(directory,
                            metric_name.rsplit(".", 1)[0] + ".json")
    return _read_json(path)


def plugin(kind, name):
    """The module ``benchmark.<kind>.<name>``: kind is one of runners,
    traffic, models, layer_metrics."""
    return importlib.import_module("{}.{}.{}".format(__package__, kind,
                                                     name))


def cell_metrics(manifest, cell_name, section):
    """The metrics of ``section`` (end_to_end | per_layer) that this
    cell reports: those that list it, or list nothing."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell_name in m["workloads"]]
