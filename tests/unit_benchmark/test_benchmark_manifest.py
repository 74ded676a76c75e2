"""BENCHMARK.json against its contract, and every file it names found
by name."""
import importlib
import json
import os
import re

import pytest

from benchmark import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFEST = manifest.load_manifest()
CELLS = [c["name"] for c in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert isinstance(MANIFEST["run_seconds"], int)
    assert MANIFEST["paths"] == ["benchmark", "tests/unit_benchmark"]
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    assert all(c["chips"] == 1 for c in MANIFEST["workloads"])
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])


@pytest.mark.parametrize("entry", MANIFEST["configs"] +
                         MANIFEST["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_bounds(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_moves_a_metric_its_cells_report(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    moved = [m for m in MANIFEST["end_to_end"]
             if m["name"] == metric["moves"]]
    assert len(moved) == 1
    reported_in = set(moved[0].get("workloads", CELLS))
    assert set(metric.get("workloads", CELLS)) <= reported_in
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    end = [m["name"] for m in manifest.cell_metrics(MANIFEST, cell,
                                                    "end_to_end")]
    assert "setup_s" in end and len(end) >= 2
    assert manifest.cell_metrics(MANIFEST, cell, "per_layer")


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda c: c["name"])
def test_cell_files_and_plugins_are_found_by_name(cell):
    assert cell["name"] == cell["config"] + "." + cell["traffic"]
    config = manifest.load_config(MANIFEST, cell["config"])
    workload = manifest.load_workload(cell["name"])
    assert config["chips"] == cell["chips"]
    family = manifest.plugin("models", config["family"])
    runner = manifest.plugin("runners", workload["runner"])
    traffic = manifest.plugin("traffic", workload["traffic"]["generator"])
    assert callable(runner.run)
    assert hasattr(family, "build_train_engine")
    assert traffic.__name__.startswith("benchmark.traffic.")
    for metric in manifest.cell_metrics(MANIFEST, cell["name"],
                                        "per_layer"):
        params = manifest.load_layer_metric(metric["name"])
        reader = manifest.plugin("layer_metrics", params["reader"])
        assert callable(reader.read)


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda e: e["name"])
def test_config_file_states_source_and_cuts(entry):
    assert entry["file"].startswith("benchmark/configs/")
    config = manifest.load_config(MANIFEST, entry["name"])
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"] == []
    assert config["model"] == {
        "n_layer": 24, "n_embd": 1024, "n_head": 16, "n_positions": 1024,
        "n_ctx": 1024, "vocab_size": 50257, "padded_vocab_size": 50304,
        "activation_function": "gelu_new", "layer_norm_epsilon": 1e-5}
    assert "assumed" in config and "check" in config


def test_no_table_in_code_lists_the_cells():
    """The harness finds cells, configurations and per-layer metrics by
    the names in the data: none of those names appears in its code. (A
    runner does name the end-to-end quantities it measures.)"""
    names = CELLS + [c["name"] for c in MANIFEST["configs"]] + \
        [m["name"] for m in MANIFEST["per_layer"]]
    for root, _, files in os.walk(manifest.PACKAGE_DIR):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    text = f.read()
                for n in names:
                    assert '"{}"'.format(n) not in text, (name, n)


def test_peaks_table_has_no_cpu_row_and_raises_on_unknown():
    from benchmark import peaks
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError):
            peaks.peaks_for(kind)


def test_importing_the_command_touches_no_backend():
    import jax._src.xla_bridge as xb
    before = set(xb._backends)
    importlib.import_module("benchmark.run")
    assert set(xb._backends) == before


@pytest.mark.parametrize("name, stem", [
    ("device_idle_share.train", "device_idle_share"),
    ("device_idle_share.a-later-cell", "device_idle_share"),
    ("batch_occupancy.docs", "batch_occupancy"),
    ("prefill_wall_share.docs", "prefill_wall_share.docs")])
def test_split_metric_reads_its_own_file_or_the_quantitys(name, stem):
    """``<quantity>.<suffix>`` has a file of its own or shares
    ``<quantity>.json``: a later cell needs no copy of it."""
    with open(os.path.join(manifest.PACKAGE_DIR, "layer_metrics",
                           stem + ".json")) as f:
        assert manifest.load_layer_metric(name) == json.load(f)
