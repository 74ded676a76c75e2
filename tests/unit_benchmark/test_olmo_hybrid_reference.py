"""The Olmo Hybrid family of the benchmark at a tiny size on the CPU:
the configuration file against the catalog row, the reference against
the program's own recipe, the serving check's controls, the counts of
bytes and operations, the new reader on a trace from before its span,
and the serve runner end to end on a tiny cell dropped into a copy of
the benchmark."""
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import benchmark_rehearsal
from benchmark import manifest
from benchmark.layer_metrics import (gated_delta_step_roofline,
                                     kernel_busy_share, program_spans)
from benchmark.models import jamba_controls, olmo_hybrid
from benchmark.models import olmo_hybrid_reference as reference

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY_DIR = os.path.join(HERE, "tiny_olmo_hybrid")
with open(os.path.join(TINY_DIR, "configs", "tiny-olmo-hybrid.json")) as f:
    TINY = json.load(f)

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# the catalog row Olmo-Hybrid-7B's `config`, as read from the model's
# public config.json
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
CUT = dict(PUBLISHED, num_hidden_layers=16, layer_types=PERIOD * 4)
MANIFEST = manifest.load_manifest()
ENTRY = [c for c in MANIFEST["configs"] if manifest.load_config(
    MANIFEST, c["name"])["family"] == "olmo_hybrid"]
CELL = "olmo-hybrid-7b-serve.evals"


def test_the_configuration_is_the_catalog_rows_cut_in_depth_only():
    assert len(ENTRY) == 1
    entry = ENTRY[0]
    config = manifest.load_config(MANIFEST, entry["name"])
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/" \
        "config.json"
    assert entry["reduced"] == config["reduced"] == \
        ["num_hidden_layers", "layer_types"]
    assert config["published"]["num_hidden_layers"] == 32
    # every key of the row at the top level of the file AND in `model`;
    # all but the depth as published: no width, head count or row cut
    assert {k: config[k] for k in CUT} == CUT
    assert config["model"] == dict(CUT, padded_vocab_size=100352)
    assert {k for k in PUBLISHED if CUT[k] != PUBLISHED[k]} == \
        set(config["reduced"])
    assert reference.param_count(PUBLISHED) == 7430870688
    assert reference.param_count(config["model"]) == 4100788944
    assert config["memory"]["weights_bytes"] == 2 * 4100788944
    assert config["memory"]["state_bytes_a_slot"] == 27371520
    assert {"block_norm_order", "qk_norm_span", "positional_encoding",
            "output_gate", "convolution", "l2_norm", "decay",
            "weights"} <= set(config["assumed"])
    assert config["family"] == "olmo_hybrid" and config["chips"] == 1
    inference = config["inference"]
    assert inference["max_batch_size"] == 64
    assert inference["prefill_buckets"] == [128, 256, 512]
    assert (inference["kv_block_size"], inference["max_seq_len"],
            inference["max_new_tokens"]) == (16, 3072, 2048)
    assert {"why", "prefill_logits_rel_rms", "decode_logits_rel_rms",
            "served_token_deficit", "decode_steps"} <= set(config["check"])
    assert config["check"]["decode_steps"] == 128
    cells = [c for c in MANIFEST["workloads"]
             if c["config"] == entry["name"]]
    assert [(c["name"], c["traffic"], c["chips"]) for c in cells] == \
        [(CELL, "evals", 1)]
    workload = manifest.load_workload(CELL)
    traffic = workload["traffic"]
    assert traffic["generator"] == "requests_balanced"
    assert (workload["latency"], workload["trace_seconds"]) == (False, 5)
    assert {k: traffic["arrivals"][k] for k in ("process", "queued")} == \
        {"process": "backlog", "queued": 3000}
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32,
        "max": 1024}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.6, "min": 128,
        "max": 2048}


def test_the_cells_metrics_are_the_issues():
    names = {m["name"] for m in manifest.cell_metrics(MANIFEST, CELL,
                                                      "per_layer")}
    assert {"batch_occupancy.evals", "kv_pool_live_share.evals",
            "device_idle_share.evals", "sched_host_ms_mean.evals",
            "step_idle_before_dispatch.evals", "step_idle_in_flight.evals",
            "step_idle_after_fetch.evals", "prefill_padding_share.evals",
            "serve_mfu.evals", "gated_delta_step_roofline.evals",
            "gated_delta_step_busy_share.evals",
            "paged_attention_roofline.evals",
            "paged_attention_busy_share.evals"} <= names
    assert {m["name"] for m in manifest.cell_metrics(
        MANIFEST, CELL, "end_to_end")} == {"serve_tokens_per_s", "setup_s"}
    for name in names:
        params = manifest.load_layer_metric(name)
        assert manifest.plugin("layer_metrics", params["reader"]).read


def test_lengths_stay_inside_the_mix_and_the_serving_window():
    from benchmark.traffic import requests_balanced
    config = manifest.load_config(MANIFEST, ENTRY[0]["name"])
    mix = manifest.load_workload(CELL)["traffic"]
    vocab = config["model"]["padded_vocab_size"]
    due, prompts, outputs = requests_balanced.generate(
        mix, 3000000019, 40.0, vocab, cycle_s=51.0)
    lens = np.array(list(map(len, prompts)))
    assert len(due) == 3000 and not due.any()
    assert lens.min() >= 32 and lens.max() <= 1024
    assert outputs.min() >= 128 and outputs.max() <= 2048
    assert (lens + outputs).max() <= config["inference"]["max_seq_len"]
    assert outputs.max() <= config["inference"]["max_new_tokens"]
    assert 320 < lens.mean() < 360
    assert outputs.mean() == pytest.approx(610, abs=15)
    assert all(p.min() >= 0 and p.max() < vocab for p in prompts[:50])


def test_the_family_trains_nothing():
    with pytest.raises(NotImplementedError, match="served, not trained"):
        olmo_hybrid.build_train_engine(TINY, 0)


def test_a_checkout_without_the_model_says_so_and_exits(monkeypatch):
    import builtins
    real = builtins.__import__

    def no_model(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "deepspeed_tpu.models" and "olmo_hybrid" in fromlist:
            raise ImportError("no such module")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.delitem(sys.modules, "deepspeed_tpu.models.olmo_hybrid",
                        raising=False)
    monkeypatch.setattr(builtins, "__import__", no_model)
    with pytest.raises(SystemExit, match="no models/olmo_hybrid.py"):
        olmo_hybrid.build_serve_engine(TINY, 0)


@pytest.mark.parametrize("layer", [0, 3], ids=["linear", "full"])
def test_weights_are_the_programs_own_recipe(layer):
    from deepspeed_tpu.models import olmo_hybrid as program
    model = TINY["model"]
    cfg = program.config_from_hf(model, dtype=jnp.float32)
    ref = reference.draw_layer(model, 9, layer)
    got = program.init_layer(cfg, 9, layer)
    if layer == 0:
        # the program holds q, k, v as one matrix, beta and the decay
        # as one, and the taps channels minor
        ref = dict(ref, qkv=jnp.concatenate([ref.pop("q"), ref.pop("k"),
                                             ref.pop("v")], axis=1),
                   ba=jnp.concatenate([ref.pop("b"), ref.pop("a")], axis=1),
                   conv_w=ref["conv_w"].T)
    assert set(ref) == set(got)
    for name in ref:
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(ref[name]))
    params = program.init_params(cfg, 9)
    np.testing.assert_array_equal(
        np.asarray(params["embed"]),
        np.asarray(reference.draw_table(model, 9, 0, 512, 64)))
    np.testing.assert_array_equal(
        np.asarray(params["head"]),
        np.asarray(reference.draw_table(model, 9, 1, 64, 512)))
    # the layer's published initialisation, not a forgetful one
    if layer == 0:
        dt = np.asarray(jax.nn.softplus(ref["dt_bias"]))
        assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
        A = np.exp(np.asarray(ref["A_log"]))
        assert 0 < A.min() and A.max() <= 16


@pytest.fixture(scope="module")
def sound_and_controls():
    """One tiny engine, served and checked; then every control."""
    seed = 3000000019                 # more than 32 signed bits hold
    engine = olmo_hybrid.build_serve_engine(TINY, seed)
    served = jamba_controls.served_requests(TINY, seed, engine, answers=12)
    got = olmo_hybrid.serve_engine_outputs(TINY, seed, engine)
    sequences, lens = olmo_hybrid.serve_check_inputs(TINY, seed)
    ref = olmo_hybrid.reference_logits(TINY, seed, sequences, lens)
    out = {"sound": olmo_hybrid.serve_check(TINY, seed, got, served,
                                            ref=ref),
           "bfloat16_matmuls": olmo_hybrid.serve_check(
               TINY, seed, rounding="bfloat16", ref=ref)}
    for control in olmo_hybrid.CONTROLS:
        out[control] = olmo_hybrid.serve_control(TINY, seed, control,
                                                 served, ref=ref)
    return out


def test_the_sound_engine_is_inside_every_limit(sound_and_controls):
    checks = sound_and_controls["sound"]
    assert set(checks) == {"prefill_logits_rel_rms",
                           "decode_logits_rel_rms", "served_token_deficit"}
    assert all(value <= limit for value, limit in checks.values())


@pytest.mark.parametrize("control",
                         olmo_hybrid.CONTROLS + ("bfloat16_matmuls",))
def test_each_control_reads_beyond_a_limit(sound_and_controls, control):
    """fp8 matmul operands, the state rounded to bfloat16 every step,
    beta without its 2, no decay, a request begun from the previous
    tenant's state, a second chunk begun from a zero state or with zero
    tails, another request's prompt (and, the tiny configuration stating
    float32, bfloat16 matmuls): not correct, by one of the check's
    limits."""
    checks = sound_and_controls[control]
    assert any(not value <= limit for value, limit in checks.values()), \
        checks


def test_the_check_crosses_a_chunks_end_twice():
    sequences, lens = olmo_hybrid.serve_check_inputs(TINY, 5)
    buckets = TINY["inference"]["prefill_buckets"]
    page = TINY["inference"]["kv_block_size"]
    assert len(lens) == len(buckets) + 3
    assert all(lo < n <= hi for n, lo, hi in
               zip(lens, [0] + buckets[:-1], buckets))
    assert lens[len(buckets)] <= page                   # a single page
    assert buckets[-1] < lens[-2] < 2 * buckets[-1]     # two chunks
    assert 2 * buckets[-1] < lens[-1] < 3 * buckets[-1]  # three
    assert (lens[-1] - 2 * buckets[-1]) % buckets[0] != 0     # padded
    assert [len(s) - n for s, n in zip(sequences, lens)] == \
        [TINY["check"]["decode_steps"]] * len(lens)
    assert len(lens) <= TINY["inference"]["max_batch_size"]


def test_no_request_to_look_at_is_not_correct():
    checks = olmo_hybrid.serve_check(TINY, 5, rounding="bfloat16",
                                     served=[])
    value, limit = checks["served_token_deficit"]
    assert not value <= limit


def test_counts_of_bytes_and_operations():
    model = manifest.load_config(MANIFEST, ENTRY[0]["name"])["model"]
    # a slot's step: 96 x 5,760 float32 read and written in 12 layers
    assert olmo_hybrid.gated_delta_step_bytes(model, 1) == \
        12 * 2 * 96 * 5760 * 4
    # the issue's arithmetic: 64 slots, 3.4 GB a step
    assert olmo_hybrid.gated_delta_step_bytes(model, 64) * 1e-9 == \
        pytest.approx(3.40, abs=0.01)
    # a page of 16 tokens: keys and values of 3,840 in 4 full layers
    assert olmo_hybrid.paged_attention_bytes(model, 16, 1) == \
        4 * 2 * 16 * 3840 * 2 == 983040
    mlp = 3 * 3840 * 11008
    linear = 3840 * 11520 + 2 * 3840 * 5760 + 2 * 3840 * 30
    full = 4 * 3840 * 3840
    weights = 16 * mlp + 12 * linear + 4 * full
    assert olmo_hybrid.serve_flops_per_token(model) == 2 * weights
    # every parameter but embedding, head, norms, taps, A and dt
    rest = 4100788944 - 2 * 100352 * 3840
    assert 0.999 * rest < weights < rest


def _run_on(trace_file):
    from benchmark import trace
    events = program_spans.from_trace(trace_file)
    run = types.SimpleNamespace(
        trace_dir=trace_file, counters={"active_slot_steps": 10,
                                        "steps": 5},
        reduction=trace.reduce_trace(trace_file, []), log=lambda m: None,
        config=dict(TINY, model=TINY["model"]), peaks={
            "hbm_bytes_per_s": 819e9})
    run.program_spans = program_spans.ProgramSpans(events, run.reduction)
    return run


def test_new_readers_find_nothing_in_a_parents_trace_and_do_not_raise():
    """A trace of a program with no ``gdn.advanced`` span and no
    ``gated_delta_step`` kernel: the new reader and the new parameter
    files return None, and the line leaves the metrics out."""
    run = _run_on(os.path.join(HERE, "fixtures_program_spans",
                               "serve_chat_steps.xplane.pb"))
    assert gated_delta_step_roofline.advanced_slots(run,
                                                    "gdn.advanced") == []
    for name, reader in (
            ("gated_delta_step_roofline.evals", gated_delta_step_roofline),
            ("gated_delta_step_busy_share.evals", kernel_busy_share),
            ("paged_attention_busy_share.evals", kernel_busy_share)):
        assert reader.read(run, manifest.load_layer_metric(name)) is None


# ------------------------------------------------- the runner, end to end
@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    copy = tmp_path_factory.mktemp("benchmark_copy_olmo_hybrid")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), copy)
    shutil.copytree(os.path.join(REPO, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    benchmark_rehearsal.add_tiny_files(str(copy), TINY_DIR)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(copy), REPO]),
               TMPDIR=str(copy))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "benchmark_rehearsal.py"),
         "tiny-olmo-hybrid.evals:0", "tiny-olmo-hybrid.evals:1"],
        cwd=str(copy), env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = {}
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            r = json.loads(line)
            out[r["trace"]] = r
    return out


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_runner_rehearsal_on_a_tiny_olmo_hybrid_cell(rehearsal,
                                                           trace):
    r = rehearsal[trace]
    assert "error" not in r, r
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert set(r["checks"]) == {"prefill_logits_rel_rms",
                                "decode_logits_rel_rms",
                                "served_token_deficit"}
    assert all(v <= limit for v, limit in r["checks"].values())
    assert r["end_to_end"]["serve_tokens_per_s"] > 0
    assert 0 < r["counters"]["active_slot_steps"] <= \
        r["counters"]["slot_steps"]


def test_padding_share_is_read_and_the_cpu_trace_has_no_kernel(rehearsal):
    """The traced rehearsal reads the chunk spans' padding; the state
    kernel's share of its roofline has nothing to read on the CPU (no
    device plane: XLA's einsum oracle ran) and is left out."""
    per_layer = rehearsal[1]["per_layer"]
    value = per_layer["tiny_gdn_padding_share"]
    assert 0 < value["value"] < 100 and value["unit"] == "%"
    assert "tiny_gdn_step_roofline" not in per_layer
