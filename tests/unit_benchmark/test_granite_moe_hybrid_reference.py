"""The Granite-MoE-Hybrid family of the benchmark at a tiny size on the
CPU: the configuration file against the catalog row, the reference
against the program's own recipe, the serving check's controls, the
counts of bytes and operations, the new reader on a trace from before
its span, and the serve runner end to end on a tiny cell dropped into a
copy of the benchmark."""
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
                                     kernel_busy_share, program_spans,
                                     ssd_step_roofline)
from benchmark.models import granite_moe_hybrid as family
from benchmark.models import granite_moe_hybrid_reference as reference
from benchmark.models import jamba_controls

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY_DIR = os.path.join(HERE, "tiny_granite_moe_hybrid")
with open(os.path.join(TINY_DIR, "configs",
                       "tiny-granite-moe-hybrid.json")) as f:
    TINY = json.load(f)

PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# the catalog row granite-4.0-h-small's `config`, as read from the
# model's public config.json
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": PERIOD * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352}
CUT = dict(PUBLISHED, num_hidden_layers=10, layer_types=PERIOD,
           num_local_experts=36, vocab_size=50176)
SHARE = {"router_num_experts": 72, "experts_held": [0, 36],
         "padded_vocab_size": 50176}
MANIFEST = manifest.load_manifest()
ENTRY = [c for c in MANIFEST["configs"] if manifest.load_config(
    MANIFEST, c["name"])["family"] == "granite_moe_hybrid"]
CELL = "granite-4.0-h-small-serve.support"


def test_the_configuration_is_the_catalog_rows_cut_to_a_chips_share():
    assert len(ENTRY) == 1
    entry = ENTRY[0]
    config = manifest.load_config(MANIFEST, entry["name"])
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/" \
        "main/config.json"
    assert entry["reduced"] == config["reduced"] == \
        ["num_hidden_layers", "layer_types", "num_local_experts",
         "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["num_local_experts"] == 72
    assert config["published"]["vocab_size"] == 100352
    # every key of the row at the top level of the file AND in `model`;
    # all but the cuts as published: no width, no head count, the
    # router's 72 outputs and its 10 a token as they are
    assert {k: config[k] for k in CUT} == CUT
    assert config["model"] == dict(CUT, **SHARE)
    assert {k for k in PUBLISHED if CUT[k] != PUBLISHED[k]} == \
        set(config["reduced"])
    whole = dict(PUBLISHED, router_num_experts=72)
    assert reference.param_count(whole, held=False) == 32207337984
    assert reference.param_count(config["model"]) == 4757211776
    assert config["memory"]["weights_bytes"] == 2 * 4757211776
    assert config["memory"]["state_bytes_a_slot"] == 38204928
    assert config["inference"]["max_batch_size"] == 96
    assert config["memory"]["page_bytes"] == 65536
    assert {"intermediate_size", "convolution", "time_step_limit",
            "gated_norm", "router", "weights"} <= set(config["assumed"])
    assert "experts 0-35" in config["deployment"] and \
        "rows 0-50,175" in config["deployment"]
    assert config["family"] == "granite_moe_hybrid" and config["chips"] == 1
    inference = config["inference"]
    assert inference["prefill_buckets"] == [256, 512, 1024, 2048]
    assert (inference["kv_block_size"], inference["max_seq_len"],
            inference["max_new_tokens"]) == (16, 10240, 1536)
    assert {"why", "prefill_logits_rel_rms", "decode_logits_rel_rms",
            "decode_logits_rel_err_p10", "served_token_deficit",
            "decode_steps"} <= set(config["check"])
    assert config["check"]["decode_steps"] == 128
    cells = [c for c in MANIFEST["workloads"]
             if c["config"] == entry["name"]]
    assert [(c["name"], c["traffic"], c["chips"]) for c in cells] == \
        [(CELL, "support", 1)]
    workload = manifest.load_workload(CELL)
    traffic = workload["traffic"]
    assert traffic["generator"] == "requests_balanced"
    assert (workload["latency"], workload["trace_seconds"]) == (False, 5)
    assert {k: traffic["arrivals"][k] for k in ("process", "queued")} == \
        {"process": "backlog", "queued": 3000}
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.8, "min": 128,
        "max": 8192}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.6, "min": 64,
        "max": 1536}


# The benchmark holds at most 128 per-layer entries and had 124: four
# are new here, and the grouped matmul's roofline is the accepted
# unsuffixed entry with this cell appended to its list (ISSUE 58 named
# twenty; CHANGES.md says which went and why).
NEW = ("serve_mfu.support", "ssd_step_roofline.support",
       "ssd_step_busy_share.support", "ssd_chunk_busy_share.support")
SETUP = ("setup_import_s", "setup_engine_s", "setup_trace_lower_s",
         "setup_compile_load_s", "setup_first_run_s",
         "setup_programs_compiled")


def test_the_cells_metrics_fit_the_benchmarks_room():
    assert len(MANIFEST["per_layer"]) <= 128
    names = {m["name"] for m in manifest.cell_metrics(MANIFEST, CELL,
                                                      "per_layer")}
    assert names == set(NEW) | {"moe_gmm_roofline"} | set(SETUP)
    assert [m["name"] for m in MANIFEST["per_layer"][-4:]] == list(NEW)
    assert {m["name"] for m in manifest.cell_metrics(
        MANIFEST, CELL, "end_to_end")} == {"serve_tokens_per_s", "setup_s"}
    for name in names:
        params = manifest.load_layer_metric(name)
        assert manifest.plugin("layer_metrics", params["reader"]).read
    # the accepted entry as it was, its list one cell longer
    gmm, = [m for m in MANIFEST["per_layer"]
            if m["name"] == "moe_gmm_roofline"]
    assert gmm["workloads"] == ["lfm2-8b-a1b-serve.extract", CELL]
    # a share of a roofline or a peak is a percentage
    for m in manifest.cell_metrics(MANIFEST, CELL, "per_layer"):
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert (m["unit"], m["better"], m["moves"]) == \
                ("%", "higher", "serve_tokens_per_s")


@pytest.mark.parametrize("metric", [
    m for m in MANIFEST["per_layer"] if manifest.load_layer_metric(
        m["name"])["reader"] == "scope_busy_share"],
    ids=lambda m: m["name"])
def test_each_scope_metric_of_any_cell_names_scopes_of_the_vocabulary(metric):
    """What tests/unit_benchmark/test_benchmark_scope_busy_share.py's
    case of this name means to hold, for a cell of any name
    (tests/conftest.py)."""
    from deepspeed_tpu.utils.annotate import DEVICE_SCOPES
    params = manifest.load_layer_metric(metric["name"])
    assert (metric["unit"], metric["better"], metric["source"],
            metric["layer"]) == ("%", "lower", "device_trace", "kernels")
    assert len(metric["workloads"]) == 1 and metric["workloads"][0] in {
        c["name"] for c in MANIFEST["workloads"]}
    if metric["name"].startswith("unscoped_busy_share."):
        assert params["unscoped"] is True and "scopes" not in params
    else:
        assert params["scopes"] and set(params["scopes"]) <= \
            set(DEVICE_SCOPES)


def test_lengths_stay_inside_the_mix_and_the_serving_window():
    from benchmark.traffic import requests_balanced
    config = manifest.load_config(MANIFEST, ENTRY[0]["name"])
    mix = manifest.load_workload(CELL)["traffic"]
    vocab = config["model"]["padded_vocab_size"]
    due, prompts, outputs = requests_balanced.generate(
        mix, 3000000019, 40.0, vocab, cycle_s=51.0)
    lens = np.array(list(map(len, prompts)))
    assert len(due) == 3000 and not due.any()
    assert lens.min() >= 128 and lens.max() <= 8192
    assert outputs.min() >= 64 and outputs.max() <= 1536
    assert (lens + outputs).max() <= config["inference"]["max_seq_len"]
    assert outputs.max() <= config["inference"]["max_new_tokens"]
    assert 1250 < lens.mean() < 1450
    assert 420 < outputs.mean() < 480
    # a fifth of the prompts in 2-4 chunks of the largest bucket
    assert 0.15 < (lens > 2048).mean() < 0.25
    assert all(p.min() >= 0 and p.max() < vocab for p in prompts[:50])


def test_the_family_trains_nothing():
    with pytest.raises(NotImplementedError, match="served, not trained"):
        family.build_train_engine(TINY, 0)


def test_a_checkout_without_the_model_says_so_and_exits(monkeypatch):
    import builtins
    real = builtins.__import__

    def no_model(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "deepspeed_tpu.models" and \
                "granite_moe_hybrid" in fromlist:
            raise ImportError("no such module")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.delitem(sys.modules,
                        "deepspeed_tpu.models.granite_moe_hybrid",
                        raising=False)
    monkeypatch.setattr(builtins, "__import__", no_model)
    with pytest.raises(SystemExit, match="no models/granite_moe_hybrid.py"):
        family.build_serve_engine(TINY, 0)


@pytest.mark.parametrize("layer", [0, 2], ids=["mamba", "attention"])
def test_weights_are_the_programs_own_recipe(layer):
    from deepspeed_tpu.models import granite_moe_hybrid as program
    model = TINY["model"]
    cfg = program.config_from_hf(model, dtype=jnp.float32)
    ref = reference.draw_layer(model, 9, layer)
    got = program.init_layer(cfg, 9, layer)
    # the program holds an expert's gate and up matrices side by side,
    # the shared MLP's likewise, and the taps channels minor
    ref = dict(ref, w13=jnp.concatenate([ref.pop("w1"), ref.pop("w3")],
                                        axis=-1),
               shared13=jnp.concatenate([ref.pop("s1"), ref.pop("s3")],
                                        axis=-1), shared2=ref.pop("s2"))
    if layer == 0:
        ref["conv_w"] = ref["conv_w"].T
    assert set(ref) == set(got)
    for name in ref:
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(ref[name]))
    assert got["w13"].shape == (4, 64, 64)          # the share: 4 of 8
    np.testing.assert_array_equal(
        np.asarray(program.init_params(cfg, 9)["embed"]),
        np.asarray(reference.draw_embedding(model, 9)))
    # the other half of the layer holds the other experts
    other = reference.draw_layer(model, 9, layer, held=(4, 8))
    whole = reference.draw_layer(model, 9, layer, held=(0, 8))
    np.testing.assert_array_equal(
        np.asarray(whole["w2"]),
        np.concatenate([np.asarray(reference.draw_layer(
            model, 9, layer)["w2"]), np.asarray(other["w2"])]))
    # the layer's published initialiser, not a forgetful one
    if layer == 0:
        dt = np.asarray(jax.nn.softplus(ref["dt_bias"]))
        assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
        A = np.exp(np.asarray(ref["A_log"]))
        assert 1 <= A.min() and A.max() <= 16
        assert np.abs(np.asarray(ref["conv_b"])).max() <= 0.5


@pytest.fixture(scope="module")
def sound_and_controls():
    """One tiny engine, served and checked; then every control."""
    seed = 3000000019                 # more than 32 signed bits hold
    engine = family.build_serve_engine(TINY, seed)
    served = jamba_controls.served_requests(TINY, seed, engine, answers=12)
    got = family.serve_engine_outputs(TINY, seed, engine)
    sequences, lens = family.serve_check_inputs(TINY, seed)
    ref = family.reference_logits(TINY, seed, sequences, lens)
    out = {"sound": family.serve_check(TINY, seed, got, served, ref=ref),
           "bfloat16_matmuls": family.serve_check(
               TINY, seed, rounding="bfloat16", ref=ref)}
    for control in family.CONTROLS:
        out[control] = family.serve_control(TINY, seed, control, served,
                                            ref=ref)
    return out


def test_the_sound_engine_is_inside_every_limit(sound_and_controls):
    checks = sound_and_controls["sound"]
    assert set(checks) == {"prefill_logits_rel_rms",
                           "decode_logits_rel_rms",
                           "decode_logits_rel_err_p10",
                           "served_token_deficit"}
    assert all(value <= limit for value, limit in checks.values()), checks


@pytest.mark.parametrize("control",
                         family.CONTROLS + ("bfloat16_matmuls",))
def test_each_control_reads_beyond_a_limit(sound_and_controls, control):
    """fp8 matmul operands, the state rounded to bfloat16 every step,
    nine of ten experts, the softmax over all experts not renormalised,
    ``1/sqrt(d_head)`` for the attention multiplier, a residual
    multiplier of 1, the gate after the norm, no decay, a request begun
    from the previous tenant's state, a second chunk begun from a zero
    state or with zero tails, no convolution bias, another request's
    prompt (and, the tiny configuration stating float32, bfloat16
    matmuls): not correct, by one of the check's limits."""
    checks = sound_and_controls[control]
    assert any(not value <= limit for value, limit in checks.values()), \
        checks


def test_the_check_crosses_a_chunks_end_twice():
    sequences, lens = family.serve_check_inputs(TINY, 5)
    buckets = TINY["inference"]["prefill_buckets"]
    page = TINY["inference"]["kv_block_size"]
    assert len(lens) == len(buckets) + 4
    assert all(lo < n <= hi for n, lo, hi in
               zip(lens, [0] + buckets[:-1], buckets))
    assert lens[len(buckets)] <= page                   # a single page
    assert buckets[-1] < lens[-3] < 2 * buckets[-1]     # two chunks
    assert 2 * buckets[-1] < lens[-2] < 3 * buckets[-1]  # three
    assert (lens[-2] - 2 * buckets[-1]) % buckets[0] != 0     # padded
    assert lens[-1] == buckets[-1] + 2      # a second chunk of two tokens
    assert [len(s) - n for s, n in zip(sequences, lens)] == \
        [TINY["check"]["decode_steps"]] * len(lens)
    assert len(lens) <= TINY["inference"]["max_batch_size"]
    # the cell's own: eight sequences in 96 slots, the longest inside
    # the serving window
    config = manifest.load_config(MANIFEST, ENTRY[0]["name"])
    sequences, lens = family.serve_check_inputs(config, 3000000019)
    assert len(lens) == 8 <= config["inference"]["max_batch_size"] == 96
    assert 4096 < lens[-2] < 6144 and max(map(len, sequences)) < \
        config["inference"]["max_seq_len"]


def test_no_request_to_look_at_is_not_correct():
    checks = family.serve_check(TINY, 5, rounding="bfloat16", served=[])
    value, limit = checks["served_token_deficit"]
    assert not value <= limit


def test_counts_of_bytes_and_operations():
    model = manifest.load_config(MANIFEST, ENTRY[0]["name"])["model"]
    # a slot's step: 128 x 8,192 float32 read and written in 9 layers
    assert family.ssd_step_bytes(model, 1) == 9 * 2 * 128 * 8192 * 4
    # the issue's arithmetic: 80 slots, 6.0 GB a step, 7.4 ms at 819 GB/s
    assert family.ssd_step_bytes(model, 80) * 1e-9 == \
        pytest.approx(6.04, abs=0.01)
    assert family.ssd_step_bytes(model, 80) / 819e9 * 1e3 == \
        pytest.approx(7.4, abs=0.05)
    # the cell's 96 slots: 7.2 GB, 8.8 ms
    assert family.ssd_step_bytes(model, 96) / 819e9 * 1e3 == \
        pytest.approx(8.85, abs=0.05)
    # a page of 16 tokens: keys and values of 8 x 128 in ONE layer
    assert family.paged_attention_bytes(model, 16, 1) == \
        2 * 16 * 1024 * 2 == 65536
    expert = 3 * 4096 * 768
    assert family.moe_gmm_flops(model, 1) == 2 * expert
    assert family.moe_gmm_bytes(model, 0, 360) == 2 * 360 * expert
    after = 4096 * 72 + 3 * 4096 * 1536 + 5 * expert
    mixer = 4096 * (8192 + 8448 + 128) + 8192 * 4096
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert family.serve_flops_per_token(model) == \
        2 * (9 * (mixer + after) + attn + after)
    # the issue's 9 x 337 + 217 MFLOP a token
    assert 2e-6 * (mixer + after) == pytest.approx(337, abs=1)
    assert 2e-6 * (attn + after) == pytest.approx(217, abs=1)


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
    """A trace of a program with no ``ssd.advanced`` span and no
    ``ssd_step`` kernel: the new reader and the new parameter files
    return None, and the line leaves the metrics out."""
    run = _run_on(os.path.join(HERE, "fixtures_program_spans",
                               "serve_chat_steps.xplane.pb"))
    assert gated_delta_step_roofline.advanced_slots(run,
                                                    "ssd.advanced") == []
    for name, reader in (
            ("ssd_step_roofline.support", ssd_step_roofline),
            ("ssd_step_busy_share.support", kernel_busy_share)):
        assert reader.read(run, manifest.load_layer_metric(name)) is None


# ------------------------------------------------- the runner, end to end
@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    copy = tmp_path_factory.mktemp("benchmark_copy_granite_moe_hybrid")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), copy)
    shutil.copytree(os.path.join(REPO, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    benchmark_rehearsal.add_tiny_files(str(copy), TINY_DIR)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(copy), REPO]),
               TMPDIR=str(copy))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "benchmark_rehearsal.py"),
         "tiny-granite-moe-hybrid.support:0",
         "tiny-granite-moe-hybrid.support:1"],
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
def test_serve_runner_rehearsal_on_a_tiny_granite_cell(rehearsal, trace):
    r = rehearsal[trace]
    assert "error" not in r, r
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert set(r["checks"]) == {"prefill_logits_rel_rms",
                                "decode_logits_rel_rms",
                                "decode_logits_rel_err_p10",
                                "served_token_deficit"}
    assert all(v <= limit for v, limit in r["checks"].values()), r["checks"]
    assert r["end_to_end"]["serve_tokens_per_s"] > 0
    assert 0 < r["counters"]["active_slot_steps"] <= \
        r["counters"]["slot_steps"]


def test_padding_share_is_read_and_the_cpu_trace_has_no_kernel(rehearsal):
    """The traced rehearsal reads the chunk spans' padding; the state
    kernel's share of its roofline has nothing to read on the CPU (no
    device plane: XLA's oracle ran) and is left out."""
    per_layer = rehearsal[1]["per_layer"]
    value = per_layer["tiny_ssd_padding_share"]
    assert 0 < value["value"] < 100 and value["unit"] == "%"
    assert "tiny_ssd_step_roofline" not in per_layer
