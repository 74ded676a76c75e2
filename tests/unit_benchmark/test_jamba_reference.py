"""The Jamba family of the benchmark at a tiny size on the CPU: the
configuration file against the catalog row, the reference against the
program, the serving check's controls, the byte counts, the new
readers on a trace of a program from before their span attribute, and
the serve runner end to end on a tiny Jamba cell dropped into a copy of
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
from benchmark.layer_metrics import (mamba_scan_roofline,
                                     mamba_step_roofline, program_spans,
                                     span_chunks, span_padding_share)
from benchmark.models import jamba, jamba_controls, jamba_reference

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY_DIR = os.path.join(HERE, "tiny_jamba")
with open(os.path.join(TINY_DIR, "configs", "tiny-jamba.json")) as f:
    TINY = json.load(f)

# the catalog row AI21-Jamba2-3B's `config`, as read from the model's
# public config.json
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True,
    "use_mamba_kernels": True, "vocab_size": 65536}
MANIFEST = manifest.load_manifest()
ENTRY = [c for c in MANIFEST["configs"]
         if manifest.load_config(MANIFEST, c["name"])["family"] == "jamba"]


def test_the_configuration_is_the_catalog_rows_nothing_cut():
    assert len(ENTRY) == 1
    entry = ENTRY[0]
    config = manifest.load_config(MANIFEST, entry["name"])
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/" \
        "config.json"
    assert entry["reduced"] == config["reduced"] == []
    # every key of the row at the top level of the file AND in `model`
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["model"] == dict(PUBLISHED, padded_vocab_size=65536)
    assert jamba_reference.param_count(config["model"]) == 3029337472
    assert {"layer_order", "weights", "positional_encoding",
            "state_dtype"} <= set(config["assumed"])
    assert config["precision_state"] in ("float32", "bfloat16")
    assert config["inference"]["max_batch_size"] == 384
    assert config["inference"]["prefill_buckets"] == [128, 256, 512]
    assert {"why", "prefill_logits_rel_rms", "decode_logits_rel_rms",
            "served_token_deficit", "decode_steps"} <= set(config["check"])
    cells = [c for c in MANIFEST["workloads"]
             if c["config"] == entry["name"]]
    assert [c["traffic"] for c in cells] == ["rollouts"]
    traffic = manifest.load_workload(cells[0]["name"])["traffic"]
    assert traffic["arrivals"] == {"process": "backlog", "queued": 4000,
                                   "sized_at_tokens_per_s": 12400}
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32,
        "max": 1024}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.6, "min": 128,
        "max": 2048}


def test_lengths_stay_inside_the_mix_and_the_serving_window():
    from benchmark.traffic import requests
    config = manifest.load_config(MANIFEST, ENTRY[0]["name"])
    cell = [c for c in MANIFEST["workloads"]
            if c["config"] == ENTRY[0]["name"]][0]
    mix = manifest.load_workload(cell["name"])["traffic"]
    vocab = config["model"]["padded_vocab_size"]
    due, prompts, outputs = requests.generate(mix, 3, 40.0, vocab,
                                              cycle_s=51.0)
    lens = np.array(list(map(len, prompts)))
    assert len(due) == 4000 and not due.any()
    assert lens.min() >= 32 and lens.max() <= 1024
    assert outputs.min() >= 128 and outputs.max() <= 2048
    assert (lens + outputs).max() <= config["inference"]["max_seq_len"]
    assert outputs.max() <= config["inference"]["max_new_tokens"]
    # a fifth of the prompts take two chunks
    largest = config["inference"]["prefill_buckets"][-1]
    assert 0.15 < (lens > largest).mean() < 0.25
    assert 320 < lens.mean() < 360
    assert outputs.mean() == pytest.approx(610, abs=15)
    assert all(p.min() >= 0 and p.max() < vocab for p in prompts[:50])


def test_the_family_trains_nothing():
    with pytest.raises(NotImplementedError, match="served, not trained"):
        jamba.build_train_engine(TINY, 0)


@pytest.mark.parametrize("layer", [0, 3], ids=["mamba", "attention"])
def test_weights_are_the_programs_own_recipe(layer):
    from deepspeed_tpu.models import jamba as program
    model = TINY["model"]
    cfg = program.config_from_hf(model, dtype=jnp.float32)
    ref = jamba_reference.draw_layer(model, 9, layer)
    got = program.init_layer(cfg, 9, layer)
    assert set(ref) == set(got)
    for name in ref:
        want = np.asarray(ref[name])
        if name in ("conv_w", "A_log"):
            want = want.T             # the program holds d_inner minor
        np.testing.assert_array_equal(np.asarray(got[name]), want)
    np.testing.assert_array_equal(
        np.asarray(program.init_params(cfg, 9)["embed"]),
        np.asarray(jamba_reference.draw_embedding(model, 9)))
    # the Mamba paper's initialisation, not a forgetful one
    if layer == 0:
        dt = np.asarray(jax.nn.softplus(ref["dt_bias"]))
        assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
        np.testing.assert_allclose(np.exp(np.asarray(ref["A_log"]))[0],
                                   np.arange(1, 17), rtol=1e-6)


@pytest.fixture(scope="module")
def sound_and_controls():
    """One tiny engine, served and checked; then every control."""
    seed = 3000000019                 # more than 32 signed bits hold
    engine = jamba.build_serve_engine(TINY, seed)
    served = jamba_controls.served_requests(TINY, seed, engine, answers=12)
    got = jamba.serve_engine_outputs(TINY, seed, engine)
    out = {"sound": jamba.serve_check(TINY, seed, got, served),
           "bfloat16_matmuls": jamba.serve_check(TINY, seed,
                                                 rounding="bfloat16")}
    for control in jamba.CONTROLS:
        out[control] = jamba.serve_control(TINY, seed, control, served)
    return out


def test_the_sound_engine_is_inside_every_limit(sound_and_controls):
    checks = sound_and_controls["sound"]
    assert set(checks) == {"prefill_logits_rel_rms",
                           "decode_logits_rel_rms", "served_token_deficit"}
    assert all(value <= limit for value, limit in checks.values())


@pytest.mark.parametrize("control", jamba.CONTROLS + ("bfloat16_matmuls",))
def test_each_control_reads_beyond_a_limit(sound_and_controls, control):
    """fp8 matmul operands, the SSM state one precision lower, a
    request begun from the previous tenant's state, a second chunk
    begun from zero (and, the tiny configuration stating float32,
    bfloat16 matmuls): not correct, by one of the check's limits."""
    checks = sound_and_controls[control]
    assert any(not value <= limit for value, limit in checks.values()), \
        checks


def test_the_check_has_a_prompt_in_every_bucket_and_one_of_two_chunks():
    sequences, lens = jamba.serve_check_inputs(TINY, 5)
    buckets = TINY["inference"]["prefill_buckets"]
    assert len(lens) == len(buckets) + 1
    assert all(lo < n <= hi for n, lo, hi in
               zip(lens, [0] + buckets[:-1], buckets))
    assert buckets[-1] < lens[-1] < 2 * buckets[-1]
    assert (lens[-1] - buckets[-1]) % buckets[0] != 0     # padded
    assert [len(s) - n for s, n in zip(sequences, lens)] == \
        [TINY["check"]["decode_steps"]] * len(lens)


def test_no_request_to_look_at_is_not_correct():
    checks = jamba.serve_check(TINY, 5, rounding="bfloat16", served=[])
    value, limit = checks["served_token_deficit"]
    assert not value <= limit


def test_counts_of_bytes():
    model = manifest.load_config(MANIFEST, ENTRY[0]["name"])["model"]
    # 26 layers; a token: x, dt, y of 5120 and B, C of 16, float32
    per_token = 26 * 4 * (3 * 5120 + 2 * 16)
    per_chunk = 26 * 3 * 4 * 16 * 5120
    assert jamba.mamba_scan_bytes(model, 512, 1) == \
        512 * per_token + per_chunk
    # a slot's step: 16 x 5120 state read and written
    assert jamba.mamba_step_bytes(model, 1, "float32") == \
        26 * (2 * 16 * 5120 * 4 + 4 * (3 * 5120 + 2 * 16))
    assert jamba.mamba_step_bytes(model, 256, "float32") * 1e-9 == \
        pytest.approx(4.77, abs=0.01)
    assert jamba.mamba_step_bytes(model, 1, "bfloat16") < \
        jamba.mamba_step_bytes(model, 1, "float32")


def test_count_of_operations_a_served_token():
    model = manifest.load_config(MANIFEST, ENTRY[0]["name"])["model"]
    mlp = 3 * 2560 * 8192
    mamba = 2560 * 10240 + 5120 * (160 + 2 * 16) + 160 * 5120 + 5120 * 2560
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128    # one key-value head
    weights = 28 * mlp + 26 * mamba + 2 * attention
    assert jamba.serve_flops_per_token(model) == 2 * weights
    # every parameter but the embedding, norms, biases, convolutions, A, D
    assert 0.99 * (3029337472 - 65536 * 2560) < weights < \
        3029337472 - 65536 * 2560


def _run_on(trace_file):
    from benchmark import trace
    events = program_spans.from_trace(trace_file)
    run = types.SimpleNamespace(
        trace_dir=trace_file, counters={"active_slot_steps": 10},
        reduction=trace.reduce_trace(trace_file, []), log=lambda m: None,
        config=dict(TINY, model=TINY["model"]), peaks={
            "hbm_bytes_per_s": 819e9})
    run.program_spans = program_spans.ProgramSpans(events, run.reduction)
    return run


def test_new_readers_find_nothing_in_a_parents_trace_and_do_not_raise():
    """A trace of the program from before ``padded`` was a chunk
    attribute, and with no ``mamba_*`` kernel in it: each new reader
    returns None, and the line leaves the metric out."""
    run = _run_on(os.path.join(HERE, "fixtures_program_spans",
                               "serve_chat_steps.xplane.pb"))
    assert run.program_spans.named(["sched.prefill.chunk"])
    assert span_chunks.chunks(run, "sched.prefill.chunk") is None
    for name, reader in (("prefill_padding_share", span_padding_share),
                         ("mamba_scan_roofline", mamba_scan_roofline),
                         ("mamba_step_roofline", mamba_step_roofline)):
        assert reader.read(run, manifest.load_layer_metric(name)) is None


def test_padding_share_of_chunk_spans():
    run = types.SimpleNamespace(
        reduction=types.SimpleNamespace(window_s=0, start=0, end=0),
        program_spans=program_spans.ProgramSpans([
            ("sched.prefill.chunk", 0.0, 1.0, {"tokens": 300,
                                               "padded": 512}),
            ("sched.prefill.chunk", 1.0, 2.0, {"tokens": 128,
                                               "padded": 128})]))
    params = manifest.load_layer_metric("prefill_padding_share.rollouts")
    assert span_padding_share.read(run, params) == \
        pytest.approx(100 * (1 - 428 / 640))


# ------------------------------------------------- the runner, end to end
@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    copy = tmp_path_factory.mktemp("benchmark_copy_jamba")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), copy)
    shutil.copytree(os.path.join(REPO, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    benchmark_rehearsal.add_tiny_files(str(copy), TINY_DIR)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(copy), REPO]),
               TMPDIR=str(copy))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "benchmark_rehearsal.py"),
         "tiny-jamba.rollouts:0", "tiny-jamba.rollouts:1"],
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
def test_serve_runner_rehearsal_on_a_tiny_jamba_cell(rehearsal, trace):
    r = rehearsal[trace]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert set(r["checks"]) == {"prefill_logits_rel_rms",
                                "decode_logits_rel_rms",
                                "served_token_deficit"}
    assert all(v <= limit for v, limit in r["checks"].values())
    assert r["end_to_end"]["serve_tokens_per_s"] > 0
    assert 0 < r["counters"]["active_slot_steps"] <= \
        r["counters"]["slot_steps"]


def test_padding_share_is_read_from_the_programs_chunk_spans(rehearsal):
    value = rehearsal[1]["per_layer"]["tiny_padding_share"]
    assert 0 < value["value"] < 100 and value["unit"] == "%"
