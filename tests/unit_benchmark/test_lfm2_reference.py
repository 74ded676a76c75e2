"""The LFM2 family of the benchmark at a tiny size on the CPU: the
configuration file against the catalog row, the reference against the
program, the serving check's controls, the counts, the two new readers
(on made-up traces, and on a trace of a program from before their span
and kernel), and the serve runner end to end on a tiny LFM2 cell
dropped into a copy of the benchmark."""
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
from benchmark import manifest, trace
from benchmark.layer_metrics import kernel_busy_share, moe_gmm_roofline
from benchmark.models import lfm2, lfm2_controls, lfm2_reference

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY_DIR = os.path.join(HERE, "tiny_lfm2")
with open(os.path.join(TINY_DIR, "configs", "tiny-lfm2.json")) as f:
    TINY = json.load(f)

# the catalog row LFM2-8B-A1B's `config`, as read from the model's
# public config.json
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention", "conv",
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}
REDUCED = ["num_hidden_layers", "layer_types"]
MANIFEST = manifest.load_manifest()
ENTRY = [c for c in MANIFEST["configs"]
         if manifest.load_config(MANIFEST, c["name"])["family"] == "lfm2"]
CELL = "lfm2-8b-a1b-serve.extract"


def test_the_configuration_is_the_catalog_rows_but_for_its_depth():
    assert len(ENTRY) == 1
    entry = ENTRY[0]
    config = manifest.load_config(MANIFEST, entry["name"])
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert entry["reduced"] == config["reduced"] == REDUCED
    # every key of the row at the top level of the file AND in `model`,
    # letter for letter apart from the two that are cut
    cut = dict(PUBLISHED, num_hidden_layers=12,
               layer_types=PUBLISHED["layer_types"][:12])
    assert {k: config[k] for k in PUBLISHED} == cut
    assert config["model"] == dict(cut, padded_vocab_size=65536,
                                   expert_bias_std=0.04)
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    # three whole periods of the published one attention layer in four
    assert config["layer_types"] == ["conv", "conv", "full_attention",
                                     "conv"] * 3
    assert "two v5e chips" in config["deployment"]
    assert "experts_held" not in config["model"]      # all 32 held
    assert {"head_dim", "tie_word_embeddings", "dense_layers", "weights",
            "expert_bias_std", "precision"} <= set(config["assumed"])
    assert 3.92e9 < lfm2_reference.param_count(config["model"]) < 3.94e9
    assert config["inference"]["max_seq_len"] == 3072
    assert {"why", "prefill_logits_rel_rms", "decode_logits_rel_rms",
            "decode_logits_rel_err_p10", "served_token_deficit",
            "decode_steps"} <= set(config["check"])
    assert "why" in config["memory"]
    cells = [c for c in MANIFEST["workloads"]
             if c["config"] == entry["name"]]
    assert [c["name"] for c in cells] == [CELL]
    traffic = manifest.load_workload(CELL)["traffic"]
    assert traffic["arrivals"]["process"] == "backlog"
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.8, "min": 64,
        "max": 2048}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.6, "min": 32,
        "max": 1024}


def test_lengths_stay_inside_the_mix_and_the_serving_window():
    from benchmark.traffic import requests
    config = manifest.load_config(MANIFEST, ENTRY[0]["name"])
    workload = manifest.load_workload(CELL)
    mix = workload["traffic"]
    vocab = config["model"]["padded_vocab_size"]
    due, prompts, outputs = requests.generate(mix, 3, 40.0, vocab,
                                              cycle_s=51.0)
    lens = np.array(list(map(len, prompts)))
    assert len(due) == mix["arrivals"]["queued"] and not due.any()
    assert mix["arrivals"]["queued"] % 1000 == 0
    assert lens.min() >= 64 and lens.max() <= 2048
    assert outputs.min() >= 32 and outputs.max() <= 1024
    assert (lens + outputs).max() <= config["inference"]["max_seq_len"]
    assert outputs.max() <= config["inference"]["max_new_tokens"]
    # a fifth of the prompts take two chunks, none three
    largest = config["inference"]["prefill_buckets"][-1]
    assert 0.15 < (lens > largest).mean() < 0.25
    assert lens.max() <= 2 * largest
    assert 640 < lens.mean() < 700 and 290 < outputs.mean() < 315
    # the smallest multiple of 1,000 that outlasts four times the rate
    rate = mix["arrivals"]["sized_at_tokens_per_s"]
    served_s = workload["lead_s"] + MANIFEST["run_seconds"]
    mean = (lens.sum() + outputs.sum()) / len(lens)
    assert mix["arrivals"]["queued"] == \
        1000 * int(np.ceil(4 * rate * served_s / mean / 1000))


def test_the_family_trains_nothing():
    with pytest.raises(NotImplementedError, match="served, not trained"):
        lfm2.build_train_engine(TINY, 0)


@pytest.mark.parametrize("layer", [0, 2, 3], ids=[
    "conv_dense", "attention_experts", "conv_experts"])
def test_weights_are_the_programs_own_recipe(layer):
    from deepspeed_tpu.models import lfm2 as program
    model = TINY["model"]
    cfg = program.config_from_hf(model, dtype=jnp.float32)
    ref = lfm2_reference.draw_layer(model, 9, layer)
    got = program.init_layer(cfg, 9, layer)
    if "router" in ref:
        # the program holds gate and up side by side
        ref["w13"] = jnp.concatenate([ref.pop("w1"), ref.pop("w3")], -1)
    assert set(ref) == set(got)
    for name in ref:
        want = np.asarray(ref[name])
        if name == "conv_w":
            want = want.T              # the program holds the taps major
        np.testing.assert_array_equal(np.asarray(got[name]), want)
    np.testing.assert_array_equal(
        np.asarray(program.init_params(cfg, 9)["embed"]),
        np.asarray(lfm2_reference.draw_embedding(model, 9)))


@pytest.fixture(scope="module")
def sound_and_controls():
    """One tiny engine, served and checked; then every control."""
    seed = 3000000019                 # more than 32 signed bits hold
    engine = lfm2.build_serve_engine(TINY, seed)
    served = lfm2_controls.served_requests(TINY, seed, engine, answers=12)
    got = lfm2.serve_engine_outputs(TINY, seed, engine)
    ids, program = lfm2_controls.router_measurement(TINY, seed, engine)
    out = {"sound": lfm2.serve_check(TINY, seed, got, served),
           "router": lfm2_controls.router_report(TINY, seed, ids, program),
           "bfloat16_matmuls": lfm2.serve_check(TINY, seed,
                                                rounding="bfloat16")}
    for control in lfm2.CONTROLS:
        out[control] = lfm2.serve_control(TINY, seed, control, served)
    return out


def test_the_sound_engine_is_inside_every_limit(sound_and_controls):
    checks = sound_and_controls["sound"]
    assert set(checks) == {"prefill_logits_rel_rms",
                           "decode_logits_rel_rms",
                           "decode_logits_rel_err_p10",
                           "served_token_deficit"}
    assert all(value <= limit for value, limit in checks.values())
    # in float32 the program routes as the reference does
    router = sound_and_controls["router"]
    assert router["program_flip_share"] == 0.0
    assert router["expert_layers"] == 5 and router["tokens"] == 64
    assert all(v >= 1.0 for v in router["hottest_over_mean_rows"].values())


@pytest.mark.parametrize("control", lfm2.CONTROLS + ("bfloat16_matmuls",))
def test_each_control_reads_beyond_a_limit(sound_and_controls, control):
    """fp8 matmul operands, three of four experts, the selection bias
    ignored, weights not renormalised, the previous tenant's tail, a
    second chunk begun from zero, rotary positions restarted, another
    request's prompt (and, the tiny configuration stating float32,
    bfloat16 matmuls): not correct, by one of the check's limits."""
    checks = sound_and_controls[control]
    assert any(not value <= limit for value, limit in checks.values()), \
        checks


def test_the_checks_prompts_sit_where_a_tail_shows():
    sequences, lens = lfm2.serve_check_inputs(TINY, 5)
    buckets = TINY["inference"]["prefill_buckets"]
    assert len(lens) == len(buckets) + 3
    assert all(lo < n <= hi for n, lo, hi in
               zip(lens, [0] + buckets[:-1], buckets))
    long_, short_second, short = lens[len(buckets):]
    assert buckets[-1] < long_ < 2 * buckets[-1]
    assert (long_ - buckets[-1]) % buckets[0] != 0        # padded
    # a second chunk, and a whole prompt, no longer than a tail reaches
    assert short_second == buckets[-1] + 2 and short == 2
    assert [len(s) - n for s, n in zip(sequences, lens)] == \
        [TINY["check"]["decode_steps"]] * len(lens)


def test_no_request_to_look_at_is_not_correct():
    checks = lfm2.serve_check(TINY, 5, rounding="bfloat16", served=[])
    value, limit = checks["served_token_deficit"]
    assert not value <= limit


def test_the_pooled_statistic_is_over_all_positions():
    ref = [np.array([[1.0, -1.0], [2.0, -2.0]]), np.array([[3.0, -3.0]])]
    got = [r + 0.1 for r in ref]
    # error 0.1 everywhere; scale: mean of 1, 4, 9
    assert lfm2._pooled_rel_rms(got, ref) == \
        pytest.approx(0.1 / np.sqrt(14 / 3))
    assert lfm2._pooled_rel_rms(ref, ref) == 0.0


def test_the_steady_statistic_passes_over_a_few_positions_far_off():
    """One position in five off by a whole expert moves the pooled RMS
    and not the tenth percentile; every position a little off moves
    both; and it is the WORST sequence's."""
    rng = np.random.default_rng(0)
    ref = [rng.standard_normal((41, 64)) for _ in range(2)]
    flipped = [r.copy() for r in ref]
    flipped[0][1::5] += 0.5 * rng.standard_normal((8, 64))
    assert lfm2._steady_rel_err(flipped, ref) == 0.0
    assert lfm2._pooled_rel_rms([g[1:] for g in flipped],
                                [r[1:] for r in ref]) > 0.1
    shifted = [ref[0], ref[1] + 0.1 * rng.standard_normal((41, 64))]
    assert 0.08 < lfm2._steady_rel_err(shifted, ref) < 0.12


def test_counts_of_operations_and_bytes():
    model = manifest.load_config(MANIFEST, ENTRY[0]["name"])["model"]
    conv, attention = 4 * 2048 * 2048, 2 * 2048 * 2048 + 2 * 2048 * 512
    dense, expert = 3 * 2048 * 7168, 3 * 2048 * 1792
    weights = 9 * conv + 3 * attention + 2 * dense + \
        10 * (4 * expert + 2048 * 32)
    assert lfm2.serve_flops_per_token(model) == 2 * weights
    assert lfm2.serve_flops_per_token(model) * 1e-9 == \
        pytest.approx(1.42, abs=0.01)
    # a share of the experts multiplies its share of a token's four
    share = dict(model, experts_held=[0, 8])
    assert lfm2.serve_flops_per_token(share) == 2 * (
        weights - 10 * 3 * expert)
    # a decode step of 384 slots: 1,536 rows a layer, all experts hit
    rows, hit = 10 * 1536, 10 * 32
    assert lfm2.moe_gmm_flops(model, rows) == 2 * rows * expert
    assert lfm2.moe_gmm_bytes(model, rows, hit) == \
        2 * (hit * expert + rows * (2 * 2048 + 3 * 1792))
    assert lfm2.moe_gmm_bytes(model, rows, hit) * 1e-9 == \
        pytest.approx(7.34, abs=0.02)
    # an expert with no row costs no weight traffic
    assert lfm2.moe_gmm_bytes(model, rows, hit - 1) == \
        lfm2.moe_gmm_bytes(model, rows, hit) - 2 * expert


# ---------------------------------------------------------------- readers
_GMM = ('%moe_gmm.{} = bf16[1536,3584]{{1,0}} custom-call(s32[43]{{0}} %g), '
        'custom_call_target="tpu_custom_call"')
_OTHER = "%fusion.7 = bf16[384,2048]{1,0} fusion(bf16[384,2048]{1,0} %x)"


def _made_up_run(kernel_s, other_s, loads, launches=2, lost=0):
    """``launches`` runs of ``jit_decode`` of 1 s, each with two kernel
    events of ``kernel_s`` and one other operation; the last ``lost``
    runs hold one kernel event only."""
    events, modules = [], []
    for i in range(launches):
        t = float(i)
        modules.append(("jit_decode({})".format(i), t, t + 1.0))
        n = 1 if i >= launches - lost else 2
        for j in range(n):
            start = t + 0.1 + j * kernel_s
            events.append((_GMM.format(j), _GMM.format(j), start,
                           start + kernel_s))
        events.append((_OTHER, _OTHER, t + 0.6, t + 0.6 + other_s))
    plane = "/device:TPU:0"
    config = manifest.load_config(MANIFEST, ENTRY[0]["name"])
    return types.SimpleNamespace(
        reduction=trace.Reduction({plane: events}, [], {plane: modules}),
        moe_loads=loads, config=config, log=lambda m: None,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})


def test_roofline_prices_the_launches_the_trace_holds_whole():
    params = manifest.load_layer_metric("moe_gmm_roofline")
    model = manifest.load_config(MANIFEST, ENTRY[0]["name"])["model"]
    loads = [(15360, 320), (15360, 320), (15360, 320)]
    run = _made_up_run(0.01, 0.1, loads)
    least = lfm2.moe_gmm_bytes(model, 15360, 320) / 819e9   # 8.96 ms
    assert least > lfm2.moe_gmm_flops(model, 15360) / 197e12
    # two launches held whole, two kernel events of 10 ms in each
    assert moe_gmm_roofline.read(run, params) == \
        pytest.approx(100 * 2 * least / 0.04)
    # a launch that lost an event loses its work with its time
    run = _made_up_run(0.01, 0.1, loads, launches=3, lost=1)
    assert moe_gmm_roofline.read(run, params) == \
        pytest.approx(100 * 2 * least / 0.04)
    # many rows on few experts: the operations bound it
    run = _made_up_run(0.01, 0.1, [(400000, 10)])
    flops = lfm2.moe_gmm_flops(model, 400000) / 197e12
    assert flops > lfm2.moe_gmm_bytes(model, 400000, 10) / 819e9
    assert moe_gmm_roofline.read(run, params) == \
        pytest.approx(100 * 2 * flops / 0.04)


def test_busy_share_is_the_kernels_time_over_the_devices_busy_time():
    params = manifest.load_layer_metric("moe_gmm_busy_share.extract")
    run = _made_up_run(0.1, 0.2, [])
    # a launch: two kernel events of 0.1 s and another of 0.2 s
    assert kernel_busy_share.read(run, params) == pytest.approx(50.0)


def test_new_readers_find_nothing_in_a_parents_trace_and_do_not_raise():
    """A trace of the program from before the ``moe.load`` span and
    with no ``moe_gmm`` kernel in it (GPT-2's serving steps): each new
    reader returns None, and the line leaves the metric out. So does a
    program with the kernel but no span, and one with neither."""
    path = os.path.join(HERE, "fixtures_program_spans",
                        "serve_chat_steps.xplane.pb")
    run = types.SimpleNamespace(
        trace_dir=path, reduction=trace.reduce_trace(path, []),
        log=lambda m: None, config=TINY,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    assert run.reduction.device_events
    for name, reader in (("moe_gmm_roofline", moe_gmm_roofline),
                         ("moe_gmm_busy_share.extract", kernel_busy_share)):
        assert reader.read(run, manifest.load_layer_metric(name)) is None
    assert run.moe_loads == []
    run = _made_up_run(0.01, 0.1, [])
    assert moe_gmm_roofline.read(
        run, manifest.load_layer_metric("moe_gmm_roofline")) is None
    empty = types.SimpleNamespace(
        reduction=trace.Reduction({}, [], {}), moe_loads=[],
        log=lambda m: None)
    for name, reader in (("moe_gmm_roofline", moe_gmm_roofline),
                         ("moe_gmm_busy_share.extract", kernel_busy_share)):
        assert reader.read(empty, manifest.load_layer_metric(name)) is None


def test_the_new_cell_reports_every_metric_the_issue_names():
    names = {m["name"] for m in manifest.cell_metrics(MANIFEST, CELL,
                                                      "per_layer")}
    assert names == {
        "batch_occupancy.extract", "kv_pool_live_share.extract",
        "device_idle_share.extract", "sched_host_ms_mean.extract",
        "step_idle_before_dispatch.extract", "step_idle_in_flight.extract",
        "step_idle_after_fetch.extract", "prefill_padding_share.extract",
        "serve_mfu.extract", "moe_gmm_roofline",
        "moe_gmm_busy_share.extract"}
    assert [m["name"] for m in manifest.cell_metrics(
        MANIFEST, CELL, "end_to_end")] == ["serve_tokens_per_s", "setup_s"]


# ------------------------------------------------- the runner, end to end
@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    copy = tmp_path_factory.mktemp("benchmark_copy_lfm2")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), copy)
    shutil.copytree(os.path.join(REPO, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    benchmark_rehearsal.add_tiny_files(str(copy), TINY_DIR)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(copy), REPO]),
               TMPDIR=str(copy))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "benchmark_rehearsal.py"),
         "tiny-lfm2.extract:0", "tiny-lfm2.extract:1"],
        cwd=str(copy), env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = {}
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            r = json.loads(line)
            out[r["trace"]] = r
    return out


@pytest.mark.parametrize("trace_on", [0, 1])
def test_serve_runner_rehearsal_on_a_tiny_lfm2_cell(rehearsal, trace_on):
    r = rehearsal[trace_on]
    assert "error" not in r, r
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert set(r["checks"]) == {"prefill_logits_rel_rms",
                                "decode_logits_rel_rms",
                                "decode_logits_rel_err_p10",
                                "served_token_deficit"}
    assert all(v <= limit for v, limit in r["checks"].values())
    assert r["end_to_end"]["serve_tokens_per_s"] > 0
    assert r["counters"]["backlog_left"] > 0


def test_the_cpu_trace_has_no_kernel_event_and_the_line_leaves_them_out(
        rehearsal):
    """Off the chip the grouped matmul is ``lax.ragged_dot`` and the
    trace has no device plane: the two kernel metrics are left out, the
    padding share (from the program's spans) is there."""
    per_layer = rehearsal[1]["per_layer"]
    assert set(per_layer) == {"tiny_lfm2_padding_share"}
    assert 0 < per_layer["tiny_lfm2_padding_share"]["value"] < 100
