"""The Moonlight family of the benchmark at a tiny size on the CPU: the
configuration file against the catalog row, the reference against the
program's weight recipe, the serving check's controls, the counts, the
latent page walk's readers (on made-up traces, and on a trace of a
program from before the kernel), and the serve runner end to end on a
tiny Moonlight cell dropped into a copy of the benchmark."""
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

import benchmark_rehearsal
from benchmark import manifest, trace
from benchmark.layer_metrics import (kernel_busy_share,
                                     paged_attention_roofline)
from benchmark.models import (moonlight, moonlight_controls,
                              moonlight_reference)
from benchmark.models.jamba_controls import served_requests
from benchmark.models.lfm2_controls import program_routing
from benchmark.traffic import requests, requests_balanced

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY_DIR = os.path.join(HERE, "tiny_moonlight")
with open(os.path.join(TINY_DIR, "configs", "tiny-moonlight.json")) as f:
    TINY = json.load(f)

# the catalog row Moonlight-16B-A3B's `config`, as read from the model's
# public config.json
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
REDUCED = ["num_hidden_layers"]
ASSUMED_IN_MODEL = ("padded_vocab_size", "expert_bias_std", "attn_in_scale",
                    "attn_out_scale", "kv_norm_eps")
MANIFEST = manifest.load_manifest()
ENTRY = [c for c in MANIFEST["configs"]
         if manifest.load_config(MANIFEST, c["name"])["family"] ==
         "moonlight"]
CELL = "moonlight-16b-a3b-serve.reasoning"


def test_the_configuration_is_the_catalog_rows_but_for_its_depth():
    assert len(ENTRY) == 1
    entry = ENTRY[0]
    config = manifest.load_config(MANIFEST, entry["name"])
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/"
        "config.json")
    assert entry["reduced"] == config["reduced"] == REDUCED
    # every key of the row at the top level of the file AND in `model`,
    # letter for letter apart from the one that is cut
    cut = dict(PUBLISHED, num_hidden_layers=5)
    assert {k: config[k] for k in PUBLISHED} == cut
    assert {k: v for k, v in config["model"].items()
            if k not in ASSUMED_IN_MODEL} == cut
    assert set(config["model"]) == set(cut) | set(ASSUMED_IN_MODEL)
    assert config["model"]["padded_vocab_size"] == 163840 == 1280 * 128
    assert config["model"]["kv_norm_eps"] == 1e-6
    assert config["published"] == {"num_hidden_layers": 27}
    assert "experts_held" not in config["model"]      # all 64 held
    assert "FIRST stage" in config["deployment"] and \
        "64 of 64" in config["deployment"]
    assert {"kv_a_layernorm_eps", "rope_scaling", "rotary_pairing",
            "expert_bias_std", "weights", "precision",
            "route_norm_eps"} <= set(config["assumed"])
    count = moonlight_reference.param_count(config["model"])
    assert 3.092e9 < count < 3.094e9
    inference = config["inference"]
    assert inference["max_seq_len"] == 8192 == \
        config["max_position_embeddings"]
    assert inference["prefill_buckets"] == [512, 1024, 2048]
    assert inference["kv_block_size"] == 16
    assert inference["paged_attention_kernel"] == "auto"
    assert {"why", "prefill_logits_rel_rms", "decode_logits_rel_rms",
            "decode_logits_rel_err_p10", "served_token_deficit",
            "decode_steps"} <= set(config["check"])
    assert "why" in config["memory"]
    # the pool by its own arithmetic: 5 layers x 640 lanes x 2 bytes
    assert config["memory"]["page_bytes"] == 16 * 5 * 640 * 2
    cells = [c for c in MANIFEST["workloads"]
             if c["config"] == entry["name"]]
    assert [c["name"] for c in cells] == [CELL]
    assert cells[0]["chips"] == 1


def test_lengths_stay_inside_the_mix_and_the_serving_window():
    config = manifest.load_config(MANIFEST, ENTRY[0]["name"])
    workload = manifest.load_workload(CELL)
    mix = workload["traffic"]
    # ISSUE 38's mix and lead-in, letter for letter
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 1536, "sigma": 0.7, "min": 256,
        "max": 5120}
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.6, "min": 256,
        "max": 3072}
    assert workload["lead_s"] == 30 and workload["drain_cap_s"] == 0
    assert mix["generator"] == "requests_balanced"
    vocab = config["model"]["padded_vocab_size"]
    due, prompts, outputs = requests_balanced.generate(mix, 3, 40.0, vocab,
                                                       cycle_s=51.0)
    lens = np.array(list(map(len, prompts)))
    assert len(due) == mix["arrivals"]["queued"] and not due.any()
    assert mix["arrivals"]["process"] == "backlog"
    assert mix["arrivals"]["queued"] % 1000 == 0
    assert lens.min() >= 256 and lens.max() <= 5120
    assert outputs.min() >= 256 and outputs.max() <= 3072
    assert (lens + outputs).max() <= config["inference"]["max_seq_len"]
    assert outputs.max() <= config["inference"]["max_new_tokens"]
    assert prompts[0].min() >= 0 and prompts[0].max() < vocab
    # a third of the prompts take two or three chunks
    largest = config["inference"]["prefill_buckets"][-1]
    assert 0.30 < (lens > largest).mean() < 0.38
    assert 0.05 < (lens > 2 * largest).mean() < 0.12
    assert lens.max() <= 3 * largest
    assert 1860 < lens.mean() < 1900 and 1180 < outputs.mean() < 1215
    # the smallest multiple of 1,000 that outlasts four times the rate
    rate = mix["arrivals"]["sized_at_tokens_per_s"]
    served_s = workload["lead_s"] + MANIFEST["run_seconds"]
    mean = (lens.sum() + outputs.sum()) / len(lens)
    assert mix["arrivals"]["queued"] == \
        1000 * int(np.ceil(4 * rate * served_s / mean / 1000))


MIX = manifest.load_workload(CELL)["traffic"]


def test_the_balanced_order_holds_the_lengths_of_the_generator_that_is_there():
    """Value for value: only the cycle's fixed order is another."""
    _, prompt_lens, output_lens = requests.cycle(MIX, 51.0)
    gaps, balanced_prompts, balanced_outputs = requests_balanced.cycle(
        MIX, 51.0)
    assert not gaps.any() and len(gaps) == MIX["arrivals"]["queued"]
    assert np.array_equal(np.sort(balanced_prompts), np.sort(prompt_lens))
    assert np.array_equal(np.sort(balanced_outputs), np.sort(output_lens))
    assert not np.array_equal(balanced_prompts, prompt_lens)
    # no pairing by rank: long prompts do not come with long answers
    assert abs(np.corrcoef(balanced_prompts, balanced_outputs)[0, 1]) < 0.05


@pytest.mark.parametrize("which", [1, 2], ids=["prompts", "answers"])
def test_every_aligned_run_holds_one_length_from_each_stratum(which):
    block = requests_balanced.BLOCK
    values = requests_balanced.cycle(MIX, 51.0)[which]
    edges = np.sort(values).reshape(block, -1)
    runs = np.sort(values.reshape(-1, block), axis=1)
    assert np.all(runs >= edges[:, 0]) and np.all(runs <= edges[:, -1])
    # and shuffled inside a run: not ascending
    assert not np.array_equal(runs, values.reshape(-1, block))


def test_every_seed_is_given_the_same_work():
    """What the order is for: any stretch of the cycle as long as what
    a run serves (the slots' 320 and a window's 330) holds the same
    tokens: 1.7% from the smallest stretch to the largest, where one
    shuffle of the whole cycle has 10.7%."""
    def stretches(values, n=650):
        doubled = np.concatenate([values, values]).cumsum()
        sums = doubled[n:n + len(values)] - doubled[:len(values)]
        return (sums.max() - sums.min()) / sums.mean()
    _, prompt_lens, output_lens = requests_balanced.cycle(MIX, 51.0)
    assert stretches(prompt_lens) < 0.025
    assert stretches(output_lens) < 0.025
    assert stretches(prompt_lens + output_lens) < 0.02
    _, prompt_lens, output_lens = requests.cycle(MIX, 51.0)
    assert stretches(prompt_lens + output_lens) > 0.10


def test_balanced_requests_are_a_function_of_the_seed():
    seed = 2 ** 31 + 12345
    a, b, c = (requests_balanced.generate(MIX, s, 86.0, 163840, cycle_s=51.0)
               for s in (seed, seed, seed + 1))
    same = lambda x, y: (np.array_equal(x[2], y[2]) and all(  # noqa: E731
        np.array_equal(p, q) for p, q in zip(x[1], y[1])))
    assert same(a, b) and not same(a, c)
    # every seed sees the same cycle from another point, the point that
    # `requests` would give it
    _, prompt_lens, output_lens = requests_balanced.cycle(MIX, 51.0)
    n = len(prompt_lens)
    for got, s in ((a, seed), (c, seed + 1)):
        phase = int(np.random.default_rng([s, 2]).integers(n))
        assert np.array_equal(got[2], np.roll(output_lens, -phase))
        assert np.array_equal(list(map(len, got[1])),
                              np.roll(prompt_lens, -phase))


def test_the_balanced_order_is_a_backlogs_alone():
    poisson = dict(MIX, arrivals={"process": "poisson", "rate_per_s": 5.0})
    with pytest.raises(ValueError, match="orders a backlog only"):
        requests_balanced.generate(poisson, 1, 10.0, 100, cycle_s=10.0)
    odd = dict(MIX, arrivals=dict(MIX["arrivals"], queued=3001))
    with pytest.raises(ValueError, match="no multiple"):
        requests_balanced.cycle(odd, 51.0)


def test_the_backlog_outlasts_a_program_four_times_as_fast():
    """`test_benchmark_traffic.py` holds the cells of `requests` to this;
    the same for the cell of `requests_balanced`."""
    workload, arrivals = manifest.load_workload(CELL), MIX["arrivals"]
    _, prompt_lens, output_lens = requests_balanced.cycle(MIX, 1.0)
    tokens = int(prompt_lens.sum() + output_lens.sum())
    lasts_below = tokens / (workload["lead_s"] + MANIFEST["run_seconds"])
    assert lasts_below >= 4 * arrivals["sized_at_tokens_per_s"]


def test_the_family_trains_nothing():
    with pytest.raises(NotImplementedError, match="served, not trained"):
        moonlight.build_train_engine(TINY, 0)


@pytest.fixture(scope="module")
def sound_and_controls():
    """One tiny engine, served and checked; then every control."""
    seed = 3000000019                 # more than 32 signed bits hold
    engine = moonlight.build_serve_engine(TINY, seed)
    served = served_requests(TINY, seed, engine, answers=12)
    got = moonlight.serve_engine_outputs(TINY, seed, engine)
    ids = np.random.default_rng([seed, 0xF11B]).integers(0, 512, 64)
    program = program_routing(engine, ids)
    out = {"sound": moonlight.serve_check(TINY, seed, got, served),
           "router": moonlight_controls.router_report(TINY, seed, ids,
                                                      program),
           "bfloat16_matmuls": moonlight.serve_check(TINY, seed,
                                                     rounding="bfloat16")}
    for control in moonlight.CONTROLS:
        out[control] = moonlight.serve_control(TINY, seed, control, served)
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
    assert router["expert_layers"] == 2 and router["tokens"] == 64
    assert all(v >= 1.0 for v in router["hottest_over_mean_rows"].values())


@pytest.mark.parametrize("control",
                         moonlight.CONTROLS + ("bfloat16_matmuls",))
def test_each_control_reads_beyond_a_limit(sound_and_controls, control):
    """fp8 matmul operands, the latent kept in e4m3, ``k_pe`` left out
    of the scores, rotary restarted at the second chunk,
    ``kv_a_layernorm`` skipped, the scale of 128, the shared expert left
    out, five of six experts, a scaling factor of 1, the selection bias
    ignored, another request's prompt (and, the tiny configuration
    stating float32, bfloat16 matmuls): not correct, by one of the
    check's limits."""
    checks = sound_and_controls[control]
    assert any(not value <= limit for value, limit in checks.values()), \
        checks


def test_the_checks_prompts_reach_three_chunks_and_a_single_page():
    for config in (TINY, manifest.load_config(MANIFEST, ENTRY[0]["name"])):
        sequences, lens = moonlight.serve_check_inputs(config, 5)
        buckets = config["inference"]["prefill_buckets"]
        assert len(lens) == len(buckets) + 3
        assert all(lo < n <= hi for n, lo, hi in
                   zip(lens, [0] + buckets[:-1], buckets))
        two, three, page = lens[len(buckets):]
        assert buckets[-1] < two < 2 * buckets[-1] < three < 3 * buckets[-1]
        assert page < config["inference"]["kv_block_size"]
        steps = config["check"]["decode_steps"]
        assert [len(s) - n for s, n in zip(sequences, lens)] == \
            [steps] * len(lens)
        assert three + steps < config["inference"]["max_seq_len"]
    # at the cell's size: a prompt and decode positions past 4,096
    assert three > 4096


def test_no_request_to_look_at_is_not_correct():
    checks = moonlight.serve_check(TINY, 5, rounding="bfloat16", served=[])
    value, limit = checks["served_token_deficit"]
    assert not value <= limit


def test_counts_of_operations_and_bytes():
    model = manifest.load_config(MANIFEST, ENTRY[0]["name"])["model"]
    attention = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 2048 * 2048
    assert attention == 13_762_560                     # 13.76M a layer
    dense, expert = 3 * 2048 * 11264, 3 * 2048 * 1408
    weights = 5 * attention + dense + 4 * (8 * expert + 2048 * 64)
    assert moonlight.serve_flops_per_token(model) == 2 * weights
    assert moonlight.serve_flops_per_token(model) * 1e-9 == \
        pytest.approx(0.831, abs=0.001)
    # a share of the routed experts multiplies its share of a token's
    # six; the shared expert is whole wherever it is held
    share = dict(model, experts_held=[0, 16])
    assert moonlight.serve_flops_per_token(share) == pytest.approx(2 * (
        weights - 4 * 4.5 * expert))
    # a decode step of 320 slots: 1,920 rows a layer, all experts hit
    rows, hit = 4 * 1920, 4 * 64
    assert moonlight.moe_gmm_flops(model, rows) == 2 * rows * expert
    assert moonlight.moe_gmm_bytes(model, rows, hit) == \
        2 * (hit * expert + rows * (2 * 2048 + 3 * 1408))
    assert moonlight.moe_gmm_bytes(model, rows, hit) * 1e-9 == \
        pytest.approx(4.56, abs=0.02)
    # a live page: 16 tokens x 5 layers x 576 USEFUL values x 2 bytes,
    # nine tenths of the 640 lanes the pool holds
    assert moonlight.paged_attention_bytes(model, 16, 1) == \
        16 * 5 * 576 * 2 == 0.9 * 16 * 5 * 640 * 2


# ---------------------------------------------------------------- readers
_MLA = ('%mla_decode.{} = f32[320,16,512]{{2,1,0}} custom-call(s32[320,512]'
        '{{1,0}} %t), custom_call_target="tpu_custom_call"')
_OTHER = "%fusion.7 = bf16[320,2048]{1,0} fusion(bf16[320,2048]{1,0} %x)"


def _made_up_run(kernel_s, other_s, steps, pages, launches=2):
    """``launches`` runs of ``jit_decode`` of 1 s, each with five kernel
    events of ``kernel_s`` and one other operation."""
    events, modules = [], []
    for i in range(launches):
        t = float(i)
        modules.append(("jit_decode({})".format(i), t, t + 1.0))
        for j in range(5):
            start = t + 0.05 + j * kernel_s
            events.append((_MLA.format(j), _MLA.format(j), start,
                           start + kernel_s))
        events.append((_OTHER, _OTHER, t + 0.7, t + 0.7 + other_s))
    plane = "/device:TPU:0"
    config = manifest.load_config(MANIFEST, ENTRY[0]["name"])
    return types.SimpleNamespace(
        reduction=trace.Reduction({plane: events}, [], {plane: modules}),
        counters={"live_kv_pages_read": pages, "steps": steps},
        config=config, log=lambda m: None,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})


def test_the_walks_roofline_prices_the_useful_lanes_of_the_live_pages():
    params = manifest.load_layer_metric("mla_decode_roofline")
    assert params["reader"] == "paged_attention_roofline"
    # 54,000 live pages a step, three steps counted, two held whole
    run = _made_up_run(0.002, 0.1, steps=3, pages=3 * 54000)
    least = 2 * 54000 * 16 * 5 * 576 * 2 / 819e9
    assert paged_attention_roofline.read(run, params) == \
        pytest.approx(100 * least / (2 * 5 * 0.002))
    # the kernel at the HBM's peak over all 640 lanes reads 90%
    per_step = 54000 * 16 * 5 * 640 * 2 / 819e9
    run = _made_up_run(per_step / 5, 0.1, steps=3, pages=3 * 54000)
    assert paged_attention_roofline.read(run, params) == pytest.approx(90.0)


def test_busy_share_is_the_walks_time_over_the_devices_busy_time():
    params = manifest.load_layer_metric("mla_decode_busy_share.reasoning")
    assert params["reader"] == "kernel_busy_share"
    run = _made_up_run(0.02, 0.1, steps=2, pages=2)
    assert kernel_busy_share.read(run, params) == pytest.approx(50.0)


def test_the_readers_find_nothing_in_a_parents_trace_and_do_not_raise():
    """A trace of a program with no ``mla_decode`` kernel in it (GPT-2's
    serving steps): each returns None, and the line leaves the metric
    out."""
    path = os.path.join(HERE, "fixtures_program_spans",
                        "serve_chat_steps.xplane.pb")
    run = types.SimpleNamespace(
        trace_dir=path, reduction=trace.reduce_trace(path, []),
        log=lambda m: None, config=TINY,
        counters={"live_kv_pages_read": 10, "steps": 2},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    assert run.reduction.device_events
    for name, reader in (
            ("mla_decode_roofline", paged_attention_roofline),
            ("mla_decode_busy_share.reasoning", kernel_busy_share)):
        assert reader.read(run, manifest.load_layer_metric(name)) is None


def test_the_new_cell_reports_every_metric_the_issue_names():
    names = {m["name"] for m in manifest.cell_metrics(MANIFEST, CELL,
                                                      "per_layer")}
    assert names == {
        "batch_occupancy.reasoning", "kv_pool_live_share.reasoning",
        "device_idle_share.reasoning", "sched_host_ms_mean.reasoning",
        "step_idle_before_dispatch.reasoning",
        "step_idle_in_flight.reasoning", "step_idle_after_fetch.reasoning",
        "prefill_padding_share.reasoning", "serve_mfu.reasoning",
        "moe_gmm_roofline.reasoning", "moe_gmm_busy_share.reasoning",
        "mla_decode_roofline", "mla_decode_busy_share.reasoning"}
    assert [m["name"] for m in manifest.cell_metrics(
        MANIFEST, CELL, "end_to_end")] == ["serve_tokens_per_s", "setup_s"]
    for name in names:
        params = manifest.load_layer_metric(name)
        manifest.plugin("layer_metrics", params["reader"])


# ------------------------------------------------- the runner, end to end
@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    copy = tmp_path_factory.mktemp("benchmark_copy_moonlight")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), copy)
    shutil.copytree(os.path.join(REPO, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    benchmark_rehearsal.add_tiny_files(str(copy), TINY_DIR)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(copy), REPO]),
               TMPDIR=str(copy))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "benchmark_rehearsal.py"),
         "tiny-moonlight.reasoning:0", "tiny-moonlight.reasoning:1"],
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
def test_serve_runner_rehearsal_on_a_tiny_moonlight_cell(rehearsal,
                                                         trace_on):
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
    assert r["counters"]["live_kv_pages_read"] > 0


def test_the_cpu_trace_has_no_kernel_event_and_the_line_leaves_them_out(
        rehearsal):
    """Off the chip the page walk is its XLA oracle and the trace has no
    device plane: the two kernel metrics are left out, the padding share
    (from the program's spans) is there."""
    per_layer = rehearsal[1]["per_layer"]
    assert set(per_layer) == {"tiny_moonlight_padding_share"}
    assert 0 < per_layer["tiny_moonlight_padding_share"]["value"] < 100
