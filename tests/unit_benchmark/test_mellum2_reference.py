"""The Mellum family of the benchmark at a tiny size on the CPU: the
configuration file against the catalog row, the backlog's sizing, the
serving check's controls, the counts, the readers of the windowed
group's metrics (on made-up spans and traces, and on a trace of a program
from before the groups), and the serve runner end to end on a tiny Mellum
cell dropped into a copy of the benchmark."""
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
from benchmark.layer_metrics import (grouped_paged_attention_roofline,
                                     kernel_busy_share, program_spans,
                                     span_attr_ratio)
from benchmark.models import mellum2, mellum2_reference
from benchmark.models.jamba_controls import served_requests
from benchmark.traffic import requests_balanced

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY_DIR = os.path.join(HERE, "tiny_mellum2")
with open(os.path.join(TINY_DIR, "configs", "tiny-mellum2.json")) as f:
    TINY = json.load(f)

SLIDING, FULL = "sliding_attention", "full_attention"
# the catalog row Mellum2-12B-A2.5B-Instruct's `config`, as read from the
# model's public config.json
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
               "original_max_position_embeddings": 8192, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782},
        SLIDING: {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types"]
ASSUMED_IN_MODEL = ("padded_vocab_size", "qk_norm_gain")
MANIFEST = manifest.load_manifest()
ENTRY = [c for c in MANIFEST["configs"]
         if manifest.load_config(MANIFEST, c["name"])["family"] == "mellum2"]
CELL = "mellum2-12b-a2.5b-serve.ide"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_the_configuration_is_the_catalog_rows_but_for_its_depth():
    assert len(ENTRY) == 1
    entry = ENTRY[0]
    config = manifest.load_config(MANIFEST, entry["name"])
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/"
        "main/config.json")
    assert entry["reduced"] == config["reduced"] == REDUCED
    # every key of the row at the top level of the file AND in `model`,
    # letter for letter apart from the three that the cut in depth cuts
    cut = dict(PUBLISHED, num_hidden_layers=8,
               layer_types=PUBLISHED["layer_types"][:8],
               mlp_layer_types=["sparse"] * 8)
    assert {k: config[k] for k in PUBLISHED} == cut
    assert {k: v for k, v in config["model"].items()
            if k not in ASSUMED_IN_MODEL} == cut
    assert set(config["model"]) == set(cut) | set(ASSUMED_IN_MODEL)
    # six sliding layers and two full: the published ratio
    assert config["layer_types"].count(SLIDING) == 6
    assert config["model"]["padded_vocab_size"] == 98304 == 768 * 128
    assert config["published"] == {"num_hidden_layers": 28}
    assert "FIRST stage" in config["deployment"] and \
        "8, 8, 8 and 4" in config["deployment"]
    assert {"qk_norm", "router_scoring", "mtp_head", "rotary_pairing",
            "window", "yarn_truncate", "precision", "weights"} <= \
        set(config["assumed"])
    assert mellum2_reference.param_count(config["model"]) == 3_794_968_832
    inference = config["inference"]
    assert inference["max_seq_len"] == 32768
    assert inference["prefill_buckets"] == [512, 1024, 2048]
    assert inference["kv_block_size"] == 16
    assert inference["paged_attention_kernel"] == "auto"
    assert inference["max_batch_size"] == 128
    assert len(inference["num_pages"]) == 2        # a page count a group
    assert {"why", "prefill_logits_rel_rms", "decode_logits_rel_rms",
            "decode_logits_rel_err_p10", "served_token_deficit",
            "decode_steps"} <= set(config["check"])
    memory = config["memory"]
    assert "why" in memory
    # the pools by their own arithmetic: 2 and 6 layers of 4 x 128 keys
    # and values, 16 tokens a page
    assert memory["page_bytes"] == [65536, 196608] == [
        mellum2.page_bytes(config["model"], 16, sliding)
        for sliding in (False, True)]
    assert memory["pool_pages"] == inference["num_pages"]
    cells = [c for c in MANIFEST["workloads"]
             if c["config"] == entry["name"]]
    assert [c["name"] for c in cells] == [CELL]
    assert cells[0]["chips"] == 1


def test_lengths_stay_inside_the_mix_and_the_serving_window():
    config = manifest.load_config(MANIFEST, ENTRY[0]["name"])
    workload = manifest.load_workload(CELL)
    mix = workload["traffic"]
    # ISSUE 42's mix, letter for letter
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 4096, "sigma": 1.0, "min": 256,
        "max": 24576}
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.6, "min": 128,
        "max": 2048}
    # the lead-in is twice the issue's 30 s: at 30 s the first wave's
    # prompts (435 chunks) have just been prefilled, no request has
    # retired yet, and a traced window of 5 s has none to look at
    assert workload["lead_s"] == 60 and workload["drain_cap_s"] == 0
    assert workload["trace_seconds"] == 5 and not workload["latency"]
    assert mix["generator"] == "requests_balanced"
    vocab = config["model"]["padded_vocab_size"]
    due, prompts, outputs = requests_balanced.generate(mix, 3, 40.0, vocab,
                                                       cycle_s=51.0)
    lens = np.array(list(map(len, prompts)))
    assert len(due) == mix["arrivals"]["queued"] == 3000 and not due.any()
    assert lens.min() >= 256 and lens.max() <= 24576
    assert outputs.min() >= 128 and outputs.max() <= 2048
    assert (lens + outputs).max() <= 26624 < \
        config["inference"]["max_seq_len"]
    assert outputs.max() <= config["inference"]["max_new_tokens"]
    # about a twelfth inside one window, a quarter past 8,192, 4% at the
    # cap; 3.4 chunks a prompt
    assert 0.07 < (lens <= 1024).mean() < 0.10
    assert 0.22 < (lens > 8192).mean() < 0.27
    assert 0.03 < (lens == 24576).mean() < 0.05
    assert 6100 < lens.mean() < 6500 and 580 < outputs.mean() < 620
    largest = config["inference"]["prefill_buckets"][-1]
    assert 3.3 < np.ceil(lens / largest).mean() < 3.7
    # the backlog outlasts a program four times as fast as the cell's
    rate = mix["arrivals"]["sized_at_tokens_per_s"]
    served_s = workload["lead_s"] + MANIFEST["run_seconds"]
    assert (lens.sum() + outputs.sum()) / served_s >= 4 * rate
    # and every seed is given the same work (blocks of 30)
    _, prompt_lens, output_lens = requests_balanced.cycle(mix, 51.0)

    def stretches(values, n=350):
        doubled = np.concatenate([values, values]).cumsum()
        sums = doubled[n:n + len(values)] - doubled[:len(values)]
        return (sums.max() - sums.min()) / sums.mean()
    # 350 requests (what a run serves) hold the same tokens within 7%
    # from the smallest stretch to the largest, a standard deviation of
    # 0.9%; one shuffle of the whole cycle has one of 5.5%
    assert stretches(prompt_lens + output_lens) < 0.07


def test_the_family_trains_nothing():
    with pytest.raises(NotImplementedError, match="served, not trained"):
        mellum2.build_train_engine(TINY, 0)


def test_a_checkout_from_before_the_family_fails_with_one_sentence(
        monkeypatch):
    """What the parent commit does with the new cell: the benchmark's
    files are laid over it, ``deepspeed_tpu.models.mellum`` is not
    there, and the run ends at once, not in a traceback."""
    import deepspeed_tpu.models
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.models.mellum", None)
    monkeypatch.delattr(deepspeed_tpu.models, "mellum", raising=False)
    with pytest.raises(SystemExit, match="has no models/mellum.py and "
                       "cannot run the mellum2 family"):
        mellum2.build_serve_engine(TINY, 0)


@pytest.fixture(scope="module")
def sound_and_controls():
    """One tiny engine, served and checked; then every control."""
    seed = 3000000019                 # more than 32 signed bits hold
    engine = mellum2.build_serve_engine(TINY, seed)
    served = served_requests(TINY, seed, engine, answers=12)
    got = mellum2.serve_engine_outputs(TINY, seed, engine)
    freed = engine.page_groups[1].freed
    mellum2.release(engine.params, engine.kv.k, engine.kv.v)
    gone = all(a.is_deleted() for kv in engine.kv_groups
               for a in kv.buffers())
    out = {"sound": mellum2.serve_check(TINY, seed, got, served),
           "freed": freed, "pools_released": gone,
           "bfloat16_matmuls": mellum2.serve_check(TINY, seed,
                                                   rounding="bfloat16")}
    for control in mellum2.CONTROLS:
        out[control] = mellum2.serve_control(TINY, seed, control, served)
    return out


def test_the_sound_engine_is_inside_every_limit(sound_and_controls):
    checks = sound_and_controls["sound"]
    assert set(checks) == {"prefill_logits_rel_rms",
                           "decode_logits_rel_rms",
                           "decode_logits_rel_err_p10",
                           "served_token_deficit"}
    assert all(value <= limit for value, limit in checks.values())
    # the check's sequences slid pages out of the window on their way
    assert sound_and_controls["freed"] > 0
    # and `release` freed the windowed pools with the first group's
    assert sound_and_controls["pools_released"]


@pytest.mark.parametrize("control",
                         mellum2.CONTROLS + ("bfloat16_matmuls",))
def test_each_control_reads_beyond_a_limit(sound_and_controls, control):
    """The window ignored, a window a page short, YaRN left out,
    ``attention_factor`` 1, the sliding layers rotated by the full
    layers' table, sigmoid for softmax, 7 of 8 experts, the chosen not
    renormalised, q/k norms skipped, keys and values in fp8, fp8
    matmuls, another request's prompt (and, the tiny configuration
    stating float32, bfloat16 matmuls): not correct, by one of the
    check's limits."""
    checks = sound_and_controls[control]
    assert any(not value <= limit for value, limit in checks.values()), \
        checks


def test_the_checks_prompts_reach_three_chunks_past_the_window():
    for config in (TINY, manifest.load_config(MANIFEST, ENTRY[0]["name"])):
        sequences, lens = mellum2.serve_check_inputs(config, 5)
        buckets = config["inference"]["prefill_buckets"]
        assert len(lens) == len(buckets) + 3
        assert all(lo < n <= hi for n, lo, hi in
                   zip(lens, [0] + buckets[:-1], buckets))
        page, three, two = lens[len(buckets):]
        assert buckets[-1] < two < 2 * buckets[-1] < three < 3 * buckets[-1]
        assert page < config["inference"]["kv_block_size"]
        # the second and the third chunk each start past a window's end
        assert buckets[-1] > config["model"]["sliding_window"]
        steps = config["check"]["decode_steps"]
        assert [len(s) - n for s, n in zip(sequences, lens)] == \
            [steps] * len(lens)
        # decode crosses pages' release
        assert steps >= 2 * config["inference"]["kv_block_size"]
    assert 4096 < three and 2048 < two < 2600


def test_no_request_to_look_at_is_not_correct():
    checks = mellum2.serve_check(TINY, 5, rounding="bfloat16", served=[])
    value, limit = checks["served_token_deficit"]
    assert not value <= limit


def test_counts_of_operations_and_bytes():
    model = manifest.load_config(MANIFEST, ENTRY[0]["name"])["model"]
    attention = 2 * 2304 * 32 * 128 + 2 * 2304 * 4 * 128
    assert attention == 21_233_664                     # 21.2M a layer
    expert = 3 * 2304 * 896
    assert expert == 6_193_152                         # 6.19M an expert
    assert mellum2.serve_flops_per_token(model) == \
        2 * 8 * (attention + 8 * expert + 2304 * 64)
    assert mellum2.serve_flops_per_token(model) * 1e-9 == \
        pytest.approx(1.135, abs=0.001)
    # 12.4 MFLOP a routed row, 12.4 MB an (expert, layer) pair hit
    assert mellum2.moe_gmm_flops(model, 1) == 2 * expert == 12_386_304
    assert mellum2.moe_gmm_bytes(model, 0, 1) == 2 * expert
    rows, hit = 8 * 1024, 8 * 64       # a decode step of 128 slots
    assert mellum2.moe_gmm_bytes(model, rows, hit) == \
        2 * (hit * expert + rows * (2 * 2304 + 3 * 896))
    # a live page of each group
    assert mellum2.paged_attention_bytes(model, 16, 1) == 65536
    assert mellum2.paged_attention_bytes(model, 16, 0, 1) == 196608
    assert mellum2.paged_attention_bytes(model, 16, 3, 2) == \
        3 * 65536 + 2 * 196608


# ---------------------------------------------------------------- readers
_WALK = ('%paged_attention_grouped.{} = f32[128,4,8,128]{{3,2,1,0}} '
         'custom-call(s32[128]{{0}} %p), custom_call_target='
         '"tpu_custom_call"')
_OTHER = "%fusion.7 = bf16[128,2304]{1,0} fusion(bf16[128,2304]{1,0} %x)"
_CHUNK = ('%while.142 = (s32[]{:T(128)}, f32[1,4,8,2048,128]{4,3,2,1,0:'
          'T(8,128)S(1)}, f32[1,4,8,2048,1]{3,2,1,4,0:T(8,128)S(1)}, '
          'f32[1,4,8,2048,1]{3,2,1,4,0}, s32[]{:T(128)}, s32[1,224]{1,0}, '
          'bf16[8577,6,16,512]{3,2,1,0}) while((s32[]{:T(128)}, ...) %t), '
          'condition=%cond, body=%body')
_MOE_LOOP = ('%while.7 = (s32[]{:T(128)}, bf16[16384,2304]{1,0}) '
             'while((s32[]{:T(128)}, ...) %u), condition=%c, body=%b')


def _made_up_run(kernel_s, other_s, steps, full, window, launches=2):
    """``launches`` runs of ``jit_decode`` of 1 s, each with eight walks
    of ``kernel_s`` and one other operation; ``steps`` spans of
    ``sched.decode.pages`` with the live pages a step."""
    events, modules, spans = [], [], []
    for i in range(launches):
        t = float(i)
        modules.append(("jit_decode({})".format(i), t, t + 1.0))
        for j in range(8):
            start = t + 0.05 + j * kernel_s
            events.append((_WALK.format(j), _WALK.format(j), start,
                           start + kernel_s))
        events.append((_OTHER, _OTHER, t + 0.7, t + 0.7 + other_s))
    for i in range(steps):
        # inside the traced window, which the device's events span
        t = 0.1 + 0.3 * i
        spans.append(("sched.decode.pages", t, t + 0.01,
                      {"window_freed": i % 2, "full_live": full,
                       "window_live": window, "window_pool": 8576}))
        spans.append(("sched.prefill.chunk", t + 0.02, t + 0.03,
                      {"window_freed": 10, "tokens": 100, "padded": 128}))
    plane = "/device:TPU:0"
    config = manifest.load_config(MANIFEST, ENTRY[0]["name"])
    reduction = trace.Reduction({plane: events}, [], {plane: modules})
    return types.SimpleNamespace(
        reduction=reduction, config=config, log=lambda m: None, peaks=PEAKS,
        program_spans=program_spans.ProgramSpans(spans), counters={})


def test_the_walks_roofline_prices_both_groups_live_pages():
    params = manifest.load_layer_metric("paged_attention_roofline.ide")
    assert params["reader"] == "grouped_paged_attention_roofline"
    # 52,000 full and 8,000 windowed live pages a step, three steps
    # counted, two runs held whole
    run = _made_up_run(0.001, 0.1, 3, 52000, 8000)
    least = 2 * (52000 * 65536 + 8000 * 196608) / 819e9
    assert grouped_paged_attention_roofline.read(run, params) == \
        pytest.approx(100 * least / (2 * 8 * 0.001))
    # the accepted reader, on the runner's counter, would price the first
    # group's pages alone
    assert manifest.load_layer_metric("paged_attention_roofline")[
        "reader"] == "paged_attention_roofline"


def test_the_windowed_pools_share_and_the_pages_it_gives_back():
    run = _made_up_run(0.001, 0.1, 4, 52000, 8000)
    share = manifest.load_layer_metric("window_pool_live_share.ide")
    assert span_attr_ratio.read(run, share) == \
        pytest.approx(100 * 8000 / 8576)
    freed = manifest.load_layer_metric("window_pages_freed_per_step.ide")
    # decode's 0 + 1 + 0 + 1 and four chunks' 10 each, over four steps
    assert span_attr_ratio.read(run, freed) == pytest.approx(42 / 4)


def test_busy_shares_of_the_walk_and_of_the_chunks_blocks():
    run = _made_up_run(0.0125, 0.1, 2, 1, 1)
    params = manifest.load_layer_metric("paged_attention_busy_share.ide")
    assert kernel_busy_share.read(run, params) == pytest.approx(50.0)
    plane = "/device:TPU:0"
    run.reduction = trace.Reduction(
        {plane: [(_CHUNK, _CHUNK, 0.0, 0.3), (_OTHER, _OTHER, 0.5, 0.55),
                 (_MOE_LOOP, _MOE_LOOP, 0.55, 0.6)]}, [])
    # the chunk's loop by what it carries (the device events have no
    # scope: read on the chip), and no other loop
    params = manifest.load_layer_metric("chunk_attention_busy_share.ide")
    assert kernel_busy_share.read(run, params) == pytest.approx(75.0)


def test_the_readers_find_nothing_in_a_parents_trace_and_do_not_raise():
    """A trace of a program from before the groups (GPT-2's serving
    steps: no such attribute on its spans, no grouped walk, no
    ``attn.chunk_blocks`` scope): each returns None, and the line leaves
    the metric out."""
    path = os.path.join(HERE, "fixtures_program_spans",
                        "serve_chat_steps.xplane.pb")
    run = types.SimpleNamespace(
        trace_dir=path, reduction=trace.reduce_trace(path, []),
        log=lambda m: None, config=TINY, counters={}, peaks=PEAKS)
    assert run.reduction.device_events
    assert program_spans.load(run).named(["sched.decode.pages"])
    for name in ("window_pool_live_share.ide",
                 "window_pages_freed_per_step.ide",
                 "paged_attention_roofline.ide",
                 "paged_attention_busy_share.ide",
                 "chunk_attention_busy_share.ide"):
        params = manifest.load_layer_metric(name)
        reader = manifest.plugin("layer_metrics", params["reader"])
        assert reader.read(run, params) is None, name


def test_the_new_cell_reports_every_metric_the_issue_names():
    names = {m["name"] for m in manifest.cell_metrics(MANIFEST, CELL,
                                                      "per_layer")}
    issue = {
        "batch_occupancy.ide", "kv_pool_live_share.ide",
        "device_idle_share.ide", "sched_host_ms_mean.ide",
        "step_idle_before_dispatch.ide", "step_idle_in_flight.ide",
        "step_idle_after_fetch.ide", "prefill_padding_share.ide",
        "serve_mfu.ide", "moe_gmm_roofline.ide", "moe_gmm_busy_share.ide",
        "window_pool_live_share.ide", "window_pages_freed_per_step.ide",
        "paged_attention_roofline.ide", "paged_attention_busy_share.ide",
        "chunk_attention_busy_share.ide"}
    assert issue <= names
    # beside them the start-up ones, which list every cell, and no other
    assert all(n.startswith("setup_") for n in names - issue)
    assert len(names - issue) == 6
    assert [m["name"] for m in manifest.cell_metrics(
        MANIFEST, CELL, "end_to_end")] == ["serve_tokens_per_s", "setup_s"]
    for name in names:
        params = manifest.load_layer_metric(name)
        manifest.plugin("layer_metrics", params["reader"])
    assert len(MANIFEST["workloads"]) == 7
    assert all(c["chips"] == 1 for c in MANIFEST["workloads"])


# the six start-up metrics as PR 40 left them: name -> (unit, source)
SETUP_METRICS = {"setup_import_s": ("s", "program_span"),
                 "setup_engine_s": ("s", "program_span"),
                 "setup_trace_lower_s": ("s", "program_span"),
                 "setup_compile_load_s": ("s", "program_span"),
                 "setup_first_run_s": ("s", "program_span"),
                 "setup_programs_compiled": ("programs", "program_counter")}


@pytest.mark.parametrize("name", sorted(SETUP_METRICS))
def test_the_start_up_metrics_list_every_cell(name):
    """What `test_benchmark_setup_record.py::
    test_manifest_entry_lists_the_six_cells` held for six cells (its six
    cases are strict xfails since the seventh, tests/conftest.py): the
    WHOLE entry as PR 40 left it, every field of it, its list naming
    every cell of the manifest in the manifest's order, however many
    there are."""
    cells = [c["name"] for c in MANIFEST["workloads"]]
    assert cells[-1] == CELL
    assert sorted(m["name"] for m in MANIFEST["per_layer"]
                  if m["name"].startswith("setup_")) == \
        sorted(SETUP_METRICS)
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    unit, source = SETUP_METRICS[name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": "engine start-up",
                     "moves": "setup_s", "workloads": cells}
    for cell in cells:
        assert entry in manifest.cell_metrics(MANIFEST, cell, "per_layer")


# ------------------------------------------------- the runner, end to end
@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    copy = tmp_path_factory.mktemp("benchmark_copy_mellum2")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), copy)
    shutil.copytree(os.path.join(REPO, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    benchmark_rehearsal.add_tiny_files(str(copy), TINY_DIR)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(copy), REPO]),
               TMPDIR=str(copy))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "benchmark_rehearsal.py"),
         "tiny-mellum2.ide:0", "tiny-mellum2.ide:1"],
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
def test_serve_runner_rehearsal_on_a_tiny_mellum2_cell(rehearsal, trace_on):
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
    # the runner's counters are the FIRST group's: the full layers'
    assert r["counters"]["live_kv_pages_read"] > 0
    assert r["counters"]["pages"] == TINY["inference"]["num_pages"][0]


def test_the_cpu_trace_has_the_spans_metrics_and_no_kernel_event(rehearsal):
    """Off the chip the walk is its XLA path and the trace has no device
    plane: the two device metrics are left out; the windowed pool's
    share and the pages it gave back, from the program's spans, are
    there, and the mechanism is on the path."""
    per_layer = rehearsal[1]["per_layer"]
    assert set(per_layer) == {"tiny_mellum2_window_live_share",
                              "tiny_mellum2_window_freed"}
    assert 0 < per_layer["tiny_mellum2_window_live_share"]["value"] <= 100
    assert per_layer["tiny_mellum2_window_freed"]["value"] > 0
