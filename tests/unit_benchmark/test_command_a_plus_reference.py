"""The Command A+ family of the benchmark at a tiny size on the CPU: the
configuration file against the catalog row and its share's arithmetic,
the backlog's sizing, the share tied to the uncut layer, the serving
check's controls, the counts, the two new readers (on made-up spans and
traces, and on a trace of a program from before them), what every
start-up metric holds for ANY number of cells, and the serve runner end
to end on a tiny cell dropped into a copy of the benchmark."""
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
from benchmark.layer_metrics import (chunk_attention_roofline,
                                     grouped_paged_attention_roofline,
                                     kernel_busy_share, program_spans)
from benchmark.models import command_a_plus, command_a_plus_reference
from benchmark.models.jamba_controls import served_requests
from benchmark.traffic import requests_balanced

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY_DIR = os.path.join(HERE, "tiny_command_a_plus")
with open(os.path.join(TINY_DIR, "configs",
                       "tiny-command-a-plus.json")) as f:
    TINY = json.load(f)

SLIDING, FULL = "sliding_attention", "full_attention"
# the catalog row command-a-plus-05-2026's `config`, as read from the
# model's public config.json
PUBLISHED = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096,
    "layer_norm_eps": 1e-05, "layer_switch": 4,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 8, "logit_scale": 1,
    "max_position_embeddings": 200000, "model_type": "cohere2_moe",
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 262144}
REDUCED = ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
SHARE_KEYS = ("router_num_experts", "experts_held", "padded_vocab_size",
              "qk_init_std")
MANIFEST = manifest.load_manifest()
ENTRY = [c for c in MANIFEST["configs"] if manifest.load_config(
    MANIFEST, c["name"])["family"] == "command_a_plus"]
CELL = "command-a-plus-serve.rag"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _config():
    return manifest.load_config(MANIFEST, ENTRY[0]["name"])


def test_the_configuration_is_the_catalog_rows_but_for_its_share():
    assert len(ENTRY) == 1
    entry, config = ENTRY[0], _config()
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/"
        "main/config.json")
    assert entry["reduced"] == config["reduced"] == REDUCED
    # every key of the row at the top level of the file AND in `model`,
    # letter for letter apart from the four that the cut cuts
    cut = dict(PUBLISHED, num_hidden_layers=4,
               layer_types=PUBLISHED["layer_types"][:4], num_experts=16,
               vocab_size=32768)
    assert {k: config[k] for k in PUBLISHED} == cut
    assert {k: v for k, v in config["model"].items()
            if k not in SHARE_KEYS} == cut
    assert set(config["model"]) == set(cut) | set(SHARE_KEYS)
    # no width is cut, nor the experts a token, the window, the heads
    for key in ("hidden_size", "intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "num_shared_experts",
                "sliding_window", "rope_theta"):
        assert config[key] == PUBLISHED[key] and key not in REDUCED
    # one whole period, the floors of 8 experts and an eighth of the rows
    assert config["layer_types"] == [SLIDING, SLIDING, SLIDING, FULL]
    model = config["model"]
    assert model["router_num_experts"] == PUBLISHED["num_experts"]
    assert model["experts_held"] == [0, 16] and model["num_experts"] == 16
    assert model["padded_vocab_size"] == model["vocab_size"] == \
        PUBLISHED["vocab_size"] // 8 == 256 * 128
    assert config["published"]["num_hidden_layers"] == 32
    assert config["published"]["num_experts"] == 128
    assert config["published"]["vocab_size"] == 262144
    for said in ("Eight chips share each layer", "16 a chip",
                 "32,768 a chip", "FIRST group", "64 v5e chips"):
        assert said in config["deployment"], said
    assert {"shared_experts", "router", "window", "positions",
            "prefix_dense", "rms_norm_eps", "vision_tower", "precision",
            "weights"} <= set(config["assumed"])
    assert model["qk_init_std"] == 0.025
    assert "0.025 was fixed" in config["assumed"]["weights"]


def test_the_count_that_bears_out_the_widths_and_the_share():
    """218.3B in all and 25.0B a token, the family's published numbers,
    from the row's keys with every expert 4,096 wide; and what one chip
    of eight holds of four layers."""
    attention = 4096 * 16384 * 2 + 4096 * 1024 * 2
    assert attention == 142_606_336
    expert = 3 * 4096 * 4096
    assert expert == 50_331_648 and 4 * expert == 201_326_592
    outside = attention + 4 * expert + 4096 * 128 + 4096
    assert outside == 344_461_312
    embedding = 262144 * 4096
    whole = 32 * (outside + 128 * expert) + embedding + 4096
    a_token = 32 * (outside + 8 * expert) + embedding + 4096
    assert round(whole / 1e9, 1) == 218.3 and round(a_token / 1e9, 1) == 25.0
    uncut = dict(PUBLISHED, router_num_experts=128)
    assert command_a_plus_reference.param_count(uncut, held=False) == whole
    model = _config()["model"]
    assert outside + 16 * expert == 1_149_767_680
    held = command_a_plus_reference.param_count(model)
    assert held == 4 * 1_149_767_680 + 32768 * 4096 + 4096 == 4_733_292_544
    memory = _config()["memory"]
    # bfloat16 but for the four float32 routers
    assert memory["weights_bytes"] == 2 * held + 2 * 4 * 4096 * 128


def test_the_pools_follow_from_the_widths():
    config = _config()
    inference, memory = config["inference"], config["memory"]
    assert inference["max_seq_len"] == 32768
    assert inference["prefill_buckets"] == [512, 1024, 2048]
    assert inference["kv_block_size"] == 16 and inference["greedy"]
    assert inference["paged_attention_kernel"] == "auto"
    slots, (full, windowed) = inference["max_batch_size"], \
        inference["num_pages"]
    # a page: 16 tokens x 8 x 128 keys and as many values x 2 B, a layer
    assert memory["page_bytes"] == [65536, 196608] == [
        command_a_plus.page_bytes(config["model"], 16, sliding)
        for sliding in (False, True)] == [16 * 8 * 128 * 2 * 2 * layers
                                          for layers in (1, 3)]
    assert memory["pool_pages"] == inference["num_pages"]
    # 4,096 keys begin anywhere in a page: 257 pages a slot, and the
    # largest chunk in flight
    window, page = config["sliding_window"], inference["kv_block_size"]
    assert windowed == slots * (window // page + 1) + \
        inference["prefill_buckets"][-1] // page
    # the garbage page rides in each pool
    assert memory["pool_bytes"] == [(full + 1) * 65536,
                                    (windowed + 1) * 196608]
    assert {"why", "prefill_logits_rel_rms", "decode_logits_rel_rms",
            "decode_logits_rel_err_p10", "served_token_deficit",
            "decode_steps"} <= set(config["check"])
    assert 13.5e9 <= memory["memory_peak_bytes"] <= 15.0e9
    assert "why" in memory
    cells = [c for c in MANIFEST["workloads"]
             if c["config"] == ENTRY[0]["name"]]
    assert [c["name"] for c in cells] == [CELL]
    assert cells[0]["chips"] == 1 and cells[0]["traffic"] == "rag"


def test_lengths_stay_inside_the_mix_and_the_serving_window():
    config = _config()
    workload = manifest.load_workload(CELL)
    mix = workload["traffic"]
    # ISSUE 50's mix, letter for letter
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 8192, "sigma": 0.5, "min": 2048,
        "max": 24576}
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.5, "min": 64,
        "max": 768}
    assert workload["lead_s"] in (60, 90) and workload["drain_cap_s"] == 0
    assert workload["trace_seconds"] == 5 and not workload["latency"]
    assert workload["runner"] == "serve"
    assert mix["generator"] == "requests_balanced"
    assert mix["arrivals"]["process"] == "backlog"
    vocab = config["model"]["padded_vocab_size"]
    due, prompts, outputs = requests_balanced.generate(mix, 3, 40.0, vocab,
                                                       cycle_s=51.0)
    lens = np.array(list(map(len, prompts)))
    assert len(due) == mix["arrivals"]["queued"] == 1200 and not due.any()
    # the ids come from the rows held
    assert max(int(p.max()) for p in prompts) < 32768
    assert lens.min() >= 2048 and lens.max() <= 24576
    assert outputs.min() >= 64 and outputs.max() <= 768
    assert (lens + outputs).max() <= 25344 < \
        config["inference"]["max_seq_len"]
    assert outputs.max() <= config["inference"]["max_new_tokens"]
    # every prompt past half a window, nine in ten past a whole one
    assert 0.88 < (lens > 4096).mean() < 0.94
    assert 8900 < lens.mean() < 9500 and 270 < outputs.mean() < 310
    largest = config["inference"]["prefill_buckets"][-1]
    assert 4.5 < np.ceil(lens / largest).mean() < 5.3
    # the backlog outlasts a program four times as fast as the cell's
    rate = mix["arrivals"]["sized_at_tokens_per_s"]
    served_s = workload["lead_s"] + MANIFEST["run_seconds"]
    assert (lens.sum() + outputs.sum()) / served_s >= 4 * rate


def test_the_family_trains_nothing():
    with pytest.raises(NotImplementedError, match="served, not trained"):
        command_a_plus.build_train_engine(TINY, 0)


def test_a_checkout_from_before_the_family_fails_with_one_sentence(
        monkeypatch):
    """What the parent commit does with the new cell: the benchmark's
    files are laid over it, ``deepspeed_tpu.models.cohere2_moe`` is not
    there, and the run ends at once, not in a traceback."""
    import deepspeed_tpu.models
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.models.cohere2_moe",
                        None)
    monkeypatch.delattr(deepspeed_tpu.models, "cohere2_moe", raising=False)
    with pytest.raises(SystemExit, match="has no models/cohere2_moe.py and "
                       "cannot run the command_a_plus family"):
        command_a_plus.build_serve_engine(TINY, 0)


# ------------------------------------------------- the share and the layer
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The reference's layer with every expert held, against its eight
    shares ``(0, 2) ... (14, 16)``: ``x``, attention and the shared
    experts' mean, which every chip computes alike, counted once (read
    from a share whose routed experts' down matrices are zeroed)."""
    import jax.numpy as jnp
    model = TINY["model"]
    assert model["router_num_experts"] == 16
    items = json.dumps(model, sort_keys=True)
    wrong = tuple(sorted((k, v) for k, v in
                         command_a_plus_reference.WRONG.items()
                         if k != "experts_held"))
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (48, model["hidden_size"])), jnp.float32)

    def layer(i, held, routed=True):
        w = command_a_plus_reference.draw_layer(model, 11, i, held)
        if not routed:
            w["w2"] = jnp.zeros_like(w["w2"])
        return np.asarray(command_a_plus_reference._layer(
            w, x, items, model["layer_types"][i], wrong, held)[0]), w

    for i in (0, 3):                  # a sliding layer and the full one
        whole, w = layer(i, (0, 16))
        alike, _ = layer(i, (0, 2), routed=False)
        shares = [layer(i, (e, e + 2))[0] for e in range(0, 16, 2)]
        parts = [share - alike for share in shares]
        np.testing.assert_allclose(alike + sum(parts), whole, atol=2e-5)
        # the routed parts are not nothing, and no two shares' are alike
        assert min(np.abs(part).max() for part in parts) > 1e-3
        # a token whose every chosen expert lies elsewhere adds nothing
        chosen = np.asarray(command_a_plus_reference.route(
            model, w, command_a_plus_reference.layer_norm(
                x, w["norm"], model["layer_norm_eps"]))[0])
        elsewhere = ~(chosen < 2).any(-1)
        assert elsewhere.any() and not elsewhere.all()
        np.testing.assert_allclose(parts[0][elsewhere], 0.0, atol=2e-6)
        assert np.abs(parts[0][~elsewhere]).min(0).max() > 1e-4
        # a share draws what the whole layer holds of its experts
        mine = command_a_plus_reference.draw_layer(model, 11, i, (6, 8))
        for name in ("w1", "w3", "w2"):
            np.testing.assert_array_equal(mine[name], w[name][6:8])


@pytest.fixture(scope="module")
def sound_and_controls():
    """One tiny engine, served and checked; then every control."""
    seed = 3000000019                 # more than 32 signed bits hold
    engine = command_a_plus.build_serve_engine(TINY, seed)
    served = served_requests(TINY, seed, engine, answers=12)
    got = command_a_plus.serve_engine_outputs(TINY, seed, engine)
    freed = engine.page_groups[1].freed
    command_a_plus.release(engine.params, engine.kv.k, engine.kv.v)
    gone = all(a.is_deleted() for kv in engine.kv_groups
               for a in kv.buffers())
    sequences, lens = command_a_plus.serve_check_inputs(TINY, seed)
    ref = command_a_plus.reference_logits(TINY, seed, sequences, lens)
    out = {"sound": command_a_plus.serve_check(TINY, seed, got, served,
                                               ref=ref),
           "freed": freed, "pools_released": gone,
           "bfloat16_matmuls": command_a_plus.serve_check(
               TINY, seed, rounding="bfloat16", ref=ref)}
    for control in command_a_plus.CONTROLS:
        out[control] = command_a_plus.serve_control(TINY, seed, control,
                                                    served, ref=ref)
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
                         command_a_plus.CONTROLS + ("bfloat16_matmuls",))
def test_each_control_reads_beyond_a_limit(sound_and_controls, control):
    """The shared experts summed, 3 of 4 shared, 7 of 8 routed, the
    chosen not renormalised, softmax for sigmoid, the next chip's share,
    the block made sequential, RMS norm for LayerNorm, the full layers
    rotated, the sliding layers not rotated, a window a page short, the
    window ignored, fp8 matmuls, keys and values in fp8, another
    request's prompt (and, the tiny configuration stating float32,
    bfloat16 matmuls): not correct, by one of the check's limits."""
    checks = sound_and_controls[control]
    assert any(not value <= limit for value, limit in checks.values()), \
        checks


def test_every_control_of_the_issue_has_a_name():
    assert len(command_a_plus.CONTROLS) == 15
    for control in command_a_plus.CONTROLS[:-1]:
        wrong = command_a_plus.control_kwargs(_config(), control)
        assert set(wrong) <= set(command_a_plus_reference.WRONG)
    assert command_a_plus.control_kwargs(
        _config(), "the_next_chips_share") == {"experts_held": (16, 32)}
    assert command_a_plus.control_kwargs(
        _config(), "window_a_page_short") == {"window": 4080}


def test_the_checks_prompts_reach_four_chunks_past_the_window():
    for config in (TINY, _config()):
        sequences, lens = command_a_plus.serve_check_inputs(config, 5)
        buckets = config["inference"]["prefill_buckets"]
        assert len(lens) == len(buckets) + 3
        assert all(lo < n <= hi for n, lo, hi in
                   zip(lens, [0] + buckets[:-1], buckets))
        page, four, two = lens[len(buckets):]
        assert buckets[-1] < two < 2 * buckets[-1]
        assert 3 * buckets[-1] < four < 4 * buckets[-1]
        assert page < config["inference"]["kv_block_size"]
        # the third and the fourth chunk start at or past the window's
        # end: the sliding table has slid before each
        assert 2 * buckets[-1] >= config["model"]["sliding_window"]
        steps = config["check"]["decode_steps"]
        assert [len(s) - n for s, n in zip(sequences, lens)] == \
            [steps] * len(lens)
        assert steps >= 2 * config["inference"]["kv_block_size"]
        vocab = config["model"]["padded_vocab_size"]
        assert max(int(s.max()) for s in sequences) < vocab
    assert 6144 < four < 6656 and 2048 < two < 2600


def test_no_request_to_look_at_is_not_correct():
    checks = command_a_plus.serve_check(TINY, 5, rounding="bfloat16",
                                        served=[])
    value, limit = checks["served_token_deficit"]
    assert not value <= limit


def test_counts_of_operations_and_bytes():
    model = _config()["model"]
    attention, expert = 142_606_336, 50_331_648
    # one routed expert a token lands here: 8 x 16 / 128
    assert command_a_plus.serve_flops_per_token(model) == \
        2 * 4 * (attention + 4096 * 128 + (4 + 1) * expert)
    assert command_a_plus.serve_flops_per_token(model) * 1e-9 == \
        pytest.approx(3.158, abs=0.001)
    # the whole layer held: all 8 of a token's experts land
    whole = dict(model, experts_held=[0, 128], num_experts=128)
    assert command_a_plus.serve_flops_per_token(whole) == \
        2 * 4 * (attention + 4096 * 128 + (4 + 8) * expert)
    # 100.7 MFLOP a routed row, 100.7 MB an (expert, layer) pair hit
    assert command_a_plus.moe_gmm_flops(model, 1) == 2 * expert == \
        100_663_296
    assert command_a_plus.moe_gmm_bytes(model, 0, 1) == 2 * expert
    rows, hit = 40, 4 * 16              # a decode step of 40 slots
    assert command_a_plus.moe_gmm_bytes(model, rows, hit) == \
        2 * (hit * expert + rows * (2 * 4096 + 3 * 4096))
    assert command_a_plus.paged_attention_bytes(model, 16, 1) == 65536
    assert command_a_plus.paged_attention_bytes(model, 16, 0, 1) == 196608
    assert command_a_plus.paged_attention_bytes(model, 16, 3, 2) == \
        3 * 65536 + 2 * 196608


def test_a_chunks_attention_is_priced_by_the_keys_it_must_visit():
    model = _config()["model"]
    a_key = 4 * 128 * 128               # 65.5 kFLOP a key, query and layer
    assert a_key == 65536
    # the first query of a request: one key in each of the four layers
    assert command_a_plus.chunk_attention_flops(model, 0, 1) == 4 * a_key
    # a first chunk: 1 + 2 + ... + 2,048 keys in every layer
    first = 2048 * 2049 // 2
    assert command_a_plus.chunk_attention_flops(model, 0, 2048) == \
        4 * a_key * first
    # a chunk that starts past the window: 4,096 keys a query in the
    # three sliding layers, every earlier key in the full one
    full = sum(range(8192 + 1, 8192 + 2048 + 1))
    assert command_a_plus.chunk_attention_flops(model, 8192, 2048) == \
        a_key * (3 * 2048 * 4096 + full)
    # one that crosses the window's end
    t = np.arange(3001, 5001)
    assert command_a_plus.chunk_attention_flops(model, 3000, 2000) == \
        a_key * (3 * np.minimum(t, 4096).sum() + t.sum())


# ---------------------------------------------------------------- readers
_CHUNK = ('%chunk_attention.{} = bf16[1,8,32768,128]{{3,2,1,0}} '
          'custom-call(s32[1]{{0}} %p), custom_call_target='
          '"tpu_custom_call"')
_WALK = ('%paged_attention_grouped.{} = f32[40,8,16,128]{{3,2,1,0}} '
         'custom-call(s32[40]{{0}} %p), custom_call_target='
         '"tpu_custom_call"')
_OTHER = "%fusion.7 = bf16[2048,4096]{1,0} fusion(bf16[2048,4096]{1,0} %x)"


def _made_up_run(kernel_s, chunks, launches=2, start_attr=True):
    """``launches`` runs of ``jit_prefill`` of 1 s, each with four chunk
    kernels of ``kernel_s`` (a layer each) and one other operation;
    one ``sched.prefill.chunk`` span for each of ``chunks`` ((start,
    tokens))."""
    events, modules, spans = [], [], []
    for i in range(launches):
        t = float(i)
        modules.append(("jit_prefill({})".format(i), t, t + 1.0))
        for j in range(4):
            start = t + 0.05 + j * kernel_s
            events.append((_CHUNK.format(j), _CHUNK.format(j), start,
                           start + kernel_s))
        events.append((_OTHER, _OTHER, t + 0.7, t + 0.8))
    for i, (start, tokens) in enumerate(chunks):
        t = 0.1 + 0.3 * i
        attrs = {"uid": i, "tokens": tokens, "padded": 2048,
                 "first": int(start == 0), "window_freed": 0}
        if start_attr:
            attrs["start"] = start
        spans.append(("sched.prefill.chunk", t, t + 0.01, attrs))
    plane = "/device:TPU:0"
    reduction = trace.Reduction({plane: events}, [], {plane: modules})
    return types.SimpleNamespace(
        reduction=reduction, config=_config(), log=lambda m: None,
        peaks=PEAKS, program_spans=program_spans.ProgramSpans(spans),
        counters={})


def test_the_chunks_roofline_prices_the_keys_each_chunk_must_visit():
    params = manifest.load_layer_metric("chunk_attention_roofline.rag")
    assert params["reader"] == "chunk_attention_roofline"
    chunks = [(0, 2048), (2048, 2048), (8192, 1500)]
    run = _made_up_run(0.010, chunks)
    model = run.config["model"]
    mean = sum(command_a_plus.chunk_attention_flops(model, s, n)
               for s, n in chunks) / 3
    # three chunks counted, two launches held whole, four kernels each
    assert chunk_attention_roofline.read(run, params) == pytest.approx(
        100 * (2 * mean / 197e12) / (2 * 4 * 0.010))
    # a program from before `start`: nothing to read, and no raise
    assert chunk_attention_roofline.read(
        _made_up_run(0.010, chunks, start_attr=False), params) is None
    # the kernel's share of the busy time, by its own name
    busy = manifest.load_layer_metric("chunk_attention_busy_share.rag")
    assert busy["reader"] == "kernel_busy_share"
    assert kernel_busy_share.read(run, busy) == pytest.approx(
        100 * 8 * 0.010 / (8 * 0.010 + 2 * 0.1))
    # the ide cell's file still looks for XLA's loop and finds none
    assert kernel_busy_share.read(run, manifest.load_layer_metric(
        "chunk_attention_busy_share.ide")) is None


def test_the_walks_roofline_prices_this_familys_pages():
    params = manifest.load_layer_metric("paged_attention_roofline.rag")
    assert params["reader"] == "grouped_paged_attention_roofline"
    events, modules, spans = [], [], []
    for i in range(2):
        t = float(i)
        modules.append(("jit_decode({})".format(i), t, t + 1.0))
        for j in range(4):
            events.append((_WALK.format(j), _WALK.format(j),
                           t + 0.1 * j, t + 0.1 * j + 0.004))
    for i in range(3):
        spans.append(("sched.decode.pages", 0.1 + 0.3 * i, 0.11 + 0.3 * i,
                      {"full_live": 24000, "window_live": 10000,
                       "window_pool": 10408, "window_freed": 1}))
    plane = "/device:TPU:0"
    run = types.SimpleNamespace(
        reduction=trace.Reduction({plane: events}, [], {plane: modules}),
        config=_config(), log=lambda m: None, peaks=PEAKS, counters={},
        program_spans=program_spans.ProgramSpans(spans))
    least = 2 * (24000 * 65536 + 10000 * 196608) / 819e9
    assert grouped_paged_attention_roofline.read(run, params) == \
        pytest.approx(100 * least / (2 * 4 * 0.004))


def test_the_readers_find_nothing_in_a_parents_trace_and_do_not_raise():
    """A trace of a program from before the share (GPT-2's serving
    steps: no ``moe.load`` span, no ``start`` on a chunk's span, no such
    kernel): each returns None, and the line leaves the metric out."""
    path = os.path.join(HERE, "fixtures_program_spans",
                        "serve_chat_steps.xplane.pb")
    run = types.SimpleNamespace(
        trace_dir=path, reduction=trace.reduce_trace(path, []),
        log=lambda m: None, config=TINY, counters={}, peaks=PEAKS)
    assert run.reduction.device_events
    for name in ("expert_rows_held_share.rag",
                 "chunk_attention_roofline.rag",
                 "chunk_attention_busy_share.rag",
                 "paged_attention_roofline.rag",
                 "window_pool_live_share.rag",
                 "moe_gmm_roofline.rag"):
        params = manifest.load_layer_metric(name)
        reader = manifest.plugin("layer_metrics", params["reader"])
        assert reader.read(run, params) is None, name


ISSUE_METRICS = {
    "batch_occupancy.rag", "kv_pool_live_share.rag",
    "device_idle_share.rag", "sched_host_ms_mean.rag",
    "step_idle_before_dispatch.rag", "step_idle_in_flight.rag",
    "step_idle_after_fetch.rag", "prefill_padding_share.rag",
    "serve_mfu.rag", "moe_gmm_roofline.rag", "moe_gmm_busy_share.rag",
    "window_pool_live_share.rag", "window_pages_freed_per_step.rag",
    "paged_attention_roofline.rag", "paged_attention_busy_share.rag",
    "chunk_attention_busy_share.rag", "expert_rows_held_share.rag",
    "chunk_attention_roofline.rag"}


def test_the_new_cell_reports_every_metric_the_issue_names():
    entries = {m["name"]: m for m in manifest.cell_metrics(
        MANIFEST, CELL, "per_layer")}
    assert ISSUE_METRICS <= set(entries)
    # beside them the start-up ones, which list every cell, and no other
    assert all(n.startswith("setup_") for n in set(entries) - ISSUE_METRICS)
    assert [m["name"] for m in manifest.cell_metrics(
        MANIFEST, CELL, "end_to_end")] == ["serve_tokens_per_s", "setup_s"]
    for name in ISSUE_METRICS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "serve_tokens_per_s"
    # a share of a roofline or of a peak is a percentage to raise
    for name in ("chunk_attention_roofline.rag", "moe_gmm_roofline.rag",
                 "paged_attention_roofline.rag", "serve_mfu.rag"):
        assert entries[name]["unit"] == "%" and \
            entries[name]["better"] == "higher"
    # the accepted entry and file of the ide cell's share stay as they were
    ide, = [m for m in MANIFEST["per_layer"]
            if m["name"] == "chunk_attention_busy_share.ide"]
    assert ide["workloads"] == ["mellum2-12b-a2.5b-serve.ide"]
    assert manifest.load_layer_metric("chunk_attention_busy_share.ide")[
        "patterns"][0].startswith("^%while")


def test_the_ide_cell_reports_what_its_issue_named_and_the_start_up():
    """What test_mellum2_reference.py held beside its count of cells."""
    ide = "mellum2-12b-a2.5b-serve.ide"
    names = {m["name"] for m in manifest.cell_metrics(MANIFEST, ide,
                                                      "per_layer")}
    issue = {n.rsplit(".", 1)[0] + ".ide" for n in ISSUE_METRICS} - {
        "expert_rows_held_share.ide", "chunk_attention_roofline.ide"}
    assert issue <= names and len(issue) == 16
    assert all(n.startswith("setup_") for n in names - issue)
    assert len(names - issue) == len(SETUP_METRICS)
    assert [m["name"] for m in manifest.cell_metrics(
        MANIFEST, ide, "end_to_end")] == ["serve_tokens_per_s", "setup_s"]


@pytest.mark.parametrize("name", sorted(ISSUE_METRICS))
def test_each_metric_of_the_cell_finds_its_reader_and_parameters(name):
    params = manifest.load_layer_metric(name)
    reader = manifest.plugin("layer_metrics", params["reader"])
    assert callable(reader.read)
    quantity = name.rsplit(".", 1)[0]
    own = os.path.join(REPO, "benchmark", "layer_metrics", name + ".json")
    # a parameter file of its own where the quantity's would read another
    # family's kernel; else the quantity's one file
    assert os.path.exists(own) == (quantity in (
        "paged_attention_roofline", "chunk_attention_busy_share"))


# what test_mellum2_reference.py pinned to seven cells (strict xfails
# since the eighth, tests/conftest.py), held for ANY number of cells
SETUP_METRICS = {"setup_import_s": ("s", "program_span"),
                 "setup_engine_s": ("s", "program_span"),
                 "setup_trace_lower_s": ("s", "program_span"),
                 "setup_compile_load_s": ("s", "program_span"),
                 "setup_first_run_s": ("s", "program_span"),
                 "setup_programs_compiled": ("programs", "program_counter")}


@pytest.mark.parametrize("name", sorted(SETUP_METRICS))
def test_a_start_up_metric_lists_every_cell_however_many(name):
    """The WHOLE entry as PR 40 left it, every field of it, its list
    naming every cell of the manifest in the manifest's order: nothing
    here counts the cells or names the last."""
    cells = [c["name"] for c in MANIFEST["workloads"]]
    assert len(set(cells)) == len(cells)
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


@pytest.mark.parametrize("cell", [c["name"] for c in MANIFEST["workloads"]])
def test_every_cell_has_one_chip_and_its_files(cell):
    """However many cells there are: each on one chip, its configuration,
    workload and every metric's reader found by name, `setup_s` and one
    other end-to-end metric reported."""
    entry = manifest.find_cell(MANIFEST, cell)
    assert entry["chips"] == 1
    assert manifest.load_config(MANIFEST, entry["config"])["chips"] == 1
    assert manifest.load_workload(cell)["runner"] in ("serve", "train")
    end_to_end = [m["name"] for m in manifest.cell_metrics(
        MANIFEST, cell, "end_to_end")]
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    for metric in manifest.cell_metrics(MANIFEST, cell, "per_layer"):
        params = manifest.load_layer_metric(metric["name"])
        manifest.plugin("layer_metrics", params["reader"])


# ------------------------------------------------- the runner, end to end
@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    copy = tmp_path_factory.mktemp("benchmark_copy_command_a_plus")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), copy)
    shutil.copytree(os.path.join(REPO, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    benchmark_rehearsal.add_tiny_files(str(copy), TINY_DIR)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(copy), REPO]),
               TMPDIR=str(copy))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "benchmark_rehearsal.py"),
         "tiny-command-a-plus.rag:0", "tiny-command-a-plus.rag:1"],
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
def test_serve_runner_rehearsal_on_a_tiny_command_a_plus_cell(rehearsal,
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
    # the runner's counters are the FIRST group's: the full layer's
    assert r["counters"]["live_kv_pages_read"] > 0
    assert r["counters"]["pages"] == TINY["inference"]["num_pages"][0]


def test_the_cpu_trace_has_the_share_that_landed_and_no_kernel_event(
        rehearsal):
    """Off the chip the trace has no device plane: the two rooflines are
    left out; the share of the routed rows that landed on the two experts
    held of sixteen, from the program's own ``moe.load`` spans, is there
    (an eighth under even routing), and so is the windowed pool's."""
    per_layer = rehearsal[1]["per_layer"]
    assert set(per_layer) == {"tiny_cap_rows_held_share",
                              "tiny_cap_window_live_share"}
    assert 4.0 < per_layer["tiny_cap_rows_held_share"]["value"] < 30.0
    assert 0 < per_layer["tiny_cap_window_live_share"]["value"] <= 100
