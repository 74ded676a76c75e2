"""The plain reference against models/gpt2.py at a tiny size, and the
control: the reference computed one precision below bfloat16 has to
come out as NOT correct under the limits the tiny configurations
state, while bfloat16 passes them."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import gpt2 as family
from benchmark.models import gpt2_reference as reference

TINY = os.path.join(os.path.dirname(__file__), "tiny", "configs")
MODEL = {"n_layer": 2, "n_embd": 64, "n_head": 4, "n_positions": 128,
         "padded_vocab_size": 512}
SEEDS = [5, 2 ** 31 + 77, 123456789]


def _config(name):
    with open(os.path.join(TINY, name + ".json")) as f:
        return json.load(f)


def _program(seed):
    from deepspeed_tpu.models import gpt2
    cfg = gpt2.GPT2Config(vocab_size=512, max_seq_len=128, n_layers=2,
                          n_heads=4, d_model=64, use_flash_attention=False,
                          loss_chunk=0, remat=False)
    return cfg, gpt2.init_params(cfg, seed=seed)


@pytest.mark.parametrize("seed", SEEDS)
def test_weights_are_the_programs_own_recipe(seed):
    _, params = _program(seed)
    w = reference.draw_weights(MODEL, seed)
    assert np.array_equal(w["wte"], np.asarray(params["wte"]))
    assert np.array_equal(w["wpe"], np.asarray(params["wpe"]))
    for i, block in enumerate(params["blocks"]):
        for ours, group, leaf in (("qkv_w", "attn", "qkv_kernel"),
                                  ("proj_w", "attn", "proj_kernel"),
                                  ("fc_w", "mlp", "fc_kernel"),
                                  ("fc2_w", "mlp", "proj_kernel")):
            assert np.array_equal(w["layers"][ours][i],
                                  np.asarray(block[group][leaf]))


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_logits_and_loss_match_the_program_in_float32(seed):
    from deepspeed_tpu.models import gpt2
    cfg, params = _program(seed)
    ids = np.random.default_rng(seed).integers(0, 512, (2, 96),
                                               dtype=np.int32)
    hidden = gpt2.forward_hidden(params, jnp.asarray(ids), cfg)
    want = np.asarray(hidden @ params["wte"].T)
    w = jax.tree_util.tree_map(jnp.asarray,
                               reference.draw_weights(MODEL, seed))
    positions = np.tile(np.arange(96, dtype=np.int32), (2, 1))
    got = np.asarray(reference.logits_at(w, jnp.asarray(ids),
                                         jnp.asarray(positions), 4))
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()
    loss = float(gpt2.lm_loss(params, jnp.asarray(ids), jnp.asarray(ids),
                              cfg, train=False))
    ref = reference.two_steps(MODEL, seed, [ids, ids], {
        "lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8}, stride=4)
    assert ref["losses"][0] == pytest.approx(loss, abs=1e-5)
    assert ref["losses"][1] < ref["losses"][0]     # the update helps
    moved = np.abs(ref["after"]["wte"] - ref["before"]["wte"])
    assert moved.max() == pytest.approx(1e-4, rel=1e-2)   # lr * sign(g)


def _verdict(checks):
    return all(value <= limit for value, limit in checks.values())


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_control_is_not_correct_and_bfloat16_is(seed):
    config = _config("tiny-serve")
    sound = family.serve_check(config, seed, rounding="bfloat16")
    control = family.serve_check(config, seed, rounding="fp8")
    assert _verdict(sound), sound
    assert not _verdict(control), control
    for name in sound:
        assert control[name][0] > 3 * sound[name][0]


@pytest.mark.parametrize("seed", SEEDS)
def test_training_control_is_not_correct_and_bfloat16_is(seed):
    config = _config("tiny-train")
    rng = np.random.default_rng(seed)
    batches = [rng.integers(0, 512, (8, 128), dtype=np.int32)
               for _ in range(2)]
    sound = family.train_check(config, seed, batches, rounding="bfloat16")
    control = family.train_check(config, seed, batches, rounding="fp8")
    assert _verdict(sound), sound
    assert not _verdict(control), control
    name = "update_sign_disagreement"
    assert control[name][0] > 3 * sound[name][0]


def test_counts_of_operations_and_bytes():
    model = {"n_layer": 24, "n_embd": 1024, "n_head": 16,
             "n_positions": 1024, "padded_vocab_size": 50304}
    assert family.param_count(model) == 354871296
    # 6 (N - position table) + 6 L s d, causal attention counted once
    assert family.train_flops_per_token(model, 1024) == \
        6 * 353822720 + 6 * 24 * 1024 * 1024
    assert family.flash_attention_flops(model, 20, 1024) == \
        20 * 24 * 6 * 1024 * 1024 * 1024
    assert family.paged_attention_bytes(model, 16, 1) == 1572864
    # 2 for each weight of the layers' matmuls; head and attention not
    assert family.serve_flops_per_token(model) == 2 * 24 * 12 * 1024 ** 2


def _greedy(config, w, prompt, want, rounding):
    """``want`` greedy tokens after ``prompt`` from the reference in
    ``rounding``: what a sound engine in that precision would serve."""
    n_head = config["model"]["n_head"]
    ids = np.zeros((1, config["model"]["n_positions"]), np.int32)
    ids[0, :len(prompt)] = prompt
    tokens = []
    for i in range(want):
        at = jnp.asarray([[len(prompt) - 1 + i]], jnp.int32)
        row = reference.logits_at(w, jnp.asarray(ids), at, n_head,
                                  rounding)
        tokens.append(int(np.asarray(row)[0, 0].argmax()))
        ids[0, len(prompt) + i] = tokens[-1]
    return tokens


@pytest.mark.parametrize("seed", SEEDS)
def test_served_tokens_under_another_requests_prompt_are_not_correct(seed):
    config = _config("tiny-serve")
    limit = config["check"]["served_token_deficit"]
    w = jax.tree_util.tree_map(
        jnp.asarray, reference.draw_weights(config["model"], seed))
    rng = np.random.default_rng(seed)
    served = []
    for n in (20, 33, 47):
        prompt = rng.integers(0, 512, n).astype(np.int32)
        served.append((prompt, _greedy(config, w, prompt, 6, "bfloat16")))
    sound = family.served_token_deficit(config, w, served)
    control = family.served_token_deficit(config, w, served, swap=True)
    assert sound <= limit < control, (sound, control)
    assert control > 3 * max(sound, 0.1 * limit)
    # and nothing served is not correct, not vacuously fine
    checks = family.serve_check(config, seed, rounding="bfloat16",
                                served=[])
    assert not _verdict(checks)


class _RenamedEngine:
    """The engine's public calls as the logits check drives them, with
    no method of the names the tap wraps."""
    num_slots = 2

    def prefill(self, slot, prompt):
        return 0

    def decode_step(self, tokens):
        return tokens

    def ensure_pages(self, slot, upto_tokens):
        return True

    def advance(self, slot):
        pass

    def free_slot(self, slot):
        pass


class _Engine(_RenamedEngine):
    """The same with the two methods that make the programs; a program
    returns logits of 8 entries as its last output."""

    def _get_prefill_fn(self, *key):
        return lambda *args: (None, None, 0, np.ones((1, 8)))

    def _get_decode_fn(self, *key):
        return lambda *args: (None, None, 0, np.ones((2, 1, 8)))

    def prefill(self, slot, prompt):
        return self._get_prefill_fn(len(prompt))(prompt)[2]

    def decode_step(self, tokens):
        self._get_decode_fn()(tokens)
        return tokens


def test_logits_are_tapped_through_public_calls_and_the_tap_removed():
    engine = _Engine()
    seqs = [np.arange(10, dtype=np.int32), np.arange(12, dtype=np.int32)]
    got = family.engine_logits(engine, seqs, [7, 9], decode_steps=3)
    assert [g.shape for g in got] == [(4, 8), (4, 8)]
    assert not vars(engine)            # the class's own methods again


def test_logits_out_of_reach_are_not_correct_and_do_not_crash():
    config = _config("tiny-serve")
    got = family.serve_engine_outputs(config, 5, _RenamedEngine())
    checks = family.serve_check(config, 5, got)
    assert np.isnan(checks["prefill_logits_rel_rms"][0])
    assert np.isnan(checks["decode_logits_rel_rms"][0])
    assert not _verdict(checks)
