"""The ``deepseek_v3`` family (Moonlight-16B-A3B) as the benchmark drives
it: the program's engine built through ``init_inference()`` from a
configuration file, the counts that price the serving step, the latent
page walk's and the grouped matmul's rooflines, and the output checks
against ``moonlight_reference``.

A configuration file's ``model`` section carries the published
``config.json`` keys (``num_hidden_layers`` as cut), plus
``padded_vocab_size`` (the rows the program holds; 163,840 is a multiple
of 128) and the ``assumed`` numbers of the weight recipe
(``expert_bias_std``, ``attn_in_scale``, ``attn_out_scale``,
``kv_norm_eps``). Serving only (``PERF.md`` section 4).

The serving check compares, on logits, what the engine's own programs
returned with the reference's full forward. Its inputs
(``serve_check_inputs``): one prompt in every prefill bucket, one of two
chunks, one of THREE chunks (past two of the largest bucket, so that
the third chunk's prefill reads two earlier chunks' latents from the
pages and the decode kernel walks many blocks) and one of a single
page, each followed by ``decode_steps`` forced tokens through
``decode_step``. The reference is given every sequence zero-padded to
the serving window (the model is causal), so that it compiles ONE
length.

The numbers are LFM2's (``lfm2.py`` says why each): with random weights
a token whose 6th and 7th router scores are nearly tied chooses another
expert under bfloat16 inputs than in float32, and its logits are then
off by much where the other positions are off by little. So
``prefill_logits_rel_rms`` (the WORST prompt's last position),
``decode_logits_rel_rms`` (pooled over all decode positions),
``decode_logits_rel_err_p10`` (per sequence the tenth percentile over
its decode positions of the position's own error, the worst sequence:
the error that EVERY position carries) and ``served_token_deficit``
over requests the scheduler retired in the window. ``serve_control``
computes the same numbers with the reference made wrong in one of
``CONTROLS``' ways.
"""
import numpy as np

from . import moonlight_reference as reference
from .jamba import (_deficit, engine_logits,
                    release)  # noqa: F401 - release is the family's too
from .lfm2 import (_logit_checks, _pooled_rel_rms,  # noqa: F401
                   row_rel_err)

CONTROLS = ("fp8_matmuls", "latent_one_precision_lower",
            "k_pe_left_out", "rotary_restarted_at_second_chunk",
            "kv_norm_skipped", "scale_of_128", "shared_expert_left_out",
            "five_of_six_experts", "scaling_factor_one",
            "expert_bias_ignored", "another_requests_prompt")


# ---------------------------------------------------------------- engines
def _program_config(config):
    import jax.numpy as jnp
    from deepspeed_tpu.models import deepseek_v3
    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[
        config["inference"]["dtype"]]
    held = config["model"].get("experts_held")
    return deepseek_v3.config_from_hf(
        config["model"], dtype=dtype,
        experts_held=tuple(held) if held else None)


def build_serve_engine(config, seed):
    """``deepspeed_tpu.init_inference()`` on the configuration's
    ``inference`` section; weights from ``seed``."""
    import deepspeed_tpu
    from deepspeed_tpu.models import deepseek_v3
    return deepspeed_tpu.init_inference(
        model=deepseek_v3.make_deepseek_v3_model(_program_config(config),
                                                 seed=seed),
        config={"inference": config["inference"]},
        seed=seed % (2 ** 31 - 1))


def build_train_engine(config, seed):
    raise NotImplementedError(
        "the Moonlight family is served, not trained: in training latent "
        "attention is a low-rank projection before ordinary attention "
        "(no cache, no absorbed form, no page kernel) and the training "
        "state of all 64 experts of a layer does not fit a chip")


# ----------------------------------------------------------------- counts
def _attention_weights(model):
    d, h = model["hidden_size"], model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, vd = model["kv_lora_rank"], model["v_head_dim"]
    return d * h * (nope + rope) + d * (rank + rope) + \
        rank * h * (nope + vd) + h * vd * d


def serve_flops_per_token(model):
    """Operations every served token needs, prompt or generated: 2 for
    each weight of the layers' matmuls it multiplies (an attention
    layer's four projections; the dense MLP of the leading layers; the
    router, the shared expert and the ``num_experts_per_tok`` routed
    experts a token is sent to in the others, the held experts' share
    of them). A floor: the head, which only a sampled position needs,
    and attention's scores and values are left out."""
    d, ff = model["hidden_size"], model["moe_intermediate_size"]
    layers = model["num_hidden_layers"]
    n_dense = min(model["first_k_dense_replace"], layers)
    first, past = reference.experts_held(model)
    share = (past - first) / model["n_routed_experts"]
    expert = (model["num_experts_per_tok"] * share + model[
        "n_shared_experts"]) * 3 * d * ff + d * model["n_routed_experts"]
    return 2.0 * (layers * _attention_weights(model) +
                  n_dense * 3 * d * model["intermediate_size"] +
                  (layers - n_dense) * expert)


def moe_gmm_flops(model, rows):
    """Operations of the expert layers' grouped matmuls for ``rows``
    routed rows (summed over the layers): 2 for each weight of the
    row's expert, gate, up and down."""
    return 2.0 * rows * 3 * model["hidden_size"] * \
        model["moe_intermediate_size"]


def moe_gmm_bytes(model, rows, experts_hit, itemsize=2):
    """Bytes the grouped matmuls must move at the least: the three
    matrices of each (expert, layer) pair HIT, once, and every row in
    and out of both matmuls."""
    d, ff = model["hidden_size"], model["moe_intermediate_size"]
    return itemsize * (experts_hit * 3 * d * ff + rows * (2 * d + 3 * ff))


def paged_attention_bytes(model, page_size, pages, itemsize=2):
    """Bytes the decode steps' attention must read at the least for
    ``pages`` live pages (summed over slots and steps): each token's
    ``kv_lora_rank + qk_rope_head_dim`` USEFUL values in every layer.
    The pool pads a row to whole lanes (576 -> 640); an implementation
    that reads the padding reads more than this, never less, so the
    share cannot pass 100%."""
    return float(pages) * page_size * model["num_hidden_layers"] * \
        (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * itemsize


# ----------------------------------------------------------------- checks
def serve_check_inputs(config, seed):
    """Seeded prompts and forced continuations (the module docstring's
    list). -> (sequences, prompt lengths)."""
    spec = config["check"]
    rng = np.random.default_rng([seed, 0xC0FFEE])
    buckets = config["inference"]["prefill_buckets"]
    vocab = config["model"]["padded_vocab_size"]
    page = config["inference"]["kv_block_size"]
    lows = [1] + [b + 1 for b in buckets[:-1]]
    lens = [int(rng.integers(max(lo, hi // 2), hi))
            for lo, hi in zip(lows, buckets)]
    edge, half = buckets[-1], max(1, buckets[0] // 2)
    lens.append(int(rng.integers(edge + half, edge + buckets[0])))
    lens.append(int(rng.integers(2 * edge + half, 2 * edge + buckets[0])))
    lens.append(int(rng.integers(max(1, page // 2), page)))
    assert max(lens) + spec["decode_steps"] < \
        config["inference"]["max_seq_len"]
    return [rng.integers(0, vocab, n + spec["decode_steps"]).astype(
        np.int32) for n in lens], lens


def serve_engine_outputs(config, seed, engine):
    """What the engine (all slots free) gives on the check's inputs."""
    sequences, lens = serve_check_inputs(config, seed)
    return engine_logits(engine, sequences, lens,
                         config["check"]["decode_steps"])


def _padded(config, ids):
    """``ids`` zero-padded to the serving window (the model is causal:
    what follows a position changes nothing before it), so that the
    reference compiles ONE length for every sequence it is given."""
    out = np.zeros((config["inference"]["max_seq_len"],), np.int32)
    out[:len(ids)] = ids
    return out


def _at(config, seed, sequences, positions, **wrong):
    """The reference's logits of each sequence, padded to the window,
    at its positions; the positions padded to one count likewise (the
    head's program compiles once)."""
    most = max(len(p) for p in positions)
    filled = [np.concatenate([p, np.zeros((most - len(p),), np.int64)])
              for p in positions]
    out = reference.forward_many(
        config["model"], seed, [_padded(config, s) for s in sequences],
        filled, **wrong)
    routing = None
    if isinstance(out, tuple):
        out, routing = out
    logits = [np.asarray(x)[:len(p)] for x, p in zip(out, positions)]
    return logits if routing is None else (logits, routing)


def reference_logits(config, seed, sequences, prompt_lens, **wrong):
    """The reference's full forward over each whole sequence (prompt
    and forced continuation), read at the prompt's last position and
    after each fed token. ``wrong``: keyword arguments of
    ``reference.forward_many`` that make a control of it."""
    steps = config["check"]["decode_steps"]
    positions = [np.arange(n - 1, n + steps) for n in prompt_lens]
    return _at(config, seed, sequences, positions, **wrong)


def served_token_deficit(config, seed, served, swap=False):
    """How far the scheduler's tokens lie from the reference's choice
    (``jamba.served_token_deficit`` says how it is counted), the
    largest over all tokens. ``swap``: each request's tokens judged
    under the NEXT request's prompt."""
    order = list(served)
    if swap:
        order = [(order[(i + 1) % len(order)][0], tokens)
                 for i, (_, tokens) in enumerate(order)]
    window = config["inference"]["max_seq_len"]
    # a swapped pair may be longer than the window: judge what fits
    order = [(prompt, tokens[:window - len(prompt)])
             for prompt, tokens in order]
    ids = [np.asarray(list(prompt) + list(tokens[:-1]), np.int32)
           for prompt, tokens in order]
    positions = [np.arange(len(prompt) - 1, len(prompt) + len(tokens) - 1)
                 for prompt, tokens in order]
    logits = _at(config, seed, ids, positions)
    return max(_deficit(got, tokens)
               for got, (_, tokens) in zip(logits, order))


def serve_check(config, seed, got=None, served=None, rounding=None,
                ref=None):
    """``{name: (value, limit)}``. Prefill (the check's prompts), then
    decode through the latent pages (``got``, from
    ``serve_engine_outputs``), against the reference's full forward at
    the same positions, on logits; without ``got``, the reference
    computed in ``rounding`` stands in the engine's place. And the
    tokens of ``served`` requests, as the scheduler gave them under
    load, against the reference's choice at each; no request to look
    at is not correct. ``ref``: the reference's logits where the caller
    has them already."""
    spec = config["check"]
    sequences, lens = serve_check_inputs(config, seed)
    if ref is None:
        ref = reference_logits(config, seed, sequences, lens)
    if got is None:
        got = reference_logits(config, seed, sequences, lens,
                               rounding=rounding)
    checks = _logit_checks(spec, got, ref)
    if served is not None:
        checks["served_token_deficit"] = (
            served_token_deficit(config, seed, served) if served
            else float("nan"), spec["served_token_deficit"])
    return checks


def control_kwargs(config, control):
    """What makes ``reference.forward_many`` the control of that name
    (those that are one wrong keyword)."""
    model = config["model"]
    return {
        "fp8_matmuls": {"rounding": "fp8"},
        "latent_one_precision_lower": {"latent_rounding": "fp8"},
        "k_pe_left_out": {"k_pe": False},
        "rotary_restarted_at_second_chunk": {
            "rope_restart_at": config["inference"]["prefill_buckets"][-1]},
        "kv_norm_skipped": {"kv_norm": False},
        "scale_of_128": {"scale_width": model["qk_nope_head_dim"]},
        "shared_expert_left_out": {"shared": False},
        "five_of_six_experts": {"top_k": model["num_experts_per_tok"] - 1},
        "scaling_factor_one": {"scaling": 1.0},
        "expert_bias_ignored": {"use_bias": False},
    }[control]


def serve_control(config, seed, control, served=None, ref=None):
    """The check's numbers with the reference made wrong in the way
    ``control`` names standing in the engine's place, ``{name: (value,
    limit)}``: ``fp8_matmuls`` (operands of every weight matmul rounded
    to e4m3's 4 significant bits), ``latent_one_precision_lower`` (what
    a token keeps rounded likewise), ``k_pe_left_out`` (the shared rope
    key left out of the scores), ``rotary_restarted_at_second_chunk``
    (positions counted from 0 again at the largest bucket's edge),
    ``kv_norm_skipped``, ``scale_of_128`` (the softmax scaled by the
    root of ``qk_nope_head_dim`` alone), ``shared_expert_left_out``,
    ``five_of_six_experts``, ``scaling_factor_one``,
    ``expert_bias_ignored``, ``another_requests_prompt`` (each served
    request's tokens judged under the next one's prompt)."""
    spec = config["check"]
    sequences, lens = serve_check_inputs(config, seed)
    if control == "another_requests_prompt":
        return {"served_token_deficit": (
            served_token_deficit(config, seed, served, swap=True),
            spec["served_token_deficit"])}
    if ref is None:
        ref = reference_logits(config, seed, sequences, lens)
    got = reference_logits(config, seed, sequences, lens,
                           **control_kwargs(config, control))
    return _logit_checks(spec, got, ref)
