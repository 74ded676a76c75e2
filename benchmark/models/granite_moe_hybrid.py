"""The ``granite_moe_hybrid`` family (granite-4.0-h-small) as the
benchmark drives it: the program's engine built through
``init_inference()`` from a configuration file, the counts that price
the serving step and the state kernel's, the grouped matmul's and the
page walk's rooflines, and the output checks against
``granite_moe_hybrid_reference``.

A configuration file's ``model`` section carries the published
``config.json`` keys (``num_hidden_layers``, ``layer_types``,
``num_local_experts`` and ``vocab_size`` as cut), plus what says which
SHARE of a layer this chip holds (``router_num_experts``, the router's
width; ``experts_held``, the range of them here; ``padded_vocab_size``,
the rows of the tied embedding here, which the traffic draws its ids
from). Serving only (``PERF.md`` section 4).

The serving check compares, on logits, what the engine's own programs
returned with the reference's full forward. Its inputs
(``serve_check_inputs``): one prompt in every prefill bucket, one of a
single page, one of two chunks and one of three (state and convolution
tails cross a chunk's end twice, and the last chunk is padded) and one
whose second chunk is TWO tokens (its last position convolves two of
the first chunk's inputs out of the tail: at the long prompts' ends, a
hundred tokens past an edge, a lost tail has faded under the decay),
each followed by ``decode_steps`` forced tokens through ``decode_step``.
The reference is given every sequence zero-padded to a multiple of the
largest bucket (the model is causal), so that it compiles few lengths.

The numbers are LFM2's (``lfm2.py`` says why each): with random weights
a token whose 10th and 11th router logits are nearly tied chooses
another expert under bfloat16 inputs than in float32, and where one of
the two is held here its logits are then off. So
``prefill_logits_rel_rms`` (the WORST prompt's last position),
``decode_logits_rel_rms`` (pooled over all decode positions),
``decode_logits_rel_err_p10`` (per sequence the tenth percentile over
its decode positions of the position's own error, the worst sequence:
the error that EVERY position carries) and ``served_token_deficit``
over requests the scheduler retired in the window from reused slots.
``serve_control`` computes the same numbers with the reference made
wrong in one of ``CONTROLS``' ways.
"""
import numpy as np

from . import granite_moe_hybrid_reference as reference
from .jamba import (_deficit, _noted, engine_logits,
                    release)  # noqa: F401 - release is the family's too
from .lfm2 import _logit_checks

CONTROLS = ("fp8_matmuls", "state_bfloat16_every_step",
            "nine_of_ten_routed", "softmax_over_all_not_renormalised",
            "attention_multiplier_rsqrt", "residual_multiplier_one",
            "gate_after_norm", "decay_left_out", "previous_tenants_state",
            "second_chunk_from_zero_state", "second_chunk_zero_tails",
            "conv_bias_left_out", "another_requests_prompt")


# ---------------------------------------------------------------- engines
def _program():
    """``deepspeed_tpu.models.granite_moe_hybrid``; a checkout from
    before the family says so in one sentence, at once."""
    try:
        from deepspeed_tpu.models import granite_moe_hybrid
    except ImportError:
        import sys
        sys.exit("benchmark: this checkout's deepspeed_tpu has no "
                 "models/granite_moe_hybrid.py and cannot run the "
                 "granite_moe_hybrid family")
    return granite_moe_hybrid


def _program_config(config):
    import jax.numpy as jnp
    # the weights are drawn in the precision they are served in
    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[
        config["inference"]["dtype"]]
    return _program().config_from_hf(config["model"], dtype=dtype)


def build_serve_engine(config, seed):
    """``deepspeed_tpu.init_inference()`` on the configuration's
    ``inference`` section; weights from ``seed``."""
    program = _program()
    import deepspeed_tpu
    return deepspeed_tpu.init_inference(
        model=program.make_granite_moe_hybrid_model(_program_config(config),
                                                    seed=seed),
        config={"inference": config["inference"]},
        seed=seed % (2 ** 31 - 1))


def build_train_engine(config, seed):
    raise NotImplementedError(
        "the granite_moe_hybrid family is served, not trained: at 16 bytes "
        "a parameter no cut of it inside the guide's floors fits a chip, "
        "and neither the chunked scan nor the grouped matmul has a backward")


# ----------------------------------------------------------------- counts
def _layers(model):
    n = model["num_hidden_layers"]
    mamba = sum(reference.is_mamba(model, i) for i in range(n))
    return mamba, n - mamba


def _expert_weights(model):
    """One routed expert's three matrices (9.4M)."""
    return 3 * model["hidden_size"] * model["intermediate_size"]


def serve_flops_per_token(model):
    """Operations every served token needs HERE, prompt or generated: 2
    for each weight of the layers' matmuls it multiplies: a Mamba-2
    layer's ``in_proj`` and ``out_proj``, an attention layer's four
    projections, and after either the router, the shared MLP and the
    routed experts of its ``num_experts_per_tok`` that are held here,
    which is the share's EXPECTATION (10 x 36 / 72 = five a token and
    layer; ``expert_rows_held_share`` says what the seeded router really
    sent): 337 MFLOP a Mamba-2 layer, 217 an attention layer. A floor:
    the head, which only a sampled position needs, the recurrence and
    attention's scores and values are left out."""
    d = model["hidden_size"]
    first, past = reference.experts_held(model)
    landed = model["num_experts_per_tok"] * (past - first) / \
        reference.router_experts(model)
    after = d * reference.router_experts(model) + \
        3 * d * model["shared_intermediate_size"] + \
        landed * _expert_weights(model)
    di = reference.d_inner(model)
    mixer = d * (di + reference.conv_channels(model) +
                 model["mamba_n_heads"]) + di * d
    dh = d // model["num_attention_heads"]
    attn = 2 * d * model["num_attention_heads"] * dh + \
        2 * d * model["num_key_value_heads"] * dh
    mamba, attention = _layers(model)
    return 2.0 * (mamba * (mixer + after) + attention * (attn + after))


def ssd_step_bytes(model, slot_steps):
    """Bytes the decode step's state update has to move for
    ``slot_steps`` (slots whose state advanced, summed over steps), all
    Mamba-2 layers: the slot's float32 state (4.19 MB a layer) read and
    written once."""
    mamba, _ = _layers(model)
    return mamba * slot_steps * 2 * 4 * (
        model["mamba_d_state"] * reference.d_inner(model))


def moe_gmm_flops(model, rows):
    """Operations of the expert layers' grouped matmuls for ``rows``
    routed rows that landed on an expert held here (summed over the
    layers): 2 for each weight of the row's expert, gate, up and down
    (18.9 MFLOP a row)."""
    return 2.0 * rows * _expert_weights(model)


def moe_gmm_bytes(model, rows, experts_hit, itemsize=2):
    """Bytes the grouped matmuls must move at the least: the three
    matrices of each (expert, layer) pair HIT, once (18.9 MB), and every
    row in and out of both matmuls."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    return itemsize * (experts_hit * 3 * d * ff + rows * (2 * d + 3 * ff))


def paged_attention_bytes(model, page_size, pages, dtype_bytes=2):
    """Bytes decode attention has to read for ``pages`` live pages (a
    count summed over slots and steps), all attention layers: their keys
    and values (8 heads of 128: 4 KB a token and layer)."""
    _, attention = _layers(model)
    dh = model["hidden_size"] // model["num_attention_heads"]
    return pages * page_size * 2 * attention * \
        model["num_key_value_heads"] * dh * dtype_bytes


# ----------------------------------------------------------------- checks
def serve_check_inputs(config, seed):
    """Seeded prompts and forced continuations: one prompt in each
    prefill bucket (its length drawn inside the bucket), one of a single
    page, one of two chunks and one of three (longer than the largest
    bucket, the last chunk padded), one two tokens past the largest
    bucket, each followed by ``decode_steps`` tokens fed one at a time
    through the decode program. -> (sequences, prompt lengths)."""
    spec = config["check"]
    rng = np.random.default_rng([seed, 0xC0FFEE])
    buckets = config["inference"]["prefill_buckets"]
    page = config["inference"]["kv_block_size"]
    vocab = config["model"]["padded_vocab_size"]
    lows = [1] + [b + 1 for b in buckets[:-1]]
    lens = [int(rng.integers(max(lo, hi // 2), hi))
            for lo, hi in zip(lows, buckets)]
    lens.append(int(rng.integers(page // 2, page + 1)))
    for chunks in (1, 2):
        lens.append(int(rng.integers(
            chunks * buckets[-1] + buckets[0] // 2,
            chunks * buckets[-1] + buckets[0])))
    lens.append(buckets[-1] + 2)
    return [rng.integers(0, vocab, n + spec["decode_steps"]).astype(
        np.int32) for n in lens], lens


def serve_engine_outputs(config, seed, engine):
    """What the engine (all slots free) gives on the check's inputs."""
    if engine.state is not None:
        _noted.append(engine.state.arrays)
    sequences, lens = serve_check_inputs(config, seed)
    return engine_logits(engine, sequences, lens,
                         config["check"]["decode_steps"])


def _padded(config, ids):
    """``ids`` zero-padded to a multiple of the largest bucket (the
    model is causal: what follows a position changes nothing before
    it), so that the reference compiles few lengths."""
    step = config["inference"]["prefill_buckets"][-1]
    out = np.zeros((-(-len(ids) // step) * step,), np.int32)
    out[:len(ids)] = ids
    return out


def _at(config, seed, sequences, positions, **wrong):
    """The reference's logits of each sequence, padded, at its
    positions; the positions padded to one count likewise (the head's
    program compiles once)."""
    most = max(len(p) for p in positions)
    filled = [np.concatenate([p, np.zeros((most - len(p),), np.int64)])
              for p in positions]
    out = reference.forward_many(
        config["model"], seed, [_padded(config, s) for s in sequences],
        filled, **wrong)
    return [np.asarray(x)[:len(p)] for x, p in zip(out, positions)]


def reference_logits(config, seed, sequences, prompt_lens, **wrong):
    """The reference's full forward over each whole sequence (prompt
    and forced continuation), read at the prompt's last position and
    after each fed token. ``wrong``: keyword arguments of
    ``reference.forward_many`` that make a control of it."""
    steps = config["check"]["decode_steps"]
    positions = [np.arange(n - 1, n + steps) for n in prompt_lens]
    return _at(config, seed, sequences, positions, **wrong)


def served_token_deficit(config, seed, served, stale_state=False,
                         swap=False):
    """How far the scheduler's tokens lie from the reference's choice
    (``jamba.served_token_deficit`` says how it is counted), the largest
    over all tokens. With ``stale_state`` the reference begins each
    request from the tails and states in which it left the PREVIOUS one
    (the first from the last's); with ``swap`` each request's tokens are
    judged under the NEXT request's prompt."""
    model, worst, previous = config["model"], 0.0, None
    order = list(served)
    if swap:
        order = [(order[(i + 1) % len(order)][0], tokens)
                 for i, (_, tokens) in enumerate(order)]
    if stale_state:
        prompt, tokens = order[-1]
        _, previous = reference.logits_at(
            model, seed, np.asarray(list(prompt) + list(tokens), np.int32),
            [0], return_state=True)
    for prompt, tokens in order:
        n, m = len(prompt), len(tokens)
        ids = np.asarray(list(prompt) + list(tokens[:-1]), np.int32)
        positions = np.arange(n - 1, n + m - 1)
        if stale_state:
            # exact length: the final state is handed on
            logits, previous = reference.logits_at(
                model, seed, ids, positions, initial=previous,
                return_state=True)
        else:
            logits = _at(config, seed, [ids], [positions])[0]
        worst = max(worst, _deficit(np.asarray(logits), tokens))
    return worst


def serve_check(config, seed, got=None, served=None, rounding=None,
                ref=None):
    """``{name: (value, limit)}``. Prefill (the check's prompts), then
    decode through the pages and the state pool (``got``, from
    ``serve_engine_outputs``), against the reference's full forward at
    the same positions, on logits; without ``got``, the reference
    computed in ``rounding`` stands in the engine's place. And the
    tokens of ``served`` requests, as the scheduler gave them under
    load, against the reference's choice at each; no request to look at
    is not correct. ``ref``: the reference's logits where the caller has
    them already."""
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
    edge = config["inference"]["prefill_buckets"][-1]
    dh = model["hidden_size"] // model["num_attention_heads"]
    return {
        "fp8_matmuls": {"rounding": "fp8"},
        "state_bfloat16_every_step": {"state_rounding": "bfloat16"},
        "nine_of_ten_routed": {"top_k": model["num_experts_per_tok"] - 1},
        "softmax_over_all_not_renormalised": {"renormalise": False},
        "attention_multiplier_rsqrt": {
            "attention_multiplier": float(dh) ** -0.5},
        "residual_multiplier_one": {"residual_multiplier": 1.0},
        "gate_after_norm": {"gate_after_norm": True},
        "decay_left_out": {"decay": False},
        "second_chunk_from_zero_state": {"reset_state_at": edge},
        "second_chunk_zero_tails": {"reset_tail_at": edge},
        "conv_bias_left_out": {"conv_bias": False},
    }[control]


def serve_control(config, seed, control, served=None, ref=None):
    """The check's numbers with the reference made wrong in the way
    ``control`` names standing in the engine's place, ``{name: (value,
    limit)}``: ``fp8_matmuls`` (operands of every weight matmul rounded
    to e4m3's 4 significant bits); ``state_bfloat16_every_step`` (the
    Mamba-2 state rounded to bfloat16 after every token);
    ``nine_of_ten_routed``; ``softmax_over_all_not_renormalised`` (the
    chosen experts weighted by the softmax over all 72);
    ``attention_multiplier_rsqrt`` (``1/sqrt(128)`` in the place of
    1/128); ``residual_multiplier_one``; ``gate_after_norm``;
    ``decay_left_out`` (``a = 1``); ``previous_tenants_state`` (each of
    the check's prompts, and each served request, begun from the tails
    and states the previous one left); ``second_chunk_from_zero_state``
    and ``second_chunk_zero_tails`` (the state / the convolution tails
    dropped at the largest bucket's edge, where a long prompt's second
    chunk starts); ``conv_bias_left_out``; ``another_requests_prompt``
    (each served request's tokens judged under the next one's prompt:
    what the served-token number is there to reject)."""
    spec = config["check"]
    sequences, lens = serve_check_inputs(config, seed)
    if control == "another_requests_prompt":
        return {"served_token_deficit": (
            served_token_deficit(config, seed, served, swap=True),
            spec["served_token_deficit"])}
    if ref is None:
        ref = reference_logits(config, seed, sequences, lens)
    if control == "previous_tenants_state":
        steps = spec["decode_steps"]
        _, finals = reference.forward_many(
            config["model"], seed, sequences, [[0]] * len(sequences),
            return_state=True)
        positions = [np.arange(n - 1, n + steps) for n in lens]
        got = reference.forward_many(
            config["model"], seed, sequences, positions,
            initial=finals[-1:] + finals[:-1])
        checks = _logit_checks(spec, [np.asarray(x) for x in got], ref)
        if served:
            checks["served_token_deficit"] = (
                served_token_deficit(config, seed, served,
                                     stale_state=True),
                spec["served_token_deficit"])
        return checks
    got = reference_logits(config, seed, sequences, lens,
                           **control_kwargs(config, control))
    return _logit_checks(spec, got, ref)
