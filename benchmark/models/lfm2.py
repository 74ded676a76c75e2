"""The LFM2-MoE family as the benchmark drives it: the program's engine
built through ``init_inference()`` from a configuration file, the
counts that price the serving step and the grouped matmul's roofline,
and the output checks against ``lfm2_reference``.

A configuration file's ``model`` section carries the published
``config.json`` keys (``num_hidden_layers`` and ``layer_types`` as cut),
plus ``padded_vocab_size`` (the rows the program holds; 65,536 is a
multiple of 128) and ``expert_bias_std`` (``assumed``). Serving only
(``PERF.md`` section 4).

The serving check compares, on logits, what the engine's own programs
returned with the reference's full forward. Its inputs
(``serve_check_inputs``): one prompt in every prefill bucket, one of
two chunks with a long padded second chunk, one of two chunks whose
second chunk is ``conv_L_cache - 1`` tokens, and one prompt of
``conv_L_cache - 1`` tokens, each followed by ``decode_steps`` forced
tokens through ``decode_step``. The two short ones are there because a
convolution's tail reaches only ``conv_L_cache - 1`` tokens on: a tail
wrongly carried, or wrongly dropped, shows in the logits right behind
it and hardly a hundred tokens later.

With random weights a token whose 4th and 5th router scores are nearly
tied chooses another expert under bfloat16 inputs than in float32 (one
(token, expert layer) pair in eight does, on the chip) and its logits
are then off by a fifth; the positions that no flip hit are off by a
fiftieth. So three numbers on logits, each relative (RMS error over
the RMS of the reference's logits about their mean):
``prefill_logits_rel_rms``, the WORST of the prompts' last positions
(a tail wrongly carried or dropped puts one prompt off by nine tenths;
a flip by a fifth); ``decode_logits_rel_rms``, pooled over ALL decode
positions of all sequences (steady from seed to seed where the worst of
some hundred positions is decided by the flips); and
``decode_logits_rel_err_p10``, per sequence the tenth percentile over
its decode positions of the position's own error, the worst sequence:
the error that EVERY position carries, which the flips leave alone and
which a lower precision, mathematics left out or positions counted
wrongly shift.
``served_token_deficit`` over requests the scheduler retired in the
window from reused slots. ``serve_control`` computes the same numbers
with the reference made wrong in one of ``CONTROLS``' ways.
"""
import numpy as np

from . import lfm2_reference as reference
from .jamba import (_deficit, _noted, engine_logits,
                    release)  # noqa: F401 - release is the family's too

CONTROLS = ("fp8_matmuls", "three_of_four_experts", "expert_bias_ignored",
            "weights_not_renormalised", "previous_tenants_tail",
            "second_chunk_from_zero", "rotary_restarted_at_second_chunk",
            "another_requests_prompt")


# ---------------------------------------------------------------- engines
def _program_config(config):
    import jax.numpy as jnp
    from deepspeed_tpu.models import lfm2
    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[
        config["inference"]["dtype"]]
    held = config["model"].get("experts_held")
    return lfm2.config_from_hf(config["model"], dtype=dtype,
                               experts_held=tuple(held) if held else None)


def build_serve_engine(config, seed):
    """``deepspeed_tpu.init_inference()`` on the configuration's
    ``inference`` section; weights from ``seed``."""
    import deepspeed_tpu
    from deepspeed_tpu.models import lfm2
    return deepspeed_tpu.init_inference(
        model=lfm2.make_lfm2_model(_program_config(config), seed=seed),
        config={"inference": config["inference"]},
        seed=seed % (2 ** 31 - 1))


def build_train_engine(config, seed):
    raise NotImplementedError(
        "the LFM2 family is served, not trained: the grouped matmul has "
        "no backward and the training state of all 32 experts of a layer "
        "does not fit a chip")


# ----------------------------------------------------------------- counts
def _layer_counts(model):
    layers = model["num_hidden_layers"]
    n_attn = sum(reference.is_attention(model, i) for i in range(layers))
    n_dense = min(model["num_dense_layers"], layers)
    return layers, n_attn, n_dense


def serve_flops_per_token(model):
    """Operations every served token needs, prompt or generated: 2 for
    each weight of the layers' matmuls it multiplies (in and out
    projections of a convolution layer; q, k, v and o of an attention
    layer; the dense MLP of the leading layers; the router and the
    ``num_experts_per_tok`` experts a token is sent to in the others,
    the held experts' share of them). A floor: the head, which only a
    sampled position needs, the convolution and attention's scores and
    values are left out."""
    d = model["hidden_size"]
    kv = (model["num_key_value_heads"] * d //
          model["num_attention_heads"])
    layers, n_attn, n_dense = _layer_counts(model)
    first, past = reference.experts_held(model)
    share = (past - first) / model["num_experts"]
    expert = (model["num_experts_per_tok"] * share * 3 * d *
              model["moe_intermediate_size"] + d * model["num_experts"])
    return 2.0 * ((layers - n_attn) * 4 * d * d +
                  n_attn * (2 * d * d + 2 * d * kv) +
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
    and out of both matmuls (hidden in, gate and up out, their gated
    product in, hidden out)."""
    d, ff = model["hidden_size"], model["moe_intermediate_size"]
    return itemsize * (experts_hit * 3 * d * ff + rows * (2 * d + 3 * ff))


# ----------------------------------------------------------------- checks
def serve_check_inputs(config, seed):
    """Seeded prompts and forced continuations (the module docstring's
    list). -> (sequences, prompt lengths)."""
    spec = config["check"]
    rng = np.random.default_rng([seed, 0xC0FFEE])
    buckets = config["inference"]["prefill_buckets"]
    vocab = config["model"]["padded_vocab_size"]
    tail = config["model"]["conv_L_cache"] - 1
    lows = [1] + [b + 1 for b in buckets[:-1]]
    lens = [int(rng.integers(max(lo, hi // 2), hi))
            for lo, hi in zip(lows, buckets)]
    lens.append(int(rng.integers(buckets[-1] + buckets[0] // 2,
                                 buckets[-1] + buckets[0])))
    lens += [buckets[-1] + tail, tail]
    return [rng.integers(0, vocab, n + spec["decode_steps"]).astype(
        np.int32) for n in lens], lens


def serve_engine_outputs(config, seed, engine):
    """What the engine (all slots free) gives on the check's inputs;
    notes its tail pool for ``release``."""
    if engine.state is not None:
        _noted.append(engine.state.arrays)
    sequences, lens = serve_check_inputs(config, seed)
    return engine_logits(engine, sequences, lens,
                         config["check"]["decode_steps"])


def _padded(config, ids):
    """``ids`` zero-padded to the serving window (the model is causal:
    what follows a position changes nothing before it), so that the
    reference compiles ONE length for every sequence it is given: a
    layer's program takes 7-13 s to compile at this size and a run's
    check would otherwise compile fifteen of them."""
    out = np.zeros((config["inference"]["max_seq_len"],), np.int32)
    out[:len(ids)] = ids
    return out


def _at(config, seed, sequences, positions, **wrong):
    """The reference's logits of each sequence, padded to the window,
    at its positions; the positions padded to one count likewise (the
    head's program compiles once). With ``return_state`` also the
    tails each sequence leaves where its own tokens end."""
    most = max(len(p) for p in positions)
    filled = [np.concatenate([p, np.zeros((most - len(p),), np.int64)])
              for p in positions]
    out = reference.forward_many(
        config["model"], seed, [_padded(config, s) for s in sequences],
        filled, lengths=[len(s) for s in sequences], **wrong)
    logits, rest = (out[0], out[1:]) if isinstance(out, tuple) else (out, ())
    logits = [np.asarray(x)[:len(p)] for x, p in zip(logits, positions)]
    return (logits,) + rest if rest else logits


def reference_logits(config, seed, sequences, prompt_lens, **wrong):
    """The reference's full forward over each whole sequence (prompt
    and forced continuation), read at the prompt's last position and
    after each fed token. ``wrong``: keyword arguments of
    ``reference.forward_many`` that make a control of it."""
    steps = config["check"]["decode_steps"]
    positions = [np.arange(n - 1, n + steps) for n in prompt_lens]
    return _at(config, seed, sequences, positions, **wrong)


def row_rel_err(got, ref):
    """Per row of logits: RMS of (got - ref) over the RMS of ref about
    its mean."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    err = np.sqrt(((got - ref) ** 2).mean(-1))
    return err / np.sqrt(((ref - ref.mean(-1, keepdims=True)) ** 2).mean(-1))


def _pooled_rel_rms(got, ref):
    """RMS of (got - ref) over all rows of all sequences, over the RMS
    of ref about its rows' means."""
    got = np.concatenate([np.asarray(g, np.float64) for g in got])
    ref = np.concatenate([np.asarray(r, np.float64) for r in ref])
    scale = ((ref - ref.mean(-1, keepdims=True)) ** 2).mean()
    return float(np.sqrt(((got - ref) ** 2).mean() / scale))


def served_token_deficit(config, seed, served, stale_state=False,
                         swap=False):
    """How far the scheduler's tokens lie from the reference's choice
    (``jamba.served_token_deficit`` says how it is counted), the
    largest over all tokens. ``stale_state``: the reference begins each
    request from the convolution tails the PREVIOUS one left;
    ``swap``: each request's tokens judged under the NEXT request's
    prompt."""
    order = list(served)
    if swap:
        order = [(order[(i + 1) % len(order)][0], tokens)
                 for i, (_, tokens) in enumerate(order)]
    ids = [np.asarray(list(prompt) + list(tokens[:-1]), np.int32)
           for prompt, tokens in order]
    positions = [np.arange(len(prompt) - 1, len(prompt) + len(tokens) - 1)
                 for prompt, tokens in order]
    wrong = {}
    if stale_state:
        # each from the tails the one before it left (the first from
        # the last's); a tail depends on its own sequence's last
        # tokens only, so the sound run's tails are the stale ones
        _, finals = _at(config, seed, ids, positions, return_state=True)
        wrong["initial"] = finals[-1:] + finals[:-1]
    logits = _at(config, seed, ids, positions, **wrong)
    return max(_deficit(got, tokens)
               for got, (_, tokens) in zip(logits, order))


def _steady_rel_err(got, ref, q=10):
    """The error that EVERY position carries: per sequence the q-th
    percentile over its decode positions of the position's own
    relative error (``row_rel_err``), the worst sequence. A flipped
    expert is off by much at the position it hits and leaves the
    others alone; a wrong precision, mathematics left out or positions
    counted wrongly shift them all."""
    return float(max(np.percentile(row_rel_err(g[1:], r[1:]), q)
                     for g, r in zip(got, ref)))


def _logit_checks(spec, got, ref):
    return {
        "decode_logits_rel_err_p10": (
            _steady_rel_err(got, ref), spec["decode_logits_rel_err_p10"]),
        "prefill_logits_rel_rms": (
            float(max(row_rel_err(g[:1], r[:1])[0]
                      for g, r in zip(got, ref))),
            spec["prefill_logits_rel_rms"]),
        "decode_logits_rel_rms": (
            _pooled_rel_rms([g[1:] for g in got], [r[1:] for r in ref]),
            spec["decode_logits_rel_rms"]),
    }


def serve_check(config, seed, got=None, served=None, rounding=None,
                ref=None):
    """``{name: (value, limit)}``. Prefill (the check's prompts), then
    decode through the cache and the tail pool (``got``, from
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
    edge = config["inference"]["prefill_buckets"][-1]
    return {
        "fp8_matmuls": {"rounding": "fp8"},
        "three_of_four_experts": {
            "top_k": config["model"]["num_experts_per_tok"] - 1},
        "expert_bias_ignored": {"use_bias": False},
        "weights_not_renormalised": {"renormalise": False},
        "second_chunk_from_zero": {"reset_at": edge},
        "rotary_restarted_at_second_chunk": {"rope_restart_at": edge},
    }[control]


def serve_control(config, seed, control, served=None, ref=None):
    """The check's numbers with the reference made wrong in the way
    ``control`` names standing in the engine's place, ``{name: (value,
    limit)}``: ``fp8_matmuls`` (operands of every weight matmul rounded
    to e4m3's 4 significant bits), ``three_of_four_experts`` (one
    expert a token fewer than the model says: mathematics left out),
    ``expert_bias_ignored`` (the choice made on the scores alone),
    ``weights_not_renormalised`` (the chosen scores used as they are),
    ``previous_tenants_tail`` (each of the check's prompts, and each
    served request, begun from the convolution tails the previous one
    left), ``second_chunk_from_zero`` (the tail dropped at the largest
    bucket's edge, where a long prompt's second chunk starts),
    ``rotary_restarted_at_second_chunk`` (positions counted from 0
    again there), ``another_requests_prompt`` (each served request's
    tokens judged under the next one's prompt)."""
    spec = config["check"]
    sequences, lens = serve_check_inputs(config, seed)
    if control == "another_requests_prompt":
        return {"served_token_deficit": (
            served_token_deficit(config, seed, served, swap=True),
            spec["served_token_deficit"])}
    if ref is None:
        ref = reference_logits(config, seed, sequences, lens)
    if control == "previous_tenants_tail":
        # the check's own prompts go into slots that the window's
        # requests used: each begun from the tails with which the
        # previous one's full forward ended (the first from the last's)
        _, finals = reference_logits(config, seed, sequences, lens,
                                     return_state=True)
        got = reference_logits(config, seed, sequences, lens,
                               initial=finals[-1:] + finals[:-1])
        checks = _logit_checks(spec, got, ref)
        if served:
            checks["served_token_deficit"] = (
                served_token_deficit(config, seed, served,
                                     stale_state=True),
                spec["served_token_deficit"])
        return checks
    wrong = control_kwargs(config, control)
    got = reference_logits(config, seed, sequences, lens, **wrong)
    return _logit_checks(spec, got, ref)
