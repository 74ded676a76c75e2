"""The ``olmo_hybrid`` family (Olmo-Hybrid-7B) as the benchmark drives it:
the program's engine built through ``init_inference()`` from a
configuration file, the counts that price the serving step and the
state kernel's and the page walk's rooflines, and the output checks
against ``olmo_hybrid_reference``.

A configuration file's ``model`` section carries the published
``config.json`` keys (``num_hidden_layers`` and ``layer_types`` as
cut), plus ``padded_vocab_size`` (the rows the program holds: the
vocabulary itself, 100,352 is a multiple of 128). Serving only: the
training state of one period does not fit a chip (``PERF.md`` section
4).

The serving check compares, on logits, what the engine's own programs
returned with the reference's full forward. Its inputs
(``serve_check_inputs``): one prompt in every prefill bucket, one of a
single page, one of two chunks and one of three (state and convolution
tails cross a chunk's end twice, and the last chunk is padded), each
followed by ``decode_steps`` forced tokens through ``decode_step``:
``prefill_logits_rel_rms`` (the worst prompt's last position),
``decode_logits_rel_rms`` (the worst decode position) and
``served_token_deficit`` over requests the scheduler retired in the
window from reused slots. ``serve_control`` computes the same numbers
with the reference made wrong in one of ``CONTROLS``' ways.
"""
import numpy as np

from . import olmo_hybrid_reference as reference
# the two logit numbers are Jamba's: the worst prompt's last position
# and the worst decode position
from .jamba import (_deficit, _logit_checks, _noted, _padded, engine_logits,
                    release)  # noqa: F401 - release is the family's too

CONTROLS = ("fp8_matmuls", "state_bfloat16_every_step",
            "beta_without_its_two", "decay_left_out",
            "previous_tenants_state", "second_chunk_from_zero_state",
            "second_chunk_zero_tails", "another_requests_prompt")


# ---------------------------------------------------------------- engines
def _program():
    """``deepspeed_tpu.models.olmo_hybrid``; a checkout from before the
    family says so in one sentence, at once."""
    try:
        from deepspeed_tpu.models import olmo_hybrid
    except ImportError:
        import sys
        sys.exit("benchmark: this checkout's deepspeed_tpu has no "
                 "models/olmo_hybrid.py and cannot run the olmo_hybrid "
                 "family")
    return olmo_hybrid


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
        model=program.make_olmo_hybrid_model(_program_config(config),
                                             seed=seed),
        config={"inference": config["inference"]},
        seed=seed % (2 ** 31 - 1))


def build_train_engine(config, seed):
    raise NotImplementedError(
        "the olmo_hybrid family is served, not trained: the training "
        "state of one period of four layers does not fit a chip")


# ----------------------------------------------------------------- counts
def _layers(model):
    n = model["num_hidden_layers"]
    linear = sum(reference.is_linear(model, i) for i in range(n))
    return linear, n - linear


def serve_flops_per_token(model):
    """Operations every served token needs, prompt or generated: 2 for
    each weight of the layers' matmuls (the gated MLP of every layer;
    q, k, v, the output gate, beta, the decay and o of a linear layer;
    q, k, v and o of a full layer). A floor: the head, which only a
    sampled position needs, the delta rule and attention's scores and
    values are left out."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    H, dv = model["linear_num_value_heads"], model["linear_value_head_dim"]
    linear, full = _layers(model)
    mixer = d * reference.conv_channels(model) + 2 * d * H * dv + 2 * d * H
    return 2.0 * ((linear + full) * 3 * d * ff + linear * mixer +
                  full * 4 * d * d)


def gated_delta_step_bytes(model, slot_steps):
    """Bytes the decode step's state update has to move for
    ``slot_steps`` (slots whose state advanced, summed over steps), all
    linear layers: the slot's float32 state read and written once."""
    linear, _ = _layers(model)
    return linear * slot_steps * 2 * 4 * (
        model["linear_key_head_dim"] * model["linear_num_value_heads"] *
        model["linear_value_head_dim"])


def paged_attention_bytes(model, page_size, pages, dtype_bytes=2):
    """Bytes decode attention has to read for ``pages`` live pages (a
    count summed over slots and steps), all full layers: their keys and
    values."""
    _, full = _layers(model)
    return pages * page_size * 2 * full * model["hidden_size"] * dtype_bytes


# ----------------------------------------------------------------- checks
def serve_check_inputs(config, seed):
    """Seeded prompts and forced continuations: one prompt in each
    prefill bucket (its length drawn inside the bucket), one of a single
    page, one of two chunks and one of three (longer than the largest
    bucket, the last chunk padded), each followed by ``decode_steps``
    tokens fed one at a time through the decode program. -> (sequences,
    prompt lengths)."""
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
    return [rng.integers(0, vocab, n + spec["decode_steps"]).astype(
        np.int32) for n in lens], lens


def serve_engine_outputs(config, seed, engine):
    """What the engine (all slots free) gives on the check's inputs."""
    if engine.state is not None:
        _noted.append(engine.state.arrays)
    sequences, lens = serve_check_inputs(config, seed)
    return engine_logits(engine, sequences, lens,
                         config["check"]["decode_steps"])


def reference_logits(config, seed, sequences, prompt_lens, **wrong):
    """The reference's full forward over each whole sequence (prompt
    and forced continuation, zero-padded to a multiple of 256: the model
    is causal), read at the prompt's last position and after each fed
    token. ``wrong``: keyword arguments of ``reference.forward_many``
    that make a control of it."""
    steps = config["check"]["decode_steps"]
    positions = [np.arange(n - 1, n + steps) for n in prompt_lens]
    logits = reference.forward_many(
        config["model"], seed, [_padded(s) for s in sequences], positions,
        **wrong)
    return [np.asarray(x) for x in logits]


def served_token_deficit(config, seed, served, stale_state=False,
                         swap=False):
    """How far the scheduler's tokens lie from the reference's choice:
    for each served request (prompt, generated tokens) the reference's
    full forward over prompt + tokens gives the logits every token was
    chosen from; a token's deficit is (largest logit - the chosen
    token's logit) over the logits' standard deviation, 0 where the
    reference chooses the same. The largest over all tokens. With
    ``stale_state`` the reference begins each request from the tails
    and states in which it left the PREVIOUS one (the first from the
    last's); with ``swap`` each request's tokens are judged under the
    NEXT request's prompt."""
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
            logits = reference.logits_at(model, seed, _padded(ids, 512),
                                         positions)
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


def serve_control(config, seed, control, served=None, ref=None):
    """The check's numbers with the reference made wrong in the way
    ``control`` names standing in the engine's place, ``{name: (value,
    limit)}``: ``fp8_matmuls`` (operands of every weight matmul rounded
    to e4m3's 4 significant bits); ``state_bfloat16_every_step`` (the
    delta rule's state rounded to bfloat16 after every token);
    ``beta_without_its_two``; ``decay_left_out`` (``a = 1``);
    ``previous_tenants_state`` (each of the check's prompts, and each
    served request, begun from the tails and states the previous one
    left); ``second_chunk_from_zero_state`` and
    ``second_chunk_zero_tails`` (the state / the convolution tails
    dropped at the largest bucket's edge, where a long prompt's second
    chunk starts); ``another_requests_prompt`` (each served request's
    tokens judged under the next one's prompt: what the served-token
    number is there to reject)."""
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
    edge = config["inference"]["prefill_buckets"][-1]
    wrong = {
        "fp8_matmuls": {"rounding": "fp8"},
        "state_bfloat16_every_step": {"state_rounding": "bfloat16"},
        "beta_without_its_two": {"beta_two": False},
        "decay_left_out": {"decay": False},
        "second_chunk_from_zero_state": {"reset_state_at": edge},
        "second_chunk_zero_tails": {"reset_tail_at": edge},
    }[control]
    got = reference_logits(config, seed, sequences, lens, **wrong)
    return _logit_checks(spec, got, ref)
