"""The ``mellum`` family (Mellum2-12B-A2.5B-Instruct) as the benchmark
drives it: the program's engine built through ``init_inference()`` from a
configuration file, the counts that price the serving step, the page
walk's and the grouped matmul's rooflines, and the output checks against
``mellum2_reference``.

A configuration file's ``model`` section carries the published
``config.json`` keys (``num_hidden_layers``, ``layer_types`` and
``mlp_layer_types`` as cut), plus ``padded_vocab_size`` (the rows the
program holds; 98,304 is a multiple of 128) and ``qk_norm_gain``
(``assumed.weights``). Serving only (``PERF.md`` section 4).

The serving check compares, on logits, what the engine's own programs
returned with the reference's full forward. Its inputs
(``serve_check_inputs``): one prompt in every prefill bucket, one of a
single page, one of THREE chunks (past two of the largest bucket: its
second and third chunks each start past a window's end, so a sliding
layer's table has slid and given pages back before each, and a full
layer's chunk reads two earlier chunks' keys from the pages) and one of
two chunks, each followed by ``decode_steps`` forced tokens through
``decode_step``: ``decode_steps`` is more than a page's tokens many
times over, so every sequence past the window decodes across pages'
release. The reference is given every sequence zero-padded to a multiple
of twice the largest bucket (the model is causal), so that it compiles
few lengths.

The numbers are LFM2's (``lfm2.py`` says why each): with random weights
a token whose 8th and 9th router probabilities are nearly tied chooses
another expert under bfloat16 inputs than in float32, and its logits
are then off by much where the other positions are off by little. So
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

from . import mellum2_reference as reference
from .jamba import (_deficit, _noted, engine_logits,
                    release)  # noqa: F401 - release is the family's too
from .lfm2 import _logit_checks

CONTROLS = ("window_ignored", "window_a_page_short", "yarn_left_out",
            "attention_factor_one", "sliding_rotated_by_full_table",
            "sigmoid_router", "seven_of_eight_experts",
            "chosen_not_renormalised", "qk_norms_skipped",
            "kv_one_precision_lower", "fp8_matmuls",
            "another_requests_prompt")


# ---------------------------------------------------------------- engines
def _program():
    """``deepspeed_tpu.models.mellum``; a checkout from before the
    family says so in one sentence, at once."""
    try:
        from deepspeed_tpu.models import mellum
    except ImportError:
        import sys
        sys.exit("benchmark: this checkout's deepspeed_tpu has no "
                 "models/mellum.py and cannot run the mellum2 family")
    return mellum


def _program_config(config):
    import jax.numpy as jnp
    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[
        config["inference"]["dtype"]]
    return _program().config_from_hf(config["model"], dtype=dtype)


def build_serve_engine(config, seed):
    """``deepspeed_tpu.init_inference()`` on the configuration's
    ``inference`` section; weights from ``seed``."""
    import deepspeed_tpu
    return deepspeed_tpu.init_inference(
        model=_program().make_mellum_model(_program_config(config),
                                           seed=seed),
        config={"inference": config["inference"]},
        seed=seed % (2 ** 31 - 1))


def build_train_engine(config, seed):
    raise NotImplementedError(
        "the Mellum family is served, not trained: a window beside full "
        "layers does its work where a cache lives, and the grouped matmul "
        "of the expert layers has no backward")


# ----------------------------------------------------------------- counts
def _attention_weights(model):
    d, dh = model["hidden_size"], model["head_dim"]
    return 2 * d * model["num_attention_heads"] * dh + \
        2 * d * model["num_key_value_heads"] * dh


def serve_flops_per_token(model):
    """Operations every served token needs, prompt or generated: 2 for
    each weight of the layers' matmuls it multiplies (a layer's four
    attention projections, its router and the ``num_experts_per_tok``
    experts the token is sent to). A floor: the head, which only a
    sampled position needs, and attention's scores and values are left
    out."""
    d, ff = model["hidden_size"], model["moe_intermediate_size"]
    layer = _attention_weights(model) + d * model["num_experts"] + \
        model["num_experts_per_tok"] * 3 * d * ff
    return 2.0 * model["num_hidden_layers"] * layer


def moe_gmm_flops(model, rows):
    """Operations of the expert layers' grouped matmuls for ``rows``
    routed rows (summed over the layers): 2 for each weight of the
    row's expert, gate, up and down (12.4 MFLOP a row)."""
    return 2.0 * rows * 3 * model["hidden_size"] * \
        model["moe_intermediate_size"]


def moe_gmm_bytes(model, rows, experts_hit, itemsize=2):
    """Bytes the grouped matmuls must move at the least: the three
    matrices of each (expert, layer) pair HIT, once (12.4 MB), and
    every row in and out of both matmuls."""
    d, ff = model["hidden_size"], model["moe_intermediate_size"]
    return itemsize * (experts_hit * 3 * d * ff + rows * (2 * d + 3 * ff))


def page_bytes(model, page_size, sliding, itemsize=2):
    """Bytes of one page of the full layers' group (65,536) or of the
    sliding layers' (196,608): keys and values of ``page_size`` tokens
    in each of the group's layers."""
    kind = reference.SLIDING if sliding else reference.FULL
    layers = sum(k == kind for k in model["layer_types"])
    return 2 * page_size * layers * model["num_key_value_heads"] * \
        model["head_dim"] * itemsize


def paged_attention_bytes(model, page_size, full_pages, window_pages=0,
                          itemsize=2):
    """Bytes the decode steps' attention must read at the least:
    ``full_pages`` live pages of the full layers' group and
    ``window_pages`` of the sliding layers' (each summed over slots and
    steps; only pages that hold a key some query sees are live, so an
    implementation that reads more reads more than this, never less,
    and the share cannot pass 100%)."""
    return float(full_pages) * page_bytes(model, page_size, False,
                                          itemsize) + \
        float(window_pages) * page_bytes(model, page_size, True, itemsize)


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
    lens.append(int(rng.integers(max(1, page // 2), page)))
    lens.append(int(rng.integers(2 * edge + half, 2 * edge + buckets[0])))
    lens.append(int(rng.integers(edge + half, edge + buckets[0])))
    assert max(lens) + spec["decode_steps"] < \
        config["inference"]["max_seq_len"]
    return [rng.integers(0, vocab, n + spec["decode_steps"]).astype(
        np.int32) for n in lens], lens


def serve_engine_outputs(config, seed, engine):
    """What the engine (all slots free) gives on the check's inputs.
    Every group's pools are noted for ``release``: the runner hands it
    the first group's only, and the reference needs the room."""
    sequences, lens = serve_check_inputs(config, seed)
    got = engine_logits(engine, sequences, lens,
                        config["check"]["decode_steps"])
    _noted.append([kv.buffers() for kv in engine.kv_groups])
    return got


def _padded(config, ids):
    """``ids`` zero-padded to a multiple of twice the largest bucket
    (the model is causal: what follows a position changes nothing
    before it), so that the reference compiles few lengths, each a
    whole number of its query blocks."""
    step = 2 * config["inference"]["prefill_buckets"][-1]
    n = -(-len(ids) // step) * step
    if n > reference.QUERY_BLOCK:
        n = -(-n // reference.QUERY_BLOCK) * reference.QUERY_BLOCK
    out = np.zeros((n,), np.int32)
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
    ids = [np.asarray(list(prompt) + list(tokens[:-1]), np.int32)
           for prompt, tokens in order]
    positions = [np.arange(len(prompt) - 1, len(prompt) + len(tokens) - 1)
                 for prompt, tokens in order]
    # one request at a time: the longest is 26,624 tokens
    return max(_deficit(_at(config, seed, [seq], [at])[0], tokens)
               for seq, at, (_, tokens) in zip(ids, positions, order))


def serve_check(config, seed, got=None, served=None, rounding=None,
                ref=None):
    """``{name: (value, limit)}``. Prefill (the check's prompts), then
    decode through both groups' pages (``got``, from
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
        "window_ignored": {"window": 0},
        "window_a_page_short": {
            "window": model["sliding_window"] -
            config["inference"]["kv_block_size"]},
        "yarn_left_out": {"yarn": False},
        "attention_factor_one": {"attention_factor": 1.0},
        "sliding_rotated_by_full_table": {"sliding_rope_of_full": True},
        "sigmoid_router": {"scoring": "sigmoid"},
        "seven_of_eight_experts": {
            "top_k": model["num_experts_per_tok"] - 1},
        "chosen_not_renormalised": {"renormalise": False},
        "qk_norms_skipped": {"qk_norm": False},
        "kv_one_precision_lower": {"kv_rounding": "fp8"},
        "fp8_matmuls": {"rounding": "fp8"},
    }[control]


def serve_control(config, seed, control, served=None, ref=None):
    """The check's numbers with the reference made wrong in the way
    ``control`` names standing in the engine's place, ``{name: (value,
    limit)}``: ``window_ignored`` (the sliding layers see every key),
    ``window_a_page_short`` (a window of 1,008: a page given back one
    step early), ``yarn_left_out`` (the full layers rotated by plain
    rotary, no factor), ``attention_factor_one``,
    ``sliding_rotated_by_full_table``, ``sigmoid_router`` (in the
    softmax's place), ``seven_of_eight_experts``,
    ``chosen_not_renormalised``, ``qk_norms_skipped``,
    ``kv_one_precision_lower`` (keys and values kept in fp8's 4
    significant bits), ``fp8_matmuls`` (operands of every weight matmul
    rounded likewise), ``another_requests_prompt`` (each served
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
