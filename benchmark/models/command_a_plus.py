"""The ``cohere2_moe`` family (command-a-plus-05-2026) as the benchmark
drives it: the program's engine built through ``init_inference()`` from a
configuration file, the counts that price the serving step, the page
walk's, the chunk's attention's and the grouped matmul's rooflines, and
the output checks against ``command_a_plus_reference``.

A configuration file's ``model`` section carries the published
``config.json`` keys (``num_hidden_layers``, ``layer_types``,
``num_experts`` and ``vocab_size`` as cut), plus what says which SHARE
of a layer this chip holds (``router_num_experts``, the router's width;
``experts_held``, the range of them here; ``padded_vocab_size``, the
rows of the tied embedding here, which the traffic draws its ids from)
and ``qk_init_std`` (``assumed.weights``). Serving only (``PERF.md``
section 4).

The serving check compares, on logits, what the engine's own programs
returned with the reference's full forward. Its inputs
(``serve_check_inputs``): one prompt in every prefill bucket, one of a
single page, one of two chunks and one of FOUR chunks that ends past
three of the largest bucket (its third and fourth chunks start at or
past the window's end, so a sliding layer's table has slid and given
pages back before each, and a full layer's chunk reads three earlier
chunks' keys from the pages), each followed by ``decode_steps`` forced
tokens through ``decode_step``: more than a page's tokens many times
over, so every sequence past the window decodes across pages' release.
The reference is given every sequence zero-padded to a multiple of
twice the largest bucket (the model is causal), so that it compiles few
lengths.

The numbers are LFM2's (``lfm2.py`` says why each): with random weights
a token whose 8th and 9th router scores are nearly tied chooses another
expert under bfloat16 inputs than in float32, and where one of the two
is held here its logits are then off by much. So
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

from . import command_a_plus_reference as reference
from .jamba import (_deficit, _noted, engine_logits,
                    release)  # noqa: F401 - release is the family's too
from .lfm2 import _logit_checks
# a page's bytes and the walks' least bytes follow from the same keys
# (layer_types, num_key_value_heads, head_dim) in both families
from .mellum2 import page_bytes, paged_attention_bytes  # noqa: F401

CONTROLS = ("shared_experts_summed", "three_of_four_shared",
            "seven_of_eight_routed", "chosen_not_renormalised",
            "softmax_router", "the_next_chips_share", "sequential_block",
            "rms_norm", "full_layers_rotated", "sliding_not_rotated",
            "window_a_page_short", "window_ignored", "fp8_matmuls",
            "kv_one_precision_lower", "another_requests_prompt")


# ---------------------------------------------------------------- engines
def _program():
    """``deepspeed_tpu.models.cohere2_moe``; a checkout from before the
    family says so in one sentence, at once."""
    try:
        from deepspeed_tpu.models import cohere2_moe
    except ImportError:
        import sys
        sys.exit("benchmark: this checkout's deepspeed_tpu has no "
                 "models/cohere2_moe.py and cannot run the command_a_plus "
                 "family")
    return cohere2_moe


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
        model=_program().make_cohere2_moe_model(_program_config(config),
                                                seed=seed),
        config={"inference": config["inference"]},
        seed=seed % (2 ** 31 - 1))


def build_train_engine(config, seed):
    raise NotImplementedError(
        "the Command A+ family is served, not trained: at 16 bytes a "
        "parameter no cut of it inside the guide's floors fits a chip, and "
        "the grouped matmul of the expert layers has no backward")


# ----------------------------------------------------------------- counts
def _attention_weights(model):
    d, dh = model["hidden_size"], model["head_dim"]
    return 2 * d * model["num_attention_heads"] * dh + \
        2 * d * model["num_key_value_heads"] * dh


def _expert_weights(model):
    """One expert's three matrices, routed or shared (50.3M)."""
    return 3 * model["hidden_size"] * model["intermediate_size"]


def serve_flops_per_token(model):
    """Operations every served token needs HERE, prompt or generated: 2
    for each weight of the layers' matmuls it multiplies: a layer's four
    attention projections, its router, its ``num_shared_experts`` shared
    experts and the routed experts of its ``num_experts_per_tok`` that
    are held here, which is the share's EXPECTATION (8 x 16 / 128 = one
    a token and layer; ``expert_rows_held_share`` says what the seeded
    router really sent). A floor: the head, which only a sampled position
    needs, and attention's scores and values are left out."""
    first, past = reference.experts_held(model)
    landed = model["num_experts_per_tok"] * (past - first) / \
        reference.router_experts(model)
    layer = _attention_weights(model) + \
        model["hidden_size"] * reference.router_experts(model) + \
        (model["num_shared_experts"] + landed) * _expert_weights(model)
    return 2.0 * model["num_hidden_layers"] * layer


def moe_gmm_flops(model, rows):
    """Operations of the expert layers' grouped matmuls for ``rows``
    routed rows that landed on an expert held here (summed over the
    layers): 2 for each weight of the row's expert, gate, up and down
    (100.7 MFLOP a row)."""
    return 2.0 * rows * _expert_weights(model)


def moe_gmm_bytes(model, rows, experts_hit, itemsize=2):
    """Bytes the grouped matmuls must move at the least: the three
    matrices of each (expert, layer) pair HIT, once (100.7 MB), and
    every row in and out of both matmuls."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    return itemsize * (experts_hit * 3 * d * ff + rows * (2 * d + 3 * ff))


def chunk_attention_flops(model, start, tokens):
    """Operations a prompt chunk's attention needs at the least: its
    ``tokens`` queries begin at position ``start``; the query at ``t``
    MUST visit ``t + 1`` keys in a full layer and ``min(t + 1,
    sliding_window)`` in a sliding one, each 4 operations a query head
    and lane of the head (the score's and the value's multiply-adds).
    Summed over the layers held. Padding, tiles past the causal edge and
    a block-diagonal query are what a kernel spends on top: they are in
    its time, never in this count."""
    t = np.arange(start + 1, start + tokens + 1, dtype=np.float64)
    keys = sum(
        (np.minimum(t, model["sliding_window"])
         if kind == reference.SLIDING else t).sum()
        for kind in model["layer_types"])
    return 4.0 * model["num_attention_heads"] * model["head_dim"] * keys


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
    lens.append(int(rng.integers(3 * edge + half, 3 * edge + buckets[0])))
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
    # one request at a time: the longest is 25,344 tokens
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
    first, past = reference.experts_held(model)
    # the next chip's experts; the last chip's neighbour is the first
    nxt = past % reference.router_experts(model)
    return {
        "shared_experts_summed": {"shared": "sum"},
        "three_of_four_shared": {
            "shared": model["num_shared_experts"] - 1},
        "seven_of_eight_routed": {
            "top_k": model["num_experts_per_tok"] - 1},
        "chosen_not_renormalised": {"renormalise": False},
        "softmax_router": {"scoring": "softmax"},
        "the_next_chips_share": {
            "experts_held": (nxt, nxt + past - first)},
        "sequential_block": {"sequential": True},
        "rms_norm": {"norm": "rms"},
        "full_layers_rotated": {
            "rotate": (reference.SLIDING, reference.FULL)},
        "sliding_not_rotated": {"rotate": ()},
        "window_a_page_short": {
            "window": model["sliding_window"] -
            config["inference"]["kv_block_size"]},
        "window_ignored": {"window": 0},
        "fp8_matmuls": {"rounding": "fp8"},
        "kv_one_precision_lower": {"kv_rounding": "fp8"},
    }[control]


def serve_control(config, seed, control, served=None, ref=None):
    """The check's numbers with the reference made wrong in the way
    ``control`` names standing in the engine's place, ``{name: (value,
    limit)}``: ``shared_experts_summed`` (not averaged),
    ``three_of_four_shared``, ``seven_of_eight_routed``,
    ``chosen_not_renormalised``, ``softmax_router`` (in the sigmoid's
    place), ``the_next_chips_share`` (experts 16-31 in the place of
    0-15), ``sequential_block`` (the experts read ``LayerNorm(x + a)``),
    ``rms_norm`` (LayerNorm without its mean), ``full_layers_rotated``,
    ``sliding_not_rotated``, ``window_a_page_short`` (a window of 4,080:
    a page given back one step early), ``window_ignored``,
    ``fp8_matmuls`` (operands of every weight matmul rounded to fp8's 4
    significant bits), ``kv_one_precision_lower`` (keys and values kept
    likewise), ``another_requests_prompt`` (each served request's tokens
    judged under the next one's prompt)."""
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
