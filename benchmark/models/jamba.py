"""The Jamba family as the benchmark drives it: the program's engine
built through ``init_inference()`` from a configuration file, the byte
counts that price the new kernels' rooflines, and the output checks
against ``jamba_reference``.

A configuration file's ``model`` section carries the published
``config.json`` keys as they stand, plus ``padded_vocab_size`` (the
rows the program holds: the vocabulary itself, 65,536 is a multiple of
128). Serving only: the training state does not fit one chip
(``PERF.md`` section 4).

The serving check compares, on logits, what the engine's own programs
returned with the reference's full forward: ``prefill_logits_rel_rms``
over one prompt in every prefill bucket AND one prompt of two chunks
(the second chunk starts from the first's state and is padded);
``decode_logits_rel_rms`` over ``decode_steps`` forced tokens through
``decode_step``; ``served_token_deficit`` over requests the scheduler
retired in the window from reused slots. ``serve_control`` computes the
same numbers with the reference made wrong in one of five ways.
"""
import numpy as np

from . import jamba_reference as reference
from .gpt2 import _LogitsTap, _relative_rms

CONTROLS = ("fp8_matmuls", "state_one_precision_lower",
            "previous_tenants_state", "second_chunk_from_zero",
            "another_requests_prompt")
_STATE_BYTES = {"float32": 4, "bfloat16": 2}
_LOWER = {"float32": "bfloat16", "bfloat16": "fp8"}
_noted = []         # the engine's state pool, for release()


# ---------------------------------------------------------------- engines
def _program_config(config):
    import jax.numpy as jnp
    from deepspeed_tpu.models import jamba
    # the weights are drawn in the precision they are served in (the
    # whole model does not fit the chip in float32)
    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[
        config["inference"]["dtype"]]
    return jamba.config_from_hf(
        config["model"], dtype=dtype,
        state_dtype=jnp.dtype(config["precision_state"]))


def build_serve_engine(config, seed):
    """``deepspeed_tpu.init_inference()`` on the configuration's
    ``inference`` section; weights from ``seed``."""
    import deepspeed_tpu
    from deepspeed_tpu.models import jamba
    return deepspeed_tpu.init_inference(
        model=jamba.make_jamba_model(_program_config(config), seed=seed),
        config={"inference": config["inference"]},
        seed=seed % (2 ** 31 - 1))


def build_train_engine(config, seed):
    raise NotImplementedError(
        "the Jamba family is served, not trained: the training state of "
        "one 14-layer period does not fit a chip")


def release(*trees):
    """Free the device memory of an engine's arrays now, the state
    pool that ``serve_engine_outputs`` noted with them: the reference
    needs the room."""
    import jax
    for leaf in jax.tree_util.tree_leaves((trees, _noted)):
        if hasattr(leaf, "delete") and not leaf.is_deleted():
            leaf.delete()
    del _noted[:]


# ----------------------------------------------------------------- counts
def serve_flops_per_token(model):
    """Operations every served token needs, prompt or generated: 2 for
    each weight of the layers' matmuls (the gated MLP of every layer;
    in, x, dt and out projections of a Mamba layer; q, k, v and o of an
    attention layer). A floor: the head, which only a sampled position
    needs, the scan and attention's scores and values are left out."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    di, n = reference.d_inner(model), model["mamba_d_state"]
    r = model["mamba_dt_rank"]
    kv = (model["num_key_value_heads"] * d //
          model["num_attention_heads"])
    layers = model["num_hidden_layers"]
    n_attn = sum(reference.is_attention(model, i) for i in range(layers))
    mamba = d * 2 * di + di * (r + 2 * n) + r * di + di * d
    attn = 2 * d * d + 2 * d * kv
    return 2.0 * (layers * 3 * d * ff + n_attn * attn +
                  (layers - n_attn) * mamba)


def mamba_scan_bytes(model, padded_tokens, chunks):
    """Bytes the prefill scan's operands and results take, all Mamba
    layers: per padded token x, dt and y (float32, d_inner each) and B,
    C (float32, d_state each); per chunk A, the initial and the final
    state (float32, d_state x d_inner each)."""
    di, n = reference.d_inner(model), model["mamba_d_state"]
    layers = sum(not reference.is_attention(model, i)
                 for i in range(model["num_hidden_layers"]))
    return layers * (padded_tokens * 4 * (3 * di + 2 * n) +
                     chunks * 3 * 4 * n * di)


def mamba_step_bytes(model, slot_steps, state_dtype):
    """Bytes the decode step's state update takes for ``slot_steps``
    (live slots summed over steps), all Mamba layers: the slot's SSM
    state read and written, x, dt and y (float32, d_inner), B and C."""
    di, n = reference.d_inner(model), model["mamba_d_state"]
    layers = sum(not reference.is_attention(model, i)
                 for i in range(model["num_hidden_layers"]))
    return layers * slot_steps * (2 * n * di * _STATE_BYTES[state_dtype] +
                                  4 * (3 * di + 2 * n))


# ----------------------------------------------------------------- checks
def serve_check_inputs(config, seed):
    """Seeded prompts and forced continuations: one prompt in each
    prefill bucket (its length drawn inside the bucket) and one of two
    chunks (longer than the largest bucket, its second chunk padded),
    each followed by ``decode_steps`` tokens fed one at a time through
    the decode program. -> (sequences, prompt lengths)."""
    spec = config["check"]
    rng = np.random.default_rng([seed, 0xC0FFEE])
    buckets = config["inference"]["prefill_buckets"]
    vocab = config["model"]["padded_vocab_size"]
    lows = [1] + [b + 1 for b in buckets[:-1]]
    lens = [int(rng.integers(max(lo, hi // 2), hi))
            for lo, hi in zip(lows, buckets)]
    lens.append(int(rng.integers(buckets[-1] + buckets[0] // 2,
                                 buckets[-1] + buckets[0])))
    return [rng.integers(0, vocab, n + spec["decode_steps"]).astype(
        np.int32) for n in lens], lens


class _RowsTap(_LogitsTap):
    def take(self, rows):
        """The last program's logits for the first ``rows`` rows."""
        if self.last is None:
            return np.full((rows, 1), np.nan, np.float32)
        last, self.last = self.last, None
        last = last.reshape(-1, last.shape[-1])
        return np.asarray(last[:rows], np.float32)


def engine_logits(engine, sequences, prompt_lens, decode_steps):
    """Prefill each prompt into a slot of its own, in chunks of the
    largest bucket as the scheduler does, and feed the forced
    continuation through ``engine.decode_step``, all sequences
    together; per sequence the logits (decode_steps + 1, V) at the
    prompt's last position and after each fed token."""
    tap = _RowsTap(engine)
    slots = list(range(len(sequences)))
    largest = engine.prefill_buckets[-1]
    out = [[] for _ in sequences]
    try:
        for slot, seq, n in zip(slots, sequences, prompt_lens):
            if not engine.try_admit(slot, seq[:n].tolist()):
                raise RuntimeError("check: no pages to prefill")
            for start in range(0, n, largest):
                engine.prefill_chunk(slot, seq[start:min(n, start + largest)],
                                     start)
            out[slot].append(tap.take(1)[0])
        for step in range(decode_steps):
            tokens = np.zeros((engine.num_slots,), np.int32)
            for slot, seq, n in zip(slots, sequences, prompt_lens):
                tokens[slot] = seq[n + step]
                if not engine.ensure_pages(slot, n + step + 1):
                    raise RuntimeError("check: no pages to decode")
            engine.decode_step(tokens, active=slots)
            logits = tap.take(len(slots))
            for slot in slots:
                engine.advance(slot)
                out[slot].append(logits[slot])
    finally:
        tap.close()
        for slot in slots:
            engine.free_slot(slot)
    return [np.stack(rows) for rows in out]


def serve_engine_outputs(config, seed, engine):
    """What the engine (all slots free) gives on the check's inputs."""
    if engine.state is not None:
        _noted.append(engine.state.arrays)
    sequences, lens = serve_check_inputs(config, seed)
    return engine_logits(engine, sequences, lens,
                         config["check"]["decode_steps"])


def _padded(ids, multiple=256):
    """``ids`` zero-padded to a multiple (the model is causal: what
    follows a position changes nothing before it), so that the
    reference compiles a few lengths and not one per sequence."""
    n = -(-len(ids) // multiple) * multiple
    out = np.zeros((n,), np.int32)
    out[:len(ids)] = ids
    return out


def reference_logits(config, seed, sequences, prompt_lens, **wrong):
    """The reference's full forward over each whole sequence (prompt
    and forced continuation), read at the prompt's last position and
    after each fed token. ``wrong``: keyword arguments of
    ``reference.forward_many`` that make a control of it."""
    steps = config["check"]["decode_steps"]
    positions = [np.arange(n - 1, n + steps) for n in prompt_lens]
    logits = reference.forward_many(
        config["model"], seed, [_padded(s) for s in sequences], positions,
        **wrong)
    return [np.asarray(x) for x in logits]


def _deficit(logits, tokens):
    chosen = logits[np.arange(len(tokens)), np.asarray(tokens)]
    return float(((logits.max(-1) - chosen) / logits.std(-1)).max())


def served_token_deficit(config, seed, served, stale_state=False,
                         swap=False):
    """How far the scheduler's tokens lie from the reference's choice:
    for each served request (prompt, generated tokens) the reference's
    full forward over prompt + tokens gives the logits every token was
    chosen from; a token's deficit is (largest logit - the chosen
    token's logit) over the logits' standard deviation, 0 where the
    reference chooses the same. The largest over all tokens. With
    ``stale_state`` the reference begins each request from the state
    in which it left the PREVIOUS one (the last from the first's): the
    control for a slot reused without its reset. With ``swap`` each
    request's tokens are judged under the NEXT request's prompt: the
    control for a request that read another's pages or state."""
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


def _logit_checks(spec, got, ref):
    prefill = max(_relative_rms(g[:1], r[:1]) for g, r in zip(got, ref))
    decode = max(_relative_rms(g[1:], r[1:]) for g, r in zip(got, ref))
    return {
        "prefill_logits_rel_rms": (prefill,
                                   spec["prefill_logits_rel_rms"]),
        "decode_logits_rel_rms": (decode, spec["decode_logits_rel_rms"]),
    }


def serve_check(config, seed, got=None, served=None, rounding=None):
    """``{name: (value, limit)}``. Prefill (every bucket and two
    chunks), then decode through the cache and the state pool
    (``got``, from ``serve_engine_outputs``), against the reference's
    full forward at the same positions, on logits; without ``got``,
    the reference computed in ``rounding`` stands in the engine's
    place. And the tokens of ``served`` requests, as the scheduler gave
    them under load, against the reference's choice at each; no
    request to look at is not correct."""
    spec = config["check"]
    sequences, lens = serve_check_inputs(config, seed)
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


def serve_control(config, seed, control, served=None):
    """The check's numbers with the reference made wrong in the way
    ``control`` names standing in the engine's place, ``{name: (value,
    limit)}``: ``fp8_matmuls`` (operands of every weight matmul rounded
    to e4m3's 4 significant bits), ``state_one_precision_lower`` (the
    SSM state rounded after every step to the precision below the one
    the configuration states), ``second_chunk_from_zero`` (the
    recurrent state dropped at the largest bucket's edge, where a long
    prompt's second chunk starts), ``previous_tenants_state`` (a
    reference that began each of the check's prompts, and each served
    request, from the state the previous one left: the prompts'
    logits show it, the served tokens hardly: after a prompt of some
    hundred tokens little of a stale state is left),
    ``another_requests_prompt`` (each served request's tokens judged
    under the next one's prompt: what the served-token number is
    there to reject)."""
    spec = config["check"]
    sequences, lens = serve_check_inputs(config, seed)
    if control == "another_requests_prompt":
        return {"served_token_deficit": (
            served_token_deficit(config, seed, served, swap=True),
            spec["served_token_deficit"])}
    if control == "previous_tenants_state":
        # the check's own prompts go into slots that the window's
        # requests used: each begun from the state in which the
        # previous one's full forward ended (the first from the last's)
        steps = spec["decode_steps"]
        _, finals = reference.forward_many(
            config["model"], seed, sequences, [[0]] * len(sequences),
            return_state=True)
        positions = [np.arange(n - 1, n + steps) for n in lens]
        got = reference.forward_many(
            config["model"], seed, sequences, positions,
            initial=finals[-1:] + finals[:-1])
        ref = reference_logits(config, seed, sequences, lens)
        checks = _logit_checks(spec, [np.asarray(x) for x in got], ref)
        if served:
            checks["served_token_deficit"] = (
                served_token_deficit(config, seed, served,
                                     stale_state=True),
                spec["served_token_deficit"])
        return checks
    wrong = {
        "fp8_matmuls": {"rounding": "fp8"},
        "state_one_precision_lower": {
            "state_rounding": _LOWER[config["precision_state"]]},
        "second_chunk_from_zero": {
            "reset_at": config["inference"]["prefill_buckets"][-1]},
    }[control]
    ref = reference_logits(config, seed, sequences, lens)
    got = reference_logits(config, seed, sequences, lens, **wrong)
    return _logit_checks(spec, got, ref)
