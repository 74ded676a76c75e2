"""The GPT-2 family as the benchmark drives it: the program's engines
built through the normal entry points from a configuration file, the
counts of operations and bytes that price its metrics, and the output
checks against ``gpt2_reference``.

A configuration file's ``model`` section carries the published sizes
under the names of the released ``config.json`` (n_layer, n_embd,
n_head, n_positions, vocab_size) plus ``padded_vocab_size``, the rows
the program really holds.
"""
import numpy as np

from . import gpt2_reference as reference


# ---------------------------------------------------------------- engines
def _program_model(model, seed, **overrides):
    from deepspeed_tpu.models import gpt2
    cfg = gpt2.GPT2Config(
        vocab_size=model["padded_vocab_size"],
        max_seq_len=model["n_positions"], n_layers=model["n_layer"],
        n_heads=model["n_head"], d_model=model["n_embd"], **overrides)
    return gpt2.make_gpt2_model(config=cfg, seed=seed)


def build_train_engine(config, seed):
    """``deepspeed_tpu.initialize()`` on the configuration's
    ``ds_config``; weights from ``seed``."""
    import deepspeed_tpu
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=_program_model(config["model"], seed,
                             **config.get("model_options", {})),
        config_params=config["ds_config"])
    return engine


def build_serve_engine(config, seed):
    """``deepspeed_tpu.init_inference()`` on the configuration's
    ``inference`` section; weights from ``seed``."""
    import deepspeed_tpu
    return deepspeed_tpu.init_inference(
        model=_program_model(config["model"], seed),
        config={"inference": config["inference"]},
        seed=seed % (2 ** 31 - 1))


def release(*trees):
    """Free the device memory of an engine's arrays now (its jitted
    programs and their closures would otherwise keep them until a
    collection): the reference needs the room."""
    import jax
    for leaf in jax.tree_util.tree_leaves(trees):
        if hasattr(leaf, "delete"):
            leaf.delete()


# ----------------------------------------------------------------- counts
def param_count(model):
    d, L = model["n_embd"], model["n_layer"]
    return (model["padded_vocab_size"] * d + model["n_positions"] * d +
            L * (12 * d * d + 13 * d) + 2 * d)


def train_flops_per_token(model, seq):
    """Operations forward and backward need per token: 6 for each
    weight a token meets in a matmul (every parameter but the position
    table, the tied head counted once) plus causal attention's scores
    and values, 6 L s d (half of the square). Recomputation is not
    counted."""
    d, L = model["n_embd"], model["n_layer"]
    matmul_params = param_count(model) - model["n_positions"] * d
    return 6.0 * matmul_params + 6.0 * L * seq * d


def serve_flops_per_token(model):
    """Operations every served token needs, prompt or generated: 2 for
    each weight of the layers' matmuls (12 d^2 a layer). A floor: the
    head, which only a sampled position needs, and attention's scores
    and values, which grow with the context, are left out."""
    d, L = model["n_embd"], model["n_layer"]
    return 2.0 * L * 12 * d * d


def flash_attention_flops(model, rows, seq):
    """Causal attention forward and backward over ``rows`` sequences of
    ``seq`` tokens, all layers: forward is two matmuls over the lower
    triangle (2 * 2 * s^2/2 * d per layer and row), backward twice that
    (dq, dk, dv, dp; the recomputed scores are not counted)."""
    d, L = model["n_embd"], model["n_layer"]
    forward = 2.0 * seq * seq * d
    return rows * L * 3.0 * forward


def paged_attention_bytes(model, page_size, pages, dtype_bytes=2):
    """Bytes decode attention has to read for ``pages`` live pages (a
    count summed over slots and steps), all layers: their keys and
    values."""
    d, L = model["n_embd"], model["n_layer"]
    return pages * page_size * 2 * L * d * dtype_bytes


# ----------------------------------------------------------------- checks
def adam_of(ds_config):
    params = ds_config["optimizer"]["params"]
    beta1, beta2 = params.get("betas", (0.9, 0.999))
    return {"lr": params["lr"], "beta1": beta1, "beta2": beta2,
            "eps": params.get("eps", 1e-8)}


def train_probe(engine, stride):
    """The engine's float32 master weights on the check's strided
    sample of coordinates, as the reference names and stacks them
    (read after the first step: the update's direction is compared)."""
    import jax
    master = engine.get_master_params()
    blocks = master["blocks"]
    pick = {"qkv_w": ("attn", "qkv_kernel"), "proj_w": ("attn",
            "proj_kernel"), "fc_w": ("mlp", "fc_kernel"),
            "fc2_w": ("mlp", "proj_kernel")}
    sample = {name: [b[group][leaf][::stride] for b in blocks]
              for name, (group, leaf) in pick.items()}
    sample.update(wte=master["wte"][::stride], wpe=master["wpe"][::stride])
    sample = jax.device_get(sample)
    return {k: np.stack(v) if isinstance(v, list) else np.asarray(v)
            for k, v in sample.items()}


def _sign_disagreement(after, reference_run):
    """Share of the sampled weights whose first update goes the other
    way than the reference's, over those the reference moves."""
    differ = moved = 0
    for name in reference.MATRICES:
        ref = np.sign(reference_run["after"][name] -
                      reference_run["before"][name])
        got = np.sign(after[name] - reference_run["before"][name])
        moved += int((ref != 0).sum())
        differ += int(((got != ref) & (ref != 0)).sum())
    return differ / moved


def train_check(config, seed, batches, engine_losses=None, probe=None,
                rounding=None):
    """``{name: (value, limit)}``: the engine's first two losses from
    the seed's weights, and the direction of its first update on a
    strided sample of the weights (``probe``, from ``train_probe``
    after the first step), against the reference's own two float32
    steps on the same batches. The update's direction has passed
    through the gradients, the second loss through the update. With
    ``rounding`` the reference computed in that precision stands in
    the engine's place (the control)."""
    spec = config["check"]
    adam = adam_of(config["ds_config"])
    ref = reference.two_steps(config["model"], seed, batches, adam,
                              spec["stride"])
    if rounding is not None:
        control = reference.two_steps(config["model"], seed, batches, adam,
                                      spec["stride"], rounding=rounding)
        engine_losses, probe = control["losses"], control["after"]
    return {
        "loss0_abs_err": (abs(engine_losses[0] - ref["losses"][0]),
                          spec["loss0_abs_err"]),
        "loss1_abs_err": (abs(engine_losses[1] - ref["losses"][1]),
                          spec["loss1_abs_err"]),
        "update_sign_disagreement": (_sign_disagreement(probe, ref),
                                     spec["update_sign_disagreement"]),
    }


def _relative_rms(got, ref):
    """RMS of (got - ref) over the RMS of ref about its mean, per row
    of logits; the worst row."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    err = np.sqrt(((got - ref) ** 2).mean(-1))
    scale = np.sqrt(((ref - ref.mean(-1, keepdims=True)) ** 2).mean(-1))
    return float((err / scale).max())


def serve_check_inputs(config, seed):
    """Seeded prompts and forced continuations for the serving check:
    one prompt in each of the configuration's prefill buckets (its
    length drawn inside the bucket) plus ``decode_steps`` tokens fed
    one at a time through the decode program."""
    spec = config["check"]
    rng = np.random.default_rng([seed, 0xC0FFEE])
    buckets = config["inference"]["prefill_buckets"]
    vocab = config["model"]["padded_vocab_size"]
    lows = [1] + [b + 1 for b in buckets[:-1]]
    lens = [int(rng.integers(max(lo, hi // 2), hi - spec["decode_steps"]))
            for lo, hi in zip(lows, buckets)]
    return [rng.integers(0, vocab, n + spec["decode_steps"]).astype(
        np.int32) for n in lens], lens


class _LogitsTap:
    """Keeps the logits that the engine's prefill and decode programs
    return beside the chosen token (their last output), while the
    engine is driven through its public calls. The engine has no public
    call that hands logits out, so the two methods that make its jitted
    programs are wrapped for the length of the check; a program whose
    arguments change does not disturb this, and where a method is no
    longer there the logits read as NaN, which is not correct."""

    def __init__(self, engine):
        self.engine, self.last, self._undo = engine, None, []
        for name in ("_get_prefill_fn", "_get_decode_fn"):
            if hasattr(engine, name):
                self._wrap(name, getattr(engine, name))

    def _wrap(self, name, make):
        def tapped_make(*args, **kwargs):
            program = make(*args, **kwargs)

            def tapped(*a, **k):
                out = program(*a, **k)
                self.last = out[-1]
                return out
            return tapped
        setattr(self.engine, name, tapped_make)
        self._undo.append(name)

    def take(self, rows):
        """The last program's logits as (rows, V); NaN if none came."""
        if self.last is None:
            return np.full((rows, 1), np.nan, np.float32)
        logits, self.last = np.asarray(self.last, np.float32), None
        return logits.reshape(rows, -1)

    def close(self):
        for name in self._undo:
            delattr(self.engine, name)     # the class's method again


def engine_logits(engine, sequences, prompt_lens, decode_steps):
    """Prefill each prompt into a slot of its own (``engine.prefill``)
    and feed the forced continuation through ``engine.decode_step``,
    all sequences together; returns per sequence the logits
    (decode_steps + 1, V) at the prompt's last position and after each
    fed token, on an engine whose slots are all free."""
    tap = _LogitsTap(engine)
    slots = list(range(len(sequences)))
    out = [[] for _ in sequences]
    try:
        for slot, seq, n in zip(slots, sequences, prompt_lens):
            engine.prefill(slot, seq[:n])
            out[slot].append(tap.take(1)[0])
        for step in range(decode_steps):
            tokens = np.zeros((engine.num_slots,), np.int32)
            for slot, seq, n in zip(slots, sequences, prompt_lens):
                tokens[slot] = seq[n + step]
                if not engine.ensure_pages(slot, n + step + 1):
                    raise RuntimeError("check: no pages to decode")
            engine.decode_step(tokens)
            logits = tap.take(engine.num_slots)
            for slot in slots:
                engine.advance(slot)
                out[slot].append(logits[slot])
    finally:
        tap.close()
        for slot in slots:
            engine.free_slot(slot)
    return [np.stack(rows) for rows in out]


def reference_logits(config, w, sequences, prompt_lens, rounding=None):
    """The reference's full forward (weights ``w``) over each whole
    sequence (prompt and forced continuation), read at the same
    positions."""
    import jax.numpy as jnp
    steps = config["check"]["decode_steps"]
    out = []
    for seq, n in zip(sequences, prompt_lens):
        positions = np.arange(n - 1, n + steps, dtype=np.int32)[None]
        # padded to the prompt's prefill bucket: as many compiled
        # reference programs as the engine has prefill programs
        bucket = min(b for b in config["inference"]["prefill_buckets"]
                     if b >= len(seq))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(seq)] = seq
        out.append(np.asarray(reference.logits_at(
            w, jnp.asarray(padded), jnp.asarray(positions),
            config["model"]["n_head"], rounding))[0])
    return out


def serve_engine_outputs(config, seed, engine):
    """What the engine (all slots free) gives on the check's inputs."""
    sequences, lens = serve_check_inputs(config, seed)
    return engine_logits(engine, sequences, lens,
                         config["check"]["decode_steps"])


def served_token_deficit(config, w, served, swap=False):
    """How far the scheduler's tokens lie from the reference's choice:
    for each served request (prompt, generated tokens) the reference's
    full forward over prompt + tokens gives the logits every token was
    chosen from; a token's deficit is (largest logit - the chosen
    token's logit) over the logits' standard deviation, 0 where the
    reference chooses the same (``chip_smoke.py``'s tie rule, in units
    of the row's spread). Returns the largest over all tokens. With
    ``swap`` each request's tokens are judged under the NEXT request's
    prompt: the control for a request that read another's pages."""
    import jax.numpy as jnp
    model = config["model"]
    width = config["inference"]["max_new_tokens"]
    worst = 0.0
    for i, (prompt, tokens) in enumerate(served):
        if swap:
            prompt = served[(i + 1) % len(served)][0]
        n, m = len(prompt), len(tokens)
        ids = np.zeros((1, model["n_positions"]), np.int32)
        ids[0, :n] = prompt
        ids[0, n:n + m - 1] = tokens[:-1]
        positions = np.minimum(n - 1 + np.arange(width), n + m - 2)
        logits = np.asarray(reference.logits_at(
            w, jnp.asarray(ids), jnp.asarray(positions[None], jnp.int32),
            model["n_head"]))[0, :m]
        chosen = logits[np.arange(m), np.asarray(tokens)]
        deficit = (logits.max(-1) - chosen) / logits.std(-1)
        worst = max(worst, float(deficit.max()))
    return worst


def serve_check(config, seed, got=None, served=None, rounding=None):
    """``{name: (value, limit)}``. Prefill, then decode through the
    paged cache (``got``, from ``serve_engine_outputs``), against the
    reference's full forward at the same positions, on logits; without
    ``got``, the reference computed in ``rounding`` stands in the
    engine's place (the control). And the tokens of ``served``
    requests, as the scheduler gave them under load, against the
    reference's choice at each (``served_token_deficit``); no request
    to look at is not correct."""
    spec = config["check"]
    import jax
    import jax.numpy as jnp
    sequences, lens = serve_check_inputs(config, seed)
    w = jax.tree_util.tree_map(
        jnp.asarray, reference.draw_weights(config["model"], seed))
    ref = reference_logits(config, w, sequences, lens)
    if got is None:
        got = reference_logits(config, w, sequences, lens, rounding)
    prefill = max(_relative_rms(g[:1], r[:1]) for g, r in zip(got, ref))
    decode = max(_relative_rms(g[1:], r[1:]) for g, r in zip(got, ref))
    checks = {
        "prefill_logits_rel_rms": (prefill,
                                   spec["prefill_logits_rel_rms"]),
        "decode_logits_rel_rms": (decode, spec["decode_logits_rel_rms"]),
    }
    if served is not None:
        checks["served_token_deficit"] = (
            served_token_deficit(config, w, served) if served
            else float("nan"), spec["served_token_deficit"])
    return checks
