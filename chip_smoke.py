#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the system starts on the chip.

One process, GPT-2 350M (gpt2_medium: 24 layers, d_model 1024, 16 heads,
vocab 50304) at full width and depth, seq 1024, bf16, random weights from
``--seed``, through the entry points a user calls:

  device   jax.devices() must be a TPU — else exit non-zero, no result
  kernels  every main-path Pallas kernel compiled for the chip
           (interpret=False) against its XLA oracle at gpt2_medium widths
  train    deepspeed_tpu.initialize() -> train_batch() steps, then
           forward/backward/step micro-steps (ZeRO-2, bf16, Adam)
  serve    deepspeed_tpu.init_inference() + continuous batching, every
           token of every stream judged by a full-sequence forward

``--four-chips`` runs ONLY the ZeRO-3 data=4 path and the one-chip run it
is compared with (needs four chips; the default needs one).

Any failed check raises: the run exits non-zero and prints no result.
The last line of stdout is the result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Timings printed on the way are observations, not metrics.

The phases are functions of a model config. ``main()`` runs them at
gpt2_medium on the chip and has no switch that lets it pass without
one; tests/unit/test_chip_entry.py calls the same functions with a tiny
config and ``rehearsal=True`` (kernels under the Pallas interpreter on
the virtual CPU mesh), so a change that breaks a phase fails a tier-1
test before it costs chip time.
"""
import argparse
import dataclasses
import gc
import json
import sys
import time
import types

import numpy as np

SEQ = 1024
# The one micro-batch the train phase uses: with remat off, 350M's
# activations at 8 x 1024 tokens fit beside the fp32 ZeRO-2 state in the
# chip's 16 GB (fused_train prints its device bytes; 13.4 GB measured).
MICRO_BATCH = 8
# bf16 kernel-vs-oracle bound: max|err| <= KERNEL_TOL * max(1, max|ref|).
# bf16 keeps 8 significant bits (2^-8 ~ 0.4%); operands and outputs are
# each rounded once and the backward chains three such matmuls.
KERNEL_TOL = 3e-2
# A served stream must be the reference's up to bf16 tie-breaks: greedy
# decoding takes the argmax of bf16 logits, the serving path accumulates
# attention in another order than a full-sequence forward (the in-kernel
# page walk), and a last-bit difference can flip an argmax between two
# near-equal logits. RULE: at EVERY token of every request, the served
# token's logit in an independent full-sequence forward over the
# request's own stream so far must lie within TIE_ULPS bf16 ulps (at the
# top logit's magnitude) of that forward's top logit. Anything else
# fails.
TIE_ULPS = 8
# per-step loss agreement, four chips vs one (bf16 grads reduce in a
# different order across chips; losses are ~ln(vocab) = 10.8)
FOUR_CHIP_LOSS_TOL = 5e-2


def log(msg):
    print(msg, flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def gpt2_medium(**overrides):
    from deepspeed_tpu.models import gpt2
    return gpt2.config_for("gpt2_medium", max_seq_len=SEQ, **overrides)


def _max_err(got, ref):
    import jax.numpy as jnp
    got, ref = jnp.asarray(got, jnp.float32), jnp.asarray(ref, jnp.float32)
    err = float(jnp.max(jnp.abs(got - ref)))
    return err, float(jnp.max(jnp.abs(ref)))


def _report_kernel(name, pairs, tol=KERNEL_TOL):
    parts = []
    for what, got, ref in pairs:
        err, scale = _max_err(got, ref)
        check(np.isfinite(err), f"{name}/{what}: non-finite output")
        check(err <= tol * max(1.0, scale),
              f"{name}/{what}: max-abs error {err:.3e} over "
              f"{tol} * max(1, {scale:.3e})")
        parts.append(f"{what}={err:.3e} (max|ref| {scale:.3g})")
    log(f"kernel {name}: max_abs_err " + ", ".join(parts))


# ------------------------------------------------------------------ kernels
def phase_kernels(cfg, seed, rehearsal=False):
    """Each main-path kernel against its XLA oracle (oracle matmuls at
    highest precision, so the bound is the kernel's own bf16 error)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import _attend_cache_rows
    from deepspeed_tpu.ops.adam.fused_adam import adam_update
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    from deepspeed_tpu.ops.transformer.attention import \
        reference_causal_attention

    interpret = rehearsal
    b, slots, page_size = 2, 8, 16
    s, h, dh, d = cfg.max_seq_len, cfg.n_heads, cfg.d_head, cfg.d_model
    ks = jax.random.split(jax.random.PRNGKey(seed), 12)
    bf16 = jnp.bfloat16

    def oracle(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    # flash attention, forward and backward
    q, k, v = (jax.random.normal(ks[i], (b, s, h, dh), bf16)
               for i in range(3))
    do = jax.random.normal(ks[3], (b, s, h, dh), bf16)

    def vjp_of(attn):
        def run(q, k, v, do):
            out, pull = jax.vjp(attn, q, k, v)
            return (out,) + pull(do)
        return run

    got = jax.jit(vjp_of(lambda q, k, v: fa.flash_attention_bshd(
        q, k, v, interpret=interpret)))(q, k, v, do)
    ref = oracle(vjp_of(reference_causal_attention))(q, k, v, do)
    _report_kernel(
        "flash_attention_bshd", list(zip(("out", "dq", "dk", "dv"),
                                         got, ref)))

    # fused LN + QKV + flash, forward and backward
    x = jax.random.normal(ks[4], (b, s, d), bf16)
    ln_s = (1.0 + 0.1 * jax.random.normal(ks[5], (d,))).astype(bf16)
    ln_b = (0.1 * jax.random.normal(ks[6], (d,))).astype(bf16)
    w = (0.02 * jax.random.normal(ks[7], (d, 3 * d))).astype(bf16)
    wb = jnp.zeros((3 * d,), bf16)
    dctx = jax.random.normal(ks[8], (b, s, d), bf16)

    def lnqkv_oracle(x, ln_s, ln_b, w, wb):
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        ln = ((xf - mu) * jax.lax.rsqrt(var + 1e-5) * ln_s + ln_b) \
            .astype(x.dtype)
        q, k, v = (t.reshape(b, s, h, dh)
                   for t in jnp.split(ln @ w + wb, 3, -1))
        return reference_causal_attention(q, k, v).reshape(b, s, d)

    def lnqkv_vjp(fn):
        def run(x, ln_s, ln_b, w, wb, dctx):
            out, pull = jax.vjp(fn, x, ln_s, ln_b, w, wb)
            dx, _, _, dw, _ = pull(dctx)
            return out, dx, dw
        return run

    got = jax.jit(lnqkv_vjp(lambda *a: fa.fused_ln_qkv_attention(
        *a, h, interpret=interpret)))(x, ln_s, ln_b, w, wb, dctx)
    ref = oracle(lnqkv_vjp(lnqkv_oracle))(x, ln_s, ln_b, w, wb, dctx)
    _report_kernel(
        "fused_ln_qkv_attention", list(zip(("ctx", "dx", "dqkv_w"),
                                           got, ref)))

    # fused Adam apply at the embedding shape (fp32 state)
    shape = (cfg.vocab_size, d)
    p = {"wte": 0.02 * jax.random.normal(ks[9], shape)}
    g = {"wte": 1e-3 * jax.random.normal(ks[10], shape)}
    state = {"step": jnp.zeros((), jnp.int32),
             "exp_avg": {"wte": 1e-3 * jax.random.normal(ks[11], shape)},
             "exp_avg_sq": {"wte": jnp.full(shape, 1e-6)}}

    def adam(use_pallas):
        return jax.jit(lambda g, st, p: adam_update(
            g, st, p, 1e-4, 0.9, 0.999, 1e-8, 0.01,
            use_pallas=use_pallas, interpret=interpret))(g, state, p)

    (gp, gs), (rp, rs) = adam(True), adam(False)
    _report_kernel("fused_adam", [
        ("param", gp["wte"], rp["wte"]),
        ("exp_avg", gs["exp_avg"]["wte"], rs["exp_avg"]["wte"]),
        ("exp_avg_sq", gs["exp_avg_sq"]["wte"], rs["exp_avg_sq"]["wte"])],
        tol=1e-5)

    # paged-attention decode: mixed live lengths, distinct pages per slot
    max_pages = s // page_size
    n_pages = slots * max_pages
    rng = np.random.RandomState(seed)
    pool_shape = (n_pages + 1, 2, page_size, h * dh)
    k_pool = jax.random.normal(ks[0], pool_shape, bf16)
    v_pool = jax.random.normal(ks[1], pool_shape, bf16)
    tables = jnp.asarray(
        1 + rng.permutation(n_pages).reshape(slots, max_pages), jnp.int32)
    positions = jnp.asarray(
        rng.randint(page_size, s - 1, size=slots), jnp.int32)
    vlens = jnp.ones((slots,), jnp.int32)
    qd = jax.random.normal(ks[2], (slots, 1, h, dh), bf16)

    def gather_oracle(qd, k_pool, v_pool, tables, positions, vlens):
        def rows_of(pool):
            g = jnp.take(pool[:, 1], tables, axis=0)
            return g.reshape(slots, max_pages * page_size, h, dh) \
                .transpose(0, 2, 1, 3)
        return _attend_cache_rows(qd, rows_of(k_pool), rows_of(v_pool),
                                  positions, dh, valid_lens=vlens)

    got = jax.jit(lambda *a: paged_attention(
        *a, layer_idx=1, page_size=page_size, interpret=interpret))(
            qd, k_pool, v_pool, tables, positions, vlens)
    ref = oracle(gather_oracle)(qd, k_pool, v_pool, tables, positions,
                                vlens)
    _report_kernel(
        "paged_attention", [("decode_ctx", got, ref)])


# -------------------------------------------------------------------- train
def _train_config(micro_batch, gas, stage, rehearsal):
    # on the chip "auto" must resolve to the compiled kernels; off it a
    # forced "pallas" runs them under the interpreter
    kernels = "pallas" if rehearsal else "auto"
    zero = {"stage": stage}
    if rehearsal and stage == 3:
        # a tiny model's leaves all sit under the default persistence
        # threshold and would stay replicated
        zero["stage3_param_persistence_threshold"] = 0
    return {
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": gas,
        "bf16": {"enabled": True},
        "zero_optimization": zero,
        "optimizer": {"type": "Adam", "params": {
            "lr": 1e-4, "fused_kernel": kernels}},
        "transformer": {"flash_attention": kernels},
        "steps_per_print": 10 ** 9,
    }


def _expected(rehearsal):
    return "interpret" if rehearsal else "pallas"


def _seeded_batch(cfg, seed, *lead):
    ids = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, size=lead + (cfg.max_seq_len,)).astype(np.int32)
    return ids, ids.copy()


def _compile_step(engine, name, batch, rehearsal):
    """Compile the engine's own jitted step program ahead of its first
    call — traced, lowered and compiled once, the call then finds the
    executable — and check the kernel is in it, not the reference."""
    from deepspeed_tpu.analysis import lower_engine_program
    t0 = time.time()
    lowered = lower_engine_program(engine, name, batch=batch)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    hlo = compiled.as_text()
    n_kernels = hlo.count("tpu_custom_call")
    log(f"{name}: compile_seconds={t2 - t0:.1f} (trace+lower "
        f"{t1 - t0:.1f}, compiler or its cache {t2 - t1:.1f}) "
        f"tpu_custom_calls={n_kernels}")
    if not rehearsal:
        check(n_kernels > 0, f"{name}: no tpu_custom_call in the compiled "
              "step — the reference ran, not the kernel")
    return hlo, compiled


def _timed_steps(step_fn, n):
    import jax
    losses, seconds = [], []
    for _ in range(n):
        t0 = time.time()
        loss = jax.block_until_ready(step_fn())
        seconds.append(time.time() - t0)
        losses.append(float(loss))
    return losses, seconds


def _check_losses(name, losses):
    check(all(np.isfinite(losses)), f"{name}: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"{name}: loss did not fall on a repeated batch: {losses}")


def phase_train(cfg, seed, micro_batch, rehearsal=False):
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2

    steps, expected = 6, _expected(rehearsal)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt2.make_gpt2_model(config=cfg, seed=seed),
        config_params=_train_config(micro_batch, 1, 2, rehearsal))
    resolved = engine.resolved_kernels()
    log(f"train: params={gpt2.num_params(cfg)} micro_batch={micro_batch} "
        f"seq={cfg.max_seq_len} zero_stage=2 kernels={resolved}")
    check(resolved == {"flash_attention": expected,
                       "fused_optimizer": expected},
          f"train: kernels resolved to {resolved}, want {expected!r}")

    micro = _seeded_batch(cfg, seed, engine.train_batch_size())
    _, compiled = _compile_step(engine, "fused_train", micro, rehearsal)
    mem = compiled.memory_analysis()
    if mem is not None:
        log("fused_train: device_bytes argument={} output={} temp={} "
            "alias={}".format(mem.argument_size_in_bytes,
                              mem.output_size_in_bytes,
                              mem.temp_size_in_bytes,
                              mem.alias_size_in_bytes))

    stacked = tuple(x[None] for x in micro)
    losses, seconds = _timed_steps(
        lambda: engine.train_batch(batch=stacked), steps)
    log(f"train_batch: first_call_seconds={seconds[0]:.2f}")
    log(f"train_batch: step_seconds={[round(t, 4) for t in seconds[1:]]}")
    log(f"train_batch: losses={[round(x, 4) for x in losses]}")
    _check_losses("train_batch", losses)

    def micro_step():
        loss = engine(*micro)
        engine.backward(loss)
        engine.step()
        return loss

    m_losses, m_seconds = _timed_steps(micro_step, 2)
    log("forward/backward/step: seconds={} losses={}".format(
        [round(t, 2) for t in m_seconds], [round(x, 4) for x in m_losses]))
    check(all(np.isfinite(m_losses)) and m_losses[-1] < losses[0],
          f"micro-steps: loss {m_losses} not below the first {losses[0]}")
    engine.close()
    return {"losses": losses + m_losses, "step_seconds": seconds}


# -------------------------------------------------------------------- serve
def _reference_logits(cfg, params, sequence, first, width):
    """bf16-model, fp32-readout logits for the tokens ``sequence[first:]``
    (row ``t``: the logits that choose ``sequence[first + t]``, after
    ``sequence[:first + t]``): one plain full-sequence XLA forward, no
    cache, no page, no kernel."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import gpt2
    ref_cfg = dataclasses.replace(cfg, use_flash_attention=False,
                                  flash_attention_backend="xla")

    @jax.jit
    def logits_at(params, ids, rows):
        hidden = gpt2.forward_hidden(params, ids, ref_cfg)
        return hidden[0, rows].astype(jnp.float32) @ \
            params["wte"].astype(jnp.float32).T

    ids = np.zeros((1, width), np.int32)
    ids[0, :len(sequence)] = sequence
    return np.asarray(logits_at(
        params, jnp.asarray(ids),
        jnp.arange(first - 1, len(sequence) - 1)))


def _bf16_ulp(x):
    return 2.0 ** (np.floor(np.log2(max(abs(float(x)), 1e-30))) - 7)


def phase_serve(cfg, seed, rehearsal=False):
    """A dozen requests of mixed prompt lengths (64-400 tokens at seq
    1024) through generate() and the continuous-batching scheduler with
    the decode kernel, and EVERY token of every stream held to the
    reference: the one a full-sequence forward over the request's own
    stream so far would choose, or one it cannot tell from it in bf16."""
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2

    n_requests, max_new_tokens, slots = 12, 24, 8
    s = cfg.max_seq_len
    buckets = (s // 4, s // 2)
    rng = np.random.RandomState(seed)
    lens = np.linspace(s // 16, 25 * s // 64, n_requests).astype(int)
    rng.shuffle(lens)
    prompts = [rng.randint(0, cfg.vocab_size, size=int(n)).tolist()
               for n in lens]

    inference = {"max_batch_size": slots, "dtype": "bf16",
                 "prefill_buckets": list(buckets), "greedy": True,
                 "max_new_tokens": max_new_tokens}
    if rehearsal:
        # off the chip ``auto`` takes the XLA gather path; the chip
        # run leaves the key at ``auto``, which must pick the kernel
        inference["paged_attention_kernel"] = "pallas"
    engine = deepspeed_tpu.init_inference(
        model=gpt2.make_gpt2_model(config=cfg, seed=seed),
        config={"inference": inference}, seed=seed)
    log(f"serve: paged_attention_kernel={engine.paged_attention_kernel}")
    check(engine.paged_attention_kernel == "pallas",
          "serve: paged_attention_kernel resolved to "
          f"{engine.paged_attention_kernel!r}, want 'pallas'")
    _compile_step(engine, "decode", None, rehearsal)
    t0 = time.time()
    outs = engine.generate(prompts)
    seconds = time.time() - t0
    log(f"serve: requests={len(outs)} prompt_lens="
        f"{sorted(int(n) for n in lens)} generate_seconds="
        f"{seconds:.1f} (compiles included) compile_stats="
        f"{dict(engine.compile_stats)} page_pool="
        f"{engine.page_pool_stats()}")
    check(len(outs) == n_requests and
          all(len(o) == max_new_tokens for o in outs),
          f"serve: expected {n_requests} streams of "
          f"{max_new_tokens} tokens, got {[len(o) for o in outs]}")

    # every token of every request (12 x 24), one reference forward a
    # request: the stream so far is the context whatever was chosen
    # before, so a tie at one token does not excuse the next
    agree = ties = 0
    for i, (prompt, out) in enumerate(zip(prompts, outs)):
        logits = _reference_logits(cfg, engine.params, prompt + out,
                                   len(prompt), s)
        parted = 0
        for j, token in enumerate(out):
            if token == int(logits[j].argmax()):
                continue
            parted += 1
            top = float(logits[j].max())
            gap = top - float(logits[j][token])
            bound = TIE_ULPS * _bf16_ulp(top)
            log(f"serve: request {i} token {j}: served={token} "
                f"reference={int(logits[j].argmax())} "
                f"reference_logit_gap={gap:.4f} tie_bound={bound:.4f} "
                f"top_logit={top:.3f}")
            check(gap <= bound,
                  f"serve: request {i} token {j} is not the reference's "
                  f"choice beyond a bf16 tie-break (gap {gap:.4f} > "
                  f"{bound:.4f})")
        agree += parted == 0
        ties += parted
    log(f"serve: {agree}/{n_requests} streams are the reference's token "
        f"for token; {ties} of {n_requests * max_new_tokens} tokens a "
        "bf16 tie-break away")
    return {"agree_with_reference": agree, "requests": n_requests,
            "tokens": n_requests * max_new_tokens, "tie_breaks": ties}


# --------------------------------------------------------------- four chips
def _device_shares(tree):
    """``(logical bytes, {device id: bytes held})`` over every
    addressable shard of ``tree`` — a replicated leaf counts in full on
    each device that holds it."""
    import jax
    held, logical = {}, 0
    for leaf in jax.tree_util.tree_leaves(tree):
        logical += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device.id] = held.get(shard.device.id, 0) + \
                shard.data.nbytes
    return logical, held


def phase_four_chips(cfg, seed, devices, rehearsal=False):
    """ZeRO-3 over data=4 against the same global batch and seed on one
    chip of the same host (gradient accumulation 4), in this process."""
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.parallel.topology import build_mesh

    check(len(devices) == 4, f"four chips, not {len(devices)}")
    micro_batch, steps, expected = 4, 4, _expected(rehearsal)
    # XLA:CPU spells the gradient reduce-scatter as an all-reduce
    promised = ("all-gather", "all-reduce" if rehearsal else
                "reduce-scatter")
    global_batch = 4 * micro_batch
    ids, labels = _seeded_batch(cfg, seed, global_batch)

    def run(mesh, gas, tag):
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=gpt2.make_gpt2_model(config=cfg, seed=seed),
            mpu=types.SimpleNamespace(mesh=mesh),
            config_params=_train_config(micro_batch, gas, 3, rehearsal))
        check(engine.train_batch_size() == global_batch,
              f"{tag}: global batch {engine.train_batch_size()}")
        resolved = engine.resolved_kernels()
        log(f"{tag}: devices={mesh.devices.size} zero_stage=3 "
            f"micro_batch={micro_batch} gas={gas} global_batch="
            f"{global_batch} kernels={resolved} (the fused optimizer "
            "kernel is single-device by rule: ops/pallas_utils.py)")
        check(resolved["flash_attention"] == expected,
              f"{tag}: flash attention resolved to {resolved}")
        per_step = global_batch // gas
        micro = (ids[:per_step], labels[:per_step])
        hlo, _ = _compile_step(engine, "fused_train", micro, rehearsal)
        stacked = tuple(x.reshape((gas, per_step) + x.shape[1:])
                        for x in (ids, labels))
        losses, seconds = _timed_steps(
            lambda: engine.train_batch(batch=stacked), steps)
        log("{}: first_call_seconds={:.2f} step_seconds={} losses={}"
            .format(tag, seconds[0], [round(t, 4) for t in seconds[1:]],
                    [round(x, 5) for x in losses]))
        _check_losses(tag, losses)
        return engine, hlo, losses

    engine, hlo, losses4 = run(build_mesh(data=4, devices=devices), 1,
                               "four_chips")
    collectives = {op: hlo.count(op) for op in
                   ("all-gather", "reduce-scatter", "all-reduce")}
    log(f"four_chips: step HLO collectives {collectives}")
    for op in promised:
        check(collectives[op] > 0,
              f"four_chips: no {op} in the ZeRO-3 step the plan promises")
    for name in ("master", "opt", "params"):
        logical, held = _device_shares(engine.state[name])
        fracs = {d: round(n / logical, 4) for d, n in sorted(held.items())}
        log(f"four_chips: {name} bytes={logical} share_by_device={fracs}")
        check(len(held) == 4 and max(fracs.values()) <= 0.34,
              f"four_chips: {name} is not split over four devices: "
              f"{fracs}")
    engine.close()
    del engine
    gc.collect()

    engine1, _, losses1 = run(
        build_mesh(data=1, devices=devices[:1]), 4, "one_chip")
    engine1.close()
    diffs = [abs(a - b) for a, b in zip(losses4, losses1)]
    log(f"four_chips vs one_chip: per-step |loss diff|="
        f"{[round(d, 5) for d in diffs]} (tolerance {FOUR_CHIP_LOSS_TOL})")
    check(max(diffs) <= FOUR_CHIP_LOSS_TOL,
          f"four-chip and one-chip losses disagree: {losses4} vs {losses1}")
    return {"losses4": losses4, "losses1": losses1}


# --------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the ZeRO-3 data=4 path and its "
                         "one-chip comparison (needs four chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t_start = time.time()
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    # device: a TPU or nothing — no retry, no CPU client, no stand-in
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, jax found platform "
                 f"{dev.platform!r}")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={jax.device_count()} jax={jax.__version__} "
        f"compile_cache={cache_dir}")

    cfg = gpt2_medium(remat=False, loss_chunk=128)
    if args.four_chips:
        check(jax.device_count() == 4, "--four-chips needs 4 devices, "
              f"jax has {jax.device_count()}")
        phase_four_chips(cfg, args.seed, jax.devices())
    else:
        phase_kernels(cfg, args.seed)
        phase_train(cfg, args.seed, MICRO_BATCH)
        gc.collect()
        phase_serve(cfg, args.seed)
    log(f"total_seconds={time.time() - t_start:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)


if __name__ == "__main__":
    main()
