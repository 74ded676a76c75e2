"""Where does the GPT-2 step time go? Ablation timings on the real chip.

Times jitted variants of the 125M workload at the bench shape and prints a
breakdown: full train step, fwd-only, fwd+bwd without optimizer, CE-only,
blocks-only (no CE), attention on/off. Run manually:

    python tests/perf/ablate_gpt2_step.py
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

MB = 192
SEQ = 1024


def _force(out):
    import jax
    return jax.block_until_ready(out)


def timed(fn, *args, reps=5):
    _force(fn(*args))
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
        _force(out)
    return (time.time() - t0) / reps * 1e3  # ms


def main():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import gpt2

    cfg = gpt2.config_for("gpt2_small", max_seq_len=SEQ, remat=True,
                          loss_chunk=128)
    params = jax.tree_util.tree_map(
        lambda p: jnp.asarray(p, jnp.bfloat16), gpt2.init_params(cfg, 0))
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(MB, SEQ)),
                      jnp.int32)

    rows = {}

    def loss_fn(p, ids):
        return gpt2.lm_loss(p, ids, ids, cfg, rng=None, train=False)

    rows["fwd_only"] = timed(jax.jit(loss_fn), params, ids)

    grad_fn = jax.jit(jax.grad(loss_fn))
    rows["fwd_bwd"] = timed(grad_fn, params, ids)

    # hidden-states only (no CE): mean of final hidden as dummy loss
    def hidden_loss(p, ids):
        h = gpt2.forward_hidden(p, ids, cfg, rng=None, train=False)
        return h.astype(jnp.float32).mean()

    rows["fwd_bwd_no_ce"] = timed(jax.jit(jax.grad(hidden_loss)), params, ids)

    # no attention (identity instead of attention mixing)
    import deepspeed_tpu.models.gpt2 as g
    orig_attn = g._attn_ctx
    g._attn_ctx = lambda x, blk, c, t: x
    try:
        rows["fwd_bwd_no_attn"] = timed(jax.jit(jax.grad(loss_fn)),
                                        params, ids)
    finally:
        g._attn_ctx = orig_attn

    # no remat
    import dataclasses
    cfg_nr = dataclasses.replace(cfg, remat=False)

    def loss_nr(p, ids):
        return gpt2.lm_loss(p, ids, ids, cfg_nr, rng=None, train=False)

    try:
        rows["fwd_bwd_no_remat"] = timed(jax.jit(jax.grad(loss_nr)),
                                         params, ids)
    except Exception as e:  # noqa: BLE001
        rows["fwd_bwd_no_remat"] = "OOM: " + str(e)[:80]

    print(json.dumps(rows, indent=2))


if __name__ == "__main__":
    main()
