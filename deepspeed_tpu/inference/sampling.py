"""Jit-compatible token sampling: greedy, temperature, top-k, top-p.

The trace-static knobs (``greedy``, ``top_k``, vocab size) select the
compiled sampler; ``temperature`` and ``top_p`` are traced operands so a
per-request override never recompiles. Top-p runs in sorted space (sample
an index into the descending-sorted logits, map back through the sort
permutation) to avoid a vocab-size scatter.
"""
from functools import lru_cache

import jax
import jax.numpy as jnp

NEG_INF = -1e30


@lru_cache(maxsize=None)
def make_sampler(greedy, top_k=0):
    """Build ``sample(logits, rng, temperature, top_p) -> (b,) int32``.

    ``logits`` is (b, vocab); every row samples independently. Cached so
    the engine's jit cache keys stay stable across calls.
    """
    if greedy:
        def sample(logits, rng, temperature, top_p):
            del rng, temperature, top_p
            with jax.named_scope("sample"):
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return sample

    def sample(logits, rng, temperature, top_p):
        with jax.named_scope("sample"):
            logits = logits.astype(jnp.float32) / \
                jnp.maximum(temperature, 1e-6)
            if top_k and top_k > 0:
                kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
                logits = jnp.where(logits < kth, NEG_INF, logits)
            order = jnp.argsort(-logits, axis=-1)
            sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            # keep tokens whose cumulative mass BEFORE them is < top_p:
            # the head token always survives, so the distribution never
            # empties
            cum_before = jnp.cumsum(probs, axis=-1) - probs
            sorted_logits = jnp.where(cum_before < top_p, sorted_logits,
                                      NEG_INF)
            idx = jax.random.categorical(rng, sorted_logits, axis=-1)
            token = jnp.take_along_axis(order, idx[..., None], axis=-1)[..., 0]
            return token.astype(jnp.int32)

    return sample
