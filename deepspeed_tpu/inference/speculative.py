"""Speculative decoding: drafters that propose ``k`` tokens per step.

The scheduler verifies proposals with ONE fused target-model program
(engine ``verify_step``: a cached forward over ``(slots, k+1)`` tokens)
and accepts the longest prefix the target agrees with — decode emits
``1 + accepted`` tokens per model step instead of 1. Greedy acceptance
reproduces the autoregressive greedy stream byte-for-byte: position i's
target logits are conditioned on drafts ``d_1..d_i``, which equal the
committed prefix for as long as every earlier draft matched the target
argmax (tests/unit/test_serving.py pins stream equality).

Two drafters, selected by ``inference.speculative.method``:

  * :class:`NGramDrafter` — host-side prompt-lookup drafting (no second
    model): match the context's trailing n-gram against its own history
    and propose what followed. Free, surprisingly strong on the
    repetitive structure real traffic has (system prompts, code, JSON).
  * :class:`ModelDrafter` — a small config-selected GPT-2 target
    sibling with its OWN contiguous KV cache (``kv_cache.KVCache``,
    which nothing else uses), proposing ``k`` greedy
    tokens via one jitted ``lax.scan`` per scheduler step. Its cache
    advances in lockstep with the target's acceptance (rejected drafts
    become stale masked entries, exactly like the target's).
"""
import numpy as np

import jax
import jax.numpy as jnp

from ..runtime.executor.jit import first_call, jit_program


class NGramDrafter:
    """Prompt-lookup drafting (host-side, deterministic, model-free).

    ``propose(context, k)`` finds the most recent earlier occurrence of
    the context's trailing ``m``-gram (``m`` from ``ngram_max`` down to
    ``ngram_min``) and proposes the ``k`` tokens that followed it,
    padding with the final proposed token; with no match it proposes
    ``k`` copies of the last token (greedy decode of small models loves
    loops, so even this degenerate draft earns acceptances)."""

    needs_model = False

    def __init__(self, ngram_max=3, ngram_min=1):
        self.ngram_max = int(ngram_max)
        self.ngram_min = int(ngram_min)

    def propose(self, context, k):
        context = list(context)
        for m in range(min(self.ngram_max, len(context) - 1),
                       self.ngram_min - 1, -1):
            suffix = context[-m:]
            for j in range(len(context) - m - 1, -1, -1):
                if context[j:j + m] == suffix:
                    cont = context[j + m:j + m + k]
                    if cont:
                        return cont + [cont[-1]] * (k - len(cont))
        return [context[-1]] * k

    # cache-lifecycle no-ops: the drafter is stateless
    def prefill(self, slot, context):
        pass

    def advance(self, slot, n):
        pass

    def free_slot(self, slot):
        pass


class ModelDrafter:
    """A small GPT-2 drafter with its own contiguous KV cache (one
    ``(slots, layers, heads, max_seq, d_head)`` buffer pair; the target
    serves from pages).

    The drafter model must share the target's tokenizer (vocab) and
    positional reach; everything else (depth/width/heads) is free —
    the classic draft/target split. Proposals are always GREEDY: the
    acceptance rule, not the drafter, owns the sampling semantics.
    """

    needs_model = True

    def __init__(self, model, num_slots, max_seq_len, dtype, mesh=None,
                 engine=None):
        from ..runtime.model import as_model
        # the serving engine whose start-up record the drafter's
        # programs are rows of (``draft.prefill``, ``draft.propose``)
        self.engine = engine
        from .decoder import decoder_of, refuse
        from .kv_cache import KVCache
        self.module = as_model(model)
        decoder = decoder_of(model, self.module)
        refuse(decoder, decoder.cache_spec(), "draft_cache")
        cfg = getattr(self.module, "config", None) or \
            getattr(model, "config", None)
        assert cfg is not None and hasattr(cfg, "n_heads"), \
            "speculative.method 'model' needs a draft model with a " \
            "GPT2Config at .config (models.gpt2.make_gpt2_model)"
        assert cfg.max_seq_len >= max_seq_len, \
            "draft model max_seq_len {} < serving max_seq_len {}".format(
                cfg.max_seq_len, max_seq_len)
        import dataclasses
        self.config = dataclasses.replace(
            cfg, dropout=0.0, scan_blocks=False, sequence_parallel=None,
            sp_mesh=None, sparse_attention=None,
            sparse_embedding_grads=False, embedding_grad_mesh=None)
        self.max_seq_len = int(max_seq_len)

        def cast(x):
            x = jnp.asarray(x)
            return x.astype(dtype) if jnp.issubdtype(x.dtype,
                                                     jnp.floating) else x
        self.params = jax.tree_util.tree_map(cast, self.module.params)
        self.kv = KVCache.allocate(
            num_slots, self.config.n_layers, self.config.n_heads,
            self.max_seq_len, self.config.d_head, dtype, mesh=mesh)
        self.lengths = np.zeros((num_slots,), np.int32)
        self._prefill_fns = {}        # bucket -> jit fn
        self._propose_fns = {}        # k -> jit fn

    # ------------------------------------------------------------ jit fns

    def _get_prefill_fn(self, bucket):
        fn = self._prefill_fns.get(bucket)
        if fn is not None:
            return fn
        from ..models import gpt2
        cfg = self.config

        def prefill(params, k_cache, v_cache, ids, slot, start):
            k_row = jax.lax.dynamic_slice_in_dim(k_cache, slot, 1, axis=0)
            v_row = jax.lax.dynamic_slice_in_dim(v_cache, slot, 1, axis=0)
            _, (k_row, v_row) = gpt2.forward_hidden(
                params, ids, cfg, cache=(k_row, v_row),
                positions=start[None])
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                k_cache, k_row, slot, axis=0)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                v_cache, v_row, slot, axis=0)
            return k_cache, v_cache

        return self._new_program(self._prefill_fns, bucket, "draft.prefill",
                                 jit_program(prefill, donate=(1, 2)))

    def _new_program(self, cache, key, program, fn):
        cache[key] = fn
        if self.engine is not None:  # (a drafter on its own: no record)
            self.engine._first_calls.append(first_call(
                fn, program, key, self.engine.startup_tag,
                self.engine.launches))
        return fn

    def _get_propose_fn(self, k):
        fn = self._propose_fns.get(k)
        if fn is not None:
            return fn
        from ..models import gpt2
        cfg = self.config

        def propose(params, k_cache, v_cache, tokens, lengths):
            # tokens (slots,): each slot's pending token. k+1 greedy
            # decode steps in one scan: the drafter must WRITE K/V for
            # every token the verify pass can commit (pending + k
            # drafts — on full acceptance the target advances k+1, and
            # a hole at the last draft's position would poison every
            # later proposal); the k+1-th PROPOSAL is discarded.
            def body(carry, _):
                k_c, v_c, tok, lens = carry
                hidden, (k_c, v_c) = gpt2.forward_hidden(
                    params, tok[:, None], cfg, cache=(k_c, v_c),
                    positions=lens)
                logits = hidden[:, 0] @ params["wte"].astype(
                    hidden.dtype).T
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (k_c, v_c, nxt, lens + 1), nxt

            (k_cache, v_cache, _, _), drafts = jax.lax.scan(
                body, (k_cache, v_cache, tokens, lengths), None,
                length=k + 1)
            return k_cache, v_cache, drafts.T[:, :k]    # (slots, k)

        return self._new_program(self._propose_fns, k, "draft.propose",
                                 jit_program(propose, donate=(1, 2)))

    # ------------------------------------------------------------- serving

    def prefill(self, slot, context):
        """Embed the full ``context`` into the drafter's cache slot (one
        bucket-padded pass; the drafter is small, so chunking it buys
        nothing) and reset the slot's length."""
        n = len(context)
        assert 1 <= n < self.max_seq_len
        bucket = 64
        while bucket < n:
            bucket *= 2
        bucket = min(bucket, self.max_seq_len)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = np.asarray(context, np.int32)
        fn = self._get_prefill_fn(bucket)
        k, v = fn(self.params, self.kv.k, self.kv.v, jnp.asarray(ids),
                  jnp.int32(slot), jnp.int32(0))
        self.kv.update((k, v))
        self.lengths[slot] = n

    def propose_batch(self, pending, k):
        """One fused draft pass for every slot: ``pending`` (slots,)
        are each slot's most recent token. Returns (slots, k) int
        proposals; inactive slots produce garbage the scheduler
        ignores (their cache writes are position-masked like the
        target's)."""
        fn = self._get_propose_fn(int(k))
        kb, vb, drafts = fn(self.params, self.kv.k, self.kv.v,
                            jnp.asarray(np.asarray(pending, np.int32)),
                            jnp.asarray(self.lengths))
        self.kv.update((kb, vb))
        return np.asarray(drafts)

    def advance(self, slot, n):
        self.lengths[slot] += int(n)

    def free_slot(self, slot):
        self.lengths[slot] = 0
