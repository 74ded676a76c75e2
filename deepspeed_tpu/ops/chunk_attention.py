"""A chunk's attention over cached keys and values in BLOCKS of keys with
a running softmax, for grouped-query heads and an optional sliding
window: the form ``ops/mla.py::prefill_attention`` has for latent rows,
made for ``(k, v)`` pages.

The float32 scores of a 2,048-token chunk against 32,768 cached keys are
8.6 GB a layer at 32 heads if materialised at once. Here a turn of the
loop takes ``block`` keys, scores the chunk's queries against them and
folds them into a float32 accumulator (flash-attention's recurrence),
and the loop is as long as the blocks that hold a key some query can see,
a count read from the data (a ``while`` on the device, no program per
context length): all live blocks for a layer without a window, at most
``(window + chunk) / block + 1`` for a windowed layer whatever the
context, because its page table slides (inference/paging.py
``GroupPages``: column 0 is the first page with a visible key).

Positions here are the TABLE's: key ``k_pos`` is the token in row
``k_pos`` of what ``rows_of_block`` walks, and a query's ``q_pos`` counts
from the same origin (the absolute position less the table's base; the
window is a difference of positions and needs no more). The masking
contract is ``ops/pallas/paged_attention.py``'s: ``k_pos <= q_pos``, with
a window ``q_pos - k_pos < window``, and the value side zeroed past the
live window (a recycled page may hold NaN there, and ``0 * NaN`` is NaN).
"""
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30
# keys a turn of the loop scores and folds
BLOCK_TOKENS = 512


def block_tokens(positions, page_size=1):
    """Keys a loop turn takes of a table of ``positions`` tokens: whole
    pages, ``BLOCK_TOKENS`` at most."""
    return min(-(-positions // page_size),
               max(1, BLOCK_TOKENS // page_size)) * page_size


def blocked_attention(q, rows_of_block, n_blocks, block, q_pos, live,
                      kv_heads, window=None):
    """q (b, s, h, dh); ``rows_of_block(c)`` -> the keys and the values
    (b, block, kv_heads, dh) each at table positions ``[c block, (c +
    1) block)``, ``h % kv_heads == 0``; ``n_blocks`` the blocks to walk (a
    Python int, or a traced scalar: the blocks that hold a live token);
    q_pos (b, s) the queries' table positions; live (b,) the last live
    one; ``window``: the keys a query sees, its own among them, or None
    for all. -> ctx (b, s, h, dh) float32."""
    with jax.named_scope("attn.chunk_blocks"):
        b, s, h, dh = q.shape
        kvh, group = kv_heads, h // kv_heads
        offs = jnp.arange(block)
        scale = 1.0 / math.sqrt(dh)
        q = q.reshape(b, s, kvh, group, dh)

        def body(c, carry):
            acc, m, l = carry
            k, v = rows_of_block(c)
            k_pos = c * block + offs                            # (block,)
            alive = k_pos[None, :] <= live[:, None]             # (b, block)
            v = jnp.where(alive[:, :, None, None], v, 0)
            scores = jnp.einsum(
                "bqkgd,bKkd->bkgqK", q, k,
                preferred_element_type=jnp.float32) * scale
            ahead = q_pos[:, :, None] - k_pos[None, None, :]    # (b, s, K)
            mask = (ahead >= 0) & alive[:, None, :]
            if window is not None:
                mask = mask & (ahead < window)
            mask = mask[:, None, None]
            scores = jnp.where(mask, scores, NEG_INF)
            m_new = jnp.maximum(m, scores.max(-1, keepdims=True))
            # a query may see no key of a block (the window): no weight
            pexp = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            acc = acc * corr + jnp.einsum(
                "bkgqK,bKkd->bkgqd", pexp.astype(v.dtype), v,
                preferred_element_type=jnp.float32)
            return acc, m_new, l * corr + pexp.sum(-1, keepdims=True)

        init = (jnp.zeros((b, kvh, group, s, dh), jnp.float32),
                jnp.full((b, kvh, group, s, 1), NEG_INF, jnp.float32),
                jnp.zeros((b, kvh, group, s, 1), jnp.float32))
        acc, _, l = jax.lax.fori_loop(0, n_blocks, body, init)
        # a padded query past the window of every live key saw none
        ctx = acc / jnp.where(l == 0.0, 1.0, l)
        return ctx.transpose(0, 3, 1, 2, 4).reshape(b, s, h, dh)


def paged_blocked_attention(q, k_pool, v_pool, layer_idx, page_tables,
                            positions, valid_lens, page_size, window=None):
    """:func:`blocked_attention` of ``s`` new queries a slot over the
    pages of one group (pools ``(pages + 1, layers, page_size, kvh *
    dh)``), whose rows for the same tokens have landed. ``positions``
    (b,): the first query's position in the TABLE (absolute less the
    table's base). -> ctx (b, s, h, dh) float32."""
    b, s, _, dh = q.shape
    kv_heads = k_pool.shape[3] // dh
    max_pages = page_tables.shape[1]
    block = block_tokens(max_pages * page_size, page_size)
    per_block = block // page_size
    tables = jnp.pad(page_tables, ((0, 0), (0, -max_pages % per_block)))
    live = positions + valid_lens - 1

    def rows_of_block(c):
        ids = jax.lax.dynamic_slice_in_dim(tables, c * per_block, per_block,
                                           axis=1)
        return tuple(pool[ids, layer_idx].reshape(b, block, kv_heads, dh)
                     for pool in (k_pool, v_pool))

    return blocked_attention(
        q, rows_of_block, jnp.max(jnp.maximum(live, 0)) // block + 1, block,
        positions[:, None] + jnp.arange(s)[None, :], live, kv_heads,
        window)
