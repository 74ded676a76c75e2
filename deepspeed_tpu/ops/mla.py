"""Multi-head latent attention (MLA, the ``deepseek_v3`` architecture's)
for any model file: the projections, and the TWO forms of the same
attention that serving needs.

With ``u`` the normed input of a layer: ``q = W_q u`` (``heads`` heads of
``nope + rope``, each split ``q_nope | q_pe``); ``W_kva u`` split ``c``
(``rank``) ``| k_pe`` (``rope``, ONE for all heads); ``c~ = RMSNorm(c)``;
rotary over the ``rope`` lanes of every head's ``q_pe`` and of ``k_pe``.
What a token keeps (:func:`project`'s ``row``) is ``[c~ | rotated k_pe |
0]``: ``rank + rope`` values that all heads share, zero-padded to whole
lanes (576 -> 640 at the published sizes), in the model's dtype. The pad
lanes are written as zeros with every row: a recycled page may hold NaN
there, and both forms below multiply them by a query's zero lanes.

* **Up-projected** (:func:`prefill_attention`; a prompt chunk, or whole
  sequences without a cache): keys and values are made from the rows,
  ``W_kvb c~`` split per head ``k_nope | v`` (``mla.kv_up``), ``k_h =
  [k_nope_h | k_pe]``, and the queries attend to them at width ``nope +
  rope`` with a running softmax over BLOCKS of keys: the float32 scores
  of a 2,048-token chunk against an 8,192-token window are 1.07 GB a
  layer if materialised. The loop runs over the blocks that hold a live
  token, a count read from the data (a ``while`` on the device, no
  program per window length): its cost grows with the tokens a request
  has, not with ``max_seq_len``.
* **Absorbed** (:func:`absorbed_attention`; a decode step): with
  ``W_kvb`` of head ``h`` split ``W_uk_h | W_uv_h``, ``q_lat_h = W_uk_h
  q_nope_h`` (``mla.absorb``), scores ``(q_lat_h . c~ + q_pe_h . k_pe)``,
  ``ctx_lat_h = sum p c~``, ``ctx_h = W_uv_h^T ctx_lat_h``: the same
  function, in which the up-projection has moved into the query and the
  output, so that the attention itself runs over the stored rows: every
  head's query ``[q_lat | q_pe | 0]`` against ONE shared "key-value head"
  whose values are the first ``rank`` lanes of its keys. On the chip that
  is ``ops/pallas/paged_attention.py::mla_decode`` over the pages
  themselves; :func:`absorbed_attention_rows` is its XLA oracle over
  gathered rows.

Which form a call takes is read from its shape: one query token a
sequence (a decode step) attends absorbed, a chunk up-projected (the
absorbed form costs ``2 (rank + rope + rank)`` operations a query, head
and key, the up-projected one ``2 (nope + rope + v)`` and the
up-projection of every key once; they meet near 170 queries a sequence
at the published sizes, and a prefill bucket is 512 or more).

Both scale by ``1 / sqrt(nope + rope)``, mask by absolute position
(``k_pos <= q_pos``) and zero the value side past the live window, the
masking contract of ``ops/pallas/paged_attention.py``.
"""
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..inference.kv_cache import write_tokens

LANES = 128
NEG_INF = -1e30
# keys a turn of the up-projected form's loop makes and folds
BLOCK_TOKENS = 512


@dataclass(frozen=True)
class MLADims:
    heads: int
    nope: int                  # qk_nope_head_dim
    rope: int                  # qk_rope_head_dim
    v: int                     # v_head_dim
    rank: int                  # kv_lora_rank
    rope_theta: float
    kv_norm_eps: float = 1e-6

    @property
    def lanes(self):
        """A cached row: ``rank + rope`` values padded to whole lanes."""
        return -(-(self.rank + self.rope) // LANES) * LANES

    @property
    def scale(self):
        return 1.0 / math.sqrt(self.nope + self.rope)


def rms_norm(x, weight, eps):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def rotary(x, positions, theta):
    """Rotary embedding over the last axis, rotate-half pairing ``(i, i
    + rope / 2)``. x (b, s, ..., rope); positions (b, s) absolute."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def project(u, w_q, w_kva, kv_norm, dims, tok_pos):
    """u (b, s, d) normed; tok_pos (b, s) absolute. -> (q_nope (b, s, h,
    nope), q_pe (b, s, h, rope) rotated, row (b, s, lanes): what the
    token keeps)."""
    with jax.named_scope("mla.project"):
        b, s, _ = u.shape
        q = (u @ w_q).reshape(b, s, dims.heads, dims.nope + dims.rope)
        q_nope, q_pe = q[..., :dims.nope], q[..., dims.nope:]
        kva = u @ w_kva
        c = rms_norm(kva[..., :dims.rank], kv_norm, dims.kv_norm_eps)
        k_pe = rotary(kva[..., dims.rank:], tok_pos, dims.rope_theta)
        pad = jnp.zeros((b, s, dims.lanes - dims.rank - dims.rope), u.dtype)
        row = jnp.concatenate([c, k_pe, pad], axis=-1)
        return q_nope, rotary(q_pe, tok_pos, dims.rope_theta), row


def kv_up(rows, w_kvb, dims):
    """Keys and values of cached rows (b, K, lanes): -> (k_nope (b, K,
    h, nope), k_pe (b, K, rope), v (b, K, h, v))."""
    with jax.named_scope("mla.kv_up"):
        b, K, _ = rows.shape
        kv = (rows[..., :dims.rank] @ w_kvb).reshape(
            b, K, dims.heads, dims.nope + dims.v)
        return (kv[..., :dims.nope],
                rows[..., dims.rank:dims.rank + dims.rope],
                kv[..., dims.nope:])


def prefill_attention(q_nope, q_pe, rows_of_block, n_blocks, block, w_kvb,
                      dims, q_pos, live):
    """The up-projected form with a running softmax over blocks of
    ``block`` keys. ``rows_of_block(c)`` -> the cached rows (b, block,
    lanes) at absolute positions ``[c block, (c + 1) block)``;
    ``n_blocks`` the blocks to walk (a Python int, or a traced scalar:
    the blocks that hold a live token); q_pos (b, s) the queries'
    absolute positions; live (b,) the last live position. -> ctx (b, s,
    h, v) float32."""
    with jax.named_scope("mla.prefill_attn"):
        b, s, h, _ = q_nope.shape
        offs = jnp.arange(block)

        def body(c, carry):
            acc, m, l = carry
            k_pos = c * block + offs                            # (block,)
            alive = k_pos[None, :] <= live[:, None]             # (b, block)
            # a dead row may hold anything (a recycled page): zero it
            # before it is up-projected into a value
            rows = jnp.where(alive[..., None], rows_of_block(c), 0)
            k_nope, k_pe, v = kv_up(rows, w_kvb, dims)
            scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                                 preferred_element_type=jnp.float32) +
                      jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe,
                                 preferred_element_type=jnp.float32)
                      ) * dims.scale
            mask = (k_pos[None, None, :] <= q_pos[:, :, None]) & \
                alive[:, None, :]                               # (b, s, K)
            scores = jnp.where(mask[:, None], scores, NEG_INF)
            m_new = jnp.maximum(m, scores.max(-1, keepdims=True))
            pexp = jnp.exp(scores - m_new)
            corr = jnp.exp(m - m_new)
            acc = acc * corr + jnp.einsum(
                "bhqk,bkhd->bhqd", pexp.astype(v.dtype), v,
                preferred_element_type=jnp.float32)
            return acc, m_new, l * corr + pexp.sum(-1, keepdims=True)

        init = (jnp.zeros((b, h, s, dims.v), jnp.float32),
                jnp.full((b, h, s, 1), NEG_INF, jnp.float32),
                jnp.zeros((b, h, s, 1), jnp.float32))
        acc, _, l = jax.lax.fori_loop(0, n_blocks, body, init)
        # position 0 is live for every query, so l counts a key at least
        return (acc / l).transpose(0, 2, 1, 3)


def absorb(q_nope, q_pe, w_kvb, dims):
    """The absorbed queries ``[q_lat | q_pe | 0]`` (b, s, h, lanes)."""
    with jax.named_scope("mla.absorb"):
        b, s, h, _ = q_nope.shape
        w_uk = w_kvb.reshape(dims.rank, h, dims.nope + dims.v)[
            ..., :dims.nope]
        q_lat = jnp.einsum("bshn,chn->bshc", q_nope, w_uk)
        pad = jnp.zeros((b, s, h, dims.lanes - dims.rank - dims.rope),
                        q_nope.dtype)
        return jnp.concatenate([q_lat.astype(q_nope.dtype), q_pe, pad],
                               axis=-1)


def unabsorb(ctx_lat, w_kvb, dims):
    """ctx_lat (b, s, h, rank) -> ctx (b, s, h, v): ``W_uv_h^T``."""
    with jax.named_scope("mla.absorb"):
        w_uv = w_kvb.reshape(dims.rank, dims.heads, dims.nope + dims.v)[
            ..., dims.nope:]
        return jnp.einsum("bshc,chv->bshv", ctx_lat.astype(w_kvb.dtype),
                          w_uv, preferred_element_type=jnp.float32)


def absorbed_attention_rows(q_abs, rows, positions, valid_lens, dims):
    """The absorbed form over gathered rows, the kernel's XLA oracle.
    q_abs (b, s, h, lanes); rows (b, K, lanes) at absolute positions
    ``0 .. K - 1``; positions, valid_lens (b,). -> ctx_lat (b, s, h,
    rank) float32."""
    b, s, h, _ = q_abs.shape
    K = rows.shape[1]
    k_pos = jnp.arange(K)
    q_pos = positions[:, None] + jnp.arange(s)[None, :]
    live = (positions + valid_lens - 1)[:, None]                # (b, 1)
    alive = k_pos[None, :] <= live                              # (b, K)
    rows = jnp.where(alive[..., None], rows, 0)
    scores = jnp.einsum("bshl,bkl->bhsk", q_abs, rows,
                        preferred_element_type=jnp.float32) * dims.scale
    mask = (k_pos[None, None, :] <= q_pos[:, :, None]) & alive[:, None, :]
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhsk,bkc->bshc", probs.astype(rows.dtype),
                      rows[..., :dims.rank],
                      preferred_element_type=jnp.float32)


def absorbed_attention(q_abs, pool, page_tables, positions, valid_lens,
                       dims, layer_idx, page_size, kernel):
    """A decode step's attention over the latent pages: the Pallas page
    walk (``kernel == "pallas"``) or its oracle over the rows gathered by
    (page, layer). -> ctx_lat (b, s, h, rank) float32."""
    if kernel == "pallas":
        from .pallas.paged_attention import mla_decode
        return mla_decode(q_abs, pool, page_tables, positions, valid_lens,
                          layer_idx=layer_idx, page_size=page_size,
                          rank=dims.rank, sm_scale=dims.scale)
    b, max_pages = page_tables.shape
    rows = pool[page_tables, layer_idx].reshape(
        b, max_pages * page_size, pool.shape[-1])
    return absorbed_attention_rows(q_abs, rows, positions, valid_lens, dims)


def _block(window, page_size=1):
    """Keys a loop turn takes of a window of ``window`` tokens: whole
    pages, ``BLOCK_TOKENS`` at most."""
    return min(-(-window // page_size), max(1, BLOCK_TOKENS // page_size)) \
        * page_size


def attention_dense(u, lp, dims):
    """A layer's attention over whole sequences u (b, S, d) from
    position 0, no cache: the up-projected form. -> (b, S, h v)."""
    b, S, _ = u.shape
    tok_pos = jnp.broadcast_to(jnp.arange(S)[None, :], (b, S))
    q_nope, q_pe, rows = project(u, lp["q"], lp["kv_a"], lp["kv_norm"],
                                 dims, tok_pos)
    block = _block(S)
    n_blocks = -(-S // block)
    rows = jnp.pad(rows, ((0, 0), (0, n_blocks * block - S), (0, 0)))
    ctx = prefill_attention(
        q_nope, q_pe,
        lambda c: jax.lax.dynamic_slice_in_dim(rows, c * block, block, 1),
        n_blocks, block, lp["kv_b"], dims, tok_pos,
        jnp.full((b,), S - 1, jnp.int32))
    return ctx.astype(u.dtype).reshape(b, S, -1)


def attention_paged(u, lp, dims, pool, layer_idx, positions, page_tables,
                    valid_lens, page_size, kernel="xla"):
    """A layer's attention against the latent pages: the chunk's rows
    are written first (``kv_cache.write_tokens``, the write of every
    paged model),
    then the chunk attends to the pages, its own rows among them, in
    the form its shape says (``s == 1``: absorbed). u (b, s, d);
    pool (pages + 1, layers, page_size, lanes). -> ((b, s, h v), pool)."""
    b, s, _ = u.shape
    max_pages = page_tables.shape[1]
    tok_pos = positions[:, None] + jnp.arange(s)[None, :]
    q_nope, q_pe, rows = project(u, lp["q"], lp["kv_a"], lp["kv_norm"],
                                 dims, tok_pos)
    pool, = write_tokens((pool,), (rows,), layer_idx, page_tables,
                         positions, valid_lens, page_size)

    if s == 1:
        ctx_lat = absorbed_attention(
            absorb(q_nope, q_pe, lp["kv_b"], dims), pool, page_tables,
            positions, valid_lens, dims, layer_idx, page_size, kernel)
        ctx = unabsorb(ctx_lat, lp["kv_b"], dims)
    else:
        block = _block(max_pages * page_size, page_size)
        per_block = block // page_size
        tables = jnp.pad(page_tables,
                         ((0, 0), (0, -max_pages % per_block)))
        live = positions + valid_lens - 1

        def rows_of_block(c):
            ids = jax.lax.dynamic_slice_in_dim(tables, c * per_block,
                                               per_block, axis=1)
            return pool[ids, layer_idx].reshape(b, block, pool.shape[-1])

        ctx = prefill_attention(
            q_nope, q_pe, rows_of_block,
            jnp.max(jnp.maximum(live, 0)) // block + 1, block, lp["kv_b"],
            dims, tok_pos, live)
    return ctx.astype(u.dtype).reshape(b, s, -1), pool
