"""Preallocated serving caches: keys and values in pages; for a model
with recurrent layers, a pool of per-slot state beside them
(:class:`StatePool`); and, for a model with latent attention, pages
whose rows are the latents themselves (one pool, no ``v``). What a
model keeps it declares itself (inference/decoder.py ``CacheSpec``); no
model is imported here.

**Pages** (:class:`PagedKVCache`, what every engine serves from): a
global pool of fixed-size
pages ``(pages, layers, page_size, heads * d_head)`` plus host-side
per-sequence page tables (inference/paging.py). The heads ride PACKED in
the minor dimension: a d_head-64 minor dimension is padded to the chip's
128 lanes in HBM (double the bytes) and the chip's compiler refuses a
page DMA out of it, while ``heads * d_head`` is lane-aligned at every
GPT-2 width — one page of one layer is one contiguous, tile-aligned
``(page_size, heads * d_head)`` slab (ops/pallas/paged_attention.py).
Sequences allocate pages on demand as they grow, so HBM scales with LIVE
tokens, not with
``slots * max_seq`` — and shared prompt prefixes map one set of pages
into many tables (prefix sharing). Physical page 0 is the reserved
garbage page: never allocated, the target of every masked/padded write.

**Latent pages** (``CacheSpec.page_lanes``): the same
pool, allocator, page tables and prefix sharing, but a token's row is
what the decoder says: for latent attention (ops/mla.py) the ``kv_lora``
latent and the rotated shared rope key, 576 values that all heads share,
zero-padded to 640 lanes (5 x 128: a minor dimension that is not a
multiple of the lanes stops the program on the chip), in ONE pool
``k``; ``v`` is None. The decoder writes the pad lanes as zeros with
every row, because a recycled page may hold anything there.

**The model drafter's contiguous cache** (:class:`KVCache`): one buffer
pair ``(k, v)`` of shape ``(slots, layers, heads, max_seq, d_head)``; a
request's batch row IS its slot index and every slot pays ``max_seq``
worth of HBM. Its one user is the small draft model of
``inference/speculative.py::ModelDrafter`` (a test pins that): no
engine serves from it since PR 48.

Reuse, per cache kind. Keys and values: recycled pages (and the
drafter's freed slots) are reused WITHOUT clearing — the
absolute-position causal mask in the
model's cached attention (models/gpt2.py ``_attend_cache_rows``,
models/jamba.py ``_attend``: ``k_pos <= q_pos``) makes stale entries
unreachable, for any garbage content including NaN
(pinned by tests/unit/test_serving.py poison tests). Recurrent state:
NO mask hides what a slot held, so it is not reused as it is: the
prefill program that runs a request's first chunk starts from zeros
whatever the slot holds (no clearing launch of its own), and a decode
step advances only the slots that are decoding (pinned by
tests/unit/test_jamba.py's NaN-poisoned state pool).

Sharding: the heads carry the tensor-parallel partition (the page
pool's packed ``heads * d_head`` axis — contiguous per head, so an even
split lands on head boundaries; the drafter's cache its ``heads``
axis), matching ``models/gpt2.py::partition_spec_fn``'s Megatron
layout on the ``model`` mesh axis (QKV column-parallel => each model
shard produces its own heads' K/V, so the cache entries it writes are
exactly the entries it owns and decode inserts no cross-shard cache
traffic; page gathers index only replicated axes).
"""
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.topology import MODEL_AXIS

# the model drafter's contiguous cache (slots, layers, heads, max_seq,
# d_head): heads sharded over the model axis.
KV_CACHE_SPEC = P(None, None, MODEL_AXIS, None, None)
# the paged pool (pages, layers, page_size, heads * d_head): the packed
# heads axis is the minor one.
PAGED_KV_CACHE_SPEC = P(None, None, None, MODEL_AXIS)


@dataclass
class KVCache:
    """The model drafter's contiguous ``(k, v)`` buffer pair
    (inference/speculative.py, its one user). Buffers are jax arrays
    updated functionally: the drafter's jitted programs donate them, so
    each step writes in place at steady state."""

    k: object
    v: object

    @classmethod
    def allocate(cls, slots, layers, heads, max_seq, d_head, dtype,
                 mesh=None):
        shape = (slots, layers, heads, max_seq, d_head)
        k, v = _shard_heads(jnp.zeros(shape, dtype),
                            jnp.zeros(shape, dtype), heads, mesh,
                            KV_CACHE_SPEC)
        return cls(k, v)

    @property
    def num_slots(self):
        return self.k.shape[0]

    @property
    def num_layers(self):
        return self.k.shape[1]

    @property
    def max_seq_len(self):
        return self.k.shape[3]

    @property
    def nbytes(self):
        return self.k.size * self.k.dtype.itemsize * 2

    def buffers(self):
        return self.k, self.v

    def update(self, buffers):
        self.k, self.v = buffers


def _shard_heads(k, v, heads, mesh, spec):
    if mesh is not None and MODEL_AXIS in mesh.shape:
        assert heads % mesh.shape[MODEL_AXIS] == 0, \
            "n_heads {} not divisible by model-parallel degree {}".format(
                heads, mesh.shape[MODEL_AXIS])
        sharding = NamedSharding(mesh, spec)
        k = jax.device_put(k, sharding)
        v = jax.device_put(v, sharding)
    return k, v


@dataclass
class StatePool:
    """Per-slot recurrent state: one array per ``StateSpec`` of the
    model's ``CacheSpec``, each ``lead + (slots,) + tail`` — layer-major,
    so a program reads and writes one layer's region (a static index on
    the leading dimension) and never copies a slab to reach a slot.
    Like the page pool the arrays are donated to, and aliased input to
    output by, every serving program. Replicated on a mesh (a model
    that keeps one refuses a ``model`` axis)."""

    arrays: tuple
    num_slots: int

    @classmethod
    def allocate(cls, specs, slots):
        return cls(tuple(
            jnp.zeros(tuple(s.lead) + (slots,) + tuple(s.tail), s.dtype)
            for s in specs), int(slots))

    @property
    def nbytes(self):
        return sum(a.size * a.dtype.itemsize for a in self.arrays)

    def buffers(self):
        return self.arrays

    def update(self, buffers):
        self.arrays = tuple(buffers)


@dataclass
class PagedKVCache:
    """The paged ``(k, v)`` pool: ``(num_pages + 1, layers, page_size,
    heads * d_head)`` — physical page 0 is the reserved garbage page
    (inference/paging.py), so ``num_pages`` counts USABLE pages. Buffers
    are jax arrays updated functionally; the engine's jitted programs
    donate them, so steady-state serving writes in place (the compiled
    programs alias both pools input to output; :func:`write_tokens` is
    the one write and holds its contract). Reads index it by (page,
    layer) together
    (models/gpt2.py ``_gather_pages``, the paged kernel's DMAs): a
    program that slices a layer out first, ``pool[:, layer]``, copies
    that layer's whole slab — the cost then grows with ``num_pages``,
    not with the tokens read."""

    k: object
    v: object                  # None where the decoder lays out one pool
    page_size: int

    @classmethod
    def allocate(cls, num_pages, layers, heads, page_size, d_head, dtype,
                 mesh=None, lanes=None):
        """``lanes`` (``CacheSpec.page_lanes``): None for the pair of
        ``heads * d_head``; else the lanes of a row of the ONE pool a
        decoder lays out itself (replicated on a mesh)."""
        if lanes is None:
            shape = (num_pages + 1, layers, page_size, heads * d_head)
            k, v = _shard_heads(jnp.zeros(shape, dtype),
                                jnp.zeros(shape, dtype), heads, mesh,
                                PAGED_KV_CACHE_SPEC)
            return cls(k, v, int(page_size))
        assert lanes % 128 == 0, \
            "a page row of {} lanes is not a multiple of 128".format(lanes)
        return cls(jnp.zeros((num_pages + 1, layers, page_size, lanes),
                             dtype), None, int(page_size))

    @property
    def num_pages(self):
        return self.k.shape[0] - 1          # minus the garbage page

    @property
    def num_layers(self):
        return self.k.shape[1]

    @property
    def nbytes(self):
        return sum(a.size * a.dtype.itemsize for a in self.buffers())

    @property
    def token_bytes(self):
        """Bytes one cached token costs, over all layers and pools, pad
        lanes included."""
        return sum(a.shape[1] * a.shape[3] * a.dtype.itemsize
                   for a in self.buffers())

    def buffers(self):
        return (self.k,) if self.v is None else (self.k, self.v)

    def update(self, buffers):
        self.k, self.v = (tuple(buffers) + (None,))[:2]


def write_path(s, page_size):
    """The granularity :func:`write_tokens` moves ``s`` new tokens a slot
    at: ``"rows"`` below a page (a decode step, a speculative verify),
    ``"pages"`` from a page up (a prefill chunk). Fixed by the shape, so
    by the program (the ``kv_write`` attribute, docs/telemetry.md)."""
    return "pages" if s >= page_size else "rows"


def read_scope(s, page_size):
    """The device scope (docs/telemetry.md, "Device scopes") of a paged
    model's read of its keys beside that write: ``attn.prefill`` for a
    prompt chunk, ``attn.decode`` for a decode or verify step, told
    apart as :func:`write_path` tells them."""
    return "attn.prefill" if write_path(s, page_size) == "pages" \
        else "attn.decode"


def write_tokens(pools, news, layer_idx, page_tables, positions,
                 valid_lens, page_size, mesh=None):
    """THE write of new cache rows into the paged pools: every paged
    model calls it (models/gpt2.py, jamba.py, lfm2.py, ops/mla.py).
    ``pools``: the key and the value pool, or the one latent pool, each
    ``(pages + 1, layers, page_size, lanes)``; ``news``: for each, the
    ``(b, s, lanes)`` rows of the ``s`` tokens that slot b holds at
    ``positions[b] + [0, s)``; ``page_tables`` (b, max_pages). Returns
    the pools, updated in place under donation.

    The contract, whatever the path: token i of slot b lands at
    ``(page_tables[b, pos // page_size], layer_idx, pos % page_size)``
    if ``i < valid_lens[b]`` and ``pos`` lies inside the slot's logical
    window (``max_pages * page_size``); no other row of any page but the
    garbage page 0 changes a bit. So rows at or past ``valid_len`` in a
    slot's last page keep what they held, a padded bucket never touches
    another sequence's pages, and a chunk may start in the middle of a
    page and may run past the window. What page 0 holds afterwards is
    unspecified (no read reaches it unmasked).

    One masked write at two granularities, chosen by the shape
    (:func:`write_path`). XLA lowers a scatter on the chip to one update
    after another, about 130 ns each whatever it holds (a 2 KB row at
    4-5% of what its bytes cost: ledger PR 38, 1.43 s of docs' 4.94 s
    window), so a chunk of a page or more moves whole pages, one DMA
    each (ops/pallas/page_write.py): the ``(s - 2) // page_size + 2``
    page frames it can touch, 65 of 32 KB a layer and pool for a bucket
    of 1,024 where there were 1,024 updates of 2 KB; only a chunk's
    first and last page can hold rows to keep, and those two are read,
    merged and written back. Below a page (``s == 1``, ``s == k + 1``)
    there is no page to move: a row a token, the scatter as it was.
    ``mesh``: the mesh the program spans, for the kernel's shard_map
    (the scatter follows GSPMD)."""
    with jax.named_scope("kv.write"):
        if write_path(news[0].shape[1], page_size) == "pages":
            return _write_pages(pools, news, layer_idx, page_tables,
                                positions, valid_lens, page_size, mesh)
        return _write_rows(pools, news, layer_idx, page_tables, positions,
                           valid_lens, page_size)


def _write_rows(pools, news, layer_idx, page_tables, positions,
                valid_lens, page_size):
    """One scatter update a token: padded tokens and positions past the
    window redirect to the garbage page."""
    b, s = news[0].shape[:2]
    max_pages = page_tables.shape[1]
    tok_pos = positions[:, None] + jnp.arange(s)[None, :]         # (b, s)
    valid = (jnp.arange(s)[None, :] < valid_lens[:, None]) & \
        (tok_pos < max_pages * page_size)
    logical = jnp.clip(tok_pos // page_size, 0, max_pages - 1)
    page = jnp.where(valid, jnp.take_along_axis(page_tables, logical,
                                                axis=1), 0)
    # the advanced (page, offset) indices broadcast to the front
    flat_page, flat_off = page.reshape(-1), (tok_pos % page_size).reshape(-1)
    return tuple(
        pool.at[flat_page, layer_idx, flat_off, :].set(
            new.reshape(b * s, -1).astype(pool.dtype))
        for pool, new in zip(pools, news))


def _write_pages(pools, news, layer_idx, page_tables, positions,
                 valid_lens, page_size, mesh):
    """One DMA a page (ops/pallas/page_write.py): the chunk's rows cut
    into the page frames ``[positions // page_size, + frames)`` of each
    slot, and for each frame its page and the rows of it that hold
    valid tokens. A frame with none is not written."""
    from ..ops.pallas.page_write import kv_page_write
    b, s = news[0].shape[:2]
    max_pages = page_tables.shape[1]
    frames = (s - 2) // page_size + 2
    first, shift = positions // page_size, positions % page_size
    # tokens [0, limit) are valid: frame rows [shift, shift + limit)
    limit = jnp.clip(jnp.minimum(
        valid_lens, max_pages * page_size - positions), 0, s)
    row0 = jnp.arange(frames)[None, :] * page_size
    lo = jnp.clip(shift[:, None] - row0, 0, page_size)
    hi = jnp.clip((shift + limit)[:, None] - row0, 0, page_size)
    logical = jnp.clip(first[:, None] + jnp.arange(frames)[None, :],
                       0, max_pages - 1)
    page = jnp.where(hi > lo,
                     jnp.take_along_axis(page_tables, logical, axis=1), 0)
    meta = jnp.stack([page, lo, hi]).reshape(3, b * frames)

    def framed(rows, shift):
        # (s, lanes) -> (frames * page_size, lanes), row i at i + shift
        rows = jnp.pad(rows, ((page_size, frames * page_size - s), (0, 0)))
        return jax.lax.dynamic_slice_in_dim(rows, page_size - shift,
                                            frames * page_size, axis=0)

    return kv_page_write(
        pools, [jax.vmap(framed)(new.astype(pool.dtype), shift).reshape(
            b * frames, page_size, -1) for pool, new in zip(pools, news)],
        meta, layer_idx, mesh=mesh)
