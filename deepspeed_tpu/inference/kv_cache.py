"""Preallocated serving caches: keys and values in a slot or a paged
layout; for a model with recurrent layers, a pool of per-slot state
beside them (:class:`StatePool`); and, for a model with latent
attention, pages whose rows are the latents themselves (one pool, no
``v``). What a model keeps it declares itself (inference/decoder.py
``CacheSpec``); no model is imported here.

**Slot layout** (:class:`KVCache`, the numerics oracle and default): one
buffer pair ``(k, v)`` of shape ``(slots, layers, heads, max_seq,
d_head)`` holds every active request's attention state; a request owns one
slot for its lifetime and its batch row in prefill/decode IS its slot
index. Every admitted request pays ``max_seq`` worth of HBM regardless of
its actual length.

**Paged layout** (:class:`PagedKVCache`): a global pool of fixed-size
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

**Latent pages** (``CacheSpec.page_lanes``, paged layout only): the same
pool, allocator, page tables and prefix sharing, but a token's row is
what the decoder says: for latent attention (ops/mla.py) the ``kv_lora``
latent and the rotated shared rope key, 576 values that all heads share,
zero-padded to 640 lanes (5 x 128: a minor dimension that is not a
multiple of the lanes stops the program on the chip), in ONE pool
``k``; ``v`` is None. The decoder writes the pad lanes as zeros with
every row, because a recycled page may hold anything there.

Reuse, per cache kind. Keys and values: freed slots and recycled pages
are reused WITHOUT clearing — the absolute-position causal mask in the
model's cached attention (models/gpt2.py ``_attend_cache_rows``,
models/jamba.py ``_attend``: ``k_pos <= q_pos``) makes stale entries
unreachable in both layouts, for any garbage content including NaN
(pinned by tests/unit/test_serving.py poison tests). Recurrent state:
NO mask hides what a slot held, so it is not reused as it is: the
prefill program that runs a request's first chunk starts from zeros
whatever the slot holds (no clearing launch of its own), and a decode
step advances only the slots that are decoding (pinned by
tests/unit/test_jamba.py's NaN-poisoned state pool).

Sharding: the heads carry the tensor-parallel partition in both layouts
(the slot cache's ``heads`` axis, the paged pool's packed ``heads *
d_head`` axis — contiguous per head, so an even split lands on head
boundaries), matching ``models/gpt2.py::partition_spec_fn``'s Megatron
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

# (slots, layers, heads, max_seq, d_head): heads sharded over the model
# axis.
KV_CACHE_SPEC = P(None, None, MODEL_AXIS, None, None)
# the paged pool (pages, layers, page_size, heads * d_head): the packed
# heads axis is the minor one.
PAGED_KV_CACHE_SPEC = P(None, None, None, MODEL_AXIS)


@dataclass
class KVCache:
    """The ``(k, v)`` buffer pair. Buffers are jax arrays updated
    functionally: the engine's jitted prefill/decode donate them, so each
    step writes in place at steady state."""

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
    programs alias both pools input to output, and their scatters
    update the operand). Reads index it by (page, layer) together
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
