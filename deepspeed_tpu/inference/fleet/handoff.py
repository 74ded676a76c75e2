"""KV page-slice handoff: the wire format between prefill and decode.

A *page slice* is one finished request's KV state, lifted out of the
prefill engine's paged pool: the page payloads (``(n_pages, layers,
page_size, heads * d_head)`` K and V stacks, gathered by physical page
id) plus the table metadata a decode engine needs to resume — resident
token count, the pending first sampled token, and the context tokens
(for prefix registration and preemption-recompute on the decode side).

Two codecs, one container:

  * **fp path** (default): the raw array bytes move verbatim — the
    import is BITWISE identical to the export, so a greedy stream
    through prefill → handoff → decode reproduces the single-engine
    paged stream byte-for-byte (the oracle the quantized path is
    judged against);
  * **int8 path** (opt-in, ``inference.fleet.handoff_quantize``): K/V
    ride the PR 3 blockwise codec (runtime/comm/quantize.py) — ~4x
    less wire below fp32. Tolerance contract (documented in
    docs/inference.md): each reconstructed lane differs from the
    original by at most ``0.5 * blockwise_absmax / 127`` plus rounding
    (the symmetric-int8 quantization step), so downstream decode
    drifts within ordinary quantization noise.

Container: ``b"DSKV"`` magic, u16 version, u32 header length, a JSON
header (segment table, shapes, dtypes, CRC32 + byte count of the
payload), then the concatenated payload bytes. Torn or truncated
payloads are rejected LOUDLY (:class:`HandoffError`): a short read
fails the length check, a corrupted one fails the CRC — never a
silently wrong cache.
"""
import json
import struct
import zlib

import numpy as np

MAGIC = b"DSKV"
VERSION = 1

_HEAD = struct.Struct(">4sHI")   # magic, version, header byte length

DEFAULT_HANDOFF_BLOCK = 256


class HandoffError(Exception):
    """A page-slice payload that cannot be trusted: bad magic, version
    skew, truncation, or checksum mismatch. Always raised loudly —
    importing a torn slice would poison the decode cache silently."""


class PageSlice:
    """One request's exported KV state (host-side numpy)."""

    __slots__ = ("k_pages", "v_pages", "page_size", "length",
                 "pending_token", "context", "trace_id")

    def __init__(self, k_pages, v_pages, page_size, length,
                 pending_token, context, trace_id=None):
        self.k_pages = k_pages        # (n_pages, layers, heads, ps, dh)
        self.v_pages = v_pages
        self.page_size = int(page_size)
        self.length = int(length)     # tokens resident in the pages
        self.pending_token = int(pending_token)
        self.context = [int(t) for t in context]
        # the request's span trace_id, carried across the handoff so
        # prefill + decode read as ONE trace (None when spans are off)
        self.trace_id = None if trace_id is None else str(trace_id)

    @property
    def n_pages(self):
        return self.k_pages.shape[0]

    @property
    def nbytes(self):
        return self.k_pages.nbytes + self.v_pages.nbytes


def export_slice(engine, slot, context, pending_token, trace_id=None):
    """Lift ``slot``'s live pages out of a paged engine's pool into a
    host :class:`PageSlice`. The slot keeps its pages (the caller
    frees it after a successful handoff — export never mutates)."""
    n_pages = int(engine.page_counts[slot])
    length = int(engine.lengths[slot])
    assert n_pages >= 1 and length >= 1, \
        "slot {} holds no live pages to export".format(slot)
    page_ids = np.asarray(engine.page_tables[slot, :n_pages], np.int32)
    k = np.asarray(engine.kv.k[page_ids])
    v = np.asarray(engine.kv.v[page_ids])
    return PageSlice(k, v, engine.page_size, length, pending_token,
                     context, trace_id=trace_id)


def serialize_slice(sl, quantize=False, block_size=DEFAULT_HANDOFF_BLOCK):
    """:class:`PageSlice` -> container bytes (fp verbatim, or the
    blockwise-int8 codec when ``quantize``)."""
    segments = []     # (name, dtype str, shape list, bytes)
    if quantize:
        from ...runtime.comm.quantize import quantize_blockwise
        import jax.numpy as jnp
        for name, arr in (("k", sl.k_pages), ("v", sl.v_pages)):
            q, scales = quantize_blockwise(jnp.asarray(arr), block_size)
            q, scales = np.asarray(q), np.asarray(scales)
            segments.append((name + "_q", q))
            segments.append((name + "_scales", scales))
    else:
        segments.append(("k", sl.k_pages))
        segments.append(("v", sl.v_pages))
    payload = b"".join(np.ascontiguousarray(a).tobytes()
                       for _, a in segments)
    header = {
        "page_size": sl.page_size,
        "length": sl.length,
        "pending_token": sl.pending_token,
        "context": sl.context,
        "trace_id": sl.trace_id,
        "shape": list(sl.k_pages.shape),
        "dtype": np.dtype(sl.k_pages.dtype).name,
        "quantized": bool(quantize),
        "block_size": int(block_size),
        "segments": [{"name": name, "dtype": np.dtype(a.dtype).name,
                      "shape": list(a.shape), "nbytes": int(a.nbytes)}
                     for name, a in segments],
        "payload_nbytes": len(payload),
        "payload_crc32": zlib.crc32(payload) & 0xFFFFFFFF,
    }
    header_bytes = json.dumps(header, sort_keys=True).encode("utf-8")
    return _HEAD.pack(MAGIC, VERSION, len(header_bytes)) + \
        header_bytes + payload


def deserialize_slice(data):
    """Container bytes -> :class:`PageSlice`, with LOUD rejection of
    anything torn: magic/version skew, truncated header or payload,
    CRC mismatch all raise :class:`HandoffError`."""
    if len(data) < _HEAD.size:
        raise HandoffError(
            "payload of {} bytes is shorter than the {}-byte container "
            "head".format(len(data), _HEAD.size))
    magic, version, header_len = _HEAD.unpack_from(data)
    if magic != MAGIC:
        raise HandoffError(
            "bad magic {!r} (want {!r}) — not a KV page slice".format(
                magic, MAGIC))
    if version != VERSION:
        raise HandoffError(
            "page-slice version {} unsupported (this codec speaks "
            "{})".format(version, VERSION))
    body = data[_HEAD.size:]
    if len(body) < header_len:
        raise HandoffError(
            "truncated header: {} of {} bytes present".format(
                len(body), header_len))
    try:
        header = json.loads(body[:header_len].decode("utf-8"))
    except ValueError as err:
        raise HandoffError("corrupt header JSON: {}".format(err))
    payload = body[header_len:]
    if len(payload) != header["payload_nbytes"]:
        raise HandoffError(
            "truncated payload: {} of {} bytes present".format(
                len(payload), header["payload_nbytes"]))
    crc = zlib.crc32(payload) & 0xFFFFFFFF
    if crc != header["payload_crc32"]:
        raise HandoffError(
            "payload checksum mismatch (crc32 {:#010x}, header says "
            "{:#010x}) — torn or corrupted handoff".format(
                crc, header["payload_crc32"]))
    arrays, off = {}, 0
    for seg in header["segments"]:
        n = seg["nbytes"]
        arrays[seg["name"]] = np.frombuffer(
            payload[off:off + n],
            dtype=np.dtype(seg["dtype"])).reshape(seg["shape"])
        off += n
    shape = tuple(header["shape"])
    dtype = np.dtype(header["dtype"])
    if header["quantized"]:
        from ...runtime.comm.quantize import dequantize_blockwise
        import jax.numpy as jnp
        size = int(np.prod(shape))
        k = np.asarray(dequantize_blockwise(
            jnp.asarray(arrays["k_q"]), jnp.asarray(arrays["k_scales"]),
            size)).reshape(shape).astype(dtype)
        v = np.asarray(dequantize_blockwise(
            jnp.asarray(arrays["v_q"]), jnp.asarray(arrays["v_scales"]),
            size)).reshape(shape).astype(dtype)
    else:
        k = arrays["k"].astype(dtype, copy=False).reshape(shape)
        v = arrays["v"].astype(dtype, copy=False).reshape(shape)
    # tolerant get: version-1 slices written before trace propagation
    # simply carry no trace_id
    return PageSlice(k, v, header["page_size"], header["length"],
                     header["pending_token"], header["context"],
                     trace_id=header.get("trace_id"))


def import_slice(engine, slot, sl):
    """Map a :class:`PageSlice` into ``slot`` of a (different) paged
    engine: allocate pages, scatter the payloads into the pool, point
    the slot's table at them. Returns the pending token (the decode
    input). The caller checks capacity via :func:`can_import` first —
    exhaustion here raises (paging.PagePoolExhausted)."""
    import jax.numpy as jnp
    assert engine.page_size == sl.page_size, \
        "page-size mismatch: engine {} vs slice {}".format(
            engine.page_size, sl.page_size)
    pool_shape = tuple(engine.kv.k.shape[1:])
    assert tuple(sl.k_pages.shape[1:]) == pool_shape, \
        "pool geometry mismatch: engine {} vs slice {}".format(
            pool_shape, tuple(sl.k_pages.shape[1:]))
    assert int(engine.page_counts[slot]) == 0 and \
        int(engine.lengths[slot]) == 0, \
        "import into live slot {}".format(slot)
    page_ids = np.asarray([engine.allocator.alloc()
                           for _ in range(sl.n_pages)], np.int32)
    k = engine.kv.k.at[page_ids].set(
        jnp.asarray(sl.k_pages, engine.kv.k.dtype))
    v = engine.kv.v.at[page_ids].set(
        jnp.asarray(sl.v_pages, engine.kv.v.dtype))
    engine.kv.update((k, v))
    engine.page_tables[slot, :sl.n_pages] = page_ids
    engine.page_counts[slot] = sl.n_pages
    engine.lengths[slot] = sl.length
    return sl.pending_token


def can_import(engine, sl):
    """True when the engine's pool can hold the slice right now (after
    trying prefix-cache eviction, mirroring admission)."""
    need = sl.n_pages
    if not engine.allocator.can_alloc(need) and \
            engine.prefix_cache is not None:
        engine.prefix_cache.evict(need)
    return engine.allocator.can_alloc(need)
