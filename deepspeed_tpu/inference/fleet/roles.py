"""Engine roles for disaggregated serving: prefill and decode.

A **prefill** role runs chunked prefill for one request at a time on
its own paged engine, samples the first token, exports the finished KV
as a serialized page-table slice (handoff.py), and immediately frees
the slot — its pool only ever holds in-flight prompts. A **decode**
role imports slices into its own pool and continues decoding through
the standard continuous-batching scheduler, so preemption, speculative
decode and telemetry all behave exactly as on a monolithic engine.

The contract the dryrun leg pins: greedy streams through
``PrefillRole.prefill_request`` → bytes → ``DecodeRole.accept`` are
byte-identical to the single-engine paged path (fp handoff), because
the prefill programs are the same jitted programs, the fp codec moves
page payloads verbatim, and the decode gather reads them through the
imported page table at identical positions.
"""
import time

from ..decoder import refuse
from ..scheduler import ContinuousBatchingScheduler, InferenceRequest
from ..paging import plan_chunks
from .handoff import (DEFAULT_HANDOFF_BLOCK, can_import, export_slice,
                      import_slice, serialize_slice)

_UNSET = object()


class PrefillRole:
    """Chunked-prefill front half over a paged :class:`InferenceEngine`."""

    def __init__(self, engine, sampling=None, quantize=False,
                 block_size=DEFAULT_HANDOFF_BLOCK):
        refuse(engine.decoder, engine.decoder.cache_spec(), "handoff")
        self.engine = engine
        self.sampling = sampling
        self.quantize = bool(quantize)
        self.block_size = int(block_size)
        engine.serving_role = "prefill"
        self._free = list(range(engine.num_slots))
        self.handoffs = 0
        self.handoff_bytes = 0

    def prefill_request(self, prompt, metrics=None):
        """Prefill ``prompt`` end to end and export its KV. Returns
        ``(payload_bytes, first_token, prefill_seconds, bucket)`` or
        None when the pool/slots cannot admit right now (the router
        keeps the request queued)."""
        engine = self.engine
        prompt = [int(t) for t in prompt]
        if not self._free:
            return None
        slot = self._free[-1]
        if not engine.try_admit(slot, prompt):
            return None
        self._free.pop()
        ic = engine.inference_config
        # the request's trace STARTS here: the root's trace_id rides the
        # page-slice header so the decode host's spans continue the SAME
        # trace (one request = one trace across role processes)
        tel = engine.telemetry
        spans = tel.spans if tel is not None else None
        span = None
        if spans is not None:
            span = spans.begin("prefill_request", role="prefill",
                               prompt_tokens=len(prompt))
        t0 = time.perf_counter()
        start = engine.match_prefix(slot, prompt)
        if start:
            engine.lengths[slot] = start
        chunks = plan_chunks(
            len(prompt) - start, ic.prefill_chunk_tokens,
            engine.bucket_for, engine.max_seq_len, start=start,
            max_chunk=engine.prefill_buckets[-1])
        token = None
        for c_start, c_len in chunks:
            c_t0 = time.time()
            token = engine.prefill_chunk(
                slot, prompt[c_start:c_start + c_len], c_start,
                sampling=self.sampling)
            engine.register_prefix(slot, prompt[:c_start + c_len])
            if span is not None:
                span.timed_child("prefill_chunk", c_t0, time.time(),
                                 start=c_start, tokens=c_len)
        dt = time.perf_counter() - t0
        if metrics is not None:
            metrics.record_prefill(len(prompt) - start, dt)
            if engine.telemetry is not None:
                # one role="prefill" serving_step per finished prefill,
                # through the same sink layer the decode schedulers
                # write — the fleet doctor's per-role host attribution
                # reads these (docs/fleet.md)
                busy = engine.num_slots - len(self._free)
                engine.telemetry.emit_serving_step(
                    step=engine.serving_record_steps, metrics=metrics,
                    active_slots=busy, queue_depth=0,
                    occupancy=busy / engine.num_slots,
                    page_pool=engine.page_pool_stats(),
                    prefix=engine.prefix_stats(), role="prefill")
                engine.serving_record_steps += 1
        sl = export_slice(engine, slot, context=prompt,
                          pending_token=token,
                          trace_id=span.trace_id
                          if span is not None else None)
        payload = serialize_slice(sl, quantize=self.quantize,
                                  block_size=self.block_size)
        engine.free_slot(slot)
        self._free.append(slot)
        self.handoffs += 1
        self.handoff_bytes += len(payload)
        if span is not None:
            span.event("handoff_export", bytes=len(payload),
                       pages=sl.n_pages)
            span.end()
        return payload, int(token), dt, engine.bucket_for(len(prompt))


class DecodeRole:
    """Decode back half: a continuous-batching scheduler whose requests
    arrive as imported page slices instead of prompts."""

    def __init__(self, engine, metrics=None, sampling=None):
        refuse(engine.decoder, engine.decoder.cache_spec(), "handoff")
        self.engine = engine
        engine.serving_role = "decode"
        self.sched = ContinuousBatchingScheduler(engine, metrics=metrics,
                                                 sampling=sampling)
        self.accepted = 0

    def _free_slot(self):
        for slot, req in enumerate(self.sched.slots):
            if req is None:
                return slot
        return None

    def free_slots(self):
        return sum(1 for r in self.sched.slots if r is None)

    @property
    def active(self):
        return self.sched.num_active

    @property
    def has_work(self):
        return self.sched.has_work

    def step(self):
        return self.sched.step()

    def accept(self, sl, max_new_tokens=None, eos_token_id=_UNSET):
        """Import one deserialized :class:`handoff.PageSlice` and start
        decoding it. Returns the live :class:`InferenceRequest` (its
        ``generated`` list IS the stream; ``state == "done"`` when
        retired), or None when no slot/pages are available — the
        router keeps the handoff queued."""
        engine = self.engine
        slot = self._free_slot()
        if slot is None or not can_import(engine, sl):
            return None
        ic = engine.inference_config
        req = InferenceRequest(
            self.sched._next_uid, sl.context,
            max_new_tokens if max_new_tokens is not None
            else ic.max_new_tokens,
            ic.eos_token_id if eos_token_id is _UNSET else eos_token_id)
        self.sched._next_uid += 1
        pending = import_slice(engine, slot, sl)
        req.slot = slot
        req.state = "decode"
        req.admit_order = self.sched._admitted
        self.sched._admitted += 1
        req.first_token_t = time.perf_counter()
        self.sched.slots[slot] = req
        if self.sched._spans is not None:
            # continue the prefill host's trace (sl.trace_id from the
            # slice header; None mints a fresh one) — ds_fleet's merged
            # view shows the request as ONE lane across both roles
            req.span = self.sched._spans.begin(
                "serving_request", trace_id=sl.trace_id, uid=req.uid,
                prompt_tokens=len(sl.context), role="decode")
            req.span.event("handoff_accept", slot=slot,
                           pages=sl.n_pages)
        if engine.drafter is not None:
            engine.drafter.prefill(slot, req.context)
        self.accepted += 1
        # the handed-off first token enters through the same EOS/budget
        # gate a monolith's prefill token does (may retire immediately)
        self.sched._append_tokens(req, [pending])
        return req

    def accept_migrated(self, sl, req):
        """Re-home a live request mid-stream (preempt-and-migrate):
        import its slice and keep its identity — uid, generated tokens,
        budget — so the stream continues where the source host stopped.
        Returns the request, or None when this host has no capacity."""
        engine = self.engine
        slot = self._free_slot()
        if slot is None or not can_import(engine, sl):
            return None
        import_slice(engine, slot, sl)
        req.slot = slot
        req.state = "decode"
        req.admit_order = self.sched._admitted
        self.sched._admitted += 1
        self.sched.slots[slot] = req
        if engine.drafter is not None:
            engine.drafter.prefill(slot, req.context)
        self.accepted += 1
        return req

    def export_request(self, req, quantize=False,
                       block_size=DEFAULT_HANDOFF_BLOCK):
        """Lift a live decoding request OFF this host (the migration
        source side): export its pages + pending token, release the
        slot. The caller re-homes the returned slice via another
        host's :meth:`accept_migrated`."""
        engine = self.engine
        assert req.slot is not None and \
            self.sched.slots[req.slot] is req, \
            "request {} is not live on this host".format(req.uid)
        assert req.state == "decode" and req.generated, \
            "only decoding requests migrate (state {!r})".format(
                req.state)
        # generated[-1] is the PENDING token (not yet in the cache) —
        # the same discipline recompute-preemption uses
        sl = export_slice(
            engine, req.slot,
            context=req.prompt + req.generated[:-1],
            pending_token=req.generated[-1])
        self.sched.slots[req.slot] = None
        engine.free_slot(req.slot)
        if engine.drafter is not None:
            engine.drafter.free_slot(req.slot)
        req.slot = None
        return sl

    def youngest(self):
        """The most recently admitted decoding request (the preempt-
        and-migrate victim policy, matching recompute-preemption's)."""
        victim = None
        for req in self.sched.slots:
            if req is None or req.state != "decode":
                continue
            if victim is None or req.admit_order > victim.admit_order:
                victim = req
        return victim
