"""Continuous-batching scheduler over the InferenceEngine's cache slots.

Admission happens at DECODE-STEP granularity: each ``step()`` first
admits queued requests into free slots (paged admission maps prefix-
cache hits and allocates prompt pages), then runs at most ONE prefill
chunk per admitted-but-not-ready slot, then one fused decode step —
plain or speculative-verify — for every decoding slot, then retires
slots whose request hit EOS / max_new_tokens / the cache ceiling. A
long request therefore never serializes the short ones behind it (the
Orca / vLLM iteration-level scheduling discipline), and with
``inference.prefill_chunk_tokens`` set, a LONG PREFILL no longer stalls
the decode batch either: the decode step keeps firing between chunks.

Speculative decoding (``inference.speculative``): the drafter proposes
``k`` tokens per decoding slot, one fused verify pass scores all slots'
proposals, and the longest target-agreeing prefix (+1 bonus token)
commits — greedy acceptance reproduces the autoregressive greedy stream
byte-for-byte.

Paged-pool pressure: admission that cannot allocate stays queued;
mid-decode exhaustion preempts the YOUNGEST decoding request (pages
freed, request requeued; its context re-prefills on re-admission — the
recompute-preemption discipline). A model whose paged layers stand in
groups (inference/decoder.py) has a pool a group: a request is admitted
when EVERY pool has room for it, a decode step that any pool cannot
serve preempts, and a retired or preempted request's pages go back to
every pool. A windowed group's pages go back as they slide out of the
window, in the step that makes it so: ``window_freed`` on the step's
``sched.decode.pages`` and ``sched.prefill.chunk`` spans.

Timing uses utils/timer.py's synchronized timers around each engine
call, fenced on the buffers the engine's programs return
(``InferenceEngine.wait``: a wait on the cache, no op sent to the device;
training's timers send one). It lands in a
:class:`utils.monitor.ServingMetrics` (prefill vs decode tokens/s, slot
occupancy, queue depth, TTFT/TPOT, speculative acceptance), which the
telemetry collector joins with page-pool occupancy and prefix-share
stats into one ``serving_step`` record per scheduler step.
"""
import time
from collections import deque

from ..utils.annotate import annotate
from ..utils.monitor import ServingMetrics
from ..utils.timer import SynchronizedWallClockTimer
from .paging import plan_chunks

_UNSET = object()


class InferenceRequest:
    """One queued/running generation request."""

    __slots__ = ("uid", "prompt", "max_new_tokens", "eos_token_id",
                 "generated", "slot", "state", "context", "chunks",
                 "chunk_idx", "arrival_t", "first_token_t", "resumed",
                 "admit_order", "span", "adapter")

    def __init__(self, uid, prompt, max_new_tokens, eos_token_id):
        self.uid = uid
        self.prompt = list(prompt)
        self.max_new_tokens = max_new_tokens
        self.eos_token_id = eos_token_id
        self.generated = []
        self.slot = None
        self.state = "queued"        # queued -> prefill -> decode -> done
        self.context = self.prompt   # tokens to embed (grows on resume)
        self.chunks = None           # [(start, len), ...] prefill plan
        self.chunk_idx = 0
        self.arrival_t = time.perf_counter()
        self.first_token_t = None
        self.resumed = False         # re-admitted after preemption
        self.admit_order = -1        # preemption picks the youngest
        self.span = None             # request trace (telemetry.spans)
        self.adapter = 0             # tenant adapter id (0 = base model)


class ContinuousBatchingScheduler:

    def __init__(self, engine, metrics=None, sampling=None):
        self.engine = engine
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.metrics.kv_token_bytes = int(engine.kv_token_bytes)
        # telemetry records ALWAYS embed the engine-lifetime counters —
        # a caller-supplied per-call `metrics` is accounted in parallel,
        # never routed into the JSONL, or its zeroed counters would make
        # join-on-step deltas go negative at the generate() boundary
        self._record_metrics = getattr(engine, "serving_metrics", None)
        if self._record_metrics is None:
            self._record_metrics = self.metrics
        self.sampling = sampling
        # diagnostics seams (docs/diagnostics.md): one is-not-None check
        # each when the spans / watchdog sections are off
        tel = getattr(engine, "telemetry", None)
        self._spans = tel.spans if tel is not None else None
        self._watchdog = tel.watchdog if tel is not None else None
        self.queue = deque()
        self.slots = [None] * engine.num_slots
        self.results = {}
        # the timers fence on the engine's own cache buffers: after a
        # launch's fetch that is a check, not a round trip to the device
        self.timers = SynchronizedWallClockTimer(fence=engine.wait)
        self._next_uid = 0
        self._admitted = 0
        self.steps = 0
        self.preemptions = 0
        # a decoder with page groups: what each group gave back as its
        # pages slid out of a window, as last noted (_note_group_pages)
        self._grouped = bool(getattr(engine, "_grouped", False))
        self._group_freed = 0
        if self._grouped:
            self._windowed = [g.window is not None
                              for g in engine.page_groups]
            self._window_pool = sum(
                g.allocator.num_pages for g in engine.page_groups
                if g.window is not None)

    def _account(self, method, *args, **kwargs):
        """Apply one ServingMetrics update to the caller's object AND
        the engine-lifetime one the telemetry records embed."""
        getattr(self.metrics, method)(*args, **kwargs)
        if self._record_metrics is not self.metrics:
            getattr(self._record_metrics, method)(*args, **kwargs)

    def _account_counters(self):
        """What the last launch's program counted, under the names the
        decoder gave (engine.last_counters); nothing for most models."""
        counters = getattr(self.engine, "last_counters", None)
        if counters:
            self._account("record_counters", counters)

    def _note_group_pages(self, span, slots=None):
        """A decoder with page groups: on ``span`` the pages its
        windowed groups gave back since the last note (``window_freed``)
        and, with the decoding ``slots``, the pages they hold in the
        groups without a window and with one and the windowed pools'
        size; the serving metrics get the same counts."""
        live, freed = self.engine.group_page_counts(
            slots if slots is not None else [])
        attrs = {"window_freed": sum(freed) - self._group_freed}
        self._group_freed = sum(freed)
        if slots is not None:
            window_live = sum(n for n, w in zip(live, self._windowed) if w)
            attrs.update(full_live=sum(live) - window_live,
                         window_live=window_live,
                         window_pool=self._window_pool)
            self._account("record_group_pages", live, freed)
        if span is not None:
            span.set_metadata(**attrs)

    # ------------------------------------------------------------- intake

    def submit(self, prompt, max_new_tokens=None, eos_token_id=_UNSET,
               adapter=0):
        """Queue a request; returns its uid (results keyed by it).
        ``adapter`` pins the request to one tenant's LoRA adapter (0 =
        the base model; needs ``engine.attach_adapters``)."""
        ic = self.engine.inference_config
        prompt = list(prompt)
        assert len(prompt) >= 1, "empty prompt"
        # admission-time validation so a bad request fails its caller,
        # not a later step() on someone else's request (a prompt longer
        # than the largest prefill bucket is no bad request: it goes in
        # chunks of that bucket, paging.plan_chunks)
        assert len(prompt) < self.engine.max_seq_len, \
            "prompt length {} leaves no room to decode (max_seq_len " \
            "{})".format(len(prompt), self.engine.max_seq_len)
        assert max_new_tokens is None or max_new_tokens >= 1, \
            "max_new_tokens must be >= 1, got {!r}".format(max_new_tokens)
        if adapter:
            assert self.engine.adapters is not None, \
                "submit(adapter={}) needs engine.attach_adapters".format(
                    adapter)
            assert 0 <= adapter < len(self.engine.adapters), \
                "adapter id {} out of range [0, {})".format(
                    adapter, len(self.engine.adapters))
        req = InferenceRequest(
            self._next_uid, prompt,
            max_new_tokens if max_new_tokens is not None
            else ic.max_new_tokens,
            ic.eos_token_id if eos_token_id is _UNSET else eos_token_id)
        req.adapter = int(adapter)
        self._next_uid += 1
        self.queue.append(req)
        return req.uid

    # ------------------------------------------------------------ stepping

    @property
    def num_active(self):
        return sum(1 for r in self.slots if r is not None)

    @property
    def has_work(self):
        return bool(self.queue) or self.num_active > 0

    def _finish(self, req):
        """Move a request's result out and release its slot + pages."""
        self.results[req.uid] = list(req.generated)
        if req.span is not None:
            req.span.event("retire", generated=len(req.generated))
            req.span.end(generated=len(req.generated))
        req.state = "done"
        self.slots[req.slot] = None
        self.engine.free_slot(req.slot)
        if self.engine.drafter is not None:
            self.engine.drafter.free_slot(req.slot)
        now = time.perf_counter()
        tpot = None
        if len(req.generated) > 1 and req.first_token_t is not None:
            tpot = (now - req.first_token_t) / (len(req.generated) - 1)
        self._account("record_completion", len(req.generated), tpot)
        req.slot = None

    def _retire_if_done(self, req):
        done = (len(req.generated) >= req.max_new_tokens or
                (req.eos_token_id is not None and req.generated and
                 req.generated[-1] == req.eos_token_id) or
                not self.engine.can_decode(req.slot))
        if done:
            self._finish(req)
        return done

    def _append_tokens(self, req, tokens):
        """Commit generated tokens, honoring EOS and the budget. Returns
        ``(appended, done)`` — how many tokens the request actually took
        (speculative accounting must not count truncated ones) and
        whether it retired."""
        appended = 0
        for tok in tokens:
            req.generated.append(int(tok))
            appended += 1
            if ((req.eos_token_id is not None and
                 int(tok) == req.eos_token_id) or
                    len(req.generated) >= req.max_new_tokens):
                break
        return appended, self._retire_if_done(req)

    def _preempt_youngest(self, exclude=()):
        """Recompute-preemption: requeue the most recently admitted
        decoding request, freeing its pages. Its context (prompt + the
        tokens generated so far, minus the pending one) re-prefills on
        re-admission and generation continues where it stopped."""
        victim = None
        for req in self.slots:
            if req is None or req in exclude or req.state != "decode":
                continue
            if victim is None or req.admit_order > victim.admit_order:
                victim = req
        if victim is None:
            return False
        if victim.span is not None:
            victim.span.event("preempted", step=self.steps,
                              generated=len(victim.generated))
        if self._watchdog is not None:
            self._watchdog.observe_pool_event("preemption")
        self.slots[victim.slot] = None
        self.engine.free_slot(victim.slot)
        if self.engine.drafter is not None:
            self.engine.drafter.free_slot(victim.slot)
        victim.slot = None
        victim.state = "queued"
        victim.resumed = True
        # generated[-1] is the PENDING token (not yet in the cache): it
        # re-enters as the decode input after the context re-prefills
        victim.context = victim.prompt + victim.generated[:-1]
        victim.chunks, victim.chunk_idx = None, 0
        self.queue.appendleft(victim)
        self.preemptions += 1
        return True

    # ------------------------------------------------------------ phases

    def _admit(self):
        for slot in range(len(self.slots)):
            if self.slots[slot] is not None or not self.queue:
                continue
            req = self.queue[0]
            if self.engine.adapters is not None:
                # BEFORE try_admit: the prefix match runs under the
                # tenant's namespace
                self.engine.assign_adapter(slot, req.adapter)
            if not self.engine.try_admit(slot, req.context):
                if self._watchdog is not None:
                    self._watchdog.observe_pool_event("admission_blocked")
                break                      # pool full: stay queued
            # the wait ends here: arrival -> a slot and its pages
            wait = time.perf_counter() - req.arrival_t
            with annotate("sched.admit.request", uid=req.uid,
                          queue_wait_us=int(wait * 1e6),
                          resumed=req.resumed):
                self.queue.popleft()
                req.slot = slot
                req.state = "prefill"
                req.admit_order = self._admitted
                self._admitted += 1
                self.slots[slot] = req
                if not req.resumed:
                    # a re-admission after preemption waited since it
                    # was preempted, not since it arrived: no queue wait
                    self._account("record_queue_wait", wait)
                if self._spans is not None:
                    if req.span is None:
                        # one span tree per REQUEST — it survives preemption
                        # (the re-admit lands as a second admit event on the
                        # same trace)
                        req.span = self._spans.begin(
                            "serving_request", uid=req.uid,
                            prompt_tokens=len(req.prompt))
                    req.span.event("admit", slot=slot, resumed=req.resumed,
                                   queue_wait_s=round(wait, 6))
                    matched = int(self.engine._admit_matched.get(slot, 0))
                    req.span.event(
                        "page_alloc",
                        pages=int(self.engine.page_counts[slot]),
                        prefix_pages=matched)
                    if matched:
                        req.span.event("prefix_hit", pages=matched)
                # the chunk plan is built at FIRST-chunk time (below): the
                # prefix match runs there, after same-step siblings have
                # registered their pages, so bursts of one system prompt
                # share within a single scheduler step
                req.chunks, req.chunk_idx = None, 0

    def _prefill_chunks(self, retired):
        ic = self.engine.inference_config
        for req in list(self.slots):
            if req is None or req.state != "prefill":
                continue
            if req.chunks is None:
                start = self.engine.match_prefix(req.slot, req.context)
                req.chunks = plan_chunks(
                    len(req.context) - start, ic.prefill_chunk_tokens,
                    self.engine.bucket_for, self.engine.max_seq_len,
                    start=start,
                    max_chunk=self.engine.prefill_buckets[-1])
                if start:
                    # prefix-cache hit: the matched pages' tokens are
                    # already resident — only the suffix embeds
                    self.engine.lengths[req.slot] = start
                    if req.span is not None:
                        req.span.event("prefix_hit", tokens=start)
            start, ln = req.chunks[req.chunk_idx]
            # first: the chunk's program starts the slot's recurrent
            # state (where the model keeps one) from zeros
            first = start == 0
            with annotate("sched.prefill.chunk", uid=req.uid, tokens=ln,
                          padded=self.engine.bucket_for(ln),
                          first=first, start=start) as span:
                if first and self.engine.state is not None:
                    self._account("record_state_reset")
                chunk = req.context[start:start + ln]
                # no page check here: try_admit reserved the WHOLE context's
                # pages at admission, so every chunk's range is covered —
                # only decode growth (ensure_pages in _decode) can starve
                t = self.timers("prefill")
                t.start()
                token = self.engine.prefill_chunk(req.slot, chunk, start,
                                                  sampling=self.sampling)
                t.stop()
                dt = t.elapsed(reset=True)
                if self._grouped:
                    self._note_group_pages(span)
                with annotate("sched.prefill.commit"):
                    self._account("record_prefill", ln, dt)
                    self._account_counters()
                    if req.span is not None:
                        now = time.time()
                        req.span.timed_child("prefill_chunk", now - dt, now,
                                             start=start, tokens=ln)
                    req.chunk_idx += 1
                    # register the pages filled SO FAR (full pages only): a
                    # same-burst sibling admitted this very step can match them
                    self.engine.register_prefix(req.slot,
                                                req.context[:start + ln])
                    if req.chunk_idx < len(req.chunks):
                        continue
                    # final chunk: the request becomes a decoder
                    req.state = "decode"
                    if self.engine.drafter is not None:
                        self.engine.drafter.prefill(req.slot, req.context)
                    if req.resumed:
                        # the pending token survived preemption;
                        # nothing sampled
                        continue
                    now = time.perf_counter()
                    req.first_token_t = now
                    ttft = now - req.arrival_t
                    self._account("record_ttft", ttft)
                    if self._watchdog is not None:
                        self._watchdog.observe_ttft(ttft)
                    if self._append_tokens(req, [token])[1]:
                        retired.append(req.uid)

    def _spec_k_eff(self):
        """Draft length this step: the configured k, or 0 (plain
        decode) whenever ANY occupied slot — decoding OR mid-prefill,
        the fused verify writes K/V for every slot — sits within k+1 of
        max_seq: a slot's table has no page past it, and the model
        drafter's contiguous cache would clamp an out-of-range write
        start and corrupt live positions. All-or-
        nothing (rather than shrinking k per step) bounds the decode
        program family to two widths, so one near-ceiling sequence
        can't trigger a cascade of mid-serving XLA recompiles."""
        k = self.engine.spec_k
        for req in self.slots:
            if req is None:
                continue
            if int(self.engine.lengths[req.slot]) + 1 + k > \
                    self.engine.max_seq_len:
                return 0
        return k

    def _decode(self, retired):
        with annotate("sched.decode.pages") as span:
            active = [r for r in self.slots
                      if r is not None and r.state == "decode"]
            if not active:
                return
            # paged capacity for this step's writes (plain decode: 1 token;
            # verify: k+1) — exhaustion preempts the youngest decoder
            drafter = self.engine.drafter
            k_eff = self._spec_k_eff() if drafter is not None else 0
            width = 1 + k_eff
            for req in list(active):
                if req.state != "decode":
                    # preempted by an earlier slot's capacity fight
                    active.remove(req)
                    continue
                ok = self.engine.ensure_pages(
                    req.slot, int(self.engine.lengths[req.slot]) + width)
                while not ok and self._preempt_youngest(exclude=(req,)):
                    ok = self.engine.ensure_pages(
                        req.slot, int(self.engine.lengths[req.slot]) + width)
                if not ok:
                    # starved even after preemption: sit this step out (its
                    # write would land in the garbage page and the token's
                    # K/V would be lost)
                    active.remove(req)
            # a later slot's capacity fight may have preempted an EARLIER
            # already-validated one — keep only the still-decoding survivors
            active = [r for r in active if r.state == "decode"]
            if not active:
                return

            slots = self.engine.num_slots
            pending = [0] * slots
            for req in active:
                pending[req.slot] = req.generated[-1]
            if self._grouped:
                self._note_group_pages(span, [r.slot for r in active])

        if k_eff >= 1:
            # ---- speculative: draft k, verify all slots in one pass
            if drafter.needs_model:
                drafts = drafter.propose_batch(pending, k_eff)
            else:
                drafts = [[0] * k_eff for _ in range(slots)]
                for req in active:
                    # prompt + generated = the TRUE token stream; a
                    # preemption-resume folded earlier generations into
                    # req.context, so context+generated would duplicate
                    # them and derail the n-gram match
                    drafts[req.slot] = drafter.propose(
                        req.prompt + req.generated, k_eff)
            tokens = [[pending[s]] + list(drafts[s])[:k_eff]
                      for s in range(slots)]
            t = self.timers("decode")
            t.start()
            chosen = self.engine.verify_step(tokens,
                                             sampling=self.sampling)
            t.stop()
            dt = t.elapsed(reset=True)
            with annotate("sched.decode.commit"):
                emitted = 0
                span_end = time.time()
                for req in active:
                    row, s = chosen[req.slot], req.slot
                    accepted = 0
                    while accepted < k_eff and \
                            int(tokens[s][accepted + 1]) == int(row[accepted]):
                        accepted += 1
                    new = [int(row[j]) for j in range(accepted + 1)]
                    self.engine.advance(s, accepted + 1)
                    if drafter.needs_model:
                        drafter.advance(s, accepted + 1)
                    self._account("record_spec", k_eff, accepted)
                    if req.span is not None:
                        # the fused verify pass scored every slot at once:
                        # each participant's child span shares its wall.
                        # Added BEFORE _append_tokens — retiring exports the
                        # tree, and a child added after export is lost
                        req.span.timed_child(
                            "spec_verify", span_end - dt, span_end,
                            step=self.steps, drafted=k_eff,
                            accepted=accepted, tokens=len(new))
                    appended, done = self._append_tokens(req, new)
                    emitted += appended
                    if done:
                        retired.append(req.uid)
                self._account("record_decode", emitted, dt)
        else:
            if drafter is not None and drafter.needs_model:
                # a k=0 propose embeds exactly the pending token into
                # the drafter's cache: advancing its lengths without
                # this write would leave a stale hole INSIDE the live
                # window and poison every draft after speculation
                # resumes (the near-ceiling slot retires, k_eff
                # returns to k)
                drafter.propose_batch(pending, 0)
            t = self.timers("decode")
            t.start()
            next_tokens = self.engine.decode_step(
                pending, sampling=self.sampling,
                active=[req.slot for req in active])
            t.stop()
            dt = t.elapsed(reset=True)
            with annotate("sched.decode.commit"):
                self._account("record_decode", len(active), dt)
                self._account_counters()
                span_end = time.time()
                for req in active:
                    self.engine.advance(req.slot)
                    if drafter is not None and drafter.needs_model:
                        drafter.advance(req.slot, 1)
                    if req.span is not None:
                        req.span.timed_child("decode", span_end - dt,
                                             span_end, step=self.steps)
                    if self._append_tokens(req,
                                           [int(next_tokens[req.slot])])[1]:
                        retired.append(req.uid)

    def step(self):
        """Admit -> prefill chunks -> one decode/verify step -> retire.
        Returns uids retired this step."""
        try:
            return self._step_impl()
        except BaseException as err:
            # flight-recorder hook: dump (once per exception object;
            # watchdog raise-trips are already dumped), re-raise
            tel = getattr(self.engine, "telemetry", None)
            if tel is not None and tel.recorder is not None:
                try:
                    tel.recorder.dump("exception:serving_step", exc=err)
                except Exception:  # noqa: BLE001 - never mask the error
                    pass
            raise

    def _step_impl(self):
        if not self.queue and self.num_active == 0:
            # idle poll: nothing to admit and no slot to decode — emit no
            # zero-work serving record (a polling serve loop would grow
            # telemetry.jsonl without bound and drag the snapshot's
            # occupancy/queue p50/p95 down to the idle value)
            return []
        with annotate("sched.step", step=self.steps):
            tel = getattr(self.engine, "telemetry", None)
            # 0-based like the training engine's records (global_steps at
            # window open) and ENGINE-lifetime (not per-generate-call), so
            # joining the JSONLs on `step` and setting trace.start_step mean
            # the same thing on both engines
            record_step = getattr(self.engine, "serving_record_steps", 0)
            if tel is not None:
                # BEFORE the step's prefill/decode work so an armed xprof
                # window opens around it, not after it (docs/telemetry.md)
                tel.on_step_begin(record_step)
            # the step body is a segment plan on the PlanExecutor
            # (runtime/executor/serving.py): admit -> prefill -> decode ->
            # retire, each phase one audited segment
            from ..runtime.executor.serving import run_serving_step
            return run_serving_step(self, record_step)

    def run(self):
        """Drive step() until every submitted request has retired; returns
        {uid: generated tokens}."""
        while self.has_work:
            self.step()
        return self.results
