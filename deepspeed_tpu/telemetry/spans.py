"""Span tracer: nested trace_id/span_id spans over the engines' steps.

One span tree per training optimizer step (root ``train_step`` with one
child per wcb/offload phase clock — the spans are a structured view of
the SAME disjoint phase timers the StepRecord already carries, so span
durations and ``phases`` always agree) and one span tree per serving
REQUEST (root ``serving_request``: admit -> prefill chunks ->
decode/spec-verify steps -> retire, with page-alloc / prefix-hit /
preemption events recorded where they happen in the scheduler).

Export is line-oriented: every completed tree writes its spans
depth-first (root first) to ``spans.jsonl`` — one JSON object per line,
schema pinned by :func:`validate_span` — and, when
``telemetry.spans.chrome_trace`` is on, as Chrome trace-event JSON
(``trace_events.json``, sinks.ChromeTraceSink) loadable in Perfetto
alongside the xprof windows from telemetry.trace.

Off (no ``telemetry.spans`` section) the engines hold ``spans = None``
and the hot paths pay one ``is not None`` check — the same
zero-overhead-off contract as the rest of telemetry.

:func:`annotate` (re-exported here from the leaf ``utils.annotate``)
is the other, smaller thing: the program's own boundaries written into
the PROFILER's trace (docs/telemetry.md, "Program spans").
"""
import itertools
import os
import time

from ..utils.annotate import annotate  # noqa: F401 - re-export
from ..utils.logging import logger

KIND_SPAN = "span"

# every exported span line carries exactly these keys
SPAN_KEYS = (
    "kind", "trace_id", "span_id", "parent_id", "name",
    "start_s", "end_s", "dur_s", "attrs", "events",
)

SPANS_MAX_EVENTS_DEFAULT = 256

_trace_counter = itertools.count()

_NUMERIC = (int, float)


class Span:
    """One node of a trace tree. Roots come from
    :meth:`SpanTracer.begin`; ``end()`` on the ROOT exports the whole
    tree through the tracer's sinks."""

    __slots__ = ("tracer", "trace_id", "span_id", "parent_id", "name",
                 "start_s", "end_s", "attrs", "events", "children",
                 "dropped_events")

    def __init__(self, tracer, name, trace_id, span_id, parent_id=None,
                 attrs=None, start_s=None):
        self.tracer = tracer
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = str(name)
        self.start_s = float(start_s if start_s is not None else time.time())
        self.end_s = None
        self.attrs = dict(attrs or {})
        self.events = []
        self.children = []
        self.dropped_events = 0

    # ------------------------------------------------------------- build
    def child(self, name, start_s=None, **attrs):
        """Open a child span (caller ends it)."""
        span = Span(self.tracer, name, self.trace_id,
                    self.tracer._next_span_id(), parent_id=self.span_id,
                    attrs=attrs, start_s=start_s)
        if len(self.children) < self.tracer.max_events:
            self.children.append(span)
        else:
            self.dropped_events += 1
        return span

    def timed_child(self, name, start_s, end_s, **attrs):
        """Child span with explicit bounds, already ended (the idiom for
        phases measured by an existing clock)."""
        span = self.child(name, start_s=start_s, **attrs)
        span.end_s = float(end_s)
        return span

    def event(self, name, wall=None, **attrs):
        """Point-in-time event on this span (page_alloc, prefix_hit,
        preempted, ...). Bounded by ``max_events_per_span``: overflow
        increments ``dropped_events`` instead of growing without bound
        on a long-running request."""
        if len(self.events) >= self.tracer.max_events:
            self.dropped_events += 1
            return
        ev = {"name": str(name),
              "wall": float(wall if wall is not None else time.time())}
        if attrs:
            ev["attrs"] = attrs
        self.events.append(ev)

    def end(self, end_s=None, **attrs):
        """Close the span; closing a ROOT exports the tree. Idempotent —
        a second end() keeps the first timestamps and does NOT re-export
        (a double export would duplicate every line in the sinks)."""
        first = self.end_s is None
        if first:
            self.end_s = float(end_s if end_s is not None else time.time())
        if attrs:
            self.attrs.update(attrs)
        if first and self.parent_id is None:
            self.tracer._export(self)

    # ------------------------------------------------------------ export
    def to_dict(self, end_default=None):
        end = self.end_s if self.end_s is not None else end_default
        attrs = self.attrs
        if self.dropped_events:
            attrs = dict(attrs, dropped_events=self.dropped_events)
        return {
            "kind": KIND_SPAN,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start_s": self.start_s,
            "end_s": end,
            "dur_s": None if end is None else max(end - self.start_s, 0.0),
            "attrs": attrs,
            "events": list(self.events),
        }

    def walk(self):
        yield self
        for child in self.children:
            for span in child.walk():
                yield span


def validate_span(rec):
    """Schema check for one exported span line. Returns a list of
    problem strings; empty list = valid."""
    problems = []
    if not isinstance(rec, dict):
        return ["span is not a dict: {!r}".format(type(rec).__name__)]
    if rec.get("kind") != KIND_SPAN:
        return ["unknown span kind {!r}".format(rec.get("kind"))]
    for key in SPAN_KEYS:
        if key not in rec:
            problems.append("missing key {!r}".format(key))
    extra = sorted(set(rec) - set(SPAN_KEYS))
    if extra:
        problems.append("unexpected key(s) {}".format(extra))
    if problems:
        return problems
    for key in ("trace_id", "span_id", "name"):
        if not isinstance(rec[key], str) or not rec[key]:
            problems.append("{} is not a non-empty string".format(key))
    if rec["parent_id"] is not None and \
            not isinstance(rec["parent_id"], str):
        problems.append("parent_id is neither null nor a string")
    for key in ("start_s", "end_s", "dur_s"):
        val = rec[key]
        if val is None and key != "start_s":
            continue            # open spans (crash bundles) have no end
        if isinstance(val, bool) or not isinstance(val, _NUMERIC):
            problems.append("{} is not a number: {!r}".format(key, val))
    if not isinstance(rec["attrs"], dict):
        problems.append("attrs is not a dict")
    events = rec["events"]
    if not isinstance(events, list):
        problems.append("events is not a list")
    else:
        for ev in events:
            if not isinstance(ev, dict) or \
                    not isinstance(ev.get("name"), str) or \
                    isinstance(ev.get("wall"), bool) or \
                    not isinstance(ev.get("wall"), _NUMERIC):
                problems.append("malformed event {!r}".format(ev))
    return problems


class SpanTracer:
    """Builds span trees and exports completed ones through its sinks
    (JsonlSink + optional ChromeTraceSink — sinks.py). The tracer OWNS
    its sinks: ``close()`` flushes/releases them."""

    def __init__(self, sinks, max_events=SPANS_MAX_EVENTS_DEFAULT,
                 job_name=""):
        self.sinks = list(sinks)
        self.max_events = int(max_events)
        self.job_name = job_name
        self._trace_prefix = "{}-{}".format(job_name or "trace",
                                            os.getpid())
        self._span_counter = itertools.count()
        self._open_roots = {}
        self.trees_exported = 0
        self.spans_exported = 0

    def _next_span_id(self):
        return "s{}".format(next(self._span_counter))

    # ------------------------------------------------------------- build
    def begin(self, name, start_s=None, trace_id=None, **attrs):
        """Open a new root span (one trace). ``end()`` on it exports the
        whole tree. Passing ``trace_id`` CONTINUES an existing trace
        instead of minting one — the disaggregated prefill -> decode
        handoff carries the prefill host's trace_id in the page-slice
        header, so one request stays ONE trace across role processes
        (ds_fleet merges the fragments into a single request lane)."""
        if trace_id is None:
            trace_id = "{}-{}".format(self._trace_prefix,
                                      next(_trace_counter))
        else:
            trace_id = str(trace_id)
        root = Span(self, name, trace_id, self._next_span_id(),
                    parent_id=None, attrs=attrs, start_s=start_s)
        self._open_roots[trace_id] = root
        return root

    def emit_step_tree(self, name, *, step, t0, t1, phases=None,
                       attrs=None, segments=None):
        """Derive and export one step's span tree from its measured
        window [t0, t1] and the StepRecord's disjoint phase clocks: the
        root spans the window; each phase becomes a child, laid out
        sequentially from t0 (the clocks are disjoint by construction —
        see engine._telemetry_phases — so the sequential layout
        preserves every duration).

        ``segments``: the PlanExecutor's executed-segment records for
        steps that ran as segment plans (runtime/executor/). When
        given, the children ARE the executed plan — one span per
        segment at its measured wall, named by its plan node, so the
        trace tree and the segment plan cannot drift (a phase-derived
        tree is the fallback for unlowered paths)."""
        root = self.begin(name, start_s=t0, **(dict(attrs or {},
                                                    step=int(step))))
        if segments:
            for rec in segments:
                start = rec.start_s if rec.start_s is not None else t0
                end = rec.end_s if rec.end_s is not None else start
                child = root.timed_child(rec.name, start, end,
                                         kind=rec.kind)
                if rec.async_run:
                    child.attrs["async"] = True
                if rec.wait_s:
                    child.attrs["wait_s"] = round(rec.wait_s, 6)
        else:
            at = t0
            for phase, dur in (phases or {}).items():
                dur = float(dur)
                root.timed_child(str(phase), at, at + dur)
                at += dur
        root.end(end_s=t1)
        return root

    # ------------------------------------------------------------ export
    def _export(self, root):
        self._open_roots.pop(root.trace_id, None)
        self.trees_exported += 1
        for span in root.walk():
            rec = span.to_dict(end_default=root.end_s)
            self.spans_exported += 1
            for sink in self.sinks:
                try:
                    sink.emit(rec)
                except Exception as err:  # noqa: BLE001 - observe, not perturb
                    logger.warning("span sink %s failed (%s)",
                                   type(sink).__name__, err)

    def open_snapshot(self):
        """Flattened dicts of every OPEN (unexported) trace — what the
        flight recorder bundles when a crash interrupts live spans."""
        out = []
        for root in list(self._open_roots.values()):
            for span in root.walk():
                # open spans export end_s/dur_s = null, honestly: the
                # crash interrupted them
                out.append(span.to_dict(end_default=None))
        return out

    def close(self):
        for sink in self.sinks:
            try:
                sink.close()
            except Exception:  # noqa: BLE001
                pass
        self.sinks = []
