"""The program's own spans in a run's trace: the ``TraceAnnotation``
events that the serving step writes from the inside (names that start
with ``sched.``, ``engine.`` or ``timer.``; docs/telemetry.md lists
them), on the profiler's clock like the device's operations.

Not a reader: the readers of the ``program_span_*`` files share it. It
opens the run's ``.xplane.pb`` once and keeps what it found on ``run``.
The spans of one thread nest, so at every moment one of them is the
innermost open one, and :func:`innermost` lays the thread out as the
pieces between the spans' edges, each under that span's name: a span's
pieces add up to its self time, and an idle gap of the device laid over
them (:func:`book`) is cut at every edge it crosses. ``trace.label_gap``
gives a whole gap to the span that covers most of it, so that an outer
span always wins; that rule stays where the ``breakdown`` needs it.
"""
import collections
import json

from .. import stats, trace

PREFIXES = ("sched.", "engine.", "timer.")
OUTSIDE = None          # the name of time that no program span covers


def innermost(events):
    """``events``: (name, start_s, end_s, ...) of one thread, nested or
    disjoint. Returns the (start_s, end_s, name) pieces, in order and
    without overlap, that tile the time some span is open; ``name`` is
    the innermost span open there."""
    pieces, stack, at = [], [], None

    def close_until(t):
        nonlocal at
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > at:
                pieces.append((at, end, name))
                at = end

    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        name, start, end = ev[0], ev[1], ev[2]
        close_until(start)
        if stack:
            if start > at:
                pieces.append((at, start, stack[-1][0]))
            # a child that outlives its parent by the clock's rounding
            end = min(end, stack[-1][1])
        at = start
        stack.append((name, max(end, start)))
    close_until(float("inf"))
    return pieces


def book(gaps, pieces):
    """Seconds of the (start_s, end_s) ``gaps`` under each name of
    ``pieces`` (both in order, each without overlap), a gap cut at
    every edge it crosses; what no piece covers is booked under
    ``OUTSIDE``."""
    booked = collections.Counter()
    i = 0
    for start, end in gaps:
        covered = 0.0
        while i < len(pieces) and pieces[i][1] <= start:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < end:
            overlap = min(end, pieces[j][1]) - max(start, pieces[j][0])
            if overlap > 0:
                booked[pieces[j][2]] += overlap
                covered += overlap
            j += 1
        booked[OUTSIDE] += (end - start) - covered
    return dict(booked)


class ProgramSpans:
    """The program spans of one thread, and the device's idle time
    booked under them."""

    def __init__(self, events, reduction=None):
        # (name, start_s, end_s, {attribute: value})
        self.events = sorted(events, key=lambda e: (e[1], -e[2]))
        self.pieces = innermost(self.events)
        self.idle = None        # {innermost span or OUTSIDE: seconds}
        planes = list(reduction.device_events.values()) \
            if reduction is not None else []
        if planes and self.events:
            # the first device plane, as the breakdown's idle gaps
            gaps = trace.idle_gaps([(s, e) for _, _, s, e in planes[0]],
                                   reduction.start, reduction.end)
            self.idle = book(gaps, self.pieces)

    def named(self, names):
        names = set(names)
        return [ev for ev in self.events if ev[0] in names]

    def table(self):
        """{span: [count, seconds, self seconds, idle seconds under its
        self time (None with no device plane)]}."""
        rows = {}
        for name, start, end, _ in self.events:
            row = rows.setdefault(name, [0, 0.0, 0.0, None])
            row[0], row[1] = row[0] + 1, row[1] + end - start
        for start, end, name in self.pieces:
            rows[name][2] += end - start
        if self.idle is not None:
            for name, row in rows.items():
                row[3] = self.idle.get(name, 0.0)
        return rows


def from_trace(path):
    """The program's events of the thread that holds most of them (the
    scheduler's), from the ``.xplane.pb`` under ``path``."""
    import jax
    data = jax.profiler.ProfileData.from_file(trace.find_xplane(path))
    best = []
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            found = [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                      dict(ev.stats)) for ev in line.events
                     if ev.name.startswith(PREFIXES)]
            if len(found) > len(best):
                best = found
    return best


def load(run):
    """The run's :class:`ProgramSpans`, read once."""
    spans = getattr(run, "program_spans", None)
    if spans is None:
        spans = run.program_spans = ProgramSpans(
            from_trace(run.trace_dir), run.reduction)
        if spans.events:
            run.log("program spans {{span: [count, s, self_s, idle_s]}}: "
                    "{}".format(json.dumps({
                        name: [row[0]] + [None if v is None else
                                          round(v, 6) for v in row[1:]]
                        for name, row in sorted(spans.table().items())})))
            waits = [ev[3]["queue_wait_us"] for ev in spans.events
                     if "queue_wait_us" in ev[3]
                     and not ev[3].get("resumed")]
            if waits:
                run.log("program spans: queue wait of {} admitted "
                        "requests p50_us={} p95_us={} max_us={}".format(
                            len(waits), stats.percentile(waits, 50),
                            stats.percentile(waits, 95), max(waits)))
            if spans.idle is not None:
                run.log("program spans: device idle outside every "
                        "program span {:.6f} s of a {:.6f} s window"
                        .format(spans.idle.get(OUTSIDE, 0.0),
                                run.reduction.window_s))
    return spans
