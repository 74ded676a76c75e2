"""From the profiler's ``.xplane.pb`` to device busy time, operation
times and labelled idle gaps (read with ``jax.profiler.ProfileData``).

A device plane is one whose name starts with ``/device:TPU:``; of its
lines those that hold single operations are read for times (``XLA
Ops``); the module line (``XLA Modules``: one event a run of a compiled
program) covers the same time again and is read only to tell which
operations belong to one run (:meth:`Reduction.whole_launches`): a
kernel's share of a roofline prices the runs that the trace holds
whole, not the steps the host counted, so that a trace that lost
operations loses their work with their time. The benchmark's
own host spans are the ``TraceAnnotation`` events of the same names on
the host planes, on the profiler's clock like the device's events, so
an idle gap is labelled by the span that covers most of it.

``python -m benchmark.trace <dir-or-file>`` prints what a trace holds:
look at one by hand before writing a pattern against it.
"""
import bisect
import collections
import glob
import os
import re
import sys

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
TOP = 10
# operations that only contain others (their bodies' operations are
# events of their own): counted for busy time, not as operations
CONTAINERS = ("while", "conditional", "call")
_KIND = re.compile(r"([a-z][a-z0-9\-]*)\(")


def short_name(event_name):
    """``%fusion.12 = bf16[20,1024]{1,0:T(8,128)} fusion(...), kind=...``
    (the device plane names an event by its whole HLO instruction) ->
    (label, kind). The label is the kind and the output's shape,
    ``fusion bf16[20,1024]``, so that the same operation of every layer
    adds up; a Mosaic kernel's is its name without the instance number,
    ``jvp__ kernel``."""
    name, _, rest = event_name.partition(" = ")
    found = _KIND.search(rest)
    if not found:
        return name.lstrip("%"), ""
    kind = found.group(1)
    if "tpu_custom_call" in rest:
        return re.sub(r"\.\d+$", "", name.lstrip("%")) + " kernel", kind
    shape = re.sub(r"\{[^}]*\}", "", rest[:found.start()]).strip()
    return (kind + " " + shape)[:96], kind


def program_of(module_name):
    """``jit_decode(1234567890)`` -> ``jit_decode``: the module line
    names a run by its program and a number."""
    return re.sub(r"\(\d+\)$", "", module_name)


def find_xplane(path):
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError("no .xplane.pb under {}".format(path))
    return found[-1]


def union_seconds(intervals):
    """Total length of the union of (start, end) intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def idle_gaps(intervals, start, end):
    """The (gap_start, gap_end) stretches of [start, end] that no
    interval covers."""
    gaps, reach = [], start
    for s, e in sorted(intervals):
        if s > reach:
            gaps.append((reach, min(s, end)))
        reach = max(reach, e)
        if reach >= end:
            break
    if reach < end:
        gaps.append((reach, end))
    return [(s, e) for s, e in gaps if e > s]


def label_gap(gap, host_spans):
    """The host span that covers most of the gap, or "unattributed"."""
    best, best_overlap = "unattributed", 0.0
    for name, s, e in host_spans:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


class Reduction:
    """One trace, reduced. Times in seconds on the profiler's clock."""

    def __init__(self, device_events, host_spans, device_modules=None):
        # device_events: {plane name: [(name, text, start_s, end_s)]}
        # device_modules: {plane name: [(program, start_s, end_s)]}
        self.device_events = device_events
        self.device_modules = device_modules or {}
        self.host_spans = host_spans
        edges = [t for evs in device_events.values()
                 for _, _, s, e in evs for t in (s, e)]
        edges += [t for _, s, e in host_spans for t in (s, e)]
        self.start, self.end = (min(edges), max(edges)) if edges else (0, 0)
        self.window_s = self.end - self.start
        per_plane = [union_seconds([(s, e) for _, _, s, e in evs])
                     for evs in device_events.values()]
        self.busy_s = sum(per_plane) / len(per_plane) if per_plane else 0.0

    def op_seconds(self):
        """{operation name: seconds}, averaged over the device planes."""
        totals = collections.Counter()
        for evs in self.device_events.values():
            for name, _, s, e in evs:
                short, kind = short_name(name)
                if kind not in CONTAINERS:
                    totals[short] += e - s
        n = max(1, len(self.device_events))
        return {name: t / n for name, t in totals.items()}

    def whole_launches(self, patterns):
        """The runs of a program (events of the module line) that hold
        the operations matching ``patterns`` whole: as many of them as
        most runs of that program hold. -> (whole runs, runs that hold
        fewer, seconds of the matching operations inside the whole
        runs), averaged over the device planes. A matching operation
        outside every run, or inside one that lost others, is not
        counted: its work cannot be told."""
        patterns = [re.compile(p) for p in patterns]
        whole = part = 0
        seconds = 0.0
        for plane, evs in self.device_events.items():
            runs = sorted(self.device_modules.get(plane, ()),
                          key=lambda m: m[1])
            starts = [m[1] for m in runs]
            held = {}                  # run index -> [operations, seconds]
            for _, text, s, e in evs:
                if not any(p.search(text) for p in patterns):
                    continue
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < runs[i][2]:
                    row = held.setdefault(i, [0, 0.0])
                    row[0], row[1] = row[0] + 1, row[1] + e - s
            by_program = collections.defaultdict(collections.Counter)
            for i, (n, _) in held.items():
                by_program[program_of(runs[i][0])][n] += 1
            for i, (n, t) in held.items():
                most = by_program[program_of(runs[i][0])].most_common(1)
                if n == most[0][0]:
                    whole, seconds = whole + 1, seconds + t
                else:
                    part += 1
        n = max(1, len(self.device_events))
        return whole / n, part / n, seconds / n

    def gap_seconds(self):
        """{host span or "unattributed": idle seconds under it}, over
        the first device plane."""
        totals = collections.Counter()
        for evs in list(self.device_events.values())[:1]:
            for gap in idle_gaps([(s, e) for _, _, s, e in evs],
                                 self.start, self.end):
                totals[label_gap(gap, self.host_spans)] += gap[1] - gap[0]
        return dict(totals)

    def breakdown(self):
        top = lambda d: [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:TOP]]
        return {"device_ops": top(self.op_seconds()),
                "idle_gaps": top(self.gap_seconds())}


def reduce_trace(path, span_names):
    import jax
    data = jax.profiler.ProfileData.from_file(find_xplane(path))
    span_names = set(span_names)
    device_events, device_modules, host_spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            events = []
            for line in plane.lines:
                if line.name in MODULE_LINES:
                    device_modules.setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                        for ev in line.events)
                if line.name not in OP_LINES:
                    continue
                for ev in line.events:
                    text = " ".join([ev.name] + [
                        str(v) for _, v in ev.stats if isinstance(v, str)])
                    events.append((ev.name, text, ev.start_ns * 1e-9,
                                   ev.end_ns * 1e-9))
            if events:
                device_events[plane.name] = events
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in span_names:
                        host_spans.append((ev.name, ev.start_ns * 1e-9,
                                           ev.end_ns * 1e-9))
    return Reduction(device_events, host_spans, device_modules)


def describe(path, out=sys.stdout):
    """Planes, lines and the heaviest events of each line, with the
    statistics of one event of each name."""
    import jax
    data = jax.profiler.ProfileData.from_file(find_xplane(path))
    for plane in data.planes:
        print("PLANE {!r}".format(plane.name), file=out)
        for line in plane.lines:
            totals, sample, n = collections.Counter(), {}, 0
            for ev in line.events:
                n += 1
                totals[ev.name] += ev.duration_ns
                sample.setdefault(ev.name, ev)
            print("  LINE {!r} events={}".format(line.name, n), file=out)
            for name, ns in totals.most_common(12):
                ev = sample[name]
                print("    {:10.3f} ms  {}  start_ns={} stats={}".format(
                    ns * 1e-6, name, ev.start_ns,
                    [(k, str(v)[:160]) for k, v in ev.stats][:12]),
                    file=out)


if __name__ == "__main__":
    describe(sys.argv[1])
