"""The host's own work in one of the program's spans: the mean, over
the events of ``span`` that ended in the traced window, of the span's
duration less the events of ``waits`` inside it (where the host only
waits for the device). Parameters: ``span``, ``waits`` (span names), ``scale`` (1000
for milliseconds)."""
import bisect
import statistics

from . import program_spans


def read(run, params):
    spans = program_spans.load(run)
    window = run.reduction
    outer = [ev for ev in spans.named([params["span"]])
             if window.window_s <= 0 or window.start <= ev[2] <= window.end]
    if not outer:
        return None
    waits = spans.named(params["waits"])
    starts = [ev[1] for ev in waits]
    waited = [0.0]
    for ev in waits:
        waited.append(waited[-1] + ev[2] - ev[1])
    own = []
    for _, start, end, _ in outer:
        lo = bisect.bisect_left(starts, start)
        hi = bisect.bisect_left(starts, end)
        own.append((end - start) - (waited[hi] - waited[lo]))
    return params["scale"] * statistics.fmean(own)
