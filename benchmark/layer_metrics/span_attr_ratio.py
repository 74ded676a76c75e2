"""One attribute of the program's own spans over another, or over a
count of spans, for the events that ended in the traced window.
Parameters: ``numerator`` and ``denominator``, each ``{"spans": [names],
"attr": name}`` (the attribute summed over those spans' events that
carry it; a denominator without ``attr`` COUNTS the events of its spans
that carry the numerator's attribute), and ``scale`` (100 for a share in
percent). A program whose spans carry no such attribute (one from before
it) gives nothing to read."""
from . import program_spans


def _values(events, spans, attr):
    return [int(ev[3][attr]) for ev in events
            if ev[0] in spans and attr in ev[3]]


def read(run, params):
    window = run.reduction
    events = [ev for ev in program_spans.load(run).events
              if window.window_s <= 0 or window.start <= ev[2] <= window.end]
    num, den = params["numerator"], params["denominator"]
    top = _values(events, num["spans"], num["attr"])
    below = _values(events, den["spans"], den.get("attr", num["attr"]))
    bottom = sum(below) if "attr" in den else len(below)
    if not top or not bottom:
        return None
    return params["scale"] * sum(top) / bottom
