"""A kernel's share of the device's busy time in the traced window:
the device time of the events matching ``patterns`` over the union of
all the device's operation intervals (``trace.Reduction.busy_s``), in
percent, averaged over the device planes. Parameters: ``patterns``.
A trace with no such event gives nothing to read."""
import re


def read(run, params):
    red = run.reduction
    if not red.device_events or red.busy_s <= 0:
        return None
    patterns = [re.compile(p) for p in params["patterns"]]
    seconds = sum(e - s for evs in red.device_events.values()
                  for _, text, s, e in evs
                  if any(p.search(text) for p in patterns))
    if not seconds:
        return None
    return 100.0 * seconds / len(red.device_events) / red.busy_s
