"""Share of the traced window in which the device ran nothing while
the innermost open span of the program was one of ``innermost``: an
idle gap is cut at every span edge it crosses and each piece booked
under the span that was innermost there, so a name stands for its self
time (``program_spans``). In percent of the window that
``device_idle_share`` divides by. Parameters: ``innermost`` (span
names)."""
from . import program_spans


def read(run, params):
    spans = program_spans.load(run)
    if spans.idle is None or run.reduction.window_s <= 0:
        return None
    seconds = sum(spans.idle.get(name, 0.0)
                  for name in params["innermost"])
    return 100.0 * seconds / run.reduction.window_s
