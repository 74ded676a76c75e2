"""The prefill chunks of a traced window, from the program's own
``sched.prefill.chunk`` spans (docs/telemetry.md): ``tokens`` real and
``padded`` bucket tokens each. Not a reader: the readers that price
prefill share it. A program whose chunk spans carry no ``padded``
attribute (one from before the attribute) gives nothing to read."""
from . import program_spans


def chunks(run, span, real="tokens", padded="padded"):
    """[(real tokens, padded tokens)] of the ``span`` events that ended
    in the traced window, or None where there are none to read."""
    window = run.reduction
    found = [(int(ev[3][real]), int(ev[3][padded]))
             for ev in program_spans.load(run).named([span])
             if real in ev[3] and padded in ev[3] and
             (window.window_s <= 0 or window.start <= ev[2] <= window.end)]
    return found or None
