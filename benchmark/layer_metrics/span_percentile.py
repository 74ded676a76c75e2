"""A percentile of the durations of one of the benchmark's host spans
inside the window. Parameters: ``span``, ``percentile``, ``scale``
(1000 for milliseconds)."""
from .. import stats


def read(run, params):
    durations = run.spans.durations(params["span"], since=run.t_open,
                                    until=run.t_close)
    if not durations:
        return None
    return params["scale"] * stats.percentile(durations,
                                              params["percentile"])
