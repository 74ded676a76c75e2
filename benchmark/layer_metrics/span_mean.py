"""The mean duration of one of the benchmark's host spans inside the
window. Parameters: ``span``, ``scale`` (1000 for milliseconds)."""
import statistics


def read(run, params):
    durations = run.spans.durations(params["span"], since=run.t_open,
                                    until=run.t_close)
    if not durations:
        return None
    return params["scale"] * statistics.fmean(durations)
