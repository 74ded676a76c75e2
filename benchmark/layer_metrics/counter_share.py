"""One of the run's counters over another, in percent. Parameters:
``numerator``, ``denominator`` (names in ``run.counters``)."""


def read(run, params):
    num = run.counters.get(params["numerator"])
    den = run.counters.get(params["denominator"])
    if num is None or not den:
        return None
    return 100.0 * num / den
