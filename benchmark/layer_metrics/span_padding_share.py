"""Share of the window's prefill work that was done on padding: 1 -
real tokens over padded tokens of the chunk spans, in percent.
Parameters: ``span``, ``real``, ``padded`` (attribute names)."""
from . import span_chunks


def read(run, params):
    found = span_chunks.chunks(run, params["span"], params["real"],
                               params["padded"])
    if not found:
        return None
    return 100.0 * (1.0 - sum(r for r, _ in found) /
                    sum(p for _, p in found))
