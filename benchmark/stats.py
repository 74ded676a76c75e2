"""The percentile the benchmark's tails are read with."""
import math


def percentile(values, q):
    """The q-th percentile (0-100) by nearest rank on the sorted
    sample: the smallest value with at least q% of the sample at or
    below it. A missing observation is passed as ``math.inf`` and so
    sits in the tail it failed to leave."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]
