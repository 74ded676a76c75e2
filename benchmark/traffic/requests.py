"""Serving traffic: requests with a due time, a prompt and an answer
length, from a data file of parameters.

``arrivals``: ``{"process": "poisson", "rate_per_s": r}`` (open loop:
exponential gaps) or ``{"process": "backlog", "queued": n,
"sized_at_tokens_per_s": r}`` (all due at time 0, before the window;
``r`` is the cell's rate when ``n`` was chosen, which the generator
does not read: a data test holds ``n`` to four times the tokens that
rate drains). ``prompt_tokens`` / ``output_tokens``:
``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
``{"dist": "uniform", "min", "max"}``.

This is a FIXED TRACE, replayed: every seed sees the SAME cycle of
requests, entered at another point. One cycle is as long as the
measured window: its gaps and lengths are read off their distributions
at evenly spaced quantiles and put into one fixed order (``ORDER_SEED``
below); the traffic is that cycle repeated, and ``--seed`` chooses
where in the cycle it starts and draws the token ids. So every
window holds exactly the cycle's requests, preceded by the same
history, and two seeds differ by phase and content, not by luck of the
draw: a tail over a few hundred requests is otherwise decided by which
of them happened to arrive together (PERF.md section 6, PR 25). The
tails read on it describe this trace, not every draw of the process.
"""
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()
ORDER_SEED = 20260927


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def _lengths(spec, n, rng):
    u = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(q)) for q in u])
        values = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        values = spec["min"] + u * (spec["max"] + 1 - spec["min"])
    else:
        raise ValueError("unknown length distribution {!r}".format(
            spec["dist"]))
    values = np.clip(np.floor(values), spec["min"], spec["max"])
    return rng.permutation(values.astype(np.int64))


def cycle(params, cycle_s):
    """The mix's one cycle: ``(gaps_s, prompt_lens, output_lens)`` in
    the fixed order every seed shares."""
    order = np.random.default_rng(ORDER_SEED)
    arrivals = params["arrivals"]
    if arrivals["process"] == "poisson":
        n = max(1, round(arrivals["rate_per_s"] * cycle_s))
        gaps = order.permutation(
            -np.log1p(-_quantiles(n)) / arrivals["rate_per_s"])
    elif arrivals["process"] == "backlog":
        n = int(arrivals["queued"])
        gaps = np.zeros(n)
    else:
        raise ValueError("unknown arrival process {!r}".format(
            arrivals["process"]))
    return (gaps, _lengths(params["prompt_tokens"], n, order),
            _lengths(params["output_tokens"], n, order))


def generate(params, seed, duration_s, vocab, cycle_s):
    """``(due_s, prompts, output_lens)``: due times in seconds from the
    generator's start, ascending, for at least ``duration_s``; prompts
    as int32 arrays. ``cycle_s`` is the measured window's length."""
    rng = np.random.default_rng([seed, 2])
    gaps, prompt_lens, output_lens = cycle(params, cycle_s)
    n = len(gaps)
    period = float(gaps.sum())
    repeats = 1 if period == 0 else int(duration_s // period) + 2
    index = (int(rng.integers(n)) + np.arange(repeats * n)) % n
    if period:
        index = index[:int(np.searchsorted(
            np.cumsum(gaps[index]), duration_s)) + 1]
    due = np.cumsum(gaps[index])
    prompt_lens, output_lens = prompt_lens[index], output_lens[index]
    tokens = rng.integers(0, vocab, int(prompt_lens.sum()), dtype=np.int32)
    prompts = np.split(tokens, np.cumsum(prompt_lens)[:-1])
    return due, prompts, output_lens
