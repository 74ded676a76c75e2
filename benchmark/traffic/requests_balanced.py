"""A backlog of ``requests``'s requests in a BALANCED fixed order.

The same data file, the same lengths (``requests.cycle``'s, value for
value: each distribution read at evenly spaced quantiles) and the same
use of ``--seed`` (the phase of the one fixed cycle, and the token
ids). Only the cycle's fixed order differs. ``requests`` shuffles the
whole cycle once; a run that serves a quarter of a long cycle then
serves whichever lengths its phase holds, and where lengths spread
widely that is another amount of work for every seed: at lognormal
sigma 0.7 / 0.6 a window of 330 requests out of 3,000 read
``serve_tokens_per_s`` with a standard deviation of 2.5% over the
phases (PERF.md section 6, PR 38). Here the order is shuffled in
blocks: each sorted distribution is cut into ``BLOCK`` strata, and
every aligned run of ``BLOCK`` requests holds one prompt length from
each stratum of the prompts and one answer length from each stratum of
the answers, in a shuffled order and paired by chance. Any stretch of
the cycle much longer than a block then holds the whole mix, so every
seed is given the same work; what a seed still chooses is where in a
block the run starts and which lengths meet in a slot.

A backlog only: arrivals with gaps keep ``requests``, whose cycle is
as long as the window.
"""
import numpy as np

from . import requests

# a tenth of what the slots hold and of what a window admits at 320
# slots and 330 requests a window, so that both hold whole blocks; a
# model of the scheduler reads the same spread at 20 to 60
BLOCK = 30


def _balanced(values, rng):
    """``values`` reordered: aligned runs of ``BLOCK``, each with one
    value from each of ``BLOCK`` equal strata of the sorted values."""
    strata = np.sort(values).reshape(BLOCK, -1)
    # row s: which run takes which of stratum s's values
    runs = rng.permuted(strata, axis=1).T
    return rng.permuted(runs, axis=1).reshape(-1)


def cycle(params, cycle_s):
    """``requests.cycle``'s lengths in the balanced order every seed
    shares: ``(gaps_s, prompt_lens, output_lens)``."""
    arrivals = params["arrivals"]
    if arrivals["process"] != "backlog":
        raise ValueError(
            "requests_balanced orders a backlog only, not {!r} arrivals"
            .format(arrivals["process"]))
    gaps, prompt_lens, output_lens = requests.cycle(params, cycle_s)
    if len(gaps) % BLOCK:
        raise ValueError("arrivals.queued {} is no multiple of {}"
                         .format(len(gaps), BLOCK))
    order = np.random.default_rng([requests.ORDER_SEED, BLOCK])
    return (gaps, _balanced(prompt_lens, order),
            _balanced(output_lens, order))


def generate(params, seed, duration_s, vocab, cycle_s):
    """``(due_s, prompts, output_lens)`` as ``requests.generate`` gives
    them for a backlog: all due at 0, one cycle, entered where
    ``--seed`` says."""
    rng = np.random.default_rng([seed, 2])
    gaps, prompt_lens, output_lens = cycle(params, cycle_s)
    n = len(gaps)
    index = (int(rng.integers(n)) + np.arange(n)) % n
    prompt_lens, output_lens = prompt_lens[index], output_lens[index]
    tokens = rng.integers(0, vocab, int(prompt_lens.sum()), dtype=np.int32)
    prompts = np.split(tokens, np.cumsum(prompt_lens)[:-1])
    return gaps, prompts, output_lens
