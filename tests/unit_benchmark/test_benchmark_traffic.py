"""Traffic is a function of the seed and of the mix's data file."""
import numpy as np
import pytest

from benchmark import manifest
from benchmark.traffic import requests, token_batches

MANIFEST = manifest.load_manifest()
WORKLOADS = {c["name"]: manifest.load_workload(c["name"])
             for c in MANIFEST["workloads"]}
MIXES = {n: w["traffic"] for n, w in WORKLOADS.items()}
SERVING = [n for n, t in MIXES.items() if t["generator"] == "requests"]
BACKLOGS = [n for n in SERVING
            if MIXES[n]["arrivals"]["process"] == "backlog"]
BIG_SEED = 2 ** 31 + 12345


def _same(a, b):
    return (np.array_equal(a[0], b[0]) and
            all(np.array_equal(x, y) for x, y in zip(a[1], b[1])) and
            np.array_equal(a[2], b[2]))


@pytest.mark.parametrize("cell", SERVING)
def test_requests_identical_for_one_seed_and_not_for_two(cell):
    a = requests.generate(MIXES[cell], BIG_SEED, 40.0, 50304, cycle_s=30.0)
    b = requests.generate(MIXES[cell], BIG_SEED, 40.0, 50304, cycle_s=30.0)
    c = requests.generate(MIXES[cell], BIG_SEED + 1, 40.0, 50304, cycle_s=30.0)
    assert _same(a, b) and not _same(a, c)


def _in_window(traffic, start, end):
    due, prompts, outputs = traffic
    return [(len(p), int(o)) for d, p, o in zip(due, prompts, outputs)
            if start <= d < end]


@pytest.mark.parametrize("cell", SERVING)
def test_every_seed_sees_the_same_cycle_from_another_point(cell):
    mix = MIXES[cell]
    a = requests.generate(mix, 1, 75.0, 50304, cycle_s=30.0)
    b = requests.generate(mix, 2, 75.0, 50304, cycle_s=30.0)
    gaps, prompt_lens, output_lens = requests.cycle(mix, 30.0)
    n = len(gaps)
    # the same requests in the same cyclic order, entered elsewhere
    seq_a = list(zip(map(len, a[1]), a[2]))[:n]
    seq_b = list(zip(map(len, b[1]), b[2]))[:n]
    assert seq_a != seq_b
    doubled = seq_a + seq_a
    assert any(doubled[k:k + n] == seq_b for k in range(n))
    assert sorted(seq_a) == sorted(zip(prompt_lens, output_lens))
    if gaps.sum() > 0:
        # any window of one cycle's length, after any lead-in, holds
        # exactly the cycle's requests
        assert gaps.sum() == pytest.approx(30.0, rel=0.01)
        for traffic, lead in ((a, 8.0), (b, 8.0), (a, 11.3)):
            window = _in_window(traffic, lead, lead + gaps.sum())
            assert sorted(window) == sorted(zip(prompt_lens, output_lens))
        assert a[0][-1] >= 75.0 and b[0][-1] >= 75.0


@pytest.mark.parametrize("cell", SERVING)
def test_lengths_stay_inside_the_mix_and_the_model(cell):
    mix = MIXES[cell]
    due, prompts, outputs = requests.generate(mix, 3, 40.0, 50304, cycle_s=30.0)
    lens = np.array(list(map(len, prompts)))
    assert np.all(np.diff(due) >= 0)
    assert lens.min() >= mix["prompt_tokens"]["min"]
    assert lens.max() <= mix["prompt_tokens"]["max"]
    assert outputs.min() >= mix["output_tokens"]["min"]
    assert outputs.max() <= mix["output_tokens"]["max"]
    assert (lens + outputs).max() < 1024      # the model's positions
    assert all(p.min() >= 0 and p.max() < 50304 for p in prompts[:50])


@pytest.mark.parametrize("cell", BACKLOGS)
def test_a_backlog_outlasts_a_program_four_times_as_fast(cell):
    """A backlog has to stay a backlog under the gains it is there to
    measure: its tokens, served from the start of the lead-in to the
    window's close, last at four times the rate the cell read when it
    was sized (``sized_at_tokens_per_s``, beside ``queued``). When the
    cell's level nears that, raise ``queued`` first: the runner refuses
    a run whose queue is empty at the close."""
    workload, arrivals = WORKLOADS[cell], MIXES[cell]["arrivals"]
    _, prompt_lens, output_lens = requests.cycle(MIXES[cell], 1.0)
    assert len(prompt_lens) == arrivals["queued"]
    tokens = int(prompt_lens.sum() + output_lens.sum())
    lasts_below = tokens / (workload["lead_s"] + MANIFEST["run_seconds"])
    assert lasts_below >= 4 * arrivals["sized_at_tokens_per_s"]


def test_poisson_rate_and_lognormal_median_are_what_the_file_says():
    mix = {"arrivals": {"process": "poisson", "rate_per_s": 5.0},
           "prompt_tokens": {"dist": "lognormal", "median": 128,
                             "sigma": 0.7, "min": 16, "max": 512},
           "output_tokens": {"dist": "uniform", "min": 16, "max": 64}}
    due, prompts, outputs = requests.generate(mix, 9, 199.0, 1000, cycle_s=200.0)
    assert len(due) == pytest.approx(1000, abs=5)
    assert due[-1] == pytest.approx(200.0, rel=0.02)
    assert np.median(list(map(len, prompts))) == pytest.approx(128, abs=2)
    assert outputs.mean() == pytest.approx(40, abs=1)


def test_unknown_distribution_or_process_raises():
    mix = dict(MIXES[SERVING[0]])
    with pytest.raises(ValueError):
        requests.generate(dict(mix, arrivals={"process": "bursts"}), 1,
                          10.0, 100, cycle_s=10.0)
    with pytest.raises(ValueError):
        requests.generate(dict(mix, prompt_tokens={"dist": "zipf"}), 1,
                          10.0, 100, cycle_s=10.0)


def test_token_batches_seeded_fresh_every_step():
    params = {"seq_len": 16}
    a = token_batches.batches(params, BIG_SEED, 4, 100)
    b = token_batches.batches(params, BIG_SEED, 4, 100)
    c = token_batches.batches(params, BIG_SEED + 1, 4, 100)
    a0, a1, b0, c0 = next(a), next(a), next(b), next(c)
    assert a0[0].shape == (1, 4, 16) and a0[0].dtype == np.int32
    assert a0[1] is a0[0]                     # labels = ids
    assert np.array_equal(a0[0], b0[0])
    assert not np.array_equal(a0[0], a1[0])
    assert not np.array_equal(a0[0], c0[0])
