"""Percentile and due-time arithmetic on fixed samples."""
import math

import pytest

from benchmark import stats
from benchmark.runners import serve
from benchmark.spans import SpanRecorder


@pytest.mark.parametrize("values, q, want", [
    (list(range(1, 101)), 95, 95), (list(range(1, 101)), 50, 50),
    ([5.0], 95, 5.0), ([1, 2, 3, 4], 50, 2), ([1, 2, 3, 4], 100, 4),
    ([3, 1, 2], 0, 1), (list(range(1, 21)) , 95, 19),
    ([1.0] * 19 + [math.inf], 95, 1.0), ([1.0] * 18 + [math.inf] * 2, 95,
                                         math.inf)])
def test_percentile_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def _request(due, want, first, last, seen=None):
    r = serve._Request(0, due, 10, want)
    r.first_t, r.last_t = first, last
    r.seen = want if seen is None else seen
    return r


def test_ttft_counts_from_when_the_request_was_due():
    g0 = 1000.0
    sample = [_request(1.0, 11, g0 + 1.25, g0 + 2.25),     # 250 ms, 100 ms
              _request(2.0, 5, g0 + 2.10, g0 + 2.50)]      # 100 ms, 100 ms
    tails = serve.latency_tails(sample, g0, 100)
    assert tails["ttft_ms"] == pytest.approx(250.0)
    assert tails["tpot_ms"] == pytest.approx(100.0)
    assert tails["failed"] == 0


def test_a_failed_request_is_a_miss_not_a_gap():
    g0 = 0.0
    good = [_request(i * 0.1, 3, i * 0.1 + 0.05, i * 0.1 + 0.15)
            for i in range(18)]
    no_first = _request(5.0, 3, None, None, seen=0)
    cut_short = _request(6.0, 3, 6.05, 6.1, seen=2)
    tails = serve.latency_tails(good + [no_first, cut_short], g0, 95)
    assert tails["failed"] == 2
    assert tails["ttft_ms"] == pytest.approx(50.0)   # 19 of 20 have one
    assert tails["tpot_ms"] == math.inf              # 2 of 20 never ended
    assert serve.latency_tails(good + [no_first], g0, 100)["ttft_ms"] == \
        math.inf


def test_single_token_answers_have_no_time_per_token():
    sample = [_request(0.0, 1, 0.1, 0.1), _request(0.0, 3, 0.1, 0.3)]
    assert serve.latency_tails(sample, 0.0, 100)["tpot_ms"] == \
        pytest.approx(100.0)


def test_span_recorder_window_and_names():
    rec = SpanRecorder()
    with rec.span("a"):
        pass
    with rec.span("b"):
        pass
    (_, s, e), = [x for x in rec.spans if x[0] == "a"]
    assert rec.names() == ["a", "b"]
    assert rec.durations("a") == [e - s]
    assert rec.durations("a", since=e + 1) == []
    assert rec.durations("a", until=s) == []
