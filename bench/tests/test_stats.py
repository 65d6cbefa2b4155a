"""Tail latencies in which refused and unfinished requests are misses."""
import math

from harness import stats


def test_percentile_nearest_rank():
    assert stats.percentile(range(1, 101), 95) == 95
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) == math.inf


def test_refused_and_unfinished_requests_are_misses():
    served = [{"due": 0.0, "first": 0.1 * (i + 1)} for i in range(18)]
    refused = {"due": 0.0, "first": None, "accepted": False}
    late = {"due": 0.0, "first": 99.0}        # after the drain's stop
    never = {"due": 0.0, "first": None}
    ttft = stats.ttfts(served + [refused, late, never], t_stop=10.0)
    assert ttft.count(math.inf) == 3
    # 21 requests: the 95th percentile is the 20th, a miss
    assert stats.percentile(ttft, 95) == math.inf
    assert stats.percentile(stats.ttfts(served, 10.0), 95) == \
        served[17]["first"]


def test_gaps_between_consecutive_tokens():
    reqs = [{"times": [1.0, 1.5, 2.5]}, {"times": [3.0]}, {}]
    assert stats.gaps(reqs) == [0.5, 1.0]
