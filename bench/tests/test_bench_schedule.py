"""The open loop's arrival schedule and the seeded sample of items."""
import numpy as np
import pytest

from bench import loops


def test_arrivals_repeat_for_a_seed_and_differ_across_seeds():
    a = loops.arrivals(1000.0, 2.0, 2 ** 31 + 9)
    assert a == loops.arrivals(1000.0, 2.0, 2 ** 31 + 9)
    assert a != loops.arrivals(1000.0, 2.0, 2 ** 31 + 10)
    assert all(0 <= x < 2.0 for x in a) and a == sorted(a)
    assert len(a) == pytest.approx(2000, rel=0.1)


def test_arrival_gaps_are_exponential():
    a = np.diff(loops.arrivals(500.0, 20.0, 7))
    assert np.mean(a) == pytest.approx(1 / 500.0, rel=0.05)
    # an exponential's standard deviation equals its mean
    assert np.std(a) == pytest.approx(np.mean(a), rel=0.05)


def test_reservoir_sample_is_seeded():
    def sample(seed):
        r = loops.Reservoir(4, seed)
        for i in range(100):
            r.offer(lambda i=i: i)
        return sorted(r.items)
    assert sample(1) == sample(1)
    assert len(sample(1)) == 4 and sample(1) != sample(2)


def test_item_samples_one_per_microbatch(p2m_vgg):
    """An item of two microbatches gives two samples: each its own frames,
    outputs and rows of the served result; padding rows are not real."""
    idx = np.arange(10, 18)
    parts = [{"theta": 1.0, "channel_rates": 0, "labels": 0},
             {"theta": 2.0, "channel_rates": 1, "labels": 1}]
    got = p2m_vgg.item_samples(5, idx, 6, {"probs": "served"}, parts)
    assert [(s.index, s.part, s.parts, s.row0, s.real) for s in got] == [
        (5, 0, 2, 0, 4), (5, 1, 2, 4, 2)]
    assert list(got[1].frames) == [14, 15, 16, 17]
    assert got[1].out == {"probs": "served", "theta": 2.0,
                          "channel_rates": 1}
