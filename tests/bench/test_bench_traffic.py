"""Arrivals, images, stragglers and weights are fixed by the seed."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "bench"))

from harness import reference, traffic  # noqa: E402

BIG = 2 ** 33 + 5  # seeds past 32 bits must not collide with their low bits


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_bench_schedule_is_determined_by_the_seed(seed):
    a = traffic.poisson_gaps(300.0, 20.0, seed, 4)
    b = traffic.poisson_gaps(300.0, 20.0, seed, 4)
    np.testing.assert_array_equal(a, b)
    assert len(a) == 6000
    assert a.sum() == pytest.approx(20.0)
    offs = traffic.arrival_offsets(a)
    assert offs[0] == 0.0 and offs[-1] < 20.0 and np.all(np.diff(offs) > 0)


def test_bench_every_seed_gets_the_same_gaps_in_another_order():
    a = traffic.poisson_gaps(300.0, 20.0, 1, 4)
    b = traffic.poisson_gaps(300.0, 20.0, BIG, 4)
    c = traffic.poisson_gaps(300.0, 20.0, BIG - 2 ** 33, 4)
    np.testing.assert_allclose(np.sort(a), np.sort(b))
    assert not np.array_equal(a, b) and not np.array_equal(b, c)
    # an exponential's mid-quantiles: mean 1 / rate, coefficient of
    # variation near 1
    assert a.mean() == pytest.approx(1 / 300.0)
    assert a.std() / a.mean() == pytest.approx(1.0, abs=0.1)


def test_bench_stragglers_are_determined_by_the_seed():
    spec = {"stragglers": {"count": 2, "delay_s": 0.05}}
    d = traffic.straggler_delays(8, spec, BIG)
    np.testing.assert_array_equal(d, traffic.straggler_delays(8, spec, BIG))
    assert sorted(d) == [0.0] * 6 + [0.05, 0.05]
    sets = {tuple(np.flatnonzero(traffic.straggler_delays(8, spec, s)))
            for s in range(20)}
    assert len(sets) > 5
    none = traffic.straggler_delays(8, {"stragglers": {"count": 0}}, 1)
    assert not none.any()


def test_bench_weights_and_images_are_determined_by_the_seed():
    cfg = {"dtype": "float32", "input_hw": 8, "layers": [
        {"name": "c1", "in_ch": 2, "out_ch": 4, "kernel": 3}]}
    w1 = reference.make_weights(cfg, BIG)["c1"]
    w2 = reference.make_weights(cfg, BIG)["c1"]
    w3 = reference.make_weights(cfg, BIG - 2 ** 33)["c1"]
    np.testing.assert_array_equal(w1, w2)
    assert not np.array_equal(w1, w3)
    assert w1.shape == (4, 2, 3, 3)
    x1 = reference.make_images(cfg, 3, BIG)
    np.testing.assert_array_equal(x1, reference.make_images(cfg, 3, BIG))
    assert x1.shape == (3, 2, 8, 8)
