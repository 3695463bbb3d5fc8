"""The traffic generator: one seed gives the same data; every seed does
the same set of work in another order."""

import itertools

import jax
import numpy as np

import generator

MIX = {"loop": "closed", "outstanding": 4, "pool": 6, "n": [100, 200],
       "d": [8, 16], "nu": [0.05, 0.5], "spectrum_rate": [0.9, 1.0]}


def test_pool_is_fixed_by_the_mix():
    a, b = generator.pool(MIX), generator.pool(MIX)
    assert a == b
    assert all(100 <= p.n <= 200 and 8 <= p.d <= 16 for p in a)
    assert all(0.05 <= p.nu <= 0.5 and 0.9 <= p.rate <= 1.0 for p in a)
    base = generator.pool({"pool": 2}, base={"n": 64, "d": 8, "nu": 0.01,
                                             "spectrum_rate": 0.95})
    assert {(p.n, p.d, p.nu, p.rate) for p in base} == {(64, 8, 0.01, 0.95)}


def test_order_shuffles_each_pass_by_seed():
    o1 = list(itertools.islice(generator.order(MIX, 5), 18))
    o2 = list(itertools.islice(generator.order(MIX, 5), 18))
    o3 = list(itertools.islice(generator.order(MIX, 6), 18))
    assert o1 == o2 and o1 != o3
    for k in range(3):
        assert sorted(o1[6 * k: 6 * k + 6]) == list(range(6))
    cyc = list(itertools.islice(generator.order({"pool": 4,
                                                 "order": "cyclic"}, 9), 9))
    assert cyc == [0, 1, 2, 3, 0, 1, 2, 3, 0]


def test_arrivals_have_the_same_gaps_for_every_seed():
    mix = {"rate_per_s": 50.0}
    a, b = generator.arrivals(mix, 1, 2.0), generator.arrivals(mix, 2, 2.0)
    assert len(a) == len(b) == 100
    assert np.allclose(sorted(np.diff(a, prepend=0)),
                       sorted(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)
    assert abs(a[-1] - 2.0) < 0.1


def test_data_repeats_for_a_seed_and_keeps_wide_seeds_apart():
    probs = generator.pool(MIX)
    big = 2**33 + 5
    d1 = generator.make(probs, big)
    d2 = generator.make(probs, big)
    d3 = generator.make(probs, 5)
    for (A1, y1), (A2, _), (A3, _), p in zip(d1, d2, d3, probs):
        assert A1.shape == (p.n, p.d) and y1.shape == (p.n,)
        assert np.array_equal(np.asarray(A1), np.asarray(A2))
        assert not np.array_equal(np.asarray(A1), np.asarray(A3))
    # the spectrum is shaped as asked: σ_j = rate^j
    p = probs[0]
    s = np.linalg.svd(np.asarray(d1[0][0], np.float64), compute_uv=False)
    want = p.rate ** np.arange(p.d)
    assert np.allclose(s, want, rtol=0.6)
    assert jax.devices()[0].platform == "cpu"
