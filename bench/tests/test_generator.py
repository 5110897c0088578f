"""The traffic generator: pure in the tick, periodic, and drawn from
``--seed`` alone, or, under a mix's ``pool_seed``, ordered by it."""
import numpy as np

import tiny
from traffic.generator import Traffic

BIG_SEED = 2**31 + 12345


def traffic(name, seed, batch=4096):
    return Traffic(tiny.load("traffic", name + ".json"), seed, batch)


def test_same_seed_same_events_and_pure_in_the_tick():
    a, b = traffic("hotspot.rate", BIG_SEED), traffic("hotspot.rate", BIG_SEED)
    for t in (0, 5, 30, 64, 1000):
        assert np.array_equal(a.points(t), b.points(t))
    p = a.points(30)
    a.points(31)
    assert np.array_equal(a.points(30), p)
    assert not np.array_equal(a.points(30), traffic("hotspot.rate", 1)
                              .points(30))


def test_timeline_is_periodic_with_fixed_sizes():
    a = traffic("hotspot.rate", 3)
    per = a.period
    for t in range(per):
        assert a.hotspot_shares(t) == a.hotspot_shares(t + 7 * per)
        assert len(a.points(t)) == a.batch
    assert max(a.hotspot_shares(t)[0] for t in range(per)) > 0.3
    assert a.hotspot_shares(0) == [0.0]


def test_mixture_is_the_deployment_not_the_seed():
    a, b = traffic("hotspot.rate", 3), traffic("hotspot.rate", 4)
    assert np.array_equal(a.weights, b.weights)
    assert np.array_equal(a.centers, b.centers)


def test_hashtags_carry_raw_term_ids_and_hot_terms():
    a = traffic("hashtags.max", BIG_SEED, batch=2000)
    t = 30                                  # both hot terms trending
    pts, terms = a.points(t), a.terms(t)
    assert terms.shape == (2000, 3) and terms.dtype == np.int64
    assert terms.min() >= 0 and terms.max() < a.vocab
    assert np.array_equal(terms, a.terms(t))
    hot = (terms[:, 0] == 0).sum() + (terms[:, 0] == 1).sum()
    assert hot > 0.3 * 2000
    assert pts.dtype == np.float32 and pts.min() >= 0 and pts.max() < 1


def _rows(a):
    return a[np.lexsort(a.T[::-1])]


def test_pool_seed_keeps_the_work_and_the_seed_draws_the_order():
    a, b = traffic("hotspot.max", BIG_SEED), traffic("hotspot.max", 7)
    for t in (0, 5, 30, 64, 1000):
        pa, pb = a.points(t), b.points(t)
        assert not np.array_equal(pa, pb)
        assert np.array_equal(_rows(pa), _rows(pb))
        assert np.array_equal(pa, traffic("hotspot.max", BIG_SEED).points(t))
    qa, qb = a.queries(3000, 0.0016), b.queries(3000, 0.0016)
    assert not np.array_equal(qa, qb)
    assert np.array_equal(_rows(qa), _rows(qb))
