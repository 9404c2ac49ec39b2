"""Every generator is a function of the seed, large seeds included."""
import numpy as np
import pytest

from bench import gen

BIG = 2**31 + 12345


def _same(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("seed", [0, BIG, 2**40 + 3])
def test_feature_rows(seed):
    kw = dict(n=40, features=12, classes=4, modes=3)
    a = gen.feature_rows(gen.seed_key(seed, 5), gen.seed_key(seed, 2), **kw)
    b = gen.feature_rows(gen.seed_key(seed, 5), gen.seed_key(seed, 2), **kw)
    _same(a, b)
    x, y = a
    assert x.shape == (40, 12) and float(x.min()) >= 0 and float(x.max()) <= 1
    assert np.bincount(np.asarray(y)).tolist() == [10, 10, 10, 10]
    c = gen.feature_rows(gen.seed_key(seed + 1, 5), gen.seed_key(seed, 2),
                         **kw)
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))


def test_planted_index_and_rows():
    proj = gen.projection(gen.seed_key(BIG, 1), features=12, dim=64)
    kw = dict(groups=5, columns=200, proto_sigma=1.5, proto_flip=0.08)
    a = gen.planted_index(gen.seed_key(BIG, 3), proj, **kw)
    b = gen.planted_index(gen.seed_key(BIG, 3), proj, **kw)
    _same((a.am, a.assign, a.supers, a.proto_raw),
          (b.am, b.assign, b.supers, b.proto_raw))
    am, assign, supers = map(np.asarray, (a.am, a.assign, a.supers))
    assert set(np.unique(am)) == {-1.0, 1.0}
    for g in range(5):
        votes = am[assign == g].sum(axis=0)
        np.testing.assert_array_equal(supers[g], np.where(votes >= 0, 1, -1))
    r1 = gen.planted_rows(gen.seed_key(BIG, 2), a.proto_raw, n=10,
                          noise_sigma=0.5)
    r2 = gen.planted_rows(gen.seed_key(BIG, 2), a.proto_raw, n=10,
                          noise_sigma=0.5)
    _same([r1], [r2])


def test_arrival_schedule_same_work_other_order():
    kw = dict(seconds=2.0, rate_rps=100.0, rows_min=1, rows_max=8,
              pool_rows=50)
    a = gen.arrival_schedule(BIG, **kw)
    _same(a, gen.arrival_schedule(BIG, **kw))
    b = gen.arrival_schedule(BIG + 1, **kw)
    t, sizes, starts = a
    assert len(t) == 200 and np.all(np.diff(t) > 0)
    assert t[-1] == pytest.approx(2.0)
    assert sizes.min() == 1 and sizes.max() == 8
    assert starts.max() + 8 <= 50
    # Another seed: the same multiset of sizes and gaps, another order.
    assert sorted(sizes) == sorted(b[1])
    np.testing.assert_allclose(np.sort(np.diff(t, prepend=0)),
                               np.sort(np.diff(b[0], prepend=0)))
    assert not np.array_equal(sizes, b[1])


def test_bulk_order():
    a = gen.bulk_order(BIG, 3, 64)
    np.testing.assert_array_equal(a, gen.bulk_order(BIG, 3, 64))
    assert sorted(a) == list(range(64))
    assert not np.array_equal(a, gen.bulk_order(BIG, 4, 64))
