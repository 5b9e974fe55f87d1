"""The open-loop generator and the seeded inputs: the same seed gives the
same schedule, queries and collection; another seed gives others, with
the same amount of work."""
import numpy as np
import pytest

from bench.lib import data, traffic

MIX = {"rate_per_s": 8.0, "k": 100, "b": 64, "more_k": 100, "think_mean_s": 1.0,
       "warmup_sessions": 4}
CFG = {"n_items": 300, "dim": 16, "storage_dtype": "float16", "data_seed": 0,
       "generator": {"n_clusters": 8, "spread": 0.15}}
BIG = 2**31 + 12345  # the driver's seeds exceed 32 signed bits


def same(a, b):
    return all(np.array_equal(getattr(a, f), getattr(b, f)) for f in ("due_s", "query", "think_s", "warmup"))


def test_same_seed_same_schedule():
    assert same(traffic.schedule(MIX, BIG, 30), traffic.schedule(MIX, BIG, 30))


def test_other_seed_other_order_same_work():
    a, b = traffic.schedule(MIX, BIG, 30), traffic.schedule(MIX, BIG + 1, 30)
    assert not same(a, b)
    assert len(a.due_s) == len(b.due_s) == 240
    assert sorted(a.query) == sorted(b.query) and not np.array_equal(a.query, b.query)
    # the same gaps and think times, in another order
    assert np.diff(a.due_s).mean() == pytest.approx(np.diff(b.due_s).mean(), rel=0.05)
    assert np.allclose(np.sort(a.think_s), np.sort(b.think_s))
    assert not np.array_equal(a.think_s, b.think_s)


def test_arrivals_fill_the_window_and_queries_skip_the_warm_up():
    s = traffic.schedule(MIX, 7, 30)
    assert s.due_s[0] == 0 and s.due_s[-1] < 30 and np.all(np.diff(s.due_s) > 0)
    assert s.think_s.mean() == pytest.approx(1.0, rel=0.05)
    # each query once, none of the warm-up's
    assert sorted(s.query) == list(range(4, 4 + 240)) and list(s.warmup) == [0, 1, 2, 3]
    assert s.n_queries == 244


def test_exponential_quantiles():
    q = traffic.exp_quantiles(1000, 2.0)
    assert q.mean() == pytest.approx(2.0, rel=0.01) and np.all(np.diff(q) > 0)


def test_collection_and_queries_are_the_configurations():
    x = data.collection(CFG)
    assert x.shape == (300, 16) and x.dtype == np.float16
    assert np.array_equal(x, data.collection(CFG))
    assert not np.array_equal(x, data.collection(dict(CFG, data_seed=1)))
    q = data.queries(CFG, 5)
    assert q.dtype == np.float32 and np.array_equal(q, data.queries(CFG, 5))
    assert not np.array_equal(q, data.queries(dict(CFG, data_seed=1), 5))
    # held out: no query is a row of the collection
    assert not np.isin(q.astype(np.float16).view(np.uint16), x.view(np.uint16)).all(axis=1).any()
