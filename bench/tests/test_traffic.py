"""The traffic generator: seeded, vectorised, and what each mix says."""
import numpy as np
import pytest

from bench import traffic

MODEL = {"emb_num": 5000, "n_tables": 3, "n_dense": 13}
ZIPF = {"distribution": "zipf", "alpha": 1.1, "drift_every": 64,
        "drift_share": 0.25, "drift_window": 1024}


def open_mix(ids=ZIPF, **load):
    base = {"loop": "open", "arrivals": "poisson", "rate_per_s": 2000.0}
    base.update(load)
    return {"ids": ids, "pooling": 4, "load": base, "slo_ms": 50,
            "buckets": [32]}


def test_same_seed_same_requests():
    a = traffic.generate(open_mix(), MODEL, 2**31 + 17, 2.0)
    b = traffic.generate(open_mix(), MODEL, 2**31 + 17, 2.0)
    c = traffic.generate(open_mix(), MODEL, 5, 2.0)
    for x, y in ((a.ids, b.ids), (a.dense, b.dense), (a.offset_s, b.offset_s)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.ids[:100], c.ids[:100])


def test_poisson_schedule_holds_its_rate():
    r = traffic.generate(open_mix(), MODEL, 3, 20.0)
    assert r.offset_s.min() >= 0 and r.offset_s.max() < 20.0
    assert np.all(np.diff(r.offset_s) >= 0)
    assert len(r) == pytest.approx(40000, rel=0.03)
    assert r.ids.shape == (len(r), 3, 4) and r.dense.shape == (len(r), 13)


@pytest.mark.parametrize("share,factor", [(0.1, 8.0), (0.2, 2.0)])
def test_bursty_schedule_keeps_the_mean_rate(share, factor):
    load = {"arrivals": "bursty", "burst_share": share,
            "burst_factor": factor, "mean_burst_s": 0.25}
    r = traffic.generate(open_mix(ids={"distribution": "random"}, **load),
                         MODEL, 4, 1000.0)
    # bursts are long and rare, so the count over 1000 s still varies by
    # some percent
    assert len(r) == pytest.approx(2_000_000, rel=0.15)
    # in a burst, 50 ms hold about factor times the mean's 100 arrivals
    counts = np.bincount((r.offset_s / 0.05).astype(int))
    assert counts.max() > 0.8 * factor * 100


def test_zipf_is_skewed_and_drifts():
    r = traffic.generate(open_mix(), MODEL, 6, 10.0)
    ids = r.ids[:, 0].ravel()
    assert ids.min() >= 0 and ids.max() < MODEL["emb_num"]
    # the hottest row of the first drift period is hot, and drift moves it
    first = np.bincount(r.ids[:64, 0].ravel(), minlength=5000)
    hot = first.argmax()
    assert first[hot] > 0.05 * 64 * 4
    late = np.bincount(r.ids[-2000:, 0].ravel(), minlength=5000)
    assert late.argmax() != hot or late[hot] < first[hot] * 2000 / 64


def test_zipf_ranks_match_searchsorted():
    cdf = traffic._zipf_cdf(10000, 1.1)
    u = np.random.default_rng(0).random((50, 7))
    np.testing.assert_array_equal(traffic.zipf_ranks(cdf, u),
                                  np.searchsorted(cdf, u))


def test_random_ids_cover_the_table_evenly():
    r = traffic.generate(open_mix(ids={"distribution": "random"}), MODEL, 7,
                         10.0)
    counts = np.bincount(r.ids[:, 1].ravel(), minlength=5000)
    assert counts.max() < 3 * counts.mean()
    assert np.count_nonzero(counts) > 0.99 * 5000


def test_closed_loop_pool():
    mix = {"ids": {"distribution": "random"}, "pooling": 8,
           "load": {"loop": "closed", "outstanding": 16, "pool": 1000},
           "slo_ms": 50, "buckets": [8]}
    r = traffic.generate(mix, MODEL, 8, 1.0)
    assert len(r) == 1000 and r.offset_s is None


def test_unknown_kinds_raise():
    with pytest.raises(ValueError):
        traffic.generate(open_mix(ids={"distribution": "uniform"}), MODEL,
                         1, 1.0)
    with pytest.raises(ValueError):
        traffic.generate(open_mix(arrivals="pareto"), MODEL, 1, 1.0)
