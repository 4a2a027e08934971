"""The traffic generator: deterministic per seed, the same work for every
seed, and the stated distributions (grid rounding, clipping, gamma CV)."""
from __future__ import annotations

import numpy as np
import pytest

from bench.core import spec, traffic

#: an open-loop mix with bursty arrivals, as a traffic file would state it
OPEN = {"order_seed": 20261016,
        "arrival": {"kind": "gamma", "cv": 2.0, "rate": 1.0}, "warm_s": 10.0,
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 32,
                   "max": 2048, "grid": [32, 64, 128, 256, 512, 1024, 2048]},
        "output": {"dist": "lognormal", "median": 64, "sigma": 0.8, "min": 16,
                   "max": 512}}
MIXES = ("agent_decode", "open")


def _mix(name):
    import json
    if name == "open":
        return OPEN
    with open(spec.BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.generate(_mix(name), 2**33 + 5, 10, 50000)
    b = traffic.generate(_mix(name), 2**33 + 5, 10, 50000)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.max_new == y.max_new and x.due == y.due
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_same_schedule_other_ids(name):
    a = traffic.generate(_mix(name), 1, 10, 50000)
    b = traffic.generate(_mix(name), 2, 10, 50000)
    assert [(len(r.prompt), r.max_new, r.due) for r in a] == \
        [(len(r.prompt), r.max_new, r.due) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_order_seed_orders_the_same_multiset():
    mix = _mix("open")
    a = traffic.generate(mix, 1, 10, 50000)
    b = traffic.generate(dict(mix, order_seed=mix["order_seed"] + 1), 1, 10, 50000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    ga, gb = np.diff([0.0] + [r.due for r in a]), np.diff([0.0] + [r.due for r in b])
    np.testing.assert_allclose(np.sort(ga), np.sort(gb), atol=1e-9)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_on_grid_and_clipped(name):
    mix = _mix(name)
    rs = traffic.generate(mix, 7, 10, 50000)
    grid = set(mix["prompt"]["grid"])
    assert {len(r.prompt) for r in rs} <= grid
    outs = np.array([r.max_new for r in rs])
    assert outs.min() >= mix["output"]["min"]
    assert outs.max() <= mix["output"]["max"]
    ids = np.concatenate([r.prompt for r in rs])
    assert ids.min() >= 0 and ids.max() < 50000


def test_lengths_follow_the_lognormal():
    spec_ = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 1,
             "max": 10**9}
    x = traffic.lengths(spec_, 4001)
    assert abs(np.median(x) - 512) <= 1
    assert np.std(np.log(x)) == pytest.approx(0.8, rel=0.05)


def test_grid_rounds_up():
    spec_ = {"dist": "lognormal", "median": 300, "sigma": 0.5, "min": 100,
             "max": 1000, "grid": [128, 256, 512, 1024]}
    x = traffic.lengths(spec_, 999)
    raw = np.clip(np.ceil(np.exp(np.log(300) + 0.5 * np.sqrt(2) * __import__(
        "scipy").special.erfinv(2 * traffic.quantile_points(999) - 1))), 100, 1000)
    assert (x >= raw).all()
    g = np.array([128, 256, 512, 1024])
    assert (g[np.searchsorted(g, raw)] == x).all()


def test_gamma_gaps_have_the_stated_cv_and_rate():
    g = traffic.gaps({"kind": "gamma", "rate": 4.0, "cv": 2.0}, 5000)
    assert g.mean() == pytest.approx(0.25, rel=1e-9)
    assert g.std() / g.mean() == pytest.approx(2.0, rel=0.1)
    p = traffic.gaps({"kind": "poisson", "rate": 4.0}, 5000)
    assert p.std() / p.mean() == pytest.approx(1.0, rel=0.05)


def test_offline_queue_is_due_at_once():
    rs = traffic.generate(_mix("agent_decode"), 3, 10, 1000)
    assert len(rs) == _mix("agent_decode")["requests"]
    assert all(r.due == 0.0 for r in rs)


def test_open_loop_covers_warmup_and_window():
    mix = _mix("open")
    rs = traffic.generate(mix, 3, 10, 1000)
    span = mix["warm_s"] + 10
    assert rs[-1].due > span
