"""Seeded traffic: the same seed repeats, other seeds offer the same work."""
import collections

import numpy as np
import pytest

from perfbench import harness, traffic as tr
from perfbench.tests import tiny


def _mix(name):
    return harness.load_json(f"{harness.HERE}/traffic/{name}.json")


def test_open_schedule_repeats_for_a_seed():
    mix = _mix("mixed-burst")
    a = tr.open_schedule(mix, 40.0, 2**31 + 5)
    b = tr.open_schedule(mix, 40.0, 2**31 + 5)
    assert [(d.t, d.uav) for d in a] == [(d.t, d.uav) for d in b]
    c = tr.open_schedule(mix, 40.0, 2**31 + 6)
    assert [(d.t, d.uav) for d in a] != [(d.t, d.uav) for d in c]


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 2**33 + 1])
def test_open_schedule_same_work_every_seed(seed):
    """Every seed sends each UAV the same number of requests, with the
    same set of gaps, in another order."""
    mix = _mix("mixed-burst")
    ref = tr.open_schedule(mix, 40.0, 1)
    got = tr.open_schedule(mix, 40.0, seed)

    def per_uav(s):
        out = collections.defaultdict(list)
        for d in s:
            out[d.uav].append(d.t)
        return out

    r, g = per_uav(ref), per_uav(got)
    assert sorted(r) == sorted(g)
    for u in r:
        assert len(r[u]) == len(g[u])
        def cyc(ts):
            return np.sort(np.diff(ts + [ts[0] + 40.0]))

        gaps_r, gaps_g = cyc(r[u]), cyc(g[u])
        np.testing.assert_allclose(gaps_r, gaps_g, rtol=1e-9, atol=1e-9)
        assert 0.0 < min(g[u]) and max(g[u]) < 40.0


def test_open_schedule_mean_rate():
    mix = _mix("mixed-burst")
    sched = tr.open_schedule(mix, 50.0, 9)
    uavs = tr.fleet(mix)
    counts = collections.Counter(d.uav for d in sched)
    rates = [g["rate_hz"] for g in mix["fleet"] for _ in range(g["uavs"])]
    for i, _ in enumerate(uavs):
        assert counts[i] == round(rates[i] * 50.0)


def test_lomax_quantiles_are_heavy_tailed():
    q = tr.lomax_quantiles(1000, 1.8)
    assert np.all(np.diff(q) > 0)
    assert abs(q.mean() - 1.25) < 0.1        # Lomax mean 1 / (shape - 1)
    assert q[-1] > 10 * np.median(q)


def test_closed_fleet_and_request_source_repeat():
    uavs = tr.fleet(_mix("context-closed"))
    assert len(uavs) == 32 and all(u.intent == "context" for u in uavs)
    a = tr.RequestSource(tiny.CLOSED, 256, 4, 2**32 + 3)
    b = tr.RequestSource(tiny.CLOSED, 256, 4, 2**32 + 3)
    for _ in range(5):
        x, y = a.next(), b.next()
        assert x["frame"] == y["frame"]
        np.testing.assert_array_equal(x["query"], y["query"])
        assert x["query"].shape == (1, 4)


def test_fleet_shares_round_to_clients():
    mix = {"loop": "closed", "clients": 7,
           "fleet": [{"intent": "context", "share": 2.0},
                     {"intent": "insight", "tier": "Balanced", "share": 1.0}]}
    uavs = tr.fleet(mix)
    assert len(uavs) == 7
    assert sum(u.intent == "context" for u in uavs) == 5
