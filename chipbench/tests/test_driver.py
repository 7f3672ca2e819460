"""The closed-loop driver: at most ``outstanding`` calls in flight, every
``fit_every``-th scored batch fitted, alone, before its slot frees."""

import asyncio
import types

import numpy as np

from chipbench.drivers import closed_loop


def test_outstanding_and_fit_cadence():
    live = {"score": 0, "fit": 0}
    peak = {"score": 0, "fit": 0}
    order = []

    async def score(scorer, x):
        live["score"] += 1
        peak["score"] = max(peak["score"], live["score"] + live["fit"])
        await asyncio.sleep(0.002)
        live["score"] -= 1
        order.append("s")
        return np.full(len(x), 0.5, np.float32)

    async def fit(scorer, x, labels, mask):
        live["fit"] += 1
        peak["fit"] = max(peak["fit"], live["fit"])
        await asyncio.sleep(0.004)
        live["fit"] -= 1
        order.append("f")
        return 0.25

    snaps = []

    def snapshot(scorer):
        snaps.append(len(order))
        return {"at": len(order)}

    entry = types.SimpleNamespace(score=score, fit=fit, snapshot=snapshot)
    pool = [(np.zeros((10, 3), np.float32), np.zeros(10, np.float32),
             np.zeros(10, np.float32)) for _ in range(3)]
    w = asyncio.run(closed_loop.run(
        entry, None, pool, seconds=0.4, outstanding=2, fit_every=8,
        keep=np.ones(1000, bool), follow_fits=1,
        anchor=np.arange(1000) == 3))
    calls, fits = w["calls"], w["fits"]
    assert peak["score"] <= 2 and peak["fit"] == 1
    assert len(calls) > 40 and all(c["ok"] for c in calls)
    assert [c["i"] for c in calls] == list(range(len(calls)))
    assert [c["k"] for c in calls[:4]] == [0, 1, 2, 0]
    scored_in_window = sum(c["done"] < w["t1"] for c in calls)
    assert len(fits) in (scored_in_window // 8, scored_in_window // 8 + 1)
    # the first fit follows the 8th completed score (a 9th may land while
    # the fit's task waits for the loop)
    assert order[:order.index("f")].count("s") in (8, 9)
    # fit 3 alone lies between two snapshots
    assert [j for j, f in enumerate(fits) if "before" in f] == [3]
    assert len(snaps) == 2 and "after" in fits[3]
    # kept: the calls that met a state the comparison knows: before and
    # during fit 0 and until fit 1 starts, during fit 3 and until fit 4
    for c in calls:
        s_, d = c["fits_started"], c["fits_done"]
        known = (s_, d) in {(0, 0), (1, 0), (1, 1), (4, 3), (4, 4)}
        assert ("out" in c) == known, (c["i"], s_, d)
    assert {(c["fits_started"], c["fits_done"]) for c in calls
            if "out" in c} >= {(0, 0), (1, 1), (4, 4)}
    # a call is due when its slot freed, never before the window opened;
    # none is sent once the window's seconds are up, and the clock that
    # closes the window is read after the last answer and the last fit
    assert min(c["due"] for c in calls) == w["t0"]
    assert max(c["due"] for c in calls) < w["t1"]
    assert w["t_end"] >= max([c["done"] for c in calls]
                             + [f["end"] for f in fits])
    assert all(c["fits_done"] <= c["fits_started"] <= len(fits)
               for c in calls)
