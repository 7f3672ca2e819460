"""The generator of flow events: reads a traffic mix and makes, from the
seed alone and on the host, one whole period of a schedule of calls, which
the driver cycles.

``flows`` resident flows (stream keys drawn from the seed, 24 bits, never
0) are served round robin: a call brings the next ``chunk`` events of
``flows_per_call`` flows, interleaved as a drain brings them (event ``t``
of every flow, then ``t + 1``), so ``flows / flows_per_call`` consecutive
calls touch every flow once and calls in flight share no flow. A flow
lives a whole number of chunks, log-normal around ``lifetime_median_events``
(capped at ``lifetime_cap_events``), and is then followed under the same
key by a new flow, whose first row carries the restart flag. Each key's
lifetimes tile the period's ``visits`` on a circle, turned by an offset of
the key's own, so that restarts are staggered and the schedule, cycled,
goes on without a seam: the state a call meets is a function of its
number.

An event id is ``1 + (offset + rank - 1) mod (vocab - 1)``: ``rank`` from
a Zipf law (``zipf_a``) over ``ids_per_flow`` ranks, the flow's
destination mix, and ``offset`` the flow's own; ``uniform_share`` of the
events are drawn uniformly over the vocabulary instead (the anomalies).

A row is int32 ``(stream key, restart flag, event id)``. There are no
labels and no set-up rows: the configuration is frozen.
"""

from __future__ import annotations

import numpy as np


def lifetimes(rng, mix: dict) -> np.ndarray:
    """One key's flows, in chunks, tiling the period's visits exactly."""
    chunk, visits = mix["chunk"], mix["visits"]
    cap = mix["lifetime_cap_events"] // chunk
    out, left = [], visits
    while left:
        events = rng.lognormal(np.log(mix["lifetime_median_events"]),
                               mix["lifetime_sigma"])
        n = int(min(max(round(events / chunk), 1), cap, left))
        out.append(n)
        left -= n
    return np.array(out)


def schedule(mix: dict, seed: int) -> dict:
    """``{"keys" [flows], "restart" [visits, flows] bool, "ids" [visits,
    flows, chunk]}``: what every key sends at each of its visits."""
    rng = np.random.default_rng([seed, 28])
    flows, chunk, visits = mix["flows"], mix["chunk"], mix["visits"]
    vocab = mix["vocab"]
    keys = rng.choice(2 ** 24 - 1, flows, replace=False) + 1
    p = np.arange(1, mix["ids_per_flow"] + 1, dtype=np.float64) ** -mix[
        "zipf_a"]
    cdf = np.cumsum(p / p.sum())
    restart = np.zeros((visits, flows), bool)
    offset = np.zeros((visits, flows), np.int64)
    for f in range(flows):
        life = lifetimes(rng, mix)
        begins = (np.cumsum(life) - life + rng.integers(visits)) % visits
        restart[begins, f] = True
        # a visit belongs to the flow begun at the last restart at or
        # before it, around the circle
        order = np.sort(begins)
        own = rng.integers(vocab - 1, size=len(order))
        offset[:, f] = own[np.searchsorted(order, np.arange(visits),
                                           side="right") - 1]
    rank = np.searchsorted(cdf, rng.random((visits, flows, chunk)))
    ids = 1 + (offset[..., None] + rank) % (vocab - 1)
    odd = rng.random(ids.shape) < mix["uniform_share"]
    ids[odd] = rng.integers(1, vocab, size=int(odd.sum()))
    return {"keys": keys, "restart": restart, "ids": ids}


def generate(mix: dict, rows_per_call: int, width: int, seed: int) -> dict:
    """``{"pool": [(rows, None, None), ...], "setup": (no rows)}``."""
    flows, per_call, chunk = mix["flows"], mix["flows_per_call"], mix["chunk"]
    if per_call * chunk != rows_per_call or flows % per_call or width != 3:
        raise ValueError("a call is flows_per_call x chunk rows of 3")
    s = schedule(mix, seed)
    pool = []
    for v in range(mix["visits"]):
        for g in range(flows // per_call):
            lanes = slice(g * per_call, (g + 1) * per_call)
            rows = np.empty((chunk, per_call, 3), np.int32)
            rows[..., 0] = s["keys"][lanes]
            rows[..., 1] = 0
            rows[0, :, 1] = s["restart"][v, lanes]
            rows[..., 2] = s["ids"][v, lanes].T
            pool.append((rows.reshape(rows_per_call, 3), None, None))
    none = np.zeros(0, np.float32)
    return {"pool": pool, "setup": (np.zeros((0, 3), np.int32), none, none)}
