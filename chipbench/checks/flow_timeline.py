"""The comparison that decides ``correct`` for a flow cell.

The timed path is held to the plain reference on what it produced at the
timed sizes. The state a call meets is a function of its number: the warm
rounds send the pool's first calls, the window sends the pool from its
start again in the order of the calls' numbers, and the program applies
calls in the order ``score()`` was called. So the comparison replays the
rows themselves: for a seeded sample of the window's calls (the first
always) and, in each, a seeded sample of its flows, it gathers the flow's
events since its last restart (a restart flag on any of a flow's rows of a
call restarts the flow at its first row of that call; the warm calls
count) and the reference computes that whole sequence forward once, no
cache. The call's scores for that flow are compared with the reference's
for the sequence's last events. After the window, the cache rows of a few
flows (``entry.state`` keeps them: the first ``cache_flows`` keys in
ascending order) are compared with the reference's latent and rope key
for those flows' sequences: appending through the cache agrees with one
full forward.

Numbers (each compared against the cell's limit of the same name):

- ``score_rms_ratio``: the root mean square of a returned score less the
  reference's, over the same of the reference's own scores with every
  matrix product's operands rounded to the compute type the configuration
  states, less the reference's: how many times the stated precision's own
  error the program's is.
- ``score_median_gap``, ``score_p90_gap``, ``score_p99_gap``: the median,
  the 90th and the 99th percentile of the gap's size. A token whose last selected and first
  left-out router scores lie within rounding routes differently in
  bfloat16 and in float32, so the widest gap (``score_gap``, reported, not
  compared) is not let decide.
- ``near_tie_share``: the share of (token, expert layer) pairs of the
  compared sequences whose margin between the last expert selected and
  the first left out is under ``tie_margin``, by the reference's own
  router: how much of the sample such tokens are.
- ``cache_rel_rms``: the root mean square of the kept cache rows less the
  reference's entries over that of the reference's entries, over the
  positions the flows hold; ``cache_off_share``: the share of those rows
  (a layer's entry of one position) that are off by more than
  ``entry_off`` of their own norm: a rounding error is far under it, a
  token that took another expert in the layer before is over it, so the
  share is of the tokens routed otherwise than the reference routes them;
  ``cache_length_gap``: the widest gap between a
  kept flow's length on the device and the start token plus its events
  since its restart.
- ``unexpected_shapes`` (score shapes, layouts and fits other than the
  cell's), ``window_compiles``, ``failed_calls``, ``evictions``,
  ``wraps``: counts, each held to 0.
"""

from __future__ import annotations

import numpy as np


def history(calls: list, upto: int, key: int) -> np.ndarray:
    """The ids of ``key``'s flow from its last restart through call
    ``upto`` of ``calls`` (a list of row arrays, in the order applied)."""
    parts = []
    for rows in reversed(calls[:upto + 1]):
        mine = rows[rows[:, 0] == key]
        if len(mine):
            parts.append(mine[:, 2])
            if mine[:, 1].any():
                break
    return np.concatenate(parts[::-1]) if parts else np.zeros(0, np.int32)


def compare(run: dict) -> dict:
    ref, cfg, cell, mix = (run["reference"], run["config"], run["cell"],
                           run["mix"])
    check, seed, pool = cell["check"], run["seed"], run["pool"]
    window = run["window"]
    done = sorted((c for c in window["calls"] if "error" not in c),
                  key=lambda c: c["i"])
    warm = [pool[(r * run["outstanding"] + i) % len(pool)][0]
            for r in range(mix["warm_rounds"])
            for i in range(run["outstanding"])]
    applied = warm + [pool[c["k"]][0] for c in done]
    at = {c["i"]: len(warm) + n for n, c in enumerate(done)}
    kept = [c for c in done if "out" in c]
    rng = np.random.default_rng([seed, 3])
    picked = kept[:1] + [kept[j] for j in sorted(rng.choice(
        np.arange(1, len(kept)), min(check["calls_compared"] - 1,
                                     max(len(kept) - 1, 0)), replace=False))]
    L = cfg["model"]["positions"]
    tokens, spans = [], []
    for c in picked:
        rows = pool[c["k"]][0]
        keys = np.unique(rows[:, 0])
        for key in rng.choice(keys, min(check["flows_compared"], len(keys)),
                              replace=False):
            ids = history(applied, at[c["i"]], key)
            mine = np.flatnonzero(rows[:, 0] == key)
            seq = np.zeros(L, np.int32)
            seq[1:1 + len(ids)] = ids[:L - 1]
            tokens.append(seq)
            spans.append((c, mine, len(ids)))
    # the flows whose cache rows the entry kept, at the window's end
    held = run["entry_state"]["cache_sample"]
    ends = []
    for key in held.arrays["keys"]:
        ids = history(applied, len(applied) - 1, int(key))
        seq = np.zeros(L, np.int32)
        seq[1:1 + len(ids)] = ids[:L - 1]
        tokens.append(seq)
        ends.append(len(ids))
    tokens = np.stack(tokens)
    want = ref.forward(seed, cfg, tokens)
    stated = ref.forward(seed, cfg, tokens[:len(spans)],
                         quant=ref.PRECISION[cfg["model"]["compute_dtype"]])
    gaps, own, ties = [], [], []
    for b, (c, mine, n) in enumerate(spans):
        last = slice(1 + n - len(mine), 1 + n)
        gaps.append(np.asarray(c["out"], np.float64)[mine]
                    - want["score"][b, last])
        own.append(stated["score"][b, last].astype(np.float64)
                   - want["score"][b, last])
        ties.append(want["margin"][cfg["first_k_dense_replace"]:, b, 1:1 + n]
                    < check["tie_margin"])
    gaps, own = np.concatenate(gaps), np.concatenate(own)
    numbers = {
        "score_rms_ratio": float(np.sqrt(np.mean(gaps ** 2)
                                         / max(np.mean(own ** 2), 1e-30))),
        "score_median_gap": float(np.median(np.abs(gaps))),
        "score_p90_gap": float(np.percentile(np.abs(gaps), 90)),
        "score_p99_gap": float(np.percentile(np.abs(gaps), 99)),
        "score_gap": float(np.max(np.abs(gaps))),
        "score_rms_gap": float(np.sqrt(np.mean(gaps ** 2))),
        "near_tie_share": float(np.mean(np.concatenate(
            [t.ravel() for t in ties])))}
    num = den = 0.0
    length_gap, off = 0, []
    for j, n in enumerate(ends):
        b = len(spans) + j
        got = held.arrays["cache"][:, j, :1 + n].astype(np.float64)
        entries = want["entries"][:, b, :1 + n]
        num += float(np.sum((got - entries) ** 2))
        den += float(np.sum(entries.astype(np.float64) ** 2))
        off.append(np.linalg.norm(got - entries, axis=-1)
                   > check["entry_off"] * np.linalg.norm(entries, axis=-1))
        length_gap = max(length_gap,
                         abs(int(held.arrays["length"][j]) - (1 + n)))
    numbers["cache_rel_rms"] = float(np.sqrt(num / max(den, 1e-30)))
    numbers["cache_off_share"] = float(np.mean(np.concatenate(
        [o.ravel() for o in off])))
    numbers["cache_length_gap"] = float(length_gap)
    state = run["entry_state"]
    layout = f"{mix['flows_per_call']}x{mix['chunk']}"
    numbers["unexpected_shapes"] = float(
        sum(k not in run["expected_shapes"]["score"]
            for k in state["score_batches"])
        + sum(k != layout for k in state["flow"]["layouts"])
        + len(state["fit_batches"]))
    numbers["window_compiles"] = float(run["window_compiles"])
    numbers["failed_calls"] = float(
        sum(not c["ok"] for c in window["calls"]))
    numbers["evictions"] = float(state["flow"]["evictions"])
    numbers["wraps"] = float(state["flow"]["wraps"])
    info = {"calls_compared": len(picked), "flows_compared": len(spans),
            "events_compared": int(len(gaps)),
            "calls_in_window": len(window["calls"]),
            "longest_sequence": int(max(n for *_, n in spans)),
            "cache_flows": len(ends), "cache_positions": int(sum(ends))}
    return {"numbers": numbers, "info": info}
