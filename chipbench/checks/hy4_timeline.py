"""The comparison that decides ``correct`` for the cell of
``hy4-preview-ep16``: ``checks/laguna_timeline.py``'s, with the kept flows'
state compared **for both arrays a layer may keep**, the latent cache and
a ``full`` layer's index keys, and the compared flows drawn from those
long enough that the selection bites.

As there, the timed path is held to the plain reference on what it
produced at the timed sizes: for a seeded sample of the window's calls (the
first always) and, in each, a seeded sample of its flows, the flow's events
since its last restart are gathered from the rows themselves (``history``)
and the reference computes that whole sequence forward once, no cache,
up-projected, the selection made afresh over the whole sequence. The
window begins with every flow empty, so **the compared calls but the
first are drawn from those in which a flow is longer than
``index_topk``** (from all, where fewer than needed are), and **all but
one of a call's compared flows from those longer than ``index_topk``**
(where the call has such; the window's first call has none): most of what
is compared attended over a selection. After
the window, what the layers keep of a few flows (``entry.state``: the
longest and the shortest resident) is compared with the reference's latent
entries and index keys for those flows' sequences at every position. The
reference's sequences are as long as the longest of a group needs, in
whole ``sequence_bucket``s.

Numbers (each compared against the cell's limit of the same name):
``score_rms_ratio``, ``score_median_gap``, ``score_p90_gap``,
``score_p99_gap``, ``near_tie_share``, ``cache_rel_rms``,
``cache_off_share``, ``cache_length_gap`` and the counts held to 0
(``unexpected_shapes``, ``window_compiles``, ``failed_calls``,
``evictions``, ``wraps``) are ``flow_timeline``'s, the cache's over the
latent of every layer; and

- ``index_rel_rms``, ``index_off_share``: the same two over the ``full``
  layers' index keys;
- ``unselected_share``: the share of the compared flows (the calls' and
  the kept) no longer than ``index_topk``, whose every event attends over
  all it has seen: held to a half, so that a run whose sample never went
  past the selection is not a sound one.
"""

from __future__ import annotations

import numpy as np

from chipbench.checks.flow_timeline import history


def forward_by_length(ref, seed: int, cfg: dict, passes: list,
                      bucket: int = 2048) -> list:
    """The reference's forwards of ``passes``, ``[(ids, quant, keep)]``
    with ``ids`` a list of flows' events since their restarts, grouped by
    length in whole ``bucket``s under one draw of the weights
    (``checks/laguna_timeline.forward_by_length``'s grouping). A pass gets
    ``[{"score" [L], "margin" [layers, L], "kept": a layer's [L, e],
    "keys": a full layer's [L, dim] where keep[b]}]`` in the order
    given."""
    groups, where = [], []
    for n, (ids, quant, keep) in enumerate(passes):
        L = [min(-(-(1 + len(i)) // bucket) * bucket,
                 cfg["model"]["positions"]) for i in ids]
        for length in sorted(set(L)):
            for kept in (False, True):
                mine = [b for b, l in enumerate(L)
                        if l == length and bool(keep[b]) == kept]
                if not mine:
                    continue
                tokens = np.zeros((len(mine), length), np.int32)
                for row, b in enumerate(mine):
                    tokens[row, 1:1 + len(ids[b])] = ids[b][:length - 1]
                groups.append((tokens, quant, kept))
                where.append((n, mine))
    out = [[None] * len(ids) for ids, _, _ in passes]
    for (n, mine), got in zip(where, ref.forward_groups(seed, cfg, groups)):
        for row, b in enumerate(mine):
            out[n][b] = {"score": got["score"][row],
                         "margin": got["margin"][:, row]}
            if "kept" in got:
                out[n][b]["kept"] = [k[row] for k in got["kept"]]
                out[n][b]["keys"] = [k[row] for k in got["keys"]]
    return out


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
    topk = cfg["index_topk"]
    # the calls in which some flow is past the selection's size: the window
    # begins with every flow empty, and its first half has few such
    longest = np.zeros(len(applied), np.int64)
    length: dict = {}
    for n, rows in enumerate(applied):
        keys = np.unique(rows[:, 0]).tolist()
        for key in keys:
            mine = rows[rows[:, 0] == key]
            length[key] = (0 if mine[:, 1].any() else length.get(key, 0)
                           ) + len(mine)
        longest[n] = max(length[k] for k in keys)
    rng = np.random.default_rng([seed, 3])
    eligible = [j for j in range(1, len(kept))
                if 1 + longest[at[kept[j]["i"]]] > topk]
    if len(eligible) < check["calls_compared"] - 1:
        eligible = list(range(1, len(kept)))
    picked = kept[:1] + [kept[j] for j in sorted(rng.choice(
        eligible, min(check["calls_compared"] - 1, len(eligible)),
        replace=False))]
    held = run["entry_state"]["cache_sample"]
    ids, spans = [], []
    for c in picked:
        rows = pool[c["k"]][0]
        past = {int(key): history(applied, at[c["i"]], key)
                for key in np.unique(rows[:, 0])}
        # all but one from the flows past the selection's size, where the
        # call has them (the window's first call, always compared, has
        # none: its flows are the short ones)
        long = sorted(k for k, i in past.items() if 1 + len(i) > topk)
        want = min(check["flows_compared"], len(past))
        chosen = list(rng.choice(long, min(want - 1, len(long)),
                                 replace=False)) if long else []
        rest = sorted(set(past) - set(chosen))
        chosen += list(rng.choice(rest, want - len(chosen), replace=False))
        for key in chosen:
            ids.append(past[key])
            spans.append((c, np.flatnonzero(rows[:, 0] == key),
                          len(past[key])))
    # the flows whose state the entry kept, at the window's end
    ends = []
    for key in held.arrays["keys"]:
        ids.append(history(applied, len(applied) - 1, int(key)))
        ends.append(len(ids[-1]))
    state_of = [b >= len(spans) for b in range(len(ids))]
    want, stated = forward_by_length(ref, seed, cfg, [
        (ids, None, state_of),
        (ids[:len(spans)], ref.PRECISION[cfg["model"]["compute_dtype"]],
         [False] * len(spans))], bucket=check.get("sequence_bucket", 2048))
    dense = cfg["mlp_layer_types"].count("dense")
    gaps, own, ties = [], [], []
    for b, (c, mine, n) in enumerate(spans):
        last = slice(1 + n - len(mine), 1 + n)
        gaps.append(np.asarray(c["out"], np.float64)[mine]
                    - want[b]["score"][last])
        own.append(stated[b]["score"][last].astype(np.float64)
                   - want[b]["score"][last])
        ties.append(want[b]["margin"][dense:, 1:1 + n] < check["tie_margin"])
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
    # the state, array by array: {name: [sum of squared gaps, of squared
    # reference, rows off]}
    tally = {"cache": [0.0, 0.0, []], "index": [0.0, 0.0, []]}
    length_gap = 0
    fulls = [l for l, k in enumerate(cfg["indexer_types"]) if k == "full"]
    for j, n in enumerate(ends):
        full = want[len(spans) + j]
        pairs = [("cache", got[j, :1 + n], rows[:1 + n])
                 for got, rows in zip(held.arrays["kept"], full["kept"])]
        pairs += [("index", held.arrays["index"][l][j, :1 + n],
                   rows[:1 + n]) for l, rows in zip(fulls, full["keys"])]
        for name, got, rows in pairs:
            got, rows = got.astype(np.float64), rows.astype(np.float64)
            t = tally[name]
            t[0] += float(np.sum((got - rows) ** 2))
            t[1] += float(np.sum(rows ** 2))
            t[2].append(np.linalg.norm(got - rows, axis=-1)
                        > check["entry_off"] * np.linalg.norm(rows, axis=-1))
        length_gap = max(length_gap,
                         abs(int(held.arrays["length"][j]) - (1 + n)))
    for name, (num, den, off) in tally.items():
        numbers[f"{name}_rel_rms"] = float(np.sqrt(num / max(den, 1e-30)))
        numbers[f"{name}_off_share"] = float(np.mean(np.concatenate(
            [o.ravel() for o in off])))
    numbers["cache_length_gap"] = float(length_gap)
    lengths = [1 + n for *_, n in spans] + [1 + n for n in ends]
    numbers["unselected_share"] = float(np.mean(
        [n <= topk for n in lengths]))
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
            "longest_sequence": int(max(lengths)),
            "sequence_lengths": sorted(lengths),
            "cache_flows": len(ends), "cache_positions": int(sum(ends))}
    return {"numbers": numbers, "info": info}
