"""The comparison that decides ``correct`` for the cell of
``lfm2-24b-a2b``: ``checks/flow_timeline.py``'s, with the kept flows' state
compared **for both kinds**.

As there, the timed path is held to the plain reference on what it
produced at the timed sizes: for a seeded sample of the window's calls (the
first always) and, in each, a seeded sample of its flows, the flow's events
since its last restart are gathered from the rows themselves (``history``)
and the reference computes that whole sequence forward once, no cache, no
tail carried. After the window, what the layers keep of a few flows
(``entry.state``: the first ``cache_flows`` keys in ascending order) is
compared with the reference's for those flows' sequences: an attention
layer's cache rows with the normed, rotated keys and the values of every
position, a convolution layer's tail with ``u`` of the flow's last two
positions. So appending through the cache, and carrying the tail from call
to call, agree with one full forward.

Numbers (each compared against the cell's limit of the same name):
``score_rms_ratio``, ``score_median_gap``, ``score_p90_gap``,
``score_p99_gap``, ``near_tie_share``, ``cache_rel_rms``,
``cache_off_share``, ``cache_length_gap`` and the counts held to 0
(``unexpected_shapes``, ``window_compiles``, ``failed_calls``,
``evictions``, ``wraps``) are ``flow_timeline``'s, the cache's over the
attention layers; and

- ``conv_rel_rms``: the root mean square of the kept tails less the
  reference's ``u`` over that of the reference's, over the convolution
  layers and the kept flows; ``conv_off_share``: the share of the tails'
  rows (a layer's ``u`` of one position) that are off by more than
  ``entry_off`` of their own norm: a rounding error is far under it; a
  tail not carried, carried from the flow before, or of a token routed
  otherwise in the layer before, is over it.
"""

from __future__ import annotations

import numpy as np

from chipbench.checks.flow_timeline import history


def _sequence(ids: np.ndarray, L: int) -> np.ndarray:
    seq = np.zeros(L, np.int32)
    seq[1:1 + len(ids)] = ids[:L - 1]
    return seq


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
            tokens.append(_sequence(ids, L))
            spans.append((c, np.flatnonzero(rows[:, 0] == key), len(ids)))
    # the flows whose state the entry kept, at the window's end
    held = run["entry_state"]["cache_sample"]
    ends = []
    for key in held.arrays["keys"]:
        ids = history(applied, len(applied) - 1, int(key))
        tokens.append(_sequence(ids, L))
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
        ties.append(want["margin"][cfg["num_dense_layers"]:, b, 1:1 + n]
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
    # the state, kind by kind: {kind: [sum of squared gaps, of squared
    # reference, rows off]}
    tally = {"cache": [0.0, 0.0, []], "conv": [0.0, 0.0, []]}
    length_gap = 0
    for j, n in enumerate(ends):
        b = len(spans) + j
        for kind, got, full in zip(cfg["layer_types"], held.arrays["kept"],
                                   want["kept"]):
            if kind == "conv":      # u of the flow's last two positions
                name, got, full = "conv", got[j], full[b, n - 1:n + 1]
            else:
                name, got, full = "cache", got[j, :1 + n], full[b, :1 + n]
            got, full = got.astype(np.float64), full.astype(np.float64)
            t = tally[name]
            t[0] += float(np.sum((got - full) ** 2))
            t[1] += float(np.sum(full ** 2))
            t[2].append(np.linalg.norm(got - full, axis=-1)
                        > check["entry_off"] * np.linalg.norm(full, axis=-1))
        length_gap = max(length_gap,
                         abs(int(held.arrays["length"][j]) - (1 + n)))
    for name, (num, den, off) in tally.items():
        numbers[f"{name}_rel_rms"] = float(np.sqrt(num / max(den, 1e-30)))
        numbers[f"{name}_off_share"] = float(np.mean(np.concatenate(
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
