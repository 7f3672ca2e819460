"""The control of the comparison of ``hy4-preview-ep16``: the plain
reference put in the program's place and computed in float8 (e4m3) ahead
of every matrix product, the step below the bfloat16 that the
configuration states. ``--entry hy4_control_fp8`` runs the cell with it;
the comparison has to come out as not correct. As
``entries/laguna_control_fp8.py`` (whose bookkeeping this is): it keeps
every flow's events since its restart on the host and computes each call's
flows whole, each sequence as long as the check would make it; its times
mean nothing."""

from __future__ import annotations

import numpy as np

# noqa: F401 below: the names the harness calls on an entry
from chipbench.entries.flow_control_fp8 import (  # noqa: F401
    QUANT, ReferenceFlows, build, close, fit, place_cache, snapshot,
)
from chipbench.entries.flow_scorer import Kept
from chipbench.entries.laguna_scorer import kept_keys


def _forward(s: ReferenceFlows, ids: list, keep: bool) -> list:
    from chipbench.checks.hy4_timeline import forward_by_length
    from chipbench.reference import hy4_moe
    got, = forward_by_length(hy4_moe, s.seed, s.config,
                             [(ids, QUANT, [keep] * len(ids))])
    return got


async def score(s: ReferenceFlows, x: np.ndarray) -> np.ndarray:
    keys, per = np.unique(x[:, 0], return_counts=True)
    name = str(1 << max(0, len(x) - 1).bit_length())
    s.score_batches[name] = s.score_batches.get(name, 0) + 1
    layout = (f"{1 << max(0, len(keys) - 1).bit_length()}x"
              f"{1 << max(0, int(per.max()) - 1).bit_length()}")
    s.layouts[layout] = s.layouts.get(layout, 0) + 1
    for key in keys.tolist():
        mine = x[x[:, 0] == key]
        old = (np.zeros(0, np.int32) if mine[:, 1].any()
               else s.ids.get(key, np.zeros(0, np.int32)))
        s.ids[key] = np.concatenate([old, mine[:, 2]])
    got = _forward(s, [s.ids[k] for k in keys.tolist()], False)
    out = np.zeros(len(x), np.float32)
    for b, key in enumerate(keys.tolist()):
        n = len(s.ids[key])
        out[x[:, 0] == key] = got[b]["score"][1 + n - per[b]:1 + n]
    return out


def state(s: ReferenceFlows) -> dict:
    """As ``entries/hy4_scorer.state``; what the kept flows' state would
    hold, every position's latent entry and index key, is computed here
    for their sequences as they ended."""
    keys = kept_keys({k: len(i) for k, i in s.ids.items()})
    got = _forward(s, [s.ids[k] for k in keys], True)
    kinds = s.config["indexer_types"]
    longest = max(len(g["score"]) for g in got)

    def stack(part, l):
        return np.stack([np.pad(g[part][l], ((0, longest - len(g["score"])),
                                             (0, 0))) for g in got])

    kept = Kept(f"the reference's own state of {len(keys)} flows")
    fulls = [l for l, k in enumerate(kinds) if k == "full"]
    kept.arrays = {
        "keys": np.array(keys, np.int64),
        "kept": [stack("kept", l) for l in range(len(kinds))],
        "index": [stack("keys", fulls.index(l)) if l in fulls else None
                  for l in range(len(kinds))],
        "length": np.array([1 + len(s.ids[k]) for k in keys])}
    return {"score_path": "reference", "score_batches": s.score_batches,
            "fit_batches": {},
            "flow": {"layouts": s.layouts, "evictions": 0, "wraps": 0,
                     "resident": len(s.ids)},
            "cache_sample": kept}
