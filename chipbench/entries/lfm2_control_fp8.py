"""The control of the comparison of ``lfm2-24b-a2b``: the plain reference
put in the program's place and computed in float8 (e4m3) ahead of every
matrix product, the step below the bfloat16 that the configuration states.
``--entry lfm2_control_fp8`` runs the cell with it; the comparison has to
come out as not correct. As ``entries/flow_control_fp8.py`` (whose
bookkeeping this is): it keeps every flow's events since its restart on
the host and computes each call's flows whole; its times mean nothing."""

from __future__ import annotations

import numpy as np

# noqa: F401 below: the names the harness calls on an entry
from chipbench.entries.flow_control_fp8 import (  # noqa: F401
    QUANT, ReferenceFlows, build, close, fit, place_cache, snapshot,
)
from chipbench.entries.flow_scorer import CACHE_FLOWS, Kept


async def score(s: ReferenceFlows, x: np.ndarray) -> np.ndarray:
    from chipbench.reference import lfm2_moe
    L = s.config["model"]["positions"]
    keys, per = np.unique(x[:, 0], return_counts=True)
    name = str(1 << max(0, len(x) - 1).bit_length())
    s.score_batches[name] = s.score_batches.get(name, 0) + 1
    layout = (f"{1 << max(0, len(keys) - 1).bit_length()}x"
              f"{1 << max(0, int(per.max()) - 1).bit_length()}")
    s.layouts[layout] = s.layouts.get(layout, 0) + 1
    tokens = np.zeros((len(keys), L), np.int32)
    for b, key in enumerate(keys.tolist()):
        mine = x[x[:, 0] == key]
        old = (np.zeros(0, np.int32) if mine[:, 1].any()
               else s.ids.get(key, np.zeros(0, np.int32)))
        s.ids[key] = np.concatenate([old, mine[:, 2]])
        tokens[b, 1:1 + len(s.ids[key])] = s.ids[key][:L - 1]
    got = lfm2_moe.forward(s.seed, s.config, tokens, quant=QUANT)
    out = np.zeros(len(x), np.float32)
    for b, key in enumerate(keys.tolist()):
        n = len(s.ids[key])
        out[x[:, 0] == key] = got["score"][b, 1 + n - per[b]:1 + n]
        # what a flow's state would hold: a cache's rows, a tail's two
        s.entries[key] = [
            k[b, n - 1:n + 1] if kind == "conv" else k[b]
            for kind, k in zip(s.config["layer_types"], got["kept"])]
    return out


def state(s: ReferenceFlows) -> dict:
    keys = sorted(s.ids)[:CACHE_FLOWS]
    kept = Kept(f"the reference's own state of {len(keys)} flows")
    kept.arrays = {
        "keys": np.array(keys, np.int64),
        "kept": [np.stack([s.entries[k][l] for k in keys])
                 for l in range(len(s.config["layer_types"]))],
        "length": np.array([1 + len(s.ids[k]) for k in keys])}
    return {"score_path": "reference", "score_batches": s.score_batches,
            "fit_batches": {},
            "flow": {"layouts": s.layouts, "evictions": 0, "wraps": 0,
                     "resident": len(s.ids)},
            "cache_sample": kept}
