"""The control of the flow comparison: the plain reference put in the
program's place and computed in float8 (e4m3) ahead of every matrix
product, the step below the bfloat16 that the configuration states.
``--entry flow_control_fp8`` runs a cell with it; the comparison has to
come out as not correct. It keeps every flow's events since its restart on
the host and computes each call's flows whole, forward once: its times
mean nothing, and no run of the benchmark proper uses it."""

from __future__ import annotations

import numpy as np

from chipbench.entries.flow_scorer import CACHE_FLOWS, Kept

QUANT = "fp8"


def _ref():
    from chipbench.reference import latent_moe
    return latent_moe


class ReferenceFlows:
    def __init__(self, config: dict, seed: int):
        self.config, self.seed = config, seed
        self.ids: dict = {}         # key -> events since the restart
        self.score_batches: dict = {}
        self.layouts: dict = {}
        self.entries: dict = {}     # key -> newest entries [layers, L, w]


def place_cache() -> str:
    from chipbench.entries import flow_scorer
    return flow_scorer.place_cache()


def build(config: dict, seed: int) -> ReferenceFlows:
    return ReferenceFlows(config, seed)


async def score(s: ReferenceFlows, x: np.ndarray) -> np.ndarray:
    L = s.config["model"]["positions"]
    keys, first, per = np.unique(x[:, 0], return_index=True,
                                 return_counts=True)
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
    got = _ref().forward(s.seed, s.config, tokens, quant=QUANT)
    out = np.zeros(len(x), np.float32)
    for b, key in enumerate(keys.tolist()):
        n = len(s.ids[key])
        out[x[:, 0] == key] = got["score"][b, 1 + n - per[b]:1 + n]
        s.entries[key] = got["entries"][:, b]
    return out


async def fit(s: ReferenceFlows, x, labels, mask) -> float:
    raise RuntimeError("the flow configuration is frozen")


def state(s: ReferenceFlows) -> dict:
    keys = sorted(s.ids)[:CACHE_FLOWS]
    kept = Kept(f"the reference's own entries of {len(keys)} flows")
    kept.arrays = {"keys": np.array(keys, np.int64),
                   "cache": np.stack([s.entries[k] for k in keys], 1),
                   "length": np.array([1 + len(s.ids[k]) for k in keys])}
    return {"score_path": "reference", "score_batches": s.score_batches,
            "fit_batches": {},
            "flow": {"layouts": s.layouts, "evictions": 0, "wraps": 0,
                     "resident": len(s.ids)},
            "cache_sample": kept}


def snapshot(s: ReferenceFlows) -> dict:
    return {"resident": len(s.ids)}


def close(s: ReferenceFlows) -> None:
    return None
