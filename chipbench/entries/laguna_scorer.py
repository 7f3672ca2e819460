"""How the configuration ``laguna-xs.2`` is put under test: the public
``InProcessScorer`` with the third flow model's spec, built from the
configuration file, and one call of ``score``, as ``entries/flow_scorer.py``
does for the first (whose calls these are). What is kept for the
comparison once the window has closed is of **both kinds of state**: of a
few flows, the longest and the shortest resident, a full layer's cache of
keys and values and a sliding layer's ring, as they lie."""

from __future__ import annotations

import numpy as np

# noqa: F401 below: the names the harness calls on an entry
from chipbench.entries.flow_scorer import (  # noqa: F401
    CACHE_FLOWS, Kept, close, fit, place_cache, score, snapshot,
)
from chipbench.entries.lfm2_scorer import born_now


def build(config: dict, seed: int):
    from linkerd_tpu.models.laguna_moe import LagunaMoEConfig
    from linkerd_tpu.models.spec import laguna_moe
    from linkerd_tpu.telemetry.anomaly import InProcessScorer
    return born_now(InProcessScorer(
        seed=seed, spec=laguna_moe(LagunaMoEConfig.from_config(config))))


def kept_keys(length_of: dict) -> list:
    """Of ``{key: length}``, ``CACHE_FLOWS`` keys: half the shortest and
    half the longest (ties by key), in ascending order."""
    by_length = sorted(length_of, key=lambda k: (length_of[k], k))
    return sorted(set(by_length[:CACHE_FLOWS // 2]
                      + by_length[-(CACHE_FLOWS - CACHE_FLOWS // 2):]))


def state_sample(scorer) -> Kept:
    """What the layers keep of ``CACHE_FLOWS`` resident flows, half the
    longest and half the shortest (ties by key: the longest have been
    round a ring, the shortest may not have filled a window), as host
    arrays: ``kept``, a layer's ``[flows, positions, 2 x kv heads x head]``
    float32 (the state lies ``[entry, positions]`` on the device),
    ``positions`` the cache's or the ring's; ``ring``, a layer's ring
    size, 0 for a cache; and ``length [flows]``."""
    table = scorer._table
    keys = kept_keys({k: int(table.length[s])
                      for k, s in table.slot_of.items()})
    slots = np.array([table.slot_of[k] for k in keys], np.int32)
    layers, length = scorer._state[:2]
    cfg = scorer.cfg
    kept = Kept(f"the state of {len(keys)} flows in {cfg.layers} layers")
    kept.arrays = {
        "keys": np.array(keys, np.int64),
        "kept": [np.asarray(a[slots], np.float32).transpose(0, 2, 1)
                 for a in layers],
        "ring": [cfg.operator(l).ring for l in range(cfg.layers)],
        "length": np.asarray(length[slots])}
    return kept


def state(scorer) -> dict:
    """``score_path``, the calls per compiled shape and layout, what the
    flow table counted over the run, and the state sample."""
    from linkerd_tpu.telemetry import phases
    d = scorer.device_state()
    calls = [c for c in phases.records() if c.t0 >= scorer.born]
    counted = {name: sum(c.counts.get(f"flow.{name}", 0) for c in calls)
               for name in ("evictions", "wraps", "restarts", "events")}
    return {"score_path": d["score_path"],
            "score_batches": d["score_batches"],
            "fit_batches": d["fit_batches"],
            "flow": {**d["flow"], **counted},
            "cache_sample": state_sample(scorer)}
