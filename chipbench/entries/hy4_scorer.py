"""How the configuration ``hy4-preview-ep16`` is put under test: the public
``InProcessScorer`` with the fifth flow model's spec, built from the
configuration file, and one call of ``score``, as ``entries/flow_scorer.py``
does for the first (whose calls these are). What is kept for the
comparison once the window has closed is of **both kinds of state**: of a
few flows, the longest and the shortest resident, every layer's latent
cache and every ``full`` layer's index keys, as they lie."""

from __future__ import annotations

import numpy as np

# noqa: F401 below: the names the harness calls on an entry
from chipbench.entries.flow_scorer import (  # noqa: F401
    CACHE_FLOWS, Kept, close, fit, place_cache, score, snapshot,
)
from chipbench.entries.laguna_scorer import kept_keys
from chipbench.entries.lfm2_scorer import born_now


def build(config: dict, seed: int):
    from linkerd_tpu.models.hy4_moe import Hy4MoEConfig
    from linkerd_tpu.models.spec import hy4_moe
    from linkerd_tpu.telemetry.anomaly import InProcessScorer
    return born_now(InProcessScorer(
        seed=seed, spec=hy4_moe(Hy4MoEConfig.from_config(config))))


def state_sample(scorer) -> Kept:
    """What the layers keep of ``CACHE_FLOWS`` resident flows, half the
    longest and half the shortest (ties by key), as host arrays:
    ``kept``, a layer's latent ``[flows, positions, 576]`` float32;
    ``keys``, a ``full`` layer's index keys ``[flows, positions, 128]``
    float32 (they lie ``[128, positions]`` on the device), None on a
    ``shared`` layer; and ``length [flows]``."""
    table = scorer._table
    keys = kept_keys({k: int(table.length[s])
                      for k, s in table.slot_of.items()})
    slots = np.array([table.slot_of[k] for k in keys], np.int32)
    layers, length = scorer._state[:2]
    cfg = scorer.cfg
    kept = Kept(f"the state of {len(keys)} flows in {cfg.layers} layers")
    latent = [a[0] if isinstance(a, tuple) else a for a in layers]
    kept.arrays = {
        "keys": np.array(keys, np.int64),
        "kept": [np.asarray(a[slots], np.float32) for a in latent],
        "index": [np.asarray(a[1][slots], np.float32).transpose(0, 2, 1)
                  if isinstance(a, tuple) else None for a in layers],
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
