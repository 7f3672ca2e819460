"""How the configuration ``lfm2-24b-a2b`` is put under test: the public
``InProcessScorer`` with the second flow model's spec, built from the
configuration file, and one call of ``score``, as ``entries/flow_scorer.py``
does for the first (whose calls these are). What is kept for the
comparison once the window has closed is of **both kinds of state**: of a
few flows, the keys and values an attention layer's cache holds and the
tail a convolution layer holds."""

from __future__ import annotations

import time

import numpy as np

# noqa: F401 below: the names the harness calls on an entry
from chipbench.entries.flow_scorer import (  # noqa: F401
    CACHE_FLOWS, Kept, close, fit, place_cache, score, snapshot,
)


def build(config: dict, seed: int):
    from linkerd_tpu.models.lfm2_moe import Lfm2MoEConfig
    from linkerd_tpu.models.spec import lfm2_moe
    from linkerd_tpu.telemetry.anomaly import InProcessScorer
    return born_now(InProcessScorer(
        seed=seed, spec=lfm2_moe(Lfm2MoEConfig.from_config(config))))


def born_now(scorer):
    """The scorer, stamped with the phase clock's reading: ``state``
    counts the calls since, this scorer's own (the log is the process's,
    and a test's process has run others before)."""
    scorer.born = time.monotonic()
    return scorer


def state_sample(scorer) -> Kept:
    """What the layers keep of the resident flows with the smallest keys,
    as host arrays: ``kept``, a layer's ``[flows, positions, 2 x kv heads
    x head]`` (an attention layer: the cache lies ``[entry, positions]``
    on the device) or ``[flows, taps - 1, hidden]`` (a convolution
    layer), float32; and ``length [flows]``."""
    table = scorer._table
    keys = sorted(table.slot_of)[:CACHE_FLOWS]
    slots = np.array([table.slot_of[k] for k in keys], np.int32)
    layers, length = scorer._state[:2]
    kinds = scorer.cfg.layer_types
    kept = Kept(f"the state of {len(keys)} flows in {len(kinds)} layers")
    kept.arrays = {
        "keys": np.array(keys, np.int64),
        "kept": [np.asarray(a[slots], np.float32) if kind == "conv"
                 else np.asarray(a[slots], np.float32).transpose(0, 2, 1)
                 for kind, a in zip(kinds, layers)],
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
