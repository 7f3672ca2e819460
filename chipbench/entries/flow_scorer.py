"""How the flow configuration is put under test: the public
``InProcessScorer`` with the flow model's spec, built from the
configuration file, and one call of ``score`` (``fit`` raises: the model is
frozen). From the program the benchmark takes this object, its
``device_state()`` and, once the window has closed, the cache rows of a
few flows; nothing else."""

from __future__ import annotations

import numpy as np

CACHE_FLOWS = 4     # flows whose cache rows are kept for the comparison


class Kept(str):
    """What ``state`` keeps for the comparison beside what it reports: the
    result line prints the text, the check reads ``arrays``."""
    arrays: dict


def place_cache() -> str:
    from linkerd_tpu.compile_cache import place_compile_cache
    return place_compile_cache()


def build(config: dict, seed: int):
    from linkerd_tpu.models.latent_moe import LatentMoEConfig
    from linkerd_tpu.models.spec import latent_moe
    from linkerd_tpu.telemetry.anomaly import InProcessScorer
    return InProcessScorer(
        seed=seed, spec=latent_moe(LatentMoEConfig.from_config(config)))


async def score(scorer, x: np.ndarray) -> np.ndarray:
    return await scorer.score(x)


async def fit(scorer, x, labels, mask) -> float:
    return await scorer.fit(x, labels, mask)


def cache_sample(scorer) -> Kept:
    """The cache rows and lengths of the resident flows with the smallest
    keys, as host arrays ``cache [layers, flows, positions, entry]``
    float32 and ``length [flows]``."""
    table = scorer._table
    keys = sorted(table.slot_of)[:CACHE_FLOWS]
    slots = np.array([table.slot_of[k] for k in keys], np.int32)
    cache, length = scorer._state[:2]
    kept = Kept(f"the cache rows of {len(keys)} flows")
    kept.arrays = {
        "keys": np.array(keys, np.int64),
        "cache": np.stack([np.asarray(c[slots], np.float32) for c in cache]),
        "length": np.asarray(length[slots])}
    return kept


def state(scorer) -> dict:
    """``score_path``, the calls per compiled shape and layout, what the
    flow table counted over the run, and the cache sample."""
    from linkerd_tpu.telemetry import phases
    d = scorer.device_state()
    calls = phases.records()
    counted = {name: sum(c.counts.get(f"flow.{name}", 0) for c in calls)
               for name in ("evictions", "wraps", "restarts", "events")}
    return {"score_path": d["score_path"],
            "score_batches": d["score_batches"],
            "fit_batches": d["fit_batches"],
            "flow": {**d["flow"], **counted},
            "cache_sample": cache_sample(scorer)}


def snapshot(scorer) -> dict:
    """Light: the flows resident and their lengths, no weights (7 GB are
    not brought to the host for a set-up the comparison does not read)."""
    table = scorer._table
    return {"resident": len(table.slot_of),
            "positions": int(table.length.sum())}


def close(scorer) -> None:
    scorer.close()
