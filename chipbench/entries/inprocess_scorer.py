"""How a configuration of ``io.l5d.jaxAnomaly`` is put under test: the
public ``InProcessScorer`` built as ``JaxAnomalyTelemeter._mk_inprocess``
builds it, and one call each of ``score`` and ``fit``: what both the
in-process telemeter and the sidecar's ``Score``/``Fit`` handlers call.
From the program the benchmark takes this object, its ``device_state()``
counts and its ``snapshot()``; nothing else."""

from __future__ import annotations

import numpy as np

# the score path a platform has to report, or the run is not the one timed
EXPECT_SCORE_PATH = {"tpu": "fused_pallas"}


def place_cache() -> str:
    """Before the first ``import jax``: the program's one compile cache."""
    from linkerd_tpu.compile_cache import place_compile_cache
    return place_compile_cache()


def build(config: dict, seed: int):
    from linkerd_tpu.telemetry.anomaly import InProcessScorer
    t = config["telemeter"]
    return InProcessScorer(seed=seed, learning_rate=t["learningRate"],
                           recon_weight=t["reconWeight"])


async def score(scorer, x: np.ndarray) -> np.ndarray:
    return await scorer.score(x)


async def fit(scorer, x, labels, mask) -> float:
    return await scorer.fit(x, labels, mask)


def state(scorer) -> dict:
    """``score_path`` and the calls per compiled shape."""
    d = scorer.device_state()
    return {"score_path": d["score_path"],
            "score_batches": d["score_batches"],
            "fit_batches": d["fit_batches"]}


def snapshot(scorer) -> dict:
    """Parameters and Adam moments as host arrays, in the reference's
    layout (``{group: [{"w", "b"}, ...]}``)."""
    import jax
    snap = scorer.snapshot()
    params = jax.tree_util.tree_map(np.asarray, snap.params)
    treedef = jax.tree_util.tree_structure(params)
    n = treedef.num_leaves
    # optax.adam's state flattens to its count, then the first moments in
    # the parameters' own order, then the second moments
    leaves = [np.asarray(a) for a in snap.opt_leaves]
    if len(leaves) != 1 + 2 * n:
        raise ValueError(f"{len(leaves)} optimizer leaves for {n} parameters")
    return {"params": params, "t": int(leaves[0]),
            "m": treedef.unflatten(leaves[1:1 + n]),
            "v": treedef.unflatten(leaves[1 + n:]),
            "norm": (np.asarray(snap.mu), np.asarray(snap.var))}


def close(scorer) -> None:
    scorer.close()
