"""The control of the comparison: the plain reference put in the
program's place and computed in float8 (e4m3), the step below the
bfloat16 that the configurations state. ``--entry control_fp8`` runs a
cell with it; the comparison has to come out as not correct. No run of the
benchmark proper uses it, and its times mean nothing."""

from __future__ import annotations

import numpy as np

from chipbench.harness import bucket, shape_name

QUANT = "fp8"


def _ref():
    # not at import: the reference imports JAX, and the compile cache has
    # to be placed before that
    from chipbench.reference import mlp36
    return mlp36


class ReferenceScorer:
    def __init__(self, config: dict, seed: int):
        ref = _ref()
        self.model, self.tel = config["model"], config["telemeter"]
        self.params = ref.init(seed, self.model)
        self.opt = ref.adam_init(self.params)
        width = self.model["in_dim"]
        self.norm, self.norm_set = (np.zeros(width, np.float32),
                                    np.ones(width, np.float32)), False
        self.score_batches, self.fit_batches = {}, {}


def place_cache() -> str:
    from chipbench.entries import inprocess_scorer
    return inprocess_scorer.place_cache()


def build(config: dict, seed: int) -> ReferenceScorer:
    return ReferenceScorer(config, seed)


async def score(s: ReferenceScorer, x: np.ndarray) -> np.ndarray:
    key = str(bucket(len(x)))
    s.score_batches[key] = s.score_batches.get(key, 0) + 1
    return _ref().scores(s.params, s.norm, x, s.tel["reconWeight"], QUANT)


async def fit(s: ReferenceScorer, x, labels, mask) -> float:
    key = shape_name(len(x))
    s.fit_batches[key] = s.fit_batches.get(key, 0) + 1
    ref = _ref()
    s.norm = ref.norm_update(s.norm if s.norm_set else None, x, labels, mask,
                             s.tel["normMomentum"])
    s.norm_set = True
    states, losses, _ = ref.fit(s.params, s.opt, s.norm, x, labels, mask,
                                s.tel["fitSteps"], s.tel["learningRate"],
                                QUANT)
    s.params, s.opt = states[-1]
    return losses[-1]


def state(s: ReferenceScorer) -> dict:
    return {"score_path": "reference",
            "score_batches": s.score_batches, "fit_batches": s.fit_batches}


def snapshot(s: ReferenceScorer) -> dict:
    import jax
    host = lambda t: jax.tree_util.tree_map(np.asarray, t)  # noqa: E731
    return {"params": host(s.params), "t": int(s.opt["t"]),
            "m": host(s.opt["m"]),
            "v": host(s.opt["v"]), "norm": s.norm}


def close(s: ReferenceScorer) -> None:
    return None
