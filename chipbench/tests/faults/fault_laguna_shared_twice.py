"""A fault for the tests and the chip readings, never for a run of the
benchmark: the shared expert counted twice in every expert layer."""

from chipbench.entries import laguna_scorer as base
from chipbench.entries.laguna_scorer import *  # noqa: F401,F403


def build(config, seed):
    s = base.build(config, seed)
    for lp in s.params["layers"]:
        if "shared_down" in lp:
            lp["shared_down"] = lp["shared_down"] * 2
    return s
