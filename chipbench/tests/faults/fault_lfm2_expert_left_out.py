"""A fault for the tests and the chip readings, never for a run of the
benchmark: one expert (the second) left out of every expert layer's routed
sum (its down-projection nought)."""

from chipbench.entries import lfm2_scorer as base
from chipbench.entries.lfm2_scorer import *  # noqa: F401,F403


def build(config, seed):
    s = base.build(config, seed)
    for lp in s.params["layers"]:
        if "exp_down" in lp:
            lp["exp_down"] = lp["exp_down"].at[1].set(0)
    return s
