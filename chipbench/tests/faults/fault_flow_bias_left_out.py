"""A fault for the tests and the chip readings, never for a run of the
benchmark: the router's per-expert bias left out of the selection."""

from chipbench.entries import flow_scorer as base
from chipbench.entries.flow_scorer import *  # noqa: F401,F403


def build(config, seed):
    s = base.build(config, seed)
    for lp in s.params["layers"]:
        if "router_bias" in lp:
            lp["router_bias"] = lp["router_bias"] * 0
    return s
