"""A fault for the tests and the chip readings, never for a run of the
benchmark: the element-wise output gate left out of every layer: each head's
output goes to the output projection as it is, not times ``sigmoid(x wg)``."""

from chipbench.entries import hy4_scorer as base
from chipbench.entries.hy4_scorer import *  # noqa: F401,F403


def build(config, seed):
    s = base.build(config, seed)
    for lp in s.params["layers"]:
        del lp["wg"]
    return s
