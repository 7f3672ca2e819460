"""A fault for the tests and the chip readings, never for a run of the
benchmark: the selection off: every event attends over every position it has
seen (index_topk past any flow), the indexer still computed."""

from chipbench.entries import hy4_scorer as base
from chipbench.entries.hy4_scorer import *  # noqa: F401,F403


def build(config, seed):
    return base.build({**config, "index_topk": 10 ** 9}, seed)
