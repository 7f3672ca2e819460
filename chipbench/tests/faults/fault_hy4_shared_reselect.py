"""A fault for the tests and the chip readings, never for a run of the
benchmark: the shared layers make a selection of their own: every layer is a
full one, with an indexer of its own drawn from the seed, in place of
reusing the selection of the full layer before it."""

from chipbench.entries import hy4_scorer as base
from chipbench.entries.hy4_scorer import *  # noqa: F401,F403


def build(config, seed):
    return base.build({**config, "indexer_types": ["full"] * len(
        config["indexer_types"])}, seed)
