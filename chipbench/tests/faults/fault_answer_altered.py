"""A fault for the tests and the chip readings, never for a run of the
benchmark: an answer altered where it is produced, one row of every call."""

import numpy as np

from chipbench.entries import inprocess_scorer as base
from chipbench.entries.inprocess_scorer import *  # noqa: F401,F403


async def score(scorer, x):
    out = np.array(await base.score(scorer, x))
    out[(len(out) * 2) // 3] += 0.5
    return out
