"""A fault for the tests and the chip readings, never for a run of the
benchmark: a restart flag that is not honoured: the flow goes on from the
cache it had."""

import numpy as np

from chipbench.entries import flow_scorer as base
from chipbench.entries.flow_scorer import *  # noqa: F401,F403


async def score(scorer, x):
    x = np.array(x)
    x[:, 1] = 0
    return await base.score(scorer, x)
