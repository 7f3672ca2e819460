"""A fault for the tests and the chip readings, never for a run of the
benchmark: the four streams collapsed to one: no hyper-connection, the plain
residual ``h + F(h)``."""

from chipbench.entries import hy4_scorer as base
from chipbench.entries.hy4_scorer import *  # noqa: F401,F403


def build(config, seed):
    return base.build({**config, "enable_ihc": False}, seed)
