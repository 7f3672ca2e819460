"""A fault for the tests and the chip readings, never for a run of the
benchmark: Sinkhorn's normalisation skipped: ``H_res = exp(a_res)`` as it
is."""

from chipbench.entries import hy4_scorer as base
from chipbench.entries.hy4_scorer import *  # noqa: F401,F403


def build(config, seed):
    return base.build({**config, "model": {
        **config["model"], "hc_sinkhorn_iterations": 0}}, seed)
