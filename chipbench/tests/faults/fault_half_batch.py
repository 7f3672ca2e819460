"""A fault for the tests and the chip readings, never for a run of the
benchmark: half of the batch left out of every train step, the mean taken
over the rest."""

from chipbench.entries import inprocess_scorer as base
from chipbench.entries.inprocess_scorer import *  # noqa: F401,F403


def build(config, seed):
    s = base.build(config, seed)
    step = s._train_step

    def broken(params, opt_state, x, labels, mask, row_mask, mu, var):
        h = len(x) // 2
        rm = None if row_mask is None else row_mask[:h]
        return step(params, opt_state, x[:h], labels[:h], mask[:h], rm,
                    mu, var)

    s._train_step = broken
    return s
