"""A fault for the tests, never for a run of the benchmark: a train step
that returns its state unchanged (the loss is still computed)."""

from chipbench.entries import inprocess_scorer as base
from chipbench.entries.inprocess_scorer import *  # noqa: F401,F403


def build(config, seed):
    s = base.build(config, seed)
    step = s._train_step

    def broken(params, opt_state, *rest):
        _, _, loss = step(params, opt_state, *rest)
        return params, opt_state, loss

    s._train_step = broken
    return s
