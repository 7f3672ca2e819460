"""A fault for the tests and the chip readings, never for a run of the
benchmark: a restart that clears the caches and keeps the rings: a
sliding layer does not write the start token's entry where a flow begins,
so the new flow's events see, as position 0, what the flow before left at
a ring's index 0."""

from chipbench.entries.laguna_scorer import *  # noqa: F401,F403
from chipbench.tests.faults.fault_laguna_window_off_by_one import build_with


def build(config, seed):
    import jax.numpy as jnp     # here: the compile cache is placed by now
    return build_with(config, seed, lambda apply: (
        lambda lp, cfg, ring, start, h, call: apply(
            lp, cfg, ring, start, h,
            call._replace(begins=jnp.zeros_like(call.begins)))))
