"""A fault for the tests and the chip readings, never for a run of the
benchmark: a restart that clears the cache and keeps the convolutions'
tails: the new flow's first chunk is convolved behind the old flow's last
two rows, not behind the start token's."""

from chipbench.entries.lfm2_scorer import *  # noqa: F401,F403
from chipbench.tests.faults.fault_lfm2_tail_not_carried import build_with


def build(config, seed):
    import jax.numpy as jnp     # here: the compile cache is placed by now
    return build_with(config, seed, lambda apply: (
        lambda lp, cfg, tail, start_tail, h, call: apply(
            lp, cfg, tail, start_tail, h,
            call._replace(begins=jnp.zeros_like(call.begins)))))
