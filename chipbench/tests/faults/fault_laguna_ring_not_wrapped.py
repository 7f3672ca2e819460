"""A fault for the tests and the chip readings, never for a run of the
benchmark: a ring that does not wrap: what a chunk brings past a ring's
end is not written (the part that would go on at the ring's start, and
every chunk of a flow that has passed the end), so the ring keeps a
flow's first positions for ever while the mask still reads it as a ring.
(The call itself attends over the right ring: the calls after it meet
the stale one.)"""

from chipbench.entries.laguna_scorer import *  # noqa: F401,F403
from chipbench.tests.faults.fault_laguna_window_off_by_one import build_with


def build(config, seed):
    import jax.numpy as jnp     # here: the compile cache is placed by now

    def unwrapped(apply):
        def faulty(lp, cfg, ring, start, h, call):
            y, new, counts = apply(lp, cfg, ring, start, h, call)
            S, _, P = ring.shape
            end = call.p0 % P + call.count      # past P: the chunk wraps
            lost = jnp.zeros((S,), jnp.int32).at[call.slot].set(
                jnp.where(call.p0 >= P, P, jnp.maximum(end - P, 0)),
                mode="drop")
            stale = jnp.arange(P)[None, None] < lost[:, None, None]
            return y, jnp.where(stale, ring, new), counts
        return faulty

    return build_with(config, seed, unwrapped)
