"""A fault for the tests and the chip readings, never for a run of the
benchmark: the selected experts' weights left unnormalised: their sigmoid
scores as they are, not over their sum."""

from chipbench.entries.lfm2_scorer import *  # noqa: F401,F403
from chipbench.tests.faults.fault_lfm2_bias_in_weights import (  # noqa: F401
    build_with, close,
)


def faulty_route(lp, cfg, x):
    import jax      # here: the compile cache is placed by now
    import jax.numpy as jnp
    xr = x.astype(jnp.bfloat16).astype(jnp.float32)
    s = jax.nn.sigmoid(jnp.dot(xr, lp["router"].astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + lp["router_bias"].astype(jnp.float32),
                           cfg.num_experts_per_tok)
    return idx, jnp.take_along_axis(s, idx, -1) * cfg.routed_scaling_factor


def build(config, seed):
    return build_with(config, seed, faulty_route)
