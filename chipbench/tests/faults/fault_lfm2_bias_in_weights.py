"""A fault for the tests and the chip readings, never for a run of the
benchmark: the selection bias leaking into the weights: the selected
experts are weighed by score + bias over their sum, not by their scores.
The program's ``route`` is replaced from ``build`` to ``close``; the step
is traced in between, and JAX's caches are cleared at both ends, so that
neither this scorer takes a sound trace nor a later one this."""

from chipbench.entries import lfm2_scorer as base
from chipbench.entries.lfm2_scorer import *  # noqa: F401,F403


def faulty_route(lp, cfg, x):
    import jax      # here: the compile cache is placed by now
    import jax.numpy as jnp
    xr = x.astype(jnp.bfloat16).astype(jnp.float32)
    s = jax.nn.sigmoid(jnp.dot(
        xr, lp["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)) + lp["router_bias"].astype(
            jnp.float32)
    sel, idx = jax.lax.top_k(s, cfg.num_experts_per_tok)
    return idx, (sel / (sel.sum(-1, keepdims=True) + cfg.route_eps)
                 * cfg.routed_scaling_factor)


def build_with(config, seed, route):
    from linkerd_tpu.models import latent_moe as lm
    s = base.build(config, seed)
    s._sound_route, lm.route = lm.route, route
    import jax
    jax.clear_caches()
    return s


def build(config, seed):
    return build_with(config, seed, faulty_route)


def close(scorer):
    from linkerd_tpu.models import latent_moe as lm
    lm.route = scorer._sound_route
    import jax
    jax.clear_caches()
    base.close(scorer)
