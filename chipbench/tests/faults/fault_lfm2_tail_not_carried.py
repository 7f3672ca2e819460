"""A fault for the tests and the chip readings, never for a run of the
benchmark: a convolution's tail not carried across calls: a chunk that
goes on a flow is convolved behind zeros, not behind the flow's last two
rows (a flow that begins still meets the start token's tail)."""

from chipbench.entries import lfm2_scorer as base
from chipbench.entries.lfm2_scorer import *  # noqa: F401,F403


def faulty_config(change):
    """``Lfm2MoEConfig`` with the convolution layers' operator put through
    ``change(apply) -> apply``: this scorer's alone, nothing of the
    program is patched."""
    from linkerd_tpu.models.lfm2_moe import Lfm2MoEConfig

    class Faulty(Lfm2MoEConfig):
        def operator(self, l):
            op = super().operator(l)
            return (op._replace(apply=change(op.apply))
                    if op.scope == "conv" else op)

    return Faulty


def build_with(config, seed, change):
    from linkerd_tpu.models.spec import lfm2_moe
    from linkerd_tpu.telemetry.anomaly import InProcessScorer
    return base.born_now(InProcessScorer(seed=seed, spec=lfm2_moe(
        faulty_config(change).from_config(config))))


def build(config, seed):
    import jax.numpy as jnp     # here: the compile cache is placed by now
    return build_with(config, seed, lambda apply: (
        lambda lp, cfg, tail, start_tail, h, call: apply(
            lp, cfg, jnp.zeros_like(tail), start_tail, h, call)))
