"""A fault for the tests and the chip readings, never for a run of the
benchmark: the window's far edge off by one block of the ring: a sliding
layer's events see the last ``window - block`` positions (384 of 512), as
a tile's loops would leave them that begin one block late. (Off by one
*position* moves an event's attention by 1 part in 512 of a layer that is
a hundredth of the stream: under the stated precision's own rounding, so
no comparison of outputs can show it; ``tests/test_laguna_moe.py`` holds
the mask to the position on the CPU.)"""

from chipbench.entries import laguna_scorer as base
from chipbench.entries.laguna_scorer import *  # noqa: F401,F403


def faulty_config(change, scope: str = "window_attention"):
    """``LagunaMoEConfig`` with the operator of the layers of ``scope``
    put through ``change(apply) -> apply``: this scorer's alone, nothing
    of the program is patched."""
    from linkerd_tpu.models.laguna_moe import LagunaMoEConfig

    class Faulty(LagunaMoEConfig):
        def operator(self, l):
            op = super().operator(l)
            return (op._replace(apply=change(op.apply))
                    if op.scope == scope else op)

    return Faulty


def build_with(config, seed, change, scope: str = "window_attention"):
    from linkerd_tpu.models.spec import laguna_moe
    from linkerd_tpu.telemetry.anomaly import InProcessScorer
    return base.born_now(InProcessScorer(seed=seed, spec=laguna_moe(
        faulty_config(change, scope).from_config(config))))


def build(config, seed):
    block = config["model"].get("ring_block", 128)

    def narrower(apply):
        def faulty(lp, cfg, ring, start, h, call):
            def attend(q, cache, slot, p0, scale, window):
                return call.attend(q, cache, slot, p0, scale,
                                   window=max(window - block, 1))
            return apply(lp, cfg, ring, start, h,
                         call._replace(attend=attend))
        return faulty

    return build_with(config, seed, narrower)
