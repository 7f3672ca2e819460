"""The model seam of the scorer path: what ``InProcessScorer`` needs to
know of a model, and nothing of its insides.

A ``ModelSpec`` says what a row is (``row_width``, ``row_dtype``), draws
the parameters (``init``), makes the state a score step reads beside them
(``init_state``) and builds the jitted score step for a platform
(``make_step``). Every model's step has one signature::

    step(params, state, rows, n, layout) -> (scores, state, counts)

``rows`` is the staged batch on the device (the step may donate it), rows
at and past ``n`` are padding, ``counts`` a dict of small arrays that
rides back with the scores. A step that advances its state takes it
donated and hands back the new one; a step that only reads it hands back
the object it was given, and the scorer then stores nothing. ``layout`` is
the table's, None where the model has no table.

Three attributes say what else the scorer may do with the model, each on
its own: ``trains`` (``fit`` and the optimizer's half of a snapshot
exist), ``single_device`` (``parallel/mesh.py`` has no layout for it) and
``make_table``: a model whose rows carry a key brings the host's table
that lays a call's rows out for the step (``map`` -> a plan with ``rows``,
``layout`` and ``counts``; ``checkpoint`` / ``rollback``). Such a model is
``keyed``: its calls apply in the order they were made, and ``describe``
reports the table and the newest call's counts for ``device_state()``.

Five instances: ``mlp36`` (the 36-column autoencoder + classifier: its
state is the normalisation triple ``(mu, var, initialised)``, which a fit
repoints and a score step only reads; trains online; sharded over a mesh)
and the flow models ``latent_moe`` (latent attention over a per-flow
cache, routed experts beside a shared one), ``lfm2_moe`` (short
convolutions among grouped-query attention layers, so two kinds of
per-flow state, routed experts alone) and ``laguna_moe`` (window and full
attention layers mixed: a ring of the newest positions beside a cache of
them all, routed experts beside a shared one) and ``hy4_moe`` (latent
attention over an indexer's selection, whose choice layers hand on, and a
residual stream four wide): keyed, frozen,
single-device, one step (``models/latent_moe.flow_step``) over any's
layers.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import numpy as np


@dataclass(frozen=True)
class ModelSpec:
    name: str
    cfg: Any
    row_width: int
    row_dtype: Any
    trains: bool
    single_device: bool
    init: Callable[[Any], Any]
    init_state: Callable[[], Any]
    make_step: Callable[[str], Callable]
    score_path: Callable[[str], str]    # the step's name on a platform
    make_table: Optional[Callable[[], Any]] = None
    describe: Optional[Callable[[Any, dict], dict]] = None

    @property
    def keyed(self) -> bool:
        return self.make_table is not None


def reads_norm(score: Callable) -> Callable:
    """``score(params, rows, mu, var) -> scores`` (the single-device
    kernel, or ``parallel/mesh.make_score_step``'s) under the seam's
    signature: the state is the normalisation triple, read and handed back
    as it came."""
    def step(params, state, rows, n, layout):
        return score(params, rows, state[0], state[1]), state, {}
    return step


def mlp36(recon_weight: float = 0.7) -> ModelSpec:
    """Today's model: a feature row is 36 float32 columns, scored on its
    own (padding rows are computed and sliced off). ``init_state`` gives
    the triple as host arrays: the scorer places it, replicated over a
    mesh."""
    from linkerd_tpu.models.anomaly import AnomalyModelConfig, init_params

    cfg = AnomalyModelConfig(recon_weight=recon_weight)

    def init_state():
        return (np.zeros(cfg.in_dim, np.float32),
                np.ones(cfg.in_dim, np.float32), np.bool_(False))

    # the kernel's module is imported where a step is built, not where a
    # spec is only looked at
    def make_step(platform: str):
        from linkerd_tpu.ops.scoring import best_scorer
        return reads_norm(best_scorer(cfg, platform, donate=True))

    def score_path(platform: str) -> str:
        from linkerd_tpu.ops.scoring import scorer_kind
        return scorer_kind(platform)

    return ModelSpec(
        name="mlp36", cfg=cfg, row_width=cfg.in_dim, row_dtype=np.float32,
        trains=True, single_device=False,
        init=lambda key: init_params(key, cfg), init_state=init_state,
        make_step=make_step, score_path=score_path)


def compiled_text(program: Callable, args: tuple, static: dict
                  ) -> Callable[[], str]:
    """A thunk that gives the optimised text of the jitted ``program`` as
    it runs on ``args`` (``phases.program``'s ``describe``). It holds the
    arguments' shapes, types and placement, not the arrays: a committed
    array's sharding, nothing for an uncommitted one, so that the lowering
    is the call's own and its compile a hit in JAX's caches."""
    import jax

    def like(a):
        return jax.ShapeDtypeStruct(
            np.shape(a), a.dtype, weak_type=getattr(a, "weak_type", False),
            sharding=a.sharding if getattr(a, "committed", False) else None)

    shapes = jax.tree_util.tree_map(like, args)
    return lambda: program.lower(*shapes, **static).compile().as_text()


def _flow_model(name: str, cfg, grouped: bool,
                sparse: bool = False) -> ModelSpec:
    """A flow model's spec: a row is int32 ``(stream key, restart flag,
    event id)``, laid out by ``FlowTable``; what the layers keep of a
    flow, the flows' lengths and the start token's constants are the
    state, donated to each step. The step is ``models/latent_moe.
    flow_step`` over the configuration's layers, built with the attention
    its platform gets (``ops/flow_attention.best_attention``: the fused
    kernel on a TPU, XLA's elsewhere; ``grouped``: over keys and values
    in groups of heads, else over the latent; ``sparse``: over the latent,
    a selection of each event's positions), with the routed experts'
    grouped product its platform gets
    (``ops/expert_product.best_expert_product``, likewise) and with the
    append of a chunk to a layer's state its platform gets
    (``ops/cache_append.best_append``, likewise), and ``describe`` says
    which."""
    import jax

    from linkerd_tpu.models import latent_moe as lm
    from linkerd_tpu.telemetry import phases
    from linkerd_tpu.telemetry.flowstate import FlowTable

    built = {}      # what make_step chose, for describe

    def make_step(platform: str):
        # the kernel's module is imported where a step is built
        from linkerd_tpu.ops.cache_append import append_kind, best_append
        from linkerd_tpu.ops.expert_product import (
            best_expert_product, expert_product_kind)
        from linkerd_tpu.ops.flow_attention import (
            attention_call, attention_kind, best_attention)
        attend = (best_attention(platform, sparse=True) if sparse
                  else best_attention(platform, grouped))
        experts = best_expert_product(platform)
        append = best_append(platform)
        built["attention"] = attention_kind(platform)
        built["append"] = append_kind(platform)
        built["state"] = {
            op.scope: {"positions": op.ring or cfg.positions,
                       "call": attention_call(platform, grouped,
                                              bool(op.ring), sparse)}
            for op in map(cfg.operator, range(cfg.layers)) if op.caches}
        built["expert_product"] = expert_product_kind(platform)
        # the state and the staged rows are the program's to reuse
        program = jax.jit(
            lm.flow_step,
            static_argnames=("cfg", "F", "T", "attend", "experts", "append"),
            donate_argnums=(1, 2))
        registered = set()      # the layouts whose program is registered

        def step(params, state, rows, n, layout):
            def run(state, rows, n):
                args = (params, state, rows, np.int32(n))
                static = {"cfg": cfg, "F": layout[0], "T": layout[1],
                          "attend": attend, "experts": experts,
                          "append": append}
                if (layout, rows.shape) not in registered:
                    registered.add((layout, rows.shape))
                    phases.program("jit_flow_step",
                                   compiled_text(program, args, static))
                return program(*args, **static)
            if state[-1] is None:
                # once: the same program, on arguments like this call's
                state = lm.with_start(run, cfg, state, rows)
            return run(state, rows, n)
        return step

    def describe(table, counts: dict) -> dict:
        tokens = counts.get("expert_tokens")
        return {"flow": {
            "slots": cfg.slots, "positions": cfg.positions,
            "experts_held": list(cfg.experts_held),
            "layer_share": cfg.layer_share,
            "attention": built.get("attention"),
            "append": built.get("append"),
            # a kind of layer: the positions a slot keeps, the call made
            "state": built.get("state"),
            "expert_product": built.get("expert_product"),
            "resident": len(table.slot_of),
            "layouts": {f"{f}x{t}": c
                        for (f, t), c in sorted(table.layouts.items())},
            "expert_tokens": None if tokens is None else tokens.tolist()}}

    return ModelSpec(
        name=name, cfg=cfg, row_width=3, row_dtype=np.int32,
        trains=False, single_device=True,
        init=lambda key: lm.init(key, cfg),
        init_state=lambda: lm.init_state(cfg), make_step=make_step,
        score_path=lambda platform: name,
        make_table=lambda: FlowTable(cfg.slots, cfg.positions,
                                     cfg.vocab_slice),
        describe=describe)


def latent_moe(cfg=None) -> ModelSpec:
    """The flow model of ``models/latent_moe.py``: latent attention over
    one cache a layer, routed experts beside a shared one."""
    from linkerd_tpu.models.latent_moe import LatentMoEConfig
    return _flow_model("latent_moe",
                       cfg if cfg is not None else LatentMoEConfig(), False)


def lfm2_moe(cfg=None) -> ModelSpec:
    """The flow model of ``models/lfm2_moe.py``: short convolutions among
    grouped-query attention layers (two kinds of per-flow state), routed
    experts with no shared one, the whole vocabulary."""
    from linkerd_tpu.models.lfm2_moe import Lfm2MoEConfig
    return _flow_model("lfm2_moe",
                       cfg if cfg is not None else Lfm2MoEConfig(), True)


def laguna_moe(cfg=None) -> ModelSpec:
    """The flow model of ``models/laguna_moe.py``: window and full
    attention layers mixed (a ring beside a cache), routed experts beside
    a shared one, the whole vocabulary through a head of its own."""
    from linkerd_tpu.models.laguna_moe import LagunaMoEConfig
    return _flow_model("laguna_moe",
                       cfg if cfg is not None else LagunaMoEConfig(), True)


def hy4_moe(cfg=None) -> ModelSpec:
    """The flow model of ``models/hy4_moe.py``: latent attention over an
    indexer's selection of each event's positions (a second array of
    state on the layers that index), a sink and a gate, a residual stream
    four wide, routed experts beside a shared one."""
    from linkerd_tpu.models.hy4_moe import Hy4MoEConfig
    return _flow_model("hy4_moe",
                       cfg if cfg is not None else Hy4MoEConfig(), False,
                       sparse=True)


SPECS = {"mlp36": mlp36, "latent_moe": latent_moe, "lfm2_moe": lfm2_moe,
         "laguna_moe": laguna_moe, "hy4_moe": hy4_moe}
