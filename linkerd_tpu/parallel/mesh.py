"""Mesh + sharding for the anomaly model: dp x tp GSPMD.

TPU-first design (scaling-book recipe): pick a mesh, annotate shardings with
NamedSharding/PartitionSpec, let XLA insert the collectives (all-gather /
reduce-scatter / psum ride ICI), profile, iterate. We do NOT hand-write
collectives for the MLP: GSPMD partitioning of Megatron-style column/row
parallel matmuls is exactly what the compiler does from the specs below.

Axes:
- ``data``  — batch-dim data parallelism (gradient psum inserted by XLA).
- ``model`` — tensor parallelism over hidden dims: encoder layer i alternates
  column-/row-parallel so activations stay sharded between layers.

Sequence/pipeline/expert parallelism intentionally do not apply at this
model's scale (per-request feature vectors, no sequence dim, single dense
model — SURVEY.md §5 "Long-context" scopes ring-attention/Ulysses out);
the mesh machinery here is what a wider model family would extend.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from linkerd_tpu.models.anomaly import (
    AnomalyModelConfig, Params, init_params, anomaly_scores, loss_fn,
    normalize_features,
)


# Per-shard hidden width below which tensor parallelism is pure
# all-gather overhead: at MLP scale (256-wide layers) the matmul per
# shard is microseconds while the collective latency is not — the
# scaling-book rule that the model axis only pays when each shard still
# saturates the MXU (round-3 BENCH: dp4xtp2 was 1.8x SLOWER than one
# device). SURVEY.md §2.4: "no TP/PP needed at MLP scale but the design
# should allow shard_map sharding of wide layers".
MIN_TP_SHARD_WIDTH = 2048


def make_mesh(
    devices: Optional[list] = None,
    tp: Optional[int] = None,
    axis_names: Tuple[str, str] = ("data", "model"),
    model_width: Optional[int] = None,
) -> Mesh:
    """Build a dp x tp mesh over ``devices`` (default: all local devices).

    ``tp`` defaults to 1 (pure data parallelism) unless ``model_width``
    is given and wide enough that each model shard stays above
    ``MIN_TP_SHARD_WIDTH``; callers override ``tp`` for real topologies.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if tp is None:
        tp = 1
        if (model_width is not None and n % 2 == 0 and n > 1
                and model_width // 2 >= MIN_TP_SHARD_WIDTH):
            tp = 2
    if n % tp != 0:
        raise ValueError(f"device count {n} not divisible by tp={tp}")
    arr = np.array(devices).reshape(n // tp, tp)
    return Mesh(arr, axis_names)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Batch rows over the data axis; feature dim replicated (a fit's
    labels and masks, ``ndim`` 1, have none)."""
    return NamedSharding(mesh, P("data", *(None,) * (ndim - 1)))


def shard_batch(mesh: Mesh, x: np.ndarray) -> jax.Array:
    """Per-device shard feed: place each device's OWN batch shard and
    assemble the global array with
    ``jax.make_array_from_single_device_arrays``.

    The old path handed the full host batch to one ``jax.device_put``
    with a NamedSharding, which on weak-scaled meshes serializes the
    whole transfer through a single host-side staging pass (BENCH_r04:
    cpu8 weak-scaled LOST to cpu1). Here each device receives exactly
    its slice — transfers are per-shard and the assembly is metadata
    only. The batch dim must divide evenly over the data axis (callers
    pad via ``_pad_rows``; ``bucket_rows`` already rounds to a multiple
    of the mesh's data size). A fit's per-row vectors go the same way.
    """
    sh = batch_sharding(mesh, x.ndim)
    idx_map = sh.addressable_devices_indices_map(x.shape)
    shards = [jax.device_put(x[idx], d) for d, idx in idx_map.items()]
    return jax.make_array_from_single_device_arrays(x.shape, sh, shards)


def _layer_specs(n_layers: int, first_col: bool = True):
    """Alternating column-/row-parallel specs for a dense chain.

    Column-parallel layer: w [in, out] sharded (None, "model"), b sharded.
    Row-parallel layer: w sharded ("model", None), b replicated (XLA adds
    the psum over the contracted axis).
    """
    specs = []
    col = first_col
    for _ in range(n_layers):
        if col:
            specs.append({"w": P(None, "model"), "b": P("model")})
        else:
            specs.append({"w": P("model", None), "b": P()})
        col = not col
    return specs


def param_specs(params: Params) -> Params:
    """PartitionSpec pytree matching an anomaly-model param pytree."""
    return {
        "enc": _layer_specs(len(params["enc"])),
        "dec": _layer_specs(len(params["dec"])),
        "cls": _layer_specs(len(params["cls"])),
    }


def param_shardings(mesh: Mesh, params: Params) -> Params:
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        param_specs(params),
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(mesh: Mesh, params: Params) -> Params:
    return jax.device_put(params, param_shardings(mesh, params))


def make_score_step(
    mesh: Mesh, cfg: AnomalyModelConfig = AnomalyModelConfig(),
    donate: bool = False,
) -> Callable[..., jax.Array]:
    """Jitted scoring step: features [B, D] -> scores [B].

    With ``mu``/``var`` (replicated device arrays), feature
    normalization runs on device, fused ahead of the first matmul: each
    data-axis shard z-scores its own rows, the host never touches the
    batch (normalize_features' contract). Without them the step scores
    raw features (pre-normalized or synthetic-test input).

    With ``donate``, the input batch buffer is donated to the step
    (``donate_argnums``): the line-rate dispatcher hands the step a
    device array assembled by ``shard_batch`` and never touches it
    again, so XLA reuses the buffer instead of allocating per batch.
    Donated inputs must not be re-read after dispatch — JAX raises on
    reuse of a deleted buffer.
    """
    xs = batch_sharding(mesh)

    def score(params: Params, x: jax.Array, mu=None, var=None) -> jax.Array:
        x = jax.lax.with_sharding_constraint(x, xs)
        if mu is not None:
            x = normalize_features(x, mu, var)
        return anomaly_scores(params, x, cfg)

    if donate:
        return jax.jit(score, donate_argnums=(1,))
    return jax.jit(score)


def make_train_step(
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    cfg: AnomalyModelConfig = AnomalyModelConfig(),
):
    """Jitted train step over the dp x tp mesh.

    Gradients are averaged over "data" and hidden-dim partial sums reduced
    over "model" by XLA-inserted collectives; we only annotate shardings.
    ``mu``/``var`` (replicated) fold feature normalization into the step
    the same way make_score_step does — train and serve see identical
    normalized inputs.
    """
    xs = batch_sharding(mesh)
    vs = NamedSharding(mesh, P("data"))

    @jax.jit
    def train_step(params: Params, opt_state, x, labels, label_mask,
                   row_mask=None, mu=None, var=None):
        x = jax.lax.with_sharding_constraint(x, xs)
        labels = jax.lax.with_sharding_constraint(labels, vs)
        label_mask = jax.lax.with_sharding_constraint(label_mask, vs)
        if row_mask is not None:
            row_mask = jax.lax.with_sharding_constraint(row_mask, vs)
        if mu is not None:
            x = normalize_features(x, mu, var)
        loss, grads = jax.value_and_grad(loss_fn)(
            params, x, labels, label_mask, cfg, row_mask)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


def init_sharded(
    mesh: Mesh,
    key: jax.Array,
    optimizer: optax.GradientTransformation,
    cfg: AnomalyModelConfig = AnomalyModelConfig(),
):
    """Initialize params + opt state and place them per the tp specs."""
    params = shard_params(mesh, init_params(key, cfg))
    opt_state = optimizer.init(params)
    return params, opt_state


def place_snapshot(
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    params_host: Params,
    opt_leaves: Optional[list] = None,
):
    """Re-place a restored (host/numpy) checkpoint onto ``mesh`` with the
    same column/row specs as a fresh init, so a snapshot taken on any
    topology (single chip, other mesh shape) hot-swaps into this one.

    ``opt_leaves`` is the checkpoint's flattened optax state (tree_leaves
    order); the state *structure* is rebuilt from ``optimizer.init`` on
    the placed params — its leaf shardings are the authoritative
    placement for the restored leaves. Returns ``(params, opt_state)``.
    """
    params = shard_params(mesh, params_host)
    template = optimizer.init(params)
    if opt_leaves is None:
        return params, template
    t_leaves, treedef = jax.tree_util.tree_flatten(template)
    if len(opt_leaves) != len(t_leaves):
        raise ValueError(
            f"optimizer state mismatch: checkpoint has {len(opt_leaves)} "
            f"leaves, optimizer expects {len(t_leaves)}")
    placed = []
    for leaf, t in zip(opt_leaves, t_leaves):
        arr = np.asarray(leaf)
        if tuple(arr.shape) != tuple(t.shape):
            raise ValueError(
                f"optimizer leaf shape mismatch: checkpoint {arr.shape} "
                f"vs optimizer {tuple(t.shape)}")
        # param-shaped moments inherit the param NamedShardings via
        # zeros_like; fresh scalars (adam's count) come back with a
        # single-device placement — committing them there would make the
        # jitted train step see mixed device sets, so replicate instead
        sharding = (t.sharding if isinstance(t.sharding, NamedSharding)
                    else replicated(mesh))
        placed.append(jax.device_put(arr.astype(t.dtype), sharding))
    return params, jax.tree_util.tree_unflatten(treedef, placed)
