"""Autoencoder + MLP-classifier anomaly model, TPU-first plain-JAX pytrees.

The model scores per-request feature vectors (see ``features.py``): the
autoencoder's reconstruction error catches novel traffic patterns without
labels, and a small classifier head on the bottleneck is trained on
fault-injected labeled traces (BASELINE.md config 3). The blended score feeds
failure-accrual / response-classification policy in the router.

TPU-first design notes:
- Parameters are a flat dict-of-dicts pytree; all ops are batched matmuls so
  XLA tiles them onto the MXU; compute runs in bfloat16 with float32 params
  and accumulation (``cfg.compute_dtype``).
- Hidden widths are multiples of 128 (MXU lane width).
- No Python control flow inside jitted fns; label masking is arithmetic.
- Sharding is applied externally via jax.sharding (see parallel/mesh.py):
  hidden axes shard over the "model" mesh axis, batch over "data".
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from linkerd_tpu.models.features import FEATURE_DIM

Params = Dict[str, Any]


@dataclass(frozen=True)
class AnomalyModelConfig:
    in_dim: int = FEATURE_DIM
    enc_dims: Tuple[int, ...] = (256, 128)
    bottleneck: int = 32
    cls_hidden: int = 128
    compute_dtype: Any = jnp.bfloat16
    # blend of normalized reconstruction error vs classifier probability
    recon_weight: float = 0.5


def _dense_init(key: jax.Array, in_dim: int, out_dim: int) -> Params:
    wkey, _ = jax.random.split(key)
    scale = jnp.sqrt(2.0 / in_dim)
    return {
        "w": (jax.random.normal(wkey, (in_dim, out_dim)) * scale).astype(jnp.float32),
        "b": jnp.zeros((out_dim,), jnp.float32),
    }


def init_params(key: jax.Array, cfg: AnomalyModelConfig = AnomalyModelConfig()) -> Params:
    dims_enc = (cfg.in_dim,) + cfg.enc_dims + (cfg.bottleneck,)
    dims_dec = tuple(reversed(dims_enc))
    keys = jax.random.split(key, len(dims_enc) - 1 + len(dims_dec) - 1 + 2)
    ki = iter(keys)
    params: Params = {"enc": [], "dec": [], "cls": []}
    for i in range(len(dims_enc) - 1):
        params["enc"].append(_dense_init(next(ki), dims_enc[i], dims_enc[i + 1]))
    for i in range(len(dims_dec) - 1):
        params["dec"].append(_dense_init(next(ki), dims_dec[i], dims_dec[i + 1]))
    params["cls"].append(_dense_init(next(ki), cfg.bottleneck, cfg.cls_hidden))
    params["cls"].append(_dense_init(next(ki), cfg.cls_hidden, 1))
    return params


def normalize_features(x: jax.Array, mu: jax.Array, var: jax.Array) -> jax.Array:
    """On-device feature normalization: z-scores with a soft variance
    floor of 1e-2 (a near-constant training dim must register novelty as
    a LARGE z-score, but not a 1e3-sigma blowup that swamps every other
    dim — hard clipping cost ~0.15 AUC on the k8s-restart benchmark).

    Folded into the jitted score/train steps (``ops/scoring.best_scorer``,
    ``parallel/mesh.make_score_step``/``make_train_step``) when mu/var
    are passed: raw f32 features ship as-is and XLA fuses the
    normalization into the first matmul's producer, so the sharded path
    normalizes each batch shard on its own device instead of one host
    thread doing the whole weak-scaled batch. The shadow evaluator
    (``lifecycle/promote.evaluate_snapshot``) applies the same function
    with the candidate snapshot's stats."""
    return (x - mu) * jax.lax.rsqrt(var + 1e-2)


def running_norm(norm, x: jax.Array, labels: jax.Array, mask: jax.Array,
                 row_mask: Optional[jax.Array] = None, *, momentum: float):
    """The running normalization statistics after one fit's batch:
    ``norm`` is ``(mu, var, initialised)``, the result the same triple.

    Mean and variance are taken over the rows NOT labelled anomalous
    (``mask == 0`` or ``labels == 0``; ``row_mask`` leaves a padded
    batch's padding rows out as well), in float32 and in two passes — the
    variance about the batch's own mean, because raw columns run into the
    thousands and E[x^2] - E[x]^2 would cancel — plus 1e-6. The first
    batch with such a row sets the pair, later ones blend in with
    ``momentum``; a batch with none leaves pair and flag as they were.
    Everything, the flag included, is decided on the device: the caller
    never waits to learn it. Jitted by ``InProcessScorer`` as a program of
    its own, on the batch its train steps read; sharded over ``data`` the
    sums reduce across the mesh."""
    mu0, var0, initialized = norm
    w = ((mask == 0.0) | (labels == 0.0)).astype(jnp.float32)
    if row_mask is not None:
        w = w * row_mask
    w = w[:, None]
    count = jnp.sum(w)
    seen = count > 0
    n = jnp.maximum(count, 1.0)
    mu = jnp.sum(w * x, axis=0) / n
    var = jnp.sum(w * jnp.square(x - mu), axis=0) / n + 1e-6
    blend = seen & initialized
    mu = jnp.where(blend, (1 - momentum) * mu0 + momentum * mu, mu)
    var = jnp.where(blend, (1 - momentum) * var0 + momentum * var, var)
    return (jnp.where(seen, mu, mu0), jnp.where(seen, var, var0),
            initialized | seen)


def _mlp(layers, x: jax.Array, dtype, final_act: bool) -> jax.Array:
    n = len(layers)
    for i, layer in enumerate(layers):
        x = x @ layer["w"].astype(dtype) + layer["b"].astype(dtype)
        if final_act or i < n - 1:
            x = jax.nn.relu(x)
    return x


def apply_model(
    params: Params, x: jax.Array, cfg: AnomalyModelConfig = AnomalyModelConfig()
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Forward pass.

    Returns ``(recon, z, logits)``: reconstruction [B, D] (float32), bottleneck
    [B, Z], classifier logits [B].
    """
    dt = cfg.compute_dtype
    h = x.astype(dt)
    z = _mlp(params["enc"], h, dt, final_act=True)
    recon = _mlp(params["dec"], z, dt, final_act=False)
    logits = _mlp(params["cls"], z, dt, final_act=False)[..., 0]
    return recon.astype(jnp.float32), z.astype(jnp.float32), logits.astype(jnp.float32)


def anomaly_scores(
    params: Params, x: jax.Array, cfg: AnomalyModelConfig = AnomalyModelConfig()
) -> jax.Array:
    """Blended anomaly score in [0, 1] per row: sigmoid-squashed normalized
    reconstruction error blended with classifier probability."""
    recon, _, logits = apply_model(params, x, cfg)
    err = jnp.mean(jnp.square(recon - x), axis=-1)
    # squash reconstruction MSE into (0,1); tanh keeps gradients tame
    recon_score = jnp.tanh(err)
    cls_score = jax.nn.sigmoid(logits)
    return cfg.recon_weight * recon_score + (1.0 - cfg.recon_weight) * cls_score


def loss_fn(
    params: Params,
    x: jax.Array,
    labels: jax.Array,
    label_mask: jax.Array,
    cfg: AnomalyModelConfig = AnomalyModelConfig(),
    row_mask: jax.Array = None,
) -> jax.Array:
    """Reconstruction MSE + masked BCE on labeled rows.

    ``labels`` in {0,1} float, ``label_mask`` 1.0 where the row is labeled
    (fault-injection traces) and 0.0 for unlabeled traffic. ``row_mask``
    (1.0 = real row) excludes padding rows added for mesh divisibility
    from BOTH loss terms; None means all rows are real. Pure arithmetic —
    no data-dependent control flow, so it jits to one fused XLA
    computation.
    """
    import optax

    recon, _, logits = apply_model(params, x, cfg)
    sq = jnp.mean(jnp.square(recon - x), axis=-1)
    if row_mask is None:
        recon_loss = jnp.mean(sq)
    else:
        recon_loss = (jnp.sum(sq * row_mask)
                      / jnp.maximum(jnp.sum(row_mask), 1.0))
        label_mask = label_mask * row_mask
    bce = optax.sigmoid_binary_cross_entropy(logits, labels)
    denom = jnp.maximum(jnp.sum(label_mask), 1.0)
    cls_loss = jnp.sum(bce * label_mask) / denom
    return recon_loss + cls_loss
