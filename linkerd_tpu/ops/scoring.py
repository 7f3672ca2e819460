"""Fused anomaly-scoring Pallas kernel.

One kernel computes the whole autoencoder+classifier forward and the blended
score for a tile of the micro-batch: weights stay resident in VMEM across the
batch grid, activations never round-trip to HBM between layers, and the only
HBM traffic is the feature tile in and the score vector out. At micro-batch
scale (hundreds to a few thousand rows of 32 features) the model is far too
small to be MXU-bound — HBM traffic and kernel-launch overhead dominate — so
the fusion is the win (see /opt/skills/guides/pallas_guide.md).

``best_scorer`` selects by the platform the parameters live on: this
kernel on ``tpu``, the plain XLA path (``models.anomaly.anomaly_scores``)
everywhere else. There is no probe and no fallback: a kernel that Mosaic
refuses on the chip is an error the caller sees, not a quieter scorer.
CPU tests run the kernel with ``interpret=True``.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from linkerd_tpu.models.anomaly import (
    AnomalyModelConfig, Params, anomaly_scores, normalize_features,
)


def _flatten_layers(params: Params):
    """Flatten the param pytree into an ordered list of (w, b) pairs:
    encoder, decoder, then classifier."""
    out = []
    for group in ("enc", "dec", "cls"):
        for layer in params[group]:
            out.append((layer["w"], layer["b"]))
    return out


def _score_kernel(x_ref, *refs, n_enc: int, n_dec: int, n_cls: int,
                  recon_weight: float, compute_dtype: Any):
    """Pallas kernel body: refs = [w0, b0, w1, b1, ..., out_ref]."""
    out_ref = refs[-1]
    wb = refs[:-1]
    x32 = x_ref[...].astype(jnp.float32)
    x = x32.astype(compute_dtype)

    def run(h, lo, n, final_act):
        for i in range(n):
            w = wb[2 * (lo + i)][...].astype(compute_dtype)
            b = wb[2 * (lo + i) + 1][...].astype(compute_dtype)
            h = jnp.dot(h, w, preferred_element_type=jnp.float32).astype(
                compute_dtype) + b
            if final_act or i < n - 1:
                h = jnp.maximum(h, 0.0)
        return h

    z = run(x, 0, n_enc, final_act=True)
    recon = run(z, n_enc, n_dec, final_act=False)
    logits = run(z, n_enc + n_dec, n_cls, final_act=False)

    # reconstruction error against the ORIGINAL f32 input, matching
    # models.anomaly.anomaly_scores (not the bf16-rounded copy)
    err = jnp.mean(jnp.square(recon.astype(jnp.float32) - x32),
                   axis=-1, keepdims=True)
    recon_score = jnp.tanh(err)
    cls_score = jax.nn.sigmoid(logits.astype(jnp.float32))
    # out is [block_rows, 1]: keep 2-D so Mosaic uses the standard layout
    out_ref[...] = recon_weight * recon_score + (1.0 - recon_weight) * cls_score


def fused_anomaly_scores(
    params: Params,
    x: jax.Array,
    cfg: AnomalyModelConfig = AnomalyModelConfig(),
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Score ``x`` [B, D] -> [B] with the fused kernel.

    Ragged batches are zero-padded up to a multiple of ``block_rows`` and
    the padding rows sliced off the result. Weights are broadcast to every
    grid step (index_map -> block 0) so they load into VMEM once and stay
    resident.
    """
    orig_b, d = x.shape
    b = ((orig_b + block_rows - 1) // block_rows) * block_rows
    if b != orig_b:
        x = jnp.pad(x, ((0, b - orig_b), (0, 0)))
    layers = _flatten_layers(params)
    n_enc = len(params["enc"])
    n_dec = len(params["dec"])
    n_cls = len(params["cls"])

    flat_args = []
    in_specs = [
        pl.BlockSpec((block_rows, d), lambda i: (i, 0)),  # x tile
    ]
    for w, bia in layers:
        flat_args.append(w)
        in_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0)))
        flat_args.append(bia)
        in_specs.append(pl.BlockSpec(bia.shape, lambda i: (0,)))

    kernel = functools.partial(
        _score_kernel,
        n_enc=n_enc, n_dec=n_dec, n_cls=n_cls,
        recon_weight=cfg.recon_weight, compute_dtype=cfg.compute_dtype,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b // block_rows,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.float32),
        interpret=interpret,
        name="anomaly_score_fused",
    )(x, *flat_args)
    return out[:orig_b, 0]


def scorer_kind(platform: str) -> str:
    """Which implementation ``best_scorer`` builds for parameters on
    ``platform``: ``"fused_pallas"`` on a TPU, ``"xla"`` elsewhere."""
    return "fused_pallas" if platform == "tpu" else "xla"


def best_scorer(cfg: AnomalyModelConfig, platform: str,
                donate: bool = False):
    """Return the jitted scorer for parameters living on ``platform``
    (``jax.Device.platform``): the fused kernel on ``tpu``, plain XLA
    elsewhere. A compile error propagates to the caller.

    The returned fn is ``(params, x, mu=None, var=None) -> scores``:
    with mu/var, ``normalize_features`` runs on device ahead of the
    kernel (XLA fuses the z-score into the input tile load), so the
    host ships raw f32 features and never touches the batch.

    With ``donate``, the input batch (argument 1) is donated: the
    line-rate dispatch path hands the step a device-resident staging
    buffer it will never re-read, and XLA reuses that buffer for the
    step's temporaries/outputs instead of allocating fresh device
    memory per micro-batch. Donated buffers raise on re-read.
    """
    score_rows = (fused_anomaly_scores
                  if scorer_kind(platform) == "fused_pallas"
                  else anomaly_scores)

    def score(p, v, mu=None, var=None):
        if mu is not None:
            with jax.named_scope("normalize"):
                v = normalize_features(v, mu, var)
        with jax.named_scope("score_rows"):
            return score_rows(p, v, cfg)

    if donate:
        return jax.jit(score, donate_argnums=(1,))
    return jax.jit(score)
