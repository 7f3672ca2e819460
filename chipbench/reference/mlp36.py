"""Plain reference of the 36-column autoencoder + classifier scorer.

The architecture as ``configs/*.json`` state it under ``model``: an encoder
36 -> 256 -> 128 -> 32 (ReLU after every layer), a mirrored decoder (ReLU
between layers, none at the end), a classifier head 32 -> 128 -> 1 on the
bottleneck; the score of a row is ``w * tanh(mean((recon - z)^2)) +
(1 - w) * sigmoid(logit)`` on the z-scored row ``z``; the loss is the mean
reconstruction error plus binary cross-entropy over the labelled rows; the
optimizer is Adam (b1 0.9, b2 0.999, eps 1e-8, no weight decay).

Straight ``jax.numpy`` in float32 with matmuls at ``highest`` precision, no
kernel, no ring, no donation; scores in blocks of rows so that it fits.
It imports nothing of the program and takes nothing the program has made:
weights come from the seed through the He-normal draw written out below
(the same draw the program makes, so both start from equal weights), the
normalisation statistics from the rows.

``quant`` names the control's precision: ``None`` is the reference itself;
``"fp8"`` rounds weights and activations to float8 (e4m3) ahead of every
matmul of the forward pass, the step below the bfloat16 that the
configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GROUPS = ("enc", "dec", "cls")
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
VAR_FLOOR = 1e-2          # soft floor inside the z-score
VAR_EPS = 1e-6            # added to a batch's variance
# exponent bits, mantissa bits, largest finite value
QUANT = {"fp8": (4, 3, 240.0), "bf16": (8, 7, 3.3e38)}
# a configuration's compute_dtype -> the name of its rounding in QUANT
PRECISION = {"bfloat16": "bf16"}


def layer_dims(model: dict) -> dict:
    """``{group: [(fan_in, fan_out), ...]}`` from the configuration's widths."""
    enc = [model["in_dim"], *model["enc_dims"], model["bottleneck"]]
    dec = enc[::-1]
    cls = [model["bottleneck"], model["cls_hidden"], 1]
    return {g: list(zip(d[:-1], d[1:]))
            for g, d in (("enc", enc), ("dec", dec), ("cls", cls))}


def init(seed: int, model: dict) -> dict:
    """He-normal weights and zero biases from the seed: one key a layer,
    split off the seed's key in layer order, its first half drawing the
    weights."""
    dims = layer_dims(model)
    n = sum(len(v) for v in dims.values())
    keys = iter(jax.random.split(jax.random.key(seed), n))
    params = {}
    for g in GROUPS:
        params[g] = []
        for fan_in, fan_out in dims[g]:
            wkey, _ = jax.random.split(next(keys))
            w = jax.random.normal(wkey, (fan_in, fan_out)) * jnp.sqrt(
                2.0 / fan_in)
            params[g].append({"w": w.astype(jnp.float32),
                              "b": jnp.zeros((fan_out,), jnp.float32)})
    return params


def adam_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros, "v": zeros, "t": jnp.zeros((), jnp.int32)}


def norm_update(norm, x, labels, mask, momentum: float):
    """Running mean and variance of the rows not labelled anomalous; the
    first batch sets them, later ones blend in with ``momentum``."""
    normal = x[(mask == 0.0) | (labels == 0.0)]
    if len(normal) == 0:
        return norm
    mu = normal.mean(axis=0, dtype=np.float64)
    var = normal.var(axis=0, dtype=np.float64) + VAR_EPS
    if norm is not None:
        mu = (1 - momentum) * norm[0] + momentum * mu
        var = (1 - momentum) * norm[1] + momentum * var
    return (mu.astype(np.float32), var.astype(np.float32))


def _q(a, quant):
    """Round to the control's precision on the way forward; gradients pass
    straight through, in float32 (float8 gradients would underflow to
    nought and leave whole leaves unmoved: a cruder failure than the
    arithmetic's own). ``reduce_precision`` and not a pair of casts: XLA
    may drop a cast down and up again (``xla_allow_excess_precision``), and
    did so on the chip (my chip run, PR 25)."""
    if quant is None:
        return a
    exponent_bits, mantissa_bits, largest = QUANT[quant]
    low = jax.lax.reduce_precision(jnp.clip(a, -largest, largest),
                                   exponent_bits, mantissa_bits)
    return a + jax.lax.stop_gradient(low - a)


def _mlp(layers, h, final_act: bool, quant):
    for i, layer in enumerate(layers):
        h = jnp.dot(_q(h, quant), _q(layer["w"], quant),
                    precision="highest") + layer["b"]
        if final_act or i < len(layers) - 1:
            h = jnp.maximum(h, 0.0)
    return h


def _forward(params, x, mu, var, quant):
    z = (x - mu) / jnp.sqrt(var + VAR_FLOOR)
    code = _mlp(params["enc"], z, True, quant)
    recon = _mlp(params["dec"], code, False, quant)
    logit = _mlp(params["cls"], code, False, quant)[:, 0]
    return jnp.mean(jnp.square(recon - z), axis=-1), logit


@functools.partial(jax.jit, static_argnames=("recon_weight", "quant"))
def _scores(params, x, mu, var, recon_weight, quant):
    err, logit = _forward(params, x, mu, var, quant)
    return recon_weight * jnp.tanh(err) + (
        1.0 - recon_weight) * jax.nn.sigmoid(logit)


def scores_on_device(params, norm, x, recon_weight: float, quant=None):
    """Scores of the rows of ``x``, left on the device."""
    return _scores(params, x, norm[0], norm[1], recon_weight, quant)


def scores(params, norm, x, recon_weight: float, quant=None,
           block: int = 1 << 18) -> np.ndarray:
    """Scores of the rows of ``x`` (host array), a block at a time."""
    mu, var = norm
    out = [np.asarray(_scores(params, x[i:i + block], mu, var,
                              recon_weight, quant))
           for i in range(0, len(x), block)]
    return np.concatenate(out) if out else np.zeros(0, np.float32)


def _loss(params, x, labels, mask, mu, var, quant):
    err, logit = _forward(params, x, mu, var, quant)
    bce = jnp.maximum(logit, 0) - logit * labels + jnp.log1p(
        jnp.exp(-jnp.abs(logit)))
    return jnp.mean(err) + jnp.sum(bce * mask) / jnp.maximum(
        jnp.sum(mask), 1.0)


def _adam(params, grads, opt, lr):
    t = opt["t"] + 1
    m = jax.tree_util.tree_map(
        lambda a, g: ADAM_B1 * a + (1 - ADAM_B1) * g, opt["m"], grads)
    v = jax.tree_util.tree_map(
        lambda a, g: ADAM_B2 * a + (1 - ADAM_B2) * g * g, opt["v"], grads)
    mhat = 1 - ADAM_B1 ** t.astype(jnp.float32)
    vhat = 1 - ADAM_B2 ** t.astype(jnp.float32)
    params = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * (a / mhat) / (jnp.sqrt(b / vhat) + ADAM_EPS),
        params, m, v)
    return params, {"m": m, "v": v, "t": t}


@functools.partial(jax.jit, static_argnames=("steps", "quant"))
def fit_steps(params, opt, mu, var, x, labels, mask, lr, steps: int,
              quant=None):
    """``steps`` Adam steps on the one batch, in one program. Returns the
    last state, the parameters after each step (stacked leaf by leaf: what
    a call could meet while the fit runs), each step's loss and the first
    gradient."""
    after, losses, first_grad = [], [], None
    for _ in range(steps):
        loss, grads = jax.value_and_grad(_loss)(
            params, x, labels, mask, mu, var, quant)
        if first_grad is None:
            first_grad = grads
        params, opt = _adam(params, grads, opt, lr)
        after.append(params)
        losses.append(loss)
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *after)
    return params, opt, stacked, jnp.stack(losses), first_grad


def fit(params, opt, norm, x, labels, mask, steps: int, lr: float,
        quant=None):
    """``fit_steps`` for a caller that wants host values: the states after
    each step ``[(params, opt), ...]`` (only the last one's moments are
    kept), each step's loss and the first gradient."""
    params, opt, stacked, losses, first_grad = fit_steps(
        params, opt, jnp.asarray(norm[0]), jnp.asarray(norm[1]),
        jnp.asarray(x), jnp.asarray(labels), jnp.asarray(mask),
        jnp.float32(lr), steps, quant)
    states = [(jax.tree_util.tree_map(lambda a, i=i: a[i], stacked), None)
              for i in range(steps - 1)] + [(params, opt)]
    return states, [float(v) for v in losses], first_grad
