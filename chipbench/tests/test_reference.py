"""The plain reference against the program's model at a small size: equal
fresh weights, and scores, loss and an Adam step that agree once the
program's model is run in float32 too."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chipbench import harness
from chipbench.reference import mlp36 as ref
from chipbench.traffic import feature_rows


def _setup(seed=11, n=640):
    cfg = harness.load_json("configs", "mlp36-online.json")
    mix = harness.load_json("traffic", "backlog1024.json")
    mix.update(rows_per_block=n // 4, setup_fit_rows=n, pool=1)
    x, labels, mask = feature_rows.generate(mix, n, 36, seed)["pool"][0]
    labels = labels.copy()
    labels[:5], mask[:5] = 1.0, 1.0
    return cfg, x, labels, mask


def test_reference_agrees_with_models_anomaly():
    from linkerd_tpu.models.anomaly import (
        AnomalyModelConfig, anomaly_scores, init_params, loss_fn,
        normalize_features)
    cfg, x, labels, mask = _setup()
    model, tel = cfg["model"], cfg["telemeter"]
    f32 = dataclasses.replace(
        AnomalyModelConfig(recon_weight=tel["reconWeight"]),
        compute_dtype=jnp.float32)
    theirs = init_params(jax.random.key(11), f32)
    ours = ref.init(11, model)
    for a, b in zip(jax.tree_util.tree_leaves(theirs),
                    jax.tree_util.tree_leaves(ours)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    norm = ref.norm_update(None, x, labels, mask, tel["normMomentum"])
    normal = x[(mask == 0) | (labels == 0)]
    np.testing.assert_allclose(norm[0], normal.mean(0), rtol=1e-5)
    np.testing.assert_allclose(norm[1], normal.var(0) + 1e-6, rtol=1e-4,
                               atol=1e-7)
    with jax.default_matmul_precision("highest"):
        xn = normalize_features(jnp.asarray(x), *norm)
        want = np.asarray(anomaly_scores(theirs, xn, f32))
        want_loss, want_grads = jax.value_and_grad(loss_fn)(
            theirs, xn, jnp.asarray(labels), jnp.asarray(mask), f32)
    got = ref.scores(ours, norm, x, tel["reconWeight"], block=256)
    np.testing.assert_allclose(got, want, atol=2e-6)

    states, losses, g1 = ref.fit(ours, ref.adam_init(ours), norm, x, labels,
                                 mask, 1, tel["learningRate"])
    assert abs(losses[0] - float(want_loss)) <= 1e-5 * abs(float(want_loss))
    for a, b in zip(jax.tree_util.tree_leaves(want_grads),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=1e-7)
    opt = optax.adam(tel["learningRate"])
    updates, _ = opt.update(want_grads, opt.init(theirs), theirs)
    stepped = optax.apply_updates(theirs, updates)
    for a, b in zip(jax.tree_util.tree_leaves(stepped),
                    jax.tree_util.tree_leaves(states[0][0])):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-6)


def test_blocks_and_the_control_precision():
    cfg, x, labels, mask = _setup(n=512)
    model, tel = cfg["model"], cfg["telemeter"]
    p = ref.init(3, model)
    norm = ref.norm_update(None, x, labels, mask, 0.2)
    whole = ref.scores(p, norm, x, 0.7, block=1 << 18)
    np.testing.assert_allclose(ref.scores(p, norm, x, 0.7, block=100),
                               whole, atol=1e-6)
    on_device = ref.scores_on_device(p, norm, jnp.asarray(x), 0.7)
    np.testing.assert_allclose(np.asarray(on_device), whole, atol=1e-6)
    # float8 is a different answer, and by far more than bfloat16 is
    low = np.max(np.abs(ref.scores(p, norm, x, 0.7, "bf16") - whole))
    lower = np.max(np.abs(ref.scores(p, norm, x, 0.7, "fp8") - whole))
    assert lower > 3 * low > 0
