"""Tests for the anomaly model, feature extraction, and sharded steps."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from linkerd_tpu.models import (
    FEATURE_DIM, FeatureVector, featurize,
    AnomalyModelConfig, init_params, apply_model, anomaly_scores, loss_fn,
)
from linkerd_tpu.models.features import featurize_batch
from linkerd_tpu.parallel import (
    make_mesh, make_train_step, make_score_step,
)
from linkerd_tpu.parallel.mesh import init_sharded, shard_params

CFG = AnomalyModelConfig()


class TestFeatures:
    def test_shape_and_bias(self):
        x = featurize(FeatureVector(latency_ms=12.0, status=503))
        assert x.shape == (FEATURE_DIM,)
        assert x.dtype == np.float32
        assert x[31] == 1.0

    def test_status_one_hot(self):
        x = featurize(FeatureVector(status=503))
        assert x[5] == 1.0  # 5xx bucket
        assert x[1] == 0.0
        x2 = featurize(FeatureVector(status=200))
        assert x2[2] == 1.0

    def test_path_hashing_stable_and_distinct(self):
        a1 = featurize(FeatureVector(dst_path="/svc/users"))
        a2 = featurize(FeatureVector(dst_path="/svc/users"))
        b = featurize(FeatureVector(dst_path="/svc/orders"))
        assert (a1 == a2).all()
        assert not (a1 == b).all()

    def test_batch(self):
        xs = featurize_batch([FeatureVector(), FeatureVector(status=500)])
        assert xs.shape == (2, FEATURE_DIM)

    def test_batch_bit_identical_to_per_row(self):
        """The vectorized batch encoder is an optimization of
        ``featurize``, not a second schema: it must agree bit-for-bit
        on every column, including edge values (negative sizes,
        out-of-range statuses, signed drift)."""
        rng = np.random.default_rng(7)
        fvs = [FeatureVector(
            latency_ms=float(rng.uniform(-5, 5000)),
            status=int(rng.integers(0, 700)),
            retries=int(rng.integers(0, 4)),
            request_bytes=int(rng.integers(-10, 10**6)),
            response_bytes=int(rng.integers(0, 10**6)),
            concurrency=int(rng.integers(0, 100)),
            ewma_ms=float(rng.uniform(0, 100)),
            queue_ms=float(rng.uniform(-1, 10)),
            exception=bool(rng.integers(0, 2)),
            retryable=bool(rng.integers(0, 2)),
            dst_path=f"/svc/s{int(rng.integers(0, 20))}",
            dst_rps=float(rng.uniform(0, 10**4)),
            lat_drift_ms=float(rng.uniform(-500, 500)),
        ) for _ in range(256)]
        batch = featurize_batch(fvs)
        ref = np.stack([featurize(fv) for fv in fvs])
        assert (batch == ref).all()


class TestModel:
    def test_forward_shapes(self):
        params = init_params(jax.random.key(0), CFG)
        x = jnp.ones((8, FEATURE_DIM))
        recon, z, logits = apply_model(params, x, CFG)
        assert recon.shape == (8, FEATURE_DIM)
        assert z.shape == (8, CFG.bottleneck)
        assert logits.shape == (8,)

    def test_scores_in_unit_interval(self):
        params = init_params(jax.random.key(0), CFG)
        x = jax.random.normal(jax.random.key(1), (16, FEATURE_DIM))
        s = anomaly_scores(params, x, CFG)
        assert s.shape == (16,)
        assert bool(jnp.all(s >= 0.0)) and bool(jnp.all(s <= 1.0))

    def test_loss_finite_and_mask_works(self):
        params = init_params(jax.random.key(0), CFG)
        x = jax.random.normal(jax.random.key(1), (8, FEATURE_DIM))
        labels = jnp.zeros(8)
        # fully unlabeled: loss is recon-only and finite
        l0 = loss_fn(params, x, labels, jnp.zeros(8), CFG)
        l1 = loss_fn(params, x, labels, jnp.ones(8), CFG)
        assert jnp.isfinite(l0) and jnp.isfinite(l1)
        assert float(l1) > float(l0)  # BCE adds loss

    def test_training_reduces_loss(self):
        """A few steps of the real sharded train step reduce loss on a
        fixed batch (8 virtual devices; tp=2 forced to keep the
        model-axis path covered now that make_mesh defaults pure-data
        at this width)."""
        mesh = make_mesh(tp=2)
        assert mesh.devices.size == 8
        assert dict(mesh.shape) == {"data": 4, "model": 2}
        opt = optax.adam(1e-3)
        params, opt_state = init_sharded(mesh, jax.random.key(0), opt, CFG)
        step = make_train_step(mesh, opt, CFG)
        x = jax.random.normal(jax.random.key(1), (64, FEATURE_DIM))
        labels = (jax.random.uniform(jax.random.key(2), (64,)) > 0.8).astype(
            jnp.float32)
        mask = jnp.ones(64)
        losses = []
        for _ in range(12):
            params, opt_state, loss = step(params, opt_state, x, labels, mask)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_sharded_score_matches_single_device(self):
        # both mesh shapes: the pure-data default and forced tp=2
        for tp in (None, 2):
            mesh = make_mesh(tp=tp)
            if tp is None:  # width heuristic: pure data at MLP scale
                assert dict(mesh.shape) == {"data": 8, "model": 1}
            params = init_params(jax.random.key(0), CFG)
            x = jax.random.normal(jax.random.key(1), (32, FEATURE_DIM))
            ref = anomaly_scores(params, x, CFG)
            sharded = shard_params(mesh, params)
            score = make_score_step(mesh, CFG)
            got = score(sharded, x)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       atol=2e-2, rtol=2e-2)

    def test_trained_ae_separates_anomalies(self):
        """Autoencoder trained on 'normal' traffic scores shifted
        anomalous traffic higher (the AUC mechanism, unsupervised)."""
        cfg = AnomalyModelConfig(recon_weight=1.0)  # recon-only
        mesh = make_mesh()
        opt = optax.adam(3e-3)
        params, opt_state = init_sharded(mesh, jax.random.key(0), opt, cfg)
        step = make_train_step(mesh, opt, cfg)
        key = jax.random.key(42)
        normal = 0.1 * jax.random.normal(key, (256, FEATURE_DIM)) + 0.5
        zeros = jnp.zeros(256)
        for _ in range(60):
            params, opt_state, _ = step(params, opt_state, normal, zeros, zeros)
        anomalous = normal + 2.0  # shifted distribution
        s_norm = anomaly_scores(params, normal[:64], cfg)
        s_anom = anomaly_scores(params, anomalous[:64], cfg)
        assert float(jnp.mean(s_anom)) > float(jnp.mean(s_norm))


class TestDeviceNormalization:
    """normalize_features folded into the jitted steps (ADVICE r5): the
    device path with raw features + mu/var must match host-side z-score
    then score, on both the sharded and fused/XLA single-chip paths."""

    def _host_norm(self, x, mu, var):
        return (np.asarray(x) - mu) / np.sqrt(var + 1e-2)

    def test_sharded_score_normalizes_on_device(self):
        mesh = make_mesh()
        params = init_params(jax.random.key(0), CFG)
        rng = np.random.default_rng(7)
        x = rng.standard_normal((32, FEATURE_DIM)).astype(np.float32) * 40 + 5
        mu = x.mean(axis=0)
        var = x.var(axis=0)
        ref = anomaly_scores(params, jnp.asarray(
            self._host_norm(x, mu, var), jnp.float32), CFG)
        score = make_score_step(mesh, CFG)
        got = score(shard_params(mesh, params), jnp.asarray(x),
                    jnp.asarray(mu), jnp.asarray(var))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-2, rtol=2e-2)

    def test_best_scorer_normalizes_on_device(self):
        from linkerd_tpu.ops.scoring import best_scorer
        params = init_params(jax.random.key(0), CFG)
        rng = np.random.default_rng(8)
        x = rng.standard_normal((64, FEATURE_DIM)).astype(np.float32) * 10
        mu = x.mean(axis=0)
        var = x.var(axis=0)
        ref = anomaly_scores(params, jnp.asarray(
            self._host_norm(x, mu, var), jnp.float32), CFG)
        scorer = best_scorer(CFG, jax.devices()[0].platform)
        got = scorer(params, jnp.asarray(x), jnp.asarray(mu),
                     jnp.asarray(var))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-2, rtol=2e-2)

    def test_train_step_normalizes_on_device(self):
        """Training with raw x + mu/var must move loss the same way as
        training on pre-normalized input (same objective)."""
        mesh = make_mesh()
        opt = optax.adam(1e-3)
        rng = np.random.default_rng(9)
        x = rng.standard_normal((64, FEATURE_DIM)).astype(np.float32) * 20
        mu = x.mean(axis=0)
        var = x.var(axis=0)
        labels = np.zeros(64, np.float32)
        step = make_train_step(mesh, opt, CFG)
        params, opt_state = init_sharded(mesh, jax.random.key(0), opt, CFG)
        _, _, loss_dev = step(params, opt_state, jnp.asarray(x),
                              jnp.asarray(labels), jnp.asarray(labels),
                              None, jnp.asarray(mu), jnp.asarray(var))
        params2, opt_state2 = init_sharded(mesh, jax.random.key(0), opt, CFG)
        _, _, loss_host = step(params2, opt_state2, jnp.asarray(
            self._host_norm(x, mu, var), jnp.float32),
            jnp.asarray(labels), jnp.asarray(labels))
        np.testing.assert_allclose(float(loss_dev), float(loss_host),
                                   rtol=2e-2)
