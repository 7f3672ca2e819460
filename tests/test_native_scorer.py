"""In-data-plane scoring tests: the native C++ scorer evaluated inside
the fastpath engines (native/scorer.h + lifecycle/export.py).

The contracts under test (COMPONENTS.md §2.14):

- blob format: export_weight_blob <-> l5dscore::parse_blob stay in
  lockstep — a real JAX snapshot exports, parses, and validates; any
  corruption (magic, CRC, truncation, geometry) is a rejected publish,
  never silently-wrong scores;
- score parity: the native f32 evaluator matches the JAX reference
  within float tolerance, and int8 quantization stays inside its error
  bound — the parity gate for serving the distilled model in-engine;
- featurizer parity: the C featurizer and the Python
  NativeFeaturizer.encode_block produce identical features for the
  same raw rows and drift state;
- hot-swap: concurrent publish + score never yields torn weights (the
  slab's reader-recheck protocol: every observed score matches one of
  the published models exactly);
- tiering: pre-scored engine rows skip the JAX dispatch but still feed
  the board/training; unscored rows (no blob) fall back to JAX.
"""

import asyncio
import threading

import numpy as np
import pytest

from linkerd_tpu.lifecycle.export import blob_meta, export_weight_blob
from linkerd_tpu.telemetry.anomaly import (
    FeatureVector, InProcessScorer, JaxAnomalyConfig, JaxAnomalyTelemeter,
)
from linkerd_tpu.telemetry.linerate import (
    NATIVE_COL_SCORE, NATIVE_COL_SCORED, NATIVE_ROW_WIDTH, NativeFeaturizer,
)
from linkerd_tpu.telemetry.metrics import MetricsTree
from linkerd_tpu.testing.faults import EchoBackend

native = pytest.importorskip("linkerd_tpu.native")

pytestmark = pytest.mark.skipif(
    not native.ensure_built(), reason="native toolchain unavailable")


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 120))


def _trained_snapshot(seed: int = 3, steps: int = 6):
    """A snapshot with non-trivial weights + normalization stats: a few
    real fit steps so mu/var initialize and params move off init."""
    async def go():
        scorer = InProcessScorer(seed=seed, learning_rate=5e-3)
        rng = np.random.default_rng(seed)
        try:
            for _ in range(steps):
                x = rng.standard_normal(
                    (32, scorer.cfg.in_dim)).astype(np.float32) * 2.0 + 1.0
                labels = (rng.random(32) > 0.8).astype(np.float32)
                await scorer.fit(x, labels, np.ones(32, np.float32))
            return scorer.snapshot()
        finally:
            scorer.close()

    return run(go())


def _numpy_reference(snap, x: np.ndarray) -> np.ndarray:
    """Pure-numpy f32 forward pass with the exact serving semantics:
    normalize -> enc (relu all) -> dec (relu except last) + cls head
    from the bottleneck -> tanh/sigmoid blend, recon error vs the
    NORMALIZED input."""
    xn = (x - snap.mu) / np.sqrt(snap.var + 1e-2)
    xn = xn.astype(np.float32)

    def dense_chain(layers, h, final_act):
        n = len(layers)
        for i, layer in enumerate(layers):
            h = h @ layer["w"].astype(np.float32) \
                + layer["b"].astype(np.float32)
            if final_act or i < n - 1:
                h = np.maximum(h, 0.0)
        return h

    z = dense_chain(snap.params["enc"], xn, final_act=True)
    recon = dense_chain(snap.params["dec"], z, final_act=False)
    logits = dense_chain(snap.params["cls"], z, final_act=False)[:, 0]
    err = np.mean((recon - xn) ** 2, axis=1)
    rw = float(snap.cfg.recon_weight)
    return (rw * np.tanh(err)
            + (1.0 - rw) / (1.0 + np.exp(-logits))).astype(np.float32)


class TestBlobFormat:
    def test_export_parses_and_roundtrips_meta(self):
        snap = _trained_snapshot()
        blob = export_weight_blob(snap, version=42, quant="f32")
        meta = blob_meta(blob)
        assert meta is not None
        assert meta["version"] == 42 and meta["quant"] == "f32"
        assert meta["in_dim"] == snap.mu.shape[0]
        # the C parser agrees with the Python header reader
        info = native.score_blob_info(blob)
        assert info["version"] == 42 and info["crc"] == meta["crc"]
        assert info["in_dim"] == meta["in_dim"]
        assert info["n_enc"] + info["n_dec"] + info["n_cls"] \
            == meta["layers"]

    def test_int8_blob_is_smaller_and_valid(self):
        snap = _trained_snapshot()
        f32 = export_weight_blob(snap, version=1, quant="f32")
        i8 = export_weight_blob(snap, version=1, quant="int8")
        assert len(i8) < len(f32) * 0.5  # ~4x on the weight payload
        assert native.score_blob_info(i8)["quant"] == 1

    def test_corruption_is_rejected_not_served(self):
        snap = _trained_snapshot()
        blob = bytearray(export_weight_blob(snap, version=1))
        # flipped weight byte: CRC catches it
        flipped = bytearray(blob)
        flipped[len(flipped) // 2] ^= 0x40
        with pytest.raises(ValueError, match="crc"):
            native.score_blob_info(bytes(flipped))
        assert blob_meta(bytes(flipped)) is None
        # bad magic
        with pytest.raises(ValueError, match="magic"):
            native.score_blob_info(b"NOTMAGIC" + bytes(blob[8:]))
        # truncation
        with pytest.raises(ValueError):
            native.score_blob_info(bytes(blob[: len(blob) // 2]))
        # a structurally-bad but CRC-valid blob: geometry still rejects
        import struct
        import zlib
        body = bytes(blob[:-4])
        bad = bytearray(body)
        # in_dim field (offset 8 magic + 8 version/quant)
        struct.pack_into("<I", bad, 16, 9999)
        bad = bytes(bad) + struct.pack("<I", zlib.crc32(bytes(bad)))
        with pytest.raises(ValueError):
            native.score_blob_info(bad)

    def test_engine_rejects_wrong_in_dim_blob(self):
        """A valid blob whose in_dim disagrees with the engine
        featurizer must not publish (the engine would index out of
        bounds at featurize time otherwise)."""
        eng = native.FastPathEngine()
        try:
            snap = _trained_snapshot()
            ok = export_weight_blob(snap, version=1)
            eng.publish_weights(ok)  # FEATURE_DIM matches: accepted
            import struct
            import zlib
            body = bytearray(ok[:-4])
            struct.pack_into("<I", body, 16, 35)  # in_dim 36 -> 35
            bad = bytes(body) + struct.pack("<I", zlib.crc32(bytes(body)))
            with pytest.raises(ValueError):
                eng.publish_weights(bad)
        finally:
            eng.close()


class TestScoreParity:
    def test_f32_matches_numpy_reference_tight(self):
        snap = _trained_snapshot()
        blob = export_weight_blob(snap, version=1, quant="f32")
        rng = np.random.default_rng(0)
        x = rng.standard_normal((256, snap.mu.shape[0])).astype(np.float32)
        got = native.score_eval(blob, x)
        ref = _numpy_reference(snap, x)
        assert got.shape == ref.shape
        assert np.abs(got - ref).max() < 1e-5

    def test_f32_matches_jax_serving_scorer(self):
        """The end-to-end parity gate: native scores vs the REAL
        serving scorer (jitted, bf16 compute on this backend) agree
        within the compute-dtype tolerance."""
        async def go():
            scorer = InProcessScorer(seed=5, learning_rate=5e-3)
            rng = np.random.default_rng(5)
            try:
                for _ in range(4):
                    x = rng.standard_normal(
                        (32, scorer.cfg.in_dim)).astype(np.float32)
                    await scorer.fit(
                        x, np.zeros(32, np.float32),
                        np.zeros(32, np.float32))
                snap = scorer.snapshot()
                blob = export_weight_blob(snap, version=1)
                x = rng.standard_normal(
                    (128, scorer.cfg.in_dim)).astype(np.float32)
                ref = np.asarray(await scorer.score(x))
                got = native.score_eval(blob, x)
                # bf16 rounds ~3 decimal digits through the stack;
                # scores live in [0, 1]
                assert np.abs(got - ref).max() < 0.05
                assert np.abs(got - ref).mean() < 0.01
            finally:
                scorer.close()

        run(go())

    def test_int8_error_bound_vs_f32(self):
        snap = _trained_snapshot()
        f32 = export_weight_blob(snap, version=1, quant="f32")
        i8 = export_weight_blob(snap, version=1, quant="int8")
        rng = np.random.default_rng(1)
        x = rng.standard_normal((256, snap.mu.shape[0])).astype(np.float32)
        a = native.score_eval(f32, x)
        b = native.score_eval(i8, x)
        # symmetric per-output-column int8 with f32 accumulate: the
        # error is a weight-rounding effect, bounded well under the
        # anomaly thresholds the control loop actuates on (>= 0.05
        # would be actuation-visible)
        assert np.abs(a - b).max() < 0.03
        assert np.abs(a - b).mean() < 0.005

    def test_scores_are_probabilities(self):
        snap = _trained_snapshot()
        blob = export_weight_blob(snap, version=1)
        x = np.random.default_rng(2).standard_normal(
            (64, snap.mu.shape[0])).astype(np.float32) * 50.0
        got = native.score_eval(blob, x)
        assert np.isfinite(got).all()
        assert (got >= 0.0).all() and (got <= 1.0).all()


class TestFeaturizerParity:
    def test_c_features_match_python_encoder(self):
        """Same raw rows, same hash column, same drift -> bit-for-bit
        identical features from the C featurizer and the Python
        NativeFeaturizer (fresh route: drift 0 on both sides)."""
        from linkerd_tpu.models.features import path_hash_cols
        dst = "/svc/parity"
        col, sign = path_hash_cols(dst)
        rng = np.random.default_rng(3)
        n = 32
        rows = np.zeros((n, NATIVE_ROW_WIDTH), np.float32)
        rows[:, 0] = 9  # route id
        rows[:, 1] = rng.uniform(0.1, 500.0, n)      # lat_ms
        rows[:, 2] = rng.choice([200, 204, 404, 500, 503], n)
        rows[:, 3] = rng.integers(0, 1 << 16, n)     # req_b
        rows[:, 4] = rng.integers(0, 1 << 20, n)     # rsp_b
        rows[:, 5] = np.arange(n) * 0.01             # ts_s
        snap = _trained_snapshot()
        blob = export_weight_blob(snap, version=1)
        scores, feats = native.score_eval_raw(
            blob, rows, cols=np.full(n, col, np.int32),
            signs=np.full(n, sign, np.float32),
            drifts=np.zeros(n, np.float32), return_features=True)
        f = NativeFeaturizer(resolver=lambda rid: dst)
        x_py, inv, dsts = f.encode_block(rows)
        assert dsts == [dst]
        # drift col (32): the Python featurizer's FIRST block seeds the
        # EWMA (drift 0) — identical to the zero drift fed to C
        assert np.allclose(feats, x_py, atol=1e-6)
        # and the scores equal evaluating those features directly
        direct = native.score_eval(blob, feats)
        assert np.allclose(scores, direct, atol=1e-6)

    def test_c_feature_dim_matches_model_schema(self):
        from linkerd_tpu.models.features import FEATURE_DIM
        assert native.score_feature_dim() == FEATURE_DIM


class TestHotSwap:
    def test_concurrent_publish_and_score_never_torn(self):
        """The slab's reader-recheck protocol: while a publisher flips
        between two models as fast as it can, every concurrently
        observed score matches model A or model B EXACTLY — a torn
        (half-swapped) weight buffer would produce a third value."""
        blob_a = native.score_test_blob(version=1, seed=11)
        blob_b = native.score_test_blob(version=2, seed=22)
        x = np.random.default_rng(4).standard_normal(
            (1, native.score_feature_dim())).astype(np.float32)
        expect_a = float(native.score_eval(blob_a, x)[0])
        expect_b = float(native.score_eval(blob_b, x)[0])
        assert abs(expect_a - expect_b) > 1e-6  # distinct models
        slab = native.ScoreSlab()
        try:
            slab.publish(blob_a)
            stop = threading.Event()
            bad = []

            def publisher():
                flip = False
                while not stop.is_set():
                    slab.publish(blob_b if flip else blob_a)
                    flip = not flip

            def scorer_thread():
                while not stop.is_set():
                    out = slab.score(x)
                    s = float(out[0])
                    if (abs(s - expect_a) > 1e-6
                            and abs(s - expect_b) > 1e-6):
                        bad.append(s)

            threads = [threading.Thread(target=publisher)] + [
                threading.Thread(target=scorer_thread) for _ in range(3)]
            for t in threads:
                t.start()
            import time
            time.sleep(1.0)
            stop.set()
            for t in threads:
                t.join()
            stats = slab.stats()
            assert stats["swaps"] > 10  # the publisher really ran
            assert bad == [], f"torn scores observed: {bad[:5]}"
        finally:
            slab.close()

    def test_slab_stats_track_version_and_crc(self):
        slab = native.ScoreSlab()
        try:
            assert slab.score(np.zeros(
                (1, native.score_feature_dim()), np.float32)) is None
            blob = native.score_test_blob(version=9, seed=1)
            slab.publish(blob)
            st = slab.stats()
            assert st["version"] == 9 and st["swaps"] == 1
            assert st["crc"] == native.score_blob_info(blob)["crc"]
        finally:
            slab.close()

    def test_slab_guards_out_of_bounds_and_closed(self):
        """The standalone slab must fail as Python errors, never as
        native out-of-bounds reads: wrong-width score input, a valid
        blob with a different in_dim, and use-after-close all raise."""
        slab = native.ScoreSlab()
        try:
            blob = native.score_test_blob(version=1, seed=1)
            slab.publish(blob)
            with pytest.raises(ValueError, match="expected"):
                slab.score(np.zeros((2, 8), np.float32))  # engine-row w
            # valid blob, wrong in_dim: rejected by the C publish
            snap = _trained_snapshot()
            ok = export_weight_blob(snap, version=1)
            import struct
            import zlib
            body = bytearray(ok[:-4])
            struct.pack_into("<I", body, 16, 35)
            # keep geometry consistent: just assert the engine-width
            # check fires before any eval (crc recomputed so parse
            # succeeds up to the in_dim gate on a same-shape blob is
            # not constructible here — the dim gate rejects first)
            bad = bytes(body) + struct.pack(
                "<I", zlib.crc32(bytes(body)))
            with pytest.raises(ValueError):
                slab.publish(bad)
        finally:
            slab.close()
        with pytest.raises(RuntimeError, match="closed"):
            slab.score(np.zeros(
                (1, native.score_feature_dim()), np.float32))
        with pytest.raises(RuntimeError, match="closed"):
            slab.stats()


class TestEngineEndToEnd:
    def test_engine_scores_all_requests_in_data_plane(self):
        """Real loopback traffic through the h1 engine: with a blob
        published and the route feature pushed, 100% of drained rows
        arrive pre-scored, the score matches an out-of-band evaluation
        of the same blob on the same features, and the stats block
        reports the serving version/CRC."""
        snap = _trained_snapshot()
        blob = export_weight_blob(snap, version=7)

        async def go():
            eng = native.FastPathEngine()
            port = eng.listen("127.0.0.1", 0)
            srv = await EchoBackend().start()
            bport = srv.bound_port
            try:
                eng.start()
                eng.set_route("svc", [("127.0.0.1", bport)])
                assert eng.set_route_feature("svc", 14, 1.0)
                assert not eng.set_route_feature("ghost", 14, 1.0)
                eng.publish_weights(blob)
                r, w = await asyncio.open_connection("127.0.0.1", port)
                rsp = b"HTTP/1.1 200 OK\r\nContent-Length: 2\r\n\r\nok"
                for _ in range(25):
                    w.write(b"GET / HTTP/1.1\r\nHost: svc\r\n\r\n")
                    await w.drain()
                    await r.readexactly(len(rsp))
                w.close()
                await w.wait_closed()
                await asyncio.sleep(0.1)
                rows = eng.drain_features()
                assert rows.shape == (25, NATIVE_ROW_WIDTH)
                assert (rows[:, NATIVE_COL_SCORED] == 1.0).all()
                assert np.isfinite(rows[:, NATIVE_COL_SCORE]).all()
                st = eng.stats()["native_scorer"]
                assert st["weights"] and st["version"] == 7
                assert st["scored"] == 25 and st["unscored"] == 0
                assert st["crc"] == blob_meta(blob)["crc"]
                # scoring cost is measured per row: the ns histogram
                # holds exactly the scored count, all sub-ms (bucket
                # 20 ~= 2^20 ns = 1.05 ms)
                hist = st["score_ns_hist"]
                assert sum(hist) == 25
                assert sum(hist[:20]) == 25, f"score >1ms: {hist}"
            finally:
                eng.close()
                await srv.close()

        run(go())

    def test_rows_without_weights_fall_through_unscored(self):
        """No blob published: rows drain with scored == 0 (the JAX
        fallback tier's signal) and the stats count them unscored."""
        async def go():
            eng = native.FastPathEngine()
            port = eng.listen("127.0.0.1", 0)
            srv = await EchoBackend().start()
            bport = srv.bound_port
            try:
                eng.start()
                eng.set_route("svc", [("127.0.0.1", bport)])
                eng.set_route_feature("svc", 14, 1.0)
                r, w = await asyncio.open_connection("127.0.0.1", port)
                rsp = b"HTTP/1.1 200 OK\r\nContent-Length: 2\r\n\r\nok"
                for _ in range(5):
                    w.write(b"GET / HTTP/1.1\r\nHost: svc\r\n\r\n")
                    await w.drain()
                    await r.readexactly(len(rsp))
                w.close()
                await w.wait_closed()
                await asyncio.sleep(0.1)
                rows = eng.drain_features()
                assert (rows[:, NATIVE_COL_SCORED] == 0.0).all()
                st = eng.stats()["native_scorer"]
                assert not st["weights"]
                assert st["unscored"] == 5 and st["scored"] == 0
            finally:
                eng.close()
                await srv.close()

        run(go())


class _StubJax:
    """A deterministic stand-in for the JAX tier."""

    def __init__(self, value=0.25):
        self.value = value
        self.score_calls = []
        self.fit_calls = []

    async def score(self, x):
        self.score_calls.append(np.array(x, copy=True))
        return np.full(len(x), self.value, np.float32)

    async def fit(self, x, labels, mask):
        self.fit_calls.append((np.array(x, copy=True), len(labels)))
        return 0.1

    def close(self):
        pass


def _nat_rows(n, route_id=4, score=0.9, scored=1.0):
    rows = np.zeros((n, NATIVE_ROW_WIDTH), np.float32)
    rows[:, 0] = route_id
    rows[:, 1] = 10.0
    rows[:, 2] = 200
    rows[:, NATIVE_COL_SCORE] = score
    rows[:, NATIVE_COL_SCORED] = scored
    return rows


class TestTieredTelemeter:
    def test_prescored_rows_skip_jax_and_feed_board(self):
        async def go():
            mt = MetricsTree()
            stub = _StubJax(value=0.25)
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(maxBatch=64, trainEveryBatches=0),
                mt, scorer=stub)
            tele.set_native_route_resolver(lambda rid: "/fp/nat")
            v = tele.native_ring.produce_views(4)
            v[0][:] = _nat_rows(4, score=0.9)
            tele.native_ring.commit(4)
            tele.native_committed(4)
            n = await tele.drain_once()
            assert n == 4
            # the JAX tier never saw the pre-scored rows
            assert stub.score_calls == []
            scores = tele.board.scores.sample()
            assert scores["/fp/nat"] == pytest.approx(0.9, abs=0.05)
            flat = mt.flatten()
            assert flat["anomaly/scored_total"] == 4
            assert flat["anomaly/native_scored_total"] == 4
            assert flat["anomaly/native_scored_fraction"] == 1.0
            assert flat["anomaly/scored_fraction"] == 1.0
            tele.close()

        run(go())

    def test_mixed_batch_splits_tiers(self):
        """Python rows + unscored native rows go to JAX; pre-scored
        native rows publish engine scores — one drained batch."""
        async def go():
            mt = MetricsTree()
            stub = _StubJax(value=0.25)
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(maxBatch=64, trainEveryBatches=0),
                mt, scorer=stub)
            tele.set_native_route_resolver(
                lambda rid: f"/fp/r{int(rid)}")
            tele.ring.append((FeatureVector(dst_path="/svc/py"), None))
            v = tele.native_ring.produce_views(4)
            block = np.concatenate([
                _nat_rows(2, route_id=1, score=0.9, scored=1.0),
                _nat_rows(2, route_id=2, score=0.0, scored=0.0),
            ])
            v[0][:] = block
            tele.native_ring.commit(4)
            tele.native_committed(4)
            n = await tele.drain_once()
            assert n == 5
            # JAX scored exactly python + unscored-native rows
            assert len(stub.score_calls) == 1
            assert len(stub.score_calls[0]) == 3
            scores = tele.board.scores.sample()
            assert scores["/fp/r1"] == pytest.approx(0.9, abs=0.05)
            assert scores["/fp/r2"] == pytest.approx(0.25, abs=0.05)
            flat = mt.flatten()
            assert flat["anomaly/scored_total"] == 5
            assert flat["anomaly/native_scored_total"] == 2
            tele.close()

        run(go())

    def test_mixed_batch_advances_drift_once(self):
        """A mixed scored/unscored block must advance the featurizer's
        per-route drift EWMA exactly ONCE per drain (a per-tier encode
        would double-step the baseline and compute the later subset's
        drift against an already-advanced EWMA)."""
        async def go():
            stub = _StubJax()
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(maxBatch=64, trainEveryBatches=0),
                MetricsTree(), scorer=stub)
            tele.set_native_route_resolver(lambda rid: "/fp/nat")
            block = np.concatenate([
                _nat_rows(3, route_id=4, score=0.9, scored=1.0),
                _nat_rows(3, route_id=4, score=0.0, scored=0.0),
            ])
            block[:, 1] = np.arange(6, dtype=np.float32) * 100.0
            v = tele.native_ring.produce_views(6)
            v[0][:] = block
            tele.native_ring.commit(6)
            tele.native_committed(6)
            await tele.drain_once()
            # reference: ONE single-pass encode over the same block
            ref = NativeFeaturizer(resolver=lambda rid: "/fp/nat")
            ref.encode_block(block)
            assert tele._native_featurizer.temporal._ewma \
                == ref.temporal._ewma
            # and the unscored rows' features the JAX tier saw match
            # the single-pass encoding (drift col 32 included)
            ref2 = NativeFeaturizer(resolver=lambda rid: "/fp/nat")
            x_ref, _, _ = ref2.encode_block(block)
            assert len(stub.score_calls) == 1
            assert np.array_equal(stub.score_calls[0], x_ref[3:])
            tele.close()

        run(go())

    def test_native_rows_still_train_jax_tier(self):
        """Engine-scored rows must keep feeding online training — the
        JAX model is the training tier for ALL traffic."""
        async def go():
            stub = _StubJax()
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(maxBatch=64, trainEveryBatches=1),
                MetricsTree(), scorer=stub)
            tele.set_native_route_resolver(lambda rid: "/fp/nat")
            v = tele.native_ring.produce_views(3)
            v[0][:] = _nat_rows(3, score=0.8)
            tele.native_ring.commit(3)
            tele.native_committed(3)
            await tele.drain_once()
            assert len(stub.fit_calls) == 1
            x_fit, n_labels = stub.fit_calls[0]
            assert len(x_fit) == 3 and n_labels == 3
            tele.close()

        run(go())

    def test_native_tier_survives_degraded_jax(self):
        """A dead JAX scorer flips degraded mode but engine-scored rows
        still publish — the native tier does not depend on the device
        being healthy."""
        class Dead:
            async def score(self, x):
                raise RuntimeError("device gone")

            async def fit(self, x, labels, mask):
                raise RuntimeError("device gone")

            def close(self):
                pass

        async def go():
            mt = MetricsTree()
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(maxBatch=64, trainEveryBatches=0),
                mt, scorer=Dead())
            tele.set_native_route_resolver(lambda rid: "/fp/nat")
            # a python row forces a JAX dispatch (which dies) alongside
            # the pre-scored native rows
            tele.ring.append((FeatureVector(dst_path="/svc/py"), None))
            v = tele.native_ring.produce_views(2)
            v[0][:] = _nat_rows(2, score=0.7)
            tele.native_ring.commit(2)
            tele.native_committed(2)
            n = await tele.drain_once()
            assert n == 2  # the native half landed
            assert tele.board.degraded
            assert tele.board.scores.sample()["/fp/nat"] == \
                pytest.approx(0.7, abs=0.05)
            # the failed JAX dispatch counts dropped, NOT completed —
            # and no scorer spans fire for the dropped Python item
            flat = mt.flatten()
            assert flat["anomaly/dropped_batches"] == 1
            assert flat.get("anomaly/batches", 0) == 0
            tele.close()

        run(go())


class TestWeightPublication:
    def test_refresh_exports_and_fans_out(self):
        async def go():
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(trainEveryBatches=0), MetricsTree())
            got = []
            tele.register_weight_sink(got.append)
            assert await tele.refresh_native_weights() is True
            assert len(got) == 1
            meta = blob_meta(got[0])
            assert meta is not None and meta["quant"] == "f32"
            state = tele.native_tier_state()
            assert state["mode"] == "primary"
            assert state["blob"]["crc"] == meta["crc"]
            assert state["publishes"] == 1 and state["engines"] == 1
            # late registration replays the last blob
            late = []
            tele.register_weight_sink(late.append)
            assert late == got
            tele.close()

        run(go())

    def test_refresh_respects_native_tier_off(self):
        async def go():
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(trainEveryBatches=0, nativeTier="off"),
                MetricsTree())
            got = []
            tele.register_weight_sink(got.append)
            assert await tele.refresh_native_weights() is False
            assert got == []
            assert tele.native_tier_state()["mode"] == "off"
            tele.close()

        run(go())

    def test_stub_scorer_without_snapshot_is_no_publish(self):
        async def go():
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(trainEveryBatches=0), MetricsTree(),
                scorer=_StubJax())
            assert await tele.refresh_native_weights() is False
            assert tele.native_tier_state()["blob"] is None
            tele.close()

        run(go())

    def test_rejecting_sink_does_not_break_others(self):
        async def go():
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(trainEveryBatches=0), MetricsTree())

            def bad(blob):
                raise ValueError("engine said no")

            got = []
            tele.register_weight_sink(bad)
            tele.register_weight_sink(got.append)
            assert await tele.refresh_native_weights() is True
            assert len(got) == 1
            tele.close()

        run(go())

    def test_online_training_republishes_without_lifecycle(self):
        """No lifecycle block: the ONLINE-trained model must still
        reach the engines on the nativeRefreshS cadence — the native
        tier may never serve the startup init blob forever while
        training improves only the JAX side."""
        async def go():
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(trainEveryBatches=1,
                                 nativeRefreshS=0.01), MetricsTree())
            got = []
            tele.register_weight_sink(got.append)
            assert await tele.refresh_native_weights() is True
            await asyncio.sleep(0.05)  # age past the refresh cadence
            tele.ring.append((FeatureVector(dst_path="/svc/py"), None))
            await tele.drain_once()  # scores + fits -> refresh task
            for _ in range(100):
                if len(got) >= 2:
                    break
                await asyncio.sleep(0.05)
            assert len(got) >= 2, "online fit never republished weights"
            tele.close()

        run(go())

    def test_int8_quant_config_exports_int8(self):
        async def go():
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(trainEveryBatches=0,
                                 nativeQuant="int8"), MetricsTree())
            got = []
            tele.register_weight_sink(got.append)
            assert await tele.refresh_native_weights() is True
            assert blob_meta(got[0])["quant"] == "int8"
            tele.close()

        run(go())

    def test_blob_meta_rides_checkpoint_manifest(self, tmp_path):
        """The serving version's manifest entry records the exported
        blob (crc/quant/bytes): lineage from training state to the
        exact bits the engines serve."""
        from linkerd_tpu.lifecycle import LifecycleConfig

        async def go():
            lc = LifecycleConfig(directory=str(tmp_path / "ckpts"),
                                 checkpointEveryS=0)
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(trainEveryBatches=0, lifecycle=lc),
                MetricsTree())
            scorer = tele._ensure_scorer()
            snap = await asyncio.to_thread(scorer.snapshot)
            v = tele.lifecycle.store.save(snap, status="promoted")
            tele.lifecycle.serving_version = v
            got = []
            tele.register_weight_sink(got.append)
            assert await tele.refresh_native_weights() is True
            meta = blob_meta(got[0])
            assert meta["version"] == v  # blob stamped with the ckpt
            entry = next(e for e in tele.lifecycle.store.versions()
                         if e.version == v)
            assert entry.native_blob is not None
            assert entry.native_blob["crc"] == meta["crc"]
            # the manifest survives a reload with the annotation
            from linkerd_tpu.lifecycle import CheckpointStore
            store2 = CheckpointStore(str(tmp_path / "ckpts"))
            entry2 = next(e for e in store2.versions()
                          if e.version == v)
            assert entry2.native_blob == entry.native_blob
            tele.close()

        run(go())

    def test_config_validation(self):
        with pytest.raises(ValueError, match="nativeTier"):
            JaxAnomalyTelemeter(
                JaxAnomalyConfig(nativeTier="sometimes"), MetricsTree())
        with pytest.raises(ValueError, match="nativeQuant"):
            JaxAnomalyTelemeter(
                JaxAnomalyConfig(nativeQuant="fp4"), MetricsTree())


class TestControllerWiring:
    def test_controller_pushes_route_feature_and_weights(self):
        """The FastPathController registers the engine as a weight sink
        at start() and pushes the dst-path hash after set_route — the
        stub engine records both."""
        from linkerd_tpu.core import Dtab, Path
        from linkerd_tpu.models.features import path_hash_cols
        from linkerd_tpu.router.fastpath import FastPathController

        class StubEngine:
            def __init__(self):
                self.features = {}
                self.blobs = []

            def start(self):
                pass

            def set_route(self, host, eps):
                pass

            def set_route_feature(self, host, col, sign):
                self.features[host] = (col, sign)
                return True

            def publish_weights(self, blob):
                self.blobs.append(blob)

            def close(self):
                pass

        async def go():
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(trainEveryBatches=0), MetricsTree())
            eng = StubEngine()
            ctl = FastPathController(
                eng, interpreter=None, base_dtab=Dtab.read(""),
                prefix=Path.read("/svc"), label="fp",
                metrics=MetricsTree(), telemeters=[tele])
            # a blob published BEFORE start() replays at registration
            assert await tele.refresh_native_weights() is True
            await ctl.start()
            assert len(eng.blobs) == 1
            ctl.push_route_feature("web")
            assert eng.features["web"] == path_hash_cols("/svc/web")
            await ctl.close()
            # close() unregistered the sink: a later promote must not
            # call into the (freed, in the real engine) publish hook
            assert await tele.refresh_native_weights() is True
            assert len(eng.blobs) == 1
            tele.close()

        run(go())

    def test_model_json_surfaces_native_tier(self):
        async def go():
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(trainEveryBatches=0), MetricsTree())
            await tele.refresh_native_weights()
            handlers = dict(tele.admin_handlers())
            rsp = await handlers["/model.json"](None)
            import json
            body = json.loads(rsp.body.decode())
            nt = body["native_tier"]
            assert nt["mode"] == "primary"
            assert nt["blob"]["version"] >= 0
            assert "native_scored_fraction" in nt
            tele.close()

        run(go())
