"""Line-rate scoring tests: donated ring dispatch safety, hot-swap
during in-flight donated batches, native-ring wraparound under
backpressure, the adaptive micro-batcher, and sidecar tier demotion.

The donation contract under test (COMPONENTS.md §2.11): a donated input
buffer must NEVER be re-read after dispatch (JAX deletes it; re-reads
raise), hot-swap during an in-flight donated batch completes or fails
cleanly, and ring wraparound drops-and-counts instead of corrupting
unconsumed rows.
"""

import asyncio
import time

import numpy as np
import pytest

from linkerd_tpu.models.features import FEATURE_DIM, FeatureVector, featurize
from linkerd_tpu.telemetry.anomaly import (
    InProcessScorer, JaxAnomalyConfig, JaxAnomalyTelemeter,
)
from linkerd_tpu.telemetry.linerate import (
    NATIVE_ROW_WIDTH, NativeFeatureRing, NativeFeaturizer, RingDispatcher,
    TieredScorer,
)
from linkerd_tpu.telemetry.metrics import MetricsTree


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 120))


def put(staging):
    """Stub placement for a stub step: the staging buffer as it is."""
    return staging


class TestRingDispatcher:
    def test_dispatch_returns_scores_and_reuses_staging(self):
        calls = []

        def step(staging):
            calls.append(staging)
            return staging.sum(axis=1)

        async def go():
            d = RingDispatcher(4, lambda n: 8)
            try:
                out1 = await d.dispatch(np.ones((3, 4), np.float32), step,
                                        put)
                out2 = await d.dispatch(
                    np.full((3, 4), 2.0, np.float32), step, put)
                assert out1.shape == (3,) and (out1 == 4.0).all()
                assert (out2 == 8.0).all()
                # double-buffered: two dispatches of one bucket use the
                # SAME two persistent staging buffers, not fresh arrays
                assert len({id(c) for c in calls}) <= 2
            finally:
                d.close()

        run(go())

    def test_backpressure_bounds_slots_per_bucket(self):
        inflight = []
        release = asyncio.Event()

        async def go():
            d = RingDispatcher(2, lambda n: 4, depth=2)

            class SlowResult:
                """np.asarray on the drainer blocks until released."""

                def __init__(self, staging):
                    self.staging = staging

                def __array__(self, dtype=None, copy=None):
                    # runs on the drainer thread
                    while not release.is_set():
                        time.sleep(0.001)
                    return np.zeros(4, np.float32)

            def step(staging):
                inflight.append(1)
                return SlowResult(staging)

            try:
                t1 = asyncio.ensure_future(
                    d.dispatch(np.ones((2, 2), np.float32), step, put))
                t2 = asyncio.ensure_future(
                    d.dispatch(np.ones((2, 2), np.float32), step, put))
                t3 = asyncio.ensure_future(
                    d.dispatch(np.ones((2, 2), np.float32), step, put))
                await asyncio.sleep(0.05)
                # only two slots exist: the third dispatch must wait
                assert len(inflight) == 2
                release.set()
                await asyncio.gather(t1, t2, t3)
                assert len(inflight) == 3
            finally:
                release.set()
                d.close()

        run(go())

    def test_step_exception_releases_slot(self):
        async def go():
            d = RingDispatcher(2, lambda n: 4)

            def boom(staging):
                raise RuntimeError("no")

            try:
                for _ in range(5):  # more dispatches than slots: a
                    # leaked slot would deadlock the later attempts
                    with pytest.raises(RuntimeError):
                        await d.dispatch(np.ones((2, 2), np.float32),
                                         boom, put)
            finally:
                d.close()

        run(go())

    def test_close_rejects_new_dispatch(self):
        async def go():
            d = RingDispatcher(2, lambda n: 4)
            d.close()
            with pytest.raises(RuntimeError):
                await d.dispatch(np.ones((1, 2), np.float32),
                                 lambda s: s, put)

        run(go())


class TestDonationSafety:
    def test_donated_device_buffer_never_rereadable(self):
        """A buffer dispatched through the ring with a donating step is
        deleted — any re-read raises instead of silently returning
        stale data. Uses a same-shape step so every backend (CPU
        included) actually consumes the donation."""
        import jax

        async def go():
            d = RingDispatcher(4, lambda n: 4)
            donating = jax.jit(lambda v: v * 2.0, donate_argnums=(0,))
            dev = jax.devices()[0]
            captured = []

            def place(staging):
                xd = jax.device_put(staging, dev)
                captured.append(xd)
                return xd

            try:
                out = await d.dispatch(
                    np.ones((4, 4), np.float32), donating, place)
                assert (out == 2.0).all()
                (xd,) = captured
                assert xd.is_deleted()
                with pytest.raises(RuntimeError):
                    np.asarray(xd)
            finally:
                d.close()

        run(go())

    def test_scorer_dispatch_path_drops_device_buffer(self):
        """On the real scorer the device copy is handed to the donating
        step and never re-read. Backends that can fold the [B, D] input
        into the [B] output consume the donation (deleted buffer,
        re-read raises); backends that decline it must still score
        correctly — the structural contract is that the path works
        without ever touching the buffer again either way."""
        import jax

        async def go():
            scorer = InProcessScorer()
            captured = []
            orig_step = scorer._scorer

            def spying(params, state, xd, n, layout):
                captured.append(xd)
                return orig_step(params, state, xd, n, layout)

            scorer._scorer = spying
            try:
                x = np.random.default_rng(0).standard_normal(
                    (16, scorer.cfg.in_dim)).astype(np.float32)
                out = await scorer.score(x)
                assert out.shape == (16,)
                assert np.isfinite(out).all()
                (xd,) = captured
                if xd.is_deleted():  # donation consumed (e.g. TPU)
                    with pytest.raises(RuntimeError):
                        np.asarray(xd)
                # either way a second batch reuses the same staging
                # slot cleanly
                out2 = await scorer.score(x)
                assert np.allclose(out, out2)
            finally:
                scorer._scorer = orig_step
                scorer.close()

        run(go())

    def test_scores_match_non_donating_reference(self):
        """Donation must not change values: ring-dispatch scores equal
        a fresh non-donating evaluation of the same model."""
        from linkerd_tpu.models.anomaly import anomaly_scores

        async def go():
            import jax
            scorer = InProcessScorer()
            x = np.random.default_rng(1).standard_normal(
                (32, scorer.cfg.in_dim)).astype(np.float32)
            got = await scorer.score(x)
            ref = np.asarray(anomaly_scores(
                scorer.params, np.asarray(x), scorer.cfg))
            assert np.allclose(got, ref, atol=2e-2)
            scorer.close()

        run(go())

    def test_hot_swap_during_inflight_donated_batch(self):
        """restore() while a donated batch is in flight: the in-flight
        batch completes against the captured (old) params; the next
        batch scores against the restored model; nothing raises."""

        async def go():
            scorer = InProcessScorer(seed=0, learning_rate=5e-3)
            rng = np.random.default_rng(2)
            x = rng.standard_normal(
                (64, scorer.cfg.in_dim)).astype(np.float32)
            labels = np.zeros(64, np.float32)
            mask = np.ones(64, np.float32)
            snap = scorer.snapshot()
            for _ in range(4):  # move the live model away from snap
                await scorer.fit(x, labels, mask)
            trained = await scorer.score(x)

            # dispatch a batch and IMMEDIATELY hot-swap mid-flight
            fut = asyncio.ensure_future(scorer.score(x))
            await asyncio.to_thread(scorer.restore, snap)
            inflight = await fut
            assert np.isfinite(inflight).all()

            after = await scorer.score(x)
            assert np.isfinite(after).all()
            # the post-swap batch scores with the RESTORED params
            fresh = InProcessScorer(seed=0, learning_rate=5e-3)
            fresh.restore(snap)
            expect = await fresh.score(x)
            assert np.allclose(after, expect, atol=1e-5)
            assert not np.allclose(after, trained, atol=1e-6)
            scorer.close()
            fresh.close()

        run(go())


class TestNativeFeatureRing:
    def test_produce_consume_roundtrip(self):
        ring = NativeFeatureRing(8)
        views = ring.produce_views(3)
        assert sum(len(v) for v in views) == 3
        views[0][:] = np.arange(
            3 * NATIVE_ROW_WIDTH, dtype=np.float32).reshape(
                3, NATIVE_ROW_WIDTH)
        ring.commit(3)
        got = ring.consume(8)
        assert got.shape == (3, NATIVE_ROW_WIDTH)
        assert (got.ravel() == np.arange(3 * NATIVE_ROW_WIDTH)).all()
        assert len(ring) == 0

    def test_wraparound_preserves_row_integrity(self):
        ring = NativeFeatureRing(4)
        # fill, consume 2, refill past the physical end
        v = ring.produce_views()
        v[0][:] = 1.0
        ring.commit(4)
        ring.consume(2)
        views = ring.produce_views()
        total = sum(len(w) for w in views)
        assert total == 2  # free slots only
        for w in views:
            w[:] = 7.0
        ring.commit(2)
        # rows come out whole and in order: two old, then two new
        a = ring.consume(16)
        b = ring.consume(16)
        rows = np.concatenate([a.copy(), b.copy()])
        assert (rows[:2] == 1.0).all()
        assert (rows[2:] == 7.0).all()

    def test_backpressure_drops_and_counts_never_corrupts(self):
        """A full ring exposes NO writable views — overflow rows are
        dropped at the producer (drop-and-count), and the unconsumed
        rows read back bit-identical."""
        ring = NativeFeatureRing(4)
        v = ring.produce_views()
        for i, w in enumerate(v):
            w[:] = float(i + 1)
        ring.commit(4)
        before = ring.buf.copy()
        assert ring.produce_views() == []  # no room: nothing writable
        ring.drop(3)  # producer counts the overflow
        assert ring.dropped == 3
        assert (ring.buf == before).all()
        assert len(ring.consume(16)) == 4

    def test_commit_beyond_free_raises(self):
        ring = NativeFeatureRing(2)
        ring.produce_views()
        ring.commit(2)
        with pytest.raises(ValueError):
            ring.commit(1)


class TestNativeFeaturizer:
    def test_vectorized_encoding_matches_featurize(self):
        """The zero-copy block encoder must agree with the per-row
        reference encoding on every column it populates."""
        f = NativeFeaturizer(resolver=lambda rid: f"/svc/route-{rid}")
        block = np.array([
            # route_id, lat_ms, status, req_b, rsp_b, ts_s
            [3, 12.5, 200, 100, 2048, 1.0],
            [3, 80.0, 500, 10, 0, 1.1],
            [7, 5.0, 404, 0, 512, 1.2],
        ], np.float32)
        x, inv, dsts = f.encode_block(block)
        assert x.shape == (3, FEATURE_DIM)
        assert sorted(dsts) == ["/svc/route-3", "/svc/route-7"]
        for i, row in enumerate(block):
            ref = featurize(FeatureVector(
                latency_ms=float(row[1]), status=int(row[2]),
                request_bytes=int(row[3]), response_bytes=int(row[4]),
                concurrency=1, dst_path=dsts[inv[i]]))
            # drift col (32) uses block-granular temporal state; all
            # other populated columns must match the reference exactly
            ref[32] = x[i, 32]
            assert np.allclose(x[i], ref, atol=1e-6), f"row {i}"

    def test_temporal_drift_reacts_to_latency_shift(self):
        f = NativeFeaturizer(resolver=lambda rid: "/svc/a")
        base = np.array([[1, 10.0, 200, 0, 0, 1.0]] * 8, np.float32)
        f.encode_block(base)
        spike = np.array([[1, 200.0, 200, 0, 0, 2.0]], np.float32)
        x, _, _ = f.encode_block(spike)
        assert x[0, 32] > 2.0  # log1p(~190) ≈ 5.2


class TestLineRateBatcher:
    def test_rows_scored_within_linger_without_manual_drain(self):
        """The batcher is deadline-triggered: appended rows score
        within ~maxLingerMs with NO manual drain call, and the scored
        fraction reads 1.0 — 100% scored is measured, not asserted."""

        class Stub:
            async def score(self, x):
                return np.zeros(len(x), np.float32)

            async def fit(self, x, labels, mask):
                return 0.0

            def close(self):
                pass

        async def go():
            mt = MetricsTree()
            cfg = JaxAnomalyConfig(maxBatch=64, trainEveryBatches=0,
                                   maxLingerMs=2.0)
            tele = JaxAnomalyTelemeter(cfg, mt, scorer=Stub())
            rec = tele.recorder()
            drain = asyncio.ensure_future(tele.run())
            try:
                from linkerd_tpu.protocol.http import Request, Response
                from linkerd_tpu.router.service import FnService

                async def ok(req):
                    return Response(200)

                svc = rec.and_then(FnService(ok))
                for _ in range(10):
                    await svc(Request())
                t0 = time.monotonic()
                while mt.flatten().get("anomaly/scored_total", 0) < 10:
                    assert time.monotonic() - t0 < 2.0, \
                        "rows not scored within deadline"
                    await asyncio.sleep(0.005)
                flat = mt.flatten()
                assert flat["anomaly/requests_total"] == 10
                assert flat["anomaly/scored_total"] == 10
                assert flat["anomaly/scored_fraction"] == 1.0
                state = tele.model_state()
                assert state["scored_fraction"] == 1.0
            finally:
                drain.cancel()
                await asyncio.gather(drain, return_exceptions=True)
                tele.close()

        run(go())

    def test_backlog_drains_in_full_batches_with_bounded_inflight(self):
        """A backlog already on the ring when ``run()`` starts is
        drained to the last row by the one loop: full batches of at
        most ``maxBatch`` with no linger between them, the remainder
        last, never more than ``scoreConcurrency`` calls in flight."""
        max_batch, concurrency = 32, 2
        backlog = 3 * max_batch + 5

        class Stub:
            def __init__(self):
                self.sizes = []
                self.inflight = self.peak = 0

            async def score(self, x):
                self.sizes.append(len(x))
                self.inflight += 1
                self.peak = max(self.peak, self.inflight)
                await asyncio.sleep(0.01)  # let the loop get ahead
                self.inflight -= 1
                return np.zeros(len(x), np.float32)

            async def fit(self, x, labels, mask):
                return 0.0

            def close(self):
                pass

        async def go():
            mt = MetricsTree()
            stub = Stub()
            cfg = JaxAnomalyConfig(maxBatch=max_batch, trainEveryBatches=0,
                                   scoreConcurrency=concurrency)
            tele = JaxAnomalyTelemeter(cfg, mt, scorer=stub)
            for i in range(backlog):
                tele.ring.append((FeatureVector(latency_ms=float(i)), None))
            drain = asyncio.ensure_future(tele.run())
            try:
                t0 = time.monotonic()
                while mt.flatten().get("anomaly/scored_total", 0) < backlog:
                    assert time.monotonic() - t0 < 5.0, \
                        f"backlog not drained: {stub.sizes}"
                    await asyncio.sleep(0.005)
                assert len(tele.ring) == 0
                assert stub.sizes == [max_batch] * 3 + [5]
                assert stub.peak == concurrency
            finally:
                drain.cancel()
                await asyncio.gather(drain, return_exceptions=True)
                tele.close()

        run(go())

    def test_native_rows_flow_through_batcher(self):
        """Engine-style rows fed through the native ring are scored,
        attributed to their dst on the board, and counted toward the
        scored fraction."""

        class Stub:
            async def score(self, x):
                # score = normalized first column so dsts differ
                return (x[:, 0] / 10.0).astype(np.float32)

            async def fit(self, x, labels, mask):
                return 0.0

            def close(self):
                pass

        async def go():
            mt = MetricsTree()
            cfg = JaxAnomalyConfig(maxBatch=64, trainEveryBatches=0)
            tele = JaxAnomalyTelemeter(cfg, mt, scorer=Stub())
            tele.set_native_route_resolver(lambda rid: f"/fp/route-{rid}")
            views = tele.native_ring.produce_views(4)
            views[0][:] = np.array([
                [1, 50.0, 200, 0, 0, 1.0, 0, 0, 0, 0, 0, 0],
                [1, 60.0, 200, 0, 0, 1.1, 0, 0, 0, 0, 0, 0],
                [2, 900.0, 500, 0, 0, 1.2, 0, 0, 0, 0, 0, 0],
                [2, 950.0, 500, 0, 0, 1.3, 0, 0, 0, 0, 0, 0],
            ], np.float32)
            tele.native_ring.commit(4)
            tele.native_committed(4)
            n = await tele.drain_once()
            assert n == 4
            flat = mt.flatten()
            assert flat["anomaly/requests_total"] == 4
            assert flat["anomaly/scored_total"] == 4
            scores = tele.board.scores.sample()
            assert set(scores) == {"/fp/route-1", "/fp/route-2"}
            assert scores["/fp/route-2"] > scores["/fp/route-1"]
            tele.close()

        run(go())

    def test_mixed_python_and_native_batch(self):
        class Stub:
            async def score(self, x):
                return np.full(len(x), 0.5, np.float32)

            async def fit(self, x, labels, mask):
                return 0.0

            def close(self):
                pass

        async def go():
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(maxBatch=64, trainEveryBatches=0),
                MetricsTree(), scorer=Stub())
            tele.ring.append((FeatureVector(dst_path="/svc/py"), None))
            tele.set_native_route_resolver(lambda rid: "/fp/nat")
            v = tele.native_ring.produce_views(2)
            v[0][:] = np.array(
                [[9, 1.0, 200, 0, 0, 1.0, 0, 0, 0, 0, 0, 0],
                 [9, 2.0, 200, 0, 0, 1.1, 0, 0, 0, 0, 0, 0]], np.float32)
            tele.native_ring.commit(2)
            n = await tele.drain_once()
            assert n == 3
            scores = tele.board.scores.sample()
            assert set(scores) == {"/svc/py", "/fp/nat"}
            tele.close()

        run(go())


class TestTieredScorer:
    class _Primary:
        def __init__(self):
            self.fail = False
            self.calls = 0

        async def score(self, x):
            self.calls += 1
            if self.fail:
                raise RuntimeError("device sick")
            return np.zeros(len(x), np.float32)

        async def fit(self, x, labels, mask):
            if self.fail:
                raise RuntimeError("device sick")
            return 0.1

        def snapshot(self):
            return "snap"

        def restore(self, snap):
            self.restored = snap

        def close(self):
            self.closed = True

    class _Fallback:
        def __init__(self):
            self.calls = 0

        async def score(self, x):
            self.calls += 1
            return np.ones(len(x), np.float32)

        async def fit(self, x, labels, mask):
            return 0.2

        def close(self):
            self.closed = True

    def test_primary_serves_then_fallback_on_failure(self):
        from linkerd_tpu.telemetry.resilience import CircuitBreaker

        async def go():
            p, f = self._Primary(), self._Fallback()
            import itertools
            tiered = TieredScorer(p, f, breaker=CircuitBreaker(
                failures=1, backoffs=itertools.repeat(0.05)))
            x = np.zeros((4, 2), np.float32)
            assert (await tiered.score(x) == 0.0).all()  # primary
            assert tiered.primary_calls == 1
            p.fail = True
            assert (await tiered.score(x) == 1.0).all()  # fell back
            assert tiered.fallback_calls == 1
            # breaker open: the next call goes straight to fallback
            assert (await tiered.score(x) == 1.0).all()
            assert p.calls == 2  # no third primary attempt
            # primary heals; the probe (after backoff) re-admits it
            p.fail = False
            await asyncio.sleep(0.06)
            assert (await tiered.score(x) == 0.0).all()
            st = tiered.tier_state()
            assert st["primary_breaker"] == "closed"
            tiered.close()
            assert p.closed and f.closed

        run(go())

    def test_lifecycle_hooks_bind_to_primary(self):
        p, f = self._Primary(), self._Fallback()
        tiered = TieredScorer(p, f)
        assert tiered.snapshot() == "snap"
        tiered.restore("other")
        assert p.restored == "other"

    def test_telemeter_builds_tiered_scorer_by_default(self):
        """sidecarAddress + the default fallback tier => TieredScorer
        with an in-process primary; sidecarTier: primary keeps the
        legacy resilient-sidecar wiring."""
        from linkerd_tpu.telemetry.resilience import ResilientScorer

        cfg = JaxAnomalyConfig(sidecarAddress="127.0.0.1:1",
                               trainEveryBatches=0)
        tele = JaxAnomalyTelemeter(cfg, MetricsTree())
        s = tele._ensure_scorer()
        assert isinstance(s, TieredScorer)
        assert isinstance(s.primary, InProcessScorer)
        assert tele.model_state()["tiers"]["primary"] == "InProcessScorer"
        tele.close()

        cfg2 = JaxAnomalyConfig(sidecarAddress="127.0.0.1:1",
                                sidecarTier="primary",
                                trainEveryBatches=0)
        tele2 = JaxAnomalyTelemeter(cfg2, MetricsTree())
        assert isinstance(tele2._ensure_scorer(), ResilientScorer)
        tele2.close()

    def test_bad_tier_value_rejected(self):
        with pytest.raises(ValueError):
            JaxAnomalyTelemeter(
                JaxAnomalyConfig(sidecarTier="nope"), MetricsTree())


class TestShardBatch:
    def test_shard_batch_matches_device_put(self):
        import jax
        from linkerd_tpu.parallel.mesh import (
            batch_sharding, make_mesh, shard_batch,
        )

        mesh = make_mesh(jax.devices()[:1])
        x = np.random.default_rng(3).standard_normal(
            (8, 4)).astype(np.float32)
        got = shard_batch(mesh, x)
        ref = jax.device_put(x, batch_sharding(mesh))
        assert got.shape == ref.shape
        assert got.sharding == ref.sharding
        assert (np.asarray(got) == np.asarray(ref)).all()


class TestFastpathNativeFeed:
    """FastPathController drains engine feature rows C -> the
    telemeter's NativeFeatureRing (no per-row Python objects) and
    counts overflow as drops."""

    class _StubEngine:
        """drain_features_into semantics of the native engines: fill up
        to len(out) rows from a pending pool, return the count."""

        def __init__(self, rows):
            self.pending = [np.asarray(r, np.float32) for r in rows]

        def drain_features_into(self, out):
            n = min(len(out), len(self.pending))
            for i in range(n):
                out[i] = self.pending.pop(0)
            return n

        def drain_features(self):
            return np.zeros((0, NATIVE_ROW_WIDTH), np.float32)

    class _StubScorer:
        async def score(self, x):
            return np.zeros(len(x), np.float32)

        async def fit(self, x, labels, mask):
            return 0.0

        def close(self):
            pass

    def _mk_controller(self, engine, tele):
        from linkerd_tpu.core import Dtab, Path
        from linkerd_tpu.router.fastpath import FastPathController
        return FastPathController(
            engine, interpreter=None, base_dtab=Dtab.read(""),
            prefix=Path.read("/svc"), label="fp",
            metrics=MetricsTree(), telemeters=[tele])

    def test_rows_drain_into_native_ring(self):
        async def go():
            mt = MetricsTree()
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(trainEveryBatches=0), mt,
                scorer=self._StubScorer())
            eng = self._StubEngine(
                [[5, 12.0, 200, 10, 20, 1.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],
                 [5, 14.0, 500, 10, 20, 1.1, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0]])
            ctl = self._mk_controller(eng, tele)
            ctl._id_to_host[5] = "web"
            ctl._forward_features()
            assert len(tele.native_ring) == 2
            assert mt.flatten()["anomaly/requests_total"] == 2
            n = await tele.drain_once()
            assert n == 2
            # resolver installed: rows attributed under the fastpath
            # prefix + engine host
            assert "/svc/web" in tele.board.scores.sample()
            tele.close()

        run(go())

    def test_overflow_drops_and_counts(self):
        async def go():
            mt = MetricsTree()
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(trainEveryBatches=0, ringCapacity=4),
                mt, scorer=self._StubScorer())
            rows = [[1, float(i), 200, 0, 0, 1.0, 0.0, 0.0, 0.0, 0.0, 0.0,
                     0.0]
                    for i in range(10)]
            ctl = self._mk_controller(self._StubEngine(rows), tele)
            ctl._forward_features()
            assert len(tele.native_ring) == 4  # capacity
            assert tele.native_ring.dropped == 6  # counted, not lost track of
            # shed rows still count toward requests_total: under
            # backpressure the scored fraction must read < 1.0
            assert mt.flatten()["anomaly/requests_total"] == 10
            await tele.drain_once()
            assert mt.flatten()["anomaly/scored_total"] == 4
            assert mt.flatten()["anomaly/scored_fraction"] == \
                pytest.approx(0.4)
            got = tele.native_ring.consume(16).copy()
            assert len(got) == 0  # drained
            tele.close()

        run(go())

    def test_fan_out_to_multiple_telemeters(self):
        """Two jaxAnomaly telemeters both receive the drained block
        (the first zero-copy, the second by copy) — neither starves."""

        async def go():
            mts = [MetricsTree(), MetricsTree()]
            teles = [JaxAnomalyTelemeter(
                JaxAnomalyConfig(trainEveryBatches=0), m,
                scorer=self._StubScorer()) for m in mts]
            rows = [[3, float(i), 200, 0, 0, 1.0, 0.0, 0.0, 0.0, 0.0, 0.0,
                     0.0]
                    for i in range(6)]
            eng = self._StubEngine(rows)
            from linkerd_tpu.core import Dtab, Path
            from linkerd_tpu.router.fastpath import FastPathController
            ctl = FastPathController(
                eng, interpreter=None, base_dtab=Dtab.read(""),
                prefix=Path.read("/svc"), label="fp",
                metrics=MetricsTree(), telemeters=teles)
            ctl._id_to_host[3] = "web"
            ctl._forward_features()
            for tele, mt in zip(teles, mts):
                assert len(tele.native_ring) == 6
                assert mt.flatten()["anomaly/requests_total"] == 6
                assert await tele.drain_once() == 6
                assert "/svc/web" in tele.board.scores.sample()
                tele.close()

        run(go())

    def test_real_engine_drain_into_plumbing(self):
        """ctypes pointer plumbing against the real native lib: an
        idle engine drains zero rows into a ring view and rejects
        non-contiguous/wrong-dtype buffers."""
        native = pytest.importorskip("linkerd_tpu.native")
        if not native.ensure_built():
            pytest.skip("native toolchain unavailable")
        eng = native.FastPathEngine()
        try:
            ring = NativeFeatureRing(16)
            views = ring.produce_views(8)
            assert eng.drain_features_into(views[0]) == 0
            with pytest.raises(ValueError):
                eng.drain_features_into(
                    np.zeros((4, NATIVE_ROW_WIDTH), np.float64))
            with pytest.raises(ValueError):
                eng.drain_features_into(
                    np.zeros((4, 2 * NATIVE_ROW_WIDTH), np.float32)[:, ::2])
        finally:
            eng.close()


class TestTieredFit:
    def test_fit_never_routes_to_fallback(self):
        """Training binds to the primary (the lifecycle-managed model):
        with the primary breaker open, fit raises ScorerUnavailable
        instead of silently training the sidecar's remote model."""
        from linkerd_tpu.telemetry.resilience import (
            CircuitBreaker, ScorerUnavailable,
        )

        class Primary:
            def __init__(self):
                self.fail = False
                self.fits = 0

            async def score(self, x):
                if self.fail:
                    raise RuntimeError("sick")
                return np.zeros(len(x), np.float32)

            async def fit(self, x, labels, mask):
                if self.fail:
                    raise RuntimeError("sick")
                self.fits += 1
                return 0.1

            def close(self):
                pass

        class Fallback:
            def __init__(self):
                self.fits = 0

            async def score(self, x):
                return np.ones(len(x), np.float32)

            async def fit(self, x, labels, mask):
                self.fits += 1
                return 0.2

            def close(self):
                pass

        async def go():
            import itertools
            p, f = Primary(), Fallback()
            tiered = TieredScorer(p, f, breaker=CircuitBreaker(
                failures=1, backoffs=itertools.repeat(30.0)))
            x = np.zeros((2, 2), np.float32)
            labels = mask = np.zeros(2, np.float32)
            assert await tiered.fit(x, labels, mask) == 0.1
            p.fail = True
            with pytest.raises(RuntimeError):
                await tiered.fit(x, labels, mask)  # breaker opens
            # open breaker: scoring falls back, training does NOT
            assert (await tiered.score(x) == 1.0).all()
            with pytest.raises(ScorerUnavailable):
                await tiered.fit(x, labels, mask)
            assert f.fits == 0  # the remote model was never trained
            tiered.close()

        run(go())
