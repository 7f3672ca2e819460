"""North-star pipeline tests: feature recorder -> micro-batch -> scorer ->
scoreboard -> policy feedback, plus the labeled fault-injection AUC
evaluation (BASELINE.md: AUC >= 0.9 on injected-fault traces)."""

import asyncio

import numpy as np
import pytest

from linkerd_tpu.linker import load_linker
from linkerd_tpu.models.features import FEATURE_DIM
from linkerd_tpu.protocol.http import Request, Response
from linkerd_tpu.protocol.http.client import HttpClient
from linkerd_tpu.protocol.http.server import serve
from linkerd_tpu.router.service import FnService
from linkerd_tpu.telemetry.anomaly import (
    AnomalyFailureAccrualPolicy, InProcessScorer, JaxAnomalyConfig,
    ScoreBoard,
)
from linkerd_tpu.telemetry.metrics import MetricsTree
from linkerd_tpu.testing.faults import FaultInjector, FaultSpec, auc


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 120))


class TestAuc:
    def test_auc_helper(self):
        assert auc([0, 0, 1, 1], [0.1, 0.2, 0.8, 0.9]) == 1.0
        assert auc([1, 1, 0, 0], [0.1, 0.2, 0.8, 0.9]) == 0.0
        assert abs(auc([0, 1, 0, 1], [0.5, 0.5, 0.5, 0.5]) - 0.5) < 1e-9


class TestScoreBoard:
    def test_ewma_and_observability(self):
        b = ScoreBoard(alpha=0.5)
        b.update_batch(["/svc/a", "/svc/a", "/svc/b"],
                       np.array([0.8, 0.6, 0.1]))
        assert 0.6 <= b.score_of("/svc/a") <= 0.8
        assert b.score_of("/svc/b") == pytest.approx(0.1)
        b.update_batch(["/svc/b"], np.array([0.9]))
        assert b.score_of("/svc/b") == pytest.approx(0.5)  # ewma moved


class TestAnomalyPolicy:
    def test_threshold_tightens_accrual(self):
        board = ScoreBoard()
        p = AnomalyFailureAccrualPolicy(
            board, failures=5, anomalous_failures=2, threshold=0.5,
            backoffs=iter([1.0, 1.0, 1.0]))
        # calm mesh: needs 5 consecutive failures
        for _ in range(4):
            assert p.record_failure() is None
        p.record_success()
        # anomalous mesh: needs only 2
        board.update_batch(["/svc/web"], np.array([0.9]))
        assert p.record_failure() is None
        assert p.record_failure() == 1.0


class TestTelemeterPipeline:
    def test_end_to_end_scoring_and_auc(self, tmp_path):
        """Full linker with the jaxAnomaly telemeter: normal traffic, then
        injected faults; anomaly scores must separate labeled traffic with
        AUC >= 0.9 and raise the per-dst score."""
        disco = tmp_path / "disco"
        disco.mkdir()

        injector = FaultInjector(FaultSpec(error_rate=0.9, latency_ms=40.0))

        async def backend(req: Request) -> Response:
            return Response(200, body=b"x" * 200)

        async def go():
            d = await serve(injector.and_then(FnService(backend)))
            (disco / "web").write_text(f"127.0.0.1 {d.bound_port}\n")
            cfg = f"""
routers:
- protocol: http
  label: rt
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers: [{{port: 0}}]
  client:
    failureAccrual: {{kind: none}}
telemetry:
- kind: io.l5d.jaxAnomaly
  maxBatch: 512
  trainEveryBatches: 1
  reconWeight: 1.0
namers:
- kind: io.l5d.fs
  rootDir: {disco}
"""
            linker = load_linker(cfg)
            await linker.start()
            tele = linker.telemeters[0]
            proxy = HttpClient("127.0.0.1", linker.routers[0].server_ports[0])
            try:
                async def send(n):
                    for _ in range(n):
                        req = Request(method="GET", uri="/")
                        req.headers.set("Host", "web")
                        await proxy(req)

                # Phase A: normal traffic; train the autoencoder on it.
                await send(120)
                ring_copy = list(tele.ring)  # snapshot once: each epoch
                for _ in range(6):           # re-trains on the same batch
                    await tele.drain_once()
                    for item in ring_copy:  # refill so training sees more
                        tele.ring.append(item)
                    await tele.drain_once()
                baseline = tele.board.score_of("/svc/web")

                # Phase B: mixed window — alternating fault bursts and
                # normal traffic, all labeled.
                for _ in range(4):
                    injector.active = True
                    await send(30)
                    injector.active = False
                    await send(30)
                # score the labeled window WITHOUT training on it
                tele.cfg.trainEveryBatches = 0
                items = list(tele.ring)
                await tele.drain_once()
                anomalous = tele.board.score_of("/svc/web")
                assert anomalous > baseline  # score rose under faults

                # AUC over the individually labeled window (ring items
                # are (fv, label, trace, enqueued_at) since the scorer
                # spans landed)
                from linkerd_tpu.models.features import featurize_batch
                fvs = [it[0] for it in items]
                labels = [it[1] for it in items]
                x = featurize_batch(fvs)
                scorer = tele._ensure_scorer()
                scores = await scorer.score(x)
                mask = [(l, s) for l, s in zip(labels, scores)
                        if l is not None]
                got_auc = auc([l for l, _ in mask], [s for _, s in mask])
                assert got_auc >= 0.9, f"AUC {got_auc}"
            finally:
                await proxy.close()
                await linker.close()
                await d.close()

        run(go())

    def test_scorer_metrics_and_admin_handler(self, tmp_path):
        async def go():
            mt = MetricsTree()
            cfg = JaxAnomalyConfig(maxBatch=64, trainEveryBatches=0,
                                   reconWeight=1.0)
            tele = cfg.mk(mt)
            rec = tele.recorder()

            async def ok(req):
                return Response(200)

            svc = rec.and_then(FnService(ok))
            for _ in range(10):
                req = Request()
                req.ctx["dst"] = type("D", (), {"path": None})
                req.ctx["dst"].path = __import__(
                    "linkerd_tpu.core.path", fromlist=["Path"]).Path.read("/svc/x")
                await svc(req)
            n = await tele.drain_once()
            assert n == 10
            flat = mt.flatten()
            assert flat["anomaly/scored_total"] == 10
            assert flat["anomaly/batches"] == 1
            assert "anomaly/dst/svc.x" in flat

            handlers = tele.admin_handlers()
            assert handlers[0][0] == "/anomaly.json"
            rsp = await handlers[0][1](Request())
            assert rsp.status == 200
            tele.close()

        run(go())


class TestGrpcSidecar:
    def test_score_and_fit_over_grpc(self):
        from linkerd_tpu.telemetry.sidecar import (
            GrpcScorerClient, ScorerSidecar, decode_fit, encode_fit,
            decode_matrix, encode_matrix,
        )

        # codec roundtrip
        x = np.random.default_rng(0).standard_normal((5, FEATURE_DIM)).astype(np.float32)
        assert (decode_matrix(encode_matrix(x)) == x).all()
        labels = np.ones(5, np.float32)
        mask = np.zeros(5, np.float32)
        x2, l2, m2 = decode_fit(encode_fit(x, labels, mask))
        assert (x2 == x).all() and (l2 == labels).all() and (m2 == mask).all()

        async def go():
            sidecar = await ScorerSidecar(warmup_rows=4).start()
            # warmup must pre-compile without contaminating scorer state
            assert sidecar.scorer._norm_initialized is False
            client = GrpcScorerClient(f"127.0.0.1:{sidecar.port}")
            try:
                scores = await client.score(x)
                assert scores.shape == (5,)
                assert np.isfinite(scores).all()
                loss = await client.fit(x, labels, np.ones(5, np.float32))
                assert np.isfinite(loss)
                # fit actually trains: loss decreases over steps
                losses = [await client.fit(x, np.zeros(5, np.float32),
                                           np.zeros(5, np.float32))
                          for _ in range(10)]
                assert losses[-1] < losses[0]
            finally:
                client.close()
                await sidecar.close()

        run(go())


class TestSidecarCodec:
    """Length-prefixed codec edge cases: zero-row and non-contiguous
    (sliced) arrays round-trip; truncated payloads raise ValueError
    instead of np.frombuffer silently misreading."""

    def test_zero_row_roundtrip(self):
        from linkerd_tpu.telemetry.sidecar import (
            decode_fit, decode_matrix, encode_fit, encode_matrix,
        )
        empty = np.zeros((0, FEATURE_DIM), np.float32)
        out = decode_matrix(encode_matrix(empty))
        assert out.shape == (0, FEATURE_DIM)
        x, l, m = decode_fit(encode_fit(
            empty, np.zeros(0, np.float32), np.zeros(0, np.float32)))
        assert x.shape == (0, FEATURE_DIM) and len(l) == 0 and len(m) == 0

    def test_non_contiguous_roundtrip(self):
        from linkerd_tpu.telemetry.sidecar import (
            decode_fit, decode_matrix, encode_fit, encode_matrix,
        )
        rng = np.random.default_rng(1)
        base = rng.standard_normal((16, FEATURE_DIM)).astype(np.float32)
        labels = np.arange(16, dtype=np.float32)
        # every-other-row views are not C-contiguous
        x, l, m = base[::2], labels[::2], labels[::2] * 0 + 1
        assert not x.flags["C_CONTIGUOUS"]
        assert (decode_matrix(encode_matrix(x)) == x).all()
        x2, l2, m2 = decode_fit(encode_fit(x, l, m))
        assert (x2 == x).all() and (l2 == l).all() and (m2 == m).all()

    def test_truncated_and_malformed_payloads_raise(self):
        from linkerd_tpu.telemetry.sidecar import (
            decode_fit, decode_matrix, encode_fit, encode_matrix,
        )
        x = np.ones((4, FEATURE_DIM), np.float32)
        good = encode_matrix(x)
        with pytest.raises(ValueError):
            decode_matrix(good[:-8])       # short payload
        with pytest.raises(ValueError):
            decode_matrix(good[:6])        # shorter than the header
        with pytest.raises(ValueError):
            decode_matrix(good + b"\x00" * 4)  # trailing garbage
        fit = encode_fit(x, np.zeros(4, np.float32), np.ones(4, np.float32))
        with pytest.raises(ValueError):
            decode_fit(fit[:-4])           # truncated mask
        with pytest.raises(ValueError):
            decode_fit(fit + b"\x00" * 4)  # trailing garbage
        with pytest.raises(ValueError):
            encode_matrix(np.ones(8, np.float32))  # not [n, d]
        with pytest.raises(ValueError):
            # label/mask row mismatch must not encode shifted payloads
            encode_fit(x, np.zeros(3, np.float32), np.ones(4, np.float32))


# -- a fit's statistics: reduced on the device from the one placed batch ------

def scorer_on(placement: str, **kw) -> InProcessScorer:
    """``one``: pinned to the first of the test mesh's eight CPU devices,
    as the chip's cell runs; ``mesh``: over all eight, as a default
    scorer in these tests does."""
    import jax
    return InProcessScorer(
        devices=jax.devices()[:1] if placement == "one" else None, **kw)


def feature_rows(seed: int, n: int, anomalous: float = 0.3):
    """Seeded rows whose columns run from units into the thousands, a
    third of them labelled, ``anomalous`` of those as anomalies."""
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=(n, FEATURE_DIM)) * np.linspace(1, 900, FEATURE_DIM)
         + np.linspace(-40, 6000, FEATURE_DIM)).astype(np.float32)
    mask = (rng.random(n) < 1 / 3).astype(np.float32)
    labels = ((rng.random(n) < anomalous) * mask).astype(np.float32)
    return x, labels, mask


def rule_in_float64(norm, x, labels, mask, momentum=0.2):
    """The configuration's rule, reckoned apart in NumPy float64: mean and
    variance of the rows not labelled anomalous; the first such batch sets
    the pair, later ones blend in; a batch with no such row changes
    nothing."""
    normal = np.asarray(x, np.float64)[(mask == 0) | (labels == 0)]
    if not len(normal):
        return norm
    mu, var = normal.mean(axis=0), normal.var(axis=0) + 1e-6
    if norm is not None:
        mu = (1 - momentum) * norm[0] + momentum * mu
        var = (1 - momentum) * norm[1] + momentum * var
    return mu, var


def _first_batch():
    return [feature_rows(1, 64)]


def _second_batch():
    return [feature_rows(1, 64), feature_rows(2, 64)]


def _every_row_anomalous():
    x, _, _ = feature_rows(3, 64)
    return [feature_rows(1, 64),
            (x * 50, np.ones(64, np.float32), np.ones(64, np.float32))]


def _rows_that_pad():
    # 50 rows pad to 64: fourteen rows of zeros that are no one's traffic
    return [feature_rows(1, 50), feature_rows(2, 41)]


def _anomalous_rows_left_out():
    x, labels, mask = feature_rows(4, 64, anomalous=0.6)
    x[labels == 1] *= 1e3        # would carry both moments if counted
    assert 8 < labels.sum() < 40
    return [feature_rows(1, 64), (x, labels, mask)]


STATISTICS_CASES = {
    "first_batch": _first_batch, "second_batch": _second_batch,
    "every_row_anomalous": _every_row_anomalous,
    "rows_that_pad": _rows_that_pad,
    "anomalous_rows_left_out": _anomalous_rows_left_out}


class TestFitStatistics:
    @pytest.mark.parametrize("case", sorted(STATISTICS_CASES))
    @pytest.mark.parametrize("placement", ["one", "mesh"])
    def test_statistics_follow_the_rule(self, placement, case):
        batches = STATISTICS_CASES[case]()

        async def go():
            scorer = scorer_on(placement, fit_steps=1)
            try:
                snaps = [scorer.snapshot()]
                for x, labels, mask in batches:
                    assert np.isfinite(await scorer.fit(x, labels, mask))
                    snaps.append(scorer.snapshot())
                return snaps
            finally:
                scorer.close()

        snaps = run(go())
        assert snaps[0].norm_initialized is False
        assert (snaps[0].mu == 0).all() and (snaps[0].var == 1).all()
        want = None
        for rows in batches:
            want = rule_in_float64(want, *rows)
        got = snaps[-1]
        assert got.norm_initialized is True
        assert got.mu.dtype == got.var.dtype == np.float32
        np.testing.assert_allclose(got.mu, want[0], rtol=1e-5)
        np.testing.assert_allclose(got.var, want[1], rtol=1e-5)
        if case == "every_row_anomalous":
            # nothing to learn from: pair and flag as the fit found them
            assert got.mu.tobytes() == snaps[-2].mu.tobytes()
            assert got.var.tobytes() == snaps[-2].var.tobytes()

    @pytest.mark.parametrize("placement", ["one", "mesh"])
    def test_a_first_batch_of_anomalies_leaves_the_flag_unset(self,
                                                              placement):
        async def go():
            scorer = scorer_on(placement, fit_steps=1)
            try:
                await scorer.fit(*_every_row_anomalous()[1])
                return scorer._norm_initialized, scorer.snapshot()
            finally:
                scorer.close()

        flag, snap = run(go())
        assert flag is False and snap.norm_initialized is False
        assert (snap.mu == 0).all() and (snap.var == 1).all()

    @pytest.mark.parametrize("fit_steps", [1, 4])
    @pytest.mark.parametrize("placement", ["one", "mesh"])
    def test_a_fit_ships_its_batch_once(self, placement, fit_steps):
        import time

        from linkerd_tpu.telemetry import phases
        x, labels, mask = feature_rows(5, 50)

        async def go():
            scorer = scorer_on(placement, fit_steps=fit_steps)
            try:
                t = time.monotonic()
                await scorer.fit(x, labels, mask)
                return t
            finally:
                scorer.close()

        t = run(go())
        (rec,) = [c for c in phases.records()
                  if c.kind == phases.FIT and c.t0 >= t]
        # 50 rows pad to 64: the rows, labels, mask and the row mask
        assert rec.counts["fit.shipped_bytes"] == 64 * (FEATURE_DIM + 3) * 4
        assert [n for n, _ in rec.marks].count(phases.STEP) == fit_steps

    @pytest.mark.parametrize("placement", ["one", "mesh"])
    def test_a_score_begun_once_fit_has_yielded_meets_its_statistics(
            self, placement):
        """Held to what the benchmark's comparison holds such a call to:
        the parameters before the fit or after any of its steps, under the
        fit's statistics; never the statistics from before it."""
        import dataclasses
        first, batch = feature_rows(6, 64), feature_rows(7, 64)
        steps = 3

        async def scores_under(twin, snap):
            twin.restore(snap)
            return await twin.score(batch[0])

        async def go():
            scorer = scorer_on(placement, fit_steps=steps)
            twins = [scorer_on(placement, fit_steps=k)
                     for k in range(1, steps + 1)]
            try:
                await scorer.fit(*first)
                before = scorer.snapshot()
                fit = asyncio.ensure_future(scorer.fit(*batch))
                # first runs when fit has reached its first await
                met = await asyncio.ensure_future(scorer.score(batch[0]))
                await fit
                after = scorer.snapshot()
                new = [dataclasses.replace(before, mu=after.mu, var=after.var)]
                for twin in twins:
                    twin.restore(before)
                    await twin.fit(*batch)
                    new.append(twin.snapshot())
                old = [dataclasses.replace(s, mu=before.mu, var=before.var)
                       for s in new]
                return (met, [await scores_under(twins[0], s) for s in new],
                        [await scores_under(twins[0], s) for s in old], after,
                        new[-1])
            finally:
                for s in [scorer] + twins:
                    s.close()

        met, under_new, under_old, after, twin_after = run(go())
        # the twin's steps are the fit's own
        assert twin_after.mu.tobytes() == after.mu.tobytes()
        np.testing.assert_allclose(
            twin_after.params["enc"][0]["w"], after.params["enc"][0]["w"],
            rtol=0, atol=1e-7)
        gap_new = [float(np.max(np.abs(met - s))) for s in under_new]
        gap_old = [float(np.max(np.abs(met - s))) for s in under_old]
        assert min(gap_new) < 1e-6, (gap_new, gap_old)
        assert min(gap_old) > 1e-3, (gap_new, gap_old)

    @pytest.mark.parametrize("placement", ["one", "mesh"])
    def test_a_score_captures_one_fits_pair_whole(self, placement):
        """``restore`` runs on a thread (the lifecycle's swap) and repoints
        the statistics while the loop dispatches: a call takes mu and var
        in one read of one attribute, so never one fit's mean beside
        another's variance."""
        import dataclasses
        import sys
        import threading

        async def go():
            scorer = scorer_on(placement, fit_steps=1)
            base = scorer.snapshot()
            d = scorer.cfg.in_dim
            snaps = [dataclasses.replace(
                base, mu=np.full(d, v, np.float32),
                var=np.full(d, v, np.float32), norm_initialized=True)
                for v in (2.0, 3.0)]
            pairs, real, stop = [], scorer._scorer, threading.Event()

            def recording(params, state, xd, n, layout):
                pairs.append(state[:2])
                return real(params, state, xd, n, layout)

            def swapper():
                i = 0
                while not stop.is_set():
                    scorer.restore(snaps[i % 2])
                    i += 1

            scorer._scorer = recording
            thread = threading.Thread(target=swapper, daemon=True)
            interval = sys.getswitchinterval()
            sys.setswitchinterval(1e-5)
            try:
                thread.start()
                x = np.zeros((8, d), np.float32)
                for _ in range(150):
                    await scorer.score(x)
            finally:
                stop.set()
                sys.setswitchinterval(interval)
                thread.join(30)
                scorer.close()
            assert not thread.is_alive()
            return pairs

        pairs = run(go())
        assert len(pairs) == 150
        tags = {(float(mu[0]), float(var[0])) for mu, var in pairs}
        assert tags <= {(0.0, 1.0), (2.0, 2.0), (3.0, 3.0)}, tags
        assert len(tags) > 1    # the thread did repoint them meanwhile

    @pytest.mark.parametrize("placement", ["one", "mesh"])
    def test_snapshot_restore_swap_warmup_keep_the_triple(self, placement):
        def triple(snap):
            return (snap.mu.tobytes(), snap.var.tobytes(),
                    snap.norm_initialized)

        async def go():
            scorer = scorer_on(placement, fit_steps=1)
            other = scorer_on(placement, seed=1, fit_steps=1)
            try:
                fresh = scorer.snapshot()
                await scorer.warmup(4)
                assert triple(scorer.snapshot()) == triple(fresh)
                assert scorer._norm_initialized is False
                await scorer.fit(*feature_rows(8, 64))
                await other.fit(*feature_rows(9, 64))
                mine, theirs = scorer.snapshot(), other.snapshot()
                assert mine.norm_initialized and triple(mine) != triple(theirs)
                # warm-up fits zeros in between and puts the triple back
                await scorer.warmup(4)
                assert triple(scorer.snapshot()) == triple(mine)
                assert scorer._norm_initialized is True
                displaced = scorer.swap(theirs)
                assert triple(displaced) == triple(mine)
                assert triple(scorer.snapshot()) == triple(theirs)
                scorer.restore(displaced)
                assert triple(scorer.snapshot()) == triple(mine)
                scorer.restore(fresh)
                assert triple(scorer.snapshot()) == triple(fresh)
                assert scorer._norm_initialized is False
            finally:
                scorer.close()
                other.close()

        run(go())
