"""Chaos / resilience e2e: deadline propagation, overload shedding, and
scorer-path graceful degradation under injected faults.

Covers the ISSUE 3 acceptance criteria: with the scorer sidecar
blackholed the data plane still answers within its deadline budget and
``anomaly/degraded`` flips (and recovers within one breaker-probe
interval once the fault clears); ``l5d-ctx-deadline`` round-trips a
two-router chain with the edge clamping to its own budget; an expired
deadline is shed at the edge without dispatching downstream; overloaded
routers shed with a retryable signal (http 503 + ``l5d-retryable``,
h2 ``RST_STREAM REFUSED_STREAM``).
"""

import asyncio
import itertools
import time

import numpy as np
import pytest

from linkerd_tpu.linker import load_linker
from linkerd_tpu.protocol.http import Request, Response
from linkerd_tpu.protocol.http.client import HttpClient
from linkerd_tpu.protocol.http.server import serve
from linkerd_tpu.router.admission import AdmissionControlFilter, OverloadShed
from linkerd_tpu.router.classifiers import ResponseClass
from linkerd_tpu.router.deadline import (
    CTX_DEADLINE, Deadline, DeadlineExceeded, DeadlineFilter,
    ServerDeadlineFilter,
)
from linkerd_tpu.router.retries import ClassifiedRetries, RetryBudget
from linkerd_tpu.router.service import FnService, filters_to_service
from linkerd_tpu.telemetry.metrics import MetricsTree
from linkerd_tpu.telemetry.resilience import (
    CircuitBreaker, ResilientScorer, ScorerUnavailable,
)
from linkerd_tpu.testing.faults import BlackholeServer, FaultScorer


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 60))


async def eventually(pred, timeout: float = 5.0, what: str = "",
                     tick=None):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if tick is not None:
            await tick()
        if pred():
            return
        await asyncio.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


class _StubScorer:
    """Minimal healthy scorer: constant scores, no jax."""

    def __init__(self):
        self.scored = 0

    async def score(self, x):
        self.scored += len(x)
        return np.zeros(len(x), np.float32)

    async def fit(self, x, labels, mask):
        return 0.0

    def close(self):
        pass


class TestDeadlineCodec:
    def test_roundtrip(self):
        dl = Deadline.after(1.5)
        assert Deadline.decode(dl.encode()) == dl

    def test_decode_rejects_garbage(self):
        assert Deadline.decode("") is None
        assert Deadline.decode("abc") is None
        assert Deadline.decode("1 2 3") is None
        assert Deadline.decode("-1 5") is None
        assert Deadline.decode("12 nope") is None

    def test_combined_takes_tightest(self):
        a = Deadline(timestamp_ns=100, deadline_ns=5_000)
        b = Deadline(timestamp_ns=200, deadline_ns=3_000)
        c = a.combined(b)
        assert c.deadline_ns == 3_000 and c.timestamp_ns == 200

    def test_remaining_and_expired(self):
        assert 0.9 < Deadline.after(1.0).remaining_s() <= 1.0
        assert Deadline.after(-0.1).expired


class TestDeadlineFilter:
    def test_expired_rejected_before_dispatch(self):
        calls = []

        async def svc(req):
            calls.append(1)
            return Response(200)

        async def go():
            req = Request()
            req.ctx["deadline"] = Deadline.after(-0.01)
            with pytest.raises(DeadlineExceeded):
                await DeadlineFilter().apply(req, FnService(svc))
            assert calls == []  # shed up front, never dispatched

        run(go())

    def test_total_timeout_without_header(self):
        async def slow(req):
            await asyncio.sleep(1.0)
            return Response(200)

        async def go():
            with pytest.raises(DeadlineExceeded):
                await DeadlineFilter(0.05).apply(Request(), FnService(slow))

        run(go())

    def test_incoming_deadline_clamps_total_timeout(self):
        async def slow(req):
            await asyncio.sleep(5.0)
            return Response(200)

        async def go():
            req = Request()
            req.ctx["deadline"] = Deadline.after(0.05)
            t0 = time.monotonic()
            with pytest.raises(DeadlineExceeded):
                # configured budget is 10s; the propagated 50ms wins
                await DeadlineFilter(10.0).apply(req, FnService(slow))
            assert time.monotonic() - t0 < 2.0

        run(go())

    def test_narrows_ctx_deadline_for_downstream(self):
        seen = {}

        async def svc(req):
            seen["dl"] = req.ctx["deadline"]
            return Response(200)

        async def go():
            req = Request()
            req.ctx["deadline"] = Deadline.after(30.0)
            await DeadlineFilter(0.5).apply(req, FnService(svc))
            # downstream sees min(incoming, now + totalTimeout)
            assert seen["dl"].remaining_s() <= 0.5

        run(go())

    def test_server_filter_decodes_header_and_sheds_expired(self):
        async def ok(req):
            return Response(200)

        async def go():
            f = ServerDeadlineFilter()
            req = Request()
            req.headers.set(CTX_DEADLINE, Deadline.after(5.0).encode())
            await f.apply(req, FnService(ok))
            assert req.ctx["deadline"].remaining_s() > 4.0

            expired = Request()
            expired.headers.set(CTX_DEADLINE,
                                Deadline.after(-0.5).encode())
            with pytest.raises(DeadlineExceeded):
                await f.apply(expired, FnService(ok))

        run(go())


class TestRetriesDeadlineClamp:
    def test_backoff_overrunning_budget_skips_retry(self):
        calls = []

        async def failing(req):
            calls.append(1)
            return Response(503)

        async def go():
            from linkerd_tpu.router.classifiers import RetryableIdempotent5XX
            metrics = MetricsTree()
            f = ClassifiedRetries(
                RetryableIdempotent5XX().mk(),
                RetryBudget(min_retries_per_s=100),
                backoffs=[5.0] * 3, metrics=metrics, scope=("svc",))
            req = Request(method="GET")
            req.ctx["deadline"] = Deadline.after(0.5)
            t0 = time.monotonic()
            rsp = await f.apply(req, FnService(failing))
            assert rsp.status == 503
            assert len(calls) == 1  # the 5s backoff would overrun 0.5s
            assert time.monotonic() - t0 < 1.0
            flat = metrics.flatten()
            assert flat["svc/retries/deadline_skipped"] == 1

        run(go())


class TestAdmissionControl:
    def test_sheds_beyond_concurrency_plus_queue(self):
        gate = asyncio.Event()

        async def waiting(req):
            await gate.wait()
            return Response(200)

        async def go():
            node = MetricsTree().scope("adm")
            f = AdmissionControlFilter(1, max_pending=1, metrics_node=node)
            svc = f.and_then(FnService(waiting))
            t1 = asyncio.ensure_future(svc(Request()))   # holds the slot
            await asyncio.sleep(0.02)
            t2 = asyncio.ensure_future(svc(Request()))   # queues
            await asyncio.sleep(0.02)
            with pytest.raises(OverloadShed):            # queue full
                await svc(Request())
            gate.set()
            r1, r2 = await asyncio.gather(t1, t2)
            assert r1.status == 200 and r2.status == 200

        run(go())

    def test_zero_pending_sheds_immediately(self):
        gate = asyncio.Event()

        async def waiting(req):
            await gate.wait()
            return Response(200)

        async def go():
            f = AdmissionControlFilter(1, max_pending=0)
            svc = f.and_then(FnService(waiting))
            t1 = asyncio.ensure_future(svc(Request()))
            await asyncio.sleep(0.02)
            with pytest.raises(OverloadShed):
                await svc(Request())
            gate.set()
            assert (await t1).status == 200

        run(go())


class TestAdmissionControlConfig:
    def test_rejected_on_non_http_protocols(self):
        from linkerd_tpu.config import ConfigError
        from linkerd_tpu.linker import Linker, parse_linker_spec

        for proto in ("thrift", "mux"):
            spec = parse_linker_spec(f"""
routers:
- protocol: {proto}
  admissionControl: {{maxConcurrency: 4}}
""")
            with pytest.raises(ConfigError, match="admissionControl"):
                Linker(spec)

    def test_bad_values_fail_config_load(self):
        from linkerd_tpu.config import ConfigError
        from linkerd_tpu.linker import Linker, parse_linker_spec

        spec = parse_linker_spec("""
routers:
- protocol: http
  admissionControl: {maxConcurrency: 0}
""")
        with pytest.raises(ConfigError, match="admissionControl"):
            Linker(spec)


class TestH2RefusedSignals:
    def test_error_responder_raises_refused_for_routing_failures(self):
        from linkerd_tpu.protocol.h2.frames import REFUSED_STREAM
        from linkerd_tpu.protocol.h2.messages import H2Request
        from linkerd_tpu.protocol.h2.stream import StreamReset
        from linkerd_tpu.router.balancer import NoBrokersAvailable
        from linkerd_tpu.router.h2_layer import H2ErrorResponder

        async def go():
            for exc in (NoBrokersAvailable("none"),
                        OverloadShed("full")):
                async def broken(req, _e=exc):
                    raise _e

                with pytest.raises(StreamReset) as ei:
                    await H2ErrorResponder().apply(
                        H2Request(), FnService(broken))
                assert ei.value.error_code == REFUSED_STREAM

        run(go())

    def test_grpc_deadline_maps_to_trailers_only_status_4(self):
        from linkerd_tpu.protocol.h2.messages import H2Request
        from linkerd_tpu.router.h2_layer import H2ErrorResponder

        async def go():
            async def expired(req):
                raise DeadlineExceeded("too late")

            req = H2Request(method="POST", path="/svc/Score")
            req.headers.set("content-type", "application/grpc")
            rsp = await H2ErrorResponder().apply(req, FnService(expired))
            assert rsp.status == 200  # Trailers-Only gRPC error shape
            assert rsp.headers.get("grpc-status") == "4"

        run(go())

    def test_refused_is_retryable_for_any_method(self):
        from linkerd_tpu.config import lookup
        from linkerd_tpu.protocol.h2.frames import REFUSED_STREAM
        from linkerd_tpu.protocol.h2.messages import H2Request
        from linkerd_tpu.protocol.h2.stream import StreamReset

        refused = StreamReset(REFUSED_STREAM, "refused")
        post = H2Request(method="POST", path="/x")
        # non-idempotent POST + transport error is normally NOT
        # retryable; REFUSED_STREAM means never-processed, so it is
        status_cls = lookup(
            "h2classifier", "io.l5d.h2.nonRetryable5XX")().mk()
        assert status_cls.classify(post, None, None, refused) \
            is ResponseClass.RETRYABLE_FAILURE
        grpc_cls = lookup("h2classifier", "io.l5d.h2.grpc.default")().mk()
        assert grpc_cls.classify(post, None, None, refused) \
            is ResponseClass.RETRYABLE_FAILURE
        never = lookup(
            "h2classifier", "io.l5d.h2.grpc.neverRetryable")().mk()
        assert never.classify(post, None, None, refused) \
            is ResponseClass.FAILURE

    def test_h2_server_concurrency_limit_sends_rst_refused(self):
        from linkerd_tpu.protocol.h2.client import H2Client
        from linkerd_tpu.protocol.h2.frames import REFUSED_STREAM
        from linkerd_tpu.protocol.h2.messages import H2Request, H2Response
        from linkerd_tpu.protocol.h2.server import serve_h2
        from linkerd_tpu.protocol.h2.stream import StreamReset

        gate = asyncio.Event()

        async def waiting(req):
            await gate.wait()
            return H2Response(status=200, body=b"ok")

        async def go():
            server = await serve_h2(FnService(waiting), max_concurrency=1)
            client = H2Client("127.0.0.1", server.bound_port)
            try:
                t1 = asyncio.ensure_future(
                    client(H2Request(method="GET", path="/a",
                                     authority="x")))
                await asyncio.sleep(0.05)
                with pytest.raises(StreamReset) as ei:
                    await client(H2Request(method="GET", path="/b",
                                           authority="x"))
                # shed on the wire as RST_STREAM REFUSED_STREAM, not a
                # synthesized 503 body
                assert ei.value.error_code == REFUSED_STREAM
                gate.set()
                rsp = await t1
                assert rsp.status == 200
            finally:
                await client.close()
                await server.close()

        run(go())


class TestCircuitBreaker:
    def test_open_probe_close_cycle(self):
        b = CircuitBreaker(failures=2, backoffs=itertools.repeat(0.02))
        assert b.state == "closed"
        b.on_failure(False)
        assert b.state == "closed"
        b.on_failure(False)
        assert b.state == "open"
        admitted, _ = b.acquire()
        assert not admitted  # backoff not yet elapsed
        time.sleep(0.03)
        admitted, probe = b.acquire()
        assert admitted and probe
        # only ONE probe per interval
        again, _ = b.acquire()
        assert not again
        b.on_success(True)
        assert b.state == "closed"

    def test_failed_probe_reopens(self):
        b = CircuitBreaker(failures=1, backoffs=itertools.repeat(0.02))
        b.on_failure(False)
        time.sleep(0.03)
        admitted, probe = b.acquire()
        assert admitted and probe
        b.on_failure(True)
        assert b.state == "open"
        admitted, _ = b.acquire()
        assert not admitted

    def test_concurrent_failures_open_once(self):
        backoffs = iter([0.05, 99.0])
        b = CircuitBreaker(failures=1, backoffs=backoffs)
        b.on_failure(False)  # opens with the 0.05 backoff
        b.on_failure(False)  # in-flight straggler: must NOT advance
        assert b.next_probe_in_s() <= 0.05

    def test_cancelled_probe_releases_slot_without_reviving(self):
        b = CircuitBreaker(failures=1, backoffs=itertools.repeat(0.0))
        b.on_failure(False)
        admitted, probe = b.acquire()
        assert admitted and probe
        b.on_cancel(probe)
        assert b.state != "closed"  # not revived
        admitted, probe = b.acquire()
        assert admitted and probe  # slot released: next probe admitted


class TestResilientScorer:
    def test_hang_bounded_then_fail_fast(self):
        async def go():
            faulty = FaultScorer(_StubScorer())
            scorer = ResilientScorer(
                faulty, call_timeout_s=0.1,
                breaker=CircuitBreaker(failures=1,
                                       backoffs=itertools.repeat(60.0)))
            x = np.zeros((4, 8), np.float32)
            assert len(await scorer.score(x)) == 4  # healthy passthrough
            faulty.mode = "hang"
            t0 = time.monotonic()
            with pytest.raises(ScorerUnavailable):
                await scorer.score(x)  # bounded by the per-call deadline
            assert time.monotonic() - t0 < 1.0
            t0 = time.monotonic()
            with pytest.raises(ScorerUnavailable):
                await scorer.score(x)  # breaker open: fails fast
            assert time.monotonic() - t0 < 0.05

        run(go())

    def test_probe_recovers_after_fault_clears(self):
        async def go():
            faulty = FaultScorer(_StubScorer())
            scorer = ResilientScorer(
                faulty, call_timeout_s=0.1,
                breaker=CircuitBreaker(failures=1,
                                       backoffs=itertools.repeat(0.05)))
            faulty.mode = "error"
            with pytest.raises(ScorerUnavailable):
                await scorer.score(np.zeros((2, 8), np.float32))
            faulty.mode = None
            await asyncio.sleep(0.06)  # one probe interval
            out = await scorer.score(np.zeros((2, 8), np.float32))
            assert len(out) == 2
            assert scorer.breaker.state == "closed"

        run(go())

    def test_grpc_client_blackholed_sidecar_bounded(self):
        from linkerd_tpu.telemetry.sidecar import GrpcScorerClient

        async def go():
            hole = await BlackholeServer().start()
            client = GrpcScorerClient(f"127.0.0.1:{hole.bound_port}")
            scorer = ResilientScorer(
                client, call_timeout_s=0.2,
                breaker=CircuitBreaker(failures=1,
                                       backoffs=itertools.repeat(60.0)))
            try:
                t0 = time.monotonic()
                with pytest.raises(ScorerUnavailable):
                    await scorer.score(np.zeros((4, 8), np.float32))
                assert time.monotonic() - t0 < 2.0  # deadline, not a hang
                t0 = time.monotonic()
                with pytest.raises(ScorerUnavailable):
                    await scorer.score(np.zeros((4, 8), np.float32))
                assert time.monotonic() - t0 < 0.05  # breaker fails fast
            finally:
                await client.aclose()
                await hole.close()

        run(go())


class TestScoreBoardStaleness:
    def test_stale_scores_decay_to_neutral(self):
        from linkerd_tpu.telemetry.anomaly import ScoreBoard

        board = ScoreBoard(alpha=1.0, ttl_s=0.1)
        board.update_batch(["/svc/web"], np.array([0.9], np.float32))
        assert board.score_of("/svc/web") == pytest.approx(0.9)
        # age it past the TTL: halfway through the decay window
        board._updated["/svc/web"] -= 0.15
        assert board.score_of("/svc/web") == pytest.approx(0.45, abs=0.1)
        # fully stale: neutral
        board._updated["/svc/web"] -= 0.2
        assert board.score_of("/svc/web") == 0.0
        assert board.anomaly_level() == 0.0

    def test_degraded_board_reads_zero(self):
        from linkerd_tpu.telemetry.anomaly import ScoreBoard

        board = ScoreBoard(ttl_s=None)
        board.update_batch(["/svc/web"], np.array([0.9], np.float32))
        assert board.anomaly_level() > 0.5
        board.degraded = True
        assert board.anomaly_level() == 0.0

    def test_accrual_policy_falls_back_when_degraded(self):
        from linkerd_tpu.telemetry.anomaly import (
            AnomalyFailureAccrualPolicy, ScoreBoard,
        )

        board = ScoreBoard(ttl_s=None)
        board.update_batch(["/svc/web"], np.array([0.95], np.float32))
        policy = AnomalyFailureAccrualPolicy(
            board, failures=5, anomalous_failures=2, threshold=0.5,
            backoffs=iter([1.0] * 10))
        # anomalous: tightened threshold fires at 2
        assert policy.record_failure() is None
        assert policy.record_failure() == 1.0
        policy.revived()
        board.degraded = True  # scorer path down: reference behavior
        for _ in range(4):
            assert policy.record_failure() is None
        assert policy.record_failure() is not None  # base 5


class TestDeadlineChainE2E:
    def test_deadline_round_trips_and_expired_shed_at_edge(self, tmp_path):
        seen = {"headers": [], "count": 0}

        async def backend_svc(req):
            seen["count"] += 1
            seen["headers"].append(req.headers.get(CTX_DEADLINE))
            return Response(200, body=b"ok")

        async def go():
            backend = await serve(FnService(backend_svc))
            disco_b = tmp_path / "disco-b"
            disco_b.mkdir()
            (disco_b / "web").write_text(
                f"127.0.0.1 {backend.bound_port}\n")
            inner = load_linker(f"""
routers:
- protocol: http
  label: inner
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers: [{{port: 0}}]
namers:
- kind: io.l5d.fs
  rootDir: {disco_b}
""")
            await inner.start()
            disco_a = tmp_path / "disco-a"
            disco_a.mkdir()
            (disco_a / "web").write_text(
                f"127.0.0.1 {inner.routers[0].server_ports[0]}\n")
            edge = load_linker(f"""
routers:
- protocol: http
  label: edge
  dtab: |
    /svc => /#/io.l5d.fs ;
  service:
    totalTimeoutMs: 2000
  servers: [{{port: 0}}]
namers:
- kind: io.l5d.fs
  rootDir: {disco_a}
""")
            await edge.start()
            proxy = HttpClient("127.0.0.1",
                               edge.routers[0].server_ports[0])
            try:
                # 1. no incoming deadline: the edge's totalTimeout is
                # stamped and rides l5d-ctx-deadline through BOTH hops
                req = Request(uri="/")
                req.headers.set("Host", "web")
                rsp = await proxy(req)
                assert rsp.status == 200
                assert seen["count"] == 1
                hdr = seen["headers"][0]
                assert hdr is not None, "deadline did not propagate"
                dl = Deadline.decode(hdr)
                assert dl is not None and 0 < dl.remaining_s() <= 2.0

                # 2. a WIDER incoming deadline is clamped to the edge's
                # own 2s budget before propagating
                req = Request(uri="/")
                req.headers.set("Host", "web")
                req.headers.set(CTX_DEADLINE,
                                Deadline.after(30.0).encode())
                rsp = await proxy(req)
                assert rsp.status == 200
                dl = Deadline.decode(seen["headers"][1])
                assert dl.remaining_s() <= 2.0

                # 3. an EXPIRED incoming deadline is shed at the edge:
                # 504, nothing dispatched downstream
                req = Request(uri="/")
                req.headers.set("Host", "web")
                req.headers.set(CTX_DEADLINE,
                                Deadline.after(-0.2).encode())
                rsp = await proxy(req)
                assert rsp.status == 504
                assert seen["count"] == 2  # backend never saw it
                flat = edge.metrics.flatten()
                assert flat[
                    "rt/edge/server/deadline/expired_at_edge"] == 1
            finally:
                await proxy.close()
                await edge.close()
                await inner.close()
                await backend.close()

        run(go())


class TestOverloadShedE2E:
    def test_router_sheds_with_retryable_503(self, tmp_path):
        gate = asyncio.Event()

        async def waiting(req):
            await gate.wait()
            return Response(200, body=b"ok")

        async def go():
            backend = await serve(FnService(waiting))
            disco = tmp_path / "disco"
            disco.mkdir()
            (disco / "web").write_text(f"127.0.0.1 {backend.bound_port}\n")
            linker = load_linker(f"""
routers:
- protocol: http
  label: shed
  admissionControl: {{maxConcurrency: 1, maxPending: 0}}
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers: [{{port: 0}}]
namers:
- kind: io.l5d.fs
  rootDir: {disco}
""")
            await linker.start()
            port = linker.routers[0].server_ports[0]
            c1, c2 = (HttpClient("127.0.0.1", port) for _ in range(2))
            try:
                req1 = Request(uri="/1")
                req1.headers.set("Host", "web")
                t1 = asyncio.ensure_future(c1(req1))
                await asyncio.sleep(0.05)
                req2 = Request(uri="/2")
                req2.headers.set("Host", "web")
                rsp = await c2(req2)
                assert rsp.status == 503
                assert rsp.headers.get("l5d-retryable") == "true"
                gate.set()
                assert (await t1).status == 200
                flat = linker.metrics.flatten()
                assert flat["rt/shed/server/admission/shed_total"] >= 1
            finally:
                await c1.close()
                await c2.close()
                await linker.close()
                await backend.close()

        run(go())


class TestH2RefusedRetryChainE2E:
    def test_edge_router_retries_refused_shed(self, tmp_path):
        """Two h2 routers chained: the inner one sheds under admission
        control with RST_STREAM REFUSED_STREAM; the edge router's
        classified retries re-dispatch the refused stream and succeed
        once the slot frees — the shed signal is retryable end-to-end."""
        from linkerd_tpu.protocol.h2.client import H2Client
        from linkerd_tpu.protocol.h2.messages import H2Request, H2Response
        from linkerd_tpu.protocol.h2.server import serve_h2

        gate = asyncio.Event()

        async def waiting(req):
            await gate.wait()
            return H2Response(status=200, body=b"ok")

        async def go():
            backend = await serve_h2(FnService(waiting))
            disco_b = tmp_path / "disco-b"
            disco_b.mkdir()
            (disco_b / "web").write_text(
                f"127.0.0.1 {backend.bound_port}\n")
            inner = load_linker(f"""
routers:
- protocol: h2
  label: inner
  admissionControl: {{maxConcurrency: 1, maxPending: 0}}
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers: [{{port: 0}}]
namers:
- kind: io.l5d.fs
  rootDir: {disco_b}
""")
            await inner.start()
            disco_a = tmp_path / "disco-a"
            disco_a.mkdir()
            (disco_a / "web").write_text(
                f"127.0.0.1 {inner.routers[0].server_ports[0]}\n")
            edge = load_linker(f"""
routers:
- protocol: h2
  label: edge
  service:
    responseClassifier: {{kind: io.l5d.h2.retryableRead5XX}}
    retries: {{backoff: {{kind: constant, ms: 50}}}}
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers: [{{port: 0}}]
namers:
- kind: io.l5d.fs
  rootDir: {disco_a}
""")
            await edge.start()
            client = H2Client("127.0.0.1",
                              edge.routers[0].server_ports[0])
            try:
                t1 = asyncio.ensure_future(client(H2Request(
                    method="GET", path="/1", authority="web")))
                await asyncio.sleep(0.1)  # t1 occupies inner's only slot

                async def free_later():
                    await asyncio.sleep(0.15)
                    gate.set()

                freer = asyncio.ensure_future(free_later())
                rsp2 = await client(H2Request(
                    method="GET", path="/2", authority="web"))
                assert rsp2.status == 200
                (await rsp2.stream.read_all())
                rsp1 = await t1
                assert rsp1.status == 200
                await freer
                flat = edge.metrics.flatten()
                assert flat["rt/edge/service/svc.web/retries/total"] >= 1
                shed = inner.metrics.flatten()[
                    "rt/inner/server/admission/shed_total"]
                assert shed >= 1
            finally:
                await client.close()
                await edge.close()
                await inner.close()
                await backend.close()

        run(go())


class TestScorerChaosE2E:
    """The acceptance chaos scenario: sidecar blackholed -> data plane
    keeps answering inside its budget, anomaly/degraded flips to 1;
    fault clears -> scoring resumes within one probe interval."""

    def test_blackholed_scorer_degrades_and_recovers(self, tmp_path):
        async def ok(req):
            return Response(200, body=b"ok")

        async def go():
            backend = await serve(FnService(ok))
            disco = tmp_path / "disco"
            disco.mkdir()
            (disco / "web").write_text(f"127.0.0.1 {backend.bound_port}\n")
            linker = load_linker(f"""
routers:
- protocol: http
  label: chaos
  dtab: |
    /svc => /#/io.l5d.fs ;
  service:
    totalTimeoutMs: 1000
  servers: [{{port: 0}}]
namers:
- kind: io.l5d.fs
  rootDir: {disco}
telemetry:
- kind: io.l5d.jaxAnomaly
  maxBatch: 128
  trainEveryBatches: 0
  scoreTtlSecs: 0.5
""")
            tele = linker.telemeters[0]
            faulty = FaultScorer(_StubScorer())
            tele._scorer = ResilientScorer(
                faulty, call_timeout_s=0.1,
                breaker=CircuitBreaker(failures=1,
                                       backoffs=itertools.repeat(0.1)))
            await linker.start()
            proxy = HttpClient("127.0.0.1",
                               linker.routers[0].server_ports[0])
            drain = asyncio.ensure_future(tele.run())
            flat = linker.metrics.flatten

            async def one_request():
                req = Request(uri="/")
                req.headers.set("Host", "web")
                t0 = time.monotonic()
                rsp = await proxy(req)
                took = time.monotonic() - t0
                assert rsp.status == 200
                # data plane answers well inside its 1s budget even
                # with the scorer path black-holed
                assert took < 1.0, f"request took {took:.3f}s"

            try:
                # healthy: traffic scores, degraded stays 0
                for _ in range(5):
                    await one_request()
                await eventually(
                    lambda: flat().get("anomaly/scored_total", 0) > 0,
                    what="initial scoring")
                assert flat()["anomaly/degraded"] == 0.0

                # blackhole the scorer: hang every call
                faulty.mode = "hang"
                await eventually(
                    lambda: flat().get("anomaly/degraded") == 1.0,
                    timeout=15.0, what="degraded gauge flip",
                    tick=one_request)
                assert tele.board.degraded
                assert tele.model_state()["degraded"] is True

                # fault clears: one breaker-probe interval (0.1s) +
                # a drain tick later, scoring resumes and the gauge
                # drops back to 0
                scored_before = flat()["anomaly/scored_total"]
                faulty.mode = None
                await eventually(
                    lambda: (flat().get("anomaly/degraded") == 0.0
                             and flat()["anomaly/scored_total"]
                             > scored_before),
                    timeout=15.0, what="recovery", tick=one_request)
                assert flat()["anomaly/score_failures"] >= 1
            finally:
                drain.cancel()
                await asyncio.gather(drain, return_exceptions=True)
                await proxy.close()
                await linker.close()
                await backend.close()

        run(go())
