"""Tenant isolation under fire.

One abusive tenant — retry storm, slowloris, connection churn — must
degrade alone. Covered here:

- tenant extraction parity (C vs Python: bit-identical FNV-1a hash,
  header + pathSegment extraction through the native engines);
- quota shrink/recover hysteresis (no flapping) through the
  TenantAdmission governor;
- LRU cardinality bounds under hostile tenant-id churn (Python board
  AND the engines' native tables);
- retry-safety of per-tenant sheds (http 503 + l5d-retryable, h2
  RST_STREAM REFUSED_STREAM);
- the h2 rapid-reset cap (CVE-2023-44487-shaped floods die with
  ENHANCE_YOUR_CALM) + native slowloris/churn defenses;
- the chaos-matrix e2e: with the attacker tenant active, the victim
  tenant's success rate stays >= 0.99 and its p99 within bounds while
  the attacker is shed — including concurrently with a native weight
  hot-swap.
"""

import asyncio
import contextlib

import pytest

from linkerd_tpu import native
from linkerd_tpu.control.admission import TenantAdmission
from linkerd_tpu.control.state import HysteresisGovernor
from linkerd_tpu.router.admission import (
    AdmissionControlFilter, OverloadShed,
)
from linkerd_tpu.router.tenancy import (
    TenantBoard, TenantIdentifierSpec, TenantTagFilter, tenant_feature,
    tenant_hash,
)
from linkerd_tpu.router.service import FnService
from linkerd_tpu.testing.faults import (
    ConnectionChurnAttack, EchoBackend, LoopbackServer, PacedTenantClient,
    SlowlorisAttack, TenantRetryStorm,
)

native_only = pytest.mark.skipif(
    not native.ensure_built(), reason="native toolchain unavailable")


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 120))


# ---------------------------------------------------------------- hashing


class TestTenantHash:
    def test_fnv1a_reference_values(self):
        # FNV-1a 32-bit test vectors (the empty string is not a tenant,
        # but the offset basis pins the algorithm)
        assert tenant_hash("a") == 0xE40C292C
        assert tenant_hash("foobar") == 0xBF9CF968

    def test_zero_folds_to_one(self):
        # 0 means "no tenant"; any real id must never hash to it
        for s in ("a", "b", "tenant", "x" * 64):
            assert tenant_hash(s) != 0

    def test_feature_fold_is_f32_exact(self):
        import numpy as np
        for s in ("alice", "bob", "t-999"):
            f = tenant_feature(tenant_hash(s))
            assert f == float(np.float32(f))
            assert 0 <= f < 2 ** 24

    @native_only
    def test_native_parity_bit_identical(self):
        ids = ["alice", "bob", "tenant-123", "UPPER", "with space",
               "ümlaut", "日本語", "x" * 200] + [f"t-{i}" for i in range(64)]
        for s in ids:
            assert tenant_hash(s) == native.tenant_hash_native(
                s.encode("utf-8")), s


class TestTenantIdentifierSpec:
    def test_header_extraction_http_and_h2(self):
        from linkerd_tpu.protocol.h2.messages import H2Request, Headers
        from linkerd_tpu.protocol.http.message import Request
        spec = TenantIdentifierSpec(kind="header", header="l5d-tenant")
        req = Request(uri="/x")
        req.headers.set("l5d-tenant", "alice")
        assert spec.extract(req) == "alice"
        h2req = H2Request(path="/x",
                          headers=Headers([("l5d-tenant", "bob")]))
        assert spec.extract(h2req) == "bob"

    def test_path_segment_extraction(self):
        from linkerd_tpu.protocol.http.message import Request
        spec = TenantIdentifierSpec(kind="pathSegment", segment=0)
        assert spec.extract(Request(uri="/acme/api/v1?q=1")) == "acme"
        assert spec.extract(Request(uri="/")) is None
        spec2 = TenantIdentifierSpec(kind="pathSegment", segment=1)
        assert spec2.extract(Request(uri="/acme/api")) == "api"

    def test_validation(self):
        with pytest.raises(ValueError):
            TenantIdentifierSpec(kind="nope").validate()
        with pytest.raises(ValueError):
            TenantIdentifierSpec(kind="header", header="").validate()
        with pytest.raises(ValueError):
            TenantIdentifierSpec(kind="pathSegment",
                                 segment=-1).validate()


# ---------------------------------------------------------------- board


class TestTenantBoard:
    def test_error_ewma_drives_level(self):
        b = TenantBoard(alpha=0.3)
        for _ in range(20):
            b.observe("bad", error=True, now=1.0)
            b.observe("good", error=False, now=1.0)
        assert b.level("bad") > 0.9
        assert b.level("good") == 0.0
        assert b.level("unknown") == 0.0

    def test_score_ewma_feeds_level(self):
        b = TenantBoard()
        b.ingest_native(0x1234, requests=100, errors=0, sheds=0,
                        score_ewma=0.8, scored=100, now=1.0)
        assert b.level("#00001234") == pytest.approx(0.8)

    def test_dominance_flags_retry_storm_shape(self):
        b = TenantBoard(window_s=1.0, fair_share_burst=2.0)
        # window 1: attacker sends 97%, victim 3%
        for _ in range(970):
            b.observe("atk", error=False, now=0.5)
        for _ in range(30):
            b.observe("vic", error=False, now=0.5)
        # rotate the window, then observe once more to land in window 2
        b.observe("atk", error=False, now=2.0)
        b.observe("vic", error=False, now=2.0)
        assert b.level("atk") > 0.0
        assert b.level("vic") == 0.0

    def test_lru_bound_under_id_churn(self):
        b = TenantBoard(max_tenants=64)
        for i in range(10_000):
            b.observe(f"churn-{i}", error=False, now=float(i))
        assert len(b.active_tenants()) <= 64
        assert b.evicted > 0

    def test_snapshot_shape(self):
        b = TenantBoard()
        b.observe("t1", error=True, now=1.0)
        b.observe_shed("t1", now=1.0)
        snap = b.snapshot()
        assert snap["t1"]["requests"] == 1
        assert snap["t1"]["sheds"] == 1
        assert snap["t1"]["errors"] == 1
        assert snap["t1"]["hash"] == tenant_hash("t1")


# ------------------------------------------------------------- governor


class _StubEngineQuotas:
    def __init__(self):
        self.quotas = {}

    def set_tenant_quota(self, thash, limit):
        if limit is None:
            self.quotas.pop(thash, None)
        else:
            self.quotas[thash] = limit


class TestTenantAdmission:
    def _mk(self, floor=0.125, quorum=3, dwell=1.0):
        board = TenantBoard()
        ta = TenantAdmission(
            board,
            governor=HysteresisGovernor(enter=0.6, exit=0.2,
                                        quorum=quorum, dwell_s=dwell),
            floor=floor, engine_base=64)
        return board, ta

    def test_quota_shrinks_then_recovers(self):
        board, ta = self._mk(dwell=0.0)
        filt = AdmissionControlFilter(32)
        eng = _StubEngineQuotas()
        ta.register(filt)
        ta.register_engine(eng)
        th = tenant_hash("atk")
        now = 100.0
        # sustained high level -> SICK after quorum steps
        for i in range(5):
            for _ in range(3):
                board.observe("atk", error=True, now=now)
            ta.step(now)
            now += 1.0
        assert filt.tenant_limit_of(th) == max(1, round(0.125 * 32))
        assert eng.quotas[th] == max(1, round(0.125 * 64))
        assert ta.transitions == 1
        # recovery: healthy traffic drains the EWMA, quota clears
        for i in range(60):
            board.observe("atk", error=False, now=now)
            ta.step(now)
            now += 1.0
        assert filt.tenant_limit_of(th) is None
        assert th not in eng.quotas
        assert ta.transitions == 2

    def test_no_flapping_on_oscillating_level(self):
        """A level oscillating between the enter and exit thresholds
        must cause at most the initial transition — the split
        thresholds + quorum + dwell absorb it."""
        board, ta = self._mk(quorum=3, dwell=5.0)
        filt = AdmissionControlFilter(32)
        ta.register(filt)
        now = 0.0
        # drive to SICK
        for _ in range(10):
            for _ in range(4):
                board.observe("osc", error=True, now=now)
            ta.step(now)
            now += 1.0
        assert ta.transitions == 1
        # now oscillate: bursts of successes and errors that keep the
        # EWMA wandering between exit (0.2) and enter (0.6)
        import itertools
        flip = itertools.cycle([True, False])
        for _ in range(100):
            board.observe("osc", error=next(flip), now=now)
            ta.step(now)
            now += 0.05
        assert ta.transitions == 1, "quota flapped"

    def test_governor_keys_bounded_under_id_churn(self):
        """The governor forgets tenants the board's LRU evicted (sick
        ones excepted) — hostile id churn must not grow its key store
        past the board bound."""
        board = TenantBoard(max_tenants=16)
        ta = TenantAdmission(
            board,
            governor=HysteresisGovernor(enter=0.6, exit=0.2, quorum=2,
                                        dwell_s=0.0),
            floor=0.125, engine_base=64)
        now = 0.0
        for i in range(2000):
            board.observe(f"churn-{i}", error=False, now=now)
            if i % 10 == 0:
                ta.step(now)
            now += 0.01
        ta.step(now)
        assert len(ta.governor.keys()) <= 16

    def test_untracked_tenants_untouched(self):
        board, ta = self._mk(dwell=0.0)
        filt = AdmissionControlFilter(32)
        ta.register(filt)
        now = 0.0
        for _ in range(5):
            for _ in range(3):
                board.observe("atk", error=True, now=now)
            board.observe("vic", error=False, now=now)
            ta.step(now)
            now += 1.0
        assert filt.tenant_limit_of(tenant_hash("atk")) is not None
        assert filt.tenant_limit_of(tenant_hash("vic")) is None


# ------------------------------------------- per-tenant admission limits


class TestAdmissionTenantLimits:
    def test_tenant_sublimit_sheds_without_touching_others(self):
        async def go():
            gate = asyncio.Event()

            async def slow(req):
                await gate.wait()
                return "ok"

            filt = AdmissionControlFilter(16)
            filt.set_tenant_limit(tenant_hash("atk"), 1)
            svc = FnService(slow)

            class Req:
                def __init__(self, tenant):
                    self.ctx = {"tenant_hash": tenant_hash(tenant)}

            t1 = asyncio.ensure_future(filt.apply(Req("atk"), svc))
            await asyncio.sleep(0.01)
            # second attacker request: over the sub-limit -> shed
            with pytest.raises(OverloadShed):
                await filt.apply(Req("atk"), svc)
            # the victim is untouched (global limit 16 has room)
            t2 = asyncio.ensure_future(filt.apply(Req("vic"), svc))
            await asyncio.sleep(0.01)
            gate.set()
            assert await t1 == "ok"
            assert await t2 == "ok"
            # slot released: attacker admits again
            assert await filt.apply(Req("atk"), svc) == "ok"

        run(go())

    def test_queued_same_tenant_counts_toward_sublimit(self):
        """The tenant slot is taken before the global queue wait, so
        a tenant cannot exceed its sub-limit via queued arrivals."""
        async def go():
            gate = asyncio.Event()

            async def slow(req):
                await gate.wait()
                return "ok"

            # global limit 1 + queue: the second atk request queues
            # globally but already holds a tenant slot
            filt = AdmissionControlFilter(1, max_pending=4)
            filt.set_tenant_limit(tenant_hash("atk"), 2)
            svc = FnService(slow)

            class Req:
                def __init__(self):
                    self.ctx = {"tenant_hash": tenant_hash("atk")}

            t1 = asyncio.ensure_future(filt.apply(Req(), svc))
            await asyncio.sleep(0.01)
            t2 = asyncio.ensure_future(filt.apply(Req(), svc))
            await asyncio.sleep(0.01)
            with pytest.raises(OverloadShed):
                await filt.apply(Req(), svc)
            gate.set()
            assert await t1 == "ok"
            assert await t2 == "ok"

        run(go())


# -------------------------------------------------- retry-safety of sheds


class TestShedRetrySafety:
    def test_http_tenant_shed_is_retryable_503(self, tmp_path):
        """Through a real linker: a tenant at its sub-limit gets 503 +
        l5d-retryable (the same contract as the global gate)."""
        from linkerd_tpu.linker import load_linker
        from linkerd_tpu.protocol.http import Request
        from linkerd_tpu.protocol.http.client import HttpClient
        from linkerd_tpu.protocol.http.server import serve

        gate = asyncio.Event()

        async def waiting(req):
            await gate.wait()
            from linkerd_tpu.protocol.http import Response
            return Response(200, body=b"ok")

        async def go():
            backend = await serve(FnService(waiting))
            disco = tmp_path / "disco"
            disco.mkdir()
            (disco / "web").write_text(
                f"127.0.0.1 {backend.bound_port}\n")
            linker = load_linker(f"""
routers:
- protocol: http
  label: tshed
  admissionControl: {{maxConcurrency: 8, maxPending: 0}}
  tenantIdentifier: {{kind: header, header: l5d-tenant}}
  tenants: {{floor: 0.125}}
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers: [{{port: 0}}]
namers:
- kind: io.l5d.fs
  rootDir: {disco}
""")
            await linker.start()
            port = linker.routers[0].server_ports[0]
            # install the sub-limit directly (the governor path is
            # covered elsewhere; here we pin the SHED SIGNAL)
            _, board, adm = linker.tenant_views[0]
            admission = adm._filters[0]
            admission.set_tenant_limit(tenant_hash("atk"), 1)
            c1, c2 = (HttpClient("127.0.0.1", port) for _ in range(2))
            try:
                req1 = Request(uri="/1")
                req1.headers.set("Host", "web")
                req1.headers.set("l5d-tenant", "atk")
                t1 = asyncio.ensure_future(c1(req1))
                await asyncio.sleep(0.05)
                req2 = Request(uri="/2")
                req2.headers.set("Host", "web")
                req2.headers.set("l5d-tenant", "atk")
                rsp = await c2(req2)
                assert rsp.status == 503
                assert rsp.headers.get("l5d-retryable") == "true"
                gate.set()
                assert (await t1).status == 200
                flat = linker.metrics.flatten()
                assert flat["rt/tshed/server/admission/"
                            "tenant_shed_total"] >= 1
            finally:
                await c1.close()
                await c2.close()
                await linker.close()
                await backend.close()

        run(go())

    def test_h2_refused_is_retryable_in_classifiers(self):
        """REFUSED_STREAM (the h2 tenant-shed signal, native and
        Python) reads as retryable in every h2 status classifier —
        even the nonRetryable5XX one: RFC 7540 §8.1.4 blesses the
        retry because the stream was never processed."""
        from linkerd_tpu.protocol.h2.classifiers import (
            H2NonRetryable5XX, H2RetryableIdempotent5XX,
            H2RetryableRead5XX,
        )
        from linkerd_tpu.protocol.h2.messages import H2Request
        from linkerd_tpu.protocol.h2.stream import (
            RST_REFUSED_STREAM, StreamReset,
        )
        from linkerd_tpu.router.classifiers import ResponseClass
        refused = StreamReset(error_code=RST_REFUSED_STREAM)
        req = H2Request(method="POST", path="/")
        for cfg in (H2NonRetryable5XX(), H2RetryableRead5XX(),
                    H2RetryableIdempotent5XX()):
            rc = cfg.mk().classify(req, None, None, refused)
            assert rc is ResponseClass.RETRYABLE_FAILURE, cfg


# --------------------------------------------------- native: extraction


@native_only
class TestNativeTenantExtraction:
    async def _h1_get(self, port, host, uri="/", headers=()):
        r, w = await asyncio.open_connection("127.0.0.1", port)
        try:
            head = f"GET {uri} HTTP/1.1\r\nHost: {host}\r\n"
            for k, v in headers:
                head += f"{k}: {v}\r\n"
            w.write(head.encode() + b"\r\n")
            await w.drain()
            line = await asyncio.wait_for(r.readline(), 10)
            status = int(line.split()[1])
            hdrs = {}
            while True:
                ln = await r.readline()
                if ln in (b"\r\n", b""):
                    break
                k, _, v = ln.decode().partition(":")
                hdrs[k.strip().lower()] = v.strip()
            n = int(hdrs.get("content-length", 0))
            if n:
                await r.readexactly(n)
            return status, hdrs
        finally:
            w.close()

    def test_header_extraction_parity_and_feature_row(self):
        async def go():
            srv = await EchoBackend().start()
            bport = srv.bound_port
            eng = native.FastPathEngine()
            eng.set_tenant("header", "l5d-tenant")
            port = eng.listen("127.0.0.1", 0)
            eng.start()
            eng.set_route("svc", [("127.0.0.1", bport)])
            try:
                for tid in ("alice", "bob", "T-42"):
                    st, _ = await self._h1_get(
                        port, "svc", headers=[("l5d-tenant", tid)])
                    assert st == 200
                await asyncio.sleep(0.05)
                rows = eng.drain_features()
                assert rows.shape[1] == 12
                got = set(float(x) for x in rows[:, 8])
                want = {tenant_feature(tenant_hash(t))
                        for t in ("alice", "bob", "T-42")}
                assert got == want
                by = eng.stats()["tenants"]["by_tenant"]
                assert set(int(k) for k in by) == {
                    tenant_hash(t) for t in ("alice", "bob", "T-42")}
            finally:
                eng.close()
                await srv.close()

        run(go())

    def test_path_segment_extraction_parity(self):
        async def go():
            srv = await EchoBackend().start()
            bport = srv.bound_port
            eng = native.FastPathEngine()
            eng.set_tenant("pathSegment", segment=0)
            port = eng.listen("127.0.0.1", 0)
            eng.start()
            eng.set_route("svc", [("127.0.0.1", bport)])
            try:
                st, _ = await self._h1_get(port, "svc",
                                           uri="/acme/api?q=1")
                assert st == 200
                await asyncio.sleep(0.05)
                rows = eng.drain_features()
                spec = TenantIdentifierSpec(kind="pathSegment",
                                            segment=0)
                from linkerd_tpu.protocol.http.message import Request
                pyside = spec.extract(Request(uri="/acme/api?q=1"))
                assert pyside == "acme"
                assert float(rows[0, 8]) == tenant_feature(
                    tenant_hash(pyside))
            finally:
                eng.close()
                await srv.close()

        run(go())

    def test_native_lru_bound_under_id_churn(self):
        async def go():
            srv = await EchoBackend().start()
            bport = srv.bound_port
            eng = native.FastPathEngine()
            eng.set_tenant("header", "l5d-tenant")
            eng.set_guard(tenant_cap=16)
            port = eng.listen("127.0.0.1", 0)
            eng.start()
            eng.set_route("svc", [("127.0.0.1", bport)])
            try:
                r, w = await asyncio.open_connection("127.0.0.1", port)
                for i in range(200):
                    w.write(f"GET / HTTP/1.1\r\nHost: svc\r\n"
                            f"l5d-tenant: churn-{i}\r\n\r\n".encode())
                    await w.drain()
                    line = await asyncio.wait_for(r.readline(), 10)
                    assert int(line.split()[1]) == 200
                    while True:
                        ln = await r.readline()
                        if ln == b"\r\n":
                            break
                    await r.readexactly(2)
                w.close()
                tn = eng.stats()["tenants"]
                assert tn["count"] <= 16
                assert tn["evicted"] >= 200 - 16 - 16  # amortized sweeps
            finally:
                eng.close()
                await srv.close()

        run(go())

    def test_native_quota_shed_is_retryable_503(self):
        async def go():
            srv = await EchoBackend().start()
            bport = srv.bound_port
            eng = native.FastPathEngine()
            eng.set_tenant("header", "l5d-tenant")
            port = eng.listen("127.0.0.1", 0)
            eng.start()
            eng.set_route("svc", [("127.0.0.1", bport)])
            try:
                eng.set_tenant_quota(tenant_hash("atk"), 0)
                st, hdrs = await self._h1_get(
                    port, "svc", headers=[("l5d-tenant", "atk")])
                assert st == 503
                assert hdrs.get("l5d-retryable") == "true"
                # the victim rides through untouched
                st, _ = await self._h1_get(
                    port, "svc", headers=[("l5d-tenant", "vic")])
                assert st == 200
                eng.set_tenant_quota(tenant_hash("atk"), None)
                st, _ = await self._h1_get(
                    port, "svc", headers=[("l5d-tenant", "atk")])
                assert st == 200
                assert eng.stats()["guard"]["tenant_shed"] == 1
            finally:
                eng.close()
                await srv.close()

        run(go())

    def test_no_route_responses_release_the_tenant_slot(self):
        """Regression: synthesized error responses (no-route 400) end
        the request without finish_exchange — the per-tenant inflight
        slot must still be released, or a quota'd tenant whose
        requests miss routes accrues phantom inflight and is shed
        forever (and its pinned table entry defeats LRU eviction)."""
        async def go():
            srv = await EchoBackend().start()
            bport = srv.bound_port
            eng = native.FastPathEngine()
            eng.set_tenant("header", "l5d-tenant")
            port = eng.listen("127.0.0.1", 0)
            eng.start()
            eng.set_route("svc", [("127.0.0.1", bport)])
            eng.set_route("dead", [])  # installed, zero endpoints: 400
            try:
                eng.set_tenant_quota(tenant_hash("t"), 2)
                r, w = await asyncio.open_connection("127.0.0.1", port)
                # 5 keep-alive requests that all 400 (no endpoints) —
                # each would leak one inflight slot pre-fix
                for _ in range(5):
                    w.write(b"GET / HTTP/1.1\r\nHost: dead\r\n"
                            b"l5d-tenant: t\r\n\r\n")
                    await w.drain()
                    line = await asyncio.wait_for(r.readline(), 10)
                    assert int(line.split()[1]) == 400
                    clen = 0
                    while True:
                        ln = await r.readline()
                        if ln in (b"\r\n", b""):
                            break
                        if ln.lower().startswith(b"content-length:"):
                            clen = int(ln.split(b":")[1])
                    if clen:
                        await r.readexactly(clen)
                w.close()
                # the tenant is idle now: a good request MUST pass
                st, _ = await self._h1_get(
                    port, "svc", headers=[("l5d-tenant", "t")])
                assert st == 200, "phantom inflight shed an idle tenant"
                by = eng.stats()["tenants"]["by_tenant"]
                assert by[str(tenant_hash("t"))]["inflight"] == 0
            finally:
                eng.close()
                await srv.close()

        run(go())

    def test_h2_native_quota_shed_is_refused_stream(self):
        from linkerd_tpu.protocol.h2.client import H2Client
        from linkerd_tpu.protocol.h2.messages import (
            H2Request, H2Response, Headers,
        )
        from linkerd_tpu.protocol.h2.server import H2Server
        from linkerd_tpu.protocol.h2.stream import StreamReset

        async def go():
            async def handler(req):
                return H2Response(status=200, body=b"ok")

            backend = await H2Server(FnService(handler)).start()
            eng = native.H2FastPathEngine()
            eng.set_tenant("header", "l5d-tenant")
            port = eng.listen("127.0.0.1", 0)
            eng.start()
            eng.set_route("echo",
                          [("127.0.0.1", backend.bound_port)])
            h2c = H2Client("127.0.0.1", port)
            try:
                eng.set_tenant_quota(tenant_hash("atk"), 0)

                async def get(tenant):
                    req = H2Request(
                        method="GET", path="/", authority="echo",
                        headers=Headers([("l5d-tenant", tenant)]))
                    rsp = await h2c(req)
                    await rsp.stream.read_all()
                    return rsp.status

                with pytest.raises(StreamReset) as ei:
                    await get("atk")
                assert ei.value.error_code == 0x7  # REFUSED_STREAM
                assert await get("vic") == 200
                eng.set_tenant_quota(tenant_hash("atk"), None)
                assert await get("atk") == 200
            finally:
                await h2c.close()
                eng.close()
                await backend.close()

        run(go())


# ---------------------------------------------- native: conn-plane guard


@native_only
class TestNativeConnectionGuard:
    def test_h1_slowloris_closed_within_budget(self):
        async def go():
            eng = native.FastPathEngine()
            eng.set_guard(header_budget_ms=600)
            port = eng.listen("127.0.0.1", 0)
            eng.start()
            try:
                loris = SlowlorisAttack(port, conns=8,
                                        drip_s=10.0).start()
                t0 = asyncio.get_event_loop().time()
                while (eng.stats()["guard"]["slowloris_closed"] < 8
                       and asyncio.get_event_loop().time() - t0 < 10):
                    await asyncio.sleep(0.2)
                await loris.stop()
                assert eng.stats()["guard"]["slowloris_closed"] >= 8
            finally:
                eng.close()

        run(go())

    def test_h1_body_stall_closed(self):
        async def go():
            async def handle(reader, writer):
                with contextlib.suppress(Exception):
                    await reader.readuntil(b"\r\n\r\n")
                await asyncio.sleep(30)

            srv = await LoopbackServer(handle).start()
            bport = srv.bound_port
            eng = native.FastPathEngine()
            eng.set_guard(header_budget_ms=30_000, body_stall_ms=600)
            port = eng.listen("127.0.0.1", 0)
            eng.start()
            eng.set_route("svc", [("127.0.0.1", bport)])
            try:
                r, w = await asyncio.open_connection("127.0.0.1", port)
                # declared 1000-byte body, send 3 bytes, stall
                w.write(b"POST / HTTP/1.1\r\nHost: svc\r\n"
                        b"Content-Length: 1000\r\n\r\nabc")
                await w.drain()
                data = await asyncio.wait_for(r.read(4096), 15)
                assert data == b""  # closed, no response
                assert eng.stats()["guard"]["body_stall_closed"] >= 1
                w.close()
            finally:
                eng.close()
                await srv.close()

        run(go())

    def test_accept_throttle_engages_under_churn(self):
        async def go():
            eng = native.FastPathEngine()
            eng.set_guard(accept_burst=20, accept_window_ms=1000)
            port = eng.listen("127.0.0.1", 0)
            eng.start()
            try:
                churn = ConnectionChurnAttack(
                    port, rate_per_s=2000, workers=8).start()
                t0 = asyncio.get_event_loop().time()
                while (eng.stats()["guard"]["accept_throttled"] == 0
                       and asyncio.get_event_loop().time() - t0 < 10):
                    await asyncio.sleep(0.1)
                await churn.stop()
                assert eng.stats()["guard"]["accept_throttled"] > 0
            finally:
                eng.close()

        run(go())

    def test_h2_rapid_reset_cap(self):
        from linkerd_tpu.protocol.h2.hpack import Encoder

        async def go():
            eng = native.H2FastPathEngine()
            eng.set_flood_guard(rst_burst=20, window_ms=5000)
            port = eng.listen("127.0.0.1", 0)
            eng.start()
            try:
                enc = Encoder()
                r, w = await asyncio.open_connection("127.0.0.1", port)
                with contextlib.suppress(ConnectionError):
                    w.write(b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n")
                    w.write(b"\x00\x00\x00\x04\x00" + b"\x00" * 4)
                    for i in range(40):
                        sid = 1 + 2 * i
                        block = enc.encode(
                            [(":method", "GET"), (":scheme", "http"),
                             (":path", "/"), (":authority", "boom")])
                        ln = len(block)
                        w.write(bytes([(ln >> 16) & 0xFF,
                                       (ln >> 8) & 0xFF, ln & 0xFF,
                                       0x01, 0x05])
                                + sid.to_bytes(4, "big") + block)
                        w.write(b"\x00\x00\x04\x03\x00"
                                + sid.to_bytes(4, "big")
                                + (8).to_bytes(4, "big"))
                        await w.drain()
                with contextlib.suppress(ConnectionError,
                                         asyncio.TimeoutError):
                    while await asyncio.wait_for(r.read(65536), 5):
                        pass
                w.close()
                assert eng.stats()["guard"]["rapid_reset_closed"] >= 1
            finally:
                eng.close()

        run(go())

    def test_h2_preface_stall_closed(self):
        async def go():
            eng = native.H2FastPathEngine()
            eng.set_guard(header_budget_ms=600)
            port = eng.listen("127.0.0.1", 0)
            eng.start()
            try:
                r, w = await asyncio.open_connection("127.0.0.1", port)
                w.write(b"PRI * HTTP/2.0\r\n")  # half a preface
                await w.drain()
                data = b"x"
                with contextlib.suppress(ConnectionError):
                    while data:
                        data = await asyncio.wait_for(r.read(65536), 10)
                assert eng.stats()["guard"]["slowloris_closed"] >= 1
                w.close()
            finally:
                eng.close()

        run(go())


# ------------------------------------------------- fastpath control loop


@native_only
class TestFastpathTenantControlPlane:
    def test_stats_loop_feeds_board_and_pushes_quota(self):
        """The FastPathController's stats tick folds engine per-tenant
        deltas into the TenantBoard and steps the governor — a tenant
        whose engine-side error rate spikes gets its quota pushed INTO
        the engine within a few ticks."""

        class StubEngine:
            def __init__(self):
                self.quotas = {}
                self.tenants = {}

            def stats(self):
                return {"routes": {}, "tenants": {
                    "count": len(self.tenants), "evicted": 0,
                    "by_tenant": dict(self.tenants)}, "guard": {}}

            def set_tenant_quota(self, thash, limit):
                if limit is None:
                    self.quotas.pop(thash, None)
                else:
                    self.quotas[thash] = limit

        from linkerd_tpu.router.fastpath import FastPathController
        from linkerd_tpu.telemetry.metrics import MetricsTree

        async def go():
            eng = StubEngine()
            board = TenantBoard()
            ta = TenantAdmission(
                board,
                governor=HysteresisGovernor(enter=0.6, exit=0.2,
                                            quorum=2, dwell_s=0.0),
                floor=0.125, engine_base=64)
            ta.register_engine(eng)
            ctl = FastPathController.__new__(FastPathController)
            ctl.engine = eng
            ctl._scope = MetricsTree().scope("rt", "t", "fastpath")
            ctl.tenant_board = board
            ctl.tenant_admission = ta
            ctl._last_tenants = {}
            ctl._last_guard = {}
            ctl._tenant_metric_keys = set()
            ctl._tenant_metric_cap = 256
            th = tenant_hash("atk")
            reqs = 0
            # the per-tick error-rate EWMA (alpha 0.1) needs ~10 all-
            # error ticks to cross enter=0.6, plus the quorum
            for tick in range(16):
                reqs += 50
                eng.tenants[str(th)] = {
                    "requests": reqs, "shed": 0, "errors": reqs,
                    "scored": 0, "score_ewma": 0.0, "inflight": 0,
                    "quota": -1}
                ctl._export_tenants(eng.stats())
            assert eng.quotas.get(th) == max(1, round(0.125 * 64))

        run(go())


# ----------------------------------------------------- the chaos matrix


def _p99(xs):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(0.99 * len(xs)))] if xs else 0.0


class TestChaosMatrixPythonPath:
    def test_retry_storm_tenant_degrades_alone(self, tmp_path):
        """The full e2e on the Python data plane: an attacker tenant
        retry-storms a failing route; its error EWMA trips the quota
        governor; its floor quota sheds the storm retryably; the
        victim tenant's success rate and p99 hold. Zero quota flaps."""
        from linkerd_tpu.linker import load_linker
        from linkerd_tpu.protocol.http import Response
        from linkerd_tpu.protocol.http.server import serve

        async def ok_handler(req):
            await asyncio.sleep(0.002)
            return Response(200, body=b"ok")

        async def boom_handler(req):
            return Response(500, body=b"boom")

        async def go():
            ok_srv = await serve(FnService(ok_handler))
            boom_srv = await serve(FnService(boom_handler))
            disco = tmp_path / "disco"
            disco.mkdir()
            (disco / "good").write_text(
                f"127.0.0.1 {ok_srv.bound_port}\n")
            (disco / "boom").write_text(
                f"127.0.0.1 {boom_srv.bound_port}\n")
            linker = load_linker(f"""
routers:
- protocol: http
  label: chaos
  admissionControl: {{maxConcurrency: 8, maxPending: 8}}
  tenantIdentifier: {{kind: header, header: l5d-tenant}}
  tenants:
    floor: 0.125
    enterThreshold: 0.5
    exitThreshold: 0.2
    quorum: 3
    cooldownS: 0.2
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers: [{{port: 0}}]
namers:
- kind: io.l5d.fs
  rootDir: {disco}
""")
            await linker.start()
            port = linker.routers[0].server_ports[0]
            try:
                # -- baseline: victim alone
                vic0 = PacedTenantClient(port, "good", "victim",
                                         rate_per_s=100)
                await vic0.run(80)
                assert vic0.success_rate == 1.0
                base_p99 = vic0.p99_ms()

                # -- attack: retry storm against the failing route.
                # A light victim trickle runs through the detection
                # window (its errors-before-quota are the governor's
                # cost, not the isolation bound's).
                storm = TenantRetryStorm(port, "boom", "attacker",
                                         concurrency=8,
                                         retry_delay_s=0.005).start()
                warm = PacedTenantClient(port, "good", "victim",
                                         rate_per_s=50)
                warm_task = asyncio.ensure_future(warm.run(500))
                # wait for the governor to trip the attacker
                _, board, adm = linker.tenant_views[0]
                t0 = asyncio.get_event_loop().time()
                while (not adm.status()["sick"]
                       and asyncio.get_event_loop().time() - t0 < 15):
                    await asyncio.sleep(0.05)
                assert adm.status()["sick"] == ["attacker"], \
                    adm.status()
                warm_task.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await warm_task
                # steady state under quota ("while the attacker is
                # shed"): the victim's bound and the attacker's shed
                # fraction are measured HERE
                ok0, shed0 = storm.ok, storm.shed
                vic = PacedTenantClient(port, "good", "victim",
                                        rate_per_s=100)
                await vic.run(200)
                ok1, shed1 = storm.ok, storm.shed
                await storm.stop()

                # the victim held. The p99 bound is 2x its no-attack
                # baseline, widened by a fixed 50 ms jitter allowance:
                # everything here — router, both downstreams, attacker
                # AND victim — shares one event loop, so tens of ms of
                # scheduling jitter is harness noise, not mesh queueing
                # (pre-quota collapse is hundreds of ms of queue waits
                # + sheds). For real (>50 ms) latencies the bound
                # degenerates to the plain 2x criterion.
                assert vic.success_rate >= 0.99, vic.success_rate
                bound = max(2 * base_p99, base_p99 + 50.0)
                assert vic.p99_ms() <= bound, (vic.p99_ms(), base_p99)
                # the attacker was shed at rate
                post = (ok1 - ok0) + (shed1 - shed0)
                assert post > 0
                assert (shed1 - shed0) / post >= 0.9, \
                    (shed1 - shed0, post)
                # zero flaps: exactly one transition (to SICK)
                assert adm.transitions == 1
                # admin surface agrees
                snap = board.snapshot()
                assert snap["attacker"]["level"] > 0.5
                assert snap["victim"]["level"] < 0.2
            finally:
                await linker.close()
                await ok_srv.close()
                await boom_srv.close()

        run(go())


@native_only
class TestChaosMatrixNative:
    @pytest.mark.parametrize("workers", [1, 2])
    def test_isolation_holds_during_weight_hot_swap(self, workers):
        """Native leg: attacker quota-shed in the ENGINE while weight
        blobs hot-swap concurrently — the victim's success rate and
        the engine's scoring pipeline both hold. Runs at workers=1
        (today's single engine) AND workers=2 (the SO_REUSEPORT shard
        group: per-core tenant tables, the N-way quota split, and the
        shared weight slab must not break the isolation loop)."""

        async def go():
            srv = await EchoBackend().start()
            bport = srv.bound_port
            eng = native.FastPathEngine(workers=workers)
            eng.set_tenant("header", "l5d-tenant")
            port = eng.listen("127.0.0.1", 0)
            eng.start()
            eng.set_route("svc", [("127.0.0.1", bport)])
            eng.set_route_feature("svc", 14, 1.0)
            # workers=2 splits this floor-division: 1 // 2 = 0 per
            # worker — the attacker is shed entirely, the victim
            # (quota-less) must still sail through on every core
            eng.set_tenant_quota(tenant_hash("attacker"), 1)

            swaps = 0
            stop = asyncio.Event()

            async def swapper():
                nonlocal swaps
                v = 1
                while not stop.is_set():
                    blob = native.score_test_blob(version=v,
                                                  quant="f32", seed=v)
                    eng.publish_weights(blob)
                    swaps += 1
                    v += 1
                    await asyncio.sleep(0.01)

            try:
                storm = TenantRetryStorm(port, "svc", "attacker",
                                         concurrency=8).start()
                swap_task = asyncio.ensure_future(swapper())
                vic = PacedTenantClient(port, "svc", "victim",
                                        rate_per_s=100)
                await vic.run(200)
                stop.set()
                await swap_task
                await storm.stop()
                assert vic.success_rate >= 0.99, vic.success_rate
                assert storm.shed_fraction >= 0.5, storm.shed_fraction
                assert swaps > 10
                st = eng.stats()
                assert st["guard"]["tenant_shed"] > 0
                # the scoring pipeline kept running through the swaps
                assert st["native_scorer"]["scored"] > 0
            finally:
                eng.close()
                await srv.close()

        run(go())
