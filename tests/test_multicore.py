"""Multi-core native data plane: SO_REUSEPORT-sharded engines.

Proves the sharding contract end to end:

- N worker engines share the router's ports and the kernel's
  per-connection spread reaches every worker;
- per-core stats slabs merge at scrape time (merged == sum of
  per-worker, histograms added element-wise, route ids in lockstep);
- ONE publish into the shared read-only weight slab fans out to every
  worker atomically (each worker's ``native_scorer`` block reports the
  same version; rows retired on every core come back pre-scored);
- per-tenant quotas split N ways (floor division: the global cap is
  never exceeded — and a limit below N sheds the tenant entirely,
  which l5dcheck's ``fastpath-workers`` rule warns about);
- ``workers=1`` keeps today's exact behavior (legacy bind, embedded
  slab, unmerged stats shape);
- the Python data plane's SNI half of ``tenantIdentifier: sni``
  (PR satellite): the asyncio TLS servers stamp ``req.ctx["sni"]``,
  and the extracted tenant hashes bit-identically to the engines'.
"""

import asyncio
import os
import subprocess

import numpy as np
import pytest

from linkerd_tpu.testing.faults import EchoBackend

native = pytest.importorskip("linkerd_tpu.native")

pytestmark = pytest.mark.skipif(
    not native.ensure_built(), reason="native toolchain unavailable")


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 120))


@pytest.fixture(scope="module")
def certs(tmp_path_factory):
    """Self-signed localhost cert (openssl CLI)."""
    d = tmp_path_factory.mktemp("mc-tls")
    cert, key = str(d / "cert.pem"), str(d / "key.pem")
    try:
        subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048",
             "-keyout", key, "-out", cert, "-days", "2", "-nodes",
             "-subj", "/CN=localhost"],
            check=True, capture_output=True, timeout=60)
    except (OSError, subprocess.SubprocessError):
        pytest.skip("openssl CLI unavailable")
    return cert, key


async def _one_shot(port: int, host: str = "svc",
                    headers: str = "") -> bytes:
    """One request on a FRESH connection (a fresh 4-tuple, so the
    kernel's REUSEPORT hash keeps spreading across workers)."""
    r, w = await asyncio.open_connection("127.0.0.1", port)
    w.write(f"GET / HTTP/1.1\r\nHost: {host}\r\n{headers}"
            f"Connection: close\r\n\r\n".encode())
    await w.drain()
    data = await r.read(65536)
    w.close()
    try:
        await w.wait_closed()
    except Exception:  # noqa: BLE001
        pass
    return data


class TestShardedEngine:
    def test_both_workers_serve_and_merged_equals_sum(self):
        async def go():
            srv = await EchoBackend().start()
            bport = srv.bound_port
            eng = native.FastPathEngine(workers=2)
            try:
                port = eng.listen("127.0.0.1", 0)
                eng.start()
                eng.set_route("svc", [("127.0.0.1", bport)])
                n = 80
                ok = 0
                for _ in range(n):
                    if b"200 OK" in await _one_shot(port):
                        ok += 1
                assert ok == n
                st = eng.stats()
                per = [s.get("routes", {}).get("svc", {})
                       for s in st["workers"]]
                reqs = [int(p.get("requests", 0)) for p in per]
                # the kernel spread must reach BOTH workers (80 fresh
                # 4-tuples: all-on-one-worker is ~2^-80)
                assert all(r > 0 for r in reqs), reqs
                assert st["routes"]["svc"]["requests"] == sum(reqs) == n
                # histograms merge element-wise
                assert sum(st["routes"]["svc"]["hist"]) == n
                # accepted merges too
                assert st["accepted"] == sum(
                    int(s.get("accepted", 0)) for s in st["workers"])
            finally:
                eng.close()
                await srv.close()

        run(go())

    def test_route_ids_lockstep_across_workers(self):
        eng = native.FastPathEngine(workers=3)
        try:
            for host in ("alpha", "beta", "gamma"):
                eng.set_route(host, [("127.0.0.1", 9)])
            eng.remove_route("beta")
            eng.set_route("beta", [("127.0.0.1", 9)])  # re-add: new id
            st = eng.stats()
            for host in ("alpha", "beta", "gamma"):
                ids = {s["routes"][host]["id"] for s in st["workers"]}
                assert len(ids) == 1, (host, ids)
        finally:
            eng.close()

    def test_single_publish_fans_out_to_all_workers(self):
        async def go():
            srv = await EchoBackend().start()
            bport = srv.bound_port
            eng = native.FastPathEngine(workers=2)
            try:
                port = eng.listen("127.0.0.1", 0)
                eng.start()
                eng.set_route("svc", [("127.0.0.1", bport)])
                eng.set_route_feature("svc", 14, 1.0)
                # ONE publish into the shared slab
                eng.publish_weights(
                    native.score_test_blob(version=7, seed=3))
                n = 60
                for _ in range(n):
                    await _one_shot(port)
                await asyncio.sleep(0.1)
                rows = eng.drain_features()
                assert len(rows) == n
                # every row pre-scored, regardless of which core
                # retired it
                assert int((rows[:, 7] > 0.5).sum()) == n
                st = eng.stats()
                ns = [s["native_scorer"] for s in st["workers"]]
                assert all(x["version"] == 7 and x["weights"]
                           for x in ns), ns
                # both cores actually evaluated (scored > 0 each)
                assert all(int(x["scored"]) > 0 for x in ns), ns
                merged = st["native_scorer"]
                assert merged["scored"] == sum(
                    int(x["scored"]) for x in ns) == n
                # hot-swap: the next publish flips EVERY worker
                eng.publish_weights(
                    native.score_test_blob(version=8, seed=4))
                st = eng.stats()
                assert all(s["native_scorer"]["version"] == 8
                           for s in st["workers"])
            finally:
                eng.close()
                await srv.close()

        run(go())

    def test_quota_splits_and_zero_per_worker_sheds_all(self):
        async def go():
            from linkerd_tpu.router.tenancy import tenant_hash
            srv = await EchoBackend().start()
            bport = srv.bound_port
            eng = native.FastPathEngine(workers=2)
            eng.set_tenant("header", "l5d-tenant")
            try:
                port = eng.listen("127.0.0.1", 0)
                eng.start()
                eng.set_route("svc", [("127.0.0.1", bport)])
                # limit 4 across 2 workers -> 2 per worker
                eng.set_tenant_quota(tenant_hash("t-a"), 4)
                ok = 0
                for _ in range(10):
                    if b"200 OK" in await _one_shot(
                            port, headers="l5d-tenant: t-a\r\n"):
                        ok += 1
                assert ok == 10  # sequential: never over quota
                st = eng.stats()
                quotas = [
                    s["tenants"]["by_tenant"][
                        str(tenant_hash("t-a"))]["quota"]
                    for s in st["workers"]
                    if s["tenants"]["by_tenant"]]
                assert quotas and all(q == 2 for q in quotas), quotas
                # merged view reports the global cap (sum of splits)
                assert st["tenants"]["by_tenant"][
                    str(tenant_hash("t-a"))]["quota"] == 4
                # limit 1 across 2 workers -> 0 per worker: shed ALL
                # (the shape l5dcheck's fastpath-workers rule warns on)
                eng.set_tenant_quota(tenant_hash("t-b"), 1)
                shed = 0
                for _ in range(6):
                    if b"503" in await _one_shot(
                            port, headers="l5d-tenant: t-b\r\n"):
                        shed += 1
                assert shed == 6
            finally:
                eng.close()
                await srv.close()

        run(go())

    def test_workers1_keeps_single_engine_stats_shape(self):
        eng = native.FastPathEngine()  # default workers=1
        try:
            assert eng.workers == 1
            eng.set_route("svc", [("127.0.0.1", 9)])
            st = eng.stats()
            assert "workers" not in st  # unmerged legacy shape
            assert "svc" in st["routes"]
        finally:
            eng.close()

    def test_drain_features_into_fans_in_across_workers(self):
        async def go():
            srv = await EchoBackend().start()
            bport = srv.bound_port
            eng = native.FastPathEngine(workers=2)
            try:
                port = eng.listen("127.0.0.1", 0)
                eng.start()
                eng.set_route("svc", [("127.0.0.1", bport)])
                n = 40
                for _ in range(n):
                    await _one_shot(port)
                await asyncio.sleep(0.1)
                out = np.zeros((n, eng.FEATURE_DIM), np.float32)
                got = eng.drain_features_into(out)
                assert got == n
                # every row is a real feature row (status col == 200)
                assert np.all(out[:n, 2] == 200.0)
            finally:
                eng.close()
                await srv.close()

        run(go())

    def test_h2_shard_group_shares_slab(self):
        eng = native.H2FastPathEngine(workers=2)
        try:
            port = eng.listen("127.0.0.1", 0)
            assert port > 0
            eng.start()
            eng.publish_weights(native.score_test_blob(version=5, seed=1))
            st = eng.stats()
            assert len(st["workers"]) == 2
            assert all(s["native_scorer"]["version"] == 5
                       for s in st["workers"])
        finally:
            eng.close()

    def test_invalid_worker_counts_rejected(self):
        with pytest.raises(ValueError):
            native.FastPathEngine(workers=0)
        with pytest.raises(ValueError):
            native.FastPathEngine(workers=65)


class TestShardedLinker:
    def test_workers_requires_fastpath(self):
        from linkerd_tpu.config import ConfigError
        from linkerd_tpu.linker import load_linker
        with pytest.raises(ConfigError, match="workers"):
            load_linker("""
routers:
- protocol: http
  workers: 2
  servers: [{port: 0}]
""")

    def test_workers_out_of_range_rejected(self):
        from linkerd_tpu.config import ConfigError
        from linkerd_tpu.linker import load_linker
        with pytest.raises(ConfigError, match="workers"):
            load_linker("""
routers:
- protocol: http
  fastPath: true
  workers: 9999
  servers: [{port: 0}]
""")

    def test_sharded_router_serves_and_exports_per_worker(self, tmp_path):
        """Assembled (in-process) linker with ``workers: 2``: traffic
        reaches both workers, the controller exports
        rt/*/fastpath/worker/<i>/* breakdowns, and the merged route
        counter equals their sum."""
        async def go():
            from linkerd_tpu.linker import load_linker
            srv = await EchoBackend().start()
            bport = srv.bound_port
            disco = tmp_path / "disco"
            disco.mkdir()
            (disco / "web").write_text(f"127.0.0.1 {bport}\n")
            linker = load_linker(f"""
routers:
- protocol: http
  label: mc
  fastPath: true
  workers: 2
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers: [{{port: 0}}]
namers:
- kind: io.l5d.fs
  rootDir: {disco}
""")
            await linker.start()
            try:
                port = linker.routers[0].server_ports[0]
                assert linker.routers[0].controller.engine.workers == 2
                # first request parks on a miss; the controller
                # resolves + broadcasts the route
                for _ in range(3):
                    if b"200 OK" in await _one_shot(port, host="web"):
                        break
                    await asyncio.sleep(0.3)
                n = 60
                ok = 0
                for _ in range(n):
                    if b"200 OK" in await _one_shot(port, host="web"):
                        ok += 1
                assert ok == n
                # the stats loop runs at 1s: wait for the export
                for _ in range(80):
                    flat = linker.metrics.flatten()
                    w0 = flat.get("rt/mc/fastpath/worker/0/requests", 0)
                    w1 = flat.get("rt/mc/fastpath/worker/1/requests", 0)
                    if w0 + w1 >= n:
                        break
                    await asyncio.sleep(0.25)
                assert w0 > 0 and w1 > 0, (w0, w1)
                merged = flat.get("rt/mc/fastpath/route/web/requests", 0)
                assert merged == w0 + w1, (merged, w0, w1)
            finally:
                await linker.close()
                await srv.close()

        run(go())


class TestPythonSniExtraction:
    """The asyncio TLS data plane's half of ``tenantIdentifier: sni``
    (ROADMAP item 5 remainder): the server surfaces the handshake's
    server name into ``req.ctx["sni"]`` and TenantTagFilter's hash is
    bit-identical to the engines' C extraction."""

    def test_http_server_surfaces_sni_parity_with_engine(self, certs):
        async def go():
            import ssl

            from linkerd_tpu.protocol.http import Response
            from linkerd_tpu.protocol.http.server import HttpServer
            from linkerd_tpu.protocol.tls import TlsServerConfig
            from linkerd_tpu.router.service import FnService
            from linkerd_tpu.router.tenancy import (
                TenantIdentifierSpec, tenant_hash,
            )

            seen = {}
            spec = TenantIdentifierSpec(kind="sni")

            async def h(req):
                seen["sni"] = req.ctx.get("sni")
                seen["tenant"] = spec.extract(req)
                return Response(200, body=b"ok")

            srv = await HttpServer(
                FnService(h),
                ssl_context=TlsServerConfig(*certs).mk_context()).start()
            try:
                cctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
                cctx.check_hostname = False
                cctx.verify_mode = ssl.CERT_NONE
                r, w = await asyncio.open_connection(
                    "127.0.0.1", srv.bound_port, ssl=cctx,
                    server_hostname="tenant-a.example")
                w.write(b"GET / HTTP/1.1\r\nHost: x\r\n"
                        b"Connection: close\r\n\r\n")
                await w.drain()
                await r.read(4096)
                w.close()
            finally:
                await srv.close()
            assert seen["sni"] == "tenant-a.example"
            assert seen["tenant"] == "tenant-a.example"
            # parity: the Python hash of the extracted SNI equals the
            # C engines' FNV-1a over the same bytes
            assert tenant_hash(seen["tenant"]) == \
                native.tenant_hash_native(b"tenant-a.example")

        run(go())

    def test_h2_server_surfaces_sni(self, certs):
        async def go():
            from linkerd_tpu.protocol.h2.client import H2Client
            from linkerd_tpu.protocol.h2.messages import H2Response
            from linkerd_tpu.protocol.h2.server import H2Server
            from linkerd_tpu.protocol.h2.stream import stream_of
            from linkerd_tpu.protocol.tls import TlsServerConfig
            from linkerd_tpu.router.service import FnService
            import ssl

            seen = {}

            async def h(req):
                seen["sni"] = req.ctx.get("sni")
                return H2Response(status=200, stream=stream_of(b"ok"))

            srv = await H2Server(
                FnService(h),
                ssl_context=TlsServerConfig(*certs).mk_context()).start()
            cctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
            cctx.check_hostname = False
            cctx.verify_mode = ssl.CERT_NONE
            cctx.set_alpn_protocols(["h2"])
            client = H2Client("127.0.0.1", srv.bound_port,
                              ssl_context=cctx,
                              server_hostname="tenant-b.example")
            try:
                from linkerd_tpu.protocol.h2.messages import H2Request
                rsp = await client(H2Request(
                    method="GET", path="/", authority="x",
                    stream=stream_of(b"")))
                assert rsp.status == 200
            finally:
                await client.close()
                await srv.close()
            assert seen["sni"] == "tenant-b.example"

        run(go())

    def test_cleartext_conn_has_no_sni(self):
        async def go():
            from linkerd_tpu.protocol.http import Response
            from linkerd_tpu.protocol.http.server import HttpServer
            from linkerd_tpu.router.service import FnService

            seen = {}

            async def h(req):
                seen["sni"] = req.ctx.get("sni")
                return Response(200, body=b"ok")

            srv = await HttpServer(FnService(h)).start()
            try:
                await _one_shot(srv.bound_port, host="x")
            finally:
                await srv.close()
            assert seen["sni"] is None

        run(go())
