"""Assembled-binary validator.

Ref: validator/src/main/scala/io/buoyant/namerd/Validator.scala:13-80 +
``validator/validateAssembled`` (project/LinkerdBuild.scala:620-634):
spawn the REAL linkerd and namerd executables as subprocesses, stand up
downstream HTTP servers, drive dtab flips through namerd's HTTP control
API, and assert traffic re-routes within bounded staleness.

Runs the full flip sequence once per control-plane protocol: the gRPC
mesh iface (io.l5d.mesh), the thrift long-poll iface (io.l5d.namerd over
io.l5d.thriftNameInterpreter), and the chunked-HTTP interpreter
(io.l5d.namerd.http) — all three of the reference's linkerd<->namerd
protocols.

Usage: python tools/validator.py [mesh|thrift|http ...]  (exit 0 = pass)

Also validates model-checkpoint stores (the lifecycle subsystem's
artifact integrity: CRCs, manifest/file agreement, lineage, orphans):

    python tools/validator.py ckpt <store-dir> [<store-dir> ...]

And runs the l5dlint static-analysis suite (tools/analysis) over the
tree — non-zero exit on any unsuppressed finding:

    python tools/validator.py lint [path ...]

And the l5drace await-atomicity/lock-discipline analysis
(tools/analysis/race) over the asyncio data plane:

    python tools/validator.py race [path ...]

And the l5dseam cross-plane contract sweep (tools/analysis/seam) over
the C++/Python boundary — ABI widths, mirrored constants, the stats
scrape map, knob plumbing (whole-seam, takes no paths):

    python tools/validator.py seam

And the l5dnat native static sweep (tools/analysis/native) over the
C++ engines — atomics ordering, fd lifecycle, event-loop discipline,
bounded tables, errno hygiene — plus a planted-violation smoke that
proves the rules still catch a relaxed publish flip (whole-tree,
takes no paths):

    python tools/validator.py nat

And the l5dbudget hot-path cost sweep (tools/analysis/budget) over the
C++ engines — syscall sites, heap allocations, lock acquisitions, and
bulk copies per declared entrypoint vs the checked-in budget manifest —
plus a planted-violation smoke AND a measured cross-check that runs the
assembled engines under load with an LD_PRELOAD syscall counter and
reconciles syscalls-per-request against the manifest's declared
expectation (whole-tree, takes no paths):

    python tools/validator.py budget

And the l5dcheck semantic config verification (tools/analysis/semantic)
over linker/namerd YAML — defaults to every fixture under tests/configs/
and examples/ when no files are given:

    python tools/validator.py config [config.yml ...]

And the chaos validation: boot the assembled linker with its anomaly
scorer sidecar black-holed, assert the data plane keeps serving within
its deadline budget, the ``anomaly/degraded`` gauge flips to 1, and —
after swapping the black hole for a live sidecar — scoring recovers
(gauge back to 0) within a breaker-probe interval:

    python tools/validator.py chaos

And the scorer-latency validation: boot the REAL linkerd binary with
the line-rate in-process scorer, drive paced traffic, and assert the
added p99 and the scored fraction (scored_total == requests_total)
from the live metrics tree:

    python tools/validator.py scorer-latency

And the trace validation: boot the REAL linkerd binary with a
two-router chain (edge -> inner over loopback) and a zipkin exporter
pointed at a stub collector, drive one request, and assert the
exported spans form a single connected tree under one trace id (edge
server -> edge client -> inner server -> inner client):

    python tools/validator.py trace

And the control-loop validation: boot the REAL linkerd and namerd
binaries with the jaxAnomaly ``control:`` block and its ONLINE-TRAINED
in-process scorer, warm it on normal traffic, then fault the primary
cluster (errors + latency) and assert from live metrics that the
reactor publishes an l5dcheck-verified dtab override (traffic shifts to
the failover cluster), and reverts it after the fault clears:

    python tools/validator.py control

And the TLS validation: boot the REAL linkerd binary with a
``fastPath: true`` router terminating TLS on the accept leg and
originating TLS on the upstream leg (self-signed cert minted with the
openssl CLI), drive HTTPS traffic, and assert from live metrics that
the NATIVE engine — not a Python fallback — served it (the
``rt/*/fastpath/tls/*`` handshake/ALPN counters only exist when the
C++ epoll loop owns the bytes) and that every TLS'd request was still
scored (scored fraction 1.0):

    python tools/validator.py tls

And the native-score validation: boot the REAL linkerd binary with a
``fastPath: true`` router and the jaxAnomaly telemeter's in-data-plane
tier (``nativeTier: primary``, the default), drive paced traffic, and
assert from live metrics that the NATIVE tier — not the JAX fallback —
scored 100% of the measured window (the ``rt/*/fastpath/scorer/*``
counters only exist when the C++ epoll loop evaluated the model), with
the client-observed added p99 reported alongside:

    python tools/validator.py native-score

And the tenant-isolation validation: boot the REAL linkerd binary with
a ``fastPath: true`` router carrying the tenant stack (tenantIdentifier
+ tenants quota governor + connectionGuard), launch attacker + victim
tenant traffic, and assert from live state that the attacker was shed
at the NATIVE tier, the victim's success rate stayed >= 0.99, and the
``rt/*/fastpath/tenant/*`` metrics agree with admin ``/tenants.json``:

    python tools/validator.py tenant

And the multi-core validation: boot the REAL linkerd binary with a
``fastPath: true`` router sharded across two SO_REUSEPORT workers
(``workers: 2``), drive paced traffic over many distinct connections,
and assert from live metrics that BOTH workers served requests
(``rt/*/fastpath/worker/<i>/*`` only moves when that worker's epoll
loop retired an exchange), that the merged route counters equal the sum
of the per-worker counters (the merge-at-scrape rule), and that the
scored fraction stayed 1.0 — the shared read-only weight slab reached
every core:

    python tools/validator.py cores

And the fleet validation: boot 3 REAL linkerd binaries + 1 namerd
binary as a coordinated mesh (cross-instance score exchange through
the namerd store + admin-server gossip, quorum-gated actuation), and
assert that a fault visible to 1/3 instances shifts nothing, a fault
visible to 2/3 triggers exactly one fleet-wide dtab shift (peers
adopt; zero flaps), and recovery reverts the namespace exactly:

    python tools/validator.py fleet
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

STALENESS_S = 5.0

# per-protocol port blocks so back-to-back runs never collide
PORTS = {
    "mesh":   {"http": 24180, "iface": 24321, "linkerd": 24140,
               "admin": 24990, "a": 24801, "b": 24802},
    "thrift": {"http": 25180, "iface": 25100, "linkerd": 25140,
               "admin": 25990, "a": 25801, "b": 25802},
    "http":   {"http": 26180, "iface": 26180, "linkerd": 26140,
               "admin": 26990, "a": 26801, "b": 26802},
    "chaos":  {"linkerd": 27140, "admin": 27990, "a": 27801,
               "sidecar": 27321},
    "trace":  {"edge": 28140, "inner": 28141, "admin": 28990,
               "a": 28801, "collector": 28411},
    "scorer": {"linkerd": 29140, "admin": 29990, "a": 29801},
    "control": {"linkerd": 30140, "admin": 30990, "namerd": 30180,
                "a": 30801, "b": 30802},
    "tls":    {"linkerd": 31140, "admin": 31990, "a": 31801},
    "native-score": {"linkerd": 32140, "admin": 32990, "a": 32801},
    "tenant": {"linkerd": 33140, "admin": 33990, "a": 33801,
               "b": 33802},
    "cores":  {"linkerd": 34140, "admin": 34990, "a": 34801},
}

IFACE_YAML = {
    "mesh": "- kind: io.l5d.mesh\n  port: {iface}\n",
    "thrift": "- kind: io.l5d.thriftNameInterpreter\n  port: {iface}\n",
    "http": "",  # the control API itself is the interpreter's protocol
}

INTERP_YAML = {
    "mesh": ("    kind: io.l5d.mesh\n"
             "    dst: /$/inet/127.0.0.1/{iface}\n"
             "    root: /default\n"),
    "thrift": ("    kind: io.l5d.namerd\n"
               "    dst: /$/inet/127.0.0.1/{iface}\n"
               "    namespace: default\n"),
    "http": ("    kind: io.l5d.namerd.http\n"
             "    dst: /$/inet/127.0.0.1/{iface}\n"
             "    namespace: default\n"),
}


def http(method: str, url: str, body: bytes = b"", headers=None) -> tuple:
    req = urllib.request.Request(url, data=body or None, method=method,
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=10) as rsp:
            return rsp.status, dict(rsp.headers), rsp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


async def downstream(name: str, port: int):
    async def on_conn(reader, writer):
        try:
            while True:
                head = await reader.readuntil(b"\r\n\r\n")
                if not head:
                    return
                body = name.encode()
                writer.write(
                    b"HTTP/1.1 200 OK\r\nContent-Length: "
                    + str(len(body)).encode() + b"\r\n\r\n" + body)
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionResetError):
            pass
        finally:
            writer.close()
    return await asyncio.start_server(on_conn, "127.0.0.1", port)


async def wait_for(predicate, timeout: float, what: str):
    """Polls in a worker thread so the in-process downstreams (which run
    on this event loop) keep serving while we wait."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            if await asyncio.to_thread(predicate):
                return
        except Exception:
            pass
        await asyncio.sleep(0.2)
    raise AssertionError(f"timed out waiting for {what}")


async def validate(protocol: str) -> None:
    ports = PORTS[protocol]
    NAMERD_HTTP = ports["http"]
    LINKERD_PORT = ports["linkerd"]
    work = tempfile.mkdtemp(prefix=f"l5d-validate-{protocol}-")
    disco = os.path.join(work, "disco")
    dtabs = os.path.join(work, "dtabs")
    os.makedirs(disco)

    d_a = await downstream("A", ports["a"])
    d_b = await downstream("B", ports["b"])
    with open(os.path.join(disco, "svc-a"), "w") as f:
        f.write(f"127.0.0.1 {ports['a']}\n")
    with open(os.path.join(disco, "svc-b"), "w") as f:
        f.write(f"127.0.0.1 {ports['b']}\n")

    namerd_yaml = os.path.join(work, "namerd.yaml")
    with open(namerd_yaml, "w") as f:
        f.write(f"""
storage:
  kind: io.l5d.fs
  directory: {dtabs}
namers:
- kind: io.l5d.fs
  rootDir: {disco}
interfaces:
{IFACE_YAML[protocol].format(**ports)}- kind: io.l5d.httpController
  port: {NAMERD_HTTP}
""")
    linkerd_yaml = os.path.join(work, "linkerd.yaml")
    with open(linkerd_yaml, "w") as f:
        f.write(f"""
routers:
- protocol: http
  label: validated
  interpreter:
{INTERP_YAML[protocol].format(**ports)}  servers:
  - port: {LINKERD_PORT}
admin:
  port: {ports['admin']}
""")

    env = dict(os.environ, PYTHONPATH=REPO)
    procs = []
    try:
        # spawn the two real binaries (ref: Validator spawns assembled jars)
        namerd = subprocess.Popen(
            [sys.executable, "-m", "linkerd_tpu.namerd", namerd_yaml],
            env=env, cwd=work)
        procs.append(namerd)
        await wait_for(lambda: http(
            "GET", f"http://127.0.0.1:{NAMERD_HTTP}/api/1/dtabs"
        )[0] == 200, 15, "namerd http controller")

        st, _, _ = await asyncio.to_thread(http,
            "POST", f"http://127.0.0.1:{NAMERD_HTTP}/api/1/dtabs/default",
            b"/svc => /#/io.l5d.fs/svc-a;")
        assert st == 204, f"dtab create: {st}"

        linkerd = subprocess.Popen(
            [sys.executable, "-m", "linkerd_tpu", linkerd_yaml],
            env=env, cwd=work)
        procs.append(linkerd)
        await wait_for(lambda: http(
            "GET", f"http://127.0.0.1:{LINKERD_PORT}/",
            headers={"Host": "web"})[2] == b"A", 15, "route to A")
        print(f"validator[{protocol}]: initial route -> A ok")

        # flip the dtab (CAS) -> expect B within bounded staleness
        st, hdrs, _ = await asyncio.to_thread(http,
            "GET", f"http://127.0.0.1:{NAMERD_HTTP}/api/1/dtabs/default")
        etag = hdrs.get("ETag")
        st, _, _ = await asyncio.to_thread(http,
            "PUT", f"http://127.0.0.1:{NAMERD_HTTP}/api/1/dtabs/default",
            b"/svc => /#/io.l5d.fs/svc-b;", headers={"If-Match": etag})
        assert st == 204, f"dtab flip: {st}"
        t0 = time.time()
        await wait_for(lambda: http(
            "GET", f"http://127.0.0.1:{LINKERD_PORT}/",
            headers={"Host": "web"})[2] == b"B",
            STALENESS_S, "re-route to B")
        print(f"validator[{protocol}]: dtab flip re-routed "
              f"in {time.time() - t0:.2f}s")

        # stale CAS must fail
        st, _, _ = await asyncio.to_thread(http,
            "PUT", f"http://127.0.0.1:{NAMERD_HTTP}/api/1/dtabs/default",
            b"/svc => /#/io.l5d.fs/svc-a;", headers={"If-Match": etag})
        assert st == 412, f"stale CAS should 412, got {st}"
        print(f"validator[{protocol}]: stale CAS rejected (412)")

        # delegate API agrees with live routing
        st, _, body = await asyncio.to_thread(http,
            "GET", f"http://127.0.0.1:{NAMERD_HTTP}"
                   f"/api/1/delegate/default?path=/svc/web")
        tree = json.loads(body)
        assert "svc-b" in json.dumps(tree), tree
        print(f"validator[{protocol}]: delegation explanation matches")
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        d_a.close()
        d_b.close()


async def validate_chaos() -> None:
    """Boot the REAL linkerd binary with its anomaly sidecar
    black-holed, prove degradation is graceful and recovery automatic.
    Prints one ``CHAOS {json}`` line with the measured windows (bench.py
    folds it into detail.resilience)."""
    import numpy as np

    from linkerd_tpu.telemetry.sidecar import ScorerSidecar
    from linkerd_tpu.testing.faults import BlackholeServer

    ports = PORTS["chaos"]
    work = tempfile.mkdtemp(prefix="l5d-validate-chaos-")
    disco = os.path.join(work, "disco")
    os.makedirs(disco)
    d_a = await downstream("A", ports["a"])
    with open(os.path.join(disco, "web"), "w") as f:
        f.write(f"127.0.0.1 {ports['a']}\n")

    hole = await BlackholeServer(port=ports["sidecar"]).start()

    linkerd_yaml = os.path.join(work, "linkerd.yaml")
    with open(linkerd_yaml, "w") as f:
        f.write(f"""
routers:
- protocol: http
  label: chaos
  dtab: |
    /svc => /#/io.l5d.fs ;
  service:
    totalTimeoutMs: 1000
  admissionControl: {{maxConcurrency: 512, maxPending: 64}}
  servers:
  - port: {ports['linkerd']}
namers:
- kind: io.l5d.fs
  rootDir: {disco}
telemetry:
- kind: io.l5d.jaxAnomaly
  sidecarAddress: 127.0.0.1:{ports['sidecar']}
  sidecarTier: primary  # the chaos scenario exercises the sidecar path
  trainEveryBatches: 0
  scoreTimeoutMs: 200
  breakerFailures: 1
  breakerMinBackoffMs: 200
  breakerMaxBackoffMs: 400
  scoreTtlSecs: 2
admin:
  port: {ports['admin']}
""")

    def degraded() -> float:
        _, _, body = http(
            "GET", f"http://127.0.0.1:{ports['admin']}"
                   f"/admin/metrics.json?q=anomaly")
        return float(json.loads(body).get("anomaly/degraded", -1.0))

    def route_ok() -> bool:
        t0 = time.time()
        st, _, body = http(
            "GET", f"http://127.0.0.1:{ports['linkerd']}/",
            headers={"Host": "web"})
        took = time.time() - t0
        assert took < 1.0, f"request took {took:.2f}s (> deadline budget)"
        return st == 200 and body == b"A"

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    linkerd = None
    sidecar = None
    try:
        linkerd = subprocess.Popen(
            [sys.executable, "-m", "linkerd_tpu", linkerd_yaml],
            env=env, cwd=work)
        await wait_for(route_ok, 20, "chaos route to A")
        print("validator[chaos]: data plane up (sidecar black-holed)")

        # the drain loop hits the black hole; the degraded gauge must
        # flip while traffic keeps succeeding inside its budget
        t0 = time.time()
        await wait_for(lambda: route_ok() and degraded() == 1.0,
                       20, "anomaly/degraded flip")
        degrade_s = time.time() - t0
        for _ in range(10):
            assert await asyncio.to_thread(route_ok)
        print(f"validator[chaos]: degraded in {degrade_s:.2f}s, "
              f"traffic still flows")

        # fault clears: a live sidecar (stub scorer, no device) takes
        # over the SAME port; a breaker probe must close the loop
        await hole.close()

        class _Stub:
            async def score(self, x):
                return np.zeros(len(x), np.float32)

            async def fit(self, x, labels, mask):
                return 0.0

            def close(self):
                pass

        sidecar = await ScorerSidecar(
            _Stub(), port=ports["sidecar"]).start()
        t0 = time.time()
        await wait_for(lambda: route_ok() and degraded() == 0.0,
                       20, "anomaly recovery")
        recover_s = time.time() - t0
        print(f"validator[chaos]: recovered in {recover_s:.2f}s")
        print("CHAOS " + json.dumps({
            "degrade_s": round(degrade_s, 2),
            "recover_s": round(recover_s, 2),
        }))
    finally:
        if linkerd is not None:
            linkerd.send_signal(signal.SIGTERM)
            try:
                linkerd.wait(timeout=10)
            except subprocess.TimeoutExpired:
                linkerd.kill()
        if sidecar is not None:
            await sidecar.close()
        await hole.close()
        d_a.close()


async def faultable_downstream(name: str, port: int, fault: dict):
    """Downstream that serves 200/<name> normally; while
    ``fault['on']`` it answers 503 after ~150ms — the feature shape
    (status + latency spike + error-rate drift) the anomaly scorer is
    trained to flag."""
    async def on_conn(reader, writer):
        try:
            while True:
                head = await reader.readuntil(b"\r\n\r\n")
                if not head:
                    return
                if fault["on"]:
                    await asyncio.sleep(0.15)
                    body = b"injected fault"
                    writer.write(
                        b"HTTP/1.1 503 Service Unavailable\r\n"
                        b"l5d-fault-label: 1\r\nContent-Length: "
                        + str(len(body)).encode() + b"\r\n\r\n" + body)
                else:
                    body = name.encode()
                    writer.write(
                        b"HTTP/1.1 200 OK\r\nl5d-fault-label: 0\r\n"
                        b"Content-Length: "
                        + str(len(body)).encode() + b"\r\n\r\n" + body)
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionResetError):
            pass
        finally:
            writer.close()
    return await asyncio.start_server(on_conn, "127.0.0.1", port)


async def validate_control() -> None:
    """Boot the REAL namerd + linkerd binaries with the reactive
    control loop configured, fault the primary cluster, and assert the
    whole loop closes: scores rise -> the reactor CAS-publishes an
    l5dcheck-verified override through namerd -> traffic shifts to the
    failover cluster -> the fault clears -> the override reverts and
    traffic returns. Prints one ``CONTROL {json}`` line with the
    measured actuation windows."""
    ports = PORTS["control"]
    work = tempfile.mkdtemp(prefix="l5d-validate-control-")
    disco = os.path.join(work, "disco")
    dtabs = os.path.join(work, "dtabs")
    os.makedirs(disco)
    fault = {"on": False}
    d_a = await faultable_downstream("A", ports["a"], fault)
    d_b = await faultable_downstream("B", ports["b"], {"on": False})
    with open(os.path.join(disco, "web"), "w") as f:
        f.write(f"127.0.0.1 {ports['a']}\n")
    with open(os.path.join(disco, "web-b"), "w") as f:
        f.write(f"127.0.0.1 {ports['b']}\n")

    namerd_yaml = os.path.join(work, "namerd.yaml")
    with open(namerd_yaml, "w") as f:
        f.write(f"""
storage:
  kind: io.l5d.fs
  directory: {dtabs}
namers:
- kind: io.l5d.fs
  rootDir: {disco}
interfaces:
- kind: io.l5d.httpController
  port: {ports['namerd']}
""")
    linkerd_yaml = os.path.join(work, "linkerd.yaml")
    with open(linkerd_yaml, "w") as f:
        f.write(f"""
routers:
- protocol: http
  label: ctrl
  interpreter:
    kind: io.l5d.namerd.http
    dst: /$/inet/127.0.0.1/{ports['namerd']}
    namespace: default
  servers:
  - port: {ports['linkerd']}
telemetry:
- kind: io.l5d.jaxAnomaly
  maxLingerMs: 2
  scoreTtlSecs: 30
  control:
    intervalMs: 50
    enterThreshold: 0.5
    exitThreshold: 0.2
    quorum: 4
    cooldownS: 1.0
    namespace: default
    namerdAddress: 127.0.0.1:{ports['namerd']}
    failover:
      /svc/web: /svc/web-b
admin:
  port: {ports['admin']}
""")

    def route() -> bytes:
        _, _, body = http(
            "GET", f"http://127.0.0.1:{ports['linkerd']}/",
            headers={"Host": "web"})
        return body

    def reactor_metric(name: str) -> float:
        _, _, body = http(
            "GET", f"http://127.0.0.1:{ports['admin']}"
                   f"/admin/metrics.json?q=control")
        return float(json.loads(body).get(
            f"control/reactor/{name}", 0.0))

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    procs = []
    try:
        namerd = subprocess.Popen(
            [sys.executable, "-m", "linkerd_tpu.namerd", namerd_yaml],
            env=env, cwd=work)
        procs.append(namerd)
        await wait_for(lambda: http(
            "GET", f"http://127.0.0.1:{ports['namerd']}/api/1/dtabs"
        )[0] == 200, 15, "namerd http controller")
        st, _, _ = await asyncio.to_thread(
            http, "POST",
            f"http://127.0.0.1:{ports['namerd']}/api/1/dtabs/default",
            b"/svc => /#/io.l5d.fs;")
        assert st == 204, f"dtab create: {st}"

        linkerd = subprocess.Popen(
            [sys.executable, "-m", "linkerd_tpu", linkerd_yaml],
            env=env, cwd=work)
        procs.append(linkerd)
        await wait_for(lambda: route() == b"A", 30, "control route to A")
        print("validator[control]: route -> A; warming the scorer "
              "on normal traffic")
        # warm: the in-process scorer online-trains on normal features
        for _ in range(300):
            assert await asyncio.to_thread(route) == b"A"
            await asyncio.sleep(0.01)
        assert reactor_metric("overrides_published") == 0

        # fault the primary cluster: errors + latency. The predicates
        # keep DRIVING traffic — scores only move while features flow.
        fault["on"] = True
        t0 = time.time()

        def drive_then(metric: str, want: float):
            def probe() -> bool:
                try:
                    route()
                except Exception:  # noqa: BLE001 — faulted traffic may
                    pass           # 503; the features still flowed
                return reactor_metric(metric) >= want
            return probe

        await wait_for(
            drive_then("overrides_published", 1),
            60, "override publish (scores must cross the threshold)")
        publish_s = time.time() - t0
        await wait_for(lambda: route() == b"B", 10, "traffic shift to B")
        shift_s = time.time() - t0
        print(f"validator[control]: override published in "
              f"{publish_s:.2f}s, traffic shifted in {shift_s:.2f}s")
        _, _, body = http("GET", f"http://127.0.0.1:{ports['admin']}"
                                 f"/control.json")
        state = json.loads(body)
        assert state["reactor"]["active_overrides"], state

        # fault clears: healthy traffic through B drives scores down
        fault["on"] = False
        t0 = time.time()
        await wait_for(
            drive_then("overrides_reverted", 1), 60, "override revert")
        await wait_for(lambda: route() == b"A", 10, "traffic return to A")
        revert_s = time.time() - t0
        print(f"validator[control]: reverted in {revert_s:.2f}s; "
              f"zero flaps: "
              f"{reactor_metric('overrides_published') == 1}")
        assert reactor_metric("overrides_published") == 1, "flapped!"
        print("CONTROL " + json.dumps({
            "publish_s": round(publish_s, 2),
            "shift_s": round(shift_s, 2),
            "revert_s": round(revert_s, 2),
        }))
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        d_a.close()
        d_b.close()


async def validate_scorer_latency() -> None:
    """Boot the REAL linkerd binary with the line-rate in-process
    scorer, drive paced traffic, and assert from the LIVE metrics tree
    that (a) 100% of requests are scored (scored fraction 1.0 once the
    linger window drains) and (b) the proxy's added p99 stays bounded
    with scoring inline. Prints one ``SCORER-LATENCY {json}`` line."""
    ports = PORTS["scorer"]
    work = tempfile.mkdtemp(prefix="l5d-validate-scorer-")
    disco = os.path.join(work, "disco")
    os.makedirs(disco)
    d_a = await downstream("A", ports["a"])
    with open(os.path.join(disco, "web"), "w") as f:
        f.write(f"127.0.0.1 {ports['a']}\n")

    linkerd_yaml = os.path.join(work, "linkerd.yaml")
    with open(linkerd_yaml, "w") as f:
        f.write(f"""
routers:
- protocol: http
  label: scorer
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers:
  - port: {ports['linkerd']}
namers:
- kind: io.l5d.fs
  rootDir: {disco}
telemetry:
- kind: io.l5d.jaxAnomaly
  maxBatch: 256
  trainEveryBatches: 0
admin:
  port: {ports['admin']}
""")

    def anomaly_metrics() -> dict:
        _, _, body = http(
            "GET", f"http://127.0.0.1:{ports['admin']}"
                   f"/admin/metrics.json?q=anomaly")
        return json.loads(body)

    def route_ok() -> bool:
        st, _, body = http(
            "GET", f"http://127.0.0.1:{ports['linkerd']}/",
            headers={"Host": "web"})
        return st == 200 and body == b"A"

    def one_timed() -> float:
        t0 = time.perf_counter()
        st, _, _ = http(
            "GET", f"http://127.0.0.1:{ports['linkerd']}/",
            headers={"Host": "web"})
        assert st == 200
        return (time.perf_counter() - t0) * 1e3

    def direct_timed() -> float:
        t0 = time.perf_counter()
        st, _, _ = http("GET", f"http://127.0.0.1:{ports['a']}/",
                        headers={"Host": "web"})
        assert st == 200
        return (time.perf_counter() - t0) * 1e3

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    linkerd = None
    try:
        linkerd = subprocess.Popen(
            [sys.executable, "-m", "linkerd_tpu", linkerd_yaml],
            env=env, cwd=work)
        await wait_for(route_ok, 30, "scorer-latency route up")
        # warm: let the first batches compile off the measured window
        for _ in range(30):
            await asyncio.to_thread(one_timed)
        await wait_for(
            lambda: anomaly_metrics().get("anomaly/scored_total", 0) > 0,
            30, "first scored batch")

        n = 300
        pace_s = 0.002  # ~500 rps paced
        lats, direct = [], []
        for i in range(n):
            lats.append(await asyncio.to_thread(one_timed))
            if i % 3 == 0:
                direct.append(await asyncio.to_thread(direct_timed))
            await asyncio.sleep(pace_s)
        lats.sort()
        direct.sort()
        p99 = lats[int(0.99 * (len(lats) - 1))]
        added_p99 = p99 - direct[len(direct) // 2]

        # the linger window is ms-scale: every recorded request must be
        # scored almost immediately after the pacing stops
        await wait_for(
            lambda: (lambda m: m.get("anomaly/requests_total", 0) > 0
                     and m.get("anomaly/scored_total", 0)
                     == m.get("anomaly/requests_total", -1))(
                         anomaly_metrics()),
            15, "scored fraction settling to 1.0")
        m = anomaly_metrics()
        frac = m["anomaly/scored_total"] / m["anomaly/requests_total"]
        assert frac == 1.0, f"scored fraction {frac}"
        assert added_p99 < 100.0, \
            f"added p99 {added_p99:.1f}ms with inline scoring"
        print("SCORER-LATENCY " + json.dumps({
            "requests": int(m["anomaly/requests_total"]),
            "scored": int(m["anomaly/scored_total"]),
            "scored_fraction": frac,
            "proxy_p50_ms": round(lats[len(lats) // 2], 3),
            "proxy_p99_ms": round(p99, 3),
            "added_p99_ms": round(added_p99, 3),
            "paced_rps": round(1.0 / pace_s, 1),
        }))
    finally:
        if linkerd is not None:
            linkerd.send_signal(signal.SIGTERM)
            try:
                linkerd.wait(timeout=10)
            except subprocess.TimeoutExpired:
                linkerd.kill()
        d_a.close()


async def tls_downstream(name: str, port: int, cert: str, key: str):
    """Keep-alive HTTP/1.1 downstream behind TLS, so the linker's
    upstream leg has to originate (and the validator can count
    upstream handshakes)."""
    import ssl as _ssl
    sctx = _ssl.SSLContext(_ssl.PROTOCOL_TLS_SERVER)
    sctx.load_cert_chain(cert, key)

    async def on_conn(reader, writer):
        try:
            while True:
                head = await reader.readuntil(b"\r\n\r\n")
                if not head:
                    return
                body = name.encode()
                writer.write(
                    b"HTTP/1.1 200 OK\r\nContent-Length: "
                    + str(len(body)).encode() + b"\r\n\r\n" + body)
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionResetError,
                OSError):
            pass
        finally:
            writer.close()
    return await asyncio.start_server(on_conn, "127.0.0.1", port,
                                      ssl=sctx)


async def validate_tls() -> None:
    """Boot the REAL linkerd binary with a fastPath router that
    terminates TLS on the accept leg and originates TLS on the upstream
    leg, drive HTTPS traffic, and assert from the LIVE metrics tree
    that (a) the native engine served it — the rt/*/fastpath/tls/*
    counters are only ever incremented by the C++ epoll loop, so a
    silent Python fallback shows zero handshakes and zero fastpath
    route requests — and (b) the line-rate scorer still saw every
    request (scored fraction 1.0: TLS'd bytes get the same zero-copy
    feature extraction as cleartext). Prints one ``TLS {json}`` line."""
    import ssl

    from linkerd_tpu import native
    if not (native.ensure_built()
            and native.FastPathEngine.tls_runtime_available()):
        raise AssertionError(
            "native toolchain or OpenSSL runtime unavailable — the "
            "tls validation proves the NATIVE engine serves TLS, so a "
            "missing runtime is a failure here, not a skip")

    ports = PORTS["tls"]
    work = tempfile.mkdtemp(prefix="l5d-validate-tls-")
    cert = os.path.join(work, "cert.pem")
    key = os.path.join(work, "key.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048",
         "-keyout", key, "-out", cert, "-days", "2", "-nodes",
         "-subj", "/CN=localhost",
         "-addext", "subjectAltName=DNS:localhost,DNS:web"],
        check=True, capture_output=True, timeout=60)

    disco = os.path.join(work, "disco")
    os.makedirs(disco)
    d_a = await tls_downstream("A", ports["a"], cert, key)
    with open(os.path.join(disco, "web"), "w") as f:
        f.write(f"127.0.0.1 {ports['a']}\n")

    linkerd_yaml = os.path.join(work, "linkerd.yaml")
    with open(linkerd_yaml, "w") as f:
        f.write(f"""
routers:
- protocol: http
  label: tls
  fastPath: true
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers:
  - port: {ports['linkerd']}
    tls:
      certPath: {cert}
      keyPath: {key}
  client:
    tls:
      trustCerts: [{cert}]
namers:
- kind: io.l5d.fs
  rootDir: {disco}
telemetry:
- kind: io.l5d.jaxAnomaly
  maxBatch: 256
  trainEveryBatches: 0
admin:
  port: {ports['admin']}
""")

    cctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    cctx.load_verify_locations(cert)

    def tls_get() -> bytes:
        # localhost as SNI/verify name (matches the cert SAN); the Host
        # header carries the routed authority, exactly as a client
        # behind a TLS-terminating edge would send it
        with socket.create_connection(("127.0.0.1", ports["linkerd"]),
                                      timeout=10) as raw:
            with cctx.wrap_socket(raw,
                                  server_hostname="localhost") as s:
                s.sendall(b"GET / HTTP/1.1\r\nHost: web\r\n"
                          b"Connection: close\r\n\r\n")
                buf = b""
                while True:
                    d = s.recv(4096)
                    if not d:
                        break
                    buf += d
        assert b" 200 " in buf.split(b"\r\n", 1)[0], buf[:200]
        return buf.rsplit(b"\r\n\r\n", 1)[-1]

    def metrics(q: str) -> dict:
        _, _, body = http(
            "GET", f"http://127.0.0.1:{ports['admin']}"
                   f"/admin/metrics.json?q={q}")
        return json.loads(body)

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    linkerd = None
    try:
        linkerd = subprocess.Popen(
            [sys.executable, "-m", "linkerd_tpu", linkerd_yaml],
            env=env, cwd=work)
        await wait_for(lambda: tls_get() == b"A", 30, "tls route to A")
        n = 40
        for _ in range(n):
            body = await asyncio.to_thread(tls_get)
            assert body == b"A", body

        def settled() -> bool:
            fp = metrics("rt/tls/fastpath")
            an = metrics("anomaly")
            return (fp.get("rt/tls/fastpath/tls/handshakes", 0) >= n
                    and fp.get("rt/tls/fastpath/route/web/requests",
                               0) >= n
                    and an.get("anomaly/requests_total", 0) >= n
                    and an.get("anomaly/scored_total", 0)
                    == an.get("anomaly/requests_total", -1))
        await wait_for(settled, 20,
                       "fastpath TLS counters + scored fraction 1.0")

        fp = metrics("rt/tls/fastpath")
        an = metrics("anomaly")
        handshakes = fp.get("rt/tls/fastpath/tls/handshakes", 0)
        up_handshakes = fp.get(
            "rt/tls/fastpath/tls/upstream_handshakes", 0)
        served = fp.get("rt/tls/fastpath/route/web/requests", 0)
        alpn_h1 = fp.get("rt/tls/fastpath/tls/alpn_http1", 0)
        assert up_handshakes >= 1, \
            "upstream leg never originated TLS natively"
        frac = (an["anomaly/scored_total"]
                / an["anomaly/requests_total"])
        assert frac == 1.0, f"scored fraction {frac}"
        print("TLS " + json.dumps({
            "requests": n,
            "native_served": served,
            "handshakes": handshakes,
            "upstream_handshakes": up_handshakes,
            "alpn_http1": alpn_h1,
            "handshake_failures":
                fp.get("rt/tls/fastpath/tls/failures", 0),
            "scored_fraction": frac,
        }))
    finally:
        if linkerd is not None:
            linkerd.send_signal(signal.SIGTERM)
            try:
                linkerd.wait(timeout=10)
            except subprocess.TimeoutExpired:
                linkerd.kill()
        d_a.close()


async def validate_native_score() -> None:
    """Boot the REAL linkerd binary with a fastPath router and the
    in-data-plane scoring tier (``nativeTier: primary``), drive paced
    traffic, and assert from the LIVE metrics tree that the NATIVE tier
    — not the JAX fallback — scored 100% of the measured window:

    - ``rt/*/fastpath/scorer/scored`` (incremented only by the C++
      epoll loop's per-request eval) grew by exactly the measured
      request count, with zero ``unscored`` growth — the engine, not a
      silent Python fallback, evaluated the model;
    - ``anomaly/native_scored_total`` grew in lockstep with
      ``anomaly/scored_total`` — every published score came from the
      engine, the JAX tier only trained;
    - the weight-slab gauges report a published blob (version + CRC
      matching /model.json's native_tier block).

    The client-observed added p99 (proxy vs direct) rides the report.
    Prints one ``NATIVE-SCORE {json}`` line."""
    from linkerd_tpu import native
    if not native.ensure_built():
        raise AssertionError(
            "native toolchain unavailable — the native-score validation "
            "proves the C++ engine scored in-data-plane, so a missing "
            "toolchain is a failure here, not a skip")

    ports = PORTS["native-score"]
    work = tempfile.mkdtemp(prefix="l5d-validate-nscore-")
    disco = os.path.join(work, "disco")
    os.makedirs(disco)
    d_a = await downstream("A", ports["a"])
    with open(os.path.join(disco, "web"), "w") as f:
        f.write(f"127.0.0.1 {ports['a']}\n")

    linkerd_yaml = os.path.join(work, "linkerd.yaml")
    with open(linkerd_yaml, "w") as f:
        f.write(f"""
routers:
- protocol: http
  label: native
  fastPath: true
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers:
  - port: {ports['linkerd']}
namers:
- kind: io.l5d.fs
  rootDir: {disco}
telemetry:
- kind: io.l5d.jaxAnomaly
  maxBatch: 256
  trainEveryBatches: 0
admin:
  port: {ports['admin']}
""")

    def metrics(q: str) -> dict:
        _, _, body = http(
            "GET", f"http://127.0.0.1:{ports['admin']}"
                   f"/admin/metrics.json?q={q}")
        return json.loads(body)

    def scorer_metrics() -> dict:
        m = metrics("rt/native/fastpath/scorer")
        m.update(metrics("anomaly"))
        return m

    def route_ok() -> bool:
        st, _, body = http(
            "GET", f"http://127.0.0.1:{ports['linkerd']}/",
            headers={"Host": "web"})
        return st == 200 and body == b"A"

    def one_timed() -> float:
        t0 = time.perf_counter()
        st, _, _ = http(
            "GET", f"http://127.0.0.1:{ports['linkerd']}/",
            headers={"Host": "web"})
        assert st == 200
        return (time.perf_counter() - t0) * 1e3

    def direct_timed() -> float:
        t0 = time.perf_counter()
        st, _, _ = http("GET", f"http://127.0.0.1:{ports['a']}/",
                        headers={"Host": "web"})
        assert st == 200
        return (time.perf_counter() - t0) * 1e3

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    linkerd = None
    try:
        linkerd = subprocess.Popen(
            [sys.executable, "-m", "linkerd_tpu", linkerd_yaml],
            env=env, cwd=work)
        await wait_for(route_ok, 30, "native-score route up")
        # warm until the weight blob has landed in the engine slab AND
        # rows started scoring in-engine (the startup export, the route
        # resolution, and the feature-hash push all have to complete;
        # warmup rows before that fall back to JAX by design)
        for _ in range(20):
            await asyncio.to_thread(one_timed)
        await wait_for(
            lambda: (lambda m: m.get(
                "rt/native/fastpath/scorer/weights", 0) == 1
                and m.get("rt/native/fastpath/scorer/scored", 0) > 0)(
                    scorer_metrics()),
            30, "weight blob published + first in-engine score")

        # settle the warmup, then snapshot — the measured window's
        # deltas are the proof (warmup rows that raced the publish fell
        # back to JAX legitimately and must not pollute the fraction)
        await asyncio.sleep(1.0)
        m0 = scorer_metrics()

        n = 300
        pace_s = 0.002  # ~500 rps paced
        lats, direct = [], []
        for i in range(n):
            lats.append(await asyncio.to_thread(one_timed))
            if i % 3 == 0:
                direct.append(await asyncio.to_thread(direct_timed))
            await asyncio.sleep(pace_s)
        lats.sort()
        direct.sort()
        p99 = lats[int(0.99 * (len(lats) - 1))]
        added_p99 = p99 - direct[len(direct) // 2]

        def d(m, key):
            return m.get(key, 0) - m0.get(key, 0)

        def settled() -> bool:
            m = scorer_metrics()
            return (d(m, "rt/native/fastpath/scorer/scored") >= n
                    and d(m, "anomaly/scored_total") >= n
                    and d(m, "anomaly/scored_total")
                    == d(m, "anomaly/requests_total"))
        await wait_for(settled, 20, "measured window drained + scored")

        m1 = scorer_metrics()
        eng_scored = d(m1, "rt/native/fastpath/scorer/scored")
        eng_unscored = d(m1, "rt/native/fastpath/scorer/unscored")
        nat = d(m1, "anomaly/native_scored_total")
        tot = d(m1, "anomaly/scored_total")
        assert eng_unscored == 0, \
            f"{eng_unscored} rows fell back to the JAX tier mid-window"
        assert eng_scored >= n, \
            f"engine scored {eng_scored} < {n} measured requests"
        frac = nat / tot if tot else 0.0
        assert frac == 1.0, \
            f"native tier scored fraction {frac} (native {nat}/{tot})"
        # the serving blob is versioned + CRC'd end to end: the engine
        # gauges agree with what /model.json says was exported
        _, _, body = http("GET", f"http://127.0.0.1:{ports['admin']}"
                                 f"/model.json")
        tier = json.loads(body)["native_tier"]
        assert tier["mode"] == "primary" and tier["blob"], tier
        assert m1.get("rt/native/fastpath/scorer/version") \
            == tier["blob"]["version"], (m1, tier)
        assert added_p99 < 50.0, \
            f"added p99 {added_p99:.1f}ms with in-engine scoring"
        print("NATIVE-SCORE " + json.dumps({
            "requests": n,
            "engine_scored": eng_scored,
            "engine_unscored": eng_unscored,
            "native_scored_fraction": frac,
            "blob_version": tier["blob"]["version"],
            "blob_crc": tier["blob"]["crc"],
            "proxy_p50_ms": round(lats[len(lats) // 2], 3),
            "proxy_p99_ms": round(p99, 3),
            "added_p99_ms": round(added_p99, 3),
            "paced_rps": round(1.0 / pace_s, 1),
        }))
    finally:
        if linkerd is not None:
            linkerd.send_signal(signal.SIGTERM)
            try:
                linkerd.wait(timeout=10)
            except subprocess.TimeoutExpired:
                linkerd.kill()
        d_a.close()


async def validate_cores() -> None:
    """Boot the REAL linkerd binary with a fastPath router sharded
    ``workers: 2`` and prove the multi-core data plane from live state:

    - both workers served: ``rt/*/fastpath/worker/<i>/requests`` grew
      for i = 0 AND 1 (each counter only moves when that worker's own
      epoll loop retired an exchange — the kernel's SO_REUSEPORT
      spread is real, not one hot socket);
    - merge-at-scrape holds: the merged route counter equals the sum
      of the per-worker request counters;
    - the shared weight slab reached every core: zero ``unscored``
      growth and ``anomaly/scored_total == anomaly/requests_total``
      over the measured window (scored fraction 1.0).

    Prints one ``CORES {json}`` line."""
    from linkerd_tpu import native
    if not native.ensure_built():
        raise AssertionError(
            "native toolchain unavailable — the cores validation proves "
            "the sharded C++ engines served, so a missing toolchain is "
            "a failure here, not a skip")

    ports = PORTS["cores"]
    work = tempfile.mkdtemp(prefix="l5d-validate-cores-")
    disco = os.path.join(work, "disco")
    os.makedirs(disco)
    d_a = await downstream("A", ports["a"])
    with open(os.path.join(disco, "web"), "w") as f:
        f.write(f"127.0.0.1 {ports['a']}\n")

    linkerd_yaml = os.path.join(work, "linkerd.yaml")
    with open(linkerd_yaml, "w") as f:
        f.write(f"""
routers:
- protocol: http
  label: cores
  fastPath: true
  workers: 2
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers:
  - port: {ports['linkerd']}
namers:
- kind: io.l5d.fs
  rootDir: {disco}
telemetry:
- kind: io.l5d.jaxAnomaly
  maxBatch: 256
  trainEveryBatches: 0
admin:
  port: {ports['admin']}
""")

    def metrics(q: str) -> dict:
        _, _, body = http(
            "GET", f"http://127.0.0.1:{ports['admin']}"
                   f"/admin/metrics.json?q={q}")
        return json.loads(body)

    def all_metrics() -> dict:
        m = metrics("rt/cores/fastpath")
        m.update(metrics("anomaly"))
        return m

    def route_ok() -> bool:
        st, _, body = http(
            "GET", f"http://127.0.0.1:{ports['linkerd']}/",
            headers={"Host": "web"})
        return st == 200 and body == b"A"

    def one() -> None:
        # urllib opens a FRESH connection per call: each request is a
        # new 4-tuple, so the kernel's per-connection REUSEPORT hash
        # keeps spreading across workers
        st, _, _ = http(
            "GET", f"http://127.0.0.1:{ports['linkerd']}/",
            headers={"Host": "web"})
        assert st == 200

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    linkerd = None
    try:
        linkerd = subprocess.Popen(
            [sys.executable, "-m", "linkerd_tpu", linkerd_yaml],
            env=env, cwd=work)
        await wait_for(route_ok, 30, "cores route up")
        # warm: let the startup weight export + route feature push land
        for _ in range(20):
            await asyncio.to_thread(one)
        await wait_for(
            lambda: metrics("rt/cores/fastpath/scorer").get(
                "rt/cores/fastpath/scorer/weights", 0) == 1,
            30, "weight blob published to the shard group")
        await asyncio.sleep(1.2)  # settle the warmup into the counters
        m0 = all_metrics()

        n = 240
        for i in range(n):
            await asyncio.to_thread(one)
            if i % 10 == 0:
                await asyncio.sleep(0.01)  # paced-ish

        def d(m, key):
            return m.get(key, 0) - m0.get(key, 0)

        def settled() -> bool:
            m = all_metrics()
            return (d(m, "rt/cores/fastpath/route/web/requests") >= n
                    and d(m, "anomaly/scored_total")
                    == d(m, "anomaly/requests_total")
                    and d(m, "anomaly/requests_total") >= n)
        await wait_for(settled, 20, "measured window drained + scored")

        m1 = all_metrics()
        per_worker = [
            d(m1, f"rt/cores/fastpath/worker/{i}/requests")
            for i in range(2)]
        merged = d(m1, "rt/cores/fastpath/route/web/requests")
        unscored = d(m1, "rt/cores/fastpath/scorer/unscored")
        scored = d(m1, "anomaly/scored_total")
        total = d(m1, "anomaly/requests_total")
        assert all(w > 0 for w in per_worker), (
            f"one worker served nothing: {per_worker} — the REUSEPORT "
            f"spread is not reaching every core")
        assert merged == sum(per_worker), (
            f"merged route counter {merged} != sum of per-worker "
            f"counters {per_worker} — the merge-at-scrape rule broke")
        assert unscored == 0, \
            f"{unscored} rows fell back to the JAX tier mid-window"
        frac = scored / total if total else 0.0
        assert frac == 1.0, \
            f"scored fraction {frac} ({scored}/{total})"
        print("CORES " + json.dumps({
            "requests": n,
            "per_worker_requests": per_worker,
            "merged_requests": merged,
            "engine_unscored": unscored,
            "scored_fraction": frac,
            "workers": 2,
        }))
    finally:
        if linkerd is not None:
            linkerd.send_signal(signal.SIGTERM)
            try:
                linkerd.wait(timeout=10)
            except subprocess.TimeoutExpired:
                linkerd.kill()
        d_a.close()


async def validate_tenant() -> None:
    """Boot the REAL linkerd binary with a fastPath router carrying
    the full tenant-isolation stack (tenantIdentifier + tenants quota
    governor + connectionGuard), launch attacker + victim tenant
    traffic, and assert from LIVE state that:

    - the attacker was shed at the NATIVE tier (the engine's
      ``guard.tenant_shed`` / per-tenant shed counters only move when
      the C++ epoll loop refused the request itself);
    - the victim tenant's success rate stayed >= 0.99 throughout;
    - ``rt/*/fastpath/tenant/*`` metrics agree with the admin
      ``/tenants.json`` view of the same engine table.

    Prints one ``TENANT {json}`` line."""
    from linkerd_tpu import native
    from linkerd_tpu.router.tenancy import tenant_hash
    from linkerd_tpu.testing.faults import (
        PacedTenantClient, TenantRetryStorm,
    )
    if not native.ensure_built():
        raise AssertionError(
            "native toolchain unavailable — the tenant validation "
            "proves the NATIVE tier sheds, so a missing lib is a "
            "failure here, not a skip")

    ports = PORTS["tenant"]
    work = tempfile.mkdtemp(prefix="l5d-validate-tenant-")
    disco = os.path.join(work, "disco")
    os.makedirs(disco)
    d_good = await downstream("G", ports["a"])

    async def boom_conn(reader, writer):
        try:
            while True:
                await reader.readuntil(b"\r\n\r\n")
                writer.write(b"HTTP/1.1 500 Boom\r\n"
                             b"Content-Length: 4\r\n\r\nboom")
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionResetError):
            pass
        finally:
            writer.close()

    d_boom = await asyncio.start_server(boom_conn, "127.0.0.1",
                                        ports["b"])
    with open(os.path.join(disco, "good"), "w") as f:
        f.write(f"127.0.0.1 {ports['a']}\n")
    with open(os.path.join(disco, "boom"), "w") as f:
        f.write(f"127.0.0.1 {ports['b']}\n")

    linkerd_yaml = os.path.join(work, "linkerd.yaml")
    with open(linkerd_yaml, "w") as f:
        f.write(f"""
routers:
- protocol: http
  label: tnt
  fastPath: true
  tenantIdentifier: {{kind: header, header: l5d-tenant}}
  tenants:
    floor: 0.05
    engineBase: 20
    enterThreshold: 0.45
    exitThreshold: 0.15
    quorum: 2
    cooldownS: 0.5
  connectionGuard:
    headerBudgetMs: 5000
    bodyStallMs: 10000
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers:
  - port: {ports['linkerd']}
namers:
- kind: io.l5d.fs
  rootDir: {disco}
admin:
  port: {ports['admin']}
""")

    def metrics(q: str) -> dict:
        _, _, body = http(
            "GET", f"http://127.0.0.1:{ports['admin']}"
                   f"/admin/metrics.json?q={q}")
        return json.loads(body)

    def tenants_json() -> dict:
        _, _, body = http(
            "GET", f"http://127.0.0.1:{ports['admin']}/tenants.json")
        return json.loads(body)

    def get_ok() -> bool:
        st, _, body = http(
            "GET", f"http://127.0.0.1:{ports['linkerd']}/",
            headers={"Host": "good", "l5d-tenant": "victim"})
        return st == 200 and body == b"G"

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    linkerd = None
    try:
        linkerd = subprocess.Popen(
            [sys.executable, "-m", "linkerd_tpu", linkerd_yaml],
            env=env, cwd=work)
        await wait_for(get_ok, 30, "fastpath route to good")

        # warm the boom route too (the storm needs it installed); in a
        # worker thread — the boom downstream serves on THIS loop
        def boom_ok() -> bool:
            st, _, _ = http(
                "GET", f"http://127.0.0.1:{ports['linkerd']}/",
                headers={"Host": "boom", "l5d-tenant": "attacker"})
            return st == 500

        await wait_for(boom_ok, 30, "fastpath route to boom")

        # attacker retry-storms the failing route; its engine-side
        # error EWMA (ingested by the fastpath stats loop each second)
        # trips the quota governor, which pushes a floor quota INTO
        # the engine — sheds then happen in the data plane
        storm = TenantRetryStorm(
            ports["linkerd"], "boom", "attacker", concurrency=8,
            retry_delay_s=0.005).start()

        def attacker_shed_natively() -> bool:
            tj = tenants_json().get("tnt", {})
            eng = (tj.get("engine") or {}).get("tenants") or {}
            by = eng.get("by_tenant") or {}
            atk = by.get(str(tenant_hash("attacker")), {})
            return int(atk.get("shed", 0)) > 0

        await wait_for(attacker_shed_natively, 45,
                       "native per-tenant shed (governor -> engine)")

        # victim rides through the live attack
        vic = PacedTenantClient(ports["linkerd"], "good", "victim",
                                rate_per_s=50)
        await vic.run(150)
        await storm.stop()
        assert vic.success_rate >= 0.99, \
            f"victim success {vic.success_rate}"

        # stats agreement: the metrics tree's per-tenant counters are
        # deltas of the same engine table /tenants.json snapshots
        await asyncio.sleep(2.5)  # two stats ticks settle the export
        tj = tenants_json()["tnt"]
        eng_by = tj["engine"]["tenants"]["by_tenant"]
        fp = metrics("rt/tnt/fastpath/tenant")
        vh = tenant_hash("victim")
        eng_vic = int(eng_by[str(vh)]["requests"])
        tree_vic = int(fp.get(
            f"rt/tnt/fastpath/tenant/{vh}/requests", 0))
        assert eng_vic > 0 and abs(tree_vic - eng_vic) <= 2, \
            f"tenant stats disagree: tree={tree_vic} engine={eng_vic}"
        guard = metrics("rt/tnt/fastpath/guard")
        shed_native = int(guard.get(
            "rt/tnt/fastpath/guard/tenant_shed", 0))
        assert shed_native > 0, "no native tenant sheds in metrics"
        quotas = tj.get("quotas") or {}
        assert quotas.get("sick"), "governor never marked the attacker"
        print("TENANT " + json.dumps({
            "attacker_shed_native": shed_native,
            "attacker_shed_fraction": round(storm.shed_fraction, 4),
            "victim_success_rate": round(vic.success_rate, 4),
            "victim_p99_ms": round(vic.p99_ms(), 2),
            "sick": quotas.get("sick"),
            "transitions": quotas.get("transitions"),
            "tenant_stats_agree": True,
        }))
    finally:
        if linkerd is not None:
            linkerd.send_signal(signal.SIGTERM)
            try:
                linkerd.wait(timeout=10)
            except subprocess.TimeoutExpired:
                linkerd.kill()
        d_good.close()
        d_boom.close()


async def validate_fleet() -> None:
    """Boot the REAL fleet — 3 linkerd binaries + 1 namerd binary
    (testing/fleet.py harness) — and assert quorum-gated coordination
    end to end: a fault visible to 1/3 instances shifts NOTHING; the
    same fault visible to 2/3 triggers exactly ONE fleet-wide dtab
    shift (peers adopt the published dentry, zero flaps); recovery
    reverts the namespace to exactly its base dtab. Prints one
    ``FLEET {json}`` line with the measured windows."""
    from linkerd_tpu.testing.fleet import FleetHarness, _http

    h = FleetHarness(n=3, quorum=2, warmup_batches=40)
    await h.start()
    try:
        h.start_traffic(interval_s=0.02)
        await h.warm(settle_s=3.0)
        print("validator[fleet]: 3 linkerds + namerd up, scorers warm")

        h.primary.fault_insts = {h.instance_ids[0]}
        await asyncio.sleep(6.0)
        pub = await h.fleet_metric_sum(
            "control/reactor/overrides_published")
        assert pub == 0, f"shifted on 1/3 evidence: {pub}"
        print("validator[fleet]: fault on 1/3 instances -> no shift")

        h.primary.fault_insts = {h.instance_ids[0], h.instance_ids[1]}
        publish_s = await h.wait_metric(
            "control/reactor/overrides_published", 1, 90)
        t0 = time.time()
        await h.wait_for(lambda: h._route_sync(2) == b"B", 20,
                         "fleet-wide shift")
        shift_s = publish_s + (time.time() - t0)
        assert await h.fleet_metric_sum(
            "control/reactor/overrides_published") == 1
        adopt_s = await h.wait_metric(
            "control/reactor/overrides_adopted", 1, 20)
        print(f"validator[fleet]: quorum fault -> ONE publish in "
              f"{publish_s:.2f}s, fleet-wide shift in {shift_s:.2f}s, "
              f"peer adoption in {adopt_s:.2f}s")

        h.primary.fault_insts = set()
        revert_s = await h.wait_metric(
            "control/reactor/overrides_reverted", 1, 90)
        await h.wait_for(lambda: h._route_sync(0) == b"A", 20,
                         "traffic back on the primary")
        assert await h.fleet_metric_sum(
            "control/reactor/overrides_published") == 1, "flapped!"

        def namespace_is_base() -> bool:
            _, body = _http("GET", h._namerd_url("/api/1/dtabs/default"))
            return json.loads(body) == [
                {"prefix": "/svc", "dst": "/#/io.l5d.fs"}]

        await h.wait_for(namespace_is_base, 10, "exact namespace revert")
        print(f"validator[fleet]: reverted exactly in {revert_s:.2f}s, "
              f"zero flaps")
        print("FLEET " + json.dumps({
            "publish_s": round(publish_s, 2),
            "shift_s": round(shift_s, 2),
            "revert_s": round(revert_s, 2),
            "publishes": 1,
        }))
    finally:
        await h.stop()


async def validate_regions() -> None:
    """Boot the REAL hierarchical fleet — 2 regions x 3 linkerd
    binaries + 1 namerd, east's store/digest traffic riding a WanProxy
    — and assert the partition-tolerance contract end to end:

    1. a region-quorum fault with the WAN up publishes exactly ONE
       cross-region failover dentry (east's traffic shifts to west's
       replica set) and reverts exactly once on recovery;
    2. the same fault with east's WAN CUT books a LOCAL override on
       region-local quorum (zero store writes) and east's traffic
       shifts to the local replica set while cut off;
    3. healing the WAN reconciles the book: the booked override is
       published to the store exactly once (adopt-if-present absorbs
       the second east instance), and recovery reverts it exactly
       once — zero flaps across the whole drill, exact namespace
       revert at the end.

    Prints one ``REGIONS {json}`` line with the measured windows."""
    from linkerd_tpu.testing.fleet import RegionFleetHarness, _http

    # stabilized governor values (measured in the flat fleet e2e): the
    # untrained scorer spikes past enter=0.5 during warm-up and drains
    # slowly after recovery — enter/exit at 0.6/0.45 with a 20-step
    # streak keeps both out of the governor
    h = RegionFleetHarness(east=2, west=1, warmup_batches=300,
                           governor_quorum=20, enter=0.6, exit=0.45)
    await h.start()
    try:
        h.start_traffic(interval_s=0.02)
        await h.warm(settle_s=3.0)
        east = [h.instance_ids[i] for i in h.region_insts("east")]
        print("validator[regions]: 2-region fleet up "
              f"(east={east}, west={h.instance_ids[h.east:]})")

        # -- 1. cross-region failover, WAN up ---------------------------
        h.primary.fault_insts = set(east)
        publish_s = await h.wait_metric(
            "control/reactor/overrides_published", 1, 90)
        t0 = time.time()
        await h.wait_for(lambda: h._route_sync(0) == b"W", 30,
                         "east traffic on west's replica set")
        shift_s = publish_s + (time.time() - t0)
        assert await h.fleet_metric_sum(
            "control/reactor/xregion_overrides") == 1, "not cross-region"
        assert await h.fleet_metric_sum(
            "control/reactor/overrides_published") == 1, "flapped!"
        print(f"validator[regions]: east quorum fault -> ONE "
              f"cross-region publish in {publish_s:.2f}s, east shifted "
              f"to west in {shift_s:.2f}s")

        h.primary.fault_insts = set()
        revert_s = await h.wait_metric(
            "control/reactor/overrides_reverted", 1, 90)
        await h.wait_for(lambda: h._route_sync(0) == b"A", 30,
                         "east traffic back on the primary")
        print(f"validator[regions]: recovery -> exact revert in "
              f"{revert_s:.2f}s")
        await asyncio.sleep(3.0)  # governor dwell drains before round 2

        # -- 2. same fault, WAN cut: local actuation continues ----------
        await h.partition_east()
        await asyncio.sleep(h.wan_ttl_s + 1.0)  # west digest goes stale
        h.primary.fault_insts = set(east)
        book_s = await h.wait_metric(
            "control/reactor/local_actuations", 1, 90)
        await h.wait_for(lambda: h._route_sync(0) == b"B", 30,
                         "east traffic on the LOCAL replica set")
        assert await h.fleet_metric_sum(
            "control/reactor/overrides_published") == 1, \
            "store write during partition"
        print(f"validator[regions]: WAN cut + quorum fault -> LOCAL "
              f"book in {book_s:.2f}s, east shifted locally, zero "
              f"store writes")

        # -- 3. heal: booked override publishes exactly once ------------
        await h.heal_east()
        heal_t0 = time.time()
        await h.wait_metric("control/reactor/heal_reconciles", 1, 60)
        await h.wait_metric("control/reactor/overrides_published", 2, 60)
        heal_s = time.time() - heal_t0
        assert await h.fleet_metric_sum(
            "control/reactor/overrides_published") == 2, "flapped!"
        print(f"validator[regions]: heal -> booked override published "
              f"exactly once in {heal_s:.2f}s")

        # adopters increment overrides_reverted too, so the wave-2
        # revert is a DELTA over whatever wave 1 left behind
        rev0 = await h.fleet_metric_sum(
            "control/reactor/overrides_reverted")
        h.primary.fault_insts = set()
        await h.wait_metric("control/reactor/overrides_reverted",
                            rev0 + 1, 90)
        await h.wait_for(lambda: h._route_sync(0) == b"A", 30,
                         "east traffic back on the primary")
        assert await h.fleet_metric_sum(
            "control/reactor/overrides_published") == 2, "flapped!"

        def namespace_is_base() -> bool:
            _, body = _http("GET", h._namerd_url("/api/1/dtabs/default"))
            return json.loads(body) == [
                {"prefix": "/svc", "dst": "/#/io.l5d.fs"}]

        await h.wait_for(namespace_is_base, 10, "exact namespace revert")
        flaps = await h.flap_count()
        assert flaps == 2, f"flap budget blown: {flaps} publishes != 2"
        print("validator[regions]: reverted exactly, 2 publishes "
              "across the whole drill (zero flaps)")
        print("REGIONS " + json.dumps({
            "xregion_publish_s": round(publish_s, 2),
            "xregion_shift_s": round(shift_s, 2),
            "revert_s": round(revert_s, 2),
            "local_book_s": round(book_s, 2),
            "heal_reconcile_s": round(heal_s, 2),
            "publishes": 2,
        }))
    finally:
        await h.stop()


async def validate_streams() -> None:
    """In-process e2e for the stream sentinel: an h2 server with the
    frame observer bound scores every stream mid-flight; ONE sick
    stream (oversized DATA frames) must be detected and RST'd with
    ENHANCE_YOUR_CALM while 10 healthy neighbors complete untouched
    (success >= 0.99), and an h1 Upgrade tunnel must relay bytes both
    ways through the front. Prints one ``STREAMS {json}`` line
    (bench.py folds it into detail.streaming)."""
    import itertools

    import numpy as np

    from linkerd_tpu.protocol.h2.client import H2Client
    from linkerd_tpu.protocol.h2.frames import ENHANCE_YOUR_CALM
    from linkerd_tpu.protocol.h2.messages import H2Request, H2Response
    from linkerd_tpu.protocol.h2.server import H2Server
    from linkerd_tpu.protocol.h2.stream import (DataFrame, H2Stream,
                                                StreamReset)
    from linkerd_tpu.protocol.http.client import HttpClient
    from linkerd_tpu.protocol.http.server import HttpServer
    from linkerd_tpu.router.service import FnService
    from linkerd_tpu.streams import H2FrameObserver, StreamSentinel

    sent = StreamSentinel(enter=0.7, exit=0.3, quorum=2, dwell_s=0.0)
    keys = itertools.count(1)
    big = np.log1p(10_000.0)  # x[8] = log1p(bytes/frame EWMA)

    def factory():
        return H2FrameObserver(
            sent, next_skey=lambda: next(keys),
            scorer=lambda x: 1.0 if x[8] > big else 0.0,
            sample_every_frames=2, min_gap_ms=0, action="rst")

    async def handler(req: H2Request) -> H2Response:
        body, _ = await req.stream.read_all()
        return H2Response(status=200, body=b"%d" % len(body))

    server = await H2Server(FnService(handler),
                            stream_observer_factory=factory).start()
    client = H2Client("127.0.0.1", server.bound_port)

    async def one(payload: bytes, frames: int) -> bool:
        src = H2Stream()
        task = asyncio.ensure_future(client(H2Request(
            method="POST", path="/s", authority="v", stream=src)))
        for _ in range(frames):
            src.offer(DataFrame(payload))
            await asyncio.sleep(0.001)
        src.offer(DataFrame(b"", eos=True))
        rsp = await task
        body, _ = await rsp.stream.read_all()
        return rsp.status == 200

    try:
        healthy = [one(b"x" * 64, 24) for _ in range(10)]
        t0 = time.time()
        sick = asyncio.ensure_future(one(b"y" * 60_000, 24))
        oks = await asyncio.gather(*healthy)
        try:
            await sick
            raise AssertionError("sick stream completed unshed")
        except StreamReset as e:
            assert e.error_code == ENHANCE_YOUR_CALM, hex(e.error_code)
            shed_ms = (time.time() - t0) * 1000.0
        success = sum(oks) / len(oks)
        assert success >= 0.99, f"neighbor success {success:.2f} < 0.99"
        assert sent.sick_transitions == 1, sent.sick_transitions
        snap = sent.snapshot()
        samples = sum(e["samples"] for e in snap["by_stream"].values())
        scored = sum(e["scored"] for e in snap["by_stream"].values())
        assert samples > 0 and scored == samples, \
            f"scored {scored}/{samples} stream samples"
        print(f"validator[streams]: sick stream shed in {shed_ms:.0f}ms "
              f"mid-flight, {len(oks)} neighbors all finished "
              f"({scored}/{samples} samples scored)")
    finally:
        await client.close()
        await server.close()

    # h1 Upgrade tunnel: the front must relay post-101 bytes both ways
    async def on_conn(reader, writer):
        data = b""
        while b"\r\n\r\n" not in data:
            chunk = await reader.read(1024)
            if not chunk:
                writer.close()
                return
            data += chunk
        writer.write(b"HTTP/1.1 101 Switching Protocols\r\n"
                     b"Upgrade: echo\r\nConnection: Upgrade\r\n\r\n")
        await writer.drain()
        got = 0
        while True:
            chunk = await reader.read(65536)
            if not chunk:
                break
            got += len(chunk)
            if got >= tunnel_bytes:
                writer.write(b"done")
                await writer.drain()
                break
        writer.close()

    tunnel_bytes = 4 * 1024 * 1024
    upstream = await asyncio.start_server(on_conn, "127.0.0.1", 0)
    up_port = upstream.sockets[0].getsockname()[1]
    h1_client = HttpClient("127.0.0.1", up_port)
    front = await HttpServer(h1_client).start()
    try:
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", front.bound_port)
        writer.write(b"GET /ws HTTP/1.1\r\nHost: x\r\n"
                     b"Connection: Upgrade\r\nUpgrade: echo\r\n\r\n")
        await writer.drain()
        head = b""
        while b"\r\n\r\n" not in head:
            head += await reader.read(1024)
        assert b"101" in head.split(b"\r\n")[0], head
        t0 = time.time()
        chunk = b"z" * 65536
        for _ in range(tunnel_bytes // len(chunk)):
            writer.write(chunk)
            await writer.drain()
        ack = await asyncio.wait_for(reader.read(16), 10)
        wall = time.time() - t0
        assert ack.startswith(b"done"), ack
        tunnel_mb_s = tunnel_bytes / wall / 1e6
        writer.close()
        print(f"validator[streams]: 101 tunnel relayed "
              f"{tunnel_bytes >> 20}MB at {tunnel_mb_s:.0f}MB/s")
    finally:
        await front.close()
        await h1_client.close()
        upstream.close()

    print("STREAMS " + json.dumps({
        "shed_ms": round(shed_ms, 1),
        "neighbor_success": success,
        "stream_samples_scored": scored,
        "tunnel_mb_s": round(tunnel_mb_s, 1),
    }))


async def validate_trace() -> None:
    """Boot the REAL linkerd binary as a two-router chain with a zipkin
    exporter, drive one traced request, assert the exported spans form
    one connected tree. Prints one ``TRACE {json}`` line."""
    ports = PORTS["trace"]
    work = tempfile.mkdtemp(prefix="l5d-validate-trace-")
    disco = os.path.join(work, "disco")
    os.makedirs(disco)
    d_a = await downstream("A", ports["a"])
    with open(os.path.join(disco, "web"), "w") as f:
        f.write(f"127.0.0.1 {ports['a']}\n")

    # stub zipkin collector: accept POST /api/v2/spans, remember spans
    spans = []

    async def on_conn(reader, writer):
        try:
            while True:
                head = await reader.readuntil(b"\r\n\r\n")
                clen = 0
                for line in head.split(b"\r\n"):
                    if line.lower().startswith(b"content-length:"):
                        clen = int(line.split(b":", 1)[1])
                body = await reader.readexactly(clen) if clen else b""
                if body:
                    spans.extend(json.loads(body))
                writer.write(b"HTTP/1.1 202 Accepted\r\n"
                             b"Content-Length: 0\r\n\r\n")
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionResetError):
            pass
        finally:
            writer.close()

    collector = await asyncio.start_server(
        on_conn, "127.0.0.1", ports["collector"])

    linkerd_yaml = os.path.join(work, "linkerd.yaml")
    with open(linkerd_yaml, "w") as f:
        f.write(f"""
routers:
- protocol: http
  label: edge
  sampleRate: 1.0
  dtab: |
    /svc => /$/inet/127.0.0.1/{ports['inner']} ;
  servers:
  - port: {ports['edge']}
- protocol: http
  label: inner
  sampleRate: 1.0
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers:
  - port: {ports['inner']}
namers:
- kind: io.l5d.fs
  rootDir: {disco}
telemetry:
- kind: io.l5d.zipkin
  port: {ports['collector']}
  batchIntervalMs: 200
admin:
  port: {ports['admin']}
""")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    linkerd = None
    try:
        linkerd = subprocess.Popen(
            [sys.executable, "-m", "linkerd_tpu", linkerd_yaml],
            env=env, cwd=work)
        await wait_for(lambda: http(
            "GET", f"http://127.0.0.1:{ports['edge']}/",
            headers={"Host": "web"})[2] == b"A", 20, "trace chain route")
        await wait_for(lambda: len(spans) >= 4, 10, "span export")

        # connected-tree assertion: one trace id; every parentId either
        # absent (the root) or another exported span's id
        trace_ids = {s["traceId"] for s in spans}
        assert len(trace_ids) == 1, f"expected 1 trace, got {trace_ids}"
        ids = {s["id"] for s in spans}
        roots = [s for s in spans if not s.get("parentId")]
        dangling = [s["id"] for s in spans
                    if s.get("parentId") and s["parentId"] not in ids]
        assert len(roots) == 1, f"expected 1 root span, got {len(roots)}"
        assert not dangling, f"spans with unexported parents: {dangling}"
        kinds = sorted((s.get("kind"),
                        s.get("localEndpoint", {}).get("serviceName"))
                       for s in spans)
        expected = sorted([
            ("SERVER", "edge"),
            ("CLIENT", f"$.inet.127.0.0.1.{ports['inner']}"),
            ("SERVER", "inner"),
            ("CLIENT", "#.io.l5d.fs.web"),
        ])
        assert kinds == expected, f"span set {kinds} != {expected}"
        # the edge server span carries the stage decomposition
        edge_srv = next(s for s in spans
                        if s["localEndpoint"]["serviceName"] == "edge")
        stage_tags = [k for k in edge_srv.get("tags", {})
                      if k.startswith("stage.")]
        assert stage_tags, "edge server span missing stage.* tags"
        print("TRACE " + json.dumps({
            "spans": len(spans),
            "connected_tree": True,
            "stage_tags": sorted(stage_tags),
        }))
    finally:
        if linkerd is not None:
            linkerd.send_signal(signal.SIGTERM)
            try:
                linkerd.wait(timeout=10)
            except subprocess.TimeoutExpired:
                linkerd.kill()
        collector.close()
        d_a.close()


def validate_checkpoints(dirs) -> int:
    """Verify each checkpoint store: per-file CRC + full decode, manifest
    agreement, lineage (parents known or recorded as pruned), orphaned
    files, and that the serving version actually loads. Exit 0 = healthy."""
    from linkerd_tpu.lifecycle import CheckpointError, CheckpointStore

    failed = 0
    for d in dirs:
        issues = []
        serving = None
        # a validator must never CREATE state: a mistyped path passing as
        # an empty healthy store would hide the real (corrupt) one
        if not os.path.isdir(d):
            issues = [f"store directory does not exist: {d}"]
        else:
            try:
                store = CheckpointStore(d)
                issues = store.verify()
                serving = store.latest_good()
                if serving is not None and not any(
                        "missing" in i or "CRC" in i for i in issues):
                    store.load(serving)  # rollback target must restore
            except CheckpointError as e:
                issues.append(f"store unreadable: {e}")
        if issues:
            failed += 1
            print(f"validator[ckpt]: {d} FAILED")
            for issue in issues:
                print(f"  - {issue}")
        else:
            n = len(store.versions())
            print(f"validator[ckpt]: {d} ok "
                  f"({n} versions, serving v{serving})")
    if failed:
        return 1
    print(f"VALIDATOR PASS (ckpt x{len(dirs)})")
    return 0


def default_config_fixtures() -> list:
    """Every YAML config the repo ships: test fixtures + examples."""
    import glob
    out = []
    for pattern in ("tests/configs/*.yml", "tests/configs/*.yaml",
                    "examples/*.yml", "examples/*.yaml"):
        out.extend(sorted(glob.glob(os.path.join(REPO, pattern))))
    return out


def validate_config(paths) -> int:
    """Run l5dcheck over linker/namerd YAML; exit 0 only when every
    config is clean (each finding fixed or justify-suppressed). Prints
    one ``CONFIGCHECK {json}`` line (bench.py folds it into
    detail.semantic_check)."""
    from tools.analysis.__main__ import main as analysis_main

    files = list(paths) or default_config_fixtures()
    if not files:
        print("validator[config]: no config fixtures found", file=sys.stderr)
        return 64
    t0 = time.perf_counter()
    rc = analysis_main(["check", *files])
    print("CONFIGCHECK " + json.dumps({
        "files": len(files),
        "wall_s": round(time.perf_counter() - t0, 3),
        "clean": rc == 0,
    }))
    if rc == 0:
        print(f"VALIDATOR PASS (config x{len(files)})")
    return rc


def validate_lint(paths) -> int:
    """Run the static-analysis suite; exit 0 only when the tree is
    clean (every finding fixed or carrying a justified suppression)."""
    from tools.analysis.__main__ import main as lint_main

    rc = lint_main(list(paths) or ["linkerd_tpu"])
    if rc == 0:
        print("VALIDATOR PASS (lint)")
    return rc


def validate_race(paths) -> int:
    """Run the race suite; exit 0 only when the data plane carries zero
    unsuppressed await-atomicity / lock-discipline findings."""
    from tools.analysis.__main__ import main as analysis_main

    rc = analysis_main(["race", *paths])
    if rc == 0:
        print("VALIDATOR PASS (race)")
    return rc


def validate_seam() -> int:
    """Run the cross-plane seam sweep; exit 0 only when the C++/Python
    boundary carries zero unsuppressed contract findings (ABI widths,
    mirrored constants, stats scrape map, knob plumbing)."""
    from tools.analysis.__main__ import main as analysis_main

    rc = analysis_main(["seam"])
    if rc == 0:
        print("VALIDATOR PASS (seam)")
    return rc


def validate_nat() -> int:
    """Run the native static sweep, then prove the analyzer still has
    teeth: plant a relaxed publish flip into a scratch copy of the
    scorer and require l5dnat to catch it. A sweep that passes because
    the rules rotted is worse than no sweep."""
    import shutil
    import tempfile

    from tools.analysis.__main__ import main as analysis_main
    from tools.analysis.native import run_native_analysis

    rc = analysis_main(["native"])
    if rc != 0:
        return rc
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory(prefix="l5dnat_smoke_") as tmp:
        shutil.copytree(os.path.join(repo, "native"),
                        os.path.join(tmp, "native"))
        scorer = os.path.join(tmp, "native", "scorer.h")
        with open(scorer, encoding="utf-8") as fh:
            text = fh.read()
        planted = "s->active.store(target, std::memory_order_release);"
        if planted not in text:
            print("validator[nat]: scorer.h publish flip not found — "
                  "update the smoke plant site", file=sys.stderr)
            return 1
        with open(scorer, "w", encoding="utf-8") as fh:
            fh.write(text.replace(
                planted,
                "s->active.store(target, std::memory_order_relaxed);"))
        caught = [f for f in run_native_analysis(repo_root=tmp)
                  if f.rule == "atomics-ordering" and not f.suppressed
                  and "active.store" in f.message]
        if not caught:
            print("validator[nat]: planted relaxed publish flip was "
                  "NOT caught — the atomics-ordering rule rotted",
                  file=sys.stderr)
            return 1
    print("VALIDATOR PASS (nat)")
    return 0


def validate_budget() -> int:
    """Three-legged budget gate. (1) static: the live tree must carry
    zero unsuppressed l5dbudget findings. (2) smoke: plant an
    undeclared syscall and a hot allocation into a scratch copy of the
    h1 loop and require the analyzer to catch both — a sweep that
    passes because the rules rotted is worse than no sweep. (3)
    measured: run BOTH assembled engines under closed-loop load with
    the LD_PRELOAD syscall counter and require syscalls-per-request
    inside the manifest's declared tolerance band."""
    import json
    import shutil
    import tempfile

    from tools.analysis.__main__ import main as analysis_main
    from tools.analysis.budget import run_budget_analysis

    rc = analysis_main(["budget"])
    if rc != 0:
        return rc

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory(prefix="l5dbudget_smoke_") as tmp:
        shutil.copytree(os.path.join(repo, "native"),
                        os.path.join(tmp, "native"))
        fp = os.path.join(tmp, "native", "fastpath.cpp")
        with open(fp, encoding="utf-8") as fh:
            text = fh.read()
        anchor = "e->now_cache_us = now_us();"
        if anchor not in text:
            print("validator[budget]: loop stamp anchor not found in "
                  "fastpath.cpp — update the smoke plant site",
                  file=sys.stderr)
            return 1
        with open(fp, "w", encoding="utf-8") as fh:
            fh.write(text.replace(
                anchor,
                anchor + " ::fcntl(0, 3);"
                " std::string planted_probe = \"x\";", 1))
        got = [f for f in run_budget_analysis(repo_root=tmp)
               if not f.suppressed]
        rules = {f.rule for f in got
                 if "fcntl" in f.message or "planted_probe" in f.message}
        if "syscall-budget" not in rules:
            print("validator[budget]: planted undeclared fcntl was NOT "
                  "caught — the syscall-budget rule rotted",
                  file=sys.stderr)
            return 1
        if "hot-alloc" not in rules:
            print("validator[budget]: planted hot allocation was NOT "
                  "caught — the hot-alloc rule rotted", file=sys.stderr)
            return 1

    from tools.syscall_budget import measure, reconcile
    for engine in ("h1", "h2"):
        m = measure(engine)
        if "error" in m:
            print(f"validator[budget]: {engine} measurement failed: "
                  f"{m['error']}", file=sys.stderr)
            return 1
        v = reconcile(engine, m)
        print(f"validator[budget]: {engine} measured "
              f"{v['measured_per_request']} syscalls/request, declared "
              f"{v['expect_per_request']} (band {v['band']}, "
              f"{v['reqs']} reqs)")
        if not v["ok"]:
            print(f"validator[budget]: {engine} measured rate is "
                  f"OUTSIDE the declared band: {json.dumps(v)}",
                  file=sys.stderr)
            return 1
    print("VALIDATOR PASS (budget)")
    return 0


async def main() -> int:
    args = sys.argv[1:]
    if args and args[0] == "lint":
        return validate_lint(args[1:])
    if args and args[0] == "race":
        return validate_race(args[1:])
    if args and args[0] == "seam":
        if len(args) > 1:
            print("validator[seam]: the seam sweep takes no paths (the "
                  "contract is whole-seam)", file=sys.stderr)
            return 64
        return validate_seam()
    if args and args[0] == "nat":
        if len(args) > 1:
            print("validator[nat]: the native sweep takes no paths "
                  "(ownership and ordering are whole-tree)",
                  file=sys.stderr)
            return 64
        return validate_nat()
    if args and args[0] == "budget":
        if len(args) > 1:
            print("validator[budget]: the budget sweep takes no paths "
                  "(the cost envelope is whole-tree)", file=sys.stderr)
            return 64
        return validate_budget()
    if args and args[0] == "config":
        return validate_config(args[1:])
    if args and args[0] == "ckpt":
        if len(args) < 2:
            print("usage: python tools/validator.py ckpt <store-dir>...",
                  file=sys.stderr)
            return 64
        return validate_checkpoints(args[1:])
    if args and args[0] == "chaos":
        await validate_chaos()
        print("VALIDATOR PASS (chaos)")
        return 0
    if args and args[0] == "control":
        await validate_control()
        print("VALIDATOR PASS (control)")
        return 0
    if args and args[0] == "trace":
        await validate_trace()
        print("VALIDATOR PASS (trace)")
        return 0
    if args and args[0] == "scorer-latency":
        await validate_scorer_latency()
        print("VALIDATOR PASS (scorer-latency)")
        return 0
    if args and args[0] == "tls":
        await validate_tls()
        print("VALIDATOR PASS (tls)")
        return 0
    if args and args[0] == "native-score":
        await validate_native_score()
        print("VALIDATOR PASS (native-score)")
        return 0
    if args and args[0] == "tenant":
        await validate_tenant()
        print("VALIDATOR PASS (tenant)")
        return 0
    if args and args[0] == "cores":
        await validate_cores()
        print("VALIDATOR PASS (cores)")
        return 0
    if args and args[0] == "fleet":
        await validate_fleet()
        print("VALIDATOR PASS (fleet)")
        return 0
    if args and args[0] == "regions":
        await validate_regions()
        print("VALIDATOR PASS (regions)")
        return 0
    if args and args[0] == "streams":
        await validate_streams()
        print("VALIDATOR PASS (streams)")
        return 0
    protocols = args or ["mesh", "thrift", "http"]
    for protocol in protocols:
        await validate(protocol)
    print(f"VALIDATOR PASS ({', '.join(protocols)})")
    return 0


if __name__ == "__main__":
    raise SystemExit(asyncio.run(main()))
