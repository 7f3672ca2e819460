"""Fleet harness: N REAL linkerd subprocesses + one namerd as a mesh.

The one test topology everything fleet-related (tests/test_fleet.py,
``tools/validator.py fleet``, bench) drives:

- one namerd (assembled binary, ``python -m linkerd_tpu.namerd``): fs
  dtab storage, fs service discovery, the HTTP control API;
- N linkerds (assembled binaries, ``python -m linkerd_tpu``): http
  routers bound through that namerd, each with the jaxAnomaly telemeter
  + ``control.fleet`` block — distinct instance ids, admin ports as
  gossip peers, shared failover config;
- two downstream clusters: ``web`` (primary, faultable) and ``web-b``
  (failover). The fault is *per-instance-visible*: requests carry an
  ``l5d-fleet-inst`` header naming which linkerd the harness drove them
  through, and the primary cluster faults (500 + latency) only the
  instances in ``fault_insts`` — so "a fault observed by 2 of 3
  instances" is literally that.

All blocking admin/API probes run in worker threads so the in-process
downstream servers (this event loop) keep serving while the harness
waits.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Sequence, Set

from linkerd_tpu.testing.faults import LoopbackServer

log = logging.getLogger(__name__)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

FAULT_HEADER = "l5d-fleet-inst"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(method: str, url: str, body: bytes = b"",
          headers: Optional[dict] = None, timeout: float = 10.0) -> tuple:
    req = urllib.request.Request(url, data=body or None, method=method,
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as rsp:
            return rsp.status, rsp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


class FaultableCluster(LoopbackServer):
    """An HTTP downstream whose responses fault (500 + added latency)
    for requests tagged with an instance id in ``fault_insts``."""

    def __init__(self, name: str, fault_delay_s: float = 0.12):
        super().__init__()
        self.name = name
        self.fault_insts: Set[str] = set()
        self.fault_delay_s = fault_delay_s
        self.requests = 0

    @property
    def port(self) -> int:
        return self.bound_port

    async def handle(self, reader, writer) -> None:
        while True:
            head = await reader.readuntil(b"\r\n\r\n")
            self.requests += 1
            inst = ""
            for line in head.split(b"\r\n")[1:]:
                k, _, v = line.partition(b":")
                if k.strip().lower() == FAULT_HEADER.encode():
                    inst = v.strip().decode("latin-1")
            if inst and inst in self.fault_insts:
                await asyncio.sleep(self.fault_delay_s)
                body = b"fault"
                status = b"500 Internal Server Error"
            else:
                body = self.name.encode()
                status = b"200 OK"
            writer.write(
                b"HTTP/1.1 " + status + b"\r\nContent-Length: "
                + str(len(body)).encode() + b"\r\n\r\n" + body)
            await writer.drain()


class FleetHarness:
    """See module docstring. Use as::

        h = FleetHarness(n=3, quorum=2)
        await h.start()
        try:
            await h.warm(requests_per_instance=150)
            h.primary.fault_insts = {h.instance_ids[0]}
            ...
        finally:
            await h.stop()
    """

    def __init__(self, n: int = 3, quorum: int = 2,
                 gossip: bool = True,
                 publish_interval_s: float = 0.5,
                 gossip_interval_ms: int = 100,
                 staleness_ttl_s: float = 5.0,
                 warmup_batches: int = 30,
                 governor_quorum: int = 4,
                 cooldown_s: float = 1.0,
                 enter: float = 0.5, exit: float = 0.2,
                 generation: int = 1,
                 workdir: Optional[str] = None):
        self.n = n
        self.quorum = quorum
        self.gossip = gossip
        self.publish_interval_s = publish_interval_s
        self.gossip_interval_ms = gossip_interval_ms
        self.staleness_ttl_s = staleness_ttl_s
        self.warmup_batches = warmup_batches
        self.governor_quorum = governor_quorum
        self.cooldown_s = cooldown_s
        self.enter = enter
        self.exit = exit
        self.generation = generation
        self.work = workdir or tempfile.mkdtemp(prefix="l5d-fleet-")
        self.instance_ids = [f"l5d-{i}" for i in range(n)]
        self.namerd_port = free_port()
        self.router_ports = [free_port() for _ in range(n)]
        self.admin_ports = [free_port() for _ in range(n)]
        self.primary = FaultableCluster("A")
        self.failover = FaultableCluster("B")
        self.procs: List[subprocess.Popen] = []
        self._traffic: List[asyncio.Task] = []
        self._env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")

    # -- config materialization -------------------------------------------
    def linkerd_yaml(self, i: int) -> str:
        peers = [f"127.0.0.1:{p}" for j, p in enumerate(self.admin_ports)
                 if j != i]
        peers_yaml = "".join(f"\n        - {p}" for p in peers)
        return f"""
routers:
- protocol: http
  label: fleet{i}
  interpreter:
    kind: io.l5d.namerd.http
    dst: /$/inet/127.0.0.1/{self.namerd_port}
    namespace: default
  servers:
  - port: {self.router_ports[i]}
telemetry:
- kind: io.l5d.jaxAnomaly
  maxLingerMs: 2
  scoreTtlSecs: 30
  control:
    intervalMs: 50
    warmupBatches: {self.warmup_batches}
    enterThreshold: {self.enter}
    exitThreshold: {self.exit}
    quorum: {self.governor_quorum}
    cooldownS: {self.cooldown_s}
    namespace: default
    namerdAddress: 127.0.0.1:{self.namerd_port}
    failover:
      /svc/web: /svc/web-b
    fleet:
      instance: {self.instance_ids[i]}
      generation: {self.generation}
      quorum: {self.quorum}
      expectInstances: {self.n}
      namespace: fleet
      publishIntervalS: {self.publish_interval_s}
      stalenessTtlS: {self.staleness_ttl_s}
      gossip: {str(self.gossip).lower()}
      gossipIntervalMs: {self.gossip_interval_ms}
      peers:{peers_yaml if peers else " []"}
admin:
  port: {self.admin_ports[i]}
"""

    def namerd_yaml(self) -> str:
        return f"""
storage:
  kind: io.l5d.fs
  directory: {os.path.join(self.work, "dtabs")}
namers:
- kind: io.l5d.fs
  rootDir: {os.path.join(self.work, "disco")}
interfaces:
- kind: io.l5d.httpController
  port: {self.namerd_port}
"""

    # -- lifecycle ---------------------------------------------------------
    async def start(self, route_timeout_s: float = 90.0) -> "FleetHarness":
        await self.primary.start()
        await self.failover.start()
        disco = os.path.join(self.work, "disco")
        os.makedirs(disco, exist_ok=True)

        def materialize() -> None:
            with open(os.path.join(disco, "web"), "w") as f:
                f.write(f"127.0.0.1 {self.primary.port}\n")
            with open(os.path.join(disco, "web-b"), "w") as f:
                f.write(f"127.0.0.1 {self.failover.port}\n")
            with open(os.path.join(self.work, "namerd.yaml"), "w") as f:
                f.write(self.namerd_yaml())
            for i in range(self.n):
                with open(os.path.join(self.work, f"linkerd{i}.yaml"),
                          "w") as f:
                    f.write(self.linkerd_yaml(i))

        await asyncio.to_thread(materialize)
        self.procs.append(subprocess.Popen(
            [sys.executable, "-m", "linkerd_tpu.namerd",
             os.path.join(self.work, "namerd.yaml")],
            env=self._env, cwd=self.work))
        await self.wait_for(
            lambda: _http("GET", self._namerd_url("/api/1/dtabs")
                          )[0] == 200,
            30.0, "namerd http controller")
        st, _ = await asyncio.to_thread(
            _http, "POST", self._namerd_url("/api/1/dtabs/default"),
            b"/svc => /#/io.l5d.fs;")
        if st != 204:
            raise AssertionError(f"dtab create failed: {st}")
        for i in range(self.n):
            self.procs.append(subprocess.Popen(
                [sys.executable, "-m", "linkerd_tpu",
                 os.path.join(self.work, f"linkerd{i}.yaml")],
                env=self._env, cwd=self.work))
        # every instance must route to the primary before the harness
        # hands control to the scenario
        for i in range(self.n):
            await self.wait_for(
                lambda i=i: self._route_sync(i) == b"A",
                route_timeout_s, f"linkerd {i} routes to A")
        return self

    async def stop(self) -> None:
        await self.stop_traffic()
        for p in self.procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in self.procs:
            try:
                await asyncio.to_thread(p.wait, 10)
            except subprocess.TimeoutExpired:
                p.kill()
        self.procs.clear()
        await self.primary.close()
        await self.failover.close()

    # -- traffic -----------------------------------------------------------
    def _namerd_url(self, path: str) -> str:
        return f"http://127.0.0.1:{self.namerd_port}{path}"

    def _route_sync(self, i: int) -> bytes:
        _, body = _http(
            "GET", f"http://127.0.0.1:{self.router_ports[i]}/",
            headers={"Host": "web", FAULT_HEADER: self.instance_ids[i]},
            timeout=5.0)
        return body

    async def route(self, i: int) -> bytes:
        """One request through linkerd ``i``, tagged with its instance
        id so cluster faults are per-instance-visible."""
        return await asyncio.to_thread(self._route_sync, i)

    async def drive(self, insts: Optional[Sequence[int]] = None,
                    requests_each: int = 20,
                    interval_s: float = 0.01) -> Dict[int, int]:
        """Paced traffic through the given instances, each at its OWN
        independent pace (one slow/faulted instance must not modulate
        the request rate the others observe — the scorers treat a rate
        shift as an anomaly, which would fake fleet-wide evidence).
        Returns per-instance 200-response counts; faulted responses
        still flow — features must keep moving for scores to move."""
        insts = list(range(self.n)) if insts is None else list(insts)

        async def one_instance(i: int) -> int:
            ok = 0
            for _ in range(requests_each):
                try:
                    if await self.route(i) in (b"A", b"B"):
                        ok += 1
                except Exception:  # noqa: BLE001 — faulted/resetting
                    pass           # responses still moved features
                await asyncio.sleep(interval_s)
            return ok

        counts = await asyncio.gather(*(one_instance(i) for i in insts))
        return dict(zip(insts, counts))

    def start_traffic(self, interval_s: float = 0.02) -> None:
        """Continuous fixed-pace traffic through every instance until
        ``stop_traffic`` — the steady carrier wave fault scenarios ride
        on (constant per-instance rate, so only the injected fault — not
        the harness's own probing cadence — moves any score)."""
        async def pump(i: int) -> None:
            while True:
                try:
                    await self.route(i)
                except asyncio.CancelledError:
                    raise
                except Exception:  # noqa: BLE001 — keep pumping through
                    pass           # faults and process restarts
                await asyncio.sleep(interval_s)

        loop = asyncio.get_running_loop()
        self._traffic = [loop.create_task(pump(i), name=f"fleet-pump-{i}")
                         for i in range(self.n)]

    async def stop_traffic(self) -> None:
        for t in self._traffic:
            t.cancel()
        if self._traffic:
            await asyncio.gather(*self._traffic, return_exceptions=True)
        self._traffic = []

    async def warm(self, settle_s: float = 2.0,
                   timeout_s: float = 60.0) -> None:
        """Wait (under ``start_traffic``) until every instance's control
        loop reports warmed_up, then ``settle_s`` more so the online
        models converge on 'normal' before any fault is injected."""
        for i in range(self.n):
            await self.wait_for(
                lambda i=i: self._flat_sync(i).get(
                    "control/warmed_up", 0.0) >= 1.0,
                timeout_s, f"instance {i} control warmup")
        await asyncio.sleep(settle_s)

    # -- observation -------------------------------------------------------
    async def admin_json(self, i: int, path: str) -> dict:
        def get() -> dict:
            _, body = _http(
                "GET", f"http://127.0.0.1:{self.admin_ports[i]}{path}")
            return json.loads(body)
        return await asyncio.to_thread(get)

    def _flat_sync(self, i: int) -> dict:
        _, body = _http(
            "GET", f"http://127.0.0.1:{self.admin_ports[i]}"
                   f"/admin/metrics.json?q=control")
        return json.loads(body)

    async def metric(self, i: int, name: str) -> float:
        flat = await asyncio.to_thread(self._flat_sync, i)
        return float(flat.get(name, 0.0))

    async def fleet_metric_sum(self, name: str) -> float:
        vals = await asyncio.gather(
            *(self.metric(i, name) for i in range(self.n)))
        return float(sum(vals))

    async def wait_for(self, predicate, timeout_s: float,
                       what: str) -> None:
        """Polls in a worker thread so the in-process downstream
        clusters (this loop) keep serving meanwhile."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                if await asyncio.to_thread(predicate):
                    return
            except Exception:  # noqa: BLE001 — probes fail while procs
                # boot; only the deadline turns that into a failure
                await asyncio.sleep(0)
            await asyncio.sleep(0.2)
        raise AssertionError(f"timed out waiting for {what}")

    async def wait_metric(self, name: str, want: float,
                          timeout_s: float) -> float:
        """Wait until the fleet-wide SUM of a control metric reaches
        ``want`` (run under ``start_traffic`` — scores only move while
        features flow). Returns the elapsed seconds."""
        t0 = time.monotonic()
        deadline = t0 + timeout_s
        while time.monotonic() < deadline:
            if await self.fleet_metric_sum(name) >= want:
                return time.monotonic() - t0
            await asyncio.sleep(0.1)
        raise AssertionError(
            f"timed out waiting for fleet {name} >= {want}")


# ---- hierarchical (2-region) topology --------------------------------------


class WanProxy:
    """A TCP forwarder standing in for one region's WAN uplink to the
    control plane. ``partition()`` closes the listener AND severs every
    established flow (in-flight watch streams die, new connects are
    refused — exactly what a cut link looks like to the far side);
    ``heal()`` re-listens on the same port."""

    def __init__(self, target_port: int):
        self.target_port = target_port
        self.port = free_port()
        self.partitioned = False
        self._server: Optional[asyncio.AbstractServer] = None
        self._pipes: Set[asyncio.Task] = set()

    async def start(self) -> "WanProxy":
        self._server = await asyncio.start_server(
            self._on_conn, "127.0.0.1", self.port)
        return self

    async def _on_conn(self, reader, writer) -> None:
        if self.partitioned:
            writer.close()
            return
        try:
            up_reader, up_writer = await asyncio.open_connection(
                "127.0.0.1", self.target_port)
        except OSError:
            writer.close()
            return
        if self.partitioned:  # cut while the uplink was connecting
            writer.close()
            up_writer.close()
            return

        async def pipe(rd, wr) -> None:
            try:
                while True:
                    data = await rd.read(65536)
                    if not data:
                        break
                    wr.write(data)
                    await wr.drain()
            except (OSError, asyncio.CancelledError):
                pass
            finally:
                try:
                    wr.close()
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass

        loop = asyncio.get_running_loop()
        for rd, wr in ((reader, up_writer), (up_reader, writer)):
            t = loop.create_task(pipe(rd, wr))
            self._pipes.add(t)
            t.add_done_callback(self._pipes.discard)

    async def partition(self) -> None:
        self.partitioned = True
        if self._server is not None:
            self._server.close()
        # drop the flows BEFORE waiting: since Python 3.12 wait_closed()
        # waits for every accepted connection, and the pipes hold them
        for t in list(self._pipes):
            t.cancel()
        if self._pipes:
            await asyncio.gather(*self._pipes, return_exceptions=True)
        self._pipes.clear()
        if self._server is not None:
            await self._server.wait_closed()
            self._server = None

    async def heal(self) -> None:
        self.partitioned = False
        if self._server is None:
            self._server = await asyncio.start_server(
                self._on_conn, "127.0.0.1", self.port)

    async def close(self) -> None:
        await self.partition()
        self.partitioned = False


class RegionFleetHarness(FleetHarness):
    """Two-region fleet on real binaries: ``east`` = instances 0..k-1
    behind one WanProxy to namerd (their WAN uplink — interpreter,
    store client, and fleet watch all ride it), ``west`` = the rest,
    plus namerd itself, reached directly. Three downstream clusters:

    - ``web``     — the primary every instance routes to (faultable);
    - ``web-b``   — the LOCAL failover replica set;
    - ``web-west``— west's replica set, east's cross-region target
      (and symmetrically, ``web-b`` is west's cross-region target in
      east).

    Gossip peers never cross the region boundary; cross-region evidence
    moves ONLY through region digests in the namerd ``fleet``
    namespace, so cutting the WanProxy is a true WAN partition: east
    keeps its intra-region quorum (gossip) and loses the store, the
    digests, and nothing else."""

    def __init__(self, east: int = 2, west: int = 1,
                 wan_ttl_s: float = 3.0,
                 digest_interval_s: float = 0.5,
                 store_timeout_ms: int = 800,
                 **kw):
        kw.setdefault("quorum", 2)
        super().__init__(n=east + west, **kw)
        self.east = east
        self.west = west
        self.wan_ttl_s = wan_ttl_s
        self.digest_interval_s = digest_interval_s
        self.store_timeout_ms = store_timeout_ms
        self.west_cluster = FaultableCluster("W")
        self.wan = WanProxy(self.namerd_port)

    # -- topology ----------------------------------------------------------
    def region_of(self, i: int) -> str:
        return "east" if i < self.east else "west"

    def region_insts(self, region: str) -> List[int]:
        return [i for i in range(self.n) if self.region_of(i) == region]

    def _region_quorum(self, region: str) -> int:
        # intra-region quorum = majority of the region's instances
        return len(self.region_insts(region)) // 2 + 1

    def _namerd_port_for(self, i: int) -> int:
        return self.wan.port if self.region_of(i) == "east" \
            else self.namerd_port

    def linkerd_yaml(self, i: int) -> str:
        region = self.region_of(i)
        peers = [f"127.0.0.1:{self.admin_ports[j]}"
                 for j in self.region_insts(region) if j != i]
        peers_yaml = "".join(f"\n        - {p}" for p in peers)
        xtarget = ("/svc/web-west" if region == "east" else "/svc/web-b")
        xregion = "west" if region == "east" else "east"
        namerd = self._namerd_port_for(i)
        return f"""
routers:
- protocol: http
  label: fleet{i}
  interpreter:
    kind: io.l5d.namerd.http
    dst: /$/inet/127.0.0.1/{namerd}
    namespace: default
  servers:
  - port: {self.router_ports[i]}
telemetry:
- kind: io.l5d.jaxAnomaly
  maxLingerMs: 2
  scoreTtlSecs: 30
  control:
    intervalMs: 50
    warmupBatches: {self.warmup_batches}
    enterThreshold: {self.enter}
    exitThreshold: {self.exit}
    quorum: {self.governor_quorum}
    cooldownS: {self.cooldown_s}
    namespace: default
    namerdAddress: 127.0.0.1:{namerd}
    storeTimeoutMs: {self.store_timeout_ms}
    failover:
      /svc/web: /svc/web-b
    regionFailover:
      /svc/web:
        {xregion}: {xtarget}
    fleet:
      instance: {self.instance_ids[i]}
      generation: {self.generation}
      quorum: {self._region_quorum(region)}
      expectInstances: {self.n}
      namespace: fleet
      publishIntervalS: {self.publish_interval_s}
      stalenessTtlS: {self.staleness_ttl_s}
      gossip: {str(self.gossip).lower()}
      gossipIntervalMs: {self.gossip_interval_ms}
      region: {region}
      wanTtlS: {self.wan_ttl_s}
      digestIntervalS: {self.digest_interval_s}
      peers:{peers_yaml if peers else " []"}
admin:
  port: {self.admin_ports[i]}
"""

    # -- lifecycle ---------------------------------------------------------
    async def start(self, route_timeout_s: float = 90.0
                    ) -> "RegionFleetHarness":
        await self.west_cluster.start()
        await self.wan.start()

        # the base start() materializes disco/web + disco/web-b; west's
        # replica set must exist before any linkerd binds it
        disco = os.path.join(self.work, "disco")
        os.makedirs(disco, exist_ok=True)

        def write_west() -> None:
            with open(os.path.join(disco, "web-west"), "w") as f:
                f.write(f"127.0.0.1 {self.west_cluster.port}\n")

        await asyncio.to_thread(write_west)
        await super().start(route_timeout_s=route_timeout_s)
        return self

    async def stop(self) -> None:
        await super().stop()
        await self.west_cluster.close()
        await self.wan.close()

    # -- scenario controls -------------------------------------------------
    async def partition_east(self) -> None:
        """Cut east's WAN uplink: east loses namerd (store, digests,
        new binds); east's intra-region gossip and its already-bound
        routes keep working."""
        await self.wan.partition()

    async def heal_east(self) -> None:
        await self.wan.heal()

    async def region_status(self, i: int) -> dict:
        return await self.admin_json(i, "/regions.json")

    async def flap_count(self) -> float:
        """Fleet-wide override PUBLISHES — the flap budget a scenario
        asserts against (each injected wave should cost exactly one).
        Reverts are deliberately not counted: every adopter increments
        ``overrides_reverted`` on recovery even though only the first
        revert writes the namespace, so publish count is the honest
        measure of namespace churn."""
        return await self.fleet_metric_sum(
            "control/reactor/overrides_published")
