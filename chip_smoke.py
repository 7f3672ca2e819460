#!/usr/bin/env python3
"""chip_smoke.py — prove the accelerator tier starts and serves on the chip.

Drives the scorer path once, through the entry points a user calls, at the
full width of the one model the repo supports (default
``AnomalyModelConfig``; weights random from a seed), and fails unless it
ran on a TPU:

  probe    a child asks JAX what it sees; anything but ``tpu`` ends the run
  build    ``native/build.py`` rebuilds libl5d_native.so from the tracked
           sources (a copied tree can carry a stale one), then h2bench
  server   ``python -m linkerd_tpu <cfg>`` at the default tiers: one
           Python-plane http router, one ``fastPath: true`` router, an fs
           namer, ``io.l5d.jaxAnomaly`` at its defaults; Host-routed
           traffic through both while polling the admin port. Run twice:
           the second boot must add no compile-cache entries for shapes
           the first already compiled
  linerate the same boot with ``nativeTier: off`` and h2bench load, so
           engine rows arrive unscored and full 1024-row buckets go
           through the donated RingDispatcher
  kernel   the fused Pallas kernel (``interpret=False``) against the
           float32 reference at 256/1024/4096/300 rows, then one
           ``InProcessScorer.warmup()``

A chip belongs to one process at a time, so this parent never imports
jax: every leg that touches the device is a child, and children run one
after another. Wall times printed here are smoke timings, not
measurements. Exit 0 and a last stdout line
``{"ok": true, "device": {...}}`` only if every leg passed; logs land in
``chiprun_out/smoke/``.
"""

from __future__ import annotations

import asyncio
import http.client
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "smoke")
PLATFORM = "tpu"          # the only platform on which this smoke passes
SEED = 0
BUDGET_S = 1100.0         # the whole run, compilation included
PROBE_TIMEOUT_S = 150.0   # libtpu's probe on a chipless box can be slow
KERNEL_ROWS = (256, 1024, 4096, 300)
KERNEL_ATOL = 2e-2
_T0 = time.monotonic()
_LIVE: list = []          # every Popen this script started


class LegFailed(Exception):
    pass


def say(msg: str) -> None:
    print(f"[smoke {time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


def remaining(cap: float) -> float:
    left = BUDGET_S - (time.monotonic() - _T0)
    if left <= 5:
        raise LegFailed("smoke budget spent")
    return min(cap, left)


def tail(path: str, n: int = 30) -> str:
    try:
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


# -- children ----------------------------------------------------------------


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    return env


def spawn(argv: list, log_path: str, cwd: str = HERE) -> subprocess.Popen:
    log = open(log_path, "wb")
    try:
        proc = subprocess.Popen(argv, cwd=cwd, env=child_env(), stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    finally:
        log.close()
    _LIVE.append(proc)
    return proc


def stop(proc: subprocess.Popen, grace_s: float = 15.0) -> int:
    """SIGTERM, wait, then kill the whole session; returns the exit code."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(grace_s)
        except subprocess.TimeoutExpired:
            pass
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            proc.kill()
        proc.wait(10)
    return proc.returncode


def stop_all() -> None:
    for proc in _LIVE:
        if proc.poll() is None:
            stop(proc, grace_s=2.0)


def run_child(name: str, argv: list, timeout_s: float,
              cwd: str = HERE) -> str:
    """Run one child to completion; returns its output. Non-zero exit or
    timeout fails the leg."""
    log_path = os.path.join(OUT, f"{name}.log")
    proc = spawn(argv, log_path, cwd=cwd)
    try:
        rc = proc.wait(remaining(timeout_s))
    except subprocess.TimeoutExpired:
        stop(proc, grace_s=2.0)
        raise LegFailed(f"{name}: timed out after {timeout_s:.0f}s\n"
                        + tail(log_path))
    if rc != 0:
        raise LegFailed(f"{name}: exit code {rc}\n" + tail(log_path))
    with open(log_path, "r", encoding="utf-8", errors="replace") as f:
        return f.read()


def last_json(text: str) -> dict:
    for line in reversed(text.splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise LegFailed("child printed no JSON line:\n" + text[-2000:])


# -- legs that run in a child of this file -----------------------------------


def _device_json() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def leg_probe() -> int:
    from linkerd_tpu.compile_cache import place_compile_cache
    place_compile_cache()
    import jax
    import jaxlib
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    print(json.dumps({"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                      "libtpu": libtpu_version, "device": _device_json()}))
    return 0


def leg_kernel() -> int:
    from linkerd_tpu.compile_cache import place_compile_cache
    place_compile_cache()
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from linkerd_tpu.models.anomaly import (
        AnomalyModelConfig, anomaly_scores, init_params,
    )
    from linkerd_tpu.ops.scoring import fused_anomaly_scores
    from linkerd_tpu.telemetry.anomaly import InProcessScorer

    out: dict = {"device": _device_json(), "rows": {}}
    dev = jax.devices()[0]
    if dev.platform != PLATFORM:
        print(f"kernel leg: platform is {dev.platform!r}, not {PLATFORM!r}")
        return 1
    cfg = AnomalyModelConfig()
    f32 = dataclasses.replace(cfg, compute_dtype=jnp.float32)
    params = jax.device_put(init_params(jax.random.key(SEED), cfg), dev)
    fused = jax.jit(lambda p, v: fused_anomaly_scores(p, v, cfg))
    reference = jax.jit(lambda p, v: anomaly_scores(p, v, f32))
    ok = True
    for rows in KERNEL_ROWS:
        x = jax.device_put(
            jax.random.normal(jax.random.key(rows), (rows, cfg.in_dim),
                              jnp.float32), dev)
        got = np.asarray(fused(params, x))
        # true float32 matmuls: the TPU default rounds f32 operands to bf16
        with jax.default_matmul_precision("float32"):
            want = np.asarray(reference(params, x))
        err = float(np.max(np.abs(got - want)))
        good = (got.shape == (rows,) and bool(np.isfinite(got).all())
                and err <= KERNEL_ATOL)
        out["rows"][str(rows)] = {"max_abs_err": round(err, 6), "ok": good}
        ok = ok and good
    if len(jax.devices()) > 1:
        # the serving default on this host is the mesh path: its batch
        # shards must land on as many distinct devices as the mesh has
        from linkerd_tpu.parallel.mesh import make_mesh, shard_batch
        mesh = make_mesh()
        xd = shard_batch(mesh, np.zeros(
            (8 * mesh.shape["data"], cfg.in_dim), np.float32))
        placed = len({s.device for s in xd.addressable_shards})
        out["mesh"] = {"shape": dict(mesh.shape), "shard_devices": placed}
        ok = ok and placed == mesh.devices.size
    # score, fit, snapshot -> restore -> score, pinned to the one device
    scorer = InProcessScorer(seed=SEED, devices=[dev])
    try:
        asyncio.run(scorer.warmup())
        out["warmup"] = scorer.device_state()
    finally:
        scorer.close()
    out["ok"] = ok
    print(json.dumps(out))
    return 0 if ok else 1


# -- the echo backend and the HTTP driver (parent, stdlib only) --------------


class EchoBackend:
    """HTTP/1.1 keep-alive backend on a thread of its own: answers every
    request head with a two-byte 200."""

    RESPONSE = b"HTTP/1.1 200 OK\r\nContent-Length: 2\r\n\r\nok"

    def __init__(self) -> None:
        self.loop = asyncio.new_event_loop()
        self.port = 0
        self._server = None
        self._thread = threading.Thread(target=self.loop.run_forever,
                                        name="smoke-backend", daemon=True)

    async def _handle(self, reader, writer) -> None:
        try:
            while True:
                await reader.readuntil(b"\r\n\r\n")
                writer.write(self.RESPONSE)
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError,
                asyncio.LimitOverrunError):
            pass
        finally:
            writer.close()

    def start(self) -> "EchoBackend":
        self._thread.start()

        async def up():
            self._server = await asyncio.start_server(
                self._handle, "127.0.0.1", 0, backlog=512)
            return self._server.sockets[0].getsockname()[1]

        self.port = asyncio.run_coroutine_threadsafe(
            up(), self.loop).result(10)
        return self

    def close(self) -> None:
        async def down():
            self._server.close()
            await self._server.wait_closed()

        if self._server is not None:
            try:
                asyncio.run_coroutine_threadsafe(
                    down(), self.loop).result(5)
            except TimeoutError:
                pass  # a daemon thread; the sockets die with the process
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(5)


class Client:
    """One keep-alive connection to a router. Every request carries an
    explicit Host header (a request without one routes nowhere and only
    looks healthy); ``answered`` counts responses of any status, which is
    what the telemeter's requests_total must match."""

    def __init__(self, port: int, host_header: str = "web") -> None:
        self.port = port
        self.host_header = host_header
        self.answered = 0
        self.ok = 0
        self._conn = None

    def get(self) -> int:
        """-> status, or 0 when the connection failed before a reply."""
        try:
            if self._conn is None:
                self._conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=10)
            self._conn.request("GET", "/", headers={"Host": self.host_header})
            rsp = self._conn.getresponse()
            rsp.read()
        except (OSError, http.client.HTTPException):
            self.close()
            return 0
        self.answered += 1
        if rsp.status == 200:
            self.ok += 1
        return rsp.status

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None


def admin_get(port: int, path: str) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", path)
        rsp = conn.getresponse()
        body = rsp.read()
        if rsp.status != 200:
            raise OSError(f"admin {path} -> {rsp.status}")
        return json.loads(body)
    finally:
        conn.close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- the server legs ---------------------------------------------------------


class Server:
    """One ``python -m linkerd_tpu`` boot: both routers, fs namer, the
    anomaly telemeter (``telemeter_yaml`` appended to its block). As a
    context manager it boots, checks the device the linker reports, and
    stops the process on the way out whatever happened."""

    def __init__(self, name: str, backend_port: int, telemeter_yaml: str):
        self.name = name
        self.t0 = time.monotonic()
        self.boot_s = 0.0
        self.dir = os.path.join(OUT, name)
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(os.path.join(self.dir, "disco"))
        with open(os.path.join(self.dir, "disco", "web"), "w") as f:
            f.write(f"127.0.0.1 {backend_port}\n")
        py_port, fp_port, self.admin_port = (
            free_port(), free_port(), free_port())
        self.py, self.fp = Client(py_port), Client(fp_port)
        self.cfg = os.path.join(self.dir, "linkerd.yaml")
        with open(self.cfg, "w") as f:
            f.write(f"""\
admin: {{ip: 127.0.0.1, port: {self.admin_port}}}
usage: {{enabled: false}}
namers:
- kind: io.l5d.fs
  rootDir: {os.path.join(self.dir, "disco")}
routers:
- protocol: http
  label: py
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers:
  - port: {py_port}
- protocol: http
  label: fp
  fastPath: true
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers:
  - port: {fp_port}
telemetry:
- kind: io.l5d.jaxAnomaly
{telemeter_yaml}""")
        self.log = os.path.join(self.dir, "linker.log")
        self.proc = None

    def __enter__(self) -> "Server":
        self.proc = spawn([sys.executable, "-m", "linkerd_tpu", self.cfg],
                          self.log)
        try:
            self._await_chip()
        except BaseException:
            self.__exit__()  # the next leg needs the chip back
            raise
        return self

    def _await_chip(self) -> None:
        """Wait until /model.json carries the device block (the telemeter
        built its scorer); fail unless that device is the chip and the
        score path is the one the platform selects."""
        deadline = time.monotonic() + remaining(180.0)
        while True:
            self.check_alive()
            try:
                device = self.model().get("device")
            except (OSError, ValueError):
                device = None
            if device is not None:
                break
            if time.monotonic() >= deadline:
                self.fail(["no device block on /model.json (the telemeter "
                           "never built its scorer)"])
            time.sleep(0.5)
        self.boot_s = time.monotonic() - self.t0
        say(f"{self.name}: server device {json.dumps(device)}")
        if device.get("platform") != PLATFORM:
            self.fail([f"the linker is scoring on platform "
                       f"{device.get('platform')!r}, not {PLATFORM!r}"])
        want = "fused_pallas" if device.get("count") == 1 else "mesh"
        if device.get("score_path") != want:
            self.fail([f"score path {device.get('score_path')!r}, expected "
                       f"{want!r} on {device.get('count')} device(s)"])
        if want == "mesh":
            say(f"{self.name}: more than one chip visible — the default "
                f"took the mesh path {device.get('mesh')}, which never "
                "runs the kernel")

    def __exit__(self, *exc) -> None:
        self.py.close()
        self.fp.close()
        if self.proc is not None:
            stop(self.proc, grace_s=5.0)

    def fail(self, bad: list) -> None:
        raise LegFailed(f"{self.name}: " + "; ".join(bad) + "\n"
                        + tail(self.log, 15))

    def check_alive(self) -> None:
        if self.proc.poll() is not None:
            self.fail([f"linker exited {self.proc.returncode}"])

    def model(self) -> dict:
        return admin_get(self.admin_port, "/model.json")

    def anomaly(self) -> dict:
        m = admin_get(self.admin_port, "/admin/metrics.json?q=anomaly")
        return {k[len("anomaly/"):]: v for k, v in m.items()
                if k.startswith("anomaly/")}

    def settle(self, sent: int, timeout_s: float) -> tuple:
        """Poll until every request sent has entered the scoring path and
        been scored (engine rows ride a 1 s stats loop); returns the last
        (anomaly metrics, /model.json) either way."""
        deadline = time.monotonic() + remaining(timeout_s)
        while True:
            a, m = self.anomaly(), self.model()
            if ((a.get("requests_total") == sent
                 and a.get("scored_total") == sent)
                    or time.monotonic() >= deadline):
                return a, m
            time.sleep(0.5)

    def finish(self, a: dict, m: dict, sent: int, bad: list,
               report: dict) -> dict:
        """The checks every server leg shares, the report line, and a
        clean SIGTERM exit; ``bad`` carries the leg's own findings."""
        if a.get("requests_total") != sent:
            bad.append(f"requests_total {a.get('requests_total')} != "
                       f"{sent} sent")
        if a.get("scored_total") != a.get("requests_total"):
            bad.append(f"scored_total {a.get('scored_total')} != "
                       f"requests_total {a.get('requests_total')}")
        for key in ("degraded", "score_failures", "dropped_batches",
                    "native_ring_dropped"):
            if a.get(key, 0) != 0:
                bad.append(f"{key} = {a.get(key)}")
        if m.get("scorer") != "InProcessScorer":
            bad.append(f"scorer is {m.get('scorer')!r}")
        if m.get("degraded"):
            bad.append("/model.json degraded")
        for plane, c in (("py", self.py), ("fp", self.fp)):
            if c.ok != c.answered:
                bad.append(f"{plane}: {c.answered - c.ok} non-200 replies")
        device = m.get("device", {})
        report.update({
            "batches": a.get("batches"),
            "score_batches": device.get("score_batches"),
            "fit_batches": device.get("fit_batches"),
            "train_steps": m.get("live_step"),
            "smoke_timing_s": {
                "boot": round(self.boot_s, 1),
                "total": round(time.monotonic() - self.t0, 1)},
        })
        say(f"{self.name}: {json.dumps(report)}")
        if bad:
            self.fail(bad)
        rc = stop(self.proc)
        if rc != 0:
            self.fail([f"linker exit code {rc} on SIGTERM"])
        dead = [ln for ln in tail(self.log, 100000).splitlines()
                if "background task" in ln and "failed" in ln]
        if dead:
            self.fail(["background tasks died:"] + dead[:10])
        return report


def training_gaps(a: dict, m: dict) -> list:
    """What the default-tier leg still waits for: online fit ran on the
    device, the in-plane tier scores, and the native blob was re-exported
    from device parameters after a fit."""
    gaps = []
    if not (m.get("live_step") or 0) > 0:
        gaps.append(f"live_step {m.get('live_step')}: fit never ran")
    if not math.isfinite(a.get("train_loss", math.nan)):
        gaps.append(f"train_loss {a.get('train_loss')}")
    if not a.get("native_scored_total", 0) > 0:
        gaps.append("native_scored_total is 0")
    nt = m.get("native_tier", {})
    version = (nt.get("blob") or {}).get("version")
    if nt.get("publishes", 0) < 2 or not (version or 0) > 0:
        gaps.append("native blob never re-exported after a fit (publishes "
                    f"{nt.get('publishes')}, blob version {version})")
    return gaps


def programs(report: dict) -> set:
    """The jitted programs a server compiled: one per score bucket and
    per fit shape."""
    return ({f"score/{b}" for b in report.get("score_batches") or {}}
            | {f"fit/{s}" for s in report.get("fit_batches") or {}})


def leg_server(name: str, backend_port: int) -> dict:
    """Default tiers. nativeRefreshS is the one knob off its default: the
    30 s re-export cadence would be the whole run."""
    with Server(name, backend_port, "  nativeRefreshS: 3\n") as srv:
        py, fp = srv.py, srv.fp
        # keep driving while polling: scores, fits and the native
        # re-export only move while rows flow
        deadline = time.monotonic() + remaining(150.0)
        while time.monotonic() < deadline:
            srv.check_alive()
            for _ in range(10):
                py.get()
                fp.get()
            if (py.ok >= 150 and fp.ok >= 150
                    and not training_gaps(srv.anomaly(), srv.model())):
                break
            time.sleep(0.05)
        sent = py.answered + fp.answered
        a, m = srv.settle(sent, 30.0)
        bad = training_gaps(a, m)
        jax_rows = a.get("scored_total", 0) - a.get("native_scored_total", 0)
        if jax_rows < py.answered:
            bad.append(f"JAX tier scored {jax_rows} rows < {py.answered} "
                       "Python-plane requests")
        nt = m.get("native_tier", {})
        device = m.get("device", {})
        return srv.finish(a, m, sent, bad, {
            "requests": {"python_plane": py.answered,
                         "fastpath": fp.answered},
            "rows": {"jax_tier": jax_rows,
                     "native_tier": a.get("native_scored_total")},
            "train_loss": a.get("train_loss"),
            "native_publishes": nt.get("publishes"),
            "native_blob_version": (nt.get("blob") or {}).get("version"),
            "device": {k: device.get(k) for k in
                       ("platform", "device_kind", "count", "score_path",
                        "mesh")},
        })


def leg_linerate(backend_port: int, h2bench: str) -> dict:
    """nativeTier: off — every engine row needs a JAX score, so h2bench
    load fills 1024-row buckets through the RingDispatcher."""
    with Server("linerate", backend_port,
                "  nativeTier: \"off\"\n") as srv:
        fp = srv.fp
        # bind the route first: the first requests park on the
        # miss -> bind -> set_route path
        deadline = time.monotonic() + remaining(30.0)
        while fp.ok < 5 and time.monotonic() < deadline:
            fp.get()
            time.sleep(0.05)
        if fp.ok < 5:
            srv.fail(["fastPath route never came up"])
        load = last_json(run_child(
            "h2bench-load",
            [h2bench, "h1load", "127.0.0.1", str(fp.port), "web",
             "32", "3"], 60.0))
        sent = fp.answered + load["reqs"]
        a, m = srv.settle(sent, 60.0)
        bad = []
        if load.get("errors"):
            bad.append(f"h2bench errors: {load['errors']}")
        if a.get("native_scored_total", 0) != 0:
            bad.append(f"native_scored_total {a.get('native_scored_total')}"
                       " with nativeTier off")
        buckets = m.get("device", {}).get("score_batches", {})
        if buckets.get("1024", 0) < 1:
            bad.append(f"no batch at the 1024-row bucket (buckets: "
                       f"{buckets})")
        return srv.finish(a, m, sent, bad, {
            "requests": {"fastpath": sent},
            "h2bench": load,
            "rows": {"jax_tier": a.get("scored_total"), "native_tier": 0},
        })


# -- the run -----------------------------------------------------------------


def cache_entries(path: str) -> int:
    try:
        return sum(1 for f in os.listdir(path) if f.endswith("-cache"))
    except OSError:
        return 0


def main() -> int:
    if not os.path.isdir(os.path.join(HERE, "linkerd_tpu")):
        print("chip_smoke: linkerd_tpu/ is not next to this script; "
              "nothing to run", file=sys.stderr)
        return 1
    sys.path.insert(0, HERE)
    from linkerd_tpu.compile_cache import place_compile_cache
    os.makedirs(OUT, exist_ok=True)
    cache_dir = place_compile_cache()   # children inherit the placement
    summary: dict = {"legs": {}, "failures": []}
    backend = None
    # killed at a time limit, the finally below still stops the children
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(1))
    try:
        probe = last_json(run_child(
            "probe", [sys.executable, __file__, "--leg", "probe"],
            PROBE_TIMEOUT_S))
        device = probe["device"]
        say(f"jax {probe['jax']} jaxlib {probe['jaxlib']} "
            f"libtpu {probe['libtpu']}; device {json.dumps(device)}")
        if device["platform"] != PLATFORM:
            print(f"chip_smoke: JAX found no accelerator: platform is "
                  f"{device['platform']!r} ({device['kind']}), this smoke "
                  f"only passes on {PLATFORM!r}", file=sys.stderr)
            return 1
        summary["versions"] = {k: probe[k]
                               for k in ("jax", "jaxlib", "libtpu")}

        t = time.monotonic()
        run_child("build-native",
                  [sys.executable, os.path.join("native", "build.py")],
                  300.0)
        h2bench = os.path.join(HERE, "native", "h2bench")
        if os.path.exists(h2bench):
            os.remove(h2bench)   # rebuilt from the tracked sources too
        run_child("build-h2bench",
                  [sys.executable, "-c",
                   "import build; print(build.build_h2bench())"],
                  300.0, cwd=os.path.join(HERE, "native"))
        say(f"native library and h2bench rebuilt "
            f"(smoke timing {time.monotonic() - t:.1f}s)")

        backend = EchoBackend().start()
        entries = [cache_entries(cache_dir)]
        seen: list = []

        def leg(name: str, fn, *args):
            try:
                summary["legs"][name] = fn(*args)
                return summary["legs"][name]
            except LegFailed as e:
                summary["failures"].append(str(e))
                say(f"FAILED {e}")
            except Exception as e:  # noqa: BLE001 — a leg's crash is a
                # failure of the smoke, and the later legs still run
                summary["failures"].append(f"{name}: {e!r}")
                say(f"FAILED {name}: {e!r}")
            return None

        for name in ("server-1", "server-2"):
            rep = leg(name, leg_server, name, backend.port)
            entries.append(cache_entries(cache_dir))
            seen.append(None if rep is None else programs(rep))
        first, second = entries[1] - entries[0], entries[2] - entries[1]
        summary["compile_cache"] = {
            "dir": cache_dir, "entries_before": entries[0],
            "written_by_first_server": first,
            "written_by_second_server": second}
        if seen[0] is not None and seen[1] is not None:
            # the second boot may meet a batch shape the first never
            # did; anything beyond that is a cache that does not hit
            fresh = sorted(seen[1] - seen[0])
            summary["compile_cache"]["shapes_new_to_second"] = fresh
            if second > len(fresh):
                summary["failures"].append(
                    f"compile cache: the second server wrote {second} "
                    f"entries but met only {len(fresh)} new shapes "
                    f"{fresh} — its programs are not being found in "
                    f"{cache_dir}")
        say(f"compile cache {json.dumps(summary['compile_cache'])}")

        leg("linerate", leg_linerate, backend.port, h2bench)

        def kernel() -> dict:
            t = time.monotonic()
            rep = last_json(run_child(
                "kernel", [sys.executable, __file__, "--leg", "kernel"],
                300.0))
            rep["smoke_timing_s"] = round(time.monotonic() - t, 1)
            say(f"kernel: {json.dumps(rep)}")
            return rep

        leg("kernel", kernel)
        summary["compile_cache"]["entries_after"] = cache_entries(cache_dir)
    except LegFailed as e:
        summary["failures"].append(str(e))
    finally:
        if backend is not None:
            backend.close()
        stop_all()

    if "jax" in sys.modules:
        summary["failures"].append(
            "the smoke's parent imported jax: it would hold the chip")
    summary["smoke_wall_s"] = round(time.monotonic() - _T0, 1)
    summary["ok"] = not summary["failures"]
    summary["claim"] = None
    with open(os.path.join(OUT, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    if summary["failures"]:
        for failure in summary["failures"]:
            print(f"chip_smoke FAILED: {failure}", file=sys.stderr)
        return 1
    print("SMOKE_SUMMARY " + json.dumps(summary))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--leg":
        sys.path.insert(0, HERE)
        raise SystemExit({"probe": leg_probe,
                          "kernel": leg_kernel}[sys.argv[2]]())
    raise SystemExit(main())
