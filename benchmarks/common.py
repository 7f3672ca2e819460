"""Shared benchmark machinery: echo downstreams, HTTP load generator,
subprocess orchestration.

The load generator is deliberately dumb-and-fast: pipelined keep-alive
HTTP/1.1 over raw asyncio protocols, counting responses by head-delimiter
occurrences (bodies are chosen to never contain CRLFCRLF). This mirrors
wrk's closed-loop model from BASELINE.md config 1.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def gen_bench_cert(dirpath: str) -> Optional[Tuple[str, str]]:
    """Self-signed cert/key for the TLS bench legs (openssl CLI; None —
    TLS rows are skipped, cleartext rows stand — when unavailable)."""
    cert = os.path.join(dirpath, "bench-cert.pem")
    key = os.path.join(dirpath, "bench-key.pem")
    try:
        subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048",
             "-keyout", key, "-out", cert, "-days", "2", "-nodes",
             "-subj", "/CN=localhost",
             "-addext", "subjectAltName=DNS:localhost,DNS:web,DNS:echo"],
            check=True, capture_output=True, timeout=60)
    except (OSError, subprocess.SubprocessError):
        return None
    return cert, key


# ---------------------------------------------------------------- downstream

class EchoProtocol(asyncio.Protocol):
    """Minimal HTTP/1.1 echo: fixed 200 response per request head seen."""

    RESPONSE = (b"HTTP/1.1 200 OK\r\n"
                b"Content-Length: 2\r\n"
                b"\r\n"
                b"ok")

    def __init__(self, delay_s: float = 0.0):
        self._buf = b""
        self._delay = delay_s
        self.transport: Optional[asyncio.Transport] = None

    def connection_made(self, transport):
        transport.set_write_buffer_limits(high=1 << 20)
        sock = transport.get_extra_info("socket")
        if sock is not None:
            try:
                import socket as _s
                sock.setsockopt(_s.IPPROTO_TCP, _s.TCP_NODELAY, 1)
            except OSError:
                pass
        self.transport = transport

    def data_received(self, data):
        self._buf += data
        n = self._buf.count(b"\r\n\r\n")
        if not n:
            return
        # bench requests are bodyless GETs: head count == request count
        self._buf = self._buf[self._buf.rfind(b"\r\n\r\n") + 4:]
        if self._delay > 0:
            loop = asyncio.get_running_loop()
            loop.call_later(self._delay, self._respond, n)
        else:
            self._respond(n)

    def _respond(self, n: int) -> None:
        if self.transport is not None and not self.transport.is_closing():
            self.transport.write(self.RESPONSE * n)

    def connection_lost(self, exc):
        self.transport = None


async def start_echo(port: int = 0, delay_s: float = 0.0):
    loop = asyncio.get_running_loop()
    server = await loop.create_server(
        lambda: EchoProtocol(delay_s), "127.0.0.1", port)
    return server, server.sockets[0].getsockname()[1]


# ---------------------------------------------------------------- load gen

class _GenConn(asyncio.Protocol):
    """One pipelined closed-loop connection: keeps `window` requests in
    flight, records a latency sample per completed batch head."""

    def __init__(self, request: bytes, window: int, done_cb):
        self.request = request
        self.window = window
        self.done_cb = done_cb
        self.inflight: List[float] = []  # send timestamps, FIFO
        self.completed = 0
        self.latencies: List[float] = []
        self._tail = b""
        self.transport: Optional[asyncio.Transport] = None
        self.closed = asyncio.get_running_loop().create_future()

    def connection_made(self, transport):
        sock = transport.get_extra_info("socket")
        if sock is not None:
            try:
                import socket as _s
                sock.setsockopt(_s.IPPROTO_TCP, _s.TCP_NODELAY, 1)
            except OSError:
                pass
        self.transport = transport
        self._fill()

    def _fill(self):
        now = time.perf_counter()
        while len(self.inflight) < self.window:
            self.inflight.append(now)
            self.transport.write(self.request)

    def data_received(self, data):
        buf = self._tail + data
        n = buf.count(b"\r\n\r\n")
        if n:
            idx = buf.rfind(b"\r\n\r\n") + 4
            self._tail = buf[idx:]
            now = time.perf_counter()
            for _ in range(min(n, len(self.inflight))):
                self.latencies.append(now - self.inflight.pop(0))
            self.completed += n
            if not self.done_cb():
                self._fill()
            elif not self.inflight and self.transport:
                self.transport.close()
        else:
            self._tail = buf[-8:] if len(buf) > 8 else buf

    def connection_lost(self, exc):
        if not self.closed.done():
            self.closed.set_result(None)


async def run_load(host: str, port: int, duration_s: float,
                   connections: int = 8, window: int = 16,
                   path: str = "/", host_header: str = "web",
                   ) -> Tuple[float, List[float]]:
    """Closed-loop load for `duration_s`; returns (req_per_s, latencies)."""
    request = (f"GET {path} HTTP/1.1\r\n"
               f"Host: {host_header}\r\n"
               f"\r\n").encode()
    deadline = time.perf_counter() + duration_s
    stop = False

    def done() -> bool:
        nonlocal stop
        if not stop and time.perf_counter() >= deadline:
            stop = True
        return stop

    loop = asyncio.get_running_loop()
    conns: List[_GenConn] = []
    t0 = time.perf_counter()
    for _ in range(connections):
        _, proto = await loop.create_connection(
            lambda: _GenConn(request, window, done), host, port)
        conns.append(proto)
    try:
        await asyncio.wait_for(
            asyncio.gather(*[c.closed for c in conns]), duration_s + 30)
    finally:
        for c in conns:
            if c.transport is not None:
                c.transport.close()
    dt = time.perf_counter() - t0
    total = sum(c.completed for c in conns)
    lats: List[float] = []
    for c in conns:
        lats.extend(c.latencies)
    return total / dt, lats


async def run_paced_load(host: str, port: int, duration_s: float,
                         rate_rps: float, connections: int = 16,
                         path: str = "/", host_header: str = "web",
                         ssl_ctx=None,
                         ) -> Tuple[float, List[float], bool]:
    """Open-loop paced load at `rate_rps`: requests are issued on a clock
    over a pool of keep-alive connections (one outstanding request per
    connection, excess arrivals queue). Returns (achieved_rps, latencies,
    saturated) — `saturated` is True when the pool could not keep pace
    (queue kept growing), in which case added-latency numbers are invalid.
    """
    request = (f"GET {path} HTTP/1.1\r\n"
               f"Host: {host_header}\r\n"
               f"\r\n").encode()
    loop = asyncio.get_running_loop()

    free: asyncio.Queue = asyncio.Queue()
    latencies: List[float] = []
    completed = 0

    class _Paced(asyncio.Protocol):
        def __init__(self):
            self._tail = b""
            self.t_sent = 0.0
            self.transport = None

        def connection_made(self, transport):
            sock = transport.get_extra_info("socket")
            if sock is not None:
                try:
                    import socket as _s
                    sock.setsockopt(_s.IPPROTO_TCP, _s.TCP_NODELAY, 1)
                except OSError:
                    pass
            self.transport = transport
            free.put_nowait(self)

        def send(self):
            self.t_sent = time.perf_counter()
            self.transport.write(request)

        def data_received(self, data):
            nonlocal completed
            buf = self._tail + data
            if b"\r\n\r\n" in buf:
                self._tail = b""
                latencies.append(time.perf_counter() - self.t_sent)
                completed += 1
                free.put_nowait(self)
            else:
                self._tail = buf[-8:]

        def connection_lost(self, exc):
            self.transport = None

    protos = []
    for _ in range(connections):
        _, p = await loop.create_connection(
            lambda: _Paced(), host, port, ssl=ssl_ctx,
            server_hostname="localhost" if ssl_ctx else None)
        protos.append(p)

    interval = 1.0 / rate_rps
    t0 = time.perf_counter()
    n_target = int(duration_s * rate_rps)
    saturated = False
    issued = 0
    for i in range(n_target):
        due = t0 + i * interval
        now = time.perf_counter()
        if due > now:
            await asyncio.sleep(due - now)
        try:
            conn = free.get_nowait()
        except asyncio.QueueEmpty:
            # behind: wait, but flag saturation if we fall > 1s behind
            if time.perf_counter() - due > 1.0:
                saturated = True
                break
            conn = await free.get()
        conn.send()
        issued += 1
    # drain
    t_end = time.perf_counter() + 5.0
    while completed < issued and time.perf_counter() < t_end:
        await asyncio.sleep(0.01)
    dt = time.perf_counter() - t0
    for p in protos:
        if p.transport is not None:
            p.transport.close()
    return completed / dt, latencies, saturated


def percentile(sorted_vals: List[float], p: float) -> float:
    if not sorted_vals:
        return float("nan")
    i = min(len(sorted_vals) - 1, int(p / 100.0 * len(sorted_vals)))
    return sorted_vals[i]


def lat_stats(latencies: List[float]) -> dict:
    s = sorted(latencies)
    return {
        "n": len(s),
        "p50_ms": round(percentile(s, 50) * 1e3, 3),
        "p90_ms": round(percentile(s, 90) * 1e3, 3),
        "p99_ms": round(percentile(s, 99) * 1e3, 3),
    }


# ------------------------------------------------------------- subprocesses

class Proc:
    """A child process running a python module until SIGTERM; communicates
    its ready state + ports by printing one JSON line to stdout."""

    def __init__(self, args: List[str], env: Optional[dict] = None):
        e = dict(os.environ)
        e["PYTHONPATH"] = REPO + os.pathsep + e.get("PYTHONPATH", "")
        # the data-plane benches' children never need the chip, and a
        # chip belongs to one process at a time: keep them off it
        e.setdefault("JAX_PLATFORMS", "cpu")
        if env:
            e.update(env)
        self.proc = subprocess.Popen(
            [sys.executable] + args, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, env=e, cwd=REPO, text=True)

    def wait_ready(self, timeout: float = 60.0) -> dict:
        """Reads one JSON line from the child's stdout."""
        import selectors
        sel = selectors.DefaultSelector()
        sel.register(self.proc.stdout, selectors.EVENT_READ)
        deadline = time.time() + timeout
        line = ""
        while time.time() < deadline:
            if not sel.select(timeout=1.0):
                if self.proc.poll() is not None:
                    break
                continue
            line = self.proc.stdout.readline()
            if line.strip():
                return json.loads(line)
        err = self.proc.stderr.read() if self.proc.poll() is not None else ""
        raise RuntimeError(f"child not ready: {line!r} {err[-2000:]}")

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(5)


def build_h2bench() -> str:
    """Build (if stale) and return the out-of-process C++ load generator
    / echo binary (native/h2bench.cpp), shared by configs 1 and 2."""
    import importlib.util as u
    spec = u.spec_from_file_location(
        "nbuild", os.path.join(REPO, "native", "build.py"))
    mod = u.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.build_h2bench()
