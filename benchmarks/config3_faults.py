"""BASELINE.md config 3: mixed http + thriftmux routers, 3 downstreams,
injected 5xx + latency spikes -> labeled anomaly traces scored by the
io.l5d.jaxAnomaly telemeter.

Measures: fault_auc (target >= 0.9, BASELINE.json north star), the
per-dst score separation (anomalous vs baseline), and the mixed-traffic
request counts per router.

Usage: python -m benchmarks.config3_faults [--requests 120]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import struct
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONFIG = """
routers:
- protocol: http
  label: web
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers: [{{port: 0}}]
  client:
    failureAccrual: {{kind: none}}
- protocol: thriftmux
  label: tmx
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers: [{{port: 0}}]
telemetry:
- kind: io.l5d.jaxAnomaly
  maxBatch: 512
  trainEveryBatches: 1
  reconWeight: 1.0
namers:
- kind: io.l5d.fs
  rootDir: {disco}
"""


async def bench(n_requests: int) -> dict:
    from linkerd_tpu.linker import load_linker
    from linkerd_tpu.models.features import featurize_batch
    from linkerd_tpu.protocol.http import Request, Response
    from linkerd_tpu.protocol.http.client import HttpClient
    from linkerd_tpu.protocol.http.server import serve
    from linkerd_tpu.protocol.mux.client import MuxClient
    from linkerd_tpu.protocol.mux.codec import Tdispatch
    from linkerd_tpu.protocol.mux.server import MuxServer
    from linkerd_tpu.protocol.thrift.codec import (
        CALL, REPLY, VERSION_1, parse_message_header,
    )
    from linkerd_tpu.router.service import FnService
    from linkerd_tpu.testing.faults import FaultInjector, FaultSpec, auc

    tmp = tempfile.TemporaryDirectory(prefix="l5d-bench3-")
    disco = os.path.join(tmp.name, "disco")
    os.makedirs(disco)

    # 3 downstreams: two http (one faultable), one thriftmux
    injector = FaultInjector(FaultSpec(error_rate=0.9, latency_ms=40.0))

    async def backend_a(req: Request) -> Response:
        return Response(200, body=b"a" * 200)

    async def backend_b(req: Request) -> Response:
        return Response(200, body=b"b" * 120)

    async def mux_backend(td: Tdispatch) -> bytes:
        name, seqid, _ = parse_message_header(td.payload)
        nb = name.encode()
        return (struct.pack(">I", (VERSION_1 | REPLY) & 0xFFFFFFFF)
                + struct.pack(">I", len(nb)) + nb
                + struct.pack(">i", seqid) + b"\x00")

    d_a = await serve(injector.and_then(FnService(backend_a)))
    d_b = await serve(FnService(backend_b))
    d_m = await MuxServer(FnService(mux_backend)).start()
    for name, port in (("svc-a", d_a.bound_port), ("svc-b", d_b.bound_port),
                       ("thriftmux", d_m.bound_port)):
        with open(os.path.join(disco, name), "w") as f:
            f.write(f"127.0.0.1 {port}\n")

    linker = load_linker(CONFIG.format(disco=disco))
    await linker.start()
    tele = linker.telemeters[0]
    http_port = linker.routers[0].server_ports[0]
    tmx_port = linker.routers[1].server_ports[0]
    proxy = HttpClient("127.0.0.1", http_port)
    mux = MuxClient("127.0.0.1", tmx_port)

    def mk_call(name: str, seqid: int) -> bytes:
        nb = name.encode()
        return (struct.pack(">I", (VERSION_1 | CALL) & 0xFFFFFFFF)
                + struct.pack(">I", len(nb)) + nb
                + struct.pack(">i", seqid) + b"\x00")

    out: dict = {"config": 3}
    try:
        async def send_http(host: str, n: int) -> None:
            for _ in range(n):
                req = Request(method="GET", uri="/")
                req.headers.set("Host", host)
                await proxy(req)

        async def send_tmx(n: int) -> None:
            for i in range(n):
                rsp = await mux(Tdispatch(0, [], "", [], mk_call("ping", i)))
                parse_message_header(rsp)

        # Phase A: normal mixed traffic; train on it.
        await asyncio.gather(send_http("svc-a", n_requests),
                             send_http("svc-b", n_requests),
                             send_tmx(n_requests))
        ring_copy = list(tele.ring)  # snapshot once: each epoch re-trains
        for _ in range(6):           # on the same normal-traffic batch
            await tele.drain_once()
            for item in ring_copy:
                tele.ring.append(item)
            await tele.drain_once()
        baseline = tele.board.score_of("/svc/svc-a")

        # Phase B: alternating fault bursts on svc-a; svc-b + tmx stay
        # healthy. The tmx sends keep the routers under mixed-protocol
        # load, but only http traffic is scored: the thriftmux router
        # carries no FeatureRecorder, so AUC is over the http window.
        for _ in range(4):
            injector.active = True
            await asyncio.gather(send_http("svc-a", n_requests // 4),
                                 send_tmx(n_requests // 8))
            injector.active = False
            await asyncio.gather(send_http("svc-a", n_requests // 4),
                                 send_http("svc-b", n_requests // 8))
        tele.cfg.trainEveryBatches = 0  # score-only
        items = list(tele.ring)
        await tele.drain_once()
        anomalous = tele.board.score_of("/svc/svc-a")

        # ring items are (fv, label, trace, enqueued_at) since the
        # scorer spans landed; external producers may still append
        # 2-tuples, so index instead of unpacking
        fvs = [it[0] for it in items]
        labels = [it[1] for it in items]
        x = featurize_batch(fvs)
        scorer = tele._ensure_scorer()
        scores = await scorer.score(x)
        pairs = [(l, s) for l, s in zip(labels, scores) if l is not None]
        got_auc = auc([l for l, _ in pairs], [float(s) for _, s in pairs])

        out["fault_auc"] = round(got_auc, 4)
        out["score_baseline"] = round(float(baseline), 4)
        out["score_anomalous"] = round(float(anomalous), 4)
        out["labeled_n"] = len(pairs)
        snap = linker.metrics.flatten()
        out["http_requests"] = snap.get("rt/web/server/requests")
        out["tmx_requests"] = snap.get("rt/tmx/server/requests")
    finally:
        await mux.close()
        await linker.close()
        await d_a.close()
        await d_b.close()
        await d_m.close()
        tmp.cleanup()
    return out


def main() -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=120)
    args = ap.parse_args()
    result = asyncio.run(bench(args.requests))
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
