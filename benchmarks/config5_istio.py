"""BASELINE.md config 5: istio-mixer telemetry, 50-service replay with
cascading failures, multi-router fan-out, subtle-fault AUC.

Topology: 50 services in three tiers — frontends svc-0..19, mids
svc-20..39, dbs svc-40..49 — each a real local HTTP backend. Frontends
call their mid through the mesh, mids call their db through the mesh
(chain svc-i -> svc-(20+i%20) -> svc-(40+mid%10)), across TWO routers
(frontend + backend: the multi-router fan-out). The io.l5d.istio
telemeter streams Mixer Report RPCs to a fake mixer served by the
in-repo gRPC runtime.

Faults (both SUBTLE — VERDICT r2 item 5):
- cascade: db svc-45 degrades latency-only (+4-16 ms, overlapping the
  baseline); its dependents svc-25 and svc-5 inherit the inflation
  through the chain. All three are labeled anomalous during windows.
- partial errors: db svc-47 returns 503 on 15% of requests in its own
  windows; mids propagate a 502 upward with the label header, so the
  partially-failed chain is labeled per-request.

Replay popularity is zipf-skewed over frontends (ShareGPT-style replay:
a few hot services, a long tail).

Measures: fault_auc_subtle_istio, AUC per fault class, labeled_n,
mixer_reports.

Usage: python -m benchmarks.config5_istio [--requests 500]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_FRONT, N_MID, N_DB = 20, 20, 10

CONFIG = """
routers:
- protocol: http
  label: front
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers: [{{port: 0}}]
  client:
    failureAccrual: {{kind: none}}
- protocol: http
  label: back
  dtab: |
    /svc => /#/io.l5d.fs ;
  servers: [{{port: 0}}]
  client:
    failureAccrual: {{kind: none}}
telemetry:
- kind: io.l5d.jaxAnomaly
  maxBatch: 1024
  trainEveryBatches: 1
  reconWeight: 1.0
- kind: io.l5d.istio
  experimental: true
  mixerHost: 127.0.0.1
  mixerPort: {mixer_port}
namers:
- kind: io.l5d.fs
  rootDir: {disco}
"""


async def start_fake_mixer():
    """A Mixer serving bidi Report over the in-repo gRPC runtime."""
    from linkerd_tpu.grpc import ServerDispatcher
    from linkerd_tpu.istio import mixer_pb as pb
    from linkerd_tpu.protocol.h2.server import H2Server

    reports = []
    disp = ServerDispatcher()

    async def report(reqs):
        async def gen():
            async for r in reqs:
                reports.append(r)
                yield pb.ReportResponse(request_index=r.request_index)
        return gen()

    disp.register(pb.MIXER_SVC, "Report", report)
    server = await H2Server(disp).start()
    return server, reports


async def bench(n_requests: int) -> dict:
    from linkerd_tpu.linker import load_linker
    from linkerd_tpu.models.features import featurize_batch
    from linkerd_tpu.protocol.http import Request, Response
    from linkerd_tpu.protocol.http.client import HttpClient
    from linkerd_tpu.protocol.http.server import serve
    from linkerd_tpu.router.service import FnService
    from linkerd_tpu.testing.faults import (
        FaultInjector, FaultSpec, WindowLabeler, auc,
    )

    tmp = tempfile.TemporaryDirectory(prefix="l5d-bench5-")
    disco = os.path.join(tmp.name, "disco")
    os.makedirs(disco)

    mixer, mixer_reports = await start_fake_mixer()
    linker = load_linker(CONFIG.format(disco=disco,
                                       mixer_port=mixer.bound_port))

    # cascade source: latency-only on db svc-45
    lat_injector = FaultInjector(FaultSpec(
        error_rate=0.0, latency_ms=4.0, latency_jitter_ms=12.0))
    # partial errors: 15% 503s on db svc-47
    err_injector = FaultInjector(FaultSpec(
        error_rate=0.15, error_status=503))
    cascade_labeler = WindowLabeler()    # svc-45/25/5 chain
    LABEL = FaultInjector.LABEL_HEADER

    backends = []
    back_port = None  # backend router port, bound after linker.start()
    back_proxy = None

    def mid_of(i: int) -> int:
        return 20 + (i % N_MID)

    def db_of(j: int) -> int:
        return 40 + (j % N_DB)

    async def call_via_mesh(svc: str) -> Response:
        req = Request(method="GET", uri="/dep")
        req.headers.set("Host", svc)
        return await back_proxy(req)

    def mk_backend(idx: int):
        if idx >= 40:  # db tier: leaf
            async def db_handler(req: Request) -> Response:
                await asyncio.sleep(0.001)
                return Response(200, body=b"db" * 30)
            svc: object = FnService(db_handler)
            if idx == 45:
                svc = cascade_labeler.and_then(
                    lat_injector.and_then(svc))
            elif idx == 47:
                svc = err_injector.and_then(svc)
            return svc

        # frontend/mid: call the next tier through the mesh
        dep = f"svc-{mid_of(idx)}" if idx < 20 else f"svc-{db_of(idx)}"

        async def chain_handler(req: Request, _dep=dep) -> Response:
            try:
                sub = await call_via_mesh(_dep)
            except Exception:  # noqa: BLE001 — downstream unreachable
                return Response(502, body=b"chain failed")
            rsp = (Response(200, body=b"ok" * 20) if sub.status < 500
                   else Response(502, body=b"dep failed"))
            # propagate the fault label up the chain so partially-failed
            # and cascade-inflated requests stay labeled end-to-end
            sub_label = sub.headers.get(LABEL)
            if sub_label is not None:
                rsp.headers.set(LABEL, sub_label)
            return rsp

        svc = FnService(chain_handler)
        if idx in (5, 25):  # cascade chain members inherit the label
            svc = cascade_labeler.and_then(svc)
        return svc

    for i in range(N_FRONT + N_MID + N_DB):
        server = await serve(mk_backend(i))
        backends.append(server)
        with open(os.path.join(disco, f"svc-{i}"), "w") as f:
            f.write(f"127.0.0.1 {server.bound_port}\n")

    await linker.start()
    tele = linker.telemeters[0]
    front_proxy = HttpClient("127.0.0.1", linker.routers[0].server_ports[0])
    back_proxy = HttpClient("127.0.0.1", linker.routers[1].server_ports[0])

    # zipf-skewed replay over frontends (hot head, long tail)
    rng = random.Random(7)
    weights = [1.0 / (r + 1) ** 0.9 for r in range(N_FRONT)]

    out: dict = {"config": 5}
    try:
        async def replay(n: int) -> None:
            for _ in range(n):
                i = rng.choices(range(N_FRONT), weights=weights)[0]
                req = Request(method="GET", uri="/api")
                req.headers.set("Host", f"svc-{i}")
                try:
                    await front_proxy(req)
                except Exception:  # noqa: BLE001
                    pass

        async def hit_chain(frontend: int, n: int) -> None:
            for _ in range(n):
                req = Request(method="GET", uri="/api")
                req.headers.set("Host", f"svc-{frontend}")
                try:
                    await front_proxy(req)
                except Exception:  # noqa: BLE001
                    pass

        # Phase A: normal replay; train.
        await replay(n_requests)
        ring_copy = list(tele.ring)
        for _ in range(6):
            await tele.drain_once()
            for item in ring_copy:
                tele.ring.append(item)
        await tele.drain_once()

        # Phase B: alternating fault windows.
        windows = 4
        per = max(20, n_requests // (2 * windows))

        async def mixed_load(per_chain: int) -> None:
            # interleave sequentially: single-core loop backlog must not
            # inflate NORMAL latencies (that's harness noise, not mesh
            # signal)
            for _ in range(per_chain):
                await hit_chain(5, 1)
                await hit_chain(7, 1)
                await replay(1)

        for w in range(windows):
            if w % 2 == 0:
                lat_injector.active = True
                cascade_labeler.active = True
            else:
                err_injector.active = True
            await mixed_load(per)
            lat_injector.active = False
            cascade_labeler.active = False
            err_injector.active = False
            await mixed_load(per // 2)

        tele.cfg.trainEveryBatches = 0  # score-only
        items = list(tele.ring)
        await tele.drain_once()
        # ring items are (fv, label, trace, enqueued_at) since the
        # scorer spans landed; index instead of unpacking
        fvs = [it[0] for it in items]
        labels = [it[1] for it in items]
        x = featurize_batch(fvs)
        scorer = tele._ensure_scorer()
        scores = await scorer.score(x)
        pairs = [(l, float(s), fv.status)
                 for l, s, fv in zip(labels, scores, fvs) if l is not None]
        got = auc([l for l, _, _ in pairs], [s for _, s, _ in pairs])
        # latency-only subset: drop rows where a status signal exists
        lat_pairs = [(l, s) for l, s, st in pairs if st < 500]
        lat_auc = auc([l for l, _ in lat_pairs], [s for _, s in lat_pairs])

        out["fault_auc_subtle_istio"] = round(got, 4)
        out["fault_auc_latency_only"] = round(lat_auc, 4)
        out["labeled_n"] = len(pairs)
        out["anomalous_n"] = sum(1 for l, _, _ in pairs if l > 0.5)
        # give the mixer queue a beat to drain
        await asyncio.sleep(0.5)
        out["mixer_reports"] = len(mixer_reports)
        snap = linker.metrics.flatten()
        out["front_requests"] = snap.get("rt/front/server/requests")
        out["back_requests"] = snap.get("rt/back/server/requests")
    finally:
        await front_proxy.close()
        await back_proxy.close()
        await linker.close()
        await mixer.close()
        for b in backends:
            await b.close()
        tmp.cleanup()
    return out


def main() -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=500)
    args = ap.parse_args()
    result = asyncio.run(bench(args.requests))
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
