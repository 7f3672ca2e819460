"""gRPC scorer sidecar: the TPU process serving anomaly scoring.

Deployment shape per BASELINE.json: the mesh router micro-batches feature
vectors over gRPC to a separate JAX/TPU process (this sidecar), so router
restarts don't lose the model and one TPU serves many routers (the same
topology as namerd serving many linkerds, SURVEY.md §2.4).

Uses grpc generic handlers with a simple length-prefixed ndarray codec
(no protoc codegen needed; the wire format is versioned by the method
names). Methods (service ``io.l5d.anomaly.Scorer``):

- ``Score``: request  = u32 n | u32 d | f32[n*d] features
             response = f32[n] scores
- ``Fit``:   request  = u32 n | u32 d | f32[n*d] x | f32[n] labels | f32[n] mask
             response = f32[1] loss
- ``Snapshot``: request = (empty)
             response = serialized ModelSnapshot (lifecycle/store format)
- ``Restore``:  request = serialized ModelSnapshot
             response = u64 restored step counter

Snapshot/Restore are the fleet hot-swap path: the lifecycle manager on
one router promotes a model, and every router pulls it into its sidecar
(or the shared sidecar restores once) without a restart.
"""

from __future__ import annotations

import asyncio
import logging
import struct
from typing import Optional, Tuple

import numpy as np

log = logging.getLogger(__name__)

SERVICE = "io.l5d.anomaly.Scorer"


def bucket_rows(n: int) -> int:
    """Power-of-two batch bucket. The single source of truth shared by the
    scorer's padding (InProcessScorer._pad_rows) and the client's
    warm-deadline keying — they must agree on what constitutes one XLA
    compilation."""
    return 1 << max(0, n - 1).bit_length()


def encode_matrix(x: np.ndarray) -> bytes:
    # ascontiguousarray normalizes sliced/strided views (a telemeter may
    # hand us arr[::2]) and zero-row windows alike; tobytes() on a 0xD
    # array is a valid empty payload.
    x = np.ascontiguousarray(x, dtype=np.float32)
    if x.ndim != 2:
        raise ValueError(f"encode_matrix wants [n, d], got shape {x.shape}")
    n, d = x.shape
    return struct.pack("<II", n, d) + x.tobytes()


def decode_matrix(data: bytes) -> np.ndarray:
    if len(data) < 8:
        raise ValueError(
            f"truncated matrix payload: {len(data)} bytes, need >= 8")
    n, d = struct.unpack_from("<II", data)
    need = 8 + 4 * n * d
    if len(data) != need:
        # a Score payload is exactly one matrix; short payloads would
        # make np.frombuffer raise a generic message, and trailing bytes
        # would silently mask a producer-side framing bug
        raise ValueError(
            f"bad matrix payload: {len(data)} bytes, "
            f"need exactly {need} for {n}x{d} f32")
    arr = np.frombuffer(data, dtype=np.float32, offset=8, count=n * d)
    return arr.reshape(n, d)


def encode_fit(x: np.ndarray, labels: np.ndarray, mask: np.ndarray) -> bytes:
    labels = np.ascontiguousarray(labels, np.float32)
    mask = np.ascontiguousarray(mask, np.float32)
    n = x.shape[0]
    if labels.shape != (n,) or mask.shape != (n,):
        raise ValueError(
            f"encode_fit row mismatch: x has {n} rows, labels "
            f"{labels.shape}, mask {mask.shape}")
    return encode_matrix(x) + labels.tobytes() + mask.tobytes()


def decode_fit(data: bytes) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    if len(data) < 8:
        raise ValueError(
            f"truncated fit payload: {len(data)} bytes, need >= 8")
    n, d = struct.unpack_from("<II", data)
    need = 8 + 4 * (n * d + 2 * n)
    if len(data) != need:
        # a silent np.frombuffer misread here would train on shifted
        # labels/mask — reject short AND long payloads outright
        raise ValueError(
            f"bad fit payload: {len(data)} bytes, need exactly {need} "
            f"for {n}x{d} f32 + 2x{n} f32")
    off = 8
    x = np.frombuffer(data, np.float32, n * d, off).reshape(n, d)
    off += 4 * n * d
    labels = np.frombuffer(data, np.float32, n, off)
    off += 4 * n
    mask = np.frombuffer(data, np.float32, n, off)
    return x, labels, mask


class ScorerSidecar:
    """grpc.aio server wrapping an in-process Scorer."""

    def __init__(self, scorer=None, host: str = "127.0.0.1", port: int = 0,
                 warmup_rows: int = 0):
        if scorer is None:
            from linkerd_tpu.telemetry.anomaly import InProcessScorer
            scorer = InProcessScorer()
        self.scorer = scorer
        self.host = host
        self.port = port
        self.warmup_rows = warmup_rows
        self._server = None

    async def start(self) -> "ScorerSidecar":
        import grpc

        scorer = self.scorer

        async def score(request: bytes, context) -> bytes:
            x = decode_matrix(request)
            s = await scorer.score(x)
            return np.ascontiguousarray(s, np.float32).tobytes()

        async def fit(request: bytes, context) -> bytes:
            x, labels, mask = decode_fit(request)
            loss = await scorer.fit(x, labels, mask)
            return np.float32([loss]).tobytes()

        async def snapshot(request: bytes, context) -> bytes:
            # request payload is empty; response is the full serialized
            # checkpoint (lifecycle/store wire format, CRC-tailed)
            from linkerd_tpu.lifecycle.store import encode_snapshot
            snap = await asyncio.to_thread(scorer.snapshot)
            return encode_snapshot(snap)

        async def restore(request: bytes, context) -> bytes:
            from linkerd_tpu.lifecycle.store import decode_snapshot
            snap = decode_snapshot(request)
            await asyncio.to_thread(scorer.restore, snap)
            # echo the restored step so callers can confirm the swap
            return struct.pack("<Q", int(snap.step))

        handler = grpc.method_handlers_generic_handler(SERVICE, {
            "Score": grpc.unary_unary_rpc_method_handler(
                score,
                request_deserializer=None, response_serializer=None),
            "Fit": grpc.unary_unary_rpc_method_handler(
                fit,
                request_deserializer=None, response_serializer=None),
            "Snapshot": grpc.unary_unary_rpc_method_handler(
                snapshot,
                request_deserializer=None, response_serializer=None),
            "Restore": grpc.unary_unary_rpc_method_handler(
                restore,
                request_deserializer=None, response_serializer=None),
        })
        self._server = grpc.aio.server()
        self._server.add_generic_rpc_handlers((handler,))
        self.port = self._server.add_insecure_port(f"{self.host}:{self.port}")
        # Warm up BEFORE serving so no real Fit/Score can race the warmup
        # window (warmup restores pre-warmup scorer state when it finishes).
        if self.warmup_rows:
            warmup = getattr(scorer, "warmup", None)
            if warmup is not None:
                await warmup(self.warmup_rows)
        await self._server.start()
        return self

    async def close(self) -> None:
        if self._server is not None:
            await self._server.stop(grace=0.5)
        closer = getattr(self.scorer, "close", None)
        if closer is not None:
            closer()  # release the scorer's dispatch ring + drainer


class GrpcScorerClient:
    """Scorer implementation that ships micro-batches to a sidecar."""

    def __init__(self, address: str, timeout_s: float = 5.0,
                 first_timeout_s: float = 60.0):
        # The first call on each RPC gets a long deadline to absorb the
        # sidecar's XLA compile; afterwards the short steady-state
        # deadline keeps failure detection responsive.
        self.address = address
        self.timeout_s = timeout_s
        self.first_timeout_s = first_timeout_s
        self._warm: set = set()
        # most recent Score call decomposition ({rpc_ms, bytes}): the
        # sidecar analogue of InProcessScorer.last_timing — scorer spans
        # annotate the gRPC hop cost instead of device phases
        self.last_timing = None
        self._channel = None
        self._score = None
        self._fit = None
        self._snapshot = None
        self._restore = None

    @staticmethod
    def _bucket(rpc: str, rows: int) -> tuple:
        # Each power-of-two bucket is a distinct XLA compilation. Warm
        # state is keyed by (rpc, bucket) so the first call into any
        # bucket gets the long deadline while compiled buckets keep the
        # short one.
        return (rpc, bucket_rows(rows))

    def _deadline(self, key: tuple) -> float:
        return self.timeout_s if key in self._warm else self.first_timeout_s

    def _ensure(self) -> None:
        if self._channel is None:
            import grpc

            self._channel = grpc.aio.insecure_channel(self.address)
            self._score = self._channel.unary_unary(
                f"/{SERVICE}/Score",
                request_serializer=None, response_deserializer=None)
            self._fit = self._channel.unary_unary(
                f"/{SERVICE}/Fit",
                request_serializer=None, response_deserializer=None)
            self._snapshot = self._channel.unary_unary(
                f"/{SERVICE}/Snapshot",
                request_serializer=None, response_deserializer=None)
            self._restore = self._channel.unary_unary(
                f"/{SERVICE}/Restore",
                request_serializer=None, response_deserializer=None)

    async def snapshot(self):
        """Pull the sidecar's full model state as a ModelSnapshot — the
        fleet-wide distribution path: one router checkpoints/promotes,
        every other router pulls and restores without restarting."""
        from linkerd_tpu.lifecycle.store import decode_snapshot
        self._ensure()
        rsp = await self._snapshot(b"", timeout=self.first_timeout_s)
        return decode_snapshot(rsp)

    async def restore(self, snap) -> int:
        """Hot-swap ``snap`` into the sidecar; returns the restored step."""
        from linkerd_tpu.lifecycle.store import encode_snapshot
        self._ensure()
        rsp = await self._restore(encode_snapshot(snap),
                                  timeout=self.first_timeout_s)
        return struct.unpack("<Q", rsp)[0]

    async def score(self, x: np.ndarray) -> np.ndarray:
        import time
        self._ensure()
        key = self._bucket("score", len(x))
        payload = encode_matrix(x)
        t0 = time.monotonic()
        rsp = await self._score(payload, timeout=self._deadline(key))
        self.last_timing = {
            "rpc_ms": (time.monotonic() - t0) * 1e3,
            "bytes": len(payload) + len(rsp),
        }
        self._warm.add(key)
        return np.frombuffer(rsp, np.float32)

    async def fit(self, x: np.ndarray, labels: np.ndarray,
                  mask: np.ndarray) -> float:
        self._ensure()
        key = self._bucket("fit", len(x))
        rsp = await self._fit(encode_fit(x, labels, mask),
                              timeout=self._deadline(key))
        self._warm.add(key)
        return float(np.frombuffer(rsp, np.float32)[0])

    async def aclose(self) -> None:
        """Close the channel, awaiting completion (use before the event
        loop shuts down)."""
        if self._channel is not None:
            ch, self._channel = self._channel, None
            await ch.close()

    def close(self) -> None:
        if self._channel is not None:
            ch, self._channel = self._channel, None
            try:
                asyncio.get_running_loop()
            except RuntimeError:
                # no running loop (interpreter teardown): nothing to
                # drain the close on; the socket dies with the process.
                # Checked BEFORE ch.close() is called so no never-awaited
                # coroutine is orphaned.
                return
            from linkerd_tpu.core.tasks import spawn
            spawn(ch.close(), what="sidecar-channel-close")


def main() -> None:
    """``python -m linkerd_tpu.telemetry.sidecar`` — run a scorer
    replica as a standalone process, optionally ANNOUNCED through the
    fs announcer so linkerds resolve it like any other service
    (``sidecarAddress: /#/io.l5d.fs/<name>``): the scorer tier becomes
    a first-class, load-balanced fleet service instead of a pinned
    host:port."""
    import argparse
    import signal

    parser = argparse.ArgumentParser(
        description="linkerd-tpu anomaly scorer replica")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--warmup-rows", type=int, default=0)
    parser.add_argument(
        "--announce-dir", default=None,
        help="fs-announcer root dir (the fs namer's rootDir); when set "
             "the replica registers itself under --announce-name and "
             "withdraws on shutdown")
    parser.add_argument("--announce-name", default="l5d-scorer")
    args = parser.parse_args()
    # before ScorerSidecar() builds the InProcessScorer and imports jax
    from linkerd_tpu.compile_cache import place_compile_cache
    place_compile_cache()

    async def amain() -> None:
        from linkerd_tpu.core import Path

        sidecar = await ScorerSidecar(
            host=args.host, port=args.port,
            warmup_rows=args.warmup_rows).start()
        announcement = None
        if args.announce_dir:
            from linkerd_tpu.announcer import FsAnnouncer
            announcer = FsAnnouncer(args.announce_dir,
                                    Path.read("/io.l5d.fs"))
            announcement = announcer.announce(
                args.host, sidecar.port, Path.read(f"/{args.announce_name}"))
            log.info("scorer replica announced as %s in %s",
                     args.announce_name, args.announce_dir)
        print(f"SCORER_SIDECAR {args.host}:{sidecar.port}", flush=True)
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        if announcement is not None:
            announcement.close()
        await sidecar.close()

    import logging as _logging
    _logging.basicConfig(level=_logging.INFO)
    asyncio.run(amain())


if __name__ == "__main__":
    main()
