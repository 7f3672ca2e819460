"""Stream sentinel: incremental scoring + mid-stream actuation.

Covers the Python plane of linkerd_tpu/streams/ end to end — the
frame-delta tracker (pinned bit-identical against the engines' C
accumulator), the bounded sentinel table under hostile stream churn,
specialist-head (route) pinning at stream open, the h2 frame observer's
sampling cadence and shed actuation, a chaos leg where one sick stream
is detected and shed mid-flight while its neighbors finish untouched,
the h1 tunnel passthrough (101 Upgrade / CONNECT byte relay with pool
handoff), and the h2 client's GOAWAY drain (in-flight streams below
last_stream_id finish on the old connection instead of being aborted).
"""

import asyncio
import itertools

import numpy as np
import pytest

from linkerd_tpu import native
from linkerd_tpu.protocol.h2.client import H2Client
from linkerd_tpu.protocol.h2.frames import ENHANCE_YOUR_CALM
from linkerd_tpu.protocol.h2.messages import H2Request, H2Response
from linkerd_tpu.protocol.h2.server import H2Server
from linkerd_tpu.protocol.h2.stream import DataFrame, H2Stream, StreamReset
from linkerd_tpu.router.service import FnService
from linkerd_tpu.streams import (
    ACTION_OBSERVE, ACTION_RST, FRAME_ANOMALY, FRAME_DATA,
    FRAME_WINDOW_UPDATE, H2FrameObserver, StreamSentinel, StreamTracker,
    fold_key, stream_feature_vector,
)
from linkerd_tpu.testing.faults import EchoBackend, LoopbackServer


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 30))


# ── C vs Python featurization parity ─────────────────────────────────────


@pytest.mark.skipif(not native.ensure_built(),
                    reason="native toolchain unavailable")
class TestFeaturizationParity:
    """The Python tracker must reproduce the engines' float32 EWMA
    arithmetic BIT-FOR-BIT: the in-plane scorer and the Python-side
    sentinel see the same stream, so their features must agree exactly
    or the two governors drift apart."""

    def trace(self, seed, n=500):
        rng = np.random.default_rng(seed)
        kinds = rng.integers(0, 3, size=n).astype(np.int32)
        gaps = (rng.random(n, dtype=np.float32) * 250.0).astype(np.float32)
        sizes = (rng.random(n, dtype=np.float32) * 65536.0).astype(
            np.float32)
        return kinds, gaps, sizes

    @pytest.mark.parametrize("seed", [7, 1234, 99991])
    def test_bit_identical_accumulators(self, seed):
        kinds, gaps, sizes = self.trace(seed)
        want = native.stream_accum(kinds, gaps, sizes)
        t = StreamTracker()
        for k, g, s in zip(kinds, gaps, sizes):
            t.frame(int(k), float(g), float(s))
        got = t.as_row()
        # uint32 view: equality of every BIT, not approximate closeness
        assert got.dtype == np.float32 and want.dtype == np.float32
        assert np.array_equal(got.view(np.uint32), want.view(np.uint32)), \
            f"C={want} py={got}"

    def test_data_only_trace_bit_identical(self):
        n = 256
        kinds = np.zeros(n, np.int32)
        gaps = np.linspace(0.5, 900.0, n).astype(np.float32)
        sizes = np.geomspace(1.0, 1e6, n).astype(np.float32)
        want = native.stream_accum(kinds, gaps, sizes)
        t = StreamTracker()
        for g, s in zip(gaps, sizes):
            t.frame(FRAME_DATA, float(g), float(s))
        assert np.array_equal(t.as_row().view(np.uint32),
                              want.view(np.uint32))


class TestStreamTracker:
    def test_frame_kinds_update_the_right_counters(self):
        t = StreamTracker()
        t.frame(FRAME_DATA, 10.0, 100.0)
        t.frame(FRAME_WINDOW_UPDATE, 5.0)
        t.frame(FRAME_ANOMALY, 1.0)
        assert (t.frames, t.data_frames, t.wu_frames, t.anomalies) == \
            (3, 1, 1, 1)
        assert t.bytes == 100

    def test_first_frame_seeds_the_ewmas(self):
        t = StreamTracker()
        t.frame(FRAME_DATA, 42.0, 1000.0)
        assert float(t.gap_ewma_ms) == 42.0
        assert float(t.bpf_ewma) == 1000.0
        assert float(t.gap_dev_ms) == 0.0

    def test_feature_vector_reflects_anomalies(self):
        t = StreamTracker()
        t.frame(FRAME_DATA, 10.0, 100.0)
        x_ok = stream_feature_vector(t, "/svc/a")
        t.frame(FRAME_ANOMALY, 1.0)
        x_bad = stream_feature_vector(t, "/svc/a")
        # status one-hot: 2xx while clean, 5xx once the stream misbehaves
        assert x_ok[2] == 1.0 and x_bad[5] == 1.0

    def test_fold_key_is_24_bit_and_never_zero(self):
        assert fold_key(0x1FFFFFF) == 0xFFFFFF
        assert fold_key(0x1000000) == 1  # folds to 0 -> reserved 1
        assert fold_key(42) == 42


# ── sentinel: governor + bounded table ───────────────────────────────────


class TestStreamSentinel:
    def mk(self, **kw):
        kw.setdefault("enter", 0.7)
        kw.setdefault("exit", 0.3)
        kw.setdefault("quorum", 2)
        kw.setdefault("dwell_s", 0.0)
        return StreamSentinel(**kw)

    def test_sick_edge_fires_rst_exactly_once(self):
        shed = []
        s = self.mk(on_rst=shed.append)
        t = 100.0
        for i in range(12):
            s.observe(5, 1.0, now=t + i)
        assert [e.key for e in shed] == [5]
        assert s.sick_transitions == 1 and s.actions_fired == 1

    def test_quorum_gates_flappy_scores(self):
        shed = []
        s = self.mk(on_rst=shed.append, quorum=3)
        t = 100.0
        # alternate high/low: EWMA never holds above enter for 3 in a row
        for i in range(30):
            s.observe(9, 1.0 if i % 2 == 0 else 0.0, now=t + i)
        assert shed == []

    def test_observe_action_never_fires_callbacks(self):
        shed = []
        s = self.mk(action=ACTION_OBSERVE, on_rst=shed.append)
        for i in range(12):
            got = s.observe(1, 1.0, now=100.0 + i)
        assert got is None or got == ACTION_OBSERVE
        assert shed == [] and s.sick_transitions == 1
        assert s.actions_fired == 0

    def test_unscored_samples_never_move_the_governor(self):
        shed = []
        s = self.mk(on_rst=shed.append)
        for i in range(20):
            s.observe(3, 1.0, scored=False, now=100.0 + i)
        assert shed == [] and s.sick_transitions == 0
        assert s.entry(3).samples == 20 and s.entry(3).scored == 0

    def test_hostile_churn_stays_bounded(self):
        # a client opening and abandoning streams must buy eviction of
        # the stalest CLOSED entries, never table growth
        s = self.mk(table_cap=64)
        for k in range(1, 10_001):
            s.open(k, now=float(k))
            s.observe(k, 0.1, now=float(k))
            s.close(k, now=float(k))
        assert len(s) <= 64
        assert s.evicted == 10_000 - 64
        # the governor table was forget()-ed along the way too
        assert len(s._gov.keys()) <= 64

    def test_live_streams_are_never_evicted(self):
        s = self.mk(table_cap=8)
        for k in range(1, 9):
            s.open(k, now=float(k))          # 8 live entries at cap
        for k in range(100, 200):
            s.open(k, now=float(k))
            s.close(k, now=float(k))         # churn through closed ones
        assert all(s.entry(k) is not None for k in range(1, 9)), \
            "a live stream was evicted"

    def test_route_pinned_at_open(self):
        # the specialist head scoring a stream is chosen at stream open
        # and must not flip mid-stream when routing changes
        s = self.mk()
        s.open(7, route="/svc/a", now=1.0)
        s.open(7, route="/svc/b", now=2.0)   # re-open: liveness refresh
        assert s.entry(7).route == "/svc/a"

    def test_ingest_rows_skips_request_rows_and_fires_on_streams(self):
        from linkerd_tpu.telemetry.linerate import (
            NATIVE_COL_KIND, NATIVE_COL_SCORE, NATIVE_COL_SCORED,
            NATIVE_COL_SEQ, NATIVE_COL_STREAM, NATIVE_ROW_WIDTH)
        shed = []
        s = self.mk(on_rst=shed.append)
        rows = np.zeros((14, NATIVE_ROW_WIDTH), np.float32)
        rows[0, NATIVE_COL_KIND] = 0.0       # request row: ignored
        rows[1, NATIVE_COL_KIND] = 1.0       # stream row, key 0: ignored
        for i in range(2, 14):
            rows[i, NATIVE_COL_KIND] = 1.0
            rows[i, NATIVE_COL_STREAM] = 77.0
            rows[i, NATIVE_COL_SEQ] = float(i * 8)
            rows[i, NATIVE_COL_SCORE] = 1.0
            rows[i, NATIVE_COL_SCORED] = 1.0
        fired = s.ingest_rows(rows, now=100.0)
        assert fired == 1 and [e.key for e in shed] == [77]
        assert s.entry(77).frames == 13 * 8
        assert len(s._streams) == 1

    def test_snapshot_shape_matches_native_streams_json(self):
        s = self.mk()
        s.open(3, route="/svc/x", now=1.0)
        s.observe(3, 0.4, now=2.0)
        snap = s.snapshot()
        assert snap["enabled"] is True and snap["count"] == 1
        ent = snap["by_stream"]["3"]
        for field in ("kind", "samples", "scored", "score_ewma",
                      "frames", "bytes", "sick", "live"):
            assert field in ent
        assert ent["route"] == "/svc/x"

    def test_score_ewma_matches_native_alpha(self):
        # alpha 1/4 in float32, same as the engines' gov_observe
        s = self.mk()
        want = np.float32(0.0)
        for i, score in enumerate([1.0, 0.5, 0.25, 1.0]):
            s.observe(1, score, now=100.0 + i)
            want = np.float32(want + np.float32(
                np.float32(0.25) * np.float32(np.float32(score) - want)))
        assert s.entry(1).score_ewma.view(np.uint32) == want.view(np.uint32)


# ── h2 frame observer (unit, stub connection) ────────────────────────────


class _StubConn:
    def __init__(self):
        self.sheds = []

    def shed_stream(self, sid, code=ENHANCE_YOUR_CALM):
        self.sheds.append((sid, code))
        return True


def mk_observer(scorer=None, action="rst", **sent_kw):
    sent_kw.setdefault("enter", 0.7)
    sent_kw.setdefault("exit", 0.3)
    sent_kw.setdefault("quorum", 2)
    sent_kw.setdefault("dwell_s", 0.0)
    sent = StreamSentinel(action=ACTION_RST if action == "rst"
                          else ACTION_OBSERVE, **sent_kw)
    keys = itertools.count(1)
    obs = H2FrameObserver(sent, next_skey=lambda: next(keys),
                          scorer=scorer, sample_every_frames=2,
                          min_gap_ms=0, action=action)
    conn = _StubConn()
    return obs.bind(conn), conn, sent


class TestH2FrameObserver:
    def test_sampling_cadence_respects_frame_budget(self):
        samples = []
        obs, _, _ = mk_observer(scorer=lambda x: samples.append(1) or 0.0)
        for i in range(10):
            obs.on_frame(1, FRAME_DATA, 10, now=float(i))
        assert len(samples) == 5  # every 2nd frame

    def test_min_gap_bounds_sampling_rate(self):
        samples = []
        obs, _, _ = mk_observer(scorer=lambda x: samples.append(1) or 0.0)
        obs.min_gap_s = 1.0
        for i in range(10):
            obs.on_frame(1, FRAME_DATA, 10, now=100.0 + i * 0.01)
        assert len(samples) == 1  # all frames inside one gap window

    def test_sick_stream_is_shed_and_closed(self):
        obs, conn, sent = mk_observer(scorer=lambda x: 1.0)
        for i in range(40):
            obs.on_frame(9, FRAME_DATA, 100, now=100.0 + i)
            if conn.sheds:
                break
        assert conn.sheds and conn.sheds[0][0] == 9
        assert conn.sheds[0][1] == ENHANCE_YOUR_CALM
        assert obs.sheds == 1
        assert 9 not in obs._slots  # slot retired with the stream

    def test_observe_action_detects_but_never_sheds(self):
        obs, conn, sent = mk_observer(scorer=lambda x: 1.0,
                                      action="observe")
        for i in range(40):
            obs.on_frame(9, FRAME_DATA, 100, now=100.0 + i)
        assert sent.sick_transitions == 1
        assert conn.sheds == [] and obs.sheds == 0

    def test_no_scorer_never_sheds(self):
        obs, conn, _ = mk_observer(scorer=None)
        for i in range(40):
            obs.on_frame(9, FRAME_DATA, 100, now=100.0 + i)
        assert conn.sheds == []

    def test_close_marks_all_streams_closed(self):
        obs, _, sent = mk_observer()
        for sid in (1, 3, 5):
            obs.on_frame(sid, FRAME_DATA, 10, now=100.0)
        obs.close()
        assert obs._slots == {}
        assert all(not e.live for e in sent._streams.values())

    def test_chaos_one_sick_stream_neighbors_untouched(self):
        # the chaos contract: the sick stream is detected and shed while
        # every neighbor completes — neighbor success must hold >= 0.99
        big = np.log1p(10_000.0)
        obs, conn, sent = mk_observer(
            scorer=lambda x: 1.0 if x[8] > big else 0.0)
        healthy = list(range(1, 41, 2))[:20]  # 20 odd sids
        sick = 99
        for i in range(40):
            now = 100.0 + i
            for sid in healthy:
                obs.on_frame(sid, FRAME_DATA, 64, now=now)
            obs.on_frame(sick, FRAME_DATA, 60_000, now=now)
        # only the sick stream is ever shed (the stub conn can't
        # actually stop it, so its re-created slot may trip again)
        assert conn.sheds and {s for s, _ in conn.sheds} == {sick}
        shed_neighbors = sum(1 for s, _ in conn.sheds if s != sick)
        assert 1.0 - shed_neighbors / len(healthy) >= 0.99


# ── e2e: mid-stream shed on the Python h2 data plane ─────────────────────


class TestH2MidStreamShed:
    def serve(self, scorer):
        sent = StreamSentinel(enter=0.7, exit=0.3, quorum=2, dwell_s=0.0)
        keys = itertools.count(1)

        def factory():
            return H2FrameObserver(
                sent, next_skey=lambda: next(keys), scorer=scorer,
                sample_every_frames=2, min_gap_ms=0, action="rst")

        async def handler(req: H2Request) -> H2Response:
            body, _ = await req.stream.read_all()
            return H2Response(status=200,
                              body=b"got:%d" % len(body))

        server = H2Server(FnService(handler),
                          stream_observer_factory=factory)
        return server, sent

    def test_sick_stream_shed_while_neighbors_complete(self):
        big = np.log1p(10_000.0)
        server, sent = self.serve(
            scorer=lambda x: 1.0 if x[8] > big else 0.0)

        async def one(client, sid_payload, frames):
            src = H2Stream()
            task = asyncio.ensure_future(client(H2Request(
                method="POST", path="/s", authority="t", stream=src)))
            for _ in range(frames):
                src.offer(DataFrame(sid_payload))
                await asyncio.sleep(0.001)
            src.offer(DataFrame(b"", eos=True))
            rsp = await task
            body, _ = await rsp.stream.read_all()
            return rsp.status, body

        async def go():
            await server.start()
            client = H2Client("127.0.0.1", server.bound_port)
            try:
                healthy = [one(client, b"x" * 64, 24) for _ in range(10)]
                sick = asyncio.ensure_future(
                    one(client, b"y" * 60_000, 24))
                results = await asyncio.gather(*healthy)
                with pytest.raises(StreamReset) as ei:
                    await sick
                assert ei.value.error_code == ENHANCE_YOUR_CALM
                # every neighbor finished clean: success 1.0 >= 0.99
                ok = sum(1 for st, body in results
                         if st == 200 and body == b"got:%d" % (64 * 24))
                assert ok / len(results) >= 0.99
                assert sent.sick_transitions == 1
            finally:
                await client.close()
                await server.close()

        run(go())

    def test_healthy_streams_only_no_actuation(self):
        server, sent = self.serve(scorer=lambda x: 0.0)

        async def go():
            await server.start()
            client = H2Client("127.0.0.1", server.bound_port)
            try:
                rsp = await client(H2Request(
                    method="POST", path="/s", authority="t",
                    body=b"k" * 4096))
                body, _ = await rsp.stream.read_all()
                assert body == b"got:4096"
                assert sent.sick_transitions == 0
                # the table saw the stream (DATA frames were tracked)
                assert len(sent) >= 1
            finally:
                await client.close()
                await server.close()

        run(go())


# ── h2 client GOAWAY drain (regression pin) ──────────────────────────────


class TestGoawayDrain:
    def test_inflight_stream_drains_not_aborts(self):
        """A GOAWAY'd singleton conn must keep serving its in-flight
        streams (at/below last_stream_id) while NEW requests ride a
        fresh connection; the old conn closes only once it empties."""
        gate = asyncio.Event()

        async def handler(req: H2Request) -> H2Response:
            if req.path == "/slow":
                await gate.wait()
            body, _ = await req.stream.read_all()
            return H2Response(status=200, body=b"ok:" + req.path.encode())

        async def go():
            server = await H2Server(FnService(handler)).start()
            client = H2Client("127.0.0.1", server.bound_port)
            try:
                # warm the conn, then hold one stream in flight
                await (await client(H2Request(
                    path="/warm", authority="t"))).stream.read_all()
                old = client._conn
                slow = asyncio.ensure_future(
                    client(H2Request(path="/slow", authority="t")))
                while old.active_streams == 0:
                    await asyncio.sleep(0.01)
                # the peer says goodbye covering the in-flight stream
                old.goaway_received = True
                # a new request must NOT abort the in-flight one: it
                # rides a fresh conn; the old conn parks for drain
                r2 = await client(H2Request(path="/new", authority="t"))
                b2, _ = await r2.stream.read_all()
                assert b2 == b"ok:/new"
                assert client._conn is not old
                assert old in client._draining
                assert not old.is_closed and not slow.done(), \
                    "drain must not abort in-flight streams"
                # let the held stream finish on the OLD conn
                gate.set()
                rsp = await slow
                body, _ = await rsp.stream.read_all()
                assert body == b"ok:/slow"
                # ...after which the drain watcher retires it
                for _ in range(100):
                    if old.is_closed and old not in client._draining:
                        break
                    await asyncio.sleep(0.02)
                assert old.is_closed and old not in client._draining
            finally:
                await client.close()
                await server.close()

        run(go())

    def test_close_tears_down_draining_conns(self):
        gate = asyncio.Event()

        async def handler(req: H2Request) -> H2Response:
            if req.path == "/slow":
                await gate.wait()
            body, _ = await req.stream.read_all()
            return H2Response(status=200, body=b"ok")

        async def go():
            server = await H2Server(FnService(handler)).start()
            client = H2Client("127.0.0.1", server.bound_port)
            try:
                await (await client(H2Request(
                    path="/a", authority="t"))).stream.read_all()
                old = client._conn
                slow = asyncio.ensure_future(
                    client(H2Request(path="/slow", authority="t")))
                while old.active_streams == 0:
                    await asyncio.sleep(0.01)
                old.goaway_received = True
                await (await client(H2Request(
                    path="/b", authority="t"))).stream.read_all()
                # the held stream keeps the old conn parked in drain
                assert old in client._draining
            finally:
                # close() with the gate still shut: the draining conn
                # must be torn down, not leaked
                await client.close()
                gate.set()
                await server.close()
            assert client._draining == [] and old.is_closed
            slow.cancel()
            try:
                await slow
            except (asyncio.CancelledError, Exception):
                pass

        run(go())


# ── h1 tunnels: 101 Upgrade / CONNECT byte relay ─────────────────────────


async def _echo_upstream():
    """A raw upstream that speaks 101-upgrade and CONNECT, then echoes
    every byte prefixed with ``echo:``."""

    async def on_conn(reader, writer):
        data = b""
        while b"\r\n\r\n" not in data:
            chunk = await reader.read(1024)
            if not chunk:
                return
            data += chunk
        head = data.split(b"\r\n", 1)[0]
        if head.startswith(b"CONNECT"):
            writer.write(b"HTTP/1.1 200 Connection established\r\n\r\n")
        elif b"no-upgrade" in data:
            # misbehaving upstream: 101 nobody asked for
            writer.write(b"HTTP/1.1 101 Switching Protocols\r\n"
                         b"Upgrade: echo\r\nConnection: Upgrade\r\n\r\n")
        else:
            writer.write(b"HTTP/1.1 101 Switching Protocols\r\n"
                         b"Upgrade: echo\r\nConnection: Upgrade\r\n\r\n")
        await writer.drain()
        while True:
            chunk = await reader.read(65536)
            if not chunk:
                break
            writer.write(b"echo:" + chunk)
            await writer.drain()

    return await LoopbackServer(on_conn).start()


class TestH1Tunnels:
    async def _front(self):
        from linkerd_tpu.protocol.http.client import HttpClient
        from linkerd_tpu.protocol.http.server import HttpServer

        upstream = await _echo_upstream()
        client = HttpClient("127.0.0.1", upstream.bound_port,
                            max_connections=2)
        front = await HttpServer(client).start()
        return upstream, client, front

    async def _raw(self, port, head: bytes):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(head)
        await writer.drain()
        data = b""
        while b"\r\n\r\n" not in data:
            chunk = await reader.read(1024)
            assert chunk, f"closed before response head: {data!r}"
            data += chunk
        head_end = data.index(b"\r\n\r\n") + 4
        return reader, writer, data[:head_end], data[head_end:]

    def test_websocket_style_upgrade_tunnels_bytes(self):
        async def go():
            upstream, client, front = await self._front()
            try:
                reader, writer, head, rest = await self._raw(
                    front.bound_port,
                    b"GET /ws HTTP/1.1\r\nHost: x\r\n"
                    b"Connection: Upgrade\r\nUpgrade: echo\r\n\r\n")
                assert b"101" in head.split(b"\r\n")[0]
                writer.write(b"hello")
                await writer.drain()
                got = rest
                while len(got) < len(b"echo:hello"):
                    got += await reader.read(1024)
                assert got == b"echo:hello"
                writer.close()
                # the relay ends and the pooled slot is released
                for _ in range(100):
                    if client._n_open == 0:
                        break
                    await asyncio.sleep(0.02)
                assert client._n_open == 0
            finally:
                await front.close()
                await client.close()
                await upstream.close()

        run(go())

    def test_connect_tunnels_bytes(self):
        async def go():
            upstream, client, front = await self._front()
            try:
                reader, writer, head, rest = await self._raw(
                    front.bound_port,
                    b"CONNECT example.test:443 HTTP/1.1\r\n"
                    b"Host: example.test:443\r\n\r\n")
                assert b" 200" in head.split(b"\r\n")[0]
                writer.write(b"tls-ish bytes")
                await writer.drain()
                got = rest
                while len(got) < len(b"echo:tls-ish bytes"):
                    got += await reader.read(1024)
                assert got == b"echo:tls-ish bytes"
                writer.close()
            finally:
                await front.close()
                await client.close()
                await upstream.close()

        run(go())

    def test_unsolicited_101_is_a_gateway_error(self):
        # the upstream switches protocols without being asked: the
        # front must answer 502, not relay bytes the client can't frame
        async def go():
            upstream, client, front = await self._front()
            try:
                _, writer, head, _ = await self._raw(
                    front.bound_port,
                    b"GET /no-upgrade HTTP/1.1\r\nHost: x\r\n\r\n")
                assert b"502" in head.split(b"\r\n")[0]
                writer.close()
                for _ in range(100):
                    if client._n_open == 0:
                        break
                    await asyncio.sleep(0.02)
                assert client._n_open == 0  # pool slot not leaked
            finally:
                await front.close()
                await client.close()
                await upstream.close()

        run(go())

    def test_plain_requests_still_pool(self):
        # the tunnel branch must not disturb ordinary keep-alive reuse
        async def go():
            from linkerd_tpu.protocol.http.client import HttpClient
            from linkerd_tpu.protocol.http.message import Request
            upstream = await EchoBackend().start()
            client = HttpClient("127.0.0.1", upstream.bound_port)
            try:
                for _ in range(3):
                    rsp = await client(Request(method="GET", uri="/"))
                    assert rsp.status == 200 and rsp.body == b"ok"
                assert client._n_open == 1  # one conn, reused
            finally:
                await client.close()
                await upstream.close()

        run(go())


# ── admin surface ────────────────────────────────────────────────────────


class TestStreamsAdminEndpoint:
    def test_streams_json_exposes_sentinel_state(self):
        from linkerd_tpu.admin.handlers import linkerd_admin_handlers
        from linkerd_tpu.admin.server import AdminServer
        from linkerd_tpu.linker import load_linker
        from linkerd_tpu.protocol.http.client import HttpClient
        from linkerd_tpu.protocol.http.message import Request

        import json

        cfg = """
routers:
- protocol: h2
  label: grpc
  dtab: |
    /svc => /$/inet/127.0.0.1/1 ;
  servers: [{port: 0}]
  streamScoring:
    sampleEveryFrames: 4
    enter: 0.9
    exit: 0.6
"""

        async def go():
            linker = load_linker(cfg)
            await linker.start()
            admin = AdminServer(linker.metrics, linker.config_dict,
                                port=0)
            admin.add_handlers(linkerd_admin_handlers(linker))
            await admin.start()
            try:
                client = HttpClient("127.0.0.1", admin.bound_port)
                rsp = await client(Request(method="GET",
                                           uri="/streams.json"))
                assert rsp.status == 200
                doc = json.loads(rsp.body)
                sent = doc["grpc"]["sentinel"]
                assert sent["enabled"] is True
                assert sent["action"] == "rst" and sent["count"] == 0
                await client.close()
            finally:
                await admin.close()
                await linker.close()

        run(go())


# ── native engine config surface (no traffic) ────────────────────────────


@pytest.mark.skipif(not native.ensure_built(),
                    reason="native toolchain unavailable")
class TestNativeStreamConfig:
    def test_stream_cfg_accepted_and_snapshot_enabled(self):
        eng = native.FastPathEngine()
        eng.set_stream_cfg(enabled=True, sample_every_frames=4,
                           min_gap_ms=5, table_cap=128, enter=0.8,
                           exit=0.4, quorum=2, dwell_ms=100,
                           action="observe")
        snap = eng.streams()
        assert snap.get("enabled") and snap.get("count", 0) == 0
        eng.close()

    def test_bad_stream_action_rejected(self):
        eng = native.FastPathEngine()
        with pytest.raises(ValueError):
            eng.set_stream_cfg(action="nuke")
        eng.close()

    def test_tunnel_guard_is_h1_only(self):
        eng = native.FastPathEngine()
        eng.set_tunnel_guard(idle_ms=1000, max_bytes=1 << 20)
        eng.close()
        h2 = native.H2FastPathEngine()
        with pytest.raises(RuntimeError):
            h2.set_tunnel_guard(idle_ms=1000)
        h2.close()
