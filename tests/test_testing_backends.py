"""The tests' own servers (``linkerd_tpu/testing``) must always tear down.

Since Python 3.12 ``asyncio.Server.wait_closed()`` waits until every
accepted connection's transport is closed. A backend whose handler
returns on EOF without closing its writer, or that waits before it drops
the connections it holds, turns a test's ``finally`` into its timeout
(ten tier-1 e2es ended that way until PR 30). Every case here leaves a
client connected and gives the server one second to close.
"""

import asyncio

import pytest

from linkerd_tpu.testing.faults import (
    BlackholeServer, EchoBackend, LoopbackServer,
)
from linkerd_tpu.testing.fleet import FaultableCluster, WanProxy
from linkerd_tpu.testing.zkserver import FakeZkServer

REQUEST = b"GET / HTTP/1.1\r\nHost: svc\r\n\r\n"


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 20))


async def _request(port: int):
    """One request on a connection that is then LEFT OPEN (keep-alive)."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(REQUEST)
    await writer.drain()
    head = await reader.readuntil(b"\r\n\r\n")
    n = int(head.lower().split(b"content-length:")[1].split(b"\r\n")[0])
    return reader, writer, head, await reader.readexactly(n)


async def _returns_without_closing(reader, writer):
    """The handler the engine e2es had: ends on EOF, writer left open."""
    await reader.read(65536)


@pytest.mark.parametrize("make", [
    EchoBackend,
    BlackholeServer,
    lambda: LoopbackServer(_returns_without_closing),
    lambda: FaultableCluster("A"),
    FakeZkServer,
], ids=["echo", "blackhole", "handler-leaves-writer-open",
        "faultable-cluster", "fake-zk"])
def test_close_returns_with_a_client_still_connected(make):
    async def go():
        server = await make().start()
        _, writer = await asyncio.open_connection(
            "127.0.0.1", server.bound_port)
        writer.write(b"x")  # accepted and being served, never hung up
        await writer.drain()
        await asyncio.sleep(0.05)
        try:
            await asyncio.wait_for(server.close(), 1.0)
        finally:
            writer.close()

    run(go())


def test_echo_backend_answers_keep_alive_and_exits_under_it():
    async def go():
        async with EchoBackend() as backend:
            reader, writer, head, body = await _request(backend.bound_port)
            assert head.startswith(b"HTTP/1.1 200 OK") and body == b"ok"
            writer.write(REQUEST)  # same connection: keep-alive
            await writer.drain()
            await reader.readuntil(b"\r\n\r\nok")
            assert backend.connections == 1
            t0 = asyncio.get_running_loop().time()
        # the block's exit closed the server under the open connection
        assert asyncio.get_running_loop().time() - t0 < 1.0
        assert await reader.read() == b""
        writer.close()

    run(go())


def test_handler_that_returns_on_eof_has_its_writer_closed():
    async def go():
        async with LoopbackServer(_returns_without_closing) as server:
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.bound_port)
            writer.write_eof()  # the peer's EOF ends the handler
            assert await asyncio.wait_for(reader.read(), 1.0) == b""
            assert not server._writers
            writer.close()

    run(go())


def test_wan_proxy_partition_severs_a_live_pipe_within_a_second():
    async def go():
        async with EchoBackend() as backend:
            proxy = await WanProxy(backend.bound_port).start()
            try:
                reader, writer, _, body = await _request(proxy.port)
                assert body == b"ok"  # the pipe is up and stays open
                await asyncio.wait_for(proxy.partition(), 1.0)
                assert await asyncio.wait_for(reader.read(), 1.0) == b""
                writer.close()
                with pytest.raises(OSError):
                    await asyncio.open_connection("127.0.0.1", proxy.port)
                await proxy.heal()
                _, writer, _, body = await _request(proxy.port)
                assert body == b"ok"
                writer.close()
            finally:
                await asyncio.wait_for(proxy.close(), 1.0)

    run(go())
