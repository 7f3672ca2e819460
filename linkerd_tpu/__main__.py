"""linkerd_tpu CLI: ``python -m linkerd_tpu path/to/config.yaml``.

Reference parity: linkerd/main/.../Main.scala:25-49 — load config, build the
linker, serve admin + routers + telemeters, await signals, drain gracefully.
"""

from __future__ import annotations

import asyncio
import logging
import signal
import sys

log = logging.getLogger("linkerd_tpu")


async def amain(config_text: str) -> None:
    # imported here, not at module level: linkerd_tpu.linker pulls in
    # jax, and main() must place the compile cache before that happens
    from linkerd_tpu.admin.server import AdminServer
    from linkerd_tpu.linker import DEFAULT_ADMIN_PORT, load_linker

    linker = load_linker(config_text)
    await linker.start()

    admin_spec = linker.spec.admin
    admin = AdminServer(
        linker.metrics, linker.config_dict,
        host=admin_spec.ip if admin_spec else "127.0.0.1",
        port=admin_spec.port if admin_spec else DEFAULT_ADMIN_PORT)
    from linkerd_tpu.admin.handlers import linkerd_admin_handlers
    admin.add_handlers(linkerd_admin_handlers(linker))
    for t in linker.telemeters:
        admin.add_handlers(t.admin_handlers())
    await admin.start()

    identifier_server = None
    if admin_spec is not None and admin_spec.httpIdentifierPort is not None:
        from linkerd_tpu.admin.handlers import mk_identifier_server
        identifier_server = await mk_identifier_server(
            linker, admin_spec.httpIdentifierPort, host=admin_spec.ip)
        log.info("identifier debug server on %s:%s", admin_spec.ip,
                 identifier_server.bound_port)

    from linkerd_tpu.core.tasks import monitor
    telemeter_tasks = [
        monitor(asyncio.create_task(t.run()),
                what=f"telemeter-{type(t).__name__}")
        for t in linker.telemeters]

    # usage telemetry is opt-out (ref: Linker.scala:116-125 implicit
    # telemeters; disable with `usage: {enabled: false}`)
    usage_cfg = linker.spec.usage or {}
    if usage_cfg.get("enabled", True):
        from linkerd_tpu.telemetry.usage import UsageDataTelemeter
        usage = UsageDataTelemeter(
            linker.spec, orgId=str(usage_cfg.get("orgId", "")))
        log.info("anonymized usage telemetry enabled -> %s "
                 "(disable with `usage: {enabled: false}`)",
                 usage._host)
        telemeter_tasks.append(monitor(asyncio.create_task(usage.run()),
                                       what="telemeter-usage"))

    for r in linker.routers:
        log.info("router %s serving on %s", r.label, r.server_ports)
    log.info("admin serving on %s:%s", admin.host, admin.bound_port)

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()

    log.info("shutting down")
    for task in telemeter_tasks:
        task.cancel()
    if identifier_server is not None:
        await identifier_server.close()
    await admin.close()
    await linker.close()


def main() -> None:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s %(message)s")
    if len(sys.argv) != 2:
        print("usage: python -m linkerd_tpu <config.yaml>", file=sys.stderr)
        raise SystemExit(64)
    with open(sys.argv[1], "r", encoding="utf-8") as f:
        text = f.read()
    from linkerd_tpu.compile_cache import place_compile_cache
    log.info("jax compile cache: %s", place_compile_cache())
    asyncio.run(amain(text))


if __name__ == "__main__":
    main()
