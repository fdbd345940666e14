"""Daemon entry point: `python -m gubernator_tpu.cmd.daemon [--config f]`
(reference cmd/gubernator/main.go:41-100). An embedder that sets what no
environment variable can (`DaemonConfig.store`, `.loader`; the
reference's `Config.Store`) calls `serve(conf)` with its own
configuration: everything after the configuration is made."""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import signal


def main() -> None:
    parser = argparse.ArgumentParser(description="gubernator-tpu daemon")
    parser.add_argument("--config", default=None, help="KEY=VALUE config file")
    parser.add_argument("--debug", action="store_true")
    args = parser.parse_args()

    from gubernator_tpu.service.envconfig import setup_daemon_config

    # Config FIRST so --config file keys (injected into the env) are seen
    # by the log settings too (reference config.go:268-310 order).
    serve(setup_daemon_config(args.config), debug=args.debug)


def serve(conf, debug: bool = False) -> None:
    """Run one daemon under `conf` until SIGINT/SIGTERM, then drain:
    the compile cache, logging, the trace level, `Daemon.spawn`, the
    signal handlers and the graceful drain. Blocks; call it from the
    main thread (the signal handlers need it)."""
    from gubernator_tpu.utils.compilecache import enable_compile_cache

    # Persistent XLA cache: a restarted daemon deserializes its decide
    # kernels instead of recompiling — serving within seconds of exec,
    # like the reference's Go daemon.
    enable_compile_cache()

    from gubernator_tpu.service.daemon import Daemon

    # GUBER_LOG_LEVEL / GUBER_LOG_FORMAT=json / GUBER_DEBUG or --debug
    # (reference config.go:286-310)
    level = (
        logging.DEBUG
        if debug or conf.debug
        else getattr(logging, conf.log_level.upper(), logging.INFO)
    )
    if conf.log_format.lower() == "json":

        class _Json(logging.Formatter):
            def format(self, record):
                return json.dumps(
                    {
                        "ts": self.formatTime(record),
                        "level": record.levelname.lower(),
                        "logger": record.name,
                        "msg": record.getMessage(),
                    }
                )

        handler = logging.StreamHandler()
        handler.setFormatter(_Json())
        logging.basicConfig(level=level, handlers=[handler])
    else:
        logging.basicConfig(
            level=level, format="%(asctime)s %(levelname)s %(name)s %(message)s"
        )

    # Span verbosity is process-global, so only the CLI entry point sets
    # it (GUBER_TRACING_LEVEL; reference config.go:717-752).
    from gubernator_tpu.utils import tracing

    tracing.set_trace_level(conf.trace_level)

    async def run() -> None:
        d = await Daemon.spawn(conf)
        logging.info(
            "gubernator-tpu listening: grpc=%s http=%s", d.grpc_address, d.http_address
        )
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        # Graceful drain, not teardown (docs/robustness.md): /readyz
        # flips to `draining`, in-flight RPCs and the engine queue finish
        # inside GUBER_DRAIN_TIMEOUT, replication queues flush, owned
        # keys hand off to ring successors, THEN the listeners die.
        logging.info(
            "signal received: draining (budget %.1fs) — queues flush and "
            "owned keys hand off before teardown",
            getattr(conf, "drain_timeout_s", 5.0),
        )
        await d.close()
        logging.info("drain complete; daemon stopped")

    asyncio.run(run())


if __name__ == "__main__":
    main()
