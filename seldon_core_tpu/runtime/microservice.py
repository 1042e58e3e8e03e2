"""Microservice CLI — wrap one user component as a serving process.

Equivalent of the reference's ``seldon-core-microservice`` entrypoint
(reference: python/seldon_core/microservice.py:186-375):

    seldon-tpu-microservice mypkg.MyModel --api BOTH --http-port 9000 \
        --grpc-port 5000 --service-type MODEL \
        --parameters '[{"name":"n","value":"2","type":"FLOAT"}]'

Differences from the reference, by design:

* one process serves REST **and** gRPC concurrently on one asyncio loop
  (the reference forces a choice of one transport per container);
* scale-out is replica processes managed by the control plane rather
  than gunicorn forks — TPU devices can't be shared by forked workers;
* component state restore/persist uses the checkpoint subsystem instead
  of whole-object pickling to Redis.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import logging
import os
import signal
import sys
from typing import Any, Dict, List, Optional

from seldon_core_tpu.runtime import knobs

from seldon_core_tpu.runtime.params import (
    PARAMETERS_ENV_NAME,
    SERVICE_PORT_ENV_NAME,
    UNIT_ID_ENV_NAME,
    parse_parameters,
)

logger = logging.getLogger(__name__)

SERVICE_TYPES = (
    "MODEL",
    "ROUTER",
    "TRANSFORMER",
    "OUTPUT_TRANSFORMER",
    "COMBINER",
    "OUTLIER_DETECTOR",
)


def import_component(dotted: str, **kwargs: Any) -> Any:
    """Instantiate a component with typed parameter kwargs.

    Accepts ``pkg.module.Class`` or the reference s2i contract's bare
    name ``MyModel`` — module ``MyModel`` defining ``class MyModel``
    (reference: python/seldon_core/microservice.py interface_name).
    """
    module_name, _, class_name = dotted.rpartition(".")
    if not module_name:
        module_name = class_name = dotted
    sys.path.insert(0, os.getcwd())
    module = importlib.import_module(module_name)
    cls = getattr(module, class_name)
    return cls(**kwargs)


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(description="seldon-core-tpu microservice")
    parser.add_argument("component", help="dotted path module.Class of the user component")
    parser.add_argument("--api", choices=("REST", "GRPC", "BOTH"), default="BOTH")
    parser.add_argument("--service-type", choices=SERVICE_TYPES, default="MODEL")
    parser.add_argument(
        "--http-port",
        type=int,
        default=int(os.environ.get(SERVICE_PORT_ENV_NAME, 9000)),
    )
    parser.add_argument("--grpc-port", type=int, default=5000)
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument(
        "--parameters", default=os.environ.get(PARAMETERS_ENV_NAME, "[]"),
        help="typed parameter list JSON",
    )
    parser.add_argument("--unit-id", default=os.environ.get(UNIT_ID_ENV_NAME, ""))
    parser.add_argument("--persistence", action="store_true", help="periodically checkpoint component state")
    parser.add_argument("--persistence-dir", default=os.environ.get("PERSISTENCE_DIR", "/tmp/seldon-tpu-state"))
    parser.add_argument("--persistence-period-s", type=float, default=60.0)
    parser.add_argument("--ssl-cert", default=os.environ.get("SELDON_TLS_CERT", ""),
                        help="PEM certificate; enables TLS on REST and gRPC")
    parser.add_argument("--ssl-key", default=os.environ.get("SELDON_TLS_KEY", ""))
    parser.add_argument("--ssl-ca", default=os.environ.get("SELDON_TLS_CA", ""),
                        help="peer-verification CA (with --ssl-require-client-auth: mTLS)")
    parser.add_argument("--ssl-require-client-auth", action="store_true",
                        default=os.environ.get("SELDON_TLS_REQUIRE_CLIENT_AUTH", "0") == "1")
    parser.add_argument("--tracing", action="store_true", default=bool(int(os.environ.get("TRACING", "0"))))
    parser.add_argument("--log-level", default=os.environ.get("SELDON_LOG_LEVEL", "INFO"))
    parser.add_argument(
        "--platform", default=knobs.raw("SELDON_TPU_PLATFORM", ""),
        help="force the jax platform (cpu|tpu|...) through jax.config "
        "before the backend initialises; JAX_PLATFORMS in the environment "
        "does the same",
    )
    return parser.parse_args(argv)


async def run_servers(
    user_model: Any,
    api: str = "BOTH",
    host: str = "0.0.0.0",
    http_port: int = 9000,
    grpc_port: int = 5000,
    unit_id: str = "",
    shutdown_event: Optional[asyncio.Event] = None,
    tls=None,
) -> None:
    """Serve until `shutdown_event` (or forever)."""
    from seldon_core_tpu.runtime import grpc_server, rest

    runner = None
    server = None
    secure = " (TLS)" if tls is not None and tls.enabled else ""
    if api in ("REST", "BOTH"):
        app = rest.build_app(user_model, unit_id=unit_id)
        runner = await rest.serve(app, host=host, port=http_port, tls=tls)
        logger.info("REST serving on %s:%d%s", host, http_port, secure)
    if api in ("GRPC", "BOTH"):
        server = await grpc_server.serve(
            user_model, port=grpc_port, host=host, unit_id=unit_id, tls=tls
        )
        logger.info("gRPC serving on %s:%d%s", host, grpc_port, secure)

    if shutdown_event is None:
        shutdown_event = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, shutdown_event.set)
            except NotImplementedError:  # pragma: no cover
                pass
    await shutdown_event.wait()

    # drain-then-exit (r12): components exposing drain() — StreamingLM's
    # generation engine — journal their live streams FIRST, so in-flight
    # handlers unblock with a clean 503 DRAINING immediately (instead of
    # hanging into the gRPC grace window) and the respawned worker
    # replays the journal (SELDON_TPU_DRAIN_JOURNAL, pinned per worker
    # by the supervisor) through the ordinary submit path.
    drain_fn = getattr(user_model, "drain", None)
    if callable(drain_fn):
        try:
            await asyncio.get_running_loop().run_in_executor(None, drain_fn)
        except Exception:  # noqa: BLE001 — drain is best-effort; exit anyway
            logger.exception("component drain failed during shutdown")

    if server is not None:
        await server.stop(grace=20.0)
    if runner is not None:
        await runner.cleanup()


def start_custom_service(user_model: Any):
    """Run the component's optional ``custom_service()`` side loop on a
    daemon thread (the reference runs it as a second process,
    reference: microservice.py:29-47,363-368 — a thread gives the same
    lifetime without the fork). Returns the thread, or None."""
    if not hasattr(user_model, "custom_service"):
        return None
    import threading

    thread = threading.Thread(
        target=user_model.custom_service, name="custom-service", daemon=True
    )
    thread.start()
    return thread


def main(argv: Optional[List[str]] = None) -> None:
    args = parse_args(argv)
    logging.basicConfig(level=args.log_level.upper(), format="%(asctime)s %(name)s %(levelname)s %(message)s")

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from seldon_core_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    if args.unit_id:
        # export the unit identity for in-process consumers that have
        # no CLI access (the telemetry ring's replica_id): supervised
        # workers get --unit-id on argv, not in their environment
        os.environ.setdefault(UNIT_ID_ENV_NAME, args.unit_id)

    kwargs = parse_parameters(json.loads(args.parameters))
    user_model = import_component(args.component, **kwargs)

    if args.tracing:
        from seldon_core_tpu.utils.tracing import setup_tracing

        # SELDON_TPU_TRACE_EXPORT: JSONL span sink for this process —
        # the per-process artifact tools/profile_trace_stitch.py reads
        # to reassemble one cross-process trace (OTLP export rides the
        # standard OTEL_EXPORTER_OTLP_ENDPOINT env either way)
        setup_tracing(
            service_name=args.unit_id or args.component,
            export_path=knobs.raw("SELDON_TPU_TRACE_EXPORT") or None,
        )

    persistence_thread = None
    if args.persistence:
        from seldon_core_tpu.utils.persistence import PersistenceManager

        manager = PersistenceManager(args.persistence_dir, args.unit_id or args.component)
        manager.restore(user_model)
        persistence_thread = manager.start_background(user_model, period_s=args.persistence_period_s)

    if hasattr(user_model, "load"):
        user_model.load()

    start_custom_service(user_model)

    tls = None
    if args.ssl_cert or args.ssl_key:
        # key-without-cert must fail loudly (TlsConfig raises), not
        # silently serve the plaintext the operator thinks is TLS
        from seldon_core_tpu.utils.tls import TlsConfig

        tls = TlsConfig(
            cert_file=args.ssl_cert,
            key_file=args.ssl_key,
            ca_file=args.ssl_ca,
            require_client_auth=args.ssl_require_client_auth,
        )

    try:
        asyncio.run(
            run_servers(
                user_model,
                api=args.api,
                host=args.host,
                http_port=args.http_port,
                grpc_port=args.grpc_port,
                unit_id=args.unit_id,
                tls=tls,
            )
        )
    finally:
        if persistence_thread is not None:
            persistence_thread.stop()


if __name__ == "__main__":
    main()
