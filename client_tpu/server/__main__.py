"""CLI entry point: ``python -m client_tpu.server``.

Starts the KServe v2 HTTP + gRPC front-ends with the built-in fixture models
and (optionally) a model repository directory of ``<name>/model.py`` models.
"""

import argparse
import asyncio


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="client_tpu.server",
        description="TPU-native KServe v2 inference server (JAX backend)",
    )
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--http-port", type=int, default=8000)
    parser.add_argument("--grpc-port", type=int, default=8001)
    parser.add_argument(
        "--model-repository",
        default=None,
        help="directory of <name>/model.py models (python_backend analogue)",
    )
    parser.add_argument(
        "--no-builtin-models",
        action="store_true",
        help="skip the built-in fixture models (simple, identity_*, repeat)",
    )
    parser.add_argument(
        "--zoo-models",
        action="store_true",
        help="also register the model-zoo adapters (resnet, llm_decode)",
    )
    parser.add_argument(
        "--max-workers", type=int, default=32, help="model execution threads"
    )
    parser.add_argument(
        "--drain-timeout",
        type=float,
        default=30.0,
        help="graceful-shutdown budget in seconds: on SIGTERM/SIGINT the "
        "server flips /v2/health/ready to 503 (liveness stays up), "
        "rejects new inferences with 503/UNAVAILABLE, and waits this "
        "long for in-flight and queued work before closing — the "
        "rolling-restart contract load balancers rely on",
    )
    parser.add_argument(
        "--platform",
        default=None,
        help="force the JAX platform (e.g. 'cpu', 'tpu'); overrides "
        "JAX_PLATFORMS — useful for CPU dev loops on a host with a chip",
    )
    parser.add_argument(
        "--grpc-frontend",
        choices=["native", "aio", "auto"],
        default="auto",
        help="gRPC front-end implementation: 'native' (C++ h2 server, the "
        "fast path), 'aio' (grpc.aio), 'auto' = native when built",
    )
    parser.add_argument(
        "--grpc-tls-cert",
        default=None,
        help="PEM certificate chain: the native gRPC front-end terminates "
        "TLS itself (grpcs, ALPN h2); requires --grpc-tls-key",
    )
    parser.add_argument(
        "--grpc-tls-key",
        default=None,
        help="PEM private key for --grpc-tls-cert",
    )
    args = parser.parse_args(argv)

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    from client_tpu.compile_cache import enable_compile_cache
    from client_tpu.observability.profiling import PROCESS

    # before the repository: model warmups are the first compilations,
    # and the process's record counts every one (setup.compile_s)
    enable_compile_cache()
    PROCESS.listen()

    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import build_repository

    repository = build_repository(
        args.model_repository,
        builtin=not args.no_builtin_models,
        zoo=args.zoo_models,
    )
    core = ServerCore(repository, max_workers=args.max_workers)

    async def serve() -> None:
        from client_tpu.server.http_server import serve_http

        impl = args.grpc_frontend
        if impl == "auto":
            from client_tpu.server.native_frontend import native_available

            impl = "native" if native_available() else "aio"

        http_runner = await serve_http(core, args.host, args.http_port)
        native_frontend = None
        grpc_server = None
        if impl == "native":
            from client_tpu.server.native_frontend import serve_grpc_native

            native_frontend, grpc_port = await serve_grpc_native(
                core,
                args.host,
                args.grpc_port,
                tls_cert=args.grpc_tls_cert,
                tls_key=args.grpc_tls_key,
            )
        else:
            if args.grpc_tls_cert:
                raise SystemExit(
                    "--grpc-tls-cert requires the native gRPC front-end"
                )
            from client_tpu.server.grpc_server import serve_grpc

            grpc_server, grpc_port = await serve_grpc(
                core, args.host, args.grpc_port
            )
        # Lifecycle events go through the structured logger (JSON lines
        # on stderr by default, the log_file setting elsewhere) so
        # orchestrators can parse them instead of scraping prose.
        core.logger.info(
            "server_started",
            host=args.host,
            http_port=http_runner.addresses[0][1],
            grpc_port=grpc_port,
            grpc_frontend=impl,
        )
        import signal

        stop_event = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, stop_event.set)
            except NotImplementedError:  # pragma: no cover - non-POSIX
                pass
        try:
            await stop_event.wait()
        finally:
            # Graceful half first: readiness false + reject new work while
            # in-flight and queued requests finish inside --drain-timeout;
            # only then do the front-ends close. core.drain() emits the
            # drain_started / drain_deadline_expired / drain_completed
            # events through the structured logger itself.
            drained = await core.drain(args.drain_timeout)
            core.logger.info("server_stopping", drained=drained)
            if native_frontend is not None:
                native_frontend.stop()
            if grpc_server is not None:
                await grpc_server.stop(grace=2)
            await http_runner.cleanup()

    try:
        asyncio.run(serve())
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
