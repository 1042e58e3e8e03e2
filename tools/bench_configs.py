"""Benchmark the five reference configs end-to-end.

BASELINE.md lists the five benchmark configurations the reference is
measured on (iris-style single model over REST, tabular regressor over
gRPC, ResNet-50, the MAB two-model graph with feedback, and the
combiner + transformer pipeline).  This harness deploys each config's
example spec through the real control plane, serves it on real
loopback ports, drives it with the client SDK under closed-loop load,
and prints one JSON line per config plus a summary line.

    python tools/bench_configs.py --quick            # CPU smoke, no resnet
    python tools/bench_configs.py --seconds 10       # the full matrix

The headline driver benchmark stays `bench.py`; this is the breadth
harness for the config matrix (reference analogue: the per-server
sample deployments under servers/*/samples + helm-charts/seldon-mab).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from typing import Any, Dict, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# name -> (spec file, request shape, transport, extras)
CONFIGS = {
    "single_model_rest": ("examples/single_model.yaml", (1, 4), "rest", {}),
    "tabular_grpc": ("examples/tabular_grpc.yaml", (1, 13), "grpc", {}),
    "resnet50_grpc": ("examples/resnet50_tpu.yaml", (1, 224, 224, 3), "grpc", {"dtype": "uint8"}),
    "mab_feedback": ("examples/mab_abtest.yaml", (1, 4), "rest", {"feedback": True}),
    "combiner_pipeline": ("examples/combiner_pipeline.yaml", (1, 4), "rest", {}),
}


async def _bench_one(
    name: str,
    spec_path: str,
    shape,
    transport: str,
    extras: Dict[str, Any],
    seconds: float,
    concurrency: int,
) -> Dict[str, Any]:
    import numpy as np

    from seldon_core_tpu.client.client import SeldonTpuClient
    from seldon_core_tpu.controlplane import Deployer, TpuDeployment
    from seldon_core_tpu.controlplane.deployer import serve_deployment
    from seldon_core_tpu.testing.loadgen import run_load

    spec = TpuDeployment.load(os.path.join(REPO, spec_path))
    # every config gets its own ephemeral ports — parallel-safe
    import socket

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    http_port, grpc_port = free_port(), free_port()
    deployer = Deployer()
    t0 = time.perf_counter()
    await deployer.apply(spec, ready_timeout_s=600.0)
    handles = None
    clients = []  # per-thread SDK clients; closed in the teardown
    try:
        handles = await serve_deployment(
            deployer, spec.name, host="127.0.0.1",
            http_port=http_port, grpc_port=grpc_port,
        )
        setup_s = time.perf_counter() - t0

        dtype = extras.get("dtype", "float32")
        payload_rng = np.random.default_rng(0)
        if dtype == "uint8":
            payload = payload_rng.integers(0, 256, size=shape).astype(np.uint8)
        else:
            payload = payload_rng.normal(size=shape).astype(np.float32)
        feedback_every = 10 if extras.get("feedback") else 0

        import threading

        tl = threading.local()

        def make_worker():
            """One client + rng + counter per worker thread (sessions,
            channels, and numpy Generators are not thread-safe)."""
            client = SeldonTpuClient(
                host="127.0.0.1", http_port=http_port, grpc_port=grpc_port,
                transport=transport,
            )
            clients.append(client)
            rng = np.random.default_rng(threading.get_ident() & 0xFFFFFFFF)
            state = {"n": 0}

            def one() -> bool:
                state["n"] += 1
                out = client.predict(payload)
                if not out.success:
                    return False
                if feedback_every and state["n"] % feedback_every == 0:
                    # the bandit loop: reward the route that served us
                    fb = client.feedback(reward=float(rng.random() < 0.7),
                                         request=payload, response=out.response)
                    return fb.success
                return True

            return one

        def request_fn() -> bool:
            fn = getattr(tl, "fn", None)
            if fn is None:
                tl.fn = fn = make_worker()
            return fn()

        result = await asyncio.to_thread(
            run_load, request_fn, seconds, concurrency, 0.5
        )
    finally:
        # teardown must run even when the load phase dies, or the leaked
        # deployment skews every following config's numbers
        for client in clients:
            try:
                client.close()
            except Exception:  # noqa: BLE001 — teardown must finish
                pass
        await deployer.delete(spec.name)
        if handles is not None:
            runner, grpc_srv = handles
            await grpc_srv.stop(grace=None)
            await runner.cleanup()
    out = {"config": name, "transport": transport, "setup_s": round(setup_s, 1)}
    out.update(result.summary())
    return out


async def main_async(args) -> int:
    results = []
    failed = 0
    for name in args.configs:
        spec_path, shape, transport, extras = CONFIGS[name]
        try:
            out = await _bench_one(
                name, spec_path, shape, transport, extras,
                seconds=args.seconds, concurrency=args.concurrency,
            )
        except Exception as e:  # noqa: BLE001 — one config must not sink the rest
            out = {"config": name, "error": f"{type(e).__name__}: {e}"[:300]}
            failed += 1
        print(json.dumps(out), flush=True)
        results.append(out)
    summary = {
        "summary": True,
        "configs_run": len(results),
        "configs_failed": failed,
        "total_qps": round(sum(r.get("qps") or 0 for r in results), 1),
    }
    print(json.dumps(summary), flush=True)
    return 1 if failed else 0


def main(argv: Optional[list] = None) -> int:
    parser = argparse.ArgumentParser(description="benchmark the five reference configs")
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--concurrency", type=int, default=8)
    parser.add_argument("--configs", default="",
                        help="comma-separated subset (default: all five)")
    parser.add_argument("--quick", action="store_true",
                        help="CPU smoke: short load, skip resnet50")
    parser.add_argument("--platform", default="",
                        help="force jax platform (cpu for local smoke)")
    args = parser.parse_args(argv)

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from seldon_core_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    if args.quick:
        args.seconds = min(args.seconds, 3.0)
        default = [c for c in CONFIGS if c != "resnet50_grpc"]
    else:
        default = list(CONFIGS)
    args.configs = [c.strip() for c in args.configs.split(",") if c.strip()] or default
    unknown = [c for c in args.configs if c not in CONFIGS]
    if unknown:
        parser.error(f"unknown configs {unknown}; choose from {sorted(CONFIGS)}")
    return asyncio.run(main_async(args))


if __name__ == "__main__":
    sys.exit(main())
