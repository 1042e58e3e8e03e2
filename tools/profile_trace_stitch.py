"""Run a real multi-process graph and print the stitched per-request
hop table — the cross-process twin of profile_engine_trace.py.

What it does, end to end:

1. spawns worker microservice processes (REST and gRPC transports) with
   ``TRACING=1`` and a per-worker ``SELDON_TPU_TRACE_EXPORT`` JSONL
   span sink;
2. builds a gateway-side predictor whose graph fans out to the workers
   over BOTH transports (an AVERAGE_COMBINER over a REST leg and a
   gRPC leg), installs the in-memory tracer, and drives ``--requests``
   predicts through it;
3. merges the gateway's spans with every worker's exported spans into
   one trace per request (W3C context propagated on every hop makes
   the worker spans real children of the gateway's node spans), and
   prints per request, per hop: total / serialize / network / handle
   decomposition plus payload bytes — the table that answers "where
   did this request's cross-process latency go";
4. verifies the stitching invariants the tracing layer promises
   (every span shares the root trace id; zero orphan microservice
   roots) and says so.

Run:  python tools/profile_trace_stitch.py [--requests 20]
      [--out /tmp/trace-stitch] [--worker seldon_core_tpu.engine.units.StubModel]
"""

import argparse
import asyncio
import json
import os
import socket
import subprocess
import sys
import time
from collections import defaultdict

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def spawn_worker(component: str, http_port: int, grpc_port: int, span_path: str,
                 log_path: str):
    # two worker processes cannot share a chip (libtpu binds one
    # process per chip), and the hops this tool times are host-side:
    # workers are held to the CPU backend whatever the parent sees
    env = dict(
        os.environ,
        TRACING="1",
        SELDON_TPU_TRACE_EXPORT=span_path,
        JAX_PLATFORMS="cpu",
    )
    # worker output goes to a FILE, not a pipe: nothing drains a pipe
    # after startup, and a chatty worker (access logs, jit-sentinel
    # WARNs) would fill the 64 KB buffer and block mid-run
    log = open(log_path, "wb")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "seldon_core_tpu.runtime.microservice",
            component, "--api", "BOTH", "--host", "127.0.0.1",
            "--http-port", str(http_port), "--grpc-port", str(grpc_port),
            "--unit-id", f"worker-{http_port}",
        ],
        cwd=REPO_ROOT, env=env,
        stdout=log, stderr=subprocess.STDOUT,
    )
    log.close()  # the child holds its own fd
    proc.log_path = log_path
    return proc


def await_ready(proc, http_port: int, timeout_s: float = 90.0) -> None:
    import urllib.request

    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if proc.poll() is not None:
            with open(proc.log_path, errors="replace") as f:
                out = f.read()
            raise SystemExit(f"worker died at startup:\n{out[-4000:]}")
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{http_port}/health/ping", timeout=1
            ) as resp:
                if resp.status < 400:
                    return
        except Exception:  # noqa: BLE001
            time.sleep(0.2)
    raise SystemExit("worker never became ready")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--rows", type=int, default=4, help="payload rows per request")
    ap.add_argument("--out", default="/tmp/trace-stitch")
    ap.add_argument(
        "--worker", default="seldon_core_tpu.engine.units.StubModel",
        help="dotted component class each worker process serves",
    )
    args = ap.parse_args()

    import numpy as np

    from seldon_core_tpu.engine import PredictorService
    from seldon_core_tpu.engine.graph import Endpoint, UnitSpec
    from seldon_core_tpu.runtime.message import InternalMessage
    from seldon_core_tpu.utils import tracing

    os.makedirs(args.out, exist_ok=True)
    http_a, grpc_a = free_port(), free_port()
    http_b, grpc_b = free_port(), free_port()
    span_a = os.path.join(args.out, "worker-a.jsonl")
    span_b = os.path.join(args.out, "worker-b.jsonl")
    for p in (span_a, span_b):
        if os.path.exists(p):
            os.remove(p)

    print(f"spawning 2 workers ({args.worker}) — REST hop :{http_a}, gRPC hop :{grpc_b}")
    workers = [
        spawn_worker(args.worker, http_a, grpc_a, span_a,
                     os.path.join(args.out, "worker-a.log")),
        spawn_worker(args.worker, http_b, grpc_b, span_b,
                     os.path.join(args.out, "worker-b.log")),
    ]
    try:
        for proc, port in zip(workers, (http_a, http_b)):
            await_ready(proc, port)

        tracer = tracing.setup_tracing("stitch-gateway", capacity=65536)
        graph = UnitSpec(
            name="combiner", type="COMBINER", implementation="AVERAGE_COMBINER",
            children=[
                UnitSpec(name="node-a", type="MODEL", remote=True,
                         endpoint=Endpoint("127.0.0.1", http_a, "REST")),
                UnitSpec(name="node-b", type="MODEL", remote=True,
                         endpoint=Endpoint("127.0.0.1", grpc_b, "GRPC")),
            ],
        )
        svc = PredictorService(graph, name="main")

        async def drive():
            puids = []
            t0 = time.perf_counter()
            for i in range(args.requests):
                msg = InternalMessage(
                    payload=np.random.default_rng(i).random((args.rows, 4)),
                    kind="ndarray",
                )
                out = await svc.predict(msg)
                assert out.status["status"] == "SUCCESS", out.status
                puids.append(out.meta.puid)
            wall = time.perf_counter() - t0
            await svc.close()
            return puids, wall

        puids, wall = asyncio.run(drive())
        print(f"drove {args.requests} requests in {wall:.2f}s "
              f"({args.requests / wall:.1f} req/s)\n")
        local_spans = [s.to_dict() for s in list(tracer.spans)]
        tracing._tracer = None
    finally:
        for proc in workers:
            proc.terminate()
        for proc in workers:
            proc.wait(timeout=20)

    worker_spans = []
    for path in (span_a, span_b):
        deadline = time.time() + 10
        while time.time() < deadline and not os.path.exists(path):
            time.sleep(0.2)
        if os.path.exists(path):
            with open(path) as f:
                worker_spans.extend(json.loads(l) for l in f if l.strip())

    gateway_path = os.path.join(args.out, "gateway.jsonl")
    with open(gateway_path, "w") as f:
        for s in local_spans:
            f.write(json.dumps(s) + "\n")
    print(f"gateway spans -> {gateway_path}")
    print(f"worker spans  -> {span_a}, {span_b} ({len(worker_spans)} spans)\n")

    # ---- stitch -----------------------------------------------------------
    spans = local_spans + worker_spans
    by_trace = defaultdict(list)
    for s in spans:
        by_trace[s["traceId"]].append(s)
    children = defaultdict(list)
    for s in spans:
        if s.get("parentSpanId"):
            children[s["parentSpanId"]].append(s)

    def dur_ms(s):
        return s["durationNano"] / 1e6

    header = (f"{'request':<26} {'hop':<34} {'transport':>9} {'total':>8} "
              f"{'serial':>7} {'network':>8} {'handle':>7} {'req B':>7} {'resp B':>7}")
    print(header)
    print("-" * len(header))
    shown = 0
    for puid in puids:
        trace = by_trace.get(puid, [])
        hops = sorted(
            (s for s in trace if s["name"].startswith("node.")),
            key=lambda s: s["name"],
        )
        for hop in hops:
            tags = hop.get("tags", {})
            handle = sum(
                dur_ms(c) for c in children.get(hop["spanId"], [])
                if c["name"].startswith("microservice.")
            )
            print(f"{puid:<26} {hop['name']:<34} "
                  f"{tags.get('transport', '-'):>9} {dur_ms(hop):>8.2f} "
                  f"{tags.get('serialize_ms', 0):>7.2f} "
                  f"{tags.get('network_ms', 0):>8.2f} {handle:>7.2f} "
                  f"{tags.get('request_bytes', 0):>7} "
                  f"{tags.get('response_bytes', 0):>7}")
        shown += 1
        if shown >= 8 and len(puids) > 8:
            print(f"... ({len(puids) - shown} more requests; same shape)")
            break

    # per-hop aggregate
    agg = defaultdict(lambda: defaultdict(float))
    counts = defaultdict(int)
    for puid in puids:
        for s in by_trace.get(puid, []):
            if not s["name"].startswith("node."):
                continue
            tags = s.get("tags", {})
            a = agg[s["name"]]
            a["total"] += dur_ms(s)
            a["serialize"] += float(tags.get("serialize_ms", 0))
            a["network"] += float(tags.get("network_ms", 0))
            a["handle"] += sum(
                dur_ms(c) for c in children.get(s["spanId"], [])
                if c["name"].startswith("microservice.")
            )
            counts[s["name"]] += 1
    print("\nper-hop means (ms):")
    for name in sorted(agg):
        n = max(1, counts[name])
        a = agg[name]
        print(f"  {name:<34} total {a['total'] / n:7.2f}  "
              f"serialize {a['serialize'] / n:6.2f}  "
              f"network {a['network'] / n:7.2f}  handle {a['handle'] / n:6.2f}")

    # ---- stitching invariants --------------------------------------------
    request_spans = [s for t in puids for s in by_trace.get(t, [])]
    stitched = len(request_spans)
    all_request_spans = [
        s for s in spans
        if s["name"].startswith(("node.", "microservice.", "predictor.", "gen."))
    ]
    share = stitched / max(1, len(all_request_spans))
    orphans = [
        s for s in worker_spans
        if s["name"].startswith("microservice.")
        and (not s.get("parentSpanId") or s["parentSpanId"] not in
             {sp["spanId"] for sp in spans})
    ]
    print(f"\nstitching: {stitched}/{len(all_request_spans)} request spans "
          f"share a gateway root trace id ({share * 100:.1f}%), "
          f"{len(orphans)} orphan microservice roots")
    if share < 0.99 or orphans:
        raise SystemExit("TRACE STITCHING BROKEN: see counts above")
    print("stitch OK: one tree per request across "
          f"{len({s['traceId'] for s in request_spans})} traces")


if __name__ == "__main__":
    main()
