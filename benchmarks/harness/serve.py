#!/usr/bin/env python3
"""The process that holds the chip: ``seldon-tpu-deploy run`` with a
control thread beside it.

The deployment is started by the deployer's own ``main`` (the function
behind the ``seldon-tpu-deploy`` console script), so the served path is
the user's.  What the benchmark adds is one idle thread that answers
three requests left as files in ``--control``, because only the process
that holds the chip can do these:

* ``device.ask``  -> ``device.json``: platform, kind, count and
  ``peak_bytes_in_use`` per device (``/health/status`` reports only
  ``bytes_in_use``);
* ``trace.start`` -> ``jax.profiler.start_trace(<control>/trace)``;
* ``trace.stop``  -> ``stop_trace`` and ``trace.done``.

``--require`` names the platform the run is for and ``--chips`` how
many devices; anything else is exit 3 before a server starts (a
measurement path never falls back).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def control_loop(control: str, stop: threading.Event) -> None:
    import jax

    def path(name):
        return os.path.join(control, name)

    def take(name) -> bool:
        try:
            os.unlink(path(name))
            return True
        except FileNotFoundError:
            return False

    def put(name, text=""):
        with open(path(name) + ".tmp", "w") as f:
            f.write(text)
        os.replace(path(name) + ".tmp", path(name))

    tracing = False
    while not stop.wait(0.05):
        if take("device.ask"):
            devices = jax.devices()
            stats = [d.memory_stats() or {} for d in devices]
            put("device.json", json.dumps({
                "platform": devices[0].platform, "kind": devices[0].device_kind,
                "count": len(devices),
                "peak_bytes_in_use": [int(s.get("peak_bytes_in_use", 0)) for s in stats],
                "bytes_in_use": [int(s.get("bytes_in_use", 0)) for s in stats],
            }))
        if not tracing and take("trace.start"):
            jax.profiler.start_trace(path("trace"))
            tracing = True
            put("trace.started", repr(time.monotonic()))
        if tracing and take("trace.stop"):
            t_stop = time.monotonic()
            jax.profiler.stop_trace()
            tracing = False
            put("trace.done", repr(t_stop))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True)
    ap.add_argument("--http-port", required=True)
    ap.add_argument("--grpc-port", required=True)
    ap.add_argument("--control", required=True)
    ap.add_argument("--require", required=True, help="platform this run is for")
    ap.add_argument("--chips", type=int, default=1, help="devices the cell needs")
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != args.require or len(devices) < args.chips:
        sys.stderr.write(f"[serve] jax found {len(devices)} device(s) of platform "
                         f"{devices[0].platform!r}; this run needs {args.chips} of "
                         f"{args.require!r}\n")
        return 3
    from seldon_core_tpu.controlplane import deployer

    stop = threading.Event()
    thread = threading.Thread(target=control_loop, args=(args.control, stop),
                              name="bench-control", daemon=True)
    thread.start()
    try:
        deployer.main(["run", args.spec, "--host", "127.0.0.1",
                       "--http-port", args.http_port, "--grpc-port", args.grpc_port])
    finally:
        stop.set()
        thread.join(timeout=30)
    return 0


if __name__ == "__main__":
    sys.exit(main())
