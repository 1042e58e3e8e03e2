#!/usr/bin/env python3
"""The one general load generator: closed-loop clients that read a
traffic file's parameters and drive the served path.

It is its own process, pinned by its parent to CPUs of its own, and
imports neither jax nor the program.  How a request travels is the
protocol's module (``harness/protocols/<traffic.protocol>.py``), what it
holds the kind's (``harness/kinds/<config.kind>.py``); both are found by
name, so this file knows neither.

Timing is the client's: every reply event is stamped with
``time.monotonic()`` on arrival, and the window's rates count the
events whose stamp lies inside it, never completed requests.

Run as ``loadgen.py --plan plan.json --out results.json``: ramp (clients
start a few at a time), then the window, which opens the moment
``until_first_tokens`` clients hold a reply (``window.json`` is written
beside ``--out``) and lasts ``seconds``; then every open request is cut.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import manifest  # noqa: E402
from harness.protocols import RequestFailed  # noqa: E402


class Client(threading.Thread):
    def __init__(self, number: int, plan: dict, shared: "Shared"):
        super().__init__(name=f"client-{number}", daemon=True)
        self.number, self.plan, self.shared = number, plan, shared
        self.conn = None
        self.records = []

    def abort(self):
        conn = self.conn
        if conn is not None:
            self.shared.protocol.abort(conn)

    def run(self):
        plan, shared = self.plan, self.shared
        work, clients = plan["schedule"], plan["clients"]
        index = self.number
        first_seen = False

        def on_event():
            nonlocal first_seen
            if not first_seen:
                first_seen = True
                shared.first_token(self.number)

        while not shared.stop_sending.is_set():
            item = work[index % len(work)]
            content = shared.kind.content(plan["model"], plan["seed"], index, item)
            rec = {"client": self.number, "index": index, **shared.kind.fields(item),
                   "ok": False, "error": None, "events": []}
            self.records.append(rec)
            try:
                if self.conn is None:
                    self.conn = shared.protocol.connect(plan)
                rec["t_send"] = time.monotonic()
                shared.protocol.call(self.conn, plan, content, item, rec["events"], on_event)
                rec["ok"] = True
            except (RequestFailed, OSError, ValueError, KeyError) as e:
                rec["error"] = f"{type(e).__name__}: {e}"[:300]
                rec["aborted"] = shared.aborting.is_set()
                if self.conn is not None:
                    shared.protocol.close(self.conn)
                self.conn = None
                if not shared.aborting.is_set():
                    time.sleep(0.05)  # a refused request must not spin
            rec["t_end"] = time.monotonic()
            index += clients
        if self.conn is not None:
            shared.protocol.close(self.conn)


class Shared:
    def __init__(self, plan: dict):
        self.protocol = manifest.module("harness/protocols", plan["protocol"])
        self.kind = manifest.module("harness/kinds", plan["kind"])
        self.stop_sending = threading.Event()
        self.aborting = threading.Event()
        self._lock = threading.Lock()
        self._first = set()

    def first_token(self, client: int) -> None:
        with self._lock:
            self._first.add(client)

    def first_tokens(self) -> int:
        with self._lock:
            return len(self._first)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--plan", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    with open(args.plan) as f:
        plan = json.load(f)
    shared = Shared(plan)
    clients = [Client(c, plan, shared) for c in range(plan["clients"])]
    ramp = plan["ramp"]
    t_ramp = time.monotonic()
    for start in range(0, len(clients), ramp["clients_per_step"]):
        for c in clients[start:start + ramp["clients_per_step"]]:
            c.start()
        time.sleep(ramp["step_s"])
    need = min(ramp.get("until_first_tokens", len(clients)), len(clients))
    deadline = t_ramp + plan["ramp_timeout_s"]
    while shared.first_tokens() < need:
        if time.monotonic() > deadline:
            sys.stderr.write(f"[loadgen] ramp: only {shared.first_tokens()} of {need} "
                             f"clients hold a stream after {plan['ramp_timeout_s']} s\n")
            return 1
        time.sleep(0.01)
    t0, cpu0 = time.monotonic(), time.process_time()
    t1 = t0 + plan["seconds"]
    window_path = os.path.join(os.path.dirname(args.out), "window.json")
    with open(window_path + ".tmp", "w") as f:
        json.dump({"t0": t0, "t1": t1, "ramp_s": t0 - t_ramp}, f)
    os.replace(window_path + ".tmp", window_path)
    time.sleep(max(0.0, t1 - time.monotonic()))
    busy_share = (time.process_time() - cpu0) / (time.monotonic() - t0)

    shared.stop_sending.set()
    shared.aborting.set()
    for c in clients:
        c.abort()
    for c in clients:
        c.join(timeout=10)
    alive = [c.name for c in clients if c.is_alive()]
    records = [r for c in clients for r in list(c.records)]
    with open(args.out + ".tmp", "w") as f:
        json.dump({"t0": t0, "t1": t1, "ramp_s": t0 - t_ramp, "busy_share": busy_share,
                   "threads_left": alive, "records": records}, f)
    os.replace(args.out + ".tmp", args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
