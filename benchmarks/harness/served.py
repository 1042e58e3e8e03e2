"""The served path as the parent uses it outside the window: one
request at a time over the cell's own protocol, and the program's
counters over HTTP."""

from __future__ import annotations

import json
import urllib.request

from harness import manifest


class BenchFailure(Exception):
    pass


def http_json(url: str, timeout: float = 10.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


class Served:
    def __init__(self, ports: dict, config: dict, traffic: dict):
        self.config, self.traffic = config, traffic
        self.base = f"http://127.0.0.1:{ports['http']}"
        self.protocol = manifest.module("harness/protocols", traffic["protocol"])
        self.plan = {"host": "127.0.0.1", "ports": ports, "path": traffic["path"],
                     "model": config["model"], "timeout_s": 600}

    def request(self, content, item, on_event=None, holder=None):
        """One request on a connection of its own; the reply.  ``holder``
        (a list) is given the open connection, so another thread can cut
        a stream short with the protocol's ``abort``."""
        conn = self.protocol.connect(self.plan)
        if holder is not None:
            holder.append(conn)
        try:
            return self.protocol.call(conn, self.plan, content, item, [], on_event)
        finally:
            self.protocol.close(conn)

    def jit_compiles(self) -> float:
        """Sum of ``seldon_tpu_jit_compiles_total`` over programs."""
        with urllib.request.urlopen(f"{self.base}/metrics", timeout=10) as resp:
            text = resp.read().decode()
        return sum(float(line.rpartition(" ")[2]) for line in text.splitlines()
                   if line.startswith("seldon_tpu_jit_compiles_total"))

    def device(self) -> dict:
        status = http_json(f"{self.base}/health/status")
        for nodes in status["predictors"].values():
            for node in nodes.values():
                return node["device"]
        raise BenchFailure(f"/health/status names no device: {status}")
