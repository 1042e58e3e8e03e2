"""``rest-predict``: ``POST <path>`` with ``{"data": {"ndarray": rows}}``,
one JSON reply with a score row per input row.  A work item is ``(rows,)``;
the one event of a request counts its rows."""

from __future__ import annotations

import http.client
import json
import socket
import time

from harness.protocols import RequestFailed


def connect(plan: dict):
    conn = http.client.HTTPConnection(plan["host"], plan["ports"]["http"],
                                      timeout=plan["timeout_s"])
    conn.connect()
    return conn


def close(conn) -> None:
    conn.close()


def abort(conn) -> None:
    if conn.sock is not None:
        try:
            conn.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass


def call(conn, plan: dict, content: list, item, events: list, on_event=None) -> list:
    try:
        conn.request("POST", plan["path"], body=json.dumps({"data": {"ndarray": content}}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
    except http.client.HTTPException as e:
        raise RequestFailed(f"{type(e).__name__}: {e}") from None
    now = time.monotonic()
    if resp.status != 200:
        raise RequestFailed(f"HTTP {resp.status}: {body[:300]!r}")
    scores = json.loads(body)["data"]["ndarray"]
    classes = plan["model"]["num_classes"]
    if len(scores) != len(content) or any(len(row) != classes for row in scores):
        raise RequestFailed(f"{len(scores)} score rows for {len(content)} input rows")
    events.append((now, len(scores)))
    if on_event is not None:
        on_event()
    return scores
