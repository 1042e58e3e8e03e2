"""``sse-generate``: ``POST <path>`` with one prompt's token ids, the
answer read as Server-Sent Events, one ``data: {"tokens": [...]}`` event
per engine chunk, closed by ``event: end``.  A work item is ``(prompt
length, new tokens)``; an event counts the tokens it carries."""

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
    conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
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
    """One streamed generation; the token ids.  ``events`` is the
    caller's, so a stream cut short keeps what arrived."""
    try:
        return _call(conn, plan, content, item, events, on_event)
    except http.client.HTTPException as e:
        raise RequestFailed(f"{type(e).__name__}: {e}") from None


def _call(conn, plan, content, item, events, on_event):
    new_tokens, vocab = int(item[1]), plan["model"]["vocab_size"]
    body = json.dumps({"data": {"ndarray": [content]},
                       "meta": {"tags": {"max_new_tokens": new_tokens}}})
    conn.request("POST", plan["path"], body=body,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    if resp.status != 200:
        raise RequestFailed(f"HTTP {resp.status}: {resp.read(300)!r}")
    ids, event, ended = [], "", False
    while True:
        line = resp.readline()
        if not line:
            break
        line = line.strip()
        if not line:
            event = ""
        elif line.startswith(b"event:"):
            event = line.split(b":", 1)[1].strip().decode()
        elif line.startswith(b"data:"):
            now = time.monotonic()
            payload = json.loads(line.split(b":", 1)[1])
            if event == "error":
                raise RequestFailed(f"stream error: {payload}")
            if event == "end":
                ended = True
                resp.read()  # to the end of the body, so the connection is reusable
                break
            got = payload["tokens"]
            if not all(isinstance(t, int) and 0 <= t < vocab for t in got):
                raise RequestFailed(f"token ids out of range: {got[:8]}")
            ids.extend(got)
            events.append((now, len(got)))
            if on_event is not None:
                on_event()
    if not ended:
        raise RequestFailed("stream closed without an end event")
    if len(ids) != new_tokens:
        raise RequestFailed(f"{len(ids)} tokens for {new_tokens} asked")
    return ids
