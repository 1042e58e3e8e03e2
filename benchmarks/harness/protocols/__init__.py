"""One module per ``traffic.protocol``, found by that name (``-`` and
``.`` become ``_``).  A protocol knows how one request of its kind
travels and what a well-formed reply is:

* ``connect(plan) -> conn``, ``close(conn)``, ``abort(conn)`` (cut from
  another thread);
* ``call(conn, plan, content, item, events, on_event=None) -> reply``:
  send ``content`` (made by the configuration's kind) for the work item
  ``item``, append one ``(monotonic arrival, units)`` to ``events`` per
  reply event, raise :class:`RequestFailed` on a refusal or a malformed
  reply.

``plan`` holds ``host``, ``ports`` (``http``, ``grpc``), ``path``,
``model`` (the configuration's sizes) and ``timeout_s``.  A protocol
imports neither jax nor the program.
"""


class RequestFailed(Exception):
    pass
