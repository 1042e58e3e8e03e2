"""Share of the device's busy time spent in operations whose output is
shaped like the KV pool or like one layer of it: the summed seconds of
the traced operations (``trace["ops"]``, keyed by opcode plus output
type and shape) whose shape holds the configuration's ``num_pages`` and
``page_size`` as adjacent dims, over ``busy_s``.

Such an operation moves the pool (a layer cut out of it, a layer
re-laid for its reader) or writes into it.  A write that aliases its
input still has the pool's shape and counts by its seconds: a write
that takes milliseconds for a few KB is the same fault as a copy.
0.0 when the trace holds no such operation."""


def pool_dims(config: dict):
    """``(num_pages, page_size)`` of the served pool, from the
    deployment's own parameters; None where the configuration has no
    paged pool."""
    try:
        graph = config["deployment"]["predictors"][0]["graph"]
        params = {p["name"]: p["value"] for p in graph["parameters"]}
        return int(params["num_pages"]), int(params["page_size"])
    except (KeyError, IndexError, TypeError, ValueError):
        return None


def shape_of(key: str) -> list:
    """The dims of a ``stable_op_name`` key (``copy_bf16_513_64_1280_``):
    its trailing run of numbers."""
    dims = []
    for token in reversed(key.rstrip("_").split("_")):
        if not token.isdigit():
            break
        dims.append(int(token))
    return dims[::-1]


def read(ctx):
    trace = ctx.get("trace")
    pool = pool_dims(ctx.get("config") or {})
    if not trace or not trace.get("busy_s") or not trace.get("ops") or not pool:
        return None
    moved = 0.0
    for key, slot in trace["ops"].items():
        dims = shape_of(key)
        if any(pair == pool for pair in zip(dims, dims[1:])):
            moved += slot["seconds"]
    return 100.0 * moved / trace["busy_s"]
