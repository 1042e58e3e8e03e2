#!/usr/bin/env python3
"""From a JAX profiler trace (``*.xplane.pb``) to the numbers the
benchmark reports.  This is the yardstick: no PR that claims a gain may
change it.

Read with ``jax.profiler.ProfileData`` and nothing else.  A device plane
is one whose name starts with ``/device:``; of its lines, ``XLA Ops``
holds one event per executed HLO operation and ``XLA Modules`` one per
executed program.

* ``busy_s``: per device, the length of the union of the ``XLA Ops``
  intervals; averaged over the devices that ran anything.
* ``window_s``: first start to last end of any device event, over all
  devices.  (The profiler's own start and stop are not in the trace, so
  idle time before the first and after the last operation is not
  counted; in a window cut from a steady run that is under one gap.)
* ``modules``: count and summed device seconds per program name, with
  the ``(<id>)`` suffix of a run removed.
* ``ops``: the same per operation, keyed by opcode plus output type and
  shape (``stable_op_name``); ``while``/``conditional``/``call`` are
  left out, their bodies' operations being events of their own.
* ``idle_gaps``: the longest gaps between operations on device 0, each
  labelled with what the host was doing: the innermost named python
  frame (``$file.py:line function``) that covers at least half of the
  gap, on the python thread with most frames in the trace (taken to be
  the one that drives the device), else ``host-unattributed``.

Run as ``trace_reduce.py <trace dir or file> [--inspect]``; prints one
JSON object.  Needs jax importable; never touches a device
(``JAX_PLATFORMS=cpu`` is set by the caller).
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return found[-1]


def union_length(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    if cur_end is not None:
        total += cur_end - cur_start
    return total


CONTROL_FLOW = ("while", "conditional", "call")
_OPCODE = re.compile(r"\s*([A-Za-z][\w\-]*)\(")
_FIRST_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")


def parse_hlo(text: str):
    """``(opcode, dtype, dims)`` of one HLO instruction as the device
    trace names it: ``%name = <shape or tuple of shapes> opcode(...)``.
    The shape is the first output's.  ``(None, None, None)`` if the text
    is not of that form (a host-side or legacy event name)."""
    _name, sep, rest = text.partition(" = ")
    if not sep:
        return None, None, None
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shapes, rest = rest[: i + 1], rest[i + 1:]
    else:
        shapes, _, rest = rest.partition(" ")
        rest = " " + rest
    op = _OPCODE.match(rest)
    shape = _FIRST_SHAPE.search(shapes)
    if not op:
        return None, None, None
    opcode = op.group(1)
    if opcode == "custom-call" and 'custom_call_target="tpu_custom_call"' in text:
        opcode = "pallas_kernel"  # Mosaic kernels arrive as this custom call
    return opcode, shape.group(1) if shape else "", shape.group(2) if shape else ""


def stable_op_name(name: str) -> str:
    """A name that survives a recompile and a refactor: opcode plus the
    first output's type and shape, e.g. ``fusion_bf16_1_513_64_20_64_``
    or ``pallas_kernel_f32_16_1_1280_``.  Instruction numbers and the
    module scopes XLA puts into instruction names are dropped, so the
    36 layers' instances of one operation add up under one key."""
    opcode, dtype, dims = parse_hlo(name)
    if opcode is None:
        return re.sub(r"\.\d+$", "", name.lstrip("%"))
    return f"{opcode}_{dtype}_{dims.replace(',', '_')}_"


def stable_module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name).strip()


def _stats(event) -> dict:
    try:
        return {str(k): v for k, v in event.stats}
    except Exception:  # noqa: BLE001 — an event without readable stats still has a name
        return {}


def reduce_trace(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    devices, host_events = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = list(line.events)
                elif line.name == MODULES_LINE:
                    modules = list(line.events)
            if ops or modules:
                devices.append((plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            # the python thread with most frames is taken to be the one
            # that drives the device; threads that wait explain no gap
            for line in plane.lines:
                frames = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                          for ev in line.events if ev.name.startswith("$")]
                if len(frames) > len(host_events):
                    host_events = frames
    if not devices:
        return {"devices": 0, "planes": [p.name for p in data.planes]}
    devices.sort()

    starts = [e.start_ns for _n, ops, mods in devices for e in ops + mods]
    ends = [e.start_ns + e.duration_ns for _n, ops, mods in devices for e in ops + mods]
    window_ns = max(ends) - min(starts)
    busy = [union_length([(e.start_ns, e.start_ns + e.duration_ns) for e in ops])
            for _n, ops, _m in devices if ops]

    modules, ops_by_name = {}, {}
    for _n, ops, mods in devices:
        for e in mods:
            slot = modules.setdefault(stable_module_name(e.name), {"count": 0, "seconds": 0.0})
            slot["count"] += 1
            slot["seconds"] += e.duration_ns / 1e9
        for e in ops:
            key = stable_op_name(e.name)
            if key.split("_", 1)[0] in CONTROL_FLOW:
                continue  # its body's operations are events of their own
            slot = ops_by_name.setdefault(key, {"count": 0, "seconds": 0.0})
            slot["count"] += 1
            slot["seconds"] += e.duration_ns / 1e9
    n = len(devices)
    for table in (modules, ops_by_name):
        for slot in table.values():  # per device, like busy_s
            slot["seconds"] /= n
            slot["count"] /= n

    # idle gaps on the first device
    first = sorted((e.start_ns, e.start_ns + e.duration_ns) for e in devices[0][1])
    gaps, reach = [], None
    for start, end in first:
        if reach is not None and start > reach:
            gaps.append((start - reach, reach, start))
        reach = end if reach is None else max(reach, end)
    gaps.sort(reverse=True)
    labelled = []
    for length, g0, g1 in gaps[:TOP]:
        best = None
        for h0, h1, name in host_events:
            cover = min(h1, g1) - max(h0, g0)
            if cover * 2 >= length and "<unknown>" not in name \
                    and (best is None or h1 - h0 < best[0]):
                best = (h1 - h0, name)
        labelled.append([best[1] if best else "host-unattributed", length / 1e9])

    top_ops = sorted(([k, v["seconds"]] for k, v in ops_by_name.items()),
                     key=lambda kv: -kv[1])
    return {
        "devices": n,
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9 if busy else 0.0,
        "modules": modules,
        "ops": dict(sorted(ops_by_name.items(), key=lambda kv: -kv[1]["seconds"])[:200]),
        "breakdown": {"device_ops": top_ops[:TOP], "idle_gaps": labelled},
    }


def inspect(path: str) -> dict:
    """What a trace holds, for a reader who has not seen one: planes,
    lines, event counts and a few events with their stats."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append({
                "line": line.name, "events": len(events),
                "sample": [{"name": e.name, "start_ns": e.start_ns,
                            "duration_ns": e.duration_ns,
                            "stats": {k: str(v)[:200] for k, v in _stats(e).items()}}
                           for e in events[:3]],
            })
        out.append({"plane": plane.name, "lines": lines})
    return {"file": find_xplane(path), "planes": out}


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--inspect"]
    fn = inspect if "--inspect" in sys.argv else reduce_trace
    print(json.dumps(fn(args[0])))
