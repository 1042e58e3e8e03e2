"""Name what the host was doing in each idle gap of the device, from a
JAX profiler trace taken of a serving paged engine.

The wave loop writes a ``seldon.wave`` step and its ``seldon.wave.<phase>``
annotations onto the engine thread's line of the same ``.xplane.pb`` that
holds the device's operations (``models/paged/seam.py _WaveSeam``).  This tool
lays the gaps between the operations of device 0 over them:

* the longest gaps, each with the seconds of it under every phase (the
  innermost annotation wins: a ``prefill`` inside ``admit`` counts as
  ``prefill``), ``uncovered`` for engine-thread time inside a step that
  no phase covers, and ``between`` for time outside any step (the
  loop's own code between two ``step()`` calls, or an idle engine);
* the same split summed over every gap, and the idle share of the trace.

Run:  python tools/profile_wave_gaps.py <trace dir or .xplane.pb> [--top 10]

Prints one JSON object.  Reads with ``jax.profiler.ProfileData`` and
touches no device (set ``JAX_PLATFORMS=cpu``).
"""

import argparse
import glob
import json
import os
import sys
from collections import defaultdict

STEP = "seldon.wave"


def find_xplane(path):
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return found[-1]


def load(path):
    """(device 0's operation intervals, the seam's annotations as
    ``(start, end, name)``), in nanoseconds on the trace's one clock."""
    from jax.profiler import ProfileData

    ops, marks = None, []
    for plane in sorted(ProfileData.from_file(find_xplane(path)).planes,
                        key=lambda p: p.name):
        for line in plane.lines:
            if plane.name.startswith("/device:") and line.name == "XLA Ops" and ops is None:
                ops = sorted((e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
            elif plane.name.startswith("/host:"):
                marks += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events if e.name.startswith(STEP)]
    return ops or [], marks


def gaps_of(ops):
    gaps, reach = [], None
    for start, end in ops:
        if reach is not None and start > reach:
            gaps.append((reach, start))
        reach = end if reach is None else max(reach, end)
    return gaps


def split(gap, marks):
    """Seconds of ``gap`` by what covers them: the innermost (shortest)
    annotation over each stretch between two annotation edges."""
    g0, g1 = gap
    over = [(a, b, n) for a, b, n in marks if a < g1 and b > g0]
    edges = sorted({g0, g1, *(min(max(x, g0), g1) for a, b, _n in over for x in (a, b))})
    out = defaultdict(float)
    for lo, hi in zip(edges, edges[1:]):
        cover = [(b - a, n) for a, b, n in over if a <= lo and b >= hi]
        if not cover:
            label = "between"
        else:
            name = min(cover)[1]
            label = "uncovered" if name == STEP else name[len(STEP) + 1:]
        out[label] += (hi - lo) / 1e9
    return dict(out)


def report(path, top):
    ops, marks = load(path)
    if not ops:
        return {"error": "no device plane with an XLA Ops line", "annotations": len(marks)}
    gaps = gaps_of(ops)
    window = ops[-1][1] - ops[0][0]
    total = defaultdict(float)
    worst_uncovered = 0.0
    for gap in gaps:
        parts = split(gap, marks)
        for k, v in parts.items():
            total[k] += v
        if gap[1] - gap[0] > 1e6:  # the acceptance line: gaps over 1 ms
            worst_uncovered = max(worst_uncovered, parts.get("uncovered", 0.0))
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "file": find_xplane(path),
        "window_s": window / 1e9,
        "idle_s": sum(b - a for a, b in gaps) / 1e9,
        "idle_pct": 100.0 * sum(b - a for a, b in gaps) / window,
        "steps": sum(1 for _a, _b, n in marks if n == STEP),
        "idle_by_phase_s": dict(sorted(total.items(), key=lambda kv: -kv[1])),
        "uncovered_worst_in_a_gap_over_1ms_s": worst_uncovered,
        "longest_gaps": [
            {"seconds": (b - a) / 1e9, "at_s": (a - ops[0][0]) / 1e9,
             "under": dict(sorted(split((a, b), marks).items(), key=lambda kv: -kv[1]))}
            for a, b in longest
        ],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args()
    print(json.dumps(report(args.trace, args.top)))


if __name__ == "__main__":
    sys.exit(main())
