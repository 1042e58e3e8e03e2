"""What the wave loop's seam costs a wave with no profiler session open:
the calls one chained wave makes to ``models/paged/seam.py _WaveSeam`` (a
step, five phases, one prefill group and its three parts, the launch's
three parts, two dispatches handed to the device clock (its watcher
thread runs beside: nothing to wait on, so it only settles), a readback,
the stats it attaches), timed alone on a seam with no engine behind it.
Host time, whatever the backend: run it where the engine will run.

Run:  python tools/profile_seam_cost.py [--waves 20000]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one_wave(seam):
    seam.begin_wave()
    seam.enter("admit")
    seam.stats(admitted=2, queue_depth=16)
    seam.begin_prefill(bucket=512, k=2, rows=2, tokens=700, padded=1024,
                       cached=0, fused=0)
    seam.sub("call")
    seam.dispatched()
    seam.sub("tail")
    seam.end_prefill()
    seam.enter("launch")
    seam.stats(steps=8, lanes=32, kv_tokens=11000, latent_tokens=0,
               pages_live=190, page_slots=384, overlapped=1)
    seam.sub("call")
    seq = seam.dispatched()
    seam.sub("post")
    seam.enter("wait", wave=seam.wave)
    seam.drained(seq - 1)
    seam.enter("harvest", wave=seam.wave)
    seam.stats(tokens=245, finished=2)
    seam.enter("record", wave=seam.wave)
    seam.end_wave(True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--waves", type=int, default=20000)
    args = ap.parse_args()

    import jax

    from seldon_core_tpu.models.paged import _WaveSeam

    class Engine:
        _jax = jax

    seam = _WaveSeam(Engine(), None)
    for _ in range(2000):
        one_wave(seam)
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter_ns()
        for _ in range(args.waves):
            one_wave(seam)
        best = min(best, (time.perf_counter_ns() - t0) / args.waves)
    walls = getattr(seam, "phase_walls", None)
    print(f"seam calls of one wave, no session open: {best:.0f} ns "
          f"(best of 5 x {args.waves} waves; phase walls "
          f"{'booked' if walls else 'not booked'})")


if __name__ == "__main__":
    main()
