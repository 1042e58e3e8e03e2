"""Drive synthetic mixed-length traffic at a local PagedEngine and
print the per-request lifecycle decomposition the flight recorder +
tracing layers exist for.

What it does, end to end (the same three observability layers a
production deployment gets, exercised standalone):

1. installs the in-memory tracer, builds a local engine, and submits a
   bimodal prompt mix (short/long alternating — the traffic shape the
   length-bucketed gather serves) with more streams than slots, so the
   queue-wait term is actually nonzero;
2. collects the flight-recorder ring and dumps it to JSONL
   (``--out``), alongside a JSONL of every gen.* span;
3. prints the per-request queue-wait / prefill / decode decomposition
   table from the lifecycle spans, plus the chunk-wall summary from
   the recorder — the table that answers "where did this request's
   latency go" without a profiler attached.

Run:  python tools/profile_engine_trace.py [--slots 8] [--streams 24]
      [--short 16] [--long 192] [--new 64] [--out /tmp/engine-trace]

Set SELDON_TPU_PROFILE_DIR and the run is taken inside one armed
profile window (``PagedEngine.arm_profile``, what ``POST /debug/profile``
calls) after a warm-up pass over the same lengths: the trace lands
there with the wave loop's ``seldon.wave.*`` annotations beside the
device's operations, and the last lines give the engine's own clocks
over the window (``host_work_s`` / ``host_wait_s``, ``first_token_s``).
"""

import argparse
import json
import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--streams", type=int, default=24)
    ap.add_argument("--short", type=int, default=16)
    ap.add_argument("--long", type=int, default=192)
    ap.add_argument("--new", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument(
        "--chunk-budget", type=int, default=0,
        help="SELDON_TPU_CHUNK_TOKEN_BUDGET for the engine (0 = "
             "monolithic prefill, the historical scheduler)",
    )
    ap.add_argument(
        "--profile-s", type=float, default=2.0,
        help="length of the armed profile window (only with "
             "SELDON_TPU_PROFILE_DIR set)",
    )
    ap.add_argument("--out", default="/tmp/engine-trace")
    args = ap.parse_args()

    import numpy as np

    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.paged import PagedEngine
    from seldon_core_tpu.models.transformer import TransformerLM
    from seldon_core_tpu.utils import tracing

    tracer = tracing.setup_tracing("profile-engine-trace", capacity=65536)

    lm = TransformerLM(
        vocab_size=args.vocab, d_model=args.d_model,
        num_layers=args.layers, num_heads=args.heads,
        max_len=args.max_len, dtype=jnp.bfloat16)
    params = lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]

    eng = PagedEngine(
        params, vocab_size=args.vocab, d_model=args.d_model,
        num_layers=args.layers, num_heads=args.heads,
        max_len=args.max_len, page_size=args.page_size,
        max_slots=args.slots, steps_per_call=8,
        chunk_token_budget=args.chunk_budget,
        dtype=jnp.bfloat16,
    )

    rng = np.random.default_rng(7)

    def draw_prompts():
        return [
            rng.integers(
                0, args.vocab,
                size=(args.short if i % 2 == 0 else args.long,),
            ).astype(np.int32)
            for i in range(args.streams)
        ]

    profiling = bool(os.environ.get("SELDON_TPU_PROFILE_DIR"))
    if profiling:
        # the window is for steady waves: meet every shape first, with
        # other content (the same content would hit the prefix cache)
        for p in draw_prompts():
            eng.submit(p, max_new_tokens=args.new)
        eng.run()
        eng.arm_profile(args.profile_s)
    prompts = draw_prompts()

    print(f"submitting {args.streams} streams ({args.short}/{args.long} "
          f"bimodal prompts, {args.new} new tokens) at {args.slots} slots")
    t0 = time.perf_counter()
    streams = [
        eng.submit(p, max_new_tokens=args.new, trace_id=f"req-{i:03d}")
        for i, p in enumerate(prompts)
    ]
    eng.run()
    wall = time.perf_counter() - t0
    while profiling and eng.profile_status()["state"] in ("armed", "tracing"):
        # a run shorter than its window: idle until the window's time
        # is up, as StreamingLM's loop does between requests
        time.sleep(0.02)
        eng.wave_boundary()
    total = sum(int(s.result.shape[0]) for s in streams)
    print(f"done: {total} tokens in {wall:.2f}s = {total / wall:.0f} tok/s\n")

    # ---- artifacts --------------------------------------------------------
    os.makedirs(args.out, exist_ok=True)
    rec_path = os.path.join(args.out, "flightrec.jsonl")
    if eng.recorder is not None:
        eng.recorder.dump_jsonl(rec_path)
    span_path = os.path.join(args.out, "spans.jsonl")
    with tracer._lock:  # noqa: SLF001 — read-only snapshot
        # the warm-up pass of a profiled run is traced too: keep the run's
        spans = [s for s in tracer.spans if s.trace_id.startswith("req-")]
    with open(span_path, "w") as f:
        for s in spans:
            f.write(json.dumps(s.to_dict()) + "\n")
    print(f"flight recorder -> {rec_path}\nspans          -> {span_path}\n")

    # ---- per-request decomposition ---------------------------------------
    by_req = defaultdict(dict)
    for s in spans:
        if s.name.startswith("gen."):
            by_req[s.trace_id][s.name] = s
    by_rid_stream = {f"req-{i:03d}": s for i, s in enumerate(streams)}
    print(f"{'request':<10} {'queue ms':>9} {'prefill ms':>11} "
          f"{'decode ms':>10} {'ttft ms':>8} {'tokens':>7} {'slot':>5} "
          f"{'evicted':>8}")
    agg = defaultdict(float)
    for rid in sorted(by_req):
        phases = by_req[rid]
        q = phases.get("gen.queued")
        p = phases.get("gen.prefill")
        d = phases.get("gen.decode")
        fin = phases.get("gen.finish")
        # TTFT: first decode token minus submit — the interactive
        # latency the chunked-prefill scheduler exists to protect
        # (queue + prefill + first decode chunk, in one number)
        st = by_rid_stream.get(rid)
        ttft = (
            (st.t_first_token - st.t_submit) * 1000.0
            if st is not None and st.t_first_token and st.t_submit else 0.0
        )
        row = [
            q.duration_s * 1000 if q else 0.0,
            p.duration_s * 1000 if p else 0.0,
            d.duration_s * 1000 if d else 0.0,
        ]
        agg["queue"] += row[0]
        agg["prefill"] += row[1]
        agg["decode"] += row[2]
        agg["ttft"] += ttft
        print(f"{rid:<10} {row[0]:>9.1f} {row[1]:>11.1f} {row[2]:>10.1f} "
              f"{ttft:>8.1f} "
              f"{(fin.tags.get('tokens') if fin else 0):>7} "
              f"{(fin.tags.get('slot') if fin else '-'):>5} "
              f"{'yes' if 'gen.evict' in phases else 'no':>8}")
    n = max(1, len(by_req))
    print(f"\nmeans: queue {agg['queue'] / n:.1f} ms, "
          f"prefill {agg['prefill'] / n:.1f} ms, "
          f"decode {agg['decode'] / n:.1f} ms, "
          f"ttft {agg['ttft'] / n:.1f} ms over {len(by_req)} requests")

    if eng.recorder is not None:
        rs = eng.recorder.stats()
        recs = eng.recorder.snapshot()
        stalls = sum(r.get("stalls", 0) for r in recs)
        print(f"chunks recorded {rs['records']}, chunk p99 "
              f"{rs['chunk_p99_ms']:.1f} ms, stalls {stalls}, "
              f"last queue depth {rs['last_queue_depth']}")
        # the scheduler's chosen chunk mix (r15): what each wave
        # actually carried under the token budget
        total = max(
            1, rs["window_prefill_tokens"] + rs["window_decode_tokens"]
        )
        mixed = sum(
            1 for r in recs
            if r.get("prefill_tokens", 0) and r.get("decode_tokens", 0)
        )
        print(f"chunk mix (budget={eng.chunk_token_budget or 'off'}): "
              f"{rs['window_prefill_tokens']} prefill + "
              f"{rs['window_decode_tokens']} decode tokens "
              f"({100.0 * rs['window_prefill_tokens'] / total:.0f}% "
              f"prefill), {mixed}/{rs['records']} waves mixed "
              "prefill+decode")
    if profiling:
        window_lines(eng.profile_status())
    eng.close()
    tracing._tracer = None


def window_lines(window):
    """The armed window, and the engine's own clocks over it (the two
    ``engine_stats()`` snapshots at its edges): the engine thread at
    work and blocked in a readback, and a stream's way to its first
    token, each closed at a harvest."""
    if window.get("state") != "done":
        print(f"profile window: {window}")
        return
    a, b = window["stats_start"], window["stats_stop"]

    def d(key):
        return b[key] - a[key]

    print(f"\nprofile window: waves {window['wave_start']}..{window['wave_stop']}, "
          f"{window['t_stop'] - window['t_start']:.3f} s under {window['dir']} "
          "(tools/profile_wave_gaps.py reads its seldon.wave.* annotations)")
    print(f"engine thread: host_work_s {d('host_work_s') * 1e3:.1f} ms, "
          f"host_wait_s {d('host_wait_s') * 1e3:.1f} ms, "
          f"host_gap_s {d('host_gap_s') * 1e3:.1f} ms; "
          f"chunk_wall_s {d('chunk_wall_s') * 1e3:.1f} ms")
    if d("first_tokens"):
        print(f"admission -> first-token harvest: {d('first_tokens')} streams, "
              f"mean {d('first_token_s') / d('first_tokens') * 1e3:.1f} ms")


if __name__ == "__main__":
    main()
