#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This parent never imports jax.  It starts, each as a process of its own:
the server (``harness/serve.py``: the deployer's own entry point in the
one process that holds the chip), the plain reference (CPU-pinned,
while the server loads) and the load generator (CPU-pinned, imports
neither jax nor the program).  Set-up is everything up to the moment
the window opens: process start, weights and pool, a seeded sample
checked against the reference, warm-up of exactly the programs the
cell's lengths can reach, and the ramp.  Then ``--seconds`` of window.
What a request holds, which sample is checked and what is warmed belong
to the configuration's kind (``harness/kinds/<config.kind>.py``), how a
request travels to the traffic file's protocol
(``harness/protocols/<traffic.protocol>.py``): both found by name.

Earlier lines say what set-up was spent on, whether anything compiled
inside the window, how many samples stand behind each percentile and
how busy the generator was.  The last line is the result object; its
last key, ``compared``, holds each number ``correct`` compared beside its
limit, and the last lines on standard error say the same.
Without an accelerator (or outside a checkout of the program) the exit
code is not 0 and no result is printed; ``--rehearse-cpu`` is the
explicit toy-size CPU mode of the tests, marked ``"rehearsal": true``.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from harness import lengths, manifest  # noqa: E402
from harness.peaks import UnknownDevice, peaks  # noqa: E402
from harness.served import BenchFailure, Served  # noqa: E402

ROOT = manifest.ROOT
RUNS_DIR = os.path.join(ROOT, ".bench_runs")
TRACE_S = 3.0  # of the steady window, from 30 % of its length on


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """One child process in its own group, output to a file."""

    def __init__(self, name: str, argv: list, env: dict, log_dir: str, cpus=None):
        self.name = name
        self.log_path = os.path.join(log_dir, f"{name}.log")
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=log,
                                         stderr=subprocess.STDOUT, start_new_session=True)
        if cpus:  # before the child has started a thread; its threads inherit
            os.sched_setaffinity(self.proc.pid, cpus)

    def log_text(self) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return f.read()
        except OSError:
            return ""

    def tail(self, lines: int = 30) -> str:
        return "".join(ln[:300] + "\n" * (len(ln) > 300)
                       for ln in self.log_text().splitlines(True)[-lines:])

    def stop(self, grace: float = 30.0) -> None:
        for sig, wait in ((signal.SIGTERM, grace), (signal.SIGKILL, 10.0)):
            if self.proc.poll() is not None:
                break
            try:
                os.killpg(self.proc.pid, sig)
            except ProcessLookupError:
                break
            try:
                self.proc.wait(timeout=wait)
            except subprocess.TimeoutExpired:
                pass
        try:  # whatever of the group outlived its leader
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


class Control:
    """Requests to the server's control thread, as files."""

    def __init__(self, path: str):
        self.path = path
        os.makedirs(path, exist_ok=True)

    def ask(self, request: str, answer: str, timeout: float) -> str:
        open(os.path.join(self.path, request), "w").close()
        deadline = time.monotonic() + timeout
        target = os.path.join(self.path, answer)
        while time.monotonic() < deadline:
            if os.path.exists(target):
                with open(target) as f:
                    text = f.read()
                os.unlink(target)
                return text
            time.sleep(0.01)
        raise BenchFailure(f"the server's control thread did not answer {request} "
                           f"in {timeout:.0f} s")


def main(argv=None) -> int:
    t_start = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy-size control-flow rehearsal on the CPU backend (tests only)")
    args = ap.parse_args(argv)

    children = []
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(1))
    try:
        return run(args, t_start, children)
    except (BenchFailure, manifest.ManifestError, UnknownDevice, OSError,
            subprocess.TimeoutExpired, KeyError, ValueError) as e:
        sys.stderr.write(f"[bench] FAILED: {type(e).__name__}: {e}\n")
        for child in children:
            sys.stderr.write(f"--- {child.name} log tail ---\n{child.tail()}\n")
        return 1
    finally:
        for child in reversed(children):
            child.stop(grace=5.0)


def run(args, t_start: float, children: list) -> int:
    m = manifest.load_manifest()
    cell, config, traffic = manifest.cell(m, args.workload)
    if not os.path.isdir(os.path.join(ROOT, "seldon_core_tpu")):
        raise BenchFailure(f"{ROOT} holds no seldon_core_tpu: not a checkout of the program")
    kind = manifest.module("harness/kinds", config["kind"])
    work = kind.multiset(traffic)
    order = lengths.schedule(work, args.seed)

    run_dir = os.path.join(RUNS_DIR, args.workload)
    shutil.rmtree(run_dir, ignore_errors=True)
    control = Control(os.path.join(run_dir, "control"))
    cpus = sorted(os.sched_getaffinity(0))
    gen_cpus = cpus[-2:] if len(cpus) >= 4 else cpus
    server_cpus = cpus[:-2] if len(cpus) >= 4 else cpus
    ref_cpus = cpus[-8:] if len(cpus) >= 12 else cpus
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    if args.rehearse_cpu:
        # the chip's decode lane is the pool chunk; off the chip the engine
        # would pick the ring chunk, whose shapes round differently
        env.update(JAX_PLATFORMS="cpu", SELDON_TPU_CHUNK_IMPL="pool")
    want = "cpu" if args.rehearse_cpu else "tpu"

    # the reference first: it builds its weights while the server loads
    served_path = os.path.join(run_dir, "served.json")
    verdict_path = os.path.join(run_dir, "reference.json")
    config_path = os.path.join(run_dir, "config.json")
    with open(config_path, "w") as f:
        json.dump(config, f)
    reference = Child("reference", [
        sys.executable, os.path.join(BENCH_DIR, "reference", "check.py"),
        "--config", config_path, "--seed", str(args.seed),
        "--served", served_path, "--out", verdict_path,
    ], dict(env, JAX_PLATFORMS="cpu"), run_dir, ref_cpus)
    children.append(reference)

    spec = copy.deepcopy(config["deployment"])
    for predictor in spec["predictors"] if config.get("seed_parameter") else []:
        predictor["graph"]["parameters"].append(
            {"name": config["seed_parameter"], "value": str(args.seed), "type": "INT"})
    spec_path = os.path.join(run_dir, "deployment.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    http_port, grpc_port = free_port(), free_port()
    server = Child("server", [
        sys.executable, os.path.join(BENCH_DIR, "harness", "serve.py"),
        "--spec", spec_path, "--http-port", str(http_port), "--grpc-port", str(grpc_port),
        "--control", control.path, "--require", want, "--chips", str(cell["chips"]),
    ], env, run_dir, server_cpus)
    children.append(server)
    ports = {"http": http_port, "grpc": grpc_port}
    served = Served(ports, config, traffic)

    deadline = time.monotonic() + 1100
    while True:
        code = server.proc.poll()
        if code is not None:
            raise BenchFailure(f"the server exited with code {code} before it was ready"
                               + (": no accelerator, or fewer chips than the cell asks for"
                                  if code == 3 else ""))
        try:
            with urllib.request.urlopen(f"{served.base}/ready", timeout=2) as resp:
                if resp.status == 200:
                    break
        except (OSError, urllib.error.URLError):
            pass
        if time.monotonic() > deadline:
            raise BenchFailure("the server was not ready after 1100 s")
        time.sleep(0.2)
    t_ready = time.monotonic()
    device = served.device()
    chip_peaks = None if args.rehearse_cpu else peaks(device["kind"])

    # the seeded sample: served now, judged by the reference meanwhile
    sample = kind.serve_sample(served, work, args.seed)
    with open(served_path + ".tmp", "w") as f:
        json.dump(sample, f)
    os.replace(served_path + ".tmp", served_path)
    t_sample = time.monotonic()

    warm = kind.warm_up(served, server, work, args.seed)
    t_warm = time.monotonic()

    while not os.path.exists(verdict_path):
        if reference.proc.poll() not in (None, 0):
            raise BenchFailure(f"the reference exited with code {reference.proc.returncode}")
        if time.monotonic() > t_warm + 600:
            raise BenchFailure("the reference gave no verdict 600 s after warm-up")
        time.sleep(0.05)
    verdict = manifest.load_json(verdict_path)
    reference.proc.wait(timeout=60)
    t_reference = time.monotonic()

    # ramp and window: the generator's own process
    plan = dict(served.plan, protocol=traffic["protocol"], kind=config["kind"],
                clients=traffic["clients"], schedule=order, seed=args.seed,
                seconds=args.seconds, ramp=traffic["ramp"], ramp_timeout_s=300)
    plan_path = os.path.join(run_dir, "plan.json")
    results_path = os.path.join(run_dir, "results.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    generator = Child("loadgen", [
        sys.executable, os.path.join(BENCH_DIR, "harness", "loadgen.py"),
        "--plan", plan_path, "--out", results_path,
    ], env, run_dir, gen_cpus)
    children.append(generator)
    window_path = os.path.join(run_dir, "window.json")
    while not os.path.exists(window_path):
        if generator.proc.poll() is not None:
            raise BenchFailure(f"the load generator exited with code "
                               f"{generator.proc.returncode} during the ramp")
        time.sleep(0.005)
    window = manifest.load_json(window_path)
    t0, t1 = window["t0"], window["t1"]
    setup_s = t0 - t_start
    compiles_before = served.jit_compiles()
    engine = {"window": [kind.counters(served), None], "samples": []}

    trace_span = None
    if args.trace:
        trace_at = t0 + 0.3 * args.seconds
        while time.monotonic() < trace_at:
            engine["samples"].append(kind.counters(served))
            time.sleep(min(1.0, max(0.0, trace_at - time.monotonic())))
        ta = float(control.ask("trace.start", "trace.started", 120))
        before = kind.counters(served)
        time.sleep(min(TRACE_S, 0.4 * args.seconds))
        after = kind.counters(served)
        tb = float(control.ask("trace.stop", "trace.done", 300))
        engine["trace"] = [before, after]
        trace_span = (ta, tb)
    while time.monotonic() < t1:
        if args.trace:
            engine["samples"].append(kind.counters(served))
        time.sleep(min(1.0, max(0.0, t1 - time.monotonic())))
    engine["window"][1] = kind.counters(served)
    window_compiles = served.jit_compiles() - compiles_before

    try:
        generator.proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        raise BenchFailure("the load generator did not end") from None
    if generator.proc.returncode != 0:
        raise BenchFailure(f"the load generator exited with code {generator.proc.returncode}")
    results = manifest.load_json(results_path)
    memory = json.loads(control.ask("device.ask", "device.json", 60))
    server.stop(grace=60.0)

    trace = None
    if args.trace:
        out = subprocess.run(
            [sys.executable, os.path.join(BENCH_DIR, "harness", "trace_reduce.py"),
             os.path.join(control.path, "trace")],
            cwd=ROOT, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
            timeout=600)
        if out.returncode != 0:
            raise BenchFailure(f"the trace reduction failed:\n{out.stderr[-2000:]}")
        trace = json.loads(out.stdout.strip().splitlines()[-1])
        if not trace.get("devices") and not args.rehearse_cpu:
            raise BenchFailure(f"the trace holds no device plane: {trace}")

    ctx = {
        "seconds": args.seconds, "window": (t0, t1), "records": results["records"],
        "setup_s": setup_s, "engine": engine, "trace": trace, "trace_span": trace_span,
        "config": config, "traffic": traffic, "peaks": chip_peaks, "device": device,
        "memory": memory, "samples": {},
    }
    from harness import window as win

    folder = "layer_metrics" if args.trace else "end_to_end"
    metrics = {}
    for entry in manifest.metrics_of(m, args.workload, "per_layer" if args.trace
                                     else "end_to_end"):
        value = manifest.reader(folder, entry["name"])(ctx)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    attempted = win.sent_in_window(ctx)
    failed = win.failed_in_window(ctx)

    say("set-up: " + json.dumps({
        "server_ready_s": t_ready - t_start, "sample_s": t_sample - t_ready,
        "warm_up_s": t_warm - t_sample, "reference_wait_s": t_reference - t_warm,
        "ramp_s": t0 - t_reference, "setup_s": setup_s,
        "reference": {k: verdict[k] for k in ("params_s", "forward_s")}, "warm_up": warm}))
    say("reference: " + kind.verdict_line(verdict))
    if window_compiles or warm["missing"]:
        say(f"WARNING: {window_compiles:.0f} PROGRAM(S) COMPILED INSIDE THE WINDOW; "
            f"warm-up missed {warm['missing']}")
    else:
        say("window compiles: 0")
    say(f"samples behind each percentile: {json.dumps(ctx['samples'])}; requests sent in "
        f"the window {len(attempted)}, failed {len(failed)}"
        + (f" ({failed[0].get('error')})" if failed else ""))
    if trace and trace.get("devices"):
        programs = {k: round(v["seconds"], 4) for k, v in trace["modules"].items()
                    if v["seconds"] >= 0.001}
        say(f"traced {trace['window_s']:.3f} s, device busy {trace['busy_s']:.3f} s; "
            f"device seconds by program: {json.dumps(programs)}")
    say(f"generator busy share {results['busy_share']:.4f} of one CPU; threads left "
        f"{results['threads_left']}; ramp {results['ramp_s']:.1f} s")

    device_out = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"],
                  "memory_peak_bytes": max(memory["peak_bytes_in_use"] or [0])}
    result = {"correct": bool(verdict["ok"]) and not failed, "attempted": len(attempted),
              "failed": len(failed), "metrics": metrics, "device": device_out}
    if args.trace and trace and trace.get("devices"):
        device_out["busy_s"] = trace["busy_s"]
        device_out["window_s"] = trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    if args.rehearse_cpu:
        result["rehearsal"] = True
    # each number ``correct`` compared, beside its limit: the line's last key
    # and the last lines on standard error
    compared = dict(getattr(kind, "compared", lambda _v: {})(verdict),
                    failed_requests=[len(failed), 0])
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    for name, (number, limit) in compared.items():
        sys.stderr.write(f"[bench] compared {name}: {number} against a limit of {limit}\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
