#!/usr/bin/env python3
"""Start the serving path on the chip, through the entry points a user calls.

Two servers, one after the other, each its own child process started by
its CLI while this parent stays off jax (a chip belongs to one process):

* ``seldon-tpu-deploy run examples/resnet50_tpu.yaml`` — ResNet-50 bf16,
  224x224x3, max_batch_size 32: uint8 ``Seldon/Predict`` over REST and
  gRPC, single and concurrent, so more than one batcher bucket runs;
  rows are 1000 wide and finite, and REST and gRPC agree.
* ``python -m seldon_core_tpu.runtime.microservice ...StreamingLM`` at
  the bench width (vocab 16384, d512, L8, H8, max_len 1024, page 64,
  16 slots): concurrent ``/predict``s of 5/200/700-token prompts, row
  width == max_new_tokens, a repeated greedy request bit-identical to
  its repeat, and decode in the Mosaic-compiled Pallas kernel
  (``kernel_active`` 1, not interpreted).
* On a host with four or more chips the same LM again with ``tp=4`` and
  with ``tp=2, dp=2``: the degrees it got must be the degrees asked
  for, its tokens must match the one-chip run's (see ``close_to``), and
  every device must hold its share of the KV pool.  Otherwise:
  "skipped: N chips".

Weights are random from a seed; nothing is fetched.  Any check that
fails, any request that fails and any child that dies is exit code 1
and no result line.  Without an accelerator the script fails before it
starts a server; ``--rehearse-cpu`` is the explicit argument that runs
the same control flow at a toy size on the CPU backend (Pallas
interpreted), to debug this script and nothing else.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
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
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
STREAMING_LM = "seldon_core_tpu.models.paged.StreamingLM"
PHASES = ("resnet", "lm", "mesh")

# the full-width configurations, and the toy ones --rehearse-cpu swaps in
RESNET = dict(spec="examples/resnet50_tpu.yaml", side=224, classes=1000, ready_s=900)
LM = dict(
    params=dict(vocab_size=16384, d_model=512, num_layers=8, num_heads=8,
                max_len=1024, max_slots=16, max_new_tokens=32),
    prompt_lens=(5, 200, 700), ready_s=600,
)
TOY_RESNET_SPEC = """\
name: rehearsal-classifier
annotations:
  seldon.io/frontend: native
predictors:
  - name: main
    traffic: 100
    graph:
      name: resnet
      type: MODEL
      implementation: JAX_SERVER
      parameters:
        - {name: model, value: resnet_tiny, type: STRING}
        - {name: num_classes, value: "10", type: INT}
        - {name: max_batch_size, value: "8", type: INT}
"""
TOY_RESNET = dict(side=32, classes=10, ready_s=600)
TOY_LM = dict(
    params=dict(vocab_size=64, d_model=32, num_layers=1, num_heads=2,
                max_len=128, max_slots=4, max_new_tokens=6, page_size=8,
                steps_per_call=4),
    prompt_lens=(5, 20, 50), ready_s=600,
)


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def close_to(got: dict, want: dict, who: str, than: str) -> str:
    """Greedy tokens of two bf16 programs that compute the same model
    in a different order (prefix-cached vs whole prefill, kernel vs
    gather lane, sharded vs one-chip reductions) are bit-identical in
    f32 — the CPU tests pin that — and on the chip can part at a
    near-tie of these random weights.  A wrong program agrees by
    chance: ~0 of a 16384 vocabulary.  So: at least half must match,
    and the count is printed as a fact."""
    pairs = [(a, b) for n in want for a, b in zip(got[n], want[n])]
    same, total = sum(a == b for a, b in pairs), len(pairs)
    check(2 * same >= total,
          f"{who}: only {same}/{total} tokens equal to {than}: {got} vs {want}")
    return (f"{same}/{total} tokens equal to {than}"
            + (" (bit-identical)" if same == total else ""))


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_get(url: str, timeout: float = 10.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read()


class Child:
    """One server process in its own process group, output to a file."""

    def __init__(self, name: str, argv: list, env: dict):
        os.makedirs(LOG_DIR, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(LOG_DIR, f"{name}.log")
        self.t0 = time.monotonic()
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                argv, cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        say(f"{name}: started pid {self.proc.pid}: {' '.join(argv[:6])} ...")

    def check_alive(self) -> None:
        code = self.proc.poll()
        check(code is None, f"{self.name}: child died (exit code {code})")

    def wait_ready(self, url: str, timeout_s: float) -> float:
        """Poll ``url`` until it answers 200; seconds since the spawn."""
        deadline = self.t0 + timeout_s
        while time.monotonic() < deadline:
            self.check_alive()
            try:
                if http_get(url, timeout=2.0)[0] == 200:
                    return time.monotonic() - self.t0
            except (OSError, urllib.error.URLError):
                pass
            time.sleep(0.5)
        raise SmokeFailure(f"{self.name}: not ready after {timeout_s:.0f}s ({url})")

    def tail(self, lines: int = 40) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(ln[:300] + "\n" * (len(ln) > 300)
                               for ln in f.readlines()[-lines:])
        except OSError:
            return ""

    def stop(self) -> None:
        """SIGTERM the group (a deployer may have supervised workers of
        its own), give the leader time to drain, then SIGKILL whatever
        of the group is left."""
        for sig, grace in ((signal.SIGTERM, 30.0), (signal.SIGKILL, 10.0)):
            try:
                os.killpg(self.proc.pid, sig)
            except ProcessLookupError:
                return
            try:
                self.proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                pass


@contextlib.contextmanager
def serving(child: Child):
    """The child serves for the body; it must still be alive at the
    end, its log tail is shown when anything fails, and it is stopped
    either way."""
    try:
        yield child
        child.check_alive()
    except BaseException:
        sys.stderr.write(f"--- {child.name} log tail ---\n{child.tail()}\n")
        raise
    finally:
        child.stop()


def cache_entries(path: str) -> int:
    try:
        return sum(1 for e in os.scandir(path) if e.is_file())
    except OSError:
        return 0


def build_native() -> str:
    """Rebuild the native core from the tracked sources: the copy on
    disk may come from another machine (-march=native) or another
    commit, and its mtime says nothing about either."""
    if not (shutil.which("make") and shutil.which("g++")):
        return "no toolchain"
    res = subprocess.run(
        ["make", "-B", "-C", os.path.join(ROOT, "native")],
        capture_output=True, text=True, timeout=600,
    )
    check(res.returncode == 0,
          f"g++ and make are present and the native build failed:\n{res.stderr[-2000:]}")
    return "built"


def probe_device(env: dict) -> dict:
    """What jax finds, asked of a throwaway child (it gives the chip
    back when it exits; this process must never hold it)."""
    code = (
        "import json, jax; d = jax.devices(); "
        "print(json.dumps({'platform': d[0].platform, 'kind': d[0].device_kind, "
        "'count': len(d)}))"
    )
    res = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    check(res.returncode == 0, f"jax found no device:\n{res.stderr[-2000:]}")
    return json.loads(res.stdout.strip().splitlines()[-1])


def same_device(reported: dict, device: dict, who: str) -> None:
    got = {k: reported.get(k) for k in ("platform", "kind", "count")}
    check(got == device, f"{who} computes on {got}, the probe found {device}")


# ---------------------------------------------------------------------------
# (a) ResNet-50 behind the deployer
# ---------------------------------------------------------------------------

def resnet_phase(cfg: dict, env: dict, device: dict, native: str, cache_dir: str) -> None:
    import numpy as np

    from seldon_core_tpu.client.client import SeldonTpuClient

    http_port, grpc_port = free_port(), free_port()
    before = cache_entries(cache_dir)
    child = Child("resnet", [
        sys.executable, "-m", "seldon_core_tpu.controlplane.deployer", "run",
        cfg["spec"], "--host", "127.0.0.1",
        "--http-port", str(http_port), "--grpc-port", str(grpc_port),
    ], env)
    with serving(child):
        base = f"http://127.0.0.1:{http_port}"
        ready_s = child.wait_ready(f"{base}/ready", cfg["ready_s"])

        def health():
            """(frontend, the one component's health_status)."""
            status = json.loads(http_get(f"{base}/health/status")[1])
            (node,) = [n for nodes in status["predictors"].values() for n in nodes.values()]
            return status["frontend"], node

        frontend, node = health()
        same_device(node["device"], device, "the ResNet server")
        say(f"resnet: ready after {ready_s:.1f} s of set-up (load + warm-up compiles of "
            f"buckets {node['buckets']}); compile cache entries {before} -> "
            f"{cache_entries(cache_dir)}")
        say(f"resnet: frontend that served: {frontend} (native core: {native})")
        check(frontend == "native" or native == "no toolchain",
              "the spec asks for the native frontend, g++/make are present, "
              "and the python app served")

        side, classes = cfg["side"], cfg["classes"]
        rng = np.random.default_rng(0)
        images = rng.integers(0, 256, size=(16, side, side, 3)).astype(np.uint8)

        def predict(transport: str, batch, port: int = grpc_port):
            client = SeldonTpuClient(
                http_port=http_port, grpc_port=port, transport=transport, timeout_s=120.0)
            try:
                resp = client.predict(batch)
            finally:
                client.close()
            check(resp.success, f"resnet {transport} predict failed: {resp.raw}")
            out = np.asarray(resp.data, np.float32)
            check(out.shape == (len(batch), classes),
                  f"resnet {transport}: rows {out.shape}, want ({len(batch)}, {classes})")
            check(bool(np.isfinite(out).all()), f"resnet {transport}: non-finite logits")
            return out

        def batcher():
            return health()[1]["batcher"]

        rest_row = predict("rest", images[:1])
        grpc_row = predict("grpc", images[:1])
        scale = float(np.abs(rest_row).max()) or 1.0
        check(float(np.abs(rest_row - grpc_row).max()) <= 0.02 * scale,
              "REST and gRPC disagree on the same image")
        one = batcher()
        check(one["batches"] >= 2 and one["padded_rows"] == 0,
              f"single-row requests should fill bucket 1 exactly: {one}")
        predict("grpc", images[:5])  # 5 rows pad into a larger bucket
        five = batcher()
        check(five["padded_rows"] > 0, f"a 5-row request padded nothing: {five}")
        with ThreadPoolExecutor(8) as pool:
            futs = [pool.submit(predict, ("rest", "grpc")[i % 2], images[i:i + 1])
                    for i in range(8)]
            rows = [f.result() for f in futs]
        if frontend == "native":
            # the C++ h2c lane: gRPC on the HTTP port, flat uint8 rows
            flat = images[:2].reshape(2, -1)
            h2c = predict("grpc", flat, port=http_port)
            ref = predict("grpc", images[:2])
            check(float(np.abs(h2c - ref).max()) <= 0.02 * scale,
                  "native h2c lane and python gRPC lane disagree")
        end = batcher()
        say(f"resnet: {end['batches']} device batches, {end['rows']} rows, "
            f"{end['padded_rows']} padded rows; {2 + len(rows)} single-row and one "
            f"5-row request answered, all {classes} wide and finite")


# ---------------------------------------------------------------------------
# (b) StreamingLM behind the microservice CLI
# ---------------------------------------------------------------------------

def lm_run(name: str, cfg: dict, env: dict, device: dict, cache_dir: str,
           extra: dict) -> dict:
    """Serve one StreamingLM child and return its tokens per prompt
    length plus the lane it reported."""
    import numpy as np

    from seldon_core_tpu.client.client import SeldonTpuClient

    params = dict(cfg["params"], **extra)
    typed = [{"name": k, "value": str(v), "type": "INT"} for k, v in params.items()]
    http_port = free_port()
    before = cache_entries(cache_dir)
    child = Child(name, [
        sys.executable, "-m", "seldon_core_tpu.runtime.microservice", STREAMING_LM,
        "--api", "REST", "--host", "127.0.0.1", "--http-port", str(http_port),
        "--parameters", json.dumps(typed),
    ], env)
    with serving(child):
        base = f"http://127.0.0.1:{http_port}"
        ready_s = child.wait_ready(f"{base}/health/ping", cfg["ready_s"])
        lane = json.loads(http_get(f"{base}/health/status")[1])["jsonData"]
        same_device(lane["device"], device, name)
        say(f"{name}: ready after {ready_s:.1f} s of set-up; lane {json.dumps(lane)}")

        rng = np.random.default_rng(7)
        prompts = {
            n: rng.integers(0, params["vocab_size"], size=(1, n)).astype(np.int32)
            for n in cfg["prompt_lens"]
        }
        width = params["max_new_tokens"]

        def predict(n: int):
            client = SeldonTpuClient(http_port=http_port, transport="rest", timeout_s=600.0)
            try:
                resp = client.microservice("predict", prompts[n])
            finally:
                client.close()
            check(resp.success, f"{name}: /predict of {n} tokens failed: {resp.raw}")
            out = np.asarray(resp.data)
            check(out.shape == (1, width), f"{name}: row {out.shape}, want (1, {width})")
            toks = out.astype(np.int64)
            check(bool((toks == out).all() and (toks >= 0).all()
                       and (toks < params["vocab_size"]).all()),
                  f"{name}: not token ids: {out}")
            return toks[0].tolist()

        t0 = time.monotonic()
        with ThreadPoolExecutor(len(prompts)) as pool:
            first = dict(zip(prompts, pool.map(predict, prompts)))
        wave_s = time.monotonic() - t0
        # the repeats are served from the prefix cache the first answer
        # left behind: the same program twice, so bit-identical
        mid = sorted(prompts)[1]
        repeats = [predict(mid), predict(mid)]
        check(repeats[0] == repeats[1],
              f"{name}: a repeated greedy request of {mid} tokens changed its "
              f"answer: {repeats}")
        cached = close_to({mid: repeats[0]}, {mid: first[mid]}, name,
                          "the uncached first answer")
        gauges = {}
        for line in http_get(f"{base}/metrics")[1].decode().splitlines():
            if line.startswith("seldon_tpu_engine_") and " " in line:
                key, _, value = line.rpartition(" ")
                gauges[key.split("{")[0]] = float(value)
        active = gauges.get("seldon_tpu_engine_kernel_active")
        say(f"{name}: first wave of {len(prompts)} concurrent prompts {cfg['prompt_lens']} "
            f"took {wave_s:.1f} s, compiles included; a repeated request is "
            f"bit-identical to its repeat, {cached}; "
            f"kernel_active gauge {active}; compile cache entries {before} -> "
            f"{cache_entries(cache_dir)}")
        check(gauges.get("seldon_tpu_engine_tokens_total", 0) >= width * len(prompts),
              f"{name}: /metrics does not count the tokens served: {gauges}")
        lane["kernel_active_gauge"] = active
        return {"tokens": first, "lane": lane}


def lm_phase(cfg, env, device, cache_dir, rehearse: bool) -> dict:
    if rehearse:
        # the kernel lane is TPU-only by default; force puts the toy run
        # through the same lane, interpreted
        env = dict(env, SELDON_TPU_PAGED_KERNEL="force")
    run = lm_run("lm", cfg, env, device, cache_dir, {"tp": 1, "dp": 1})
    lane = run["lane"]
    check(lane["kernel_active"] and lane["kernel_active_gauge"] == 1.0,
          f"decode did not run in the Pallas kernel: {lane}")
    check(lane["device"]["pallas_interpret"] is rehearse,
          f"Pallas interpret mode is {lane['device']['pallas_interpret']}")
    return run


def mesh_phase(cfg, env, device, cache_dir, one_chip: dict) -> None:
    if device["count"] < 4:
        say(f"mesh: skipped: {device['count']} chips")
        return
    for tp, dp in ((4, 1), (2, 2)):
        name = f"lm-tp{tp}-dp{dp}"
        run = lm_run(name, cfg, env, device, cache_dir, {"tp": tp, "dp": dp})
        lane = run["lane"]
        check((lane["tp"], lane["dp"]) == (tp, dp),
              f"{name}: asked for tp={tp} dp={dp}, the engine runs "
              f"tp={lane['tp']} dp={lane['dp']}")
        same = close_to(run["tokens"], one_chip["tokens"], name, "the one-chip run")
        in_use = lane["device"].get("bytes_in_use")
        if in_use is None:
            say(f"{name}: {same}; this backend reports no memory_stats")
            continue
        share = lane["pool_shard_bytes"]
        say(f"{name}: {same}; pool share per device {share} B, "
            f"bytes in use per device {in_use}")
        check(min(in_use[: tp * dp]) >= share,
              f"{name}: a device holds less than its {share} B pool share: {in_use}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on the CPU backend, to debug this script")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help=f"comma-separated subset of {PHASES} (mesh needs lm)")
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = set(phases) - set(PHASES)
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}")
    if "mesh" in phases and "lm" not in phases:
        ap.error("the mesh phase compares against the lm phase's tokens")

    env = dict(os.environ, PYTHONUNBUFFERED="1")
    resnet, lm = dict(RESNET), dict(LM)
    if args.rehearse_cpu:
        os.makedirs(LOG_DIR, exist_ok=True)
        spec = os.path.join(LOG_DIR, "rehearsal_resnet.yaml")
        with open(spec, "w") as f:
            f.write(TOY_RESNET_SPEC)
        resnet, lm = dict(TOY_RESNET, spec=spec), dict(TOY_LM)
        env.update(JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=8")
    want = "cpu" if args.rehearse_cpu else "tpu"

    # a killed smoke must still stop its child: turn SIGTERM into an
    # exit that runs the finally blocks
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(1))
    t_start = time.monotonic()
    try:
        sys.path.insert(0, ROOT)
        # fails here in a directory that holds nothing else of the repo
        from seldon_core_tpu.utils.compile_cache import compile_cache_dir

        device = probe_device(env)
        say(f"platform {device['platform']}, device_kind {device['kind']}, "
            f"{device['count']} device(s)")
        check(device["platform"] == want,
              f"jax found platform {device['platform']!r}, not {want!r}: no accelerator "
              "(--rehearse-cpu is the explicit CPU mode)")
        cache_dir = compile_cache_dir()
        say(f"compile cache: {cache_dir} ({cache_entries(cache_dir)} entries)")
        native = build_native()
        one_chip = None
        if "resnet" in phases:
            resnet_phase(resnet, env, device, native, cache_dir)
        if "lm" in phases:
            one_chip = lm_phase(lm, env, device, cache_dir, args.rehearse_cpu)
        if "mesh" in phases:
            mesh_phase(lm, env, device, cache_dir, one_chip)
        check("jax" not in sys.modules, "the smoke's parent imported jax")
    except (SmokeFailure, subprocess.TimeoutExpired, OSError, ImportError,
            KeyError, ValueError) as e:
        sys.stderr.write(f"[chip_smoke] FAILED: {type(e).__name__}: {e}\n")
        return 1
    say(f"phases {phases} passed in {time.monotonic() - t_start:.0f} s")
    result = {"ok": True, "device": device}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
