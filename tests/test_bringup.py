"""Bring-up invariants (PR 21): the things that decide whether the
serving path runs on a chip at all, checked on the CPU tier.

* the compile cache is placeable from outside and never moves;
* a deployment's ``/health/status`` says which device served;
* one process per chip: device-exclusive components are refused as
  worker processes, everything else is held to the CPU backend, and
  placement never initialises the backend;
* nothing a kernel accepts in the interpreter is refused by the chip
  for a reason the wrapper can see (VMEM of a padded block).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STREAMING_LM = "seldon_core_tpu.models.paged.StreamingLM"
STUB = "seldon_core_tpu.engine.units.StubModel"


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------


class TestCompileCache:
    @pytest.fixture
    def config_writes(self, monkeypatch):
        writes = []
        monkeypatch.setattr(jax.config, "update", lambda k, v: writes.append((k, v)))
        return writes

    def test_env_placed_cache_writes_nothing(self, monkeypatch, config_writes):
        from seldon_core_tpu.utils.compile_cache import configure_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert configure_compile_cache() == "/somewhere/else"
        assert config_writes == []  # jax reads the variable itself

    def test_default_is_the_checkout_and_never_moves(self, monkeypatch, config_writes):
        from seldon_core_tpu.utils.compile_cache import configure_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert configure_compile_cache() == want
        assert configure_compile_cache() == want
        assert config_writes == [("jax_compilation_cache_dir", want)] * 2
        # and another process, started elsewhere, lands on the same path
        env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
        env["PYTHONPATH"] = REPO
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax\n"
             "from seldon_core_tpu.utils.compile_cache import configure_compile_cache\n"
             "print(configure_compile_cache())\n"
             "print(jax.config.jax_compilation_cache_dir)"],
            cwd="/", env=env, capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr
        assert out.stdout.split() == [want, want]


# ---------------------------------------------------------------------------
# /health/status says where it ran
# ---------------------------------------------------------------------------


class TestHealthStatus:
    """The component hooks are pinned where the components are already
    loaded (tests/test_jaxserver.py, tests/test_paged_tp.py); here, the
    gateway route that carries them to a deployment's client."""

    def test_gateway_route_serves_component_health(self):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine import PredictorService, UnitSpec
        from seldon_core_tpu.engine.server import Gateway, build_gateway_app
        from seldon_core_tpu.models.jaxserver import JaxServer

        server = JaxServer(model="mlp", num_classes=3, input_shape=[4],
                           dtype="float32", max_batch_size=2, warmup=False)
        unit = UnitSpec(name="clf", type="MODEL", component=server)
        gateway = Gateway([(PredictorService(unit, name="main"), 1.0)])

        async def scenario():
            async with TestClient(TestServer(build_gateway_app(gateway))) as client:
                resp = await client.get("/health/status")
                assert resp.status == 200
                return await resp.json()

        try:
            body = asyncio.run(scenario())
        finally:
            server.unload()
        assert body["frontend"] == "python"
        device = body["predictors"]["main"]["clf"]["device"]
        assert device["platform"] == "cpu" and device["count"] == len(jax.devices())
        assert device["pallas_interpret"] is True  # not a TPU: interpreted


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------


def _remote_spec(implementation):
    from seldon_core_tpu.controlplane import TpuDeployment

    return TpuDeployment.from_dict({
        "name": "chip-guard",
        "predictors": [{
            "name": "main",
            "graph": {
                "name": "root", "type": "MODEL", "implementation": "SIMPLE_MODEL",
                "children": [{
                    "name": "worker", "type": "MODEL",
                    "implementation": implementation, "remote": True,
                }],
            },
        }],
    })


class TestOneProcessPerChip:
    def test_device_exclusive_remote_worker_refused(self, monkeypatch):
        """Beside a deployer that may hold the chip, a device-exclusive
        worker would hang on device acquisition: refused with guidance,
        before anything is spawned."""
        from seldon_core_tpu.controlplane import supervisor
        from seldon_core_tpu.controlplane.deployer import build_generation
        from seldon_core_tpu.controlplane.spec import DeploymentSpecError

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(
            supervisor.subprocess, "Popen",
            lambda *a, **k: pytest.fail("a worker was spawned"),
        )
        with pytest.raises(DeploymentSpecError, match="device-exclusive.*in-process"):
            build_generation(_remote_spec("STREAMING_LM"))

    def test_cpu_held_deployment_passes_the_guard(self, monkeypatch):
        from seldon_core_tpu.controlplane.deployer import _reject_device_exclusive_remote

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        _reject_device_exclusive_remote("worker", STREAMING_LM, {})  # no raise
        monkeypatch.delenv("JAX_PLATFORMS")
        _reject_device_exclusive_remote("worker", STREAMING_LM, {"JAX_PLATFORMS": "cpu"})
        _reject_device_exclusive_remote("worker", STUB, {})  # needs no device

    def test_supervisor_pins_deviceless_workers_to_cpu(self, monkeypatch):
        from seldon_core_tpu.controlplane import supervisor
        from seldon_core_tpu.controlplane.supervisor import ProcessSpec, SupervisedProcess

        spawned = []

        class FakeProc:
            pid = 1

        monkeypatch.setattr(
            supervisor.subprocess, "Popen",
            lambda cmd, env, cwd: spawned.append(env) or FakeProc(),
        )
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        SupervisedProcess(ProcessSpec("stub", STUB, 1, 2))._spawn()
        SupervisedProcess(ProcessSpec("lm", STREAMING_LM, 3, 4))._spawn()
        SupervisedProcess(ProcessSpec("unknown", "no.such.Module", 5, 6))._spawn()
        SupervisedProcess(
            ProcessSpec("explicit", STUB, 7, 8, env={"JAX_PLATFORMS": "tpu"}))._spawn()
        assert [e["JAX_PLATFORMS"] for e in spawned] == ["cpu", "tpu", "tpu", "tpu"]

    def test_supervisor_admits_one_chip_owner(self, monkeypatch):
        from seldon_core_tpu.controlplane.spec import DeploymentSpecError
        from seldon_core_tpu.controlplane.supervisor import (
            SupervisedProcess,
            Supervisor,
            replica_worker_specs,
        )

        monkeypatch.setattr(SupervisedProcess, "start", lambda self: None)
        monkeypatch.setattr(SupervisedProcess, "wait_ready", lambda self, t=0: True)
        monkeypatch.setattr(SupervisedProcess, "stop", lambda self, grace_s=0: None)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        sup = Supervisor()
        with pytest.raises(DeploymentSpecError, match="one\\s+process per chip"):
            sup.add_group(replica_worker_specs("lm", replicas=2))
        assert sup.processes == {}  # the group was rolled back
        # held to the CPU backend, any number of replicas is fine
        sup.add_group(replica_worker_specs("lm", replicas=2, env={"JAX_PLATFORMS": "cpu"}))
        assert len(sup.processes) == 2

    def test_placement_never_asks_jax_for_devices(self, monkeypatch):
        from seldon_core_tpu.controlplane import TpuDeployment, plan_placement

        monkeypatch.setattr(jax, "devices", lambda *a: pytest.fail("backend touched"))
        dep = TpuDeployment.from_dict({
            "name": "d", "predictors": [{"name": "main", "graph": {
                "name": "m", "type": "MODEL", "implementation": "SIMPLE_MODEL"}}],
        })
        assert plan_placement(dep).placements == {}
        assert plan_placement(dep, device_ids=[0, 1]).for_predictor("main").device_ids == [0]

    @pytest.mark.parametrize("field", [{"meshAxes": {"data": 2, "model": 2}},
                                       {"deviceIds": [0, 1]}])
    def test_predictor_level_placement_is_refused(self, field):
        """Nothing carries a predictor's meshAxes/deviceIds to its
        components; accepting them would serve on one chip under a
        multi-chip label."""
        from seldon_core_tpu.controlplane import TpuDeployment, default_and_validate
        from seldon_core_tpu.controlplane.spec import DeploymentSpecError

        dep = TpuDeployment.from_dict({
            "name": "d", "predictors": [dict({"name": "main", "graph": {
                "name": "m", "type": "MODEL", "implementation": "SIMPLE_MODEL"}}, **field)],
        })
        with pytest.raises(DeploymentSpecError, match="meshAxes/deviceIds"):
            default_and_validate(dep)


# ---------------------------------------------------------------------------
# kernels: what the interpreter accepts, the chip must too
# ---------------------------------------------------------------------------


class TestKernelFences:
    def test_fused_normalize_refuses_a_block_over_vmem(self):
        """A 224x224x3 image block pads its channel dim to 128 lanes and
        needs ~60 MiB of VMEM: Mosaic refuses it on the v5e
        (tools/probe_kernels.py), so the wrapper refuses it everywhere."""
        import jax.numpy as jnp

        from seldon_core_tpu.ops import fused_normalize, imagenet_affine

        with pytest.raises(ValueError, match="MiB of VMEM.*normalize=False"):
            fused_normalize(jnp.zeros((1, 224, 224, 3), jnp.uint8), *imagenet_affine())

    def test_int8_matmul_pads_k_like_the_chip(self):
        """K pads to the 128-lane tile on every backend, so the
        interpreter runs the path Mosaic compiles."""
        import jax.numpy as jnp

        from seldon_core_tpu.ops import int8_matmul, quantize_weights

        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 100)).astype(np.float32)
        w_q, scale = quantize_weights(rng.normal(size=(100, 128)).astype(np.float32))
        jaxpr = str(jax.make_jaxpr(int8_matmul)(
            jnp.asarray(x), jnp.asarray(w_q), jnp.asarray(scale)))
        assert "f32[8,128]" in jaxpr and "i8[128,128]" in jaxpr  # K: 100 -> 128
        out = int8_matmul(jnp.asarray(x), jnp.asarray(w_q), jnp.asarray(scale))
        ref = x @ (w_q.astype(np.float32) * scale)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)
