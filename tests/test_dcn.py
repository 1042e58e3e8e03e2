"""Cross-process (DCN) serving: remote graph nodes + multi-host jax.

VERDICT round-2 item 7: a deployment whose graph spans
supervisor-spawned worker processes via GrpcClient edges (process
placement emitting endpoints), plus a real 2-process
``jax.distributed`` exercise of parallel/multihost.py.

Reference analogue: the operator creates one Deployment+Service per
graph container and the engine calls them over the pod network
(reference: operator/controllers/seldondeployment_controller.go:268-494,
engine/.../InternalPredictionService.java:192-467); multi-host compute
is the reference's NCCL/MPI layer re-done as jax.distributed + XLA
collectives over DCN.
"""

import asyncio
import os
import socket
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from seldon_core_tpu.controlplane import Deployer, TpuDeployment
from seldon_core_tpu.runtime.message import InternalMessage

pytestmark = pytest.mark.slow  # compile-heavy: excluded from the default fast tier (make test-all)


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _remote_child_spec(name: str) -> TpuDeployment:
    return TpuDeployment.from_dict(
        {
            "name": name,
            "predictors": [
                {
                    "name": "main",
                    "traffic": 100,
                    "graph": {
                        "name": "combiner",
                        "type": "COMBINER",
                        "implementation": "AVERAGE_COMBINER",
                        "children": [
                            {
                                "name": "local-leg",
                                "type": "MODEL",
                                "implementation": "SIMPLE_MODEL",
                            },
                            {
                                "name": "remote-leg",
                                "type": "MODEL",
                                "implementation": "SIMPLE_MODEL",
                                "remote": True,
                            },
                        ],
                    },
                }
            ],
        }
    )


@pytest.mark.e2e
class TestRemoteGraphNode:
    def test_graph_spans_worker_process_over_grpc(self):
        """remote:true node runs in a supervisor-spawned process; the
        executor reaches it over a GrpcClient DCN edge; the combiner
        merges the local and remote legs."""
        spec = _remote_child_spec("dcn-e2e")

        async def scenario():
            deployer = Deployer()
            managed = await deployer.apply(spec, ready_timeout_s=90.0)
            gen = managed.current
            assert gen.supervisor is not None
            workers = list(gen.supervisor.processes.values())
            assert len(workers) == 1
            assert workers[0].alive() and workers[0].ready()
            # endpoint was emitted onto the generation's cloned graph...
            remote_unit = [
                u for u in gen.spec.predictors[0].graph.walk() if u.name == "remote-leg"
            ][0]
            assert remote_unit.endpoint is not None
            assert remote_unit.endpoint.port == workers[0].spec.grpc_port
            # ...but never onto the caller's spec object
            caller_unit = [
                u for u in spec.predictors[0].graph.walk() if u.name == "remote-leg"
            ][0]
            assert caller_unit.endpoint is None

            out = await managed.gateway.predict(InternalMessage(payload=np.ones((1, 2))))
            assert out.status is None or out.status.get("status") != "FAILURE"
            # both legs return StubModel.OUTPUT; the average equals it
            np.testing.assert_allclose(out.array(), [[0.9, 0.05, 0.05]])
            # the remote hop is recorded in the request path
            assert "remote-leg" in out.meta.request_path

            pid = workers[0].proc.pid
            await deployer.delete("dcn-e2e")
            return pid

        pid = asyncio.run(scenario())
        for _ in range(50):
            try:
                os.kill(pid, 0)
            except OSError:
                break
            time.sleep(0.1)
        else:
            raise AssertionError(f"worker pid {pid} still alive after delete")

    def test_rolling_reapply_respawns_worker(self):
        """Re-applying the same spec object builds a fresh generation
        with its own worker; the old worker is drained afterwards."""
        spec = _remote_child_spec("dcn-roll")

        async def scenario():
            deployer = Deployer()
            managed = await deployer.apply(spec, ready_timeout_s=90.0)
            first = managed.current
            first_worker = list(first.supervisor.processes.values())[0]
            first_port = first_worker.spec.grpc_port
            managed = await deployer.apply(spec, ready_timeout_s=90.0)
            second = managed.current
            second_port = list(second.supervisor.processes.values())[0].spec.grpc_port
            assert second.generation == first.generation + 1
            assert second_port != first_port
            out = await managed.gateway.predict(InternalMessage(payload=np.ones((1, 2))))
            np.testing.assert_allclose(out.array(), [[0.9, 0.05, 0.05]])
            # old generation's drain (background) eventually stops its worker
            for _ in range(100):
                if not first_worker.alive():
                    break
                await asyncio.sleep(0.1)
            else:
                raise AssertionError("old generation worker never stopped")
            await deployer.delete("dcn-roll")

        asyncio.run(scenario())


_MULTIHOST_WORKER = textwrap.dedent(
    """
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    from seldon_core_tpu.parallel import multihost

    is_multi = multihost.initialize()
    info = multihost.host_info()
    assert is_multi, info
    assert info["process_count"] == 2, info
    assert info["global_devices"] == 8, info

    import jax.numpy as jnp
    from functools import partial
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh = multihost.global_mesh({"data": 8})

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P())
    def total(x):
        return lax.psum(x, "data")

    # replicated input; psum over the 8 devices spanning both processes
    y = float(total(jnp.asarray(1.0)))
    assert y == 8.0, y
    print(f"MULTIHOST_OK process={info['process_index']} psum={y}", flush=True)
    """
)


@pytest.mark.e2e
class TestMultihostJaxDistributed:
    def test_two_process_psum_over_dcn(self, tmp_path):
        """parallel/multihost.py drives a real 2-process
        jax.distributed runtime; a psum spans both processes."""
        port = socket.socket()
        port.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{port.getsockname()[1]}"
        port.close()

        script = tmp_path / "worker.py"
        script.write_text(_MULTIHOST_WORKER)
        procs = []
        for pid in range(2):
            env = dict(os.environ)
            env.update(
                {
                    "JAX_COORDINATOR_ADDRESS": coord,
                    "JAX_NUM_PROCESSES": "2",
                    "JAX_PROCESS_ID": str(pid),
                    "JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                    # the worker runs from a tmp script path; the repo
                    # root is not implicitly importable there
                    "PYTHONPATH": REPO_ROOT + os.pathsep + env.get("PYTHONPATH", ""),
                }
            )
            procs.append(
                subprocess.Popen(
                    [sys.executable, str(script)],
                    env=env,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                    cwd=REPO_ROOT,
                )
            )
        outputs = []
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outputs.append(out)
        for i, (p, out) in enumerate(zip(procs, outputs)):
            assert p.returncode == 0, f"process {i} failed:\n{out}"
            assert "MULTIHOST_OK" in out, out
        assert any("process=0" in o for o in outputs)
        assert any("process=1" in o for o in outputs)
