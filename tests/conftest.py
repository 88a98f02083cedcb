"""Shared pytest fixtures.

Mirrors the reference's conftest pattern (``python/ray/tests/conftest.py``:
``ray_start_regular`` :305 boots a real single-node runtime in-process;
``ray_start_cluster`` :386 boots a multi-node cluster on one machine).

JAX-level tests run on a virtual 8-device CPU mesh
(``xla_force_host_platform_device_count``), the standard way to test TPU
sharding logic without TPU hardware.
"""

import os

# Must be set before jax backend init anywhere in the test process.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()

import pytest  # noqa: E402


@pytest.fixture
def ray_start_regular():
    """Single-node runtime with 4 CPUs (reference: ray_start_regular)."""
    import ray_tpu as ray

    rt = ray.init(num_cpus=4, num_tpus=0, ignore_reinit_error=False)
    yield rt
    ray.shutdown()


@pytest.fixture
def chaos_controller():
    """Chaos-injection harness bound to the current runtime (list this
    fixture AFTER the fixture that boots the runtime, e.g.
    ``ray_start_regular``).  Arms the process's syncpoints for the
    test's duration and disarms + cancels schedules on teardown, so the
    whole battery can run under ``RAY_TPU_LOCKCHECK=1``.

    ``kill_head``/``restart_head`` are exposed too: attach an external
    head first (``ctl.attach_head(Cluster(external_head=True))``) —
    an in-process head shares the test's pid, so there is nothing
    survivable to kill and the methods raise."""
    from ray_tpu.chaos import ChaosController

    ctl = ChaosController()
    yield ctl
    ctl.stop()


@pytest.fixture
def ray_start_cluster():
    """Multi-node-on-one-host cluster handle (reference:
    ray_start_cluster / cluster_utils.Cluster)."""
    import ray_tpu as ray

    class Cluster:
        def __init__(self):
            self.rt = ray.init(num_cpus=2, num_tpus=0)

        def add_node(self, **kw):
            return self.rt.add_node(**kw)

        def remove_node(self, node_id):
            return self.rt.remove_node(node_id)

    c = Cluster()
    yield c
    ray.shutdown()
