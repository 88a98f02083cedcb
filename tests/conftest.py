"""Shared pytest fixtures.

Mirrors the reference's conftest pattern (``python/ray/tests/conftest.py``:
``ray_start_regular`` :305 boots a real single-node runtime in-process;
``ray_start_cluster`` :386 boots a multi-node cluster on one machine).

JAX-level tests run on a virtual 8-device CPU mesh
(``xla_force_host_platform_device_count``), the standard way to test TPU
sharding logic without TPU hardware.

Tier-1 compiles to check, not to run fast: its CPU programs run for
milliseconds and are compiled for seconds, so they are compiled with most
of XLA's optimisations off (``COMPILE_TO_CHECK`` below, JAX's own flag for
"the cost of optimization is greater than that of running a less-optimized
program").  What reads what an OPTIMISING compiler makes sets the flag
aside with ``compiled_to_run``: ``tests/test_tpu_compile.py`` for its module
(the TPU compiler's text and its memory analysis), and the ONE comparison of
the suite whose tolerance is the optimised arithmetic's
(``tests/test_delta.py``: a token's loss within 2e-5).
"""

import contextlib
import os

# Must be set before jax is imported anywhere in the test process (workers a
# test spawns inherit all three).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
# ``jax.config.jax_disable_most_optimizations``: XLA's backend optimisation
# level 0 and LLVM's expensive passes off, for every program this process
# and its children compile.
COMPILE_TO_CHECK = "jax_disable_most_optimizations"
os.environ[COMPILE_TO_CHECK.upper()] = "1"

import pytest  # noqa: E402


@contextlib.contextmanager
def compiled_to_run():
    """Sets ``COMPILE_TO_CHECK`` aside: what is compiled inside is compiled
    with the compiler's defaults (what is ALREADY compiled stays as it is:
    build the program inside)."""
    import jax

    was = jax.config.read(COMPILE_TO_CHECK)
    jax.config.update(COMPILE_TO_CHECK, False)
    try:
        yield
    finally:
        jax.config.update(COMPILE_TO_CHECK, was)


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
