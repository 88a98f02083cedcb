"""The device half of a worker's environment, on both spawn paths.

A worker that is granted chips must run on them or fail: its platform is
FORCED to the TPU (a TPU runtime that cannot start is then an error, not
a quiet fall back to the CPU where every kernel runs interpreted), and it
gets the compile cache directory — inherited when the caller set one,
the one fixed path in the checkout when not.  A worker without chips is
pinned to the CPU.  The tasks only look at ``os.environ``: nothing here
imports JAX, so no process reaches for a TPU this machine does not have.
"""

import os
import time

import pytest

import ray_tpu as ray
from ray_tpu._private.device_env import (compile_cache_dir, pick_chips,
                                          worker_device_env)
from ray_tpu.cluster_utils import Cluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEYS = ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR", "TPU_VISIBLE_CHIPS",
        "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS")


@ray.remote
def device_env():
    import os
    import sys

    assert "jax" not in sys.modules
    return {k: os.environ.get(k) for k in KEYS} | {"pid": os.getpid()}


@pytest.fixture(params=["unset", "set"])
def cache_dir(request, monkeypatch, tmp_path):
    """The compile-cache directory a TPU worker must see."""
    if request.param == "set":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        return str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    return os.path.join(REPO, ".jax_cache")


def _check(tpu, cpu, cache_dir):
    assert tpu["JAX_PLATFORMS"] == "tpu"
    assert tpu["JAX_COMPILATION_CACHE_DIR"] == cache_dir
    assert tpu["TPU_VISIBLE_CHIPS"] == "0"
    assert cpu["JAX_PLATFORMS"] == "cpu"
    assert cpu["TPU_VISIBLE_CHIPS"] is None


def test_head_spawned_workers(monkeypatch, cache_dir):
    monkeypatch.setenv("RAY_TPU_FORCE_NUM_TPUS", "1")
    ray.init(num_cpus=2)  # the chip count comes from "detection"
    try:
        assert ray.cluster_resources()["TPU"] == 1
        tpu = ray.get(device_env.options(num_tpus=1).remote(), timeout=60)
        cpu = ray.get(device_env.remote(), timeout=60)
        _check(tpu, cpu, cache_dir)
        # The one chip is granted again only after its worker is gone:
        # a second grant is a NEW process, and it does arrive.
        again = ray.get(device_env.options(num_tpus=1).remote(), timeout=60)
        assert again["pid"] != tpu["pid"]
        with pytest.raises(OSError):
            os.kill(tpu["pid"], 0)
    finally:
        ray.shutdown()


def test_agent_spawned_workers(cache_dir):
    """Agents are started with JAX_PLATFORMS=cpu by every launcher; a
    TPU worker on such a node must not inherit that."""
    c = Cluster(head_num_cpus=1)
    try:
        c.add_node(num_cpus=2, num_tpus=1, external=True,
                   env_overrides={"JAX_PLATFORMS": "cpu"})
        tpu = ray.get(device_env.options(num_tpus=1).remote(), timeout=60)
        cpu = ray.get(device_env.options(
            num_cpus=1, resources=None).remote(), timeout=60)
        _check(tpu, cpu, cache_dir)
        again = ray.get(device_env.options(num_tpus=1).remote(), timeout=60)
        assert again["pid"] != tpu["pid"]  # reap_worker / worker_reaped

        # An ACTOR holding the node's one chip: ray.kill must reach the
        # agent's child, and the chip must come back after its exit.
        @ray.remote(num_tpus=1)
        class Holder:
            def pid(self):
                import os

                return os.getpid()

        holder = Holder.remote()
        pid = ray.get(holder.pid.remote(), timeout=60)
        ray.kill(holder)
        after = ray.get(device_env.options(num_tpus=1).remote(), timeout=60)
        assert after["pid"] != pid
        with pytest.raises(OSError):
            os.kill(pid, 0)
    finally:
        c.shutdown()


def test_retired_worker_never_reregisters():
    """The head retires a worker with a kill and closes the connection.
    The worker's reader must take that EOF for what it is, not for a
    head failover: one that re-dialed and re-registered while its exec
    thread was still busy (the kill waits in the task queue) came back
    as an idle worker under its old chips' env key, was handed the next
    TPU task once its predecessor's chips were freed, and died with it
    ("Worker died executing Holder.__init__" under load)."""
    from ray_tpu._private import api_internal, protocol

    ray.init(num_cpus=1)
    try:
        rt = api_internal.require_runtime()

        @ray.remote
        def busy():
            import time

            time.sleep(5)

        busy.remote()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            with rt.lock:
                running = [w for w in rt.head_node.all_workers.values()
                           if w.inflight and w.conn is not None]
            if running:
                break
            time.sleep(0.05)
        (w,) = running
        time.sleep(0.5)  # the task is executing, not queued
        # What _kill_worker_locked puts on the wire when it runs on the
        # worker's own reader thread (the usual case: the task's result
        # ends the lease): the kill, then the FIN.
        w.send(("kill",))
        protocol.shutdown_conn(w.conn)
        # Gone at the EOF, long before the sleep would let the exec
        # thread pop the kill — and it never asked to come back.
        w.proc.wait(timeout=3)
        assert rt.reregistered_workers == 0
    finally:
        ray.shutdown()


def test_compile_cache_dir_is_one_fixed_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache_dir() == os.path.join(REPO, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache_dir() == "/elsewhere"


@pytest.mark.parametrize("chips,host_chips,bounds", [
    ([0], 1, None),             # the whole (one-chip) host: its own bounds
    ([0, 1, 2, 3], 4, None),    # the whole 2x2 host: its own bounds
    ([0], 0, None),             # host not known (forced count): untouched
    ([2], 4, "1,1,1"),          # one chip of four: a slice of its own
    ([0, 1], 4, "1,2,1"),       # also ray.init(num_tpus=2) on that host
    ([2, 3], 4, "1,2,1"),
])
def test_chip_bounds(chips, host_chips, bounds):
    env = worker_device_env(chips, host_chips)
    assert env["JAX_PLATFORMS"] == "tpu"
    assert env["TPU_VISIBLE_CHIPS"] == ",".join(map(str, chips))
    assert env.get("TPU_CHIPS_PER_PROCESS_BOUNDS") == bounds
    assert env.get("TPU_CHIPS_PER_HOST_BOUNDS") == bounds
    assert env.get("TPU_PROCESS_BOUNDS") == ("1,1,1" if bounds else None)
    if bounds:  # co-resident processes: a controller port each
        assert env["TPU_MESH_CONTROLLER_PORT"] == str(8476 + chips[0])


@pytest.mark.parametrize("chips", [[1, 2], [0, 2], [0, 1, 2]])
def test_chips_that_are_no_slice_are_refused(chips):
    with pytest.raises(ValueError, match="not a slice"):
        worker_device_env(chips, 4)


@pytest.mark.parametrize("free,n,want", [
    ([3, 0, 1], 1, [3]),
    ([1, 2], 2, None),            # free, but not neighbours in a row
    ([1, 2, 3], 2, [2, 3]),
    ([3, 0, 1, 2], 2, [0, 1]),
    ([2, 3, 0, 1], 4, [2, 3, 0, 1]),
    ([0, 1, 2], 4, None),         # one still attached to a retiring worker
])
def test_pick_chips(free, n, want):
    assert pick_chips(free, n, 4) == want


def test_min_compile_time_is_a_default_only(monkeypatch):
    key = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
    monkeypatch.delenv(key, raising=False)
    assert worker_device_env([0], 1)[key] == "0"
    monkeypatch.setenv(key, "2")  # inherited by the worker as it is
    assert key not in worker_device_env([0], 1)


def test_chip_count_no_process_can_own_is_refused():
    ray.init(num_cpus=1, num_tpus=4)
    try:
        with pytest.raises(ValueError, match="1, 2 or all 4"):
            ray.get(device_env.options(num_tpus=3).remote(), timeout=60)
        assert ray.available_resources()["TPU"] == 4
    finally:
        ray.shutdown()


def test_node_offering_a_share_no_process_can_own_is_refused(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FORCE_NUM_TPUS", "4")  # "a 4-chip host"
    with pytest.raises(ValueError, match="1, 2 or all 4"):
        ray.init(num_cpus=1, num_tpus=3)
    assert not ray.is_initialized()


def test_retiring_chip_holds_back_only_its_own_class(monkeypatch):
    """While a retired TPU worker is still leaving its chip, the next
    TPU task waits for it; a CPU task (another scheduling class) does
    not."""
    from ray_tpu._private import device_env as denv

    real_reap = denv.reap

    def slow_reap(proc, grace_s=30.0):
        time.sleep(3.0)
        real_reap(proc, grace_s)

    monkeypatch.setattr(denv, "reap", slow_reap)
    ray.init(num_cpus=2, num_tpus=1)
    try:
        ray.get(device_env.remote(), timeout=60)  # a warm CPU worker
        ray.get(device_env.options(num_tpus=1).remote(), timeout=60)
        t0 = time.monotonic()
        tpu = device_env.options(num_tpus=1).remote()
        ray.get(device_env.remote(), timeout=60)
        assert time.monotonic() - t0 < 1.5
        ray.get(tpu, timeout=60)
        assert time.monotonic() - t0 > 2.5
    finally:
        ray.shutdown()
