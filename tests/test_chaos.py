"""Chaos-injection harness tests.

The acceptance battery from ROADMAP item 4: a cluster under injected
worker/agent kills keeps every ``ray.get`` correct (reconstruction +
retries absorbing the faults), ``RAY_TPU_CHAOS`` env rules kill spawned
processes deterministically at named syncpoints (mid-striped-pull worker
death), agent death mid-lease interacts with lease revocation, and the
whole battery re-runs under ``RAY_TPU_LOCKCHECK=1`` with zero cycles.

Reference analog: ``python/ray/_private/test_utils.py`` kill_raylet /
NodeKillerActor + the chaos_test release suites.
"""

import os
import subprocess
import sys
import tempfile
import textwrap
import time

import numpy as np
import pytest

import ray_tpu as ray
from ray_tpu._private import recovery
from ray_tpu.chaos import ChaosController
from ray_tpu.util.scheduling_strategies import (
    NodeAffinitySchedulingStrategy as NA,
)


@ray.remote
def _stage1(i):
    return np.full(260_000, i, dtype=np.int64)  # ~2 MB: shm-homed


@ray.remote
def _stage2(a):
    time.sleep(0.05)
    return int(a[0]) * 10


# ------------------------------------------------------------ unit-level --

def test_controller_at_syncpoint_fires_nth():
    fired = []
    ctl = ChaosController.__new__(ChaosController)  # no runtime needed
    ctl._rt = None
    import threading

    ctl._lock = threading.Lock()
    ctl._timers = []
    ctl._net = None
    ctl._sync_actions = {}
    ctl._pending = []
    ctl._pending_ev = threading.Event()
    ctl._stopped = False
    ctl._runner = threading.Thread(target=ctl._run_loop, daemon=True)
    ctl._runner.start()
    recovery.set_chaos_hook(ctl._fire)
    try:
        ctl.at_syncpoint("probe", fired.append, "hit", n=3)
        for _ in range(2):
            recovery.syncpoint("probe")
        time.sleep(0.1)
        assert fired == []
        recovery.syncpoint("probe")
        deadline = time.time() + 2
        while not fired and time.time() < deadline:
            time.sleep(0.01)
        assert fired == ["hit"]
    finally:
        ctl.stop()
    assert not recovery.chaos_armed()


def test_env_rule_parse_ignores_garbage():
    rules = recovery.parse_chaos_rules(
        "worker:pull_chunk:3, bogus, agent:agent_msg:nope, driver:x:1")
    assert rules == [("worker", "pull_chunk", 3), ("driver", "x", 1)]


def test_syncpoint_is_noop_unarmed():
    assert not recovery.chaos_armed()
    recovery.syncpoint("anything")  # must not raise, must cost ~nothing


def test_chaos_fixture_kill_worker_mid_task_retries(ray_start_regular,
                                                    chaos_controller):
    """The pytest-fixture form of the harness: a mid-task worker kill
    is absorbed by the system-failure retry budget."""

    @ray.remote(max_retries=3)
    def slow(i):
        time.sleep(0.3)
        return i

    refs = [slow.remote(i) for i in range(4)]
    time.sleep(0.15)
    assert chaos_controller.kill_worker(mid_task=True) is not None
    assert ray.get(refs, timeout=60) == list(range(4))
    assert chaos_controller.stats()["chaos_kills"] == 1


# ------------------------------------------------------------ acceptance --

def test_chaos_acceptance_fanout_survives_worker_and_agent_kill():
    """THE acceptance scenario: 2-agent cluster, 40-task fan-out with a
    dependency chain, one mid-run worker kill AND one agent kill —
    every ray.get returns the correct value, reconstructions >= 1, and
    no ObjectLostError ever reaches the driver."""
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(head_num_cpus=0)
    chaos = None
    try:
        n1 = c.add_node(num_cpus=2, external=True)
        n2 = c.add_node(num_cpus=2, external=True)
        chaos = ChaosController(c.rt)

        # Stage 1: 20 producers pinned across both nodes so the agent
        # kill is guaranteed to take some results with it.
        s1 = [_stage1.options(scheduling_strategy=NA(
            node_id=(n1 if i % 2 else n2), soft=True)).remote(i)
            for i in range(20)]
        ray.wait(s1, num_returns=len(s1), timeout=60)

        # Stage 2 (the dependency chain) starts; mid-run, kill a busy
        # worker AND the n2 agent — stage-2 tasks retry (system-failure
        # budget) and their lost stage-1 args reconstruct from lineage.
        s2 = [_stage2.remote(r) for r in s1]
        time.sleep(0.15)
        assert chaos.kill_worker(mid_task=True) is not None
        assert chaos.kill_agent(n2) == n2

        out = ray.get(s2, timeout=120)
        assert out == [i * 10 for i in range(20)]
        stats = c.rt.transfer_stats()
        assert stats["reconstructions"] >= 1, stats
        assert stats["chaos_kills"] == 2
    finally:
        if chaos is not None:
            chaos.stop()
        c.shutdown()


# ------------------------------------------------- env-rule chaos kills --

def test_env_rule_kills_worker_mid_striped_pull():
    """A worker armed with ``worker:pull_chunk:2`` dies mid-stream while
    pulling a cross-node argument; the task retries on a fresh worker
    (the one-shot lockfile keeps the rule from re-firing) and the get
    succeeds.  This is the deterministic kill-mid-pull the wall-clock
    schedules can't hit reliably."""
    from ray_tpu.cluster_utils import Cluster

    chaos_dir = tempfile.mkdtemp()
    c = Cluster(head_num_cpus=2)
    try:
        n1 = c.add_node(num_cpus=2, external=True)
        n2 = c.add_node(
            num_cpus=2, external=True,
            env_overrides={"RAY_TPU_CHAOS": "worker:pull_chunk:2",
                           "RAY_TPU_CHAOS_DIR": chaos_dir})
        big = _stage1.options(
            scheduling_strategy=NA(node_id=n1, soft=False)).remote(7)
        ray.wait([big], num_returns=1, timeout=30)

        @ray.remote(max_retries=3)
        def consume(a):
            return int(a[0])

        # The n2 consumer pulls an ~2 MB segment (>= 2 chunks) from n1
        # and dies at chunk 2 of the stream.
        out = ray.get(consume.options(
            scheduling_strategy=NA(node_id=n2, soft=False)).remote(big),
            timeout=90)
        assert out == 7
        # The rule really fired: its one-shot lockfile was claimed by
        # the worker that died for it (a chaos test whose kill silently
        # missed proves nothing).
        claim = os.path.join(
            chaos_dir,
            f"ray_tpu_chaos_{c.rt.session_id}_worker_pull_chunk_2")
        assert os.path.exists(claim), "chaos env rule never fired"
    finally:
        c.shutdown()


def test_chaos_kill_agent_mid_lease_revocation_interplay():
    """Kill an agent whose workers are LEASED to a peer holder mid-push:
    the head revokes the leases (lease_revocations counts) and the
    holder's retries land the work elsewhere — completion, not loss."""
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(head_num_cpus=0)
    chaos = None
    try:
        n1 = c.add_node(num_cpus=1, external=True)
        n2 = c.add_node(num_cpus=2, external=True)
        chaos = ChaosController(c.rt)
        kf = tempfile.mktemp()

        @ray.remote
        def coordinator(kill_file):
            @ray.remote
            def slow(i):
                time.sleep(0.25)
                return i * 3

            refs = [slow.remote(i) for i in range(16)]
            open(kill_file + ".ready", "w").write("x")
            return ray.get(refs)

        fut = coordinator.options(
            scheduling_strategy=NA(node_id=n1, soft=False),
            num_cpus=1).remote(kf)
        deadline = time.time() + 60
        while not os.path.exists(kf + ".ready") \
                and time.time() < deadline:
            time.sleep(0.05)
        assert os.path.exists(kf + ".ready")
        time.sleep(0.3)  # leases granted on n2, pushes in flight
        assert chaos.kill_agent(n2) == n2
        assert ray.get(fut, timeout=120) == [i * 3 for i in range(16)]
        stats = c.rt.transfer_stats()
        assert stats["lease_revocations"] >= 1, stats
        assert stats["chaos_kills"] >= 1
    finally:
        if chaos is not None:
            chaos.stop()
        c.shutdown()


# --------------------------------------------------- lockcheck battery --

@pytest.mark.slow  # duplicate-coverage subprocess drill: the kill/
#                   restart/reconstruction machinery runs tier-1 in the
#                   acceptance tests above (and the failover battery),
#                   and the lock-order pins it checks have sub-second
#                   tier-1 representatives in tests/test_lockcheck.py;
#                   this re-run with the checker installed rides the
#                   slow lane next to the failover lockcheck battery
def test_chaos_battery_under_lockcheck_zero_cycles():
    """The chaos battery's single-host shape re-run with the lockdep
    checker installed: worker kill + actor restart + reconstruction
    machinery must introduce no lock-order cycles (the lineage-table
    leaf is additionally pinned in tests/test_lockcheck.py)."""
    code = textwrap.dedent("""
        import os, time
        import ray_tpu as ray
        from ray_tpu.devtools import lockcheck
        from ray_tpu.chaos import ChaosController
        assert lockcheck.enabled()
        rt = ray.init(num_cpus=2, num_tpus=0)
        chaos = ChaosController(rt)

        @ray.remote(max_retries=3)
        def f(i):
            time.sleep(0.02)
            return i + 1

        @ray.remote(max_restarts=1, max_task_retries=-1)
        class C:
            def __init__(self):
                self.n = 0
            def inc(self):
                self.n += 1
                return self.n
            def __ray_save__(self):
                return self.n
            def __ray_restore__(self, n):
                self.n = n

        c = C.remote()
        assert ray.get([c.inc.remote() for _ in range(3)]) == [1, 2, 3]
        refs = [f.remote(i) for i in range(24)]
        time.sleep(0.1)
        chaos.kill_worker(mid_task=True, actor=False)
        chaos.kill_worker(mid_task=False, actor=True)
        assert ray.get(refs, timeout=60) == list(range(1, 25))
        assert ray.get(c.inc.remote(), timeout=30) == 4  # restored
        stats = rt.transfer_stats()
        assert stats["chaos_kills"] >= 2
        assert stats["actor_restarts"] >= 1
        chaos.stop()
        ray.shutdown()
        bad = lockcheck.violations()
        assert not bad, "lock-order violations: " + repr(bad)
        print("CHAOS_LOCKCHECK_OK")
    """)
    env = dict(os.environ, RAY_TPU_LOCKCHECK="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-4000:])
    assert "CHAOS_LOCKCHECK_OK" in proc.stdout
