"""Fault-tolerance subsystem tests: lineage-based reconstruction
(head-owned and worker-owned objects, recursive arg rebuilds, depleted
retries, byte-budget eviction), the system-vs-application retry split
(``retry_exceptions=``), restartable actors with ``__ray_save__``/
``__ray_restore__`` checkpoint hooks and ``max_task_retries`` replay.

Reference analogs: ``python/ray/tests/test_reconstruction*.py``,
``test_actor_failures.py`` (checkpointing), ``test_task_retries``.
"""

import os
import pickle
import tempfile
import time

import numpy as np
import pytest

import ray_tpu as ray
from ray_tpu._private import recovery
from ray_tpu.util.scheduling_strategies import (
    NodeAffinitySchedulingStrategy as NA,
)


@pytest.fixture
def cluster():
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(head_num_cpus=2)
    yield c
    c.shutdown()


@ray.remote
def _make(n):
    return np.arange(n, dtype=np.int64)


@ray.remote
def _double(x):
    return x * 2


# ------------------------------------------- structured ObjectLostError --

def test_object_lost_error_structured_fields_and_pickle():
    e = ray.exceptions.ObjectLostError(
        object_id="ab" * 16, owner="driver", home="feed", phase="pull")
    assert e.object_id == "ab" * 16
    assert e.phase == "pull"
    assert e.reconstructable
    # One constructor everywhere => one message shape.
    assert "phase=pull" in str(e) and "home=feed" in str(e)
    e2 = pickle.loads(pickle.dumps(e))
    assert (e2.object_id, e2.owner, e2.home, e2.phase) == \
        (e.object_id, e.owner, e.home, e.phase)
    assert isinstance(e2, ray.exceptions.ObjectLostError)


def test_freed_and_owner_died_are_not_reconstructable():
    assert not ray.exceptions.ObjectFreedError.reconstructable
    assert not ray.exceptions.OwnerDiedError.reconstructable
    # Subclasses keep the structured fields through pickling too.
    e = pickle.loads(pickle.dumps(
        ray.exceptions.OwnerDiedError(object_id="cd" * 16, phase="export")))
    assert isinstance(e, ray.exceptions.OwnerDiedError)
    assert not e.reconstructable and e.object_id == "cd" * 16


# --------------------------------------------------- lineage table unit --

def _spec(i, num_returns=1, arg=b"", max_retries=3):
    from ray_tpu._private.ids import new_task_id

    return {"task_id": new_task_id().binary(), "num_returns": num_returns,
            "name": f"t{i}", "args": [("inline", arg)], "kwargs": {},
            "max_retries": max_retries}


def test_lineage_table_budget_evicts_oldest_first():
    t = recovery.LineageTable(budget_bytes=4 * recovery._SPEC_BASE_COST)
    specs = [_spec(i) for i in range(8)]
    for s in specs:
        t.record(s)
    stats = t.stats()
    assert stats["evicted"] > 0
    assert stats["bytes"] <= 4 * recovery._SPEC_BASE_COST
    # Oldest entries evicted; newest survive.
    assert specs[0]["task_id"][:12] not in t
    assert specs[-1]["task_id"][:12] in t


def test_lineage_table_releases_on_last_return_object():
    from ray_tpu._private.ids import TaskID

    t = recovery.LineageTable(budget_bytes=0)  # unbounded
    s = _spec(0, num_returns=2)
    t.record(s)
    tid = TaskID(s["task_id"])
    assert t.release(tid.object_id(0).binary()) is None  # one still alive
    entry = t.release(tid.object_id(1).binary())
    assert entry is not None and entry["spec"] is s
    assert s["task_id"][:12] not in t and t.stats()["bytes"] == 0


def test_lineage_table_attempt_budget_depletes():
    t = recovery.LineageTable(budget_bytes=0)
    s = _spec(0, max_retries=2)
    t.record(s)
    prefix = s["task_id"][:12]
    assert t.note_attempt(prefix)
    assert t.note_attempt(prefix)
    assert not t.note_attempt(prefix)  # depleted: recovery must refuse


def test_head_lineage_budget_rides_system_config():
    rt = ray.init(num_cpus=2,
                  _system_config={"lineage_bytes_budget": 4096})
    try:
        assert rt.lineage.budget == 4096
        refs = [_double.remote(i) for i in range(40)]
        ray.get(refs)
        assert rt.lineage.stats()["evicted"] > 0
        assert rt.lineage.stats()["bytes"] <= 4096
    finally:
        ray.shutdown()


# ------------------------------------------------ retry semantics split --

def test_retry_exceptions_opt_in_counts_executions(ray_start_regular):
    path = tempfile.mktemp()

    @ray.remote(max_retries=3, retry_exceptions=[ValueError])
    def flaky(p):
        n = int(open(p).read()) if os.path.exists(p) else 0
        open(p, "w").write(str(n + 1))
        if n < 2:
            raise ValueError("transient")
        return n

    assert ray.get(flaky.remote(path)) == 2
    # EXACTLY first-failure + retries: 3 executions, no more no less.
    assert int(open(path).read()) == 3


def test_app_errors_do_not_retry_without_opt_in(ray_start_regular):
    path = tempfile.mktemp()

    @ray.remote(max_retries=3)
    def fails(p):
        n = int(open(p).read()) if os.path.exists(p) else 0
        open(p, "w").write(str(n + 1))
        raise ValueError("app bug")

    with pytest.raises(ray.exceptions.TaskError):
        ray.get(fails.remote(path))
    # max_retries is a SYSTEM-failure budget: the app error ran once.
    assert int(open(path).read()) == 1


def test_retry_exceptions_type_filter(ray_start_regular):
    path = tempfile.mktemp()

    @ray.remote(max_retries=3, retry_exceptions=[KeyError])
    def fails(p):
        n = int(open(p).read()) if os.path.exists(p) else 0
        open(p, "w").write(str(n + 1))
        raise ValueError("not retryable")

    with pytest.raises(ray.exceptions.TaskError):
        ray.get(fails.remote(path))
    assert int(open(path).read()) == 1


def test_retry_exceptions_bare_class_shorthand(ray_start_regular):
    path = tempfile.mktemp()

    @ray.remote(max_retries=2, retry_exceptions=ValueError)
    def flaky(p):
        n = int(open(p).read()) if os.path.exists(p) else 0
        open(p, "w").write(str(n + 1))
        if n < 1:
            raise ValueError("transient")
        return n

    assert ray.get(flaky.remote(path)) == 1
    assert int(open(path).read()) == 2
    with pytest.raises(TypeError):
        flaky.options(retry_exceptions="ValueError")._build_spec(
            ray_start_regular, (path,), {})
    with pytest.raises(TypeError):
        # Strings INSIDE the list must be rejected too — they could
        # never match, silently disabling the opt-in.
        flaky.options(retry_exceptions=["ValueError"])._build_spec(
            ray_start_regular, (path,), {})


def test_retry_exceptions_budget_depletes(ray_start_regular):
    path = tempfile.mktemp()

    @ray.remote(max_retries=2, retry_exceptions=True)
    def always(p):
        n = int(open(p).read()) if os.path.exists(p) else 0
        open(p, "w").write(str(n + 1))
        raise RuntimeError("always")

    with pytest.raises(ray.exceptions.TaskError):
        ray.get(always.remote(path))
    assert int(open(path).read()) == 3  # 1 + 2 retries


# --------------------------------------------- head-owned reconstruction --

def test_reconstruction_counts_and_reconstructing_event(cluster):
    n1 = cluster.add_node(num_cpus=2, external=True)
    ref = _make.options(
        scheduling_strategy=NA(node_id=n1, soft=True)).remote(2_000_000)
    ray.wait([ref], num_returns=1, timeout=30)
    cluster.kill_agent(n1)
    time.sleep(0.5)
    got = ray.get(ref, timeout=60)
    assert int(got.sum()) == int(np.arange(2_000_000, dtype=np.int64).sum())
    stats = cluster.rt.transfer_stats()
    assert stats["reconstructions"] >= 1
    states = [e["state"] for e in cluster.rt.task_events]
    assert "RECONSTRUCTING" in states


def test_recursive_arg_reconstruction(cluster):
    """Consumer output AND its argument both died with the node: the
    owner rebuilds the argument first, then the consumer (recursive
    recovery walk, cycle-safe)."""
    n1 = cluster.add_node(num_cpus=2, external=True)
    x = _make.options(
        scheduling_strategy=NA(node_id=n1, soft=True)).remote(1_500_000)
    y = _double.options(
        scheduling_strategy=NA(node_id=n1, soft=True)).remote(x)
    ray.wait([y], num_returns=1, timeout=30)
    cluster.kill_agent(n1)
    time.sleep(0.5)
    got = ray.get(y, timeout=90)
    assert int(got[:5].sum()) == 2 * int(np.arange(5).sum())
    assert cluster.rt.transfer_stats()["reconstructions"] >= 2


def test_depleted_retries_surfaces_structured_object_lost(cluster):
    n1 = cluster.add_node(num_cpus=2, external=True)
    ref = _make.options(
        max_retries=0,
        scheduling_strategy=NA(node_id=n1, soft=True)).remote(1_500_000)
    ray.wait([ref], num_returns=1, timeout=30)
    cluster.kill_agent(n1)
    time.sleep(0.5)
    with pytest.raises(ray.exceptions.ObjectLostError) as ei:
        ray.get(ref, timeout=30)
    # The refusal carries the structured identity, and counts.
    assert ei.value.object_id == ref.id().hex()
    assert cluster.rt.transfer_stats()["reconstruction_failures"] >= 1
    assert cluster.rt.transfer_stats()["reconstructions"] == 0


# ------------------------------------------- worker-owned (direct path) --

def test_worker_owned_direct_path_reconstruction():
    """THIS is what the head's lineage cannot cover: a worker's
    direct-submitted tasks never reach the head, so the worker's own
    DirectCaller lineage must rebuild their lost returns (owner-side
    recovery, Ownership NSDI'21)."""
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(head_num_cpus=0)
    try:
        n1 = c.add_node(num_cpus=1, external=True)
        c.add_node(num_cpus=2, external=True)
        kf = tempfile.mktemp()

        @ray.remote
        def coordinator(kill_file):
            @ray.remote
            def make(i):
                return np.full(300_000, i, dtype=np.int64)

            refs = [make.remote(i) for i in range(8)]
            # wait (NOT get): results stay un-materialized segments
            ray.wait(refs, num_returns=len(refs), timeout=60)
            open(kill_file + ".ready", "w").write("x")
            while not os.path.exists(kill_file + ".done"):
                time.sleep(0.1)
            time.sleep(0.5)
            return [int(ray.get(r)[0]) for r in refs]

        fut = coordinator.options(
            scheduling_strategy=NA(node_id=n1, soft=False),
            num_cpus=1).remote(kf)
        deadline = time.time() + 60
        while not os.path.exists(kf + ".ready") \
                and time.time() < deadline:
            time.sleep(0.1)
        assert os.path.exists(kf + ".ready"), "coordinator never started"
        # n1 is full (the coordinator) => every subtask ran on n2; kill
        # it and every result segment is gone.
        killed = [n for n in c.rt.list_nodes()
                  if n["node_id"] != n1 and not n["labels"].get("head")]
        c.kill_agent(killed[0]["node_id"])
        time.sleep(0.3)
        open(kf + ".done", "w").write("x")
        assert ray.get(fut, timeout=120) == list(range(8))
        time.sleep(1.0)  # xfer_stats delta flush
        assert c.rt.transfer_stats()["reconstructions"] >= 8
    finally:
        c.shutdown()


# ------------------------------------------------- restartable actors --

@ray.remote(max_restarts=2, max_task_retries=-1)
class _CheckpointedCounter:
    def __init__(self):
        self.n = 0

    def inc(self):
        self.n += 1
        return self.n

    def pid(self):
        return os.getpid()

    def __ray_save__(self):
        return self.n

    def __ray_restore__(self, n):
        self.n = n


def test_actor_restart_with_checkpoint_hooks(ray_start_regular):
    rt = ray_start_regular
    c = _CheckpointedCounter.remote()
    for _ in range(3):
        ray.get(c.inc.remote())
    pid = ray.get(c.pid.remote())
    time.sleep(0.3)  # conflated actor_checkpoint message lands
    os.kill(pid, 9)
    v = ray.get(c.inc.remote(), timeout=30)
    assert v == 4, f"state not restored (got {v})"
    assert ray.get(c.pid.remote()) != pid
    stats = rt.transfer_stats()
    assert stats["actor_restarts"] >= 1


def test_actor_restart_without_hooks_resets_state(ray_start_regular):
    @ray.remote(max_restarts=1, max_task_retries=-1)
    class Plain:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

        def pid(self):
            return os.getpid()

    c = Plain.remote()
    for _ in range(3):
        ray.get(c.inc.remote())
    os.kill(ray.get(c.pid.remote()), 9)
    assert ray.get(c.inc.remote(), timeout=30) == 1  # fresh __init__


def test_actor_inflight_replay_per_max_task_retries(ray_start_regular):
    path = tempfile.mktemp()

    @ray.remote(max_restarts=1, max_task_retries=2)
    class Slow:
        def work(self, p):
            n = int(open(p).read()) if os.path.exists(p) else 0
            open(p, "w").write(str(n + 1))
            time.sleep(1.0)
            return "done"

        def pid(self):
            return os.getpid()

    c = Slow.remote()
    pid = ray.get(c.pid.remote())
    fut = c.work.remote(path)
    time.sleep(0.4)  # mid-execution
    os.kill(pid, 9)
    # The in-flight call REPLAYS on the restarted actor (at-least-once).
    assert ray.get(fut, timeout=30) == "done"
    assert int(open(path).read()) == 2


def test_actor_inflight_fails_without_task_retries(ray_start_regular):
    @ray.remote(max_restarts=1)
    class Slow:
        def work(self):
            time.sleep(1.0)
            return "done"

        def pid(self):
            return os.getpid()

    c = Slow.remote()
    pid = ray.get(c.pid.remote())
    fut = c.work.remote()
    time.sleep(0.4)
    os.kill(pid, 9)
    with pytest.raises(ray.exceptions.ActorDiedError):
        ray.get(fut, timeout=30)
    # ...but the actor itself restarted and serves new calls.
    assert ray.get(c.pid.remote(), timeout=30) != pid


def test_put_only_objects_stay_unrecoverable_and_count(cluster):
    """ray.put has no lineage — recovery refuses (the documented
    refusal case), counted as a reconstruction failure."""
    n1 = cluster.add_node(num_cpus=2, external=True)

    @ray.remote
    def make_put():
        return ray.put(np.arange(1_000_000))

    inner = ray.get(make_put.options(
        scheduling_strategy=NA(node_id=n1)).remote(), timeout=30)
    cluster.kill_agent(n1)
    time.sleep(0.5)
    with pytest.raises(ray.exceptions.ObjectLostError):
        ray.get(inner, timeout=30)
    assert cluster.rt.transfer_stats()["reconstruction_failures"] >= 1
