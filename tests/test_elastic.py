"""Elastic-pod battery: preemption-aware node drain, notice sources,
spot scale-down through the drain protocol, and the sustained-traffic
chaos drill.

Reference pattern: the DrainNode protocol tests + chaos release jobs —
a planned departure (scale-down, spot warning window) must lose nothing
(leases revoked, restartable actors checkpointed to a surviving store,
small sole-copy objects migrated), while a no-warning kill falls back
to PR 9's lineage reconstruction.
"""

import time

import pytest

import ray_tpu as ray
from ray_tpu import serve
from ray_tpu.autoscaler import FakeSliceProvider, StandardAutoscaler
from ray_tpu.chaos import ChaosController
from ray_tpu.cluster_utils import Cluster

ELASTIC_KEYS = ("preemptions", "drains_completed", "drain_timeouts",
                "objects_migrated")


def _wait_for(fn, timeout=20.0, interval=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


def _one_head_node(rt):
    return sum(1 for n in rt.list_nodes() if n["alive"]) == 1


def test_drain_migrates_objects_and_node_removal_loses_nothing():
    """drain_node on a node holding sole-copy shm results: the objects
    are pulled and re-homed on the head's surviving store, the released
    agent exits cleanly, and every get after the node is gone is served
    from the migrated copy — zero reconstructions."""
    c = Cluster(head_num_cpus=1)
    try:
        nid = c.add_node(num_cpus=2, resources={"slice": 1}, external=True)

        @ray.remote(resources={"slice": 0.1})
        def produce(i):
            import numpy as np

            return np.full(300_000, i)  # ~2.4 MB -> the node's shm store

        refs = [produce.remote(i) for i in range(4)]
        ray.wait(refs, num_returns=4, timeout=60, fetch_local=False)
        rt = c.rt
        assert rt.drain_node(nid, 20.0, "test") is True
        st = rt.transfer_stats()
        assert st["drains_completed"] == 1
        assert st["drain_timeouts"] == 0
        assert st["objects_migrated"] >= 4
        # The drain_node release makes the agent exit on its own — no
        # terminate, no kill.
        assert _wait_for(lambda: _one_head_node(rt)), rt.list_nodes()
        vals = ray.get(refs, timeout=60)
        assert [int(v[0]) for v in vals] == [0, 1, 2, 3]
        assert rt.transfer_stats()["reconstructions"] == 0
    finally:
        c.shutdown()


def test_drain_migrates_spilled_sole_copies():
    """A node under store pressure SPILLS results to its local disk —
    which dies with the node exactly like its shm pages.  Drain
    migrates spilled sole-copies under the size cap too (the object
    server attaches them by absolute path like any segment)."""
    c = Cluster(head_num_cpus=1)
    try:
        # 4 MB store cap on the node: four ~2.4 MB results cannot all
        # stay resident — at least two spill to the node's disk.
        nid = c.add_node(num_cpus=2, resources={"slice": 1},
                         external=True,
                         env_overrides={"RAY_TPU_STORE_BYTES":
                                        str(4 * 1024 * 1024)})

        @ray.remote(resources={"slice": 0.1})
        def produce(i):
            import numpy as np

            return np.full(300_000, i)

        refs = [produce.remote(i) for i in range(4)]
        ray.wait(refs, num_returns=4, timeout=60, fetch_local=False)
        rt = c.rt
        with rt.lock:
            spilled = sum(1 for st in rt.objects.values()
                          if st.descr is not None
                          and st.descr[0] == "spilled")
        assert spilled >= 1, "store cap never forced a spill"
        assert rt.drain_node(nid, 20.0, "test") is True
        st = rt.transfer_stats()
        assert st["objects_migrated"] >= 4  # resident AND spilled moved
        assert _wait_for(lambda: _one_head_node(rt)), rt.list_nodes()
        vals = ray.get(refs, timeout=60)
        assert [int(v[0]) for v in vals] == [0, 1, 2, 3]
        assert rt.transfer_stats()["reconstructions"] == 0
    finally:
        c.shutdown()


def test_drain_force_checkpoints_actor_to_surviving_store():
    """A restartable actor on the draining node gets a forced
    __ray_save__ whose state is re-homed on the HEAD's store (a
    checkpoint homed on the dying node would be dropped at restart,
    PR 9); after the node dies the actor restarts on fresh capacity
    with the drained state intact."""
    c = Cluster(head_num_cpus=1)
    try:
        nid = c.add_node(num_cpus=2, resources={"slice": 1}, external=True)

        @ray.remote(max_restarts=-1, resources={"slice": 0.5})
        class Ck:
            def __init__(self):
                import numpy as np

                self.n = 0
                # Big enough that the forced checkpoint must ship as
                # PARTS (the store path, not inline) — pinning the
                # re-homing, not just the hook.
                self.buf = np.arange(300_000)

            def bump(self):
                self.n += 1
                return self.n

            def get(self):
                return self.n

            def __ray_save__(self):
                return (self.n, self.buf)

            def __ray_restore__(self, state):
                self.n, self.buf = state

        a = Ck.remote()
        assert ray.get(a.bump.remote(), timeout=60) == 1
        assert ray.get(a.bump.remote(), timeout=60) == 2
        rt = c.rt
        assert rt.drain_node(nid, 20.0, "test") is True
        with rt.lock:
            (actor,) = list(rt.actors.values())
            ck = actor.checkpoint
        # Forced checkpoint retained, homed on the head's (surviving)
        # store — not the draining node's.
        assert ck is not None and ck[0] == "shm" and ck[3] == rt.store_id
        assert _wait_for(lambda: _one_head_node(rt)), rt.list_nodes()
        # Fresh capacity: the actor restarts there and restores the
        # state saved AT DRAIN TIME (n == 2), not a fresh __init__.
        c.add_node(num_cpus=2, resources={"slice": 1}, external=True)
        assert ray.get(a.get.remote(), timeout=90) == 2
        st = rt.transfer_stats()
        assert st["drains_completed"] == 1
        assert st["actor_restarts"] == 1
    finally:
        c.shutdown()


def test_preempt_notice_graceful_self_drain():
    """The warning-window path end to end: chaos ``preempt`` (SIGUSR1)
    -> agent preempt_notice -> head drain -> drain_node release ->
    clean agent exit.  Zero object loss, zero reconstructions."""
    c = Cluster(head_num_cpus=1)
    try:
        c.add_node(num_cpus=2, resources={"slice": 1}, external=True)

        @ray.remote(resources={"slice": 0.1})
        def produce(i):
            import numpy as np

            return np.full(300_000, i)

        refs = [produce.remote(i) for i in range(3)]
        ray.wait(refs, num_returns=3, timeout=60, fetch_local=False)
        rt = c.rt
        with ChaosController(rt) as chaos:
            assert chaos.preempt_node(notice=True) is not None
            assert _wait_for(
                lambda: rt.transfer_stats()["drains_completed"] >= 1)
            st = rt.transfer_stats()
            assert st["preemptions"] == 1
            assert st["objects_migrated"] >= 3
            assert st["chaos_kills"] == 1
            assert _wait_for(lambda: _one_head_node(rt))
            vals = ray.get(refs, timeout=60)
            assert [int(v[0]) for v in vals] == [0, 1, 2]
            assert rt.transfer_stats()["reconstructions"] == 0
    finally:
        c.shutdown()


def test_no_notice_preemption_recovers_via_lineage():
    """The no-warning variant (SIGKILL): the same objects are LOST with
    the node and come back through PR 9 lineage reconstruction on a
    surviving slice — correct gets, bounded rebuild, no drain counters."""
    c = Cluster(head_num_cpus=1)
    try:
        nid1 = c.add_node(num_cpus=2, resources={"slice": 1},
                          external=True)

        @ray.remote(resources={"slice": 0.1})
        def produce(i):
            import numpy as np

            return np.full(300_000, i)

        refs = [produce.remote(i) for i in range(3)]
        ray.wait(refs, num_returns=3, timeout=60, fetch_local=False)
        # The surviving slice the producers re-execute on.
        c.add_node(num_cpus=2, resources={"slice": 1}, external=True)
        rt = c.rt
        with ChaosController(rt) as chaos:
            assert chaos.preempt_node(node_id=nid1, notice=False) == nid1
            vals = ray.get(refs, timeout=120)
            assert [int(v[0]) for v in vals] == [0, 1, 2]
            st = rt.transfer_stats()
            assert 1 <= st["reconstructions"] <= 3
            for k in ELASTIC_KEYS:
                assert st[k] == 0, (k, st[k])
    finally:
        c.shutdown()


def test_scale_down_routes_through_drain():
    """Idle scale-down goes through the drain protocol before
    terminate_node — counter-pinned on both sides (head transfer_stats
    and StandardAutoscaler.stats())."""
    c = Cluster(head_num_cpus=2)
    try:
        provider = FakeSliceProvider(c, {
            "spot-v5e": {"resources": {"CPU": 2, "slice": 1},
                         "max_workers": 2, "spot": True},
        })
        scaler = StandardAutoscaler(c.rt, provider, idle_timeout_s=1.0)

        @ray.remote(resources={"slice": 0.5})
        def f(i):
            return i * 3

        refs = [f.remote(i) for i in range(2)]
        time.sleep(0.2)
        launched = scaler.update()["launched"]
        assert launched
        assert ray.get(refs, timeout=120) == [0, 3]
        del refs
        gone = []
        deadline = time.monotonic() + 45
        while time.monotonic() < deadline and len(gone) < len(launched):
            gone += scaler.update()["terminated"]
            time.sleep(0.3)
        assert len(gone) == len(launched), gone
        # The drain runs off-thread (the tick stays reactive): the
        # counters land at its conclusion, just after the report.
        assert _wait_for(lambda: c.rt.transfer_stats()
                         ["drains_completed"] >= len(gone), 30)
        assert c.rt.transfer_stats()["drain_timeouts"] == 0
        assert _wait_for(lambda: scaler.stats()
                         ["drains_completed"] >= len(gone), 10)
        sc = scaler.stats()
        assert sc["drains_requested"] >= len(gone)
        assert sc["autoscaler_errors"] == 0
    finally:
        c.shutdown()


def test_elastic_knobs_ride_worker_env():
    """_system_config elastic knobs reach spawned workers through
    _worker_config_env (both spawn paths share it; test_config_env.py
    pins the map for every field, this pins it live)."""
    ray.init(num_cpus=1, _system_config={
        "drain_deadline_s": 3.5,
        "drain_migrate_max_bytes": 123456,
        "spot_fallback_threshold": 7})
    try:
        @ray.remote
        def probe():
            import os

            return (os.environ.get("RAY_TPU_DRAIN_DEADLINE_S"),
                    os.environ.get("RAY_TPU_DRAIN_MIGRATE_MAX_BYTES"),
                    os.environ.get("RAY_TPU_SPOT_FALLBACK_THRESHOLD"))

        assert ray.get(probe.remote(), timeout=60) == (
            "3.5", "123456", "7")
    finally:
        ray.shutdown()


def _elastic_drill(graceful: bool, duration_s: float,
                   p99_bound_s: float):
    """THE drill: sustained serve + task traffic while the autoscaler
    adds spot slices and chaos preempts one mid-run.  Returns the head
    stats and the serve p99 for the caller's variant-specific asserts.
    Every serve response and every task get is checked for exact
    correctness inline."""
    c = Cluster(head_num_cpus=2)
    scaler = None
    try:
        rt = c.rt
        provider = FakeSliceProvider(c, {
            "spot-v5e": {"resources": {"CPU": 2, "slice": 1},
                         "max_workers": 3, "spot": True},
        })
        scaler = StandardAutoscaler(rt, provider, idle_timeout_s=20.0,
                                    update_interval_s=0.4)
        scaler.start()

        # Preemption-tolerant replica: restart + in-flight replay (the
        # elastic ray_actor_options plumb) — a preempted replica is a
        # latency blip, not an error.
        @serve.deployment(num_replicas=1, num_cpus=0.5,
                          ray_actor_options={"max_restarts": -1,
                                             "max_task_retries": -1,
                                             "resources": {"slice": 0.25}})
        class Echo:
            def __call__(self, body):
                return {"double": body["x"] * 2}

        @ray.remote(resources={"slice": 0.25}, max_retries=6)
        def work(i):
            import numpy as np

            return np.full(200_000, i)  # node-store-homed result

        # The replica itself needs a slice: serve demand drives the
        # FIRST node launch through the autoscaler (no manual add).
        handle = serve.run(Echo.bind())
        with ChaosController(rt) as chaos:
            lat = []
            task_refs = {}
            t_end = time.monotonic() + duration_s
            preempt_at = t_end - duration_s / 2
            preempted = False
            i = 0
            while time.monotonic() < t_end or not preempted:
                i += 1
                task_refs[i] = work.remote(i)
                t0 = time.monotonic()
                out = ray.get(handle.remote({"x": i}), timeout=90)
                lat.append(time.monotonic() - t0)
                assert out == {"double": 2 * i}
                if not preempted and time.monotonic() >= preempt_at:
                    preempted = chaos.preempt_node(
                        notice=graceful) is not None
                time.sleep(0.03)
            assert preempted, "chaos never found a node to preempt"
            # Every task get exactly correct — graceful drains migrated
            # the preempted node's results, hard kills rebuild them via
            # lineage; either way no wrong answers, no losses.
            for k, ref in task_refs.items():
                v = ray.get(ref, timeout=120)
                assert int(v[0]) == k, (k, int(v[0]))
            lat.sort()
            p99 = lat[max(0, int(len(lat) * 0.99) - 1)]
            assert p99 < p99_bound_s, f"p99 {p99:.2f}s over bound"
            assert scaler.stats()["autoscaler_errors"] == 0
            return rt.transfer_stats(), p99, len(task_refs)
    finally:
        try:
            if scaler is not None:
                scaler.stop()
            serve.shutdown()
        finally:
            c.shutdown()


def test_elastic_drill_graceful_notice():
    """Acceptance: sustained serve + task traffic, autoscaler-driven
    node adds, one graceful preemption — every get correct, zero object
    loss (reconstructions == 0), drain counter-pinned, p99 bounded."""
    st, _p99, _n = _elastic_drill(graceful=True, duration_s=4.0,
                                  p99_bound_s=30.0)
    assert st["preemptions"] >= 1
    assert st["drains_completed"] >= 1
    assert st["reconstructions"] == 0
    assert st["chaos_kills"] >= 1


def test_elastic_drill_no_notice():
    """Acceptance, hard half: the same drill with a no-warning SIGKILL
    — gets stay correct via lineage, reconstructions bounded by the
    task count, no drain counters move."""
    st, _p99, n_tasks = _elastic_drill(graceful=False, duration_s=4.0,
                                       p99_bound_s=30.0)
    assert st["chaos_kills"] >= 1
    assert st["drains_completed"] == 0 and st["preemptions"] == 0
    # Bounded: only the killed node's unconsumed results rebuild (each
    # at most once more per retry budget — in practice once).
    assert st["reconstructions"] <= 2 * n_tasks


@pytest.mark.slow
def test_elastic_drill_sustained():
    """The long variant: more traffic, the same invariants, and the
    spot accounting visible after the churn."""
    st, p99, _n = _elastic_drill(graceful=True, duration_s=10.0,
                                 p99_bound_s=30.0)
    assert st["preemptions"] >= 1
    assert st["drains_completed"] >= 1
    assert st["reconstructions"] == 0
    assert p99 < 30.0
