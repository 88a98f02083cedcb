"""Head failover: full-state snapshots + worker reconnect-and-replay.

The acceptance battery for ROADMAP item 5(a): a LIVE 2-agent cluster
under sustained task + serve traffic crosses a hard head kill
(SIGKILL — no atexit, no final snapshot) and restart with

- every ``ray.get`` correct (no errors, no wrong values),
- agent worker processes NOT respawned (PIDs stable across the blip),
- a restored named actor resuming from retained state (adoption for a
  surviving worker; ``__ray_restore__`` of the last ``__ray_save__``
  checkpoint for one that died with the head — NOT a fresh __init__),
- traffic stalling for a bounded window rather than failing,

plus the head-role chaos env rules, knob env-plumbing through both
worker spawn paths, and the battery's lockcheck re-run.

Reference analog: GCS failover — redis-backed table persistence
(redis_store_client.h:28), GcsInitData load (gcs_server.h:77), and
workers reconnecting across a GCS restart
(gcs_failover_worker_reconnect_timeout, ray_config_def.h:62).
"""

import os
import subprocess
import sys
import tempfile
import textwrap
import threading
import time

import pytest

import ray_tpu as ray
from ray_tpu.chaos import ChaosController
from ray_tpu.cluster_utils import Cluster


FAILOVER_COUNTERS = ("reconnected_nodes", "reregistered_workers",
                     "adopted_actors")


@ray.remote
def _double(x):
    return x * 2, os.getpid()


@ray.remote(max_restarts=-1, max_task_retries=-1)
class _Counter:
    def __init__(self):
        self.n = 0

    def incr(self):
        self.n += 1
        return self.n

    def pid(self):
        return os.getpid()

    def __ray_save__(self):
        return self.n

    def __ray_restore__(self, n):
        self.n = n


class _Traffic(threading.Thread):
    """Sustained request loop: records per-op completion times and any
    error — the blip shows up as a completion GAP, never as a failure."""

    def __init__(self, op, check):
        super().__init__(daemon=True)
        self._op = op
        self._check = check
        self.completions = []
        self.errors = []
        self.stop = threading.Event()

    def run(self):
        i = 0
        while not self.stop.is_set():
            try:
                out = ray.get(self._op(i), timeout=60)
                if not self._check(i, out):
                    self.errors.append((i, "wrong value", out))
                self.completions.append(time.monotonic())
            except Exception as e:  # noqa: BLE001
                self.errors.append((i, "error", repr(e)))
            i += 1
            time.sleep(0.03)

    def max_gap(self):
        gaps = [b - a for a, b in zip(self.completions,
                                      self.completions[1:])]
        return max(gaps) if gaps else float("inf")


# ------------------------------------------------------------ acceptance --

def test_head_failover_acceptance_live_cluster():
    """THE acceptance scenario: 2-agent cluster, sustained task + serve
    traffic, hard head kill + restart = a bounded blip."""
    from ray_tpu import serve

    c = Cluster(external_head=True, head_num_cpus=0)
    chaos = None
    task_t = serve_t = None
    try:
        c.add_node(num_cpus=2, external=True)
        c.add_node(num_cpus=2, external=True)
        chaos = ChaosController(c.rt, arm_syncpoints=False, head=c)

        cnt = _Counter.options(name="survivor").remote()
        assert ray.get([cnt.incr.remote() for _ in range(5)],
                       timeout=60) == [1, 2, 3, 4, 5]
        actor_pid = ray.get(cnt.pid.remote(), timeout=30)

        @serve.deployment(num_replicas=1)
        class Echo:
            def __call__(self, x):
                return x * 3, os.getpid()

        handle = serve.run(Echo.bind())
        triple, serve_pid = ray.get(handle.remote(7), timeout=60)
        assert triple == 21

        # Warm-up so the lease plane + direct actor channels exist,
        # then record the task-worker PID set the blip must preserve.
        warm = ray.get([_double.remote(i) for i in range(8)], timeout=60)
        pids_before = {p for _, p in warm}

        task_t = _Traffic(lambda i: _double.remote(i),
                          lambda i, out: out[0] == i * 2)
        serve_t = _Traffic(lambda i: handle.remote(i),
                           lambda i, out: out[0] == i * 3)
        task_t.start()
        serve_t.start()
        time.sleep(1.2)  # traffic flowing; snapshot loop has the state

        t_kill = time.monotonic()
        assert chaos.kill_head() is not None
        time.sleep(0.8)  # a real restart takes operator/systemd time
        chaos.restart_head()

        # Let traffic run well past the blip, then stop.
        deadline = time.monotonic() + 45
        while time.monotonic() < deadline:
            if task_t.completions and serve_t.completions \
                    and task_t.completions[-1] > t_kill + 6 \
                    and serve_t.completions[-1] > t_kill + 6:
                break
            time.sleep(0.25)
        task_t.stop.set()
        serve_t.stop.set()
        task_t.join(timeout=70)
        serve_t.join(timeout=70)

        # Every get correct — the blip is a GAP, never a failure.
        assert task_t.errors == [], task_t.errors[:5]
        assert serve_t.errors == [], serve_t.errors[:5]
        assert task_t.completions[-1] > t_kill + 2, "no post-blip tasks"
        assert serve_t.completions[-1] > t_kill + 2, "no post-blip serves"
        # Stall bounded: well under the grace windows, nowhere near an
        # outage.
        assert task_t.max_gap() < 30, task_t.max_gap()
        assert serve_t.max_gap() < 30, serve_t.max_gap()

        # Worker processes were NOT respawned: every pre-blip worker
        # process is still alive (none was torn down and replaced), and
        # both actors kept their exact process.  (A fresh worker MAY
        # additionally spawn if dispatch raced a survivor's re-dial —
        # progress beats strict reuse; what must never happen is a
        # survivor dying.)
        for p in pids_before:
            os.kill(p, 0)  # raises if the pre-blip worker died
        assert ray.get(cnt.pid.remote(), timeout=60) == actor_pid
        # The named actor resumed from retained state (adoption — its
        # counter kept counting, it never re-ran __init__).
        assert ray.get(cnt.incr.remote(), timeout=60) >= 6
        _t, pid2 = ray.get(handle.remote(1), timeout=60)
        assert pid2 == serve_pid

        stats = c.rt.transfer_stats()
        assert stats["reconnected_nodes"] == 2, stats
        # Both agents' workers + this client re-registered.
        assert stats["reregistered_workers"] >= 3, stats
        # Counter actor + serve controller + replica all adopted.
        assert stats["adopted_actors"] >= 3, stats
        assert chaos.stats()["head_kills"] == 1
    finally:
        for t in (task_t, serve_t):
            if t is not None:
                t.stop.set()
        if chaos is not None:
            chaos.stop()
        try:
            serve.shutdown()
        except Exception:
            pass
        c.shutdown()


def _wait_for_snapshot_of(c, name, state, timeout=30.0):
    """Until the head's snapshot FILE (what a restarted head reads) holds
    the named actor with a ``__ray_save__`` checkpoint of ``state``: the
    checkpoint rides the worker's connection behind the call's result and
    the snapshot loop writes on its own clock, so neither has landed for
    sure when the last ``get`` returns."""
    from ray_tpu._private import protocol, serialization

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with open(c._head_cfg["gcs_snapshot_path"], "rb") as f:
                actors = serialization.loads_inline(f.read())["actors"]
        except OSError:     # not written yet (the head renames it in)
            actors = []
        for row in actors:
            descr = row["checkpoint"]
            if row["name"] == name and descr is not None and (
                    descr[0] != protocol.INLINE
                    or serialization.loads_inline(descr[1]) == state):
                return
        time.sleep(0.05)
    raise AssertionError(f"no snapshot of {name!r} at {state!r} in {timeout}s")


def test_cold_restore_named_actor_from_checkpoint():
    """An actor whose worker DIES WITH THE HEAD (killed alongside it,
    so nothing re-claims the incarnation) is re-created by the restarted
    head from its retained ``__ray_save__`` checkpoint — state
    continues, __init__'s fresh state does not win."""
    c = Cluster(external_head=True, head_num_cpus=2,
                _system_config={"head_reregister_timeout_s": 2.0})
    try:
        cnt = _Counter.options(name="ck").remote()
        assert ray.get([cnt.incr.remote() for _ in range(3)],
                       timeout=60) == [1, 2, 3]
        actor_pid = ray.get(cnt.pid.remote(), timeout=60)
        _wait_for_snapshot_of(c, "ck", 3)  # checkpoint + snapshot both land
        c.kill_head()
        os.kill(actor_pid, 9)
        c.restart_head()
        # Nothing re-claims the actor inside the re-register window;
        # this CLIENT reconnects on its own.
        cnt2 = ray.get_actor("ck")
        # 4, not 1: __ray_restore__ ran over the fresh __init__.
        assert ray.get(cnt2.incr.remote(), timeout=90) == 4
        stats = c.rt.transfer_stats()
        assert stats["adopted_actors"] == 0, stats  # cold path, not adoption
    finally:
        c.shutdown()


# ----------------------------------------------------- head chaos rules --

def test_env_rule_kills_head_at_snapshot_syncpoint():
    """RAY_TPU_CHAOS head-role rules arm in the head process (the gap
    this PR closes — only workers and agents armed them before):
    ``head:snapshot:2`` hard-kills the head at its 2nd snapshot write,
    the one-shot claim file proves it fired, and a restart resumes the
    cluster."""
    chaos_dir = tempfile.mkdtemp()
    c = Cluster(external_head=True, head_num_cpus=0,
                head_env={"RAY_TPU_CHAOS": "head:snapshot:2",
                          "RAY_TPU_CHAOS_DIR": chaos_dir})
    try:
        c.add_node(num_cpus=2, external=True)
        assert ray.get(_double.remote(5), timeout=60)[0] == 10
        # Keep the head's tables dirty until the rule fires: steady-
        # state task traffic rides the lease plane (zero head messages),
        # so mutate the head-registered object table with client puts —
        # over-inline-size ones, which register via put_parts.
        deadline = time.time() + 30
        while c.head_proc.poll() is None and time.time() < deadline:
            try:
                ref = ray.put(os.urandom(1_200_000))
                del ref
            except Exception:
                break  # head died mid-put: exactly what we want
            time.sleep(0.1)
        c.head_proc.wait(timeout=30)
        claims = [f for f in os.listdir(chaos_dir)
                  if "_head_snapshot_" in f]
        assert claims, "head chaos rule never fired"
        c.restart_head()
        assert ray.get(_double.remote(6), timeout=90)[0] == 12
    finally:
        c.shutdown()


# ------------------------------------------------------- knob plumbing --

def test_failover_knob_env_plumbing_both_spawn_paths():
    """PR 5-9 convention for new knobs: _system_config overrides reach
    spawned workers through the RAY_TPU_* env namespace via
    _worker_config_env — probed through BOTH spawn paths (head-local
    subprocess and agent-forked), with every failover counter zero in a
    blip-free run."""
    c = Cluster(head_num_cpus=2, _system_config={
        "head_reconnect_grace_s": 7.25,
        "head_reregister_timeout_s": 3.5,
    })
    try:
        nid = c.add_node(num_cpus=1, external=True)
        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy as NA,
        )

        @ray.remote
        def probe():
            from ray_tpu._private.config import GLOBAL_CONFIG as cfg

            return (cfg.head_reconnect_grace_s,
                    cfg.head_reregister_timeout_s)

        expected = (7.25, 3.5)
        # Head-local spawn path.
        assert ray.get(probe.options(scheduling_strategy=NA(
            node_id=c.rt.head_node.node_id.hex(), soft=False)).remote(),
            timeout=60) == expected
        # Agent spawn path.
        assert ray.get(probe.options(scheduling_strategy=NA(
            node_id=nid, soft=False)).remote(), timeout=60) == expected
        stats = c.rt.transfer_stats()
        for k in FAILOVER_COUNTERS:
            assert stats[k] == 0, (k, stats)
    finally:
        c.shutdown()


def test_snapshot_hygiene_counters_and_final_snapshot(tmp_path):
    """Satellite: gcs_snapshots/gcs_snapshot_failures surface in
    transfer_stats()/state_query, and a clean shutdown() writes a final
    snapshot even when nothing dirty was pending a periodic write."""
    snap = str(tmp_path / "gcs.bin")
    rt = ray.init(num_cpus=2, _system_config={
        "gcs_snapshot_path": snap,
        "gcs_snapshot_interval_s": 0.2,
    })
    try:
        rt.kv_put(b"k", b"v")
        deadline = time.time() + 10
        while time.time() < deadline \
                and rt.transfer_stats()["gcs_snapshots"] == 0:
            time.sleep(0.05)
        stats = rt.state_query("transfer_stats")[0]
        assert stats["gcs_snapshots"] >= 1, stats
        assert stats["gcs_snapshot_failures"] == 0, stats
        rt.kv_put(b"k2", b"v2")  # dirty again, inside the interval
        before = os.path.getmtime(snap)
        n_before = rt.transfer_stats()["gcs_snapshots"]
    finally:
        ray.shutdown()
    # The final shutdown snapshot captured the last-interval mutation.
    assert os.path.getmtime(snap) >= before
    from ray_tpu._private import serialization

    with open(snap, "rb") as f:
        data = serialization.loads_inline(f.read())
    assert data["kv"]["default"][b"k2"] == b"v2"
    assert data["version"] >= 2
    assert n_before >= 1


# --------------------------------------------------- lockcheck battery --

@pytest.mark.slow  # duplicate-coverage drill: the acceptance test above
#                   exercises the same failover machinery; this re-runs
#                   it with the lockdep checker installed (sub-second
#                   tier-1 representatives: the hygiene + plumbing tests)
def test_failover_battery_under_lockcheck_zero_cycles():
    """The failover drill re-run under RAY_TPU_LOCKCHECK=1: snapshot
    widening, restore/reconcile, client reconnect-and-replay must
    introduce no lock-order cycles in the driver/client process (the
    head + workers inherit the checker via the env too)."""
    code = textwrap.dedent("""
        import os, time
        os.environ["JAX_PLATFORMS"] = "cpu"
        import ray_tpu as ray
        from ray_tpu.devtools import lockcheck
        from ray_tpu.cluster_utils import Cluster
        assert lockcheck.enabled()

        # Leg 1: in-process snapshot -> restore (the head-side paths).
        snap = "/tmp/rtpu_lockcheck_gcs_%d" % os.getpid()
        rt = ray.init(num_cpus=2, _system_config={
            "gcs_snapshot_path": snap})

        @ray.remote
        def f(i):
            return i + 1

        @ray.remote(max_restarts=1)
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

        c = C.options(name="lc").remote()
        assert ray.get([f.remote(i) for i in range(8)]) == list(range(1, 9))
        assert ray.get(c.inc.remote()) == 1
        rt._snapshot_gcs()
        ray.shutdown()
        rt2 = ray.init(num_cpus=2, _system_config={
            "gcs_snapshot_path": snap, "gcs_restore": True})
        c2 = ray.get_actor("lc")
        assert ray.get(c2.inc.remote(), timeout=60) >= 1
        assert ray.get(f.remote(41), timeout=60) == 42
        ray.shutdown()
        os.unlink(snap)

        # Leg 2: live kill+restart with the client machinery under the
        # checker (head/agent/workers inherit RAY_TPU_LOCKCHECK).
        cl = Cluster(external_head=True, head_num_cpus=0)
        try:
            cl.add_node(num_cpus=2, external=True)
            assert ray.get(f.remote(1), timeout=60) == 2
            time.sleep(0.5)
            cl.kill_head()
            cl.restart_head()
            assert ray.get(f.remote(2), timeout=90) == 3
        finally:
            cl.shutdown()
        bad = lockcheck.violations()
        assert not bad, "lock-order violations: " + repr(bad)
        print("FAILOVER_LOCKCHECK_OK")
    """)
    env = dict(os.environ, RAY_TPU_LOCKCHECK="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-4000:])
    assert "FAILOVER_LOCKCHECK_OK" in proc.stdout
