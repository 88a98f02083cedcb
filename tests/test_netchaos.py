"""Gray-failure acceptance battery (ISSUE 14).

Where ``test_chaos.py`` proves the cluster survives CLEAN failures
(kills, closed connections — a peer dies and its socket says so), this
battery proves it survives the failures that announce nothing: a
stalled-but-alive link mid-transfer, a one-way partition the head can
only notice as silence.  The failure-detection plane (deadlines on
every wire operation, transport retries + hedging, head-side heartbeat
suspicion) is what turns each of these from a forever-hang into a
bounded, structured recovery — and ``chaos.ChaosNet`` is what makes
them injectable.

Reference analog: GcsHealthCheckManager + per-RPC gRPC deadlines;
"Gray Failure: The Achilles' Heel of Cloud-Scale Systems" (HotOS'17).
"""

import os
import socket
import subprocess
import sys
import tempfile
import textwrap
import threading
import time
import types

import numpy as np
import pytest

import ray_tpu as ray
from ray_tpu import chaos as chaos_mod
from ray_tpu._private import protocol
from ray_tpu.chaos import ChaosController, ChaosNet
from ray_tpu.util.scheduling_strategies import (
    NodeAffinitySchedulingStrategy as NA,
)

# Tiny windows so suspicion/deadline tests complete in seconds; every
# cluster test in this file shares them.
FAST_FD = {
    "net_stall_timeout_s": 0.8,
    "net_connect_timeout_s": 2.0,
    "net_retry_count": 1,
    "net_retry_backoff_base_ms": 20.0,
    "health_check_period_s": 0.25,
    "health_check_timeout_s": 1.0,
    "health_check_failure_threshold": 2,
    "health_check_initial_delay_s": 1.0,
}


@ray.remote(max_retries=3)
def _make(i):
    return np.full(260_000, i, dtype=np.int64)  # ~2 MB: shm-homed


@ray.remote(max_retries=3)
def _consume(a):
    return int(a[0])


# ------------------------------------------------------------ unit-level --

def test_parse_net_rules_ignores_garbage():
    rules = chaos_mod.parse_net_rules(
        "worker:send:stall:1, bogus, agent:chunk_send:delay-2.5:3,"
        "agent:recv:delay-x:1, driver:*:drop:2, agent:send:explode:1")
    assert rules == [
        ("worker", "send", "stall", 0.0, 1),
        ("agent", "chunk_send", "delay", 2.5, 3),
        ("driver", "*", "drop", 0.0, 2),
    ]


def test_chaosnet_hook_verdicts_and_restore():
    """Drop/dup verdicts, per-conn scoping, countdown, and a stall that
    parks the calling thread until restore — no cluster needed."""
    net = ChaosNet()
    conn_a, conn_b = object(), object()
    net.add_rule("send", "drop", conn=conn_a)
    net.add_rule("send", "dup", conn=conn_b, after=2)
    assert net._hook("send", conn_a) == "drop"
    assert net._hook("send", conn_b) is None      # countdown not reached
    assert net._hook("send", conn_b) == "dup"     # 2nd op arms it
    assert net._hook("recv", conn_b) is None      # wrong point
    assert net.stats()["net_faults"] == 2

    net.add_rule("recv", "stall", conn=conn_a)
    parked = threading.Event()
    resumed = threading.Event()

    def reader():
        parked.set()
        net._hook("recv", conn_a)  # parks until restore
        resumed.set()

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    assert parked.wait(2)
    time.sleep(0.1)
    assert not resumed.is_set()   # genuinely parked (socket-open stall)
    net.restore(conn_a)
    assert resumed.wait(2)
    # conn_b's rule survived the scoped restore.
    assert net.stats()["net_rules"] == 1


def test_env_net_rule_one_shot_claim(tmp_path):
    """Two ChaosNet instances racing the same claim file: exactly one
    fires (the kill rules' O_EXCL convention)."""
    claim = str(tmp_path / "claim")
    fired = 0
    for _ in range(2):
        net = ChaosNet()
        net.add_rule("send", "drop", claim=claim)
        if net._hook("send", None) == "drop":
            fired += 1
    assert fired == 1


def test_recv_deadline_trips_on_silent_peer():
    """A recv with an armed zero-progress deadline surfaces
    NetTimeoutError in ~the deadline, not forever — and NetTimeoutError
    is an OSError so every existing conn-EOF discovery site absorbs
    it."""
    from multiprocessing.connection import Pipe

    here, there = Pipe()
    try:
        t0 = time.monotonic()
        with pytest.raises(protocol.NetTimeoutError):
            protocol.recv_deadline(here, 0.3)
        assert time.monotonic() - t0 < 3.0
        assert issubclass(protocol.NetTimeoutError, OSError)
        # Cleared deadline: a late message still arrives (the conn is
        # not poisoned by the trip).
        protocol.send(there, ("late", 1))  # noqa: RTL501 -- synthetic verb on a local Pipe, never on the cluster wire
        assert protocol.recv(here) == ("late", 1)
    finally:
        here.close()
        there.close()


def test_shutdown_conn_wakes_a_parked_reader():
    """The watchdog retirement contract: close() alone does NOT wake a
    thread already blocked in read() on Linux — shutdown_conn must, so
    the stalled-channel watchdogs (direct dping, worker hc_ping) can
    push their parked readers into the death/reconnect path."""
    import socket as socketlib
    from multiprocessing.connection import Connection

    a, b = socketlib.socketpair()
    conn = Connection(a.detach())
    other = Connection(b.detach())
    woke = threading.Event()
    err: list = []

    def reader():
        try:
            protocol.recv(conn)
        except (EOFError, OSError) as e:
            err.append(e)
        woke.set()

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    time.sleep(0.2)
    assert not woke.is_set()          # genuinely parked
    protocol.shutdown_conn(conn)
    assert woke.wait(3), "shutdown_conn failed to wake the parked reader"
    assert err                        # EOF/OSError, never a value
    conn.close()
    other.close()


def test_dial_bounds_a_stalled_auth_handshake():
    """An accepted-but-silent listener (process hung right after
    accept) cannot hang the dialer: the auth handshake rides the same
    connect deadline."""
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    addr = srv.getsockname()
    try:
        t0 = time.monotonic()
        with pytest.raises((protocol.NetTimeoutError, OSError)):
            protocol.dial(addr, authkey=b"k", connect_timeout=0.4)
        assert time.monotonic() - t0 < 4.0
    finally:
        srv.close()


def test_suspicion_state_machine_unit():
    """Sub-second unit rep of the suspicion window (the wall-clock
    variants below are the slow lane): ALIVE -> SUSPECT (counted once)
    -> probe per period -> DEAD past the threshold; any message fully
    absolves."""
    from ray_tpu._private.runtime import Runtime

    head = types.SimpleNamespace(suspected_nodes=0)
    peer = types.SimpleNamespace(last_seen=100.0, hc_suspect=False,
                                 hc_misses=0, hc_probe_ts=0.0)
    timeout, period, threshold = 5.0, 1.0, 2
    step = Runtime._suspect_step_locked

    def tick(now):
        probes, dead = [], []
        step(head, peer, now, timeout, period, threshold, probes, dead)
        return bool(probes), bool(dead)

    assert tick(103.0) == (False, False)          # within the window
    assert tick(106.0) == (True, False)           # SUSPECT: first probe
    assert peer.hc_suspect and head.suspected_nodes == 1
    assert tick(106.5) == (False, False)          # probe window open
    assert tick(107.1) == (True, False)           # miss 2
    assert tick(108.2) == (False, True)           # past threshold: DEAD
    # A different peer that speaks again is fully absolved.
    peer2 = types.SimpleNamespace(last_seen=100.0, hc_suspect=False,
                                  hc_misses=0, hc_probe_ts=0.0)
    probes, dead = [], []
    step(head, peer2, 106.0, timeout, period, threshold, probes, dead)
    assert peer2.hc_suspect
    peer2.last_seen = 107.0                       # spoke again
    step(head, peer2, 107.5, timeout, period, threshold, probes, dead)
    assert not peer2.hc_suspect and peer2.hc_misses == 0
    assert head.suspected_nodes == 2              # counted once per episode


# ------------------------------------------------------- knob plumbing --

def test_net_knobs_ride_worker_env_both_spawn_paths():
    """_system_config failure-detection knobs reach spawned workers
    through _worker_config_env on BOTH spawn paths (head-local
    subprocess and agent-forked); test_config_env.py pins the map for
    every field, this pins a handful live on both paths."""
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(head_num_cpus=1, _system_config={
        "net_stall_timeout_s": 7.5,
        "net_connect_timeout_s": 2.25,
        "net_retry_count": 9,
        "net_retry_backoff_base_ms": 12.5,
        "health_check_period_s": 1.75,
        "health_check_timeout_s": 6.5,
        "health_check_failure_threshold": 4,
        "health_check_initial_delay_s": 3.25,
    })
    try:
        nid = c.add_node(num_cpus=1, external=True)

        @ray.remote
        def probe():
            from ray_tpu._private.config import GLOBAL_CONFIG as cfg

            return (cfg.net_stall_timeout_s, cfg.net_connect_timeout_s,
                    cfg.net_retry_count, cfg.net_retry_backoff_base_ms,
                    cfg.health_check_period_s,
                    cfg.health_check_timeout_s,
                    cfg.health_check_failure_threshold,
                    cfg.health_check_initial_delay_s)

        expected = (7.5, 2.25, 9, 12.5, 1.75, 6.5, 4, 3.25)
        head_hex = c.rt.head_node.node_id.hex()
        assert ray.get(probe.options(scheduling_strategy=NA(
            node_id=head_hex, soft=False)).remote(), timeout=60) \
            == expected
        assert ray.get(probe.options(scheduling_strategy=NA(
            node_id=nid, soft=False)).remote(), timeout=60) == expected
    finally:
        c.shutdown()


# ------------------------------------------------------------ acceptance --

def _netchaos_fanout(n_tasks=40):
    """THE gray-failure acceptance scenario (shared with the lockcheck
    re-run): 2-agent cluster, ``n_tasks`` fan-out, with BOTH gray
    layers injected mid-run — the n2 data plane stalls mid-chunk (env
    net-chaos rule in the agent) and the n2 head link stalls (nothing
    EOFs, ever).  Every get must return the correct value, bounded;
    the deadline core counts stalls/retries/hedges; suspicion declares
    the node dead and lineage reconstructs what the relay can no
    longer reach.  Returns (values, stats, elapsed_s, agent_alive)."""
    from ray_tpu.cluster_utils import Cluster

    chaos_dir = tempfile.mkdtemp()
    c = Cluster(head_num_cpus=2, _system_config=dict(FAST_FD))
    chaos = None
    try:
        n1 = c.add_node(num_cpus=2, external=True)
        n2 = c.add_node(
            num_cpus=2, external=True,
            env_overrides={
                "RAY_TPU_CHAOS_NET": "agent:chunk_send:stall:2",
                "RAY_TPU_CHAOS_DIR": chaos_dir,
            })
        chaos = ChaosController(c.rt)

        half = n_tasks // 2
        # Soft pins: producers prefer (and land on) n2 while it is
        # healthy, and their lineage re-executions can place on n1 once
        # suspicion declares n2 dead (a hard pin would strand them).
        s1 = [_make.options(scheduling_strategy=NA(
            node_id=n2, soft=True)).remote(i) for i in range(half)]
        ray.wait(s1, num_returns=len(s1), timeout=60)

        # Consumers pinned cross-node: every arg pull crosses the link
        # that is about to go gray.  Mid-run, stall the n2 head link
        # too — no process dies, no socket closes.
        s2 = [_consume.options(scheduling_strategy=NA(
            node_id=n1, soft=True)).remote(r) for r in s1]
        time.sleep(0.2)
        assert chaos.stall_link(n2) == n2

        t0 = time.monotonic()
        out = ray.get(s2, timeout=120)
        elapsed = time.monotonic() - t0
        # The counters ride the workers' periodic delta stream: wait for
        # the fan-out's own stall, and the reconstruction that followed
        # it, to be counted — a ``get`` that has returned says nothing of
        # when its workers last flushed.
        deadline = time.monotonic() + 15
        stats = c.rt.transfer_stats()
        while time.monotonic() < deadline and not (
                stats["stall_timeouts"] and stats["reconstructions"]):
            time.sleep(0.1)
            stats = c.rt.transfer_stats()
        proc = c._agents.get(n2)
        alive = proc is not None and proc.poll() is None
        return out, stats, elapsed, alive
    finally:
        if chaos is not None:
            chaos.stop()
        c.shutdown()


def test_netchaos_acceptance_stalled_link_fanout():
    """A mid-run STALLED (not killed) agent: every get correct and
    bounded, zero hangs, stalls counted, the node suspected, and losses
    recovered through the existing lineage path."""
    out, stats, elapsed, agent_alive = _netchaos_fanout()
    assert out == list(range(20))
    # Bounded, not hanging: stall deadline trips + retries + hedge +
    # suspicion window + reconstruction all fit well inside the get
    # timeout; the explicit wall bound pins "bounded" against creep.
    assert elapsed < 90, elapsed
    assert stats["stall_timeouts"] >= 1, stats
    assert stats["suspected_nodes"] >= 1, stats
    assert stats["net_retries"] >= 1, stats
    assert stats["reconstructions"] >= 1, stats
    # Gray, not clean: the stalled agent process never exited.
    assert agent_alive


@pytest.mark.slow
def test_netchaos_oneway_partition_declares_dead_and_revokes():
    """One-way partition (the head goes deaf to a perfectly healthy
    agent): suspicion alone — silence, probes, threshold — declares
    the node dead and the PR 6 path revokes its leases, without ANY
    process having exited."""
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(head_num_cpus=0, _system_config=dict(FAST_FD))
    chaos = None
    try:
        n2 = c.add_node(num_cpus=2, external=True)
        chaos = ChaosController(c.rt)

        # Park lease-holding work on the node so there are leases to
        # revoke when suspicion declares it dead.
        @ray.remote
        def slow(i):
            time.sleep(8)
            return i

        refs = [slow.options(scheduling_strategy=NA(
            node_id=n2, soft=False)).remote(i) for i in range(2)]
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline \
                and c.rt.transfer_stats()["lease_grants"] == 0:
            time.sleep(0.1)

        assert chaos.partition(n2, direction="in") == n2
        deadline = time.monotonic() + 20
        dead = False
        while time.monotonic() < deadline:
            nodes = {n["node_id"]: n["alive"] for n in c.rt.list_nodes()}
            if nodes.get(n2) is False:
                dead = True
                break
            time.sleep(0.2)
        assert dead, "suspicion never declared the partitioned node dead"
        stats = c.rt.transfer_stats()
        assert stats["suspected_nodes"] >= 1, stats
        proc = c._agents.get(n2)
        assert proc is not None and proc.poll() is None, \
            "partition variant must not kill any process"
        del refs
    finally:
        if chaos is not None:
            chaos.stop()
        c.shutdown()


@pytest.mark.slow
def test_drop_worker_connection_stall_variant_ab():
    """The A/B satellite: drop_worker_connection(stall=False) is the
    clean half-death (immediate EOF discovery), stall=True the gray one
    (socket open, head deaf) — one API; the gray drop is only
    discoverable by suspicion, counts a net_fault, and the fan-out
    still completes."""
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(head_num_cpus=0, _system_config=dict(FAST_FD))
    chaos = None
    try:
        c.add_node(num_cpus=2, external=True)
        chaos = ChaosController(c.rt)

        @ray.remote(max_retries=3)
        def f(i):
            time.sleep(0.25)
            return i * 3

        refs = [f.remote(i) for i in range(16)]
        # Wait until a worker is demonstrably up (first result back)
        # before taking its conn away — dropping during spawn finds no
        # victim.
        ready, _ = ray.wait(refs, num_returns=1, timeout=60)
        assert ready
        assert chaos.drop_worker_connection(stall=True) is not None
        assert ray.get(refs, timeout=90) == [i * 3 for i in range(16)]
        stats = c.rt.transfer_stats()
        assert stats["suspected_nodes"] >= 1, stats
        assert chaos.stats()["net_faults"] >= 1
    finally:
        if chaos is not None:
            chaos.stop()
        c.shutdown()


# ----------------------------------------------------- lockcheck re-run --

@pytest.mark.slow
def test_netchaos_battery_under_lockcheck():
    """The acceptance shape re-run under RAY_TPU_LOCKCHECK=1: the new
    suspicion loop, deadline retries, and net-chaos hook must introduce
    zero lock-order cycles (head/agent/workers all inherit the
    instrumentation)."""
    code = textwrap.dedent("""
        import os, tempfile, time
        os.environ["JAX_PLATFORMS"] = "cpu"
        import numpy as np
        import ray_tpu as ray
        from ray_tpu.devtools import lockcheck
        from ray_tpu.chaos import ChaosController
        from ray_tpu.cluster_utils import Cluster
        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy as NA,
        )

        cfg = {"net_stall_timeout_s": 0.8, "net_retry_count": 1,
               "net_retry_backoff_base_ms": 20.0,
               "health_check_period_s": 0.25,
               "health_check_timeout_s": 1.0,
               "health_check_failure_threshold": 2,
               "health_check_initial_delay_s": 1.0}
        chaos_dir = tempfile.mkdtemp()
        c = Cluster(head_num_cpus=2, _system_config=cfg)
        chaos = None
        try:
            n1 = c.add_node(num_cpus=2, external=True)
            n2 = c.add_node(num_cpus=2, external=True, env_overrides={
                "RAY_TPU_CHAOS_NET": "agent:chunk_send:stall:2",
                "RAY_TPU_CHAOS_DIR": chaos_dir})
            chaos = ChaosController(c.rt)

            @ray.remote(max_retries=3)
            def make(i):
                return np.full(260_000, i, dtype=np.int64)

            @ray.remote(max_retries=3)
            def consume(a):
                return int(a[0])

            s1 = [make.options(scheduling_strategy=NA(
                node_id=n2, soft=True)).remote(i) for i in range(8)]
            ray.wait(s1, num_returns=len(s1), timeout=60)
            s2 = [consume.options(scheduling_strategy=NA(
                node_id=n1, soft=True)).remote(r) for r in s1]
            time.sleep(0.2)
            assert chaos.stall_link(n2) == n2
            assert ray.get(s2, timeout=120) == list(range(8))
            stats = c.rt.transfer_stats()
            assert stats["stall_timeouts"] >= 1, stats
            assert stats["suspected_nodes"] >= 1, stats
        finally:
            if chaos is not None:
                chaos.stop()
            c.shutdown()
        bad = lockcheck.violations()
        assert not bad, "lock-order violations: " + repr(bad)
        print("NETCHAOS_LOCKCHECK_OK")
    """)
    env = dict(os.environ, RAY_TPU_LOCKCHECK="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-4000:])
    assert "NETCHAOS_LOCKCHECK_OK" in proc.stdout
