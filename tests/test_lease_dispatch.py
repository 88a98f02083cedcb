"""Decentralized dispatch: bulk lease grants, spillback, revocation,
renewal, and the head-off-the-submit-path acceptance criterion
(reference: raylet lease-based hybrid scheduling + spillback,
local_task_manager.h:58; ownership of task metadata at the submitting
worker — Ownership, NSDI'21)."""

import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

import ray_tpu as ray
from ray_tpu._private import api_internal


def _settled_stats(rt, timeout=6.0):
    """transfer_stats once the periodic worker deltas stop changing."""
    stats = rt.transfer_stats()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        time.sleep(0.35)
        nxt = rt.transfer_stats()
        if nxt == stats:
            return nxt
        stats = nxt
    return stats


def _wait_counter(rt, key, min_val, timeout=8.0):
    """Poll until a transfer_stats counter reaches min_val (worker
    deltas ride the 0.25s flusher)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        stats = rt.transfer_stats()
        if stats[key] >= min_val:
            return stats
        time.sleep(0.1)
    return rt.transfer_stats()


@ray.remote
def _noop():
    return None


@ray.remote
def _nap(t):
    time.sleep(t)
    return os.getpid()


@ray.remote
class _Client:
    def burst(self, n):
        import ray_tpu as ray

        return len(ray.get([_noop.remote() for _ in range(n)]))

    def slow_burst(self, n, t):
        import ray_tpu as ray

        return len(set(ray.get([_nap.remote(t) for _ in range(n)])))

    def lease_slots_seen(self, n):
        """Run a burst, then report the slot caps and peak inflight of
        the leases THIS process held (the holder-side view of the
        max_tasks_in_flight_per_worker cap)."""
        import ray_tpu as ray
        from ray_tpu._private.worker_main import get_worker_runtime

        rt = get_worker_runtime()
        peaks = {}

        def sample():
            while not done[0]:
                with rt.direct.lock:
                    for pool in rt.direct.pools.values():
                        for lease in pool["leases"]:
                            key = id(lease)
                            peaks[key] = (
                                lease.slots,
                                max(peaks.get(key, (0, 0))[1],
                                    len(lease.inflight)))
                time.sleep(0.002)

        done = [False]
        t = threading.Thread(target=sample, daemon=True)
        t.start()
        ray.get([_nap.remote(0.02) for _ in range(n)])
        done[0] = True
        t.join(timeout=5)
        return list(peaks.values())


def test_acceptance_head_brokered_stays_flat_under_fanin():
    """The acceptance criterion: a 500-task multi-client fan-in rides
    the lease plane — leased_submits carries the traffic while
    head_brokered_submits stays ~flat (bounded by lease-grant/renewal
    and starvation events, NOT task count)."""
    ray.init(num_cpus=16)
    rt = api_internal.get_runtime()
    try:
        clients = [_Client.remote() for _ in range(4)]
        # Warm-up: workers spawn, first leases get granted.
        assert ray.get([c.burst.remote(5) for c in clients]) == [5] * 4
        s0 = _settled_stats(rt)
        assert ray.get([c.burst.remote(125) for c in clients]) == [125] * 4
        s1 = _settled_stats(rt)
        leased = s1["leased_submits"] - s0["leased_submits"]
        brokered = (s1["head_brokered_submits"]
                    - s0["head_brokered_submits"])
        # The fan-in is 500 tasks; the lease plane must carry the bulk
        # and the head must see at most a starvation-bounded trickle.
        assert leased + brokered >= 500, (leased, brokered)
        assert leased >= 400, (leased, brokered)
        assert brokered <= 100, (leased, brokered)
        assert s1["lease_grants"] >= 1
    finally:
        ray.shutdown()


@pytest.mark.slow  # the slots bound keeps its tier-1 representative in
                   # the renewal unit test below (stub-host, sub-second);
                   # this adds only the in-cluster sampling geometry
def test_holder_never_exceeds_granted_slots():
    """Lease pipelining vs the max_tasks_in_flight_per_worker cap: the
    head grants min(lease_slots, max_tasks_in_flight_per_worker) slots
    and the holder never pipelines past them — renewal keeps a lease
    alive, it never widens it."""
    ray.init(num_cpus=8, _system_config={"lease_slots": 64})
    rt = api_internal.get_runtime()
    try:
        cap = rt.config.max_tasks_in_flight_per_worker
        c = _Client.remote()
        seen = ray.get(c.lease_slots_seen.remote(60), timeout=120)
        assert seen, "burst never held a lease"
        for slots, peak_inflight in seen:
            assert slots <= cap, (slots, cap)
            assert peak_inflight <= slots, (peak_inflight, slots)
    finally:
        ray.shutdown()


def test_unsolicited_grant_piggybacks_on_brokered_burst():
    """A burst of direct-eligible specs arriving at the head marks the
    sender lease-starved: the head piggybacks a bulk lease_grant on the
    exchange (counted in lease_grants) so the next burst rides the
    direct plane.  Redundant-grant guard: a sender that already holds a
    lease gets no offer."""
    ray.init(num_cpus=8)
    rt = api_internal.get_runtime()
    try:
        ray.get(_noop.remote())  # spawn at least one live worker
        with rt.lock:
            lessee = next(
                w for n in rt.nodes.values()
                for w in n.all_workers.values()
                if not w.dead and w.conn is not None)
        fake_burst = [{"name": "t", "resources": {"CPU": 1.0}}
                      for _ in range(8)]
        g0 = rt.lease_grants
        rt._maybe_offer_lease(lessee, fake_burst)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and rt.lease_grants == g0:
            time.sleep(0.05)
        assert rt.lease_grants > g0
        # The lessee now holds leases: a second burst is guarded.
        deadline = time.monotonic() + 5
        held = False
        while time.monotonic() < deadline and not held:
            with rt.lock:
                held = any(w.client_lease is lessee
                           for n in rt.nodes.values()
                           for w in n.all_workers.values())
            time.sleep(0.02)
        assert held
        g1 = rt.lease_grants
        rt._maybe_offer_lease(lessee, fake_burst)
        time.sleep(0.5)
        assert rt.lease_grants == g1
    finally:
        ray.shutdown()


def test_renewal_batches_one_message_per_n_pushes(monkeypatch):
    """Holder-side renewal amortization, pinned at the unit level: a
    granted lease is renewed with ONE lease_renew message per
    lease_renew_tasks pushes (not one per task), and the holder never
    pipelines past the granted slot count."""
    import queue as queue_mod

    from ray_tpu._private import direct as direct_mod
    from ray_tpu._private import protocol, serialization
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu._private.ids import new_task_id

    monkeypatch.setattr(GLOBAL_CONFIG, "lease_ttl_s", 30.0)
    monkeypatch.setattr(GLOBAL_CONFIG, "lease_renew_tasks", 4)

    sent_head = []

    class FakeConn:
        def __init__(self):
            self._q = queue_mod.SimpleQueue()

        def send_bytes(self, b):
            pass

        def recv_bytes(self):
            return self._q.get()  # parks the reader thread

        def close(self):
            pass

    class Host:
        store_id = "stub"
        shm = None

        def head_request(self, build):
            return {"grants": [("w1", ("127.0.0.1", 1), None)],
                    "slots": 2, "ttl": 30.0, "hint": None}

        def head_send(self, msg):
            sent_head.append(msg)

        def dial(self, addr):
            return FakeConn()

        def get_payload(self, fid):
            return b"payload"

        def submit_via_head(self, spec):
            sent_head.append(("submit", 0, spec))

        def submit_via_head_many(self, specs):
            sent_head.append(("submit_batch", specs))

    caller = direct_mod.DirectCaller(Host())

    def spec():
        return {"task_id": new_task_id().binary(), "num_returns": 1,
                "name": "t", "args": [], "kwargs": {}, "func_id": "f",
                "resources": {"CPU": 1.0}}

    caller.submit_many([spec() for _ in range(12)])
    deadline = time.monotonic() + 5
    lease = None
    while time.monotonic() < deadline and lease is None:
        with caller.lock:
            for pool in caller.pools.values():
                if pool["leases"]:
                    lease = pool["leases"][0]
        time.sleep(0.01)
    assert lease is not None
    assert lease.slots == 2
    descr = (protocol.INLINE, serialization.dumps_inline(None))
    pushed_total = 0
    for _ in range(24):
        with caller.lock:
            rids = list(lease.inflight)
        if not rids:
            break
        assert len(rids) <= 2, rids  # granted slots bound the pipeline
        pushed_total += len(rids)
        caller._on_result_batch(
            lease, [(rid, True, [descr], {}) for rid in rids])
    assert pushed_total >= 12

    def flat(msgs):
        for m in msgs:
            if protocol.is_batch(m):
                yield from m[1]
            else:
                yield m

    renews = [m for m in flat(sent_head) if m[0] == "lease_renew"]
    assert renews, sent_head
    assert all(m[1] == ["w1"] for m in renews)
    # One renewal per lease_renew_tasks=4 pushes (not one per task).
    assert len(renews) <= 12 // 4, renews
    caller.shutdown()


@pytest.mark.slow  # ~16s; revocation-on-node-death now has a faster
# tier-1 rep in tests/test_chaos.py (kill-agent-mid-lease interplay),
# and the renewal/TTL units above stay tier-1
def test_lease_revocation_on_node_death_mid_push():
    """A node dies while a holder is pushing onto its leased workers:
    the head revokes the leases explicitly (lease_revocations counts
    them) and every pushed spec still completes — rerouted through the
    head or re-leased elsewhere, none lost."""
    ray.init(num_cpus=1)
    rt = api_internal.get_runtime()
    try:
        node2 = rt.add_node(num_cpus=8)
        c = _Client.remote()  # takes the head's only CPU slot
        # Long enough burst that the node dies mid-stream.
        fut = c.slow_burst.remote(24, 0.04)
        deadline = time.monotonic() + 20
        leased_on_node2 = False
        while time.monotonic() < deadline and not leased_on_node2:
            with rt.lock:
                leased_on_node2 = any(
                    w.client_lease is not None and not w.dead
                    for w in rt.nodes[node2].all_workers.values())
            time.sleep(0.01)
        assert leased_on_node2, "no lease ever landed on the added node"
        rt.remove_node(node2)
        # All 24 tasks must still produce results (>=1 distinct pid).
        assert ray.get(fut, timeout=120) >= 1
        stats = _wait_counter(rt, "lease_revocations", 1)
        assert stats["lease_revocations"] >= 1, stats
    finally:
        ray.shutdown()


def test_spillback_bounces_and_work_completes():
    """An oversubscribed leased worker bounces excess pushes
    (lease_spillback_depth); the holder re-lands them (other leases /
    hint-steered requests / head fallback) and the burst completes with
    spillbacks counted."""
    ray.init(num_cpus=8, _system_config={"lease_spillback_depth": 2})
    rt = api_internal.get_runtime()
    try:
        c = _Client.remote()
        assert ray.get(c.slow_burst.remote(32, 0.05), timeout=120) >= 1
        stats = _wait_counter(rt, "spillbacks", 1)
        assert stats["spillbacks"] >= 1, stats
        assert stats["leased_submits"] >= 1, stats
    finally:
        ray.shutdown()


@pytest.mark.slow  # spillback + counters keep their tier-1
                   # representative in the single-node test above; this
                   # adds only the two-node hint-landing geometry
def test_spillback_hint_steers_next_lease_to_second_node():
    """The bounced-back hint names the next-best node and the holder's
    next lease request honors it: with the head node saturated, the
    spilled work's replacement leases land on the second node."""
    ray.init(num_cpus=4, _system_config={"lease_spillback_depth": 2,
                                         "lease_slots": 4})
    rt = api_internal.get_runtime()
    try:
        node2 = rt.add_node(num_cpus=8)
        c = _Client.remote()
        fut = c.slow_burst.remote(48, 0.05)
        # Sample DURING the burst for a CLIENT lease on node2: the
        # head-fallback reroute after SPILL_MAX bounces places ordinary
        # head-dispatch leases (client_lease is None), so only the
        # hint-steered lease_req can produce this observation.
        leased_on_node2 = False
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not leased_on_node2:
            with rt.lock:
                leased_on_node2 = any(
                    w.client_lease is not None and not w.dead
                    for w in rt.nodes[node2].all_workers.values())
            time.sleep(0.01)
        assert ray.get(fut, timeout=120) >= 1
        stats = _settled_stats(rt)
        if stats["spillbacks"] < 1:
            pytest.skip("burst drained without oversubscription "
                        "(load-dependent); spillback covered above")
        # Replacement CLIENT leases were drawn from the hinted node.
        assert leased_on_node2, stats
    finally:
        ray.shutdown()


def test_lockcheck_battery_over_lease_plane():
    """The fan-in + spillback + revocation battery re-run under
    RAY_TPU_LOCKCHECK=1: zero lock-order cycles across the dispatcher
    thread, the dirty-shard marking, lease granting and the holder-side
    pools."""
    code = textwrap.dedent("""
        import time
        import ray_tpu as ray
        from ray_tpu.devtools import lockcheck
        from ray_tpu._private import api_internal

        assert lockcheck.enabled()
        ray.init(num_cpus=8,
                 _system_config={"lease_spillback_depth": 2})
        rt = api_internal.get_runtime()

        @ray.remote
        def nap(t):
            time.sleep(t)
            return None

        @ray.remote
        class Client:
            def burst(self, n, t):
                import ray_tpu as ray
                return len(ray.get([nap.remote(t) for _ in range(n)]))

        clients = [Client.remote() for _ in range(3)]
        assert ray.get([c.burst.remote(30, 0.01) for c in clients]) \\
            == [30, 30, 30]
        # Revocation path: kill a leased worker mid-burst.
        fut = clients[0].burst.remote(30, 0.05)
        deadline = time.monotonic() + 15
        victim = None
        while victim is None and time.monotonic() < deadline:
            with rt.lock:
                for node in rt.nodes.values():
                    for w in node.all_workers.values():
                        if w.client_lease is not None and not w.dead \\
                                and w.proc is not None:
                            victim = w
                            break
                    if victim:
                        break
            time.sleep(0.01)
        if victim is not None:
            victim.proc.terminate()
        assert ray.get(fut, timeout=120) == 30
        dirty_site = rt._dispatch_dirty_lock._site
        ray.shutdown()
        bad = lockcheck.violations()
        assert not bad, "lock-order violations: " + repr(bad)
        # Per-shard dirty lock is a LEAF: nothing is acquired under it
        # (the dispatcher event is set OUTSIDE it by design).
        edges = lockcheck.edges()
        assert edges.get(dirty_site, set()) == set(), edges.get(dirty_site)
        print("LEASE_LOCKCHECK_OK")
    """)
    env = dict(os.environ, RAY_TPU_LOCKCHECK="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "LEASE_LOCKCHECK_OK" in proc.stdout
