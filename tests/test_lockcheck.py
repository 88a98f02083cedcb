"""Runtime lock-order checker tests: cycle detection on deliberately
inverted locks, the RAY_TPU_LOCKCHECK env opt-in, the documented lock
conventions of object_transfer/shm_store verified against the recorded
acquisition graph, and the async event-loop stall watch."""

import asyncio
import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from ray_tpu.devtools import lockcheck


@pytest.fixture
def checker():
    """Install instrumentation for one test; always restore the real
    threading.Lock/RLock factories afterwards."""
    lockcheck.install(raise_on_cycle=False)
    lockcheck.clear()
    yield lockcheck
    lockcheck.uninstall()


# -- core cycle detection ---------------------------------------------------

def _make_two_locks():
    # Distinct lines => distinct lock classes (site = creation file:line).
    lock_a = threading.Lock()
    lock_b = threading.Lock()
    return lock_a, lock_b


def test_inverted_two_lock_acquisition_detected(checker):
    lock_a, lock_b = _make_two_locks()
    with lock_a:
        with lock_b:
            pass
    assert checker.violations() == []  # one order alone is fine
    with lock_b:
        with lock_a:
            pass
    assert len(checker.violations()) == 1
    assert "potential deadlock" in checker.violations()[0]
    with pytest.raises(lockcheck.LockOrderError):
        checker.assert_acyclic()


def test_consistent_order_stays_clean(checker):
    lock_a, lock_b = _make_two_locks()
    for _ in range(3):
        with lock_a:
            with lock_b:
                pass
    assert checker.violations() == []
    checker.assert_acyclic()


def test_three_lock_cycle_detected(checker):
    lock_a = threading.Lock()
    lock_b = threading.Lock()
    lock_c = threading.Lock()
    with lock_a:
        with lock_b:
            pass
    with lock_b:
        with lock_c:
            pass
    with lock_c:
        with lock_a:
            pass  # closes a -> b -> c -> a
    assert len(checker.violations()) == 1


def test_raise_mode_raises_and_releases(checker):
    lockcheck.install(raise_on_cycle=True)
    lock_a, lock_b = _make_two_locks()
    with lock_a:
        with lock_b:
            pass
    with pytest.raises(lockcheck.LockOrderError):
        with lock_b:
            with lock_a:
                pass
    # The violating acquire must not leak either lock.
    assert not lock_a.locked()
    assert not lock_b.locked()


def test_rlock_reentrancy_is_not_a_cycle(checker):
    rlock = threading.RLock()
    with rlock:
        with rlock:
            pass
    assert checker.violations() == []


def test_condition_variable_wait_notify_under_proxies(checker):
    # Condition over a proxied Lock exercises the _release_save/_is_owned
    # fallback paths; a hang or crash here means the proxy broke the
    # threading.Condition contract.
    cond = threading.Condition(threading.Lock())
    ready = []

    def waiter():
        with cond:
            ready.append(True)
            cond.wait(timeout=5)
            ready.append("woken")

    thread = threading.Thread(target=waiter, daemon=True)
    thread.start()
    deadline = time.monotonic() + 5
    while not ready and time.monotonic() < deadline:
        time.sleep(0.005)
    with cond:
        cond.notify_all()
    thread.join(timeout=5)
    assert ready == [True, "woken"]
    checker.assert_acyclic()


def test_cross_thread_lock_handoff_leaves_no_stale_hold(checker):
    """A plain Lock acquired on one thread and released on another (the
    handoff pattern RTL401 suppressions endorse) must clear the
    ACQUIRING thread's held entry — otherwise every later acquisition on
    that thread records bogus edges from the handed-off lock."""
    handoff = threading.Lock()
    other_a = threading.Lock()
    other_b = threading.Lock()
    handoff.acquire()  # held by main thread, released elsewhere

    releaser = threading.Thread(target=handoff.release)
    releaser.start()
    releaser.join(timeout=5)
    assert not handoff.locked()
    # Main thread no longer holds anything: these nestings must not
    # record edges from the handed-off lock's site.  (Edges recorded
    # WHILE handoff was held — e.g. Thread.start()'s internal Event
    # lock — are legitimate and may exist.)
    with other_a:
        with other_b:
            pass
    handoff_site = handoff._site
    edges = checker.edges()
    assert other_a._site not in edges.get(handoff_site, set()), edges
    assert other_b._site not in edges.get(handoff_site, set()), edges
    assert other_b._site in edges.get(other_a._site, set())
    assert checker.violations() == []


def test_uninstall_restores_real_factories():
    lockcheck.install()
    lockcheck.uninstall()
    assert not lockcheck.enabled()
    assert not isinstance(threading.Lock(), lockcheck._LockProxy)


# -- env opt-in -------------------------------------------------------------

def test_env_flag_runtime_smoke_and_inversion_detection():
    """One subprocess covers both env-opt-in scenarios (kept to a single
    interpreter spawn for tier-1 budget):

    1. the standard-run smoke — a real init/task/actor/put workload under
       RAY_TPU_LOCKCHECK=1 completes with ZERO lock-order violations,
       which keeps future scale-out PRs honest about lock ordering;
    2. the acceptance scenario — a deliberately inverted two-lock
       acquisition afterwards IS reported by the env-installed checker.
    """
    code = textwrap.dedent("""
        import threading
        import ray_tpu
        from ray_tpu.devtools import lockcheck
        assert lockcheck.enabled(), "env flag did not install lockcheck"
        ray_tpu.init(num_cpus=2, num_tpus=0)

        @ray_tpu.remote
        def f(x):
            return x + 1

        assert ray_tpu.get([f.remote(i) for i in range(4)]) == [1, 2, 3, 4]

        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def inc(self):
                self.n += 1
                return self.n

            async def peek(self):
                return self.n

        c = Counter.remote()
        assert ray_tpu.get([c.inc.remote() for _ in range(3)]) == [1, 2, 3]
        assert ray_tpu.get(c.peek.remote()) == 3
        ref = ray_tpu.put(list(range(50000)))
        assert len(ray_tpu.get(ref)) == 50000
        ray_tpu.shutdown()
        bad = lockcheck.violations()
        assert not bad, "lock-order violations in runtime: " + repr(bad)

        a = threading.Lock()
        b = threading.Lock()
        with a:
            with b:
                pass
        with b:
            with a:
                pass
        assert len(lockcheck.violations()) == 1, lockcheck.violations()
        print("LOCKCHECK_SMOKE_OK")
    """)
    env = dict(os.environ, RAY_TPU_LOCKCHECK="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "LOCKCHECK_SMOKE_OK" in proc.stdout


# -- documented lock conventions --------------------------------------------

class _DeadConn:
    """Stand-in connection: dial succeeds, first send fails."""

    def fileno(self):
        raise OSError("no fd")  # enable_nodelay tolerates this

    def send_bytes(self, data):
        raise OSError("peer gone")

    def close(self):
        pass


def test_object_puller_lock_order_convention(checker, monkeypatch):
    """object_transfer.ObjectPuller's documented convention: the registry
    lock and every pool's condition lock are independent leaves — the
    recorded acquisition graph must contain NO edge between them (in
    either direction), even on the fetch-failure path where evict()
    (condition lock) follows a failed stream on an exclusively-held
    connection."""
    import multiprocessing.connection

    from ray_tpu._private.object_transfer import ObjectPuller

    monkeypatch.setattr(multiprocessing.connection, "Client",
                        lambda addr, authkey=None: _DeadConn())
    puller = ObjectPuller(authkey=b"x", pool_size=2, stripe_threshold=0)
    assert isinstance(puller._lock, lockcheck._LockProxy)
    with pytest.raises(OSError):
        puller.fetch("store-1", "tcp://127.0.0.1:1", "segment")
    # The failed fetch exercised: registry (pool creation), the pool
    # condition (acquire's count bump, dial outside it, evict's count
    # drop + waiter wakeup), and the stream send on a lock-free
    # exclusively-acquired connection.
    pool = puller._pools["store-1"]
    registry_site = puller._lock._site
    pool_site = pool.cv._lock._site
    edges = lockcheck.edges()
    assert pool_site not in edges.get(registry_site, set()), (
        "registry lock held while taking a pool condition lock")
    assert registry_site not in edges.get(pool_site, set()), (
        "pool condition lock held while taking the registry lock")
    assert all(registry_site not in targets
               for targets in edges.values()), (
        f"some lock is held while acquiring the registry lock: {edges}")
    checker.assert_acyclic()
    puller.close()


def test_pull_registry_lock_order_convention(checker):
    """object_transfer.PullRegistry's documented convention: the registry
    ``_lock`` is an INDEPENDENT LEAF — never held across a dial, stream
    I/O or an event wait, and NO other lock is acquired under it (note
    Event.set acquires the event's internal condition lock, so finish()
    must — and does — set outside ``_lock``).  The recorded acquisition
    graph must show zero outgoing edges from the registry lock across
    the leader/waiter/retain/consume/failure paths."""
    from ray_tpu._private.object_transfer import PullRegistry

    class _Seg:
        size = 7

        def close(self):
            pass

    reg = PullRegistry()
    assert isinstance(reg._lock, lockcheck._LockProxy)
    # Leader + concurrent waiter sharing its result.
    ent, leader = reg.begin(("s", "a"))
    assert leader
    got = []
    waiter = threading.Thread(target=lambda: got.append(ent.wait(5)))
    waiter.start()
    seg = _Seg()
    reg.finish(("s", "a"), ent, seg)
    waiter.join(timeout=5)
    assert got == [seg]
    assert reg.deduped_pulls == 0  # the waiter attached via wait(), not begin
    # Prefetch retention + consume.
    pent, pleader = reg.begin(("s", "b"), prefetch=True)
    assert pleader
    reg.finish(("s", "b"), pent, _Seg(), retain=True)
    cent, cleader = reg.begin(("s", "b"))
    assert not cleader and reg.take(("s", "b"), cent) is pent.seg
    # Failure path wakes into the fallback.
    fent, fleader = reg.begin(("s", "c"))
    assert fleader
    reg.finish(("s", "c"), fent, None)
    assert fent.wait(1) is None
    registry_site = reg._lock._site
    edges = checker.edges()
    assert edges.get(registry_site, set()) == set(), (
        f"a lock was acquired while holding the pull-registry lock: "
        f"{edges.get(registry_site)}")
    checker.assert_acyclic()


def test_put_registry_lock_order_convention(checker, tmp_path):
    """object_transfer.PutRegistry's documented convention: the
    server-side put-registry ``_lock`` is an INDEPENDENT LEAF — it
    guards only the entry table and writer counts; reservation (file
    create + store accounting), stripe recv streaming, and mapping
    teardown all run OUTSIDE it.  The recorded acquisition graph must
    show zero outgoing edges from it across the reserve/write/commit/
    abort/dead-writer paths.  (The store's own ``_lock``, taken inside
    reserve_put, is a separate class acquired while the registry lock is
    NOT held.)"""
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.object_transfer import CHUNK, PutRegistry
    from ray_tpu._private.shm_store import ShmStore

    class _FeedConn:
        """recv_bytes_into stub: fills the requested range with zeros,
        one CHUNK-sized message at a time."""

        def __init__(self):
            self.left = 0

        def recv_bytes_into(self, view, off=0):
            n = min(CHUNK, len(view) - off)
            view[off:off + n] = b"\0" * n
            return n

    store = ShmStore(shm_dir=str(tmp_path), session_id="putlock")
    reg = PutRegistry(store)
    assert isinstance(reg._lock, lockcheck._LockProxy)
    # Reserve -> stripe write -> commit.
    name = reg.reserve(ObjectID.from_random().binary(), 4096)
    assert reg.write(name, _FeedConn(), 0, 4096)
    kind, ident, total = reg.commit(name)
    assert (kind, ident, total) == ("shm", name, 4096)
    # Reserve -> abort; a late stripe for the aborted put drains via the
    # discard path (needs recv_bytes, absent on the stub -> use a fresh
    # name with zero length instead: the bounds check refuses in-lock).
    name2 = reg.reserve(ObjectID.from_random().binary(), 4096)
    reg.abort(name2)
    assert not reg.write(name2, _FeedConn(), 0, 0)
    reg_site = reg._lock._site
    edges = checker.edges()
    assert edges.get(reg_site, set()) == set(), (
        f"a lock was acquired while holding the put-registry lock: "
        f"{edges.get(reg_site)}")
    checker.assert_acyclic()
    store.cleanup()


def test_streaming_stats_lock_convention(checker):
    """data/streaming_executor.StreamingStats._lock's documented
    convention: an independent LEAF — the executor's dispatch loop is
    single-threaded and the lock only guards counter snapshots read by
    Dataset.stats(), so it is never held across submission/wait/get and
    NO other lock is acquired under it.  The recorded acquisition graph
    must show zero outgoing edges from the stats lock across the
    row-create/update/snapshot paths."""
    from ray_tpu.data.streaming_executor import StreamingStats

    stats = StreamingStats(budget_bytes=1 << 20, inflight_cap=4)
    assert isinstance(stats._lock, lockcheck._LockProxy)
    row = stats.op_row("map+filter")
    with stats._lock:
        row["inflight"] += 1
        stats.admitted_tasks += 1
    stats.note_live_bytes(512)
    # Concurrent reader (the Dataset.stats() shape) while the "executor
    # thread" keeps mutating.
    got = []
    reader = threading.Thread(target=lambda: got.append(stats.summary()))
    reader.start()
    stats.note_live_bytes(1024)
    reader.join(timeout=5)
    assert got and got[0]["admitted_tasks"] == 1
    assert stats.summary()["peak_inflight_bytes"] == 1024
    stats_site = stats._lock._site
    edges = checker.edges()
    assert edges.get(stats_site, set()) == set(), (
        f"a lock was acquired while holding the streaming-stats lock: "
        f"{edges.get(stats_site)}")
    checker.assert_acyclic()


def test_shuffle_stats_lock_convention(checker, monkeypatch):
    """data/shuffle._STATS_LOCK's documented convention: an independent
    LEAF — it guards only the process-local shuffle counter dict read by
    ``shuffle_stats()`` (the xfer_stats flusher / transfer_stats merge)
    and is never held across serialization, a push, or any wire call.
    The recorded acquisition graph must show zero outgoing edges from
    the stats lock across the note/snapshot paths."""
    from ray_tpu.data import shuffle as _sh

    # Module-level lock predates install(): swap in one created under
    # instrumentation (the _copy_pool_lock test's pattern).
    monkeypatch.setattr(_sh, "_STATS_LOCK", threading.Lock())
    monkeypatch.setattr(_sh, "_STATS", {
        "shuffle_pushed_bytes": 0, "shuffle_merges": 0,
        "shuffle_spills": 0, "shuffle_hedges": 0})
    assert isinstance(_sh._STATS_LOCK, lockcheck._LockProxy)
    _sh.note("shuffle_pushed_bytes", 4096)
    _sh.note("shuffle_merges")
    # Concurrent reader (the flush-thread shape) while the "map task"
    # keeps counting.
    got = []
    reader = threading.Thread(
        target=lambda: got.append(_sh.shuffle_stats()))
    reader.start()
    _sh.note("shuffle_hedges")
    reader.join(timeout=5)
    assert got and got[0]["shuffle_pushed_bytes"] == 4096
    assert _sh.shuffle_stats()["shuffle_merges"] == 1
    stats_site = _sh._STATS_LOCK._site
    edges = checker.edges()
    assert edges.get(stats_site, set()) == set(), (
        f"a lock was acquired while holding the shuffle-stats lock: "
        f"{edges.get(stats_site)}")
    checker.assert_acyclic()


def test_train_stats_lock_convention(checker, monkeypatch):
    """train/pipeline_actors._STATS_LOCK's documented convention: an
    independent LEAF guarding only the process-local training counter
    dict read by ``train_stats()`` (the xfer_stats flusher /
    transfer_stats merge); never held across serialization, a push, or
    any wire call — zero outgoing edges across the note/snapshot paths."""
    from ray_tpu.train import pipeline_actors as _pa

    monkeypatch.setattr(_pa, "_STATS_LOCK", threading.Lock())
    monkeypatch.setattr(_pa, "_STATS", {
        "microbatch_pushes": 0, "stage_restarts": 0,
        "learner_queue_stalls": 0})
    assert isinstance(_pa._STATS_LOCK, lockcheck._LockProxy)
    _pa.note("microbatch_pushes", 3)
    _pa.note("stage_restarts")
    got = []
    reader = threading.Thread(
        target=lambda: got.append(_pa.train_stats()))
    reader.start()
    _pa.note("learner_queue_stalls")
    reader.join(timeout=5)
    assert got and got[0]["microbatch_pushes"] == 3
    assert _pa.train_stats()["stage_restarts"] == 1
    stats_site = _pa._STATS_LOCK._site
    edges = checker.edges()
    assert edges.get(stats_site, set()) == set(), (
        f"a lock was acquired while holding the training-stats lock: "
        f"{edges.get(stats_site)}")
    checker.assert_acyclic()


def test_lineage_table_lock_is_leaf(checker):
    """recovery.LineageTable._lock's documented convention: an
    independent LEAF.  Both owners take it while already holding their
    big lock — the head's runtime lock (record in _submit_specs, release
    in _maybe_free_locked) and every DirectCaller's ownership lock — and
    the table runs NO callbacks and acquires NO lock under it (eviction
    RETURNS entries for the caller to release at its own level).  The
    recorded graph must show the incoming edge and zero outgoing
    edges."""
    import ray_tpu as ray
    from ray_tpu._private import api_internal

    ray.init(num_cpus=2, num_tpus=0)
    try:
        rt = api_internal.get_runtime()
        assert isinstance(rt.lineage._lock, lockcheck._LockProxy)

        @ray.remote
        def f(x):
            return x + 1

        refs = [f.remote(i) for i in range(8)]
        assert ray.get(refs) == list(range(1, 9))
        # Release path: dropping the refs drives lineage.release under
        # the runtime lock (the recorded inward edge).
        del refs
        import gc

        gc.collect()
        time.sleep(0.2)
        lineage_site = rt.lineage._lock._site
    finally:
        ray.shutdown()
    edges = checker.edges()
    assert edges.get(lineage_site, set()) == set(), (
        f"a lock was acquired while holding the lineage-table lock: "
        f"{edges.get(lineage_site)}")
    checker.assert_acyclic()


def test_shm_store_copy_pool_lock_convention(checker, monkeypatch,
                                             tmp_path):
    """shm_store's documented convention: the module copy-pool lock and
    the store's _lock are independent leaves — a large (parallel-copied)
    put followed by pooled reuse must record no edge between them."""
    if (os.cpu_count() or 1) < 2:
        pytest.skip("parallel copy path needs >= 2 cores")
    from ray_tpu._private import shm_store as shm_mod
    from ray_tpu._private.ids import ObjectID

    # The module-level pool lock predates install(); swap in a fresh
    # (instrumented) one and force pool re-creation through it.
    monkeypatch.setattr(shm_mod, "_copy_pool_lock", threading.Lock())
    monkeypatch.setattr(shm_mod, "_copy_pool", None)
    store = shm_mod.ShmStore(shm_dir=str(tmp_path), session_id="lockchk",
                             pool_bytes=256 << 20)
    assert isinstance(store._lock, lockcheck._LockProxy)
    payload = memoryview(bytearray(shm_mod._PARALLEL_COPY_MIN + 1024))
    name, size = store.create_from_parts(ObjectID.from_random(), b"meta",
                                         [payload])
    store.unlink(name, size, reusable=True)
    # Second create reuses the pooled mapping (pool scan under _lock).
    name2, _size2 = store.create_from_parts(ObjectID.from_random(),
                                            b"meta", [payload])
    store_site = store._lock._site
    pool_site = shm_mod._copy_pool_lock._site
    edges = lockcheck.edges()
    assert pool_site not in edges.get(store_site, set()), (
        "store._lock held while taking the copy-pool lock")
    assert store_site not in edges.get(pool_site, set()), (
        "copy-pool lock held while taking store._lock")
    checker.assert_acyclic()
    store.cleanup()


def test_dispatch_shard_dirty_lock_convention(checker):
    """Decentralized dispatch's documented convention: the per-shard
    dirty-set lock (Runtime._dispatch_dirty_lock) is an independent LEAF
    — marking a shard dirty happens under the runtime lock on the hot
    paths, the dispatcher's wake event is set OUTSIDE it, and NO other
    lock is ever acquired under it.  The recorded acquisition graph must
    show zero outgoing edges from it across a real submit/result cycle
    (driver bursts route through the deferred-dispatch marking)."""
    import ray_tpu as ray
    from ray_tpu._private import api_internal

    ray.init(num_cpus=2, num_tpus=0)
    try:
        rt = api_internal.get_runtime()
        assert isinstance(rt._dispatch_dirty_lock, lockcheck._LockProxy)

        @ray.remote
        def f(x):
            return x + 1

        # Burst (deferred marking) + per-result class top-ups.
        assert ray.get([f.remote(i) for i in range(8)]) == \
            list(range(1, 9))
        dirty_site = rt._dispatch_dirty_lock._site
    finally:
        ray.shutdown()
    edges = checker.edges()
    assert edges.get(dirty_site, set()) == set(), (
        f"a lock was acquired while holding the dispatch dirty lock: "
        f"{edges.get(dirty_site)}")
    checker.assert_acyclic()


# -- event-loop stall watch -------------------------------------------------

def test_event_loop_stall_recorded(checker):
    loop = asyncio.new_event_loop()
    lockcheck.watch_loop(loop, threshold_s=0.05)
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    try:
        async def blocking_handler():
            time.sleep(0.12)  # noqa: RTL102 -- deliberate stall for test
            return "done"

        fut = asyncio.run_coroutine_threadsafe(blocking_handler(), loop)
        assert fut.result(timeout=5) == "done"
        deadline = time.monotonic() + 2
        while not lockcheck.stalls() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert any("took" in s for s in lockcheck.stalls()), \
            lockcheck.stalls()
    finally:
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=5)
        loop.close()


def test_fast_async_handler_records_no_stall(checker):
    loop = asyncio.new_event_loop()
    lockcheck.watch_loop(loop, threshold_s=0.05)
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    try:
        async def quick():
            return 1

        assert asyncio.run_coroutine_threadsafe(quick(), loop).result(5) \
            == 1
        assert lockcheck.stalls() == []
    finally:
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=5)
        loop.close()


def test_serve_batcher_locks_are_leaves(checker):
    """serve/batching + serve/continuous documented convention: both
    batcher locks are independent LEAVES — they guard only the pending
    queue and counters, the wrapped/step function runs with no lock
    held, and caller events are set outside them.  The recorded
    acquisition graph must show zero outgoing edges from either lock
    across a concurrent submit/step/retire cycle (including a stats
    snapshot taken mid-flight, the serving_stats path)."""
    from ray_tpu.serve.batching import _Batcher
    from ray_tpu.serve.continuous import _ContinuousBatcher

    def stepfn(slots):
        time.sleep(0.001)
        for s in slots:
            s.state = (s.state or 0) + 1
            if s.state >= s.request:
                s.finish(s.state)

    cont = _ContinuousBatcher(stepfn, None, 4, 0.0, continuous=True)
    oneshot = _Batcher(lambda items: [x * 2 for x in items], None, 4,
                       0.02)
    assert isinstance(cont._lock, lockcheck._LockProxy)
    assert isinstance(oneshot._lock, lockcheck._LockProxy)
    results = []
    threads = [threading.Thread(target=lambda n=n:
                                results.append(cont.submit(n)))
               for n in (1, 2, 3, 1, 2, 3)]
    threads += [threading.Thread(target=lambda n=n:
                                 results.append(oneshot.submit(n)))
                for n in (4, 5, 6)]
    for t in threads:
        t.start()
    cont.stats()  # concurrent snapshot while the batch runs
    oneshot.stats()
    for t in threads:
        t.join(timeout=30)
    assert len(results) == 9
    edges = checker.edges()
    for site in (cont._lock._site, oneshot._lock._site):
        assert edges.get(site, set()) == set(), (
            f"a lock was acquired while holding a serve batcher lock: "
            f"{edges.get(site)}")
    checker.assert_acyclic()


def test_disagg_chain_lock_is_leaf(checker, monkeypatch):
    """serve/tpu_replica documented convention: the replica's
    ``_chain_lock`` (handoff bookkeeping + ingest-info cache) is an
    independent LEAF — kv_debug releases it BEFORE taking the engine
    guard, prefill_export's fallback counting nests nothing under it,
    and no wire call runs while it is held.  Driven through a real
    prefill-only handoff (inline fallback: no runtime) plus the debug
    snapshot, the acquisition graph must show zero outgoing edges from
    the chain lock."""
    from ray_tpu._private import config as _cfg
    from ray_tpu.serve.tpu_replica import MeshShardedDecoder

    # The paged batcher attaches at first call off the process config.
    monkeypatch.setattr(_cfg.GLOBAL_CONFIG, "paged_kv", True)
    dec = MeshShardedDecoder(paged=True, kv_blocks=32, kv_block_size=8)
    assert isinstance(dec._chain_lock, lockcheck._LockProxy)
    assert dec.kv_ingest_info() is None          # no runtime: inline tier
    descr, sampler = dec.prefill_export(
        {"prompt": list(range(12)), "tokens": 4})
    assert descr[0] == "inline" and sampler["pos"] == 12
    dbg = dec.kv_debug()
    assert dbg["chain"]["inline_fallbacks"] == 1
    assert dbg["exports_outstanding"] == 0
    chain_site = dec._chain_lock._site
    edges = checker.edges()
    assert edges.get(chain_site, set()) == set(), (
        f"a lock was acquired while holding the chain-handoff lock: "
        f"{edges.get(chain_site)}")
    checker.assert_acyclic()


def test_disagg_router_affinity_lock_is_leaf(checker):
    """serve/api documented convention: DeploymentHandle's
    ``_affinity_lock`` (prefix-affinity table + router counters) is an
    independent LEAF — _pick_prefill takes the router ``_lock`` and the
    affinity lock STRICTLY sequentially (reps snapshot, then table
    lookup; p2c fallback, then registration), so the recorded graph
    must show zero outgoing edges from the affinity lock and no edge
    between the two in either direction."""
    from ray_tpu.serve.api import DeploymentHandle

    class _Rep:
        def __init__(self, aid):
            self._actor_id = aid

    h = object.__new__(DeploymentHandle)
    h._router_init()
    h._affinity_on = True
    from collections import OrderedDict

    h._affinity = OrderedDict()
    h._affinity_lock = threading.Lock()
    h._router_prefix_hits = 0
    h._router_prefix_misses = 0
    h._prefill_replicas = [_Rep(b"a"), _Rep(b"b")]
    assert isinstance(h._affinity_lock, lockcheck._LockProxy)
    prompt = list(range(24))
    first = h._pick_prefill(prompt)              # miss -> p2c + register
    assert first in h._prefill_replicas
    assert h._pick_prefill(prompt) is first      # affinity hit
    h._prefill_replicas = [_Rep(b"c")]           # old pick died
    again = h._pick_prefill(prompt)              # stale prune + re-pin
    assert again._actor_id == b"c"
    stats = h.router_stats()
    assert stats["router_prefix_hits"] == 1
    assert stats["router_prefix_misses"] == 2
    aff_site = h._affinity_lock._site
    lock_site = h._lock._site
    edges = checker.edges()
    assert edges.get(aff_site, set()) == set(), (
        f"a lock was acquired while holding the affinity lock: "
        f"{edges.get(aff_site)}")
    assert aff_site not in edges.get(lock_site, set()), (
        "router _lock held while taking the affinity lock")
    checker.assert_acyclic()


def test_paged_batcher_lock_stays_leaf_with_kv_engine(checker):
    """Paged-KV admission convention (serve/kv_cache.py): the engine
    adopts the batcher's LEAF lock via bind() — block-availability
    re-checks at admission, retire-time frees, step-side write planning,
    and a mid-flight stats snapshot all run under the ONE batcher lock,
    with caller events still set outside it.  Driven through allocator
    exhaustion (parks + re-admission) the acquisition graph must show
    zero outgoing edges from the batcher lock."""
    from ray_tpu.serve.continuous import _ContinuousBatcher
    from ray_tpu.serve.kv_cache import PagedKVEngine

    eng = PagedKVEngine(4, 4, tokens_for=lambda r: ((), r),
                        prefix_caching=False)

    def stepfn(slots):
        time.sleep(0.001)
        for s in slots:
            s.state = (s.state or 0) + 1
            # Step-side engine paths acquire the SAME (leaf) guard.
            eng.plan_writes(s, s.state - 1, 1)
            eng.note_tokens(1)
            if s.state >= s.request:
                s.finish(s.state)

    b = _ContinuousBatcher(stepfn, None, 8, 0.0, continuous=True, kv=eng)
    assert isinstance(b._lock, lockcheck._LockProxy)
    assert eng._guard is b._lock   # bind() adopted the batcher leaf
    results = []
    # 16-token pool, 8-token budgets: >2 concurrent submits exhaust the
    # pool so the run exercises park -> retire -> re-admit boundaries.
    threads = [threading.Thread(target=lambda n=n:
                                results.append(b.submit(n)))
               for n in (8, 8, 8, 8, 8, 8)]
    for t in threads:
        t.start()
    b.stats()                      # concurrent snapshot mid-flight
    for t in threads:
        t.join(timeout=30)
    assert len(results) == 6
    s = b.stats()
    assert s["admission_parks"] >= 1 and s["kv_blocks_used"] == 0
    edges = checker.edges()
    assert edges.get(b._lock._site, set()) == set(), (
        f"a lock was acquired while holding the paged batcher leaf "
        f"lock: {edges.get(b._lock._site)}")
    checker.assert_acyclic()
