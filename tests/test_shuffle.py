"""Push-based shuffle battery (``ray_tpu/data/shuffle.py`` +
``streaming_executor.ShuffleOperator``).

Covered here:
- push-vs-legacy byte-identical results for sort (asc/desc),
  random_shuffle, groupby aggregate and map_groups, including runs
  randomized around ``shuffle_partition_bytes_target`` (reducer counts
  decoupled from the block count);
- merge-on-arrival ordering pins: tie-heavy sorts with a tiny
  ``shuffle_merge_fanin`` (intermediate merges forced, arrival order
  exercised), the exact legacy random permutation reproduced block by
  block, group rows emitted in None-safe key order;
- None-key sorts complete on both engines with Nones ordered last
  (first when descending) — the ``(x is None, x)`` convention;
- off-switch pin: ``push_shuffle=off`` reproduces the legacy path
  byte-identically, every new counter zero, and the shuffle module is
  never even imported;
- knob env-plumbing probe: the three shuffle knobs follow
  ``_system_config`` into spawned workers;
- the battery shape re-run under ``RAY_TPU_LOCKCHECK=1`` with zero
  lock-order cycles;
- the head's control plane flat on both engines
  (``head_brokered_submits`` and ``brokered_put_parts`` zero);
- slow lane: the kill-one-node-AND-stall-another chaos drill
  (reconstructions >= 1, shuffle_hedges >= 1, zero ObjectLostError).
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
from ray_tpu import data as rd

SHUFFLE_COUNTERS = ("shuffle_pushed_bytes", "shuffle_merges",
                    "shuffle_spills", "shuffle_hedges")
# The head's control plane: no partition payload or spec of either
# engine rides a head message.
HEAD_COUNTERS = ("head_brokered_submits", "brokered_put_parts")

# Tiny failure-detection windows for the chaos drill (the
# test_netchaos.py convention).
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


def _rows(n, seed):
    """Distinct float sort keys (no ties -> strict byte identity),
    integer-exact aggregation values."""
    rng = np.random.default_rng(seed)
    return [{"k": float(v), "g": i % 13, "v": i}
            for i, v in enumerate(rng.random(n))]


def _battery(ds):
    return {
        "sort_asc": ds.sort(key="k").take_all(),
        "sort_desc": ds.sort(key="k", descending=True).take_all(),
        "random": ds.random_shuffle(seed=3).take_all(),
        "agg": ds.groupby("g").aggregate(
            rd.Sum("v"), rd.Count(), rd.Min("v"), rd.Max("v")).take_all(),
        "map_groups": ds.groupby("g").map_groups(
            lambda rs: [{"g": rs[0]["g"],
                         "vs": [r["v"] for r in rs]}]).take_all(),
    }


def _run_battery(system_config, rows, parallelism=5):
    rt = ray.init(num_cpus=4, _system_config=system_config)
    try:
        res = _battery(rd.from_items(rows, parallelism=parallelism))
        stats = {k: v for k, v in rt.transfer_stats().items()
                 if k in SHUFFLE_COUNTERS + HEAD_COUNTERS}
        return res, stats
    finally:
        ray.shutdown()


# ------------------------------------------------ byte-identity pins ----

def test_push_vs_legacy_byte_identical():
    """The exact-equality contract: with push_shuffle on, every shuffle
    mode reproduces the legacy output bit-for-bit — same rows, same
    order, same block boundaries (R = n when no bytes target is set)."""
    rows = _rows(300, seed=0)
    on, on_stats = _run_battery({}, rows)
    off, off_stats = _run_battery({"push_shuffle": False}, rows)
    for mode in on:
        assert on[mode] == off[mode], mode
    assert on_stats["shuffle_pushed_bytes"] > 0, on_stats
    assert on_stats["shuffle_merges"] > 0, on_stats
    assert all(on_stats[k] == 0 for k in HEAD_COUNTERS), on_stats
    # Off-switch pin: every new counter zero (the head's two with them).
    assert all(v == 0 for v in off_stats.values()), off_stats


def test_partition_bytes_target_randomized():
    """Randomized ``shuffle_partition_bytes_target`` decouples R from
    the block count; the flattened sort output and the combined group
    rows stay identical to legacy at EVERY target (global order does
    not depend on where block boundaries fall)."""
    rows = _rows(400, seed=1)
    legacy, _ = _run_battery({"push_shuffle": False}, rows)
    rng = np.random.default_rng(7)
    # ~30 KB of pickled rows: one target per regime — tiny (clamped to
    # 4x the block count), mid (a few reducers), huge (R=1) — each
    # jittered so block-boundary placement is genuinely randomized.
    targets = [int(rng.integers(300, 900)),
               int(rng.integers(4_000, 9_000)),
               int(rng.integers(40_000, 90_000))]
    seen_r = set()
    for tgt in targets:
        rt = ray.init(num_cpus=4, _system_config={
            "shuffle_partition_bytes_target": tgt})
        try:
            ds = rd.from_items(rows, parallelism=5)
            out = ds.sort(key="k")
            got = out.take_all()
            assert got == legacy["sort_asc"], tgt
            assert out._stats is not None and out._stats.shuffle
            seen_r.add(out._stats.shuffle["reducers"])
            # Group rows land on different reducers at different R, but
            # the combined (key-ordered) result set is invariant.
            agg = ds.groupby("g").aggregate(
                rd.Sum("v"), rd.Count(), rd.Min("v"), rd.Max("v")
            ).take_all()
            assert sorted(agg, key=lambda r: r["g"]) == \
                sorted(legacy["agg"], key=lambda r: r["g"]), tgt
        finally:
            ray.shutdown()
    # The randomized targets really exercised different reducer counts.
    assert len(seen_r) >= 2, (targets, seen_r)


def test_sort_none_keys_both_engines():
    """Satellite pin: None sort keys no longer TypeError — they order
    after every real key (before, when descending), identically on the
    push and legacy engines."""
    rows = _rows(120, seed=2)
    for i in range(0, 120, 10):
        rows[i] = dict(rows[i], k=None)
    outs = {}
    for name, cfg in (("push", {}), ("legacy", {"push_shuffle": False})):
        ray.init(num_cpus=4, _system_config=cfg)
        try:
            ds = rd.from_items(rows, parallelism=4)
            outs[name] = (ds.sort(key="k").take_all(),
                          ds.sort(key="k", descending=True).take_all())
        finally:
            ray.shutdown()
    assert outs["push"] == outs["legacy"]
    asc, desc = outs["push"]
    assert [r["k"] for r in asc[-12:]] == [None] * 12
    assert [r["k"] for r in desc[:12]] == [None] * 12
    real = [r["k"] for r in asc if r["k"] is not None]
    assert real == sorted(real)


# ------------------------------------------- merge-on-arrival pins ----

def test_merge_on_arrival_sort_ordering_tie_heavy():
    """Tie-heavy keys + fanin=2 (intermediate merges forced while later
    maps are still arriving): the output must equal a STABLE sort of
    the map-order concatenation — equal keys keep block order — for
    both directions.  This is the strict-merge-key guarantee: arrival
    order cannot perturb the result."""
    sizes = [7, 61, 3, 40, 19]  # uneven blocks: maps finish out of order
    rows, blocks = [], []
    v = 0
    for s in sizes:
        blk = [{"k": v % 5, "v": (v := v + 1)} for _ in range(s)]
        blocks.append(blk)
        rows.extend(blk)
    ray.init(num_cpus=4, _system_config={"shuffle_merge_fanin": 2})
    try:
        ds = rd.from_items(rows, parallelism=len(sizes))
        asc = ds.sort(key="k")
        got_asc = asc.take_all()
        got_desc = ds.sort(key="k", descending=True).take_all()
        assert got_asc == sorted(rows, key=lambda r: r["k"])
        assert got_desc == sorted(rows, key=lambda r: r["k"],
                                  reverse=True)
        # fanin=2 really forced intermediate merges on arrival (not
        # just the one finalize merge per reducer).
        assert asc._stats.shuffle["shuffle_merges"] >= 1, \
            asc._stats.shuffle
    finally:
        ray.shutdown()


def test_random_shuffle_reproduces_exact_legacy_permutation():
    """The push engine must land EXACTLY the legacy permutation: per
    reducer j, the rows map i's RNG(seed+i) assigned to j, concatenated
    in map order, then shuffled by RNG(seed+1000+j) — computed here
    from first principles, not by running the legacy engine."""
    seed, n = 11, 4
    rows = [{"v": i} for i in range(200)]
    per_block = [rows[i * 50:(i + 1) * 50] for i in range(n)]
    expected = []
    assignments = [np.random.default_rng(seed + i).integers(
        0, n, size=50) for i in range(n)]
    for j in range(n):
        part = [r for i in range(n)
                for r, a in zip(per_block[i], assignments[i]) if a == j]
        np.random.default_rng(seed + 1000 + j).shuffle(part)
        expected.extend(part)
    ray.init(num_cpus=4)
    try:
        got = rd.from_items(rows, parallelism=n).random_shuffle(
            seed=seed).take_all()
        assert got == expected
    finally:
        ray.shutdown()


def test_groupby_rows_emitted_in_key_order_per_block():
    """Each output block's group rows are emitted in None-safe key
    order and every group appears exactly once across blocks."""
    rows = [{"g": i % 9, "v": i} for i in range(180)]
    ray.init(num_cpus=4)
    try:
        out = rd.from_items(rows, parallelism=4).groupby("g") \
            .aggregate(rd.Sum("v"), rd.Count())
        blocks = [list(b) for b in
                  (ray.get(r) for r in out._executed_refs())]
        seen = []
        for blk in blocks:
            keys = [r["g"] for r in blk]
            assert keys == sorted(keys), keys
            seen.extend(keys)
        assert sorted(seen) == list(range(9))
        for r in (row for blk in blocks for row in blk):
            g = r["g"]
            assert r["sum(v)"] == sum(v for v in range(180) if v % 9 == g)
            assert r["count()"] == 20
    finally:
        ray.shutdown()


# ------------------------------------------------ switches and knobs ----

def test_push_shuffle_off_never_imports_shuffle_module():
    """Off-switch hygiene in a fresh process: the legacy path runs
    without ever importing ray_tpu.data.shuffle (so its counters cannot
    even exist to drift) and transfer_stats reports all-zero shuffle
    counters sourced from the head's own fields."""
    code = textwrap.dedent("""
        import sys
        import ray_tpu as ray
        from ray_tpu import data as rd

        rt = ray.init(num_cpus=4, _system_config={"push_shuffle": False})
        ds = rd.from_items([{"k": i % 7, "v": i} for i in range(60)],
                           parallelism=3)
        assert [r["k"] for r in ds.sort(key="k").take_all()] == \\
            sorted(i % 7 for i in range(60))
        ds.random_shuffle(seed=1).take_all()
        stats = rt.transfer_stats()
        for k in ("shuffle_pushed_bytes", "shuffle_merges",
                  "shuffle_spills", "shuffle_hedges"):
            assert stats[k] == 0, (k, stats[k])
        assert "ray_tpu.data.shuffle" not in sys.modules
        st = ds.sort(key="k").materialize()
        assert "Push shuffle" not in st.stats()
        ray.shutdown()
        print("OFF_SWITCH_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAY_TPU_PUSH_SHUFFLE", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=180,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, (proc.stdout[-2000:],
                                  proc.stderr[-4000:])
    assert "OFF_SWITCH_OK" in proc.stdout


def test_shuffle_knobs_follow_system_config_into_workers():
    """The three knobs ride _system_config -> _worker_config_env -> the
    worker environment (the RTL504-enforced contract)."""
    ray.init(num_cpus=2, _system_config={
        "push_shuffle": False,
        "shuffle_partition_bytes_target": 123456,
        "shuffle_merge_fanin": 5,
    })
    try:
        @ray.remote
        def probe():
            import os

            return (os.environ.get("RAY_TPU_PUSH_SHUFFLE"),
                    os.environ.get(
                        "RAY_TPU_SHUFFLE_PARTITION_BYTES_TARGET"),
                    os.environ.get("RAY_TPU_SHUFFLE_MERGE_FANIN"))

        assert ray.get(probe.remote(), timeout=60) == \
            ("0", "123456", "5")
    finally:
        ray.shutdown()


def test_stats_surface_shuffle_summary():
    """Dataset.stats() grows the push-shuffle line; shuffle_summary()
    mirrors transfer_stats keys and reads all-zero on the legacy path."""
    from ray_tpu.data.execution import DatasetStats

    ray.init(num_cpus=4)
    try:
        ds = rd.from_items(_rows(80, seed=4), parallelism=4)
        out = ds.sort(key="k").materialize()
        assert "Push shuffle:" in out.stats()
        s = out._stats.shuffle_summary()
        assert s["reducers"] == 4 and s["maps"] == 4
        assert s["shuffle_pushed_bytes"] > 0
    finally:
        ray.shutdown()
    empty = DatasetStats().shuffle_summary()
    assert set(empty) == {"maps", "reducers", "shuffle_pushed_bytes",
                          "shuffle_merges", "shuffle_spills",
                          "shuffle_hedges"}
    assert all(v == 0 for v in empty.values())


# ------------------------------------------------- lockcheck battery ----

def test_shuffle_battery_lockcheck_clean():
    """The battery shape under RAY_TPU_LOCKCHECK=1 (head + workers all
    instrumented): zero lock-order cycles recorded in the driver."""
    code = textwrap.dedent("""
        import numpy as np
        import ray_tpu as ray
        from ray_tpu import data as rd
        from ray_tpu.devtools import lockcheck

        ray.init(num_cpus=4, _system_config={"shuffle_merge_fanin": 2})
        rng = np.random.default_rng(0)
        rows = [{"k": float(v), "g": i % 7, "v": i}
                for i, v in enumerate(rng.random(150))]
        ds = rd.from_items(rows, parallelism=5)
        assert [r["k"] for r in ds.sort(key="k").take_all()] == \\
            sorted(r["k"] for r in rows)
        ds.random_shuffle(seed=2).take_all()
        ds.groupby("g").aggregate(rd.Sum("v")).take_all()
        ray.shutdown()
        bad = lockcheck.violations()
        assert not bad, "lock-order violations: " + repr(bad)
        lockcheck.assert_acyclic()
        print("SHUFFLE_LOCKCHECK_OK")
    """)
    env = dict(os.environ, RAY_TPU_LOCKCHECK="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, (proc.stdout[-2000:],
                                  proc.stderr[-4000:])
    assert "SHUFFLE_LOCKCHECK_OK" in proc.stdout


# ------------------------------------------------------- slow lane ----

@pytest.mark.slow
def test_shuffle_chaos_drill_kill_one_node_stall_another():
    """THE shuffle chaos acceptance: 3-agent cluster, input blocks homed
    on the doomed nodes, then — the moment the map wave is submitted —
    one node's agent is KILLED and another's head link goes gray
    (ChaosNet stall, nothing EOFs).  The shuffle must complete with
    correct, fully-sorted results: lost input blocks reconstruct
    through lineage (reconstructions >= 1), unreachable reducer stores
    force map-side hedges and/or reducer rebuilds (shuffle_hedges >= 1),
    and no ObjectLostError ever reaches the consumer."""
    from ray_tpu.chaos import ChaosController
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.data.dataset import Dataset
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy as NA,
    )

    @ray.remote(max_retries=3)
    def mk_block(i):
        # > max_inline_object_size per block, so blocks are shm-homed
        # on their producer node (the kill genuinely loses them) rather
        # than riding the task result inline through the head.
        rng = np.random.default_rng(1000 + i)
        return [{"k": float(v), "p": bytes(6000)}
                for v in rng.random(300)]

    c = Cluster(head_num_cpus=2, _system_config=dict(FAST_FD))
    chaos = None
    try:
        n1 = c.add_node(num_cpus=2, external=True)
        n2 = c.add_node(num_cpus=2, external=True)
        n3 = c.add_node(num_cpus=2, external=True)
        chaos = ChaosController(c.rt)

        # Producers soft-pinned to the two doomed nodes: the kill takes
        # input blocks with it, so re-run maps MUST reconstruct them.
        homes = [n1, n2, n1, n2, n3, n1]
        blocks = [mk_block.options(scheduling_strategy=NA(
            node_id=homes[i], soft=True)).remote(i)
            for i in range(len(homes))]
        ray.wait(blocks, num_returns=len(blocks), timeout=60)

        fired = []

        def wreck():
            fired.append(chaos.kill_agent(n1))
            fired.append(chaos.stall_link(n2))

        chaos.at_syncpoint("shuffle:maps_submitted", wreck, n=1)

        out = Dataset(blocks).sort(key="k")
        rows = out.take_all()  # any ObjectLostError would surface here

        expected = sorted(
            float(v) for i in range(len(homes))
            for v in np.random.default_rng(1000 + i).random(300))
        assert [r["k"] for r in rows] == expected
        assert len(fired) == 2 and fired[0] == n1 and fired[1] == n2, \
            fired
        stats = c.rt.transfer_stats()
        assert stats["reconstructions"] >= 1, stats
        assert stats["shuffle_hedges"] >= 1, stats
    finally:
        if chaos is not None:
            chaos.stop()
        c.shutdown()
