"""Locality-aware scheduling: the default policy scores candidate nodes
by argument bytes homed in their object store and prefers the
top-locality node, without ever stalling a class or bypassing the
pipeline depth cap.

Reference analog: locality-aware lease selection in
``scheduling/policy/hybrid_scheduling_policy.cc`` through the owner's
object directory — the head holds that directory here (every SHM/SPILLED
descriptor carries ``(size, home store_id)``), so placement can chase
the bytes instead of shipping them.

Covered:
- the acceptance micro: a fan-out whose single large arg is homed on one
  node agent schedules >= 80% of tasks onto that node (``locality_hits``)
  and ``locality_bytes_saved`` records the avoided transfers;
- locality preference never bypasses ``max_tasks_in_flight_per_worker``:
  past the depth cap the spill-over tasks place normally (counted in
  ``locality_misses``);
- scheduler policy edges with no prior coverage: ``node_affinity`` soft
  fallback when the named node is full or dead (and hard affinity
  pending forever on a dead node), a PG task whose bundle can never fit
  staying queued while the PG itself stays usable;
- ``spread`` tie-breaking is deterministic (earliest node in
  ``node_order`` wins among equals).
"""

import os
import time

import numpy as np
import pytest

import ray_tpu as ray
from ray_tpu.util.scheduling_strategies import (
    NodeAffinitySchedulingStrategy as NA,
)

ARG_MB = 4


@pytest.fixture
def cluster_factory():
    from ray_tpu.cluster_utils import Cluster

    made = []

    def make(**kw):
        c = Cluster(**kw)
        made.append(c)
        return c

    yield make
    for c in made:
        c.shutdown()


def _home_big_arg(n1: str, nbytes: int):
    """A large object homed in node ``n1``'s store (produced there)."""

    @ray.remote
    def make(n):
        return np.ones(n, np.uint8)

    ref = make.options(scheduling_strategy=NA(n1)).remote(nbytes)
    ready, _ = ray.wait([ref], num_returns=1, timeout=60)
    assert ready
    return ref


@ray.remote
def _where(_a):
    return os.environ["RAY_TPU_NODE_ID"]


# ------------------------------------------------------ acceptance micro --

def test_locality_fanout_prefers_home_node(cluster_factory):
    c = cluster_factory(head_num_cpus=4)
    n1 = c.add_node(num_cpus=2, external=True)
    c.add_node(num_cpus=2, external=True)
    ref = _home_big_arg(n1, ARG_MB << 20)

    base_hits = c.rt.locality_hits
    base_saved = c.rt.locality_bytes_saved
    n = 20
    nodes = ray.get([_where.remote(ref) for _ in range(n)], timeout=120)
    frac = nodes.count(n1) / n
    assert frac >= 0.8, f"only {frac:.0%} of tasks ran on the arg's node"
    assert c.rt.locality_hits - base_hits >= int(n * 0.8), \
        (c.rt.locality_hits, base_hits)
    saved = c.rt.locality_bytes_saved - base_saved
    assert saved >= int(n * 0.8) * (ARG_MB << 20), saved


# -------------------------------------------- depth-cap interaction ------

def test_locality_does_not_bypass_pipeline_depth_cap(cluster_factory):
    depth = 2
    c = cluster_factory(
        head_num_cpus=2,
        _system_config={"max_tasks_in_flight_per_worker": depth})
    n1 = c.add_node(num_cpus=1, external=True)
    ref = _home_big_arg(n1, 2 << 20)

    @ray.remote
    def slow(_a):
        # Long enough that all 6 submissions dispatch while every task
        # still runs (submission is milliseconds), short for suite time.
        time.sleep(0.6)
        return os.environ["RAY_TPU_NODE_ID"]

    base_hits = c.rt.locality_hits
    base_miss = c.rt.locality_misses
    # 6 tasks, all preferring n1 (1 CPU): one fresh lease + one pipelined
    # slot reach the depth cap; the other 4 must place on the head even
    # though their bytes live on n1 — locality never queues past the cap.
    nodes = ray.get([slow.remote(ref) for _ in range(6)], timeout=120)
    assert nodes.count(n1) == depth, nodes
    assert c.rt.locality_hits - base_hits == depth
    assert c.rt.locality_misses - base_miss == 6 - depth


# ------------------------------------------------ policy edges ------------

def test_node_affinity_soft_falls_back_when_node_full(ray_start_regular):
    rt = ray_start_regular
    nid = rt.add_node(num_cpus=1)

    @ray.remote
    def hold():
        time.sleep(5)
        return "held"

    @ray.remote
    def quick():
        return os.environ["RAY_TPU_NODE_ID"]

    h = hold.options(scheduling_strategy=NA(nid.hex())).remote()
    time.sleep(0.3)  # let the hard-affinity task take the node's slot
    out = ray.get(
        quick.options(scheduling_strategy=NA(nid.hex(), soft=True)).remote(),
        timeout=30)
    # Soft affinity fell back to another node instead of queueing.
    assert out != nid.hex()
    ray.cancel(h, force=True)


def test_node_affinity_dead_node_soft_vs_hard(ray_start_regular):
    rt = ray_start_regular
    nid = rt.add_node(num_cpus=1)
    rt.remove_node(nid)

    @ray.remote
    def quick():
        return os.environ["RAY_TPU_NODE_ID"]

    out = ray.get(
        quick.options(scheduling_strategy=NA(nid.hex(), soft=True)).remote(),
        timeout=30)
    assert out != nid.hex()
    hard = quick.options(scheduling_strategy=NA(nid.hex())).remote()
    ready, not_ready = ray.wait([hard], num_returns=1, timeout=1.5)
    assert not ready and not_ready == [hard]


def test_pg_task_rejected_when_bundle_cannot_fit(ray_start_regular):
    from ray_tpu.util import (
        PlacementGroupSchedulingStrategy,
        placement_group,
        remove_placement_group,
    )

    pg = placement_group([{"CPU": 1}], strategy="PACK")
    assert pg.wait(30)

    @ray.remote(num_cpus=2)
    def too_big():
        return "ran"

    @ray.remote(num_cpus=1)
    def fits():
        return "ran"

    strat = PlacementGroupSchedulingStrategy(pg, 0)
    big_ref = too_big.options(scheduling_strategy=strat).remote()
    ready, _ = ray.wait([big_ref], num_returns=1, timeout=1.5)
    assert ready == []  # 2 CPUs can never fit the 1-CPU bundle
    # The bundle stays usable for correctly-sized work behind it.
    assert ray.get(fits.options(scheduling_strategy=strat).remote(),
                   timeout=30) == "ran"
    remove_placement_group(pg)


# ------------------------------------------------- spread determinism ----

def test_spread_tie_break_is_deterministic(ray_start_regular):
    from ray_tpu._private.runtime import TaskRecord

    rt = ray_start_regular
    rt.add_node(num_cpus=4)
    rt.add_node(num_cpus=4)

    def pick():
        rec = TaskRecord(
            {"scheduling_strategy": ("spread",), "args": [],
             "num_returns": 1, "task_id": b"\0" * 16},
            {"CPU": 1.0}, 0)
        with rt.lock:
            return rt._pick_node_locked(rec)

    # All nodes idle: equal scores on the two equal nodes; the head's
    # score differs (different total resources) but whatever wins must
    # win every time.
    first = pick()
    assert all(pick() is first for _ in range(10))
    # Break the tie by consuming capacity on the winner: the next pick
    # moves to the earliest remaining best node, again deterministically.
    with rt.lock:
        first.acquire({"CPU": 1.0})
    second = pick()
    assert second is not first
    assert all(pick() is second for _ in range(10))
    with rt.lock:
        first.release({"CPU": 1.0})
