"""Object spilling under store-capacity pressure.

Reference analog: ``src/ray/raylet/local_object_manager.h:41`` — when the
plasma store fills, unpinned primary copies spill to external storage and
restore on access; here the owner (driver) spills LRU unpinned READY
residents to ``spill_dir`` and readers restore transparently (same on-disk
layout as a shm segment, so the read path cannot tell the difference).
"""

import glob
import os

import numpy as np
import pytest

import ray_tpu as ray


CAP = 48 * 1024 * 1024  # 48 MB store
OBJ = 10 * 1024 * 1024  # 10 MB objects


@pytest.fixture
def small_store():
    rt = ray.init(num_cpus=4,
                  _system_config={"object_store_memory": CAP,
                                  "shm_pool_bytes": 0})
    yield rt
    ray.shutdown()


def test_put_past_capacity_spills_and_restores(small_store):
    rt = small_store
    refs = [ray.put(np.full(OBJ, i, dtype=np.uint8)) for i in range(10)]
    # 100 MB of live objects in a 48 MB store: spill files must exist.
    spilled = glob.glob(os.path.join(rt.spill_dir, "rtpu-*"))
    assert spilled, "no spill files created"
    # every object still reads back correctly (resident or restored)
    for i, r in enumerate(refs):
        arr = ray.get(r)
        assert arr[0] == i and arr[-1] == i and arr.shape[0] == OBJ


def test_spilled_object_feeds_task(small_store):
    rt = small_store
    refs = [ray.put(np.full(OBJ, i, dtype=np.uint8)) for i in range(10)]

    @ray.remote
    def head_byte(a):
        return int(a[0])

    # index 0 is the LRU victim — certainly spilled by now
    assert glob.glob(os.path.join(rt.spill_dir, "rtpu-*"))
    assert ray.get([head_byte.remote(r) for r in refs],
                   timeout=120) == list(range(10))


def test_freeing_spilled_object_removes_file(small_store):
    rt = small_store
    refs = [ray.put(np.full(OBJ, i, dtype=np.uint8)) for i in range(10)]
    n_before = len(glob.glob(os.path.join(rt.spill_dir, "rtpu-*")))
    assert n_before > 0
    del refs
    import gc
    import time

    gc.collect()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if not glob.glob(os.path.join(rt.spill_dir, "rtpu-*")):
            break
        time.sleep(0.2)
    assert not glob.glob(os.path.join(rt.spill_dir, "rtpu-*"))


def test_worker_results_spill_too(small_store):
    """Task returns (worker-created segments) participate: the owner spills
    them and notifies the creating worker to drop its pooled mapping."""
    rt = small_store

    @ray.remote
    def make(i):
        return np.full(OBJ, i, dtype=np.uint8)

    refs = [make.remote(i) for i in range(10)]
    vals = ray.get(refs, timeout=120)
    for i, v in enumerate(vals):
        assert v[0] == i


def test_worker_owned_puts_spill(small_store):
    """A worker whose OWN store fills during owner-local puts spills its
    owned objects per-node (local_object_manager.h:41) — the v1 design
    only spilled on the head node."""
    rt = small_store

    @ray.remote
    class Putter:
        def fill(self, n, size):
            import numpy as np

            import ray_tpu as ray

            refs = [ray.put(np.full(size, i, dtype=np.uint8))
                    for i in range(n)]
            # All live simultaneously: 100 MB owned in a 48 MB cap.
            return [int(ray.get(r)[0]) for r in refs]

    p = Putter.remote()
    assert ray.get(p.fill.remote(10, OBJ), timeout=120) == list(range(10))


def test_remote_node_task_returns_overflow():
    """A REMOTE (agent) node overfills its
    store during task returns and the job still completes — returns
    spill on that node and the driver restores them through the
    transfer path."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    cluster = Cluster(head_num_cpus=1)
    try:
        node_id = cluster.add_node(
            num_cpus=2, external=True,
            env_overrides={"RAY_TPU_STORE_BYTES": str(CAP),
                           "RAY_TPU_POOL_BYTES": "0"})

        @ray.remote(scheduling_strategy=NodeAffinitySchedulingStrategy(
            node_id=node_id, soft=False))
        def make(i):
            import numpy as np

            return np.full(OBJ, i, dtype=np.uint8)

        # 100 MB of returns against a 48 MB remote store cap.
        refs = [make.remote(i) for i in range(10)]
        vals = ray.get(refs, timeout=180)
        for i, v in enumerate(vals):
            assert v[0] == i and len(v) == OBJ
    finally:
        cluster.shutdown()
