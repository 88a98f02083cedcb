"""Host-collective tests (reference pattern:
python/ray/util/collective/tests)."""

import numpy as np
import pytest

import ray_tpu as ray
from ray_tpu.util import collective as col


@pytest.fixture
def ray8():
    rt = ray.init(num_cpus=8)
    yield rt
    ray.shutdown()


@ray.remote
class Member:
    def execute(self, fn, *a, **kw):
        return fn(*a, **kw)

    def do_allreduce(self, rank):
        return col.allreduce(np.full(4, rank + 1.0), op="sum")

    def do_allgather(self, rank):
        return col.allgather(np.array([rank], np.float32))

    def do_reducescatter(self, rank):
        return col.reducescatter(np.arange(8, dtype=np.float32), op="sum")

    def do_broadcast(self, rank):
        arr = np.full(3, 42.0) if rank == 0 else np.zeros(3)
        return col.broadcast(arr, src_rank=0)

    def do_sendrecv(self, rank):
        if rank == 0:
            col.send(np.array([7.0, 8.0]), dst_rank=1)
            return None
        return col.recv(src_rank=0)


def _make_group(n):
    members = [Member.options(num_cpus=1).remote() for _ in range(n)]
    col.create_collective_group(members, n, list(range(n)))
    return members


def test_allreduce_sum(ray8):
    members = _make_group(3)
    outs = ray.get([m.do_allreduce.remote(i) for i, m in enumerate(members)])
    for o in outs:
        assert np.allclose(o, np.full(4, 1.0 + 2.0 + 3.0))


def test_allgather(ray8):
    members = _make_group(3)
    outs = ray.get([m.do_allgather.remote(i) for i, m in enumerate(members)])
    for o in outs:
        assert [float(x[0]) for x in o] == [0.0, 1.0, 2.0]


def test_reducescatter(ray8):
    members = _make_group(2)
    outs = ray.get([m.do_reducescatter.remote(i)
                    for i, m in enumerate(members)])
    full = 2 * np.arange(8, dtype=np.float32)
    assert np.allclose(outs[0], full[:4])
    assert np.allclose(outs[1], full[4:])


def test_broadcast(ray8):
    members = _make_group(3)
    outs = ray.get([m.do_broadcast.remote(i)
                    for i, m in enumerate(members)])
    for o in outs:
        assert np.allclose(o, 42.0)


def test_send_recv(ray8):
    members = _make_group(2)
    outs = ray.get([m.do_sendrecv.remote(i) for i, m in enumerate(members)])
    assert outs[0] is None
    assert np.allclose(outs[1], [7.0, 8.0])


def test_actor_pool(ray8):
    @ray.remote
    class Sq:
        def sq(self, x):
            return x * x

    from ray_tpu.util import ActorPool
    pool = ActorPool([Sq.remote() for _ in range(2)])
    out = sorted(pool.map(lambda a, v: a.sq.remote(v), range(6)))
    assert out == [0, 1, 4, 9, 16, 25]


def test_distributed_queue(ray8):
    from ray_tpu.util.queue import Queue, Empty
    q = Queue(maxsize=4)
    q.put({"a": 1})
    q.put(2)
    assert q.qsize() == 2
    assert q.get() == {"a": 1}
    assert q.get() == 2
    with pytest.raises(Empty):
        q.get(timeout=0.2)
    q.shutdown()


@ray.remote
class RingMember:
    """Large payloads: the ring transport engages (>= 1024 elements)."""

    def execute(self, fn, *a, **kw):
        return fn(*a, **kw)

    def ring_allreduce(self, rank, n):
        out = col.allreduce(np.full(n, rank + 1.0, np.float32), op="sum")
        assert col._group("default").ring is not None, "ring not active"
        return float(out[0]), float(out[-1]), out.shape

    def ring_allgather(self, rank, n):
        outs = col.allgather(np.full(n, float(rank), np.float32))
        return [float(o[0]) for o in outs]

    def ring_reducescatter(self, rank, n, world):
        out = col.reducescatter(np.arange(n, dtype=np.float64), op="sum")
        expect = np.array_split(np.arange(n) * world, world)[rank]
        assert np.allclose(out, expect), (out[:4], expect[:4])
        return len(out)

    def ring_mean(self, rank, n):
        out = col.allreduce(np.full(n, rank + 1.0, np.float32), op="mean")
        return float(out[0])

    def timed(self, rank, n, reps):
        import time

        arr = np.ones(n, np.float32)
        col.allreduce(arr)  # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            col.allreduce(arr)
        return time.perf_counter() - t0


def _ring_group(n):
    members = [RingMember.options(num_cpus=1).remote() for _ in range(n)]
    col.create_collective_group(members, n, list(range(n)))
    return members


def test_ring_allreduce(ray8):
    members = _ring_group(4)
    n = 40_000
    outs = ray.get([m.ring_allreduce.remote(i, n)
                    for i, m in enumerate(members)], timeout=120)
    for first, last, shape in outs:
        assert first == last == 1 + 2 + 3 + 4
        assert shape == (n,)


def test_ring_allgather(ray8):
    members = _ring_group(3)
    outs = ray.get([m.ring_allgather.remote(i, 5000)
                    for i, m in enumerate(members)], timeout=120)
    for o in outs:
        assert o == [0.0, 1.0, 2.0]


def test_ring_reducescatter_matches_star_semantics(ray8):
    members = _ring_group(4)
    lens = ray.get([m.ring_reducescatter.remote(i, 10_000, 4)
                    for i, m in enumerate(members)], timeout=120)
    assert sum(lens) == 10_000


def test_ring_mean(ray8):
    members = _ring_group(3)
    outs = ray.get([m.ring_mean.remote(i, 4096)
                    for i, m in enumerate(members)], timeout=120)
    assert all(abs(o - 2.0) < 1e-5 for o in outs)


@pytest.mark.slow  # ~6s perf A/B; ring CORRECTNESS keeps its tier-1
# coverage via the sub-second ring_allreduce/allgather/reducescatter/
# mean tests above — this row only re-measures the speedup.
def test_ring_beats_star_bench(ray8):
    """A big allreduce through the ring vs the star.
    On multi-core hardware the ring wins >2x (every link busy vs one
    actor's GIL); on a 1-core CI box we only record the numbers."""
    import os

    n = 2_000_000  # 8 MB fp32 per rank
    world = 4
    members = _ring_group(world)
    t_ring = max(ray.get([m.timed.remote(i, n, 3)
                          for i, m in enumerate(members)], timeout=300))

    # Same workload with the ring disabled (star coordinator).
    def _kill_ring():
        g = col._group("default")
        if g.ring is not None:
            g.ring.close()
            g.ring = None
        return True

    ray.get([m.execute.remote(_kill_ring) for m in members])
    t_star = max(ray.get([m.timed.remote(i, n, 3)
                          for i, m in enumerate(members)], timeout=300))
    print(f"ring={t_ring:.3f}s star={t_star:.3f}s "
          f"speedup={t_star / t_ring:.2f}x")
    if (os.cpu_count() or 1) >= 4:
        assert t_star / t_ring > 2.0


def test_ring_reducescatter_multidim_matches_star(ray8):
    """Multi-dim reducescatter splits along axis 0 on BOTH transports."""
    @ray.remote
    class M2:
        def execute(self, fn, *a, **kw):
            return fn(*a, **kw)

        def rs(self, rank):
            out = col.reducescatter(np.ones((400, 8), np.float32) * (rank + 1))
            return out.shape, float(out[0, 0])

    members = [M2.options(num_cpus=1).remote() for _ in range(2)]
    col.create_collective_group(members, 2, [0, 1])
    outs = ray.get([m.rs.remote(i) for i, m in enumerate(members)],
                   timeout=120)
    for shape, v in outs:
        assert shape == (200, 8)
        assert v == 3.0  # 1 + 2
