"""Round benchmark: core-runtime microbenchmarks mirroring the reference's
harness (`python/ray/_private/ray_perf.py:93`, numbers in BASELINE.md) plus
TPU compute benchmarks (flash attention, flagship train step) on the real
chip.  Prints ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "tpu": {...}}

value/vs_baseline = geometric mean of (ours / reference-published) over the
core task/actor/object microbenchmarks — 1.0 is parity with the numbers the
reference repo publishes for itself (release_logs/2.3.0/microbenchmark.json).
The "tpu" dict carries device-compute numbers (tokens/s, MFU, flash-attention
timings) that the reference has no counterpart for (its release tests assert
completion, not throughput).  Per-metric results go to stderr.
"""

import json
import sys
import time


# Reference-published means (BASELINE.md, release_logs/2.3.0).
BASELINE = {
    "single_client_tasks_sync": 1304.0,
    "single_client_tasks_async": 11031.0,
    "multi_client_tasks_async": 28385.0,
    "one_one_actor_calls_sync": 2142.0,
    "one_one_actor_calls_async": 8099.0,
    "one_one_actor_calls_concurrent": 4928.0,
    "one_one_async_actor_calls_sync": 1559.0,
    "one_n_actor_calls_async": 10962.0,
    "n_n_actor_calls_async": 32387.0,
    "single_client_get_calls": 5902.0,
    "single_client_put_gigabytes": 20.4,
    "multi_client_put_gigabytes": 36.2,
    "single_client_wait_1k_refs": 5.45,
    "single_client_get_object_containing_10k_refs": 13.3,
    # Ray Client (external process driving the cluster; the reference
    # proxies through gRPC — microbenchmark.json client__* rows).
    "client_get_calls": 1190.7,
    "client_put_calls": 832.7,
    "client_put_gigabytes": 0.0457,
    "client_one_one_actor_calls_sync": 533.3,
}

# Not folded into the headline geomean: the reference's get_calls number
# measures plasma-store gets through a store RPC, while ours are in-process
# zero-copy mmap attaches — a structurally different (and much faster)
# operation, so the ratio would flatter the geomean apples-to-oranges.
NON_COMPARABLE = {"single_client_get_calls"}


def timeit(fn, n, warmup=50):
    fn(min(warmup, n))
    t0 = time.perf_counter()
    fn(n)
    return n / (time.perf_counter() - t0)


def timeit_best_of(fn, n, warmup=50, rounds=3):
    """Best-of-N with the raw per-round samples preserved.  The contended
    multi-client rows swing 2-4x on IDENTICAL code under shared-host load
    (PR 2's interleaved A/B notes); recording every sample in the round
    JSON makes that drift diagnosable from the artifact instead of
    looking like a code regression."""
    fn(min(warmup, n))
    samples = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        fn(n)
        samples.append(round(n / (time.perf_counter() - t0), 1))
    return max(samples), samples


def core_bench():
    import numpy as np

    import ray_tpu as ray
    # Actors below hold 19 CPU slots; the rest are worker-pool slots for
    # task leases (the reference harness runs on a 64-vCPU box with the
    # full core count available).
    ray.init(num_cpus=32)

    @ray.remote
    def f():
        return None

    @ray.remote
    class Actor:
        def m(self):
            return None

    @ray.remote
    class Client:
        """Driver-proxy submitting work from a worker process
        (ray_perf's 'multi client' metrics)."""

        def run_tasks(self, n):
            import ray_tpu as ray
            ray.get([f.remote() for _ in range(n)])

        def call_actor(self, target, n):
            import ray_tpu as ray
            ray.get([target.m.remote() for _ in range(n)])

        def put_bytes(self, nbytes, reps):
            import numpy as np

            import ray_tpu as ray
            # Source array allocated once per client and kept warm across
            # calls (ray_perf.py's multi-client put loop reuses one warm
            # buffer per client; a cold np.zeros would measure the
            # kernel's zero-page faulting, not the store).
            a = getattr(self, "_buf", None)
            if a is None or len(a) != nbytes:
                a = self._buf = np.ones(nbytes, dtype=np.uint8)
            for _ in range(reps):
                ray.put(a)

    results = {}
    # Raw best-of-3 samples for the contended fan-in rows, carried into
    # the round JSON next to the headline values.
    raw_samples = {}

    def tasks_sync(n):
        for _ in range(n):
            ray.get(f.remote())

    results["single_client_tasks_sync"] = timeit(tasks_sync, 300, 30)

    def tasks_async(n):
        ray.get([f.remote() for _ in range(n)])

    results["single_client_tasks_async"] = timeit(tasks_async, 3000)

    clients = [Client.remote() for _ in range(4)]

    def multi_tasks_async(n):
        per = n // len(clients)
        ray.get([c.run_tasks.remote(per) for c in clients])

    results["multi_client_tasks_async"], raw_samples[
        "multi_client_tasks_async"] = timeit_best_of(
            multi_tasks_async, 4000, 400)

    a = Actor.remote()
    ray.get(a.m.remote())

    def actor_sync(n):
        for _ in range(n):
            ray.get(a.m.remote())

    results["one_one_actor_calls_sync"] = timeit(actor_sync, 1000)

    def actor_async(n):
        ray.get([a.m.remote() for _ in range(n)])

    results["one_one_actor_calls_async"] = timeit(actor_async, 3000)

    @ray.remote
    class ThreadedActor:
        def m(self):
            return None

    ta = ThreadedActor.options(max_concurrency=4).remote()
    ray.get(ta.m.remote())

    def actor_concurrent(n):
        ray.get([ta.m.remote() for _ in range(n)])

    results["one_one_actor_calls_concurrent"] = timeit(actor_concurrent,
                                                       2000)

    @ray.remote
    class AsyncActor:
        async def m(self):
            return None

    aa = AsyncActor.remote()
    ray.get(aa.m.remote())

    def async_actor_sync(n):
        for _ in range(n):
            ray.get(aa.m.remote())

    results["one_one_async_actor_calls_sync"] = timeit(async_actor_sync,
                                                       800)

    actors = [Actor.remote() for _ in range(8)]
    ray.get([b.m.remote() for b in actors])

    def one_n_async(n):
        per = n // len(actors)
        ray.get([b.m.remote() for b in actors for _ in range(per)])

    results["one_n_actor_calls_async"] = timeit(one_n_async, 4000)

    targets = [Actor.remote() for _ in range(4)]
    ray.get([t.m.remote() for t in targets])

    def n_n_async(n):
        per = n // len(clients)
        ray.get([c.call_actor.remote(t, per)
                 for c, t in zip(clients, targets)])

    results["n_n_actor_calls_async"], raw_samples[
        "n_n_actor_calls_async"] = timeit_best_of(n_n_async, 4000, 400)

    # get calls on shm-resident objects: fresh refs each round so the
    # runtime's value cache cannot short-circuit deserialization; the puts
    # happen OUTSIDE the timed region (baseline measures gets only).
    small = np.zeros(1310720, dtype=np.uint8)  # ~1.3MB > inline cutoff
    warm = [ray.put(small) for _ in range(50)]
    for r in warm:
        ray.get(r)
    del warm
    refs = [ray.put(small) for _ in range(500)]
    t0 = time.perf_counter()
    for r in refs:
        ray.get(r)
    results["single_client_get_calls"] = 500 / (time.perf_counter() - t0)
    del refs

    arr = np.zeros(1024 * 1024 * 100, dtype=np.uint8)  # 100 MB

    def put_gb(n):
        for _ in range(n):
            ray.put(arr)

    # Best-of-3 with raw per-round samples (like the contended fan-in
    # rows): the put rows are memory-bandwidth-bound and swing with
    # shared-host load, so drift must be diagnosable from the artifact.
    gb = len(arr) / 1e9
    best, samples = timeit_best_of(put_gb, 20, 3)
    results["single_client_put_gigabytes"] = best * gb
    raw_samples["single_client_put_gigabytes"] = [
        round(s * gb, 3) for s in samples]

    def multi_put_gb(n):
        reps = n // len(clients)
        ray.get([c.put_bytes.remote(len(arr), reps) for c in clients])

    best, samples = timeit_best_of(multi_put_gb, 12, 4)
    results["multi_client_put_gigabytes"] = best * gb
    raw_samples["multi_client_put_gigabytes"] = [
        round(s * gb, 3) for s in samples]

    def wait_1k(n):
        for _ in range(n):
            refs = [f.remote() for _ in range(1000)]
            ray.wait(refs, num_returns=1000, timeout=60)

    results["single_client_wait_1k_refs"] = timeit(wait_1k, 8, 1)

    # Baseline semantics (ray_perf.py): a task builds the container once
    # outside the timed region; the metric is gets/s of an object whose
    # payload is 10k ObjectRefs (deserialize + register + drop 10k refs
    # per get).  Distinct worker-created containers per iteration so the
    # driver's value cache can't short-circuit deserialization.
    @ray.remote
    def make_box():
        import ray_tpu as ray
        return [ray.put(b"x") for _ in range(10000)]

    K = 6
    boxes = [make_box.remote() for _ in range(K)]
    got = ray.get(boxes[0])  # warm
    assert len(got) == 10000
    del got
    t0 = time.perf_counter()
    for box in boxes[1:]:
        got = ray.get(box)
        assert len(got) == 10000
        del got
    results["single_client_get_object_containing_10k_refs"] = (
        (K - 1) / (time.perf_counter() - t0))
    del boxes

    results.update(_client_bench())
    ray.shutdown()
    return results, raw_samples


_CLIENT_SCRIPT = r"""
import json, os, sys, time
import numpy as np
import ray_tpu as ray

ray.init(address=os.environ["RT_ADDR"], _authkey=os.environ["RT_KEY"])


@ray.remote
class CA:
    def m(self):
        return None


def timeit(fn, n, warm):
    fn(warm)
    t0 = time.perf_counter()
    fn(n)
    return n / (time.perf_counter() - t0)


out = {}
a = CA.remote()
ray.get(a.m.remote())
out["client_one_one_actor_calls_sync"] = timeit(
    lambda n: [ray.get(a.m.remote()) for _ in range(n)], 500, 50)
small = np.ones(1024, np.uint8)
out["client_put_calls"] = timeit(
    lambda n: [ray.put(small) for _ in range(n)], 1000, 100)
refs = [ray.put(small) for _ in range(500)]
t0 = time.perf_counter()
for r in refs:
    ray.get(r)
out["client_get_calls"] = 500 / (time.perf_counter() - t0)
big = np.ones(100 << 20, np.uint8)
gb = big.nbytes / 1e9
out["client_put_gigabytes"] = timeit(
    lambda n: [ray.put(big) for _ in range(n)], 8, 2) * gb
print("RESULT " + json.dumps(out))
"""


def _client_bench():
    """Ray-Client rows: a SUBPROCESS attaches in client mode and runs
    the reference's client__* loops (ray_perf.py client section)."""
    import os
    import subprocess
    import sys as _sys

    from ray_tpu._private import api_internal

    rt = api_internal.get_runtime()
    env = dict(os.environ,
               RT_ADDR=rt.tcp_address, RT_KEY=rt._authkey.hex(),
               JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    try:
        out = subprocess.run([_sys.executable, "-c", _CLIENT_SCRIPT],
                             capture_output=True, text=True, timeout=300,
                             env=env)
    except subprocess.TimeoutExpired:
        # A wedged client must not discard the core results already
        # collected.
        print("  client bench timed out; skipping client rows",
              file=sys.stderr)
        return {}
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    print(f"  client bench failed: {out.stderr[-500:]}", file=sys.stderr)
    return {}


def locality_bench():
    """Arg-locality microbench: a fan-out of tasks over one node-homed
    large arg — reports tasks/s and off_home_arg_bytes, the per-task
    upper bound on cross-node arg traffic (tasks that ran away from the
    arg's home node x arg size; singleflight dedup means actual wire
    bytes can be lower), so regressions stay visible in the round
    trajectory."""
    import os

    import numpy as np

    import ray_tpu as ray
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy as NA,
    )

    arg_bytes = 8 << 20
    n_tasks = 64

    @ray.remote
    def make(n):
        return np.ones(n, np.uint8)

    @ray.remote
    def crunch(a):
        return os.environ["RAY_TPU_NODE_ID"]

    def run():
        c = Cluster(head_num_cpus=4)
        try:
            home = c.add_node(num_cpus=4, external=True)
            ref = make.options(scheduling_strategy=NA(home)).remote(
                arg_bytes)
            ray.wait([ref], num_returns=1, timeout=60)
            ray.get([crunch.remote(ref) for _ in range(4)], timeout=120)
            t0 = time.perf_counter()
            nodes = ray.get([crunch.remote(ref) for _ in range(n_tasks)],
                            timeout=300)
            dt = time.perf_counter() - t0
            # Worker prefetch/dedup deltas arrive on the periodic
            # flusher: wait for the counters to settle before recording.
            stats = c.rt.transfer_stats()
            deadline = time.perf_counter() + 3.0
            while time.perf_counter() < deadline:
                time.sleep(0.3)
                nxt = c.rt.transfer_stats()
                if nxt == stats:
                    break
                stats = nxt
            return {
                "tasks_per_s": round(n_tasks / dt, 1),
                "off_home_arg_bytes":
                    sum(1 for nd in nodes if nd != home) * arg_bytes,
                "on_home_node": nodes.count(home),
                "locality_hits": stats["locality_hits"],
                "locality_misses": stats["locality_misses"],
                "locality_bytes_saved": stats["locality_bytes_saved"],
                "prefetch_hit_bytes": stats["prefetch_hit_bytes"],
                "deduped_pulls": stats["deduped_pulls"],
            }
        finally:
            c.shutdown()

    out = {"arg_mb": arg_bytes >> 20, "n_tasks": n_tasks,
           "locality_on": run()}
    print(f"  [locality] {out['locality_on']['tasks_per_s']}/s, "
          f"{out['locality_on']['off_home_arg_bytes'] >> 20} MB off-home",
          file=sys.stderr)
    return out


def data_streaming_bench():
    """ray_tpu.data streaming-engine row: a fixed 3-stage paced pipeline
    (fused chain, 2 MB output blocks) run with the operator-graph
    executor on vs the legacy windowed path — rows/s and the engine's
    peak in-flight bytes, so the backpressured engine's admission win
    (bytes-budgeted, cluster-wide — vs the legacy 8-chain count window)
    and any regression stay visible in the round trajectory.  Stages are
    paced with sleeps at num_cpus=0 so the comparison measures engine
    structure, not host load."""
    import numpy as np

    import ray_tpu as ray
    from ray_tpu import data as rd

    n_blocks, rows_per_block = 32, 64
    blk = 2 << 20

    def build():
        def inflate(b):
            time.sleep(0.04)
            return {"x": np.zeros(blk // 8, np.float64)}

        def scale(b):
            time.sleep(0.02)
            return {"x": b["x"] + 1.0}

        def mark(b):
            time.sleep(0.02)
            return {"x": -b["x"]}

        return (rd.from_items(list(range(n_blocks * rows_per_block)),
                              parallelism=n_blocks)
                .map_batches(inflate, num_cpus=0)
                .map_batches(scale, num_cpus=0)
                .map_batches(mark, num_cpus=0))

    def run(streaming):
        sc = None if streaming else {"streaming_executor": False}
        ray.init(num_cpus=16, _system_config=sc)
        def consume(ds):
            # Consumption path (iter_batches, zero-copy whole blocks):
            # this is where the legacy path's 8-chain window binds
            # (materialize() opens the legacy window fully and would
            # measure nothing).
            for _ in ds.iter_batches(batch_size=None):
                pass

        try:
            consume(build())        # warm the worker pool
            t0 = time.perf_counter()
            ds = build()
            consume(ds)
            dt = time.perf_counter() - t0
            s = ds._stats.streaming_summary()
            return {
                "rows_per_s": round(n_blocks * rows_per_block / dt, 1),
                "wall_s": round(dt, 3),
                "peak_inflight_bytes": s["peak_inflight_bytes"],
                "admitted_tasks": s["admitted_tasks"],
                "backpressure_stalls": s["backpressure_stalls"],
            }
        finally:
            ray.shutdown()

    out = {"n_blocks": n_blocks, "block_mb": blk >> 20,
           "streaming_on": run(True), "streaming_off": run(False)}
    print(f"  [data_streaming] on: {out['streaming_on']['rows_per_s']} "
          f"rows/s, peak "
          f"{out['streaming_on']['peak_inflight_bytes'] >> 20} MB "
          f"in-flight; off: {out['streaming_off']['rows_per_s']} rows/s",
          file=sys.stderr)
    return out


def serve_paged_bench():
    """Serving memory-plane rows (in-process, sleep-paced so the A/B
    measures engine structure): (a) skewed-length paged-vs-dense at
    EQUAL simulated HBM — dense gets hbm/max_seq_len slots, paged gets
    hbm/block_size blocks, so the ratio is pure block-granular packing;
    (b) prefix-cache variant — 12 clients sharing a 512-token system
    prompt, cached vs uncached, decoded chains bitwise-compared;
    (c) speculative decoding — draft k=4 vs greedy, exact-match
    acceptance, chains bitwise-compared.  Best-of-3 with raw samples."""
    import threading

    from ray_tpu.serve.continuous import _ContinuousBatcher
    from ray_tpu.serve.kv_cache import PagedKVEngine
    from ray_tpu.serve.tpu_replica import MeshShardedDecoder

    def drive(b, reqs, timeout=120):
        results, lats = {}, {}

        def client(i, r):
            t0 = time.perf_counter()
            results[i] = b.submit(r)
            lats[i] = time.perf_counter() - t0

        threads = [threading.Thread(target=client, args=(i, r))
                   for i, r in enumerate(reqs)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout)
        wall = time.perf_counter() - t0
        assert len(results) == len(reqs), "paged bench request failed"
        return results, lats, wall

    out = {}

    # -- (a) skewed-length paged-vs-dense at equal HBM ---------------------
    step_s, hbm_tokens, max_seq, bs = 0.004, 1024, 128, 8
    reqs = [{"tokens": max_seq if i % 16 == 0 else 16} for i in range(96)]

    def paced(slots):
        time.sleep(step_s)
        for s in slots:
            s.state = (s.state or 0) + 1
            if s.state >= s.request["tokens"]:
                s.finish(s.state)

    def ab_run(paged):
        best, samples = None, []
        for _ in range(3):
            kv = PagedKVEngine(
                hbm_tokens // bs, bs, prefix_caching=False, max_slots=64,
                tokens_for=lambda r: ((), r["tokens"])) if paged else None
            b = _ContinuousBatcher(paced, None, hbm_tokens // max_seq,
                                   0.0, continuous=True, kv=kv)
            _, lats, wall = drive(b, reqs)
            flat = sorted(lats.values())
            row = {
                "req_s": round(len(reqs) / wall, 1),
                "p50_ms": round(flat[len(flat) // 2] * 1e3, 2),
                "p99_ms": round(flat[min(len(flat) - 1,
                                         int(len(flat) * 0.99))] * 1e3,
                                2),
                "batch_occupancy": b.stats()["batch_occupancy"],
            }
            samples.append(row)
            if best is None or row["req_s"] > best["req_s"]:
                best = row
        return {**best, "samples": samples}

    dense, paged = ab_run(False), ab_run(True)
    out["paged_ab"] = {
        "hbm_tokens": hbm_tokens, "max_seq_len": max_seq,
        "block_size": bs, "dense": dense, "paged": paged,
        "speedup_req_s": round(paged["req_s"] / max(dense["req_s"],
                                                    1e-9), 2),
    }
    print(f"  [serve-paged] A/B at {hbm_tokens}-token HBM: paged "
          f"{paged['req_s']} req/s (occ {paged['batch_occupancy']}) vs "
          f"dense {dense['req_s']} req/s (occ "
          f"{dense['batch_occupancy']}) — "
          f"{out['paged_ab']['speedup_req_s']}x", file=sys.stderr)

    # -- (b) prefix-cache variant: shared 512-token system prompt ----------
    sys_prompt = [i % 64 for i in range(512)]
    preqs = [{"prompt": sys_prompt + [i], "tokens": 4 + i % 5}
             for i in range(12)]

    def decode_run(prefix_on):
        best, samples, outs = None, [], None
        for _ in range(3):
            dec = MeshShardedDecoder(paged=True, kv_blocks=128,
                                     kv_block_size=16, max_slots=16,
                                     prefix_caching=prefix_on,
                                     speculative_k=0)
            b = _ContinuousBatcher(dec._paged_step, None, 8, 0.0,
                                   continuous=True, kv=dec.serve_kv_engine)
            results, _, wall = drive(b, preqs)
            s = b.stats()
            row = {"req_s": round(len(preqs) / wall, 1),
                   "prefix_hits": s["prefix_hits"],
                   "prefix_blocks_shared": s["prefix_blocks_shared"],
                   "cow_copies": s["cow_copies"],
                   "admission_parks": s["admission_parks"]}
            samples.append(row)
            if best is None or row["req_s"] > best["req_s"]:
                best = row
            outs = results  # identical across rounds (greedy, pinned)
        return {**best, "samples": samples}, outs

    cached, cached_outs = decode_run(True)
    uncached, uncached_outs = decode_run(False)
    ref = MeshShardedDecoder()
    out["prefix_cache"] = {
        "prompt_tokens": len(sys_prompt), "clients": len(preqs),
        "cached": cached, "uncached": uncached,
        "bitwise_identical": cached_outs == uncached_outs == {
            i: ref.reference_decode(r["prompt"], r["tokens"])
            for i, r in enumerate(preqs)},
        "speedup_req_s": round(cached["req_s"]
                               / max(uncached["req_s"], 1e-9), 2),
    }
    print(f"  [serve-paged] prefix cache (512-token shared prompt): "
          f"{cached['req_s']} req/s, {cached['prefix_hits']} hits, "
          f"{cached['prefix_blocks_shared']} blocks shared vs uncached "
          f"{uncached['req_s']} req/s "
          f"({out['prefix_cache']['speedup_req_s']}x, bitwise="
          f"{out['prefix_cache']['bitwise_identical']})", file=sys.stderr)

    # -- (c) speculative decoding ------------------------------------------
    sreqs = [{"prompt": [i], "tokens": 8 + i % 8} for i in range(12)]

    def spec_run(k):
        dec = MeshShardedDecoder(paged=True, kv_blocks=64,
                                 kv_block_size=8, speculative_k=k)
        b = _ContinuousBatcher(dec._paged_step, None, 8, 0.0,
                               continuous=True, kv=dec.serve_kv_engine)
        results, _, wall = drive(b, sreqs)
        s = b.stats()
        return results, {"req_s": round(len(sreqs) / wall, 1),
                         "steps": s["steps"],
                         "tokens_per_step": s["tokens_per_step"],
                         "spec_proposed": s["spec_proposed"],
                         "spec_accepted": s["spec_accepted"]}

    greedy_outs, greedy = spec_run(0)
    spec_outs, spec = spec_run(4)
    out["speculative"] = {
        "k": 4, "greedy": greedy, "spec": spec,
        "accept_rate": round(spec["spec_accepted"]
                             / max(spec["spec_proposed"], 1), 3),
        "bitwise_identical": spec_outs == greedy_outs == {
            i: ref.reference_decode(r["prompt"], r["tokens"])
            for i, r in enumerate(sreqs)},
    }
    print(f"  [serve-paged] speculative k=4: "
          f"{spec['tokens_per_step']} tokens/step "
          f"(greedy {greedy['tokens_per_step']}), accept rate "
          f"{out['speculative']['accept_rate']}, bitwise="
          f"{out['speculative']['bitwise_identical']}", file=sys.stderr)
    return out


def serve_latency_bench():
    """Serving hot-path row: p50/p99 latency and req/s under N
    concurrent clients driving a paced continuous-batching decode
    deployment THROUGH the RequestProxy tier (client actor → proxy →
    replica step loop), continuous batching on vs off at equal
    max_batch_size — best-of-3 with the raw per-round samples kept in
    the round JSON, plus the steady-state head_brokered_submits delta
    (the proxy-tier observable: 0 — request traffic rides the direct
    actor channels).  Steps are sleep-paced so the A/B measures engine
    structure, not host load."""
    import ray_tpu as ray
    from ray_tpu import serve

    n_clients, reqs_per_client = 8, 12
    step_s = 0.004

    def run(continuous):
        sc = None if continuous else {"continuous_batching": False}
        rt = ray.init(num_cpus=16, _system_config=sc)
        try:
            @serve.deployment(num_replicas=1, max_concurrency=32)
            class Decode:
                @serve.batch(mode="continuous", max_batch_size=8,
                             batch_wait_timeout_s=0.05)
                def step(self, slots):
                    time.sleep(step_s)
                    for s in slots:
                        if s.state is None:
                            s.state = {"n": 0,
                                       "need": s.request["tokens"]}
                        s.state["n"] += 1
                        if s.state["n"] >= s.state["need"]:
                            s.finish(s.state["n"])

                def __call__(self, body):
                    return self.step(body)

            serve.start(proxy_location="Disabled", num_proxies=2)
            serve.run(Decode.bind(), name="decode")
            proxies = serve.api._state["request_proxies"]

            @ray.remote
            class Client:
                def run(self, proxies, n, depth=4):
                    """Pipelined client: up to `depth` requests in
                    flight (a sequential client's think-time RTT would
                    idle freed batch slots and measure the wire, not
                    the engine)."""
                    import time as _t

                    import ray_tpu as ray
                    lats = []
                    inflight = {}  # ref -> submit time
                    i = 0
                    while i < n or inflight:
                        while i < n and len(inflight) < depth:
                            body = {"tokens": 24 if i % 4 == 0 else 2}
                            ref = proxies[i % len(proxies)] \
                                .handle_request.remote(
                                    "decode", (body,), None)
                            inflight[ref] = _t.perf_counter()
                            i += 1
                        done, _ = ray.wait(list(inflight),
                                           num_returns=1, timeout=120)
                        for r in done:
                            lats.append(
                                _t.perf_counter() - inflight.pop(r))
                            ray.get(r)
                    return lats

            clients = [Client.remote() for _ in range(n_clients)]
            ray.get([c.run.remote(proxies, 2) for c in clients],
                    timeout=300)  # warm actor channels + batcher
            time.sleep(1.0)
            before = rt.transfer_stats()["head_brokered_submits"]
            best = None
            samples = []
            for _ in range(3):
                t0 = time.perf_counter()
                lats = ray.get(
                    [c.run.remote(proxies, reqs_per_client)
                     for c in clients], timeout=600)
                dt = time.perf_counter() - t0
                flat = sorted(x for ls in lats for x in ls)
                total = n_clients * reqs_per_client
                row = {
                    "req_s": round(total / dt, 1),
                    "p50_ms": round(flat[len(flat) // 2] * 1e3, 2),
                    "p99_ms": round(
                        flat[min(len(flat) - 1,
                                 int(len(flat) * 0.99))] * 1e3, 2),
                }
                samples.append(row)
                if best is None or row["req_s"] > best["req_s"]:
                    best = row
            delta = rt.transfer_stats()["head_brokered_submits"] - before
            stats = serve.serving_stats("decode")
            return {**best, "samples": samples,
                    "head_brokered_delta": delta,
                    "batch_occupancy": stats.get("batch_occupancy"),
                    "steps": stats.get("steps"),
                    "mode": stats.get("mode")}
        finally:
            serve.shutdown()
            ray.shutdown()

    out = {"n_clients": n_clients, "reqs_per_client": reqs_per_client,
           "step_ms": step_s * 1e3,
           "continuous_on": run(True), "continuous_off": run(False)}
    on, off = out["continuous_on"], out["continuous_off"]
    out["speedup_req_s"] = round(on["req_s"] / max(off["req_s"], 1e-9), 2)
    print(f"  [serve] continuous: {on['req_s']} req/s, p50 "
          f"{on['p50_ms']}ms, p99 {on['p99_ms']}ms; one-shot: "
          f"{off['req_s']} req/s ({out['speedup_req_s']}x); "
          f"head_brokered_delta={on['head_brokered_delta']}",
          file=sys.stderr)
    # Serving memory plane (paged KV / prefix cache / speculative): its
    # failure must not discard the base serve row.
    try:
        out["paged"] = serve_paged_bench()
    except Exception as e:  # noqa: BLE001 — sub-row must not kill the row
        print(f"  [serve-paged] bench failed: {e!r}", file=sys.stderr)
        out["paged"] = {"error": repr(e)}
    return out


def disagg_serving_bench():
    """Disaggregated prefill/decode row: p50 time-to-first-token and
    req/s under mixed traffic — long-prompt "doc" requests (112-token
    prompts drawn from 15 prefix families) interleaved with
    short-decode "chat" requests — disaggregated (3 prefill + 2
    decode replicas, KV chains streamed over the striped put path) vs
    the monolithic engine (5 identical replicas) at equal replica
    count, best-of-3 with raw per-round samples.  The mechanism under
    test is cache partitioning: 15 families x 14 blocks each cannot
    fit in ONE 96-block replica pool (~6.9 families), so monolithic
    p2c — which spreads every family across all five replicas — holds
    a sub-half hit rate STRUCTURALLY and pays the full 896 ms
    re-prefill on most docs, while prefix-affinity routing pins 5
    families to each prefill home (70 of 96 blocks) where they all
    fit and steady-state doc prefills are tail-only (the request
    tails are unique per round, so rounds measure the shared-prefix
    mechanism, not whole-prompt replay).  A third leg re-runs
    disaggregated mode with prefix_affinity off (pure p2c = the
    random-routing baseline) and compares the summed engine
    prefix-cache hits.  Prefill pacing (8 ms/token synthetic stall,
    one sleep per engine step) makes prefill cost dominate the
    millisecond-scale host noise, as in the other serve rows."""
    import ray_tpu as ray
    from ray_tpu import serve

    prefill_ms = 8.0
    doc_len, doc_tail, doc_tokens = 96, 16, 2
    chat_pre, chat_tail, chat_tokens = 32, 4, 8
    kv_blocks, kv_block = 96, 8
    doc_gap_s, chat_gap_s = 0.17, 0.21
    n_docs, n_chats = 30, 24
    n_chat_families, n_doc_families = 2, 15

    def doc_prompt(i):
        fam = i % n_doc_families
        return ([(7 + fam * 5 + j) % 64 for j in range(doc_len)]
                + [(i * 13 + j) % 64 for j in range(doc_tail)])

    def chat_prompt(i):
        fam = i % n_chat_families
        return ([(31 + fam * 11 + j) % 64 for j in range(chat_pre)]
                + [(i * 17 + j) % 64 for j in range(chat_tail)])

    def run(disagg, affinity):
        from ray_tpu.serve.tpu_replica import MeshShardedDecoder

        sc = {"paged_kv": True, "disaggregated_serving": disagg,
              "prefix_affinity": affinity}
        rt = ray.init(num_cpus=16, _system_config=sc)
        try:
            dep = serve.deployment(
                MeshShardedDecoder, name="mix", max_concurrency=48,
                num_replicas=(2 if disagg else 5),
                prefill_replicas=(3 if disagg else 0))
            handle = serve.run(
                dep.bind(kv_blocks=kv_blocks, kv_block_size=kv_block,
                         max_slots=16, use_kernel=False,
                         speculative_k=3,
                         prefill_ms_per_token=prefill_ms),
                name="mix")
            # The twin's replicas spawn asynchronously; pinning a
            # family while a pool is below strength parks every home
            # on one replica, so wait for full strength first.
            deadline = time.perf_counter() + 30
            while time.perf_counter() < deadline:
                with handle._lock:
                    n_dec = len(handle._replicas)
                    n_pre = len(handle._prefill_replicas)
                if n_dec >= (2 if disagg else 5) and \
                        (not disagg or n_pre >= 3):
                    break
                time.sleep(0.05)
            # Warmup pins each doc family to a prefill home (p2c
            # steers successive long prefills apart), then a parallel
            # pass warms the compile caches on the pinned paths.
            for f in range(n_doc_families):
                ray.get(handle.remote({"prompt": doc_prompt(f),
                                       "tokens": doc_tokens}),
                        timeout=120)
            for f in range(n_chat_families):
                ray.get(handle.remote({"prompt": chat_prompt(f),
                                       "tokens": chat_tokens}),
                        timeout=120)
            warm = [handle.remote(
                {"prompt": doc_prompt(n_doc_families + f),
                 "tokens": doc_tokens}) for f in range(n_doc_families)]
            warm += [handle.remote(
                {"prompt": chat_prompt(n_chat_families + f),
                 "tokens": chat_tokens})
                for f in range(2 * n_chat_families)]
            ray.get(warm, timeout=120)

            def one_round(r):
                events = []
                for i in range(n_docs):
                    events.append((i * doc_gap_s, {
                        "prompt": doc_prompt(100 + r * n_docs + i),
                        "tokens": doc_tokens}, False))
                for i in range(n_chats):
                    events.append((i * chat_gap_s, {
                        "prompt": chat_prompt(100 + r * n_chats + i),
                        "tokens": chat_tokens}, True))
                events.sort(key=lambda e: e[0])
                before = rt.transfer_stats()["head_brokered_submits"]
                inflight = {}
                ttfts = {"doc": [], "chat": []}
                t0 = time.perf_counter()
                k = 0
                # Open-loop driver: requests go out on the offered
                # schedule whether or not the engine keeps up, so a
                # saturated engine shows queue growth in TTFT instead
                # of silently shedding load.
                while k < len(events) or inflight:
                    now = time.perf_counter() - t0
                    while k < len(events) and events[k][0] <= now:
                        _, body, chat = events[k]
                        k += 1
                        body = dict(body)
                        body["_timing"] = True
                        body["_t0"] = time.time()
                        inflight[handle.remote(body)] = chat
                    if not inflight:
                        time.sleep(0.001)
                        continue
                    done, _ = ray.wait(list(inflight), num_returns=1,
                                       timeout=0.002)
                    for r in done:
                        chat = inflight.pop(r)
                        out = ray.get(r)
                        ttfts["chat" if chat else "doc"].append(
                            out["ttft"])
                wall = time.perf_counter() - t0
                delta = rt.transfer_stats()["head_brokered_submits"] \
                    - before

                def pct(vals, q):
                    vals = sorted(vals)
                    return round(
                        vals[min(len(vals) - 1,
                                 int(len(vals) * q))] * 1e3, 2)

                both = ttfts["doc"] + ttfts["chat"]
                return {
                    "p50_ttft_ms": pct(both, 0.5),
                    "p90_ttft_ms": pct(both, 0.9),
                    "doc_p50_ttft_ms": pct(ttfts["doc"], 0.5),
                    "chat_p50_ttft_ms": pct(ttfts["chat"], 0.5),
                    "req_s": round((n_docs + n_chats) / wall, 1),
                    "wall_s": round(wall, 2),
                    "head_brokered_delta": delta,
                }

            samples = [one_round(r) for r in range(3)]
            best = min(samples, key=lambda s: s["p50_ttft_ms"])
            stats = serve.serving_stats("mix")
            return {**best, "samples": samples,
                    "prefix_hits": stats.get("prefix_hits"),
                    "kv_chains_exported": stats.get(
                        "kv_chains_exported"),
                    "kv_chain_bytes_streamed": stats.get(
                        "kv_chain_bytes_streamed"),
                    "router": handle.router_stats()}
        finally:
            serve.shutdown()
            ray.shutdown()

    out = {
        "workload": {
            "prefill_ms_per_token": prefill_ms,
            "doc_prompt_len": doc_len + doc_tail,
            "chat_prompt_len": chat_pre + chat_tail,
            "doc_families": n_doc_families,
            "offered_req_s": round(
                1.0 / doc_gap_s + 1.0 / chat_gap_s, 1),
        },
        "disagg": run(True, True),
        "mono": run(False, True),
        "random_routing": run(True, False),
    }
    d, m, r = out["disagg"], out["mono"], out["random_routing"]
    out["ttft_p50_speedup"] = round(
        m["p50_ttft_ms"] / max(d["p50_ttft_ms"], 1e-9), 2)
    out["req_s_ratio"] = round(d["req_s"] / max(m["req_s"], 1e-9), 2)
    out["affinity_vs_random_prefix_hits"] = {
        "affinity": d["prefix_hits"], "random": r["prefix_hits"]}
    print(f"  [disagg_serving] disagg: p50 ttft {d['p50_ttft_ms']}ms, "
          f"{d['req_s']} req/s; mono: {m['p50_ttft_ms']}ms, "
          f"{m['req_s']} req/s ({out['ttft_p50_speedup']}x ttft, "
          f"{out['req_s_ratio']}x req/s); prefix_hits affinity="
          f"{d['prefix_hits']} random={r['prefix_hits']}; "
          f"chain_bytes={d['kv_chain_bytes_streamed']}, "
          f"head_brokered_delta={d['head_brokered_delta']}",
          file=sys.stderr)
    return out


def recovery_bench():
    """Fault-tolerance row: a 32-task fan-out (2 MB results pinned to an
    external node) suffers a mid-run worker kill (tasks retry) and then
    loses the node itself before the results are consumed.  Reports
    completion wall-clock, whether every get returned the correct
    value, and the reconstruction counter; best-of-3 with raw samples
    in the round JSON (PR 6-8 convention)."""
    import numpy as np

    import ray_tpu as ray
    from ray_tpu.chaos import ChaosController
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy as NA,
    )

    n_tasks = 32

    @ray.remote(max_retries=3)
    def make(i):
        time.sleep(0.02)
        return np.full(260_000, i, dtype=np.int64)

    @ray.remote
    def check(a):
        return int(a[0])

    def one_round():
        c = Cluster(head_num_cpus=4)
        chaos = None
        try:
            node = c.add_node(num_cpus=4, external=True)
            chaos = ChaosController(c.rt)
            t0 = time.perf_counter()
            s1 = [make.options(scheduling_strategy=NA(
                node_id=node, soft=True)).remote(i)
                for i in range(n_tasks)]
            time.sleep(0.15)
            chaos.kill_worker(mid_task=True)  # retries absorb this
            ray.wait(s1, num_returns=len(s1), timeout=120)
            chaos.kill_agent(node)  # results lost before consumption
            ok = True
            try:
                vals = ray.get([check.remote(r) for r in s1],
                               timeout=120)
                ok = vals == list(range(n_tasks))
            except ray.exceptions.RayTpuError:
                ok = False
            dt = time.perf_counter() - t0
            stats = c.rt.transfer_stats()
            return {"wall_s": round(dt, 2), "completed": ok,
                    "reconstructions": stats["reconstructions"],
                    "chaos_kills": stats["chaos_kills"]}
        finally:
            if chaos is not None:
                chaos.stop()
            c.shutdown()

    samples = [one_round() for _ in range(3)]
    on = min(samples, key=lambda s: (not s["completed"], s["wall_s"]))
    out = {"n_tasks": n_tasks, "recovery_on": {**on, "samples": samples}}
    print(f"  [recovery] {on['wall_s']}s, completed={on['completed']},"
          f" reconstructions={on['reconstructions']}", file=sys.stderr)
    return out


def shuffle_bench(rounds=3):
    """Push-shuffle row: an all-to-all sort + groupby with the PULL-
    SERVE PLANE paced (env net-chaos ``delay`` on every agent
    data-chunk send, one claim dir per node so every node's object
    server is paced, ``object_pool_size=1`` so transfers per peer pair
    serialize like a real bandwidth-limited link), push engine on vs
    off on identical data.  The paced resource is the per-node serve
    path that the legacy engine routes EVERY partition byte through at
    the reduce barrier; the push engine's whole thesis is that map-side
    ``put_range`` writes partition bytes straight into the consumer
    store and never queues behind that plane (its input-block reads
    still pay the same paced pulls, so the comparison shares the slow
    plane for everything except the contested partition hop).  Pacing
    also makes the A/B load-independent on a 2-vCPU host: walls are
    dominated by deterministic injected sleeps, not scheduler noise.
    ``max_inline_object_size`` is lowered so the legacy engine's
    partitions (~320 KB at R=16) are node-store homed and actually
    traverse the data plane rather than riding head messages.

    ``gbps`` = dataset bytes / wall to full consumption; ``completed``
    pins exact row counts.  Both modes must keep the head control
    plane flat — ``head_brokered_submits`` and ``brokered_put_parts``
    per-run DELTAS zero (no partition payload or spec ever rides a
    head message).  Best-of-``rounds`` per mode with raw samples
    (PR 6/7 convention), plus a chaos variant: kill one producer node
    AND gray-stall another's head link mid-shuffle — lineage rebuild +
    reducer hedging must still land the exact sorted output."""
    import pickle
    import tempfile

    import numpy as np

    import ray_tpu as ray
    from ray_tpu import data as rd
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.data.dataset import Dataset
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy as NA,
    )

    n_blocks = 16
    rows_per_block = 600
    n_groups = 7
    delay_ms = 240
    part_target = 20_000_000  # R=4 on ~80 MB: push partitions ~1.25 MB,
    # R decoupled from the 16-block count (legacy is locked to R=16).

    def _mk_rows(i):
        rng = np.random.default_rng(77 + i)
        return [{"k": float(v), "g": j % n_groups, "v": j,
                 "p": bytes(8192)}
                for j, v in enumerate(rng.random(rows_per_block))]

    @ray.remote(max_retries=3)
    def mk_block(i):
        return _mk_rows(i)

    block_bytes = len(pickle.dumps(_mk_rows(0), protocol=5))
    total_bytes = block_bytes * n_blocks
    total_rows = rows_per_block * n_blocks

    pace = f"agent:chunk_send:delay-{delay_ms}:1"

    def one_round(push_on):
        cfg = {"push_shuffle": push_on,
               "shuffle_partition_bytes_target": part_target,
               "max_inline_object_size": 65536,
               "object_pool_size": 1}
        c = Cluster(head_num_cpus=0, _system_config=cfg)
        try:
            nodes = [c.add_node(
                num_cpus=2, external=True,
                env_overrides={
                    "RAY_TPU_CHAOS_NET": pace,
                    # A claim dir PER NODE: the one-shot claim-file
                    # convention then arms the delay once per node —
                    # every node's serve plane paced.
                    "RAY_TPU_CHAOS_DIR": tempfile.mkdtemp(),
                }) for _ in range(2)]
            blocks = [mk_block.options(scheduling_strategy=NA(
                node_id=nodes[i % 2], soft=True)).remote(i)
                for i in range(n_blocks)]
            ray.wait(blocks, num_returns=len(blocks), timeout=60)

            def timed(build, expect_rows):
                st0 = c.rt.transfer_stats()
                t0 = time.perf_counter()
                n = build(Dataset(blocks)).count()
                dt = time.perf_counter() - t0
                st1 = c.rt.transfer_stats()

                def delta(k):
                    return st1.get(k, 0) - st0.get(k, 0)

                return {"wall_s": round(dt, 2),
                        "gbps": round(total_bytes / 1e9 / dt, 4),
                        "completed": n == expect_rows,
                        "head_brokered_submits":
                            delta("head_brokered_submits"),
                        "brokered_put_parts": delta("brokered_put_parts"),
                        "shuffle_pushed_bytes":
                            delta("shuffle_pushed_bytes"),
                        "shuffle_hedges": delta("shuffle_hedges")}

            sort_row = timed(lambda ds: ds.sort(key="k"), total_rows)
            grp_row = timed(
                lambda ds: ds.groupby("g").aggregate(
                    rd.Sum("v"), rd.Count()), n_groups)
            return sort_row, grp_row
        finally:
            c.shutdown()

    def best_of(push_on):
        pairs = [one_round(push_on) for _ in range(rounds)]

        def pick(samples):
            best = min(samples,
                       key=lambda s: (not s["completed"], -s["gbps"]))
            return {**best, "samples": samples}

        return (pick([p[0] for p in pairs]),
                pick([p[1] for p in pairs]))

    def chaos_round():
        """The drill as a bench row: unpaced 3-node cluster, input
        blocks homed on the doomed nodes, kill + gray-stall the moment
        the map wave is submitted."""
        from ray_tpu.chaos import ChaosController

        fd = {"net_stall_timeout_s": 0.8, "net_connect_timeout_s": 2.0,
              "net_retry_count": 1, "net_retry_backoff_base_ms": 20.0,
              "health_check_period_s": 0.25,
              "health_check_timeout_s": 1.0,
              "health_check_failure_threshold": 2,
              "health_check_initial_delay_s": 1.0}
        c = Cluster(head_num_cpus=2, _system_config=fd)
        chaos = None
        try:
            n1 = c.add_node(num_cpus=2, external=True)
            n2 = c.add_node(num_cpus=2, external=True)
            n3 = c.add_node(num_cpus=2, external=True)
            chaos = ChaosController(c.rt)
            homes = [n1, n2, n1, n3]
            blocks = [mk_block.options(scheduling_strategy=NA(
                node_id=homes[i % len(homes)], soft=True)).remote(i)
                for i in range(n_blocks)]
            ray.wait(blocks, num_returns=len(blocks), timeout=60)

            def wreck():
                chaos.kill_agent(n1)
                chaos.stall_link(n2)

            chaos.at_syncpoint("shuffle:maps_submitted", wreck, n=1)
            t0 = time.perf_counter()
            n = Dataset(blocks).sort(key="k").count()
            dt = time.perf_counter() - t0
            st = c.rt.transfer_stats()
            return {"wall_s": round(dt, 2), "completed": n == total_rows,
                    "reconstructions": st.get("reconstructions", 0),
                    "shuffle_hedges": st.get("shuffle_hedges", 0)}
        finally:
            if chaos is not None:
                chaos.stop()
            c.shutdown()

    sort_push, grp_push = best_of(True)
    sort_legacy, grp_legacy = best_of(False)
    try:
        chaos_row = chaos_round()
    except Exception as e:  # noqa: BLE001 — extra row must not kill A/B
        chaos_row = {"error": repr(e)}

    out = {"dataset_mb": round(total_bytes / 1e6, 2),
           "delay_ms": delay_ms, "rounds": rounds,
           "sort_push": sort_push, "sort_legacy": sort_legacy,
           "groupby_push": grp_push, "groupby_legacy": grp_legacy,
           "chaos": chaos_row}
    sp, sl = out["sort_push"], out["sort_legacy"]
    print(f"  [shuffle] sort push {sp['gbps']}GB/s vs legacy "
          f"{sl['gbps']}GB/s ({sp['gbps'] / max(sl['gbps'], 1e-9):.2f}x),"
          f" groupby {grp_push['gbps']}GB/s vs {grp_legacy['gbps']}GB/s;"
          f" chaos completed={chaos_row.get('completed')} "
          f"(reconstructions={chaos_row.get('reconstructions')}, "
          f"hedges={chaos_row.get('shuffle_hedges')})",
          file=sys.stderr)
    return out


def pipeline_train_bench(rounds=3):
    """Distributed pipeline-training row: a 2-stage llama-tiny actor
    pipeline on two paced external nodes (same env net-chaos pacing as
    the shuffle row: every data-plane chunk send — activation/grad
    stripe pushes included — pays a deterministic delay, so the A/B is
    load-independent and transfer cost is really on the wire).  The SAME
    trainer steps under both schedules, so weights, jit caches, and the
    paced link are identical: ``fill_drain`` drives synchronous per-
    stage wave barriers (the GPipe shape with every transfer on the
    critical path), ``1f1b`` the async one-forward-one-backward
    submission that overlaps microbatch t+1's transfer with t's compute
    across stages.  M = 2*pp microbatches (the 1F1B steady-state
    sweet spot).

    ``tok_s`` = batch tokens * steps / wall; ``bubble_fraction`` =
    1 - sum(stage busy_s deltas) / (pp * wall) — the measured idle
    share the schedule leaves on the stages.  Best-of-``rounds`` with
    raw samples (PR 6/7 convention), plus a chaos variant: SIGKILL a
    mid-pipeline stage mid-epoch — the epoch must complete from the
    stage's ``__ray_save__`` checkpoint with bounded replay
    (``stage_restarts`` >= 1) and zero ObjectLostError at the driver."""
    import tempfile

    import numpy as np

    import ray_tpu as ray
    from ray_tpu.cluster_utils import Cluster

    pp = 3
    M = 2 * pp
    batch, seq = 12, 16
    steps = 2
    delay_ms = 120
    # role "worker": the activation/grad stripe pushes run in the STAGE
    # ACTOR's process (`_send_piece_range`), not the node agent's serve
    # loop — pacing the agent (the shuffle row's choice) would leave
    # the push path free.
    pace = f"worker:chunk_send:delay-{delay_ms}:1"

    def build_trainer():
        import jax
        import optax

        from ray_tpu.models import llama as L
        from ray_tpu.train.pipeline_actors import PipelineTrainer

        cfg = L.LlamaConfig.tiny(num_layers=pp)  # one layer per stage
        params = L.init_params(jax.random.PRNGKey(0), cfg)
        tr = PipelineTrainer(
            L.make_pipeline_stage_fn(cfg), L.make_pipeline_loss_fn(cfg),
            L.pipeline_stage_params(params, pp),
            optimizer=optax.sgd(1e-2), num_microbatches=M,
            distributed=True)
        rng = np.random.default_rng(0)
        tok = rng.integers(0, cfg.vocab_size,
                           size=(batch, seq + 1)).astype(np.int32)
        return tr, tok[:, :-1], tok[:, 1:]

    def one_round():
        c = Cluster(head_num_cpus=0, _system_config={})
        try:
            # One CPU per node: the two stage actors are forced onto
            # DIFFERENT nodes, so every activation/grad hop crosses the
            # paced link.
            for _ in range(pp):
                c.add_node(num_cpus=1, external=True, env_overrides={
                    "RAY_TPU_CHAOS_NET": pace,
                    "RAY_TPU_CHAOS_DIR": tempfile.mkdtemp(),
                })
            tr, x, t = build_trainer()
            assert tr.distributed
            tr.step(x, t)  # warm the per-stage jit caches

            def timed(schedule):
                busy0 = sum(s["busy_s"] for s in tr.stage_stats())
                st0 = c.rt.transfer_stats()
                t0 = time.perf_counter()
                for _ in range(steps):
                    tr.step(x, t, schedule=schedule)
                dt = time.perf_counter() - t0
                busy1 = sum(s["busy_s"] for s in tr.stage_stats())
                st1 = c.rt.transfer_stats()
                time.sleep(1.2)  # the pushes counter flushes async
                st1 = c.rt.transfer_stats()
                return {
                    "wall_s": round(dt, 2),
                    "tok_s": round(batch * seq * steps / dt, 1),
                    "bubble_fraction": round(
                        1.0 - (busy1 - busy0) / (pp * dt), 3),
                    "microbatch_pushes": st1["microbatch_pushes"]
                    - st0["microbatch_pushes"],
                }

            fd = timed("fill_drain")
            ofb = timed("1f1b")
            tr.shutdown()
            return fd, ofb
        finally:
            c.shutdown()

    def chaos_round():
        """Mid-epoch SIGKILL of the last (loss) stage while a step's
        schedule is in flight; unpaced so the row stays quick."""
        import threading

        rt = ray.init(num_cpus=4, num_tpus=0)
        try:
            tr, x, t = build_trainer()
            losses = [tr.step(x, t)["loss"]]
            pids = tr.stage_pids()
            time.sleep(0.5)  # checkpoint message lands

            def killer():
                time.sleep(0.1)
                import os

                os.kill(pids[1], 9)

            th = threading.Thread(target=killer)
            th.start()
            completed = True
            try:
                for _ in range(3):
                    losses.append(tr.step(x, t)["loss"])
            except Exception:  # noqa: BLE001 — incl. any ObjectLostError
                completed = False
            th.join()
            time.sleep(1.2)
            st = rt.transfer_stats()
            tr.shutdown()
            return {"completed": completed, "steps": len(losses),
                    "stage_restarts": st["stage_restarts"]}
        finally:
            ray.shutdown()

    pairs = [one_round() for _ in range(rounds)]

    def pick(samples):
        best = max(samples, key=lambda s: s["tok_s"])
        return {**best, "samples": samples}

    fd, ofb = pick([p[0] for p in pairs]), pick([p[1] for p in pairs])
    try:
        chaos_row = chaos_round()
    except Exception as e:  # noqa: BLE001 — extra row must not kill A/B
        chaos_row = {"error": repr(e)}

    out = {"pp": pp, "microbatches": M, "tokens_per_step": batch * seq,
           "delay_ms": delay_ms, "rounds": rounds,
           "fill_drain": fd, "1f1b": ofb, "chaos": chaos_row}
    print(f"  [pipeline_train] 1f1b {ofb['tok_s']} tok/s vs fill_drain "
          f"{fd['tok_s']} tok/s "
          f"({ofb['tok_s'] / max(fd['tok_s'], 1e-9):.2f}x), bubble "
          f"{ofb['bubble_fraction']} vs {fd['bubble_fraction']}; chaos "
          f"completed={chaos_row.get('completed')} "
          f"(stage_restarts={chaos_row.get('stage_restarts')})",
          file=sys.stderr)
    return out


def impala_throughput_bench(iters=4):
    """Distributed IMPALA row: rollout workers -> aggregator actors ->
    the learner's host->device double-buffered queue, env-frames/s with
    the queue's measured occupancy, double-buffering on
    (``impala_queue_depth=2`` — the h2d of batch t+1 issues while the
    update for batch t computes) vs off (depth 0: direct per-update
    transfer), aggregators on in both modes so the only variable is
    the loader thread.  On CPU ``jnp.asarray`` is a near-free memcpy,
    so — like the shuffle row's paced pull plane — the shared
    ``_to_device`` hop is paced with a fixed per-batch delay modeling a
    real host->accelerator interconnect, applied identically in BOTH
    modes: depth 2 hides it behind the running update, depth 0 pays it
    serially, which makes the A/B load-independent."""
    import numpy as np  # noqa: F401 -- parity with workers

    pace_ms = 15

    def cartpole():
        import gymnasium

        return gymnasium.make("CartPole-v1")

    def one_mode(depth):
        import ray_tpu as ray
        from ray_tpu.rllib import ImpalaConfig
        from ray_tpu.rllib import impala as impala_mod

        real_to_device = impala_mod._to_device

        def paced_to_device(tm):
            time.sleep(pace_ms / 1000.0)
            return real_to_device(tm)

        impala_mod._to_device = paced_to_device
        ray.init(num_cpus=8, num_tpus=0,
                 _system_config={"impala_queue_depth": depth})
        try:
            config = (ImpalaConfig()
                      .environment(cartpole)
                      .rollouts(num_rollout_workers=2,
                                num_envs_per_worker=2,
                                rollout_fragment_length=32)
                      .training(lr=4e-3, num_aggregators=2,
                                max_batches_per_step=4))
            algo = config.build()
            algo.train()  # warm jit + fill the sample pipeline
            frames = 0
            t0 = time.perf_counter()
            for _ in range(iters):
                frames += algo.train()["num_env_steps_sampled"]
            dt = time.perf_counter() - t0
            q = (algo._h2d.queue_stats() if algo._h2d is not None
                 else {"gets": 0, "stalls": 0, "occupancy_avg": 0.0})
            algo.stop()
            return {"frames_s": round(frames / dt, 1),
                    "queue_depth": depth,
                    "queue_gets": q["gets"],
                    "queue_stalls": q["stalls"],
                    "queue_occupancy_avg": round(q["occupancy_avg"], 3)}
        finally:
            impala_mod._to_device = real_to_device
            ray.shutdown()

    def best_of(depth, rounds=3):
        samples = [one_mode(depth) for _ in range(rounds)]
        best = max(samples, key=lambda s: s["frames_s"])
        best["samples_frames_s"] = [s["frames_s"] for s in samples]
        return best

    on = best_of(2)
    off = best_of(0)
    out = {"h2d_pace_ms": pace_ms,
           "double_buffer_on": on, "double_buffer_off": off}
    print(f"  [impala_throughput] depth2 {on['frames_s']} frames/s "
          f"(occupancy {on['queue_occupancy_avg']}, stalls "
          f"{on['queue_stalls']}) vs depth0 {off['frames_s']} frames/s",
          file=sys.stderr)
    return out


def elastic_drill_bench():
    """Elastic-pods row: sustained small-task traffic against an
    autoscaled spot slice pool crosses ONE mid-run preemption (graceful
    notice: leases revoked, sole-copy results migrated, agent released
    cleanly).  Reports req/s and p99 task latency under the churn plus
    the drain/reconstruction counters; best-of-3 with raw per-round
    samples (PR 6/7 convention)."""
    import numpy as np  # noqa: F401 -- workers import it; keep parity

    import ray_tpu as ray
    from ray_tpu.autoscaler import FakeSliceProvider, StandardAutoscaler
    from ray_tpu.chaos import ChaosController
    from ray_tpu.cluster_utils import Cluster

    duration_s = 6.0

    @ray.remote(resources={"slice": 0.25}, max_retries=6)
    def work(i):
        import numpy as np

        # ~1.6 MB: over the inline cutoff, so results are node-store
        # homed — the sole-copy bytes the drain migrates.
        return np.full(200_000, i)

    def one_round():
        c = Cluster(head_num_cpus=2)
        scaler = chaos = None
        try:
            provider = FakeSliceProvider(c, {
                "spot-v5e": {"resources": {"CPU": 2, "slice": 1},
                             "max_workers": 3, "spot": True}})
            scaler = StandardAutoscaler(c.rt, provider,
                                        idle_timeout_s=30.0,
                                        update_interval_s=0.4)
            scaler.start()
            chaos = ChaosController(c.rt)
            lat, held = [], {}
            ok = True
            t_start = time.perf_counter()
            t_end = t_start + duration_s
            preempt_at = t_end - duration_s / 2
            preempted = False
            i = 0
            while time.perf_counter() < t_end or not preempted:
                wave = {i + k: work.remote(i + k) for k in range(4)}
                i += 4
                t0 = time.perf_counter()
                vals = ray.get(list(wave.values()), timeout=120)
                lat.append((time.perf_counter() - t0) / len(wave))
                ok = ok and [int(v[0]) for v in vals] == list(wave)
                # every 4th wave's results are HELD unconsumed — the
                # sole-copy objects the preempted node must not lose
                if (i // 4) % 4 == 0:
                    held.update(wave)
                if not preempted and time.perf_counter() >= preempt_at:
                    preempted = chaos.preempt_node(notice=True) is not None
            for k, ref in held.items():
                v = ray.get(ref, timeout=120)
                ok = ok and int(v[0]) == k
            # Real elapsed, not the nominal window: the loop overruns
            # t_end when the preemption lands late.
            elapsed = time.perf_counter() - t_start
            lat.sort()
            st = c.rt.transfer_stats()
            return {
                "req_per_s": round(i / elapsed, 1),
                "p50_ms": round(lat[len(lat) // 2] * 1e3, 1),
                "p99_ms": round(lat[max(0, int(len(lat) * 0.99) - 1)]
                                * 1e3, 1),
                "completed": ok and preempted,
                "drains_completed": st["drains_completed"],
                "objects_migrated": st["objects_migrated"],
                "reconstructions": st["reconstructions"],
            }
        finally:
            if chaos is not None:
                chaos.stop()
            if scaler is not None:
                scaler.stop()
            c.shutdown()

    samples = [one_round() for _ in range(3)]
    on = min(samples, key=lambda s: (not s["completed"], s["p99_ms"]))
    out = {"duration_s": duration_s, "drain_on": {**on, "samples": samples}}
    print(f"  [elastic] {on['req_per_s']} req/s p99 {on['p99_ms']}ms"
          f" migrated={on['objects_migrated']} rebuilds="
          f"{on['reconstructions']}", file=sys.stderr)
    return out


# Peak bf16 FLOP/s by device kind (for MFU).
_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,  # v6e / Trillium
    "TPU v6e": 918e12,
}


def tpu_bench():
    """Device-compute benchmarks on the real chip.  No chip is an error:
    a device row measured anywhere else is not a device row."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private.device_env import compile_cache_dir

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"device rows need a TPU; JAX's backend here is "
            f"{jax.default_backend()!r}")
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())

    dev = jax.devices()[0]
    peak = _PEAK_FLOPS[dev.device_kind]  # unknown kind: no default peak
    out = {"device_kind": dev.device_kind, "peak_bf16_flops": peak}

    import numpy as np

    from ray_tpu.ops.attention import flash_attention, mha_reference

    # Dispatch is async and a sync fetch pays a round trip, so every
    # measurement chains N dependent steps inside ONE jitted scan and
    # divides.
    def time_chained(attn, q, k, v, iters):
        @jax.jit
        def chain(q, k, v):
            def loss(qq):
                return attn(qq, k, v, causal=True).astype(jnp.float32).sum()

            def body(c, _):
                val, g = jax.value_and_grad(loss)(c)
                return (c + 1e-6 * g.astype(c.dtype)), val

            c, vals = jax.lax.scan(body, q, None, length=iters)
            return c[0, 0, 0, 0] + vals.sum()

        np.asarray(chain(q, k, v))  # compile + warm
        t0 = time.perf_counter()
        np.asarray(chain(q, k, v))
        return (time.perf_counter() - t0) / iters

    # Flash attention fwd+bwd vs the XLA reference, bf16 shapes.  d=64
    # keys keep their round-3/4 names for cross-round comparison; d=128
    # is the FLAGSHIP geometry (head_dim=128, __graft_entry__).
    for (h, d) in ((16, 64), (8, 128)):
        tag = "" if d == 64 else f"_d{d}"
        b = 4
        for seq in (2048, 8192):
            key = jax.random.PRNGKey(0)
            q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                         (b, seq, h, d),
                                         dtype=jnp.bfloat16)
                       for i in range(3))
            t_flash = time_chained(flash_attention, q, k, v, 16)
            # fwd 4*b*h*s^2*d + bwd 2x = 12 (full, non-causal count).
            flops = 12 * b * h * seq * seq * d
            out[f"flash_attn{tag}_s{seq}_ms"] = round(t_flash * 1e3, 3)
            out[f"flash_attn{tag}_s{seq}_tflops"] = round(
                flops / t_flash / 1e12, 1)
            extra = ""
            if seq <= 2048:
                # The XLA reference materializes (s, s) scores — OOMs at
                # 8k; its existence at 2k is the speedup context.
                t_ref = time_chained(mha_reference, q, k, v, 16)
                out[f"flash_attn{tag}_s{seq}_vs_xla"] = round(
                    t_ref / t_flash, 3)
                extra = f", {t_ref/t_flash:.2f}x XLA ref"
            # jax's own pallas TPU flash kernel on the same shapes —
            # the strongest public baseline for this op.
            from jax.experimental.pallas.ops.tpu.flash_attention \
                import flash_attention as jax_flash

            def jx(qq, kk, vv, causal=True):
                tq = jnp.transpose(qq, (0, 2, 1, 3))
                tk = jnp.transpose(kk, (0, 2, 1, 3))
                tv = jnp.transpose(vv, (0, 2, 1, 3))
                o = jax_flash(tq, tk, tv, causal=causal,
                              sm_scale=qq.shape[-1] ** -0.5)
                return jnp.transpose(o, (0, 2, 1, 3))

            t_jax = time_chained(jx, q, k, v, 16)
            out[f"flash_attn{tag}_s{seq}_vs_jax_pallas"] = round(
                t_jax / t_flash, 3)
            extra += f", {t_jax/t_flash:.2f}x jax-pallas"
            print(f"  [tpu] flash d={d} s={seq}: {t_flash*1e3:.2f}ms "
                  f"({flops/t_flash/1e12:.1f} TF/s full-count{extra})",
                  file=sys.stderr)

    # Train steps: flagship (162M, round-comparable keys) and a ~1.2B
    # config where HBM is actually tight on one chip — remat + donation
    # + bf16 params/optimizer are what make it fit (BASELINE.json
    # north-star direction; reference scale context:
    # release/alpa_tests/train_opt_2_7b_minimum.py).
    import optax

    from __graft_entry__ import _flagship_cfg
    from ray_tpu.models import LlamaConfig
    from ray_tpu.train import init_train_state, make_train_step

    def train_bench(prefix, cfg, batch, iters):
        seq = cfg.max_seq_len
        opt = optax.adamw(1e-3)
        state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
        step = make_train_step(cfg, opt, donate=False)
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (batch, seq + 1), 0,
                                    cfg.vocab_size, dtype=jnp.int32)

        from functools import partial

        # State buffers are donated: XLA updates params/opt state in
        # place across the whole scan instead of double-buffering ~3x
        # param bytes — this is what lets the 1.2B config fit.
        @partial(jax.jit, donate_argnums=(0,))
        def run(state, tokens):
            def body(s, _):
                s2, m = step(s, {"tokens": tokens})
                return s2, m["loss"]
            return jax.lax.scan(body, state, None, length=iters)

        state, losses = run(state, tokens)   # compile + warm
        np.asarray(losses)
        t0 = time.perf_counter()
        state, losses = run(state, tokens)
        np.asarray(losses)
        dt = (time.perf_counter() - t0) / iters

        n_params = sum(x.size
                       for x in jax.tree_util.tree_leaves(state.params))
        toks = batch * seq
        # 6N per token (fwd+bwd matmuls) + attention 12*L*s*h*d/token.
        step_flops = toks * (6 * n_params
                             + 12 * cfg.num_layers * seq * cfg.num_heads
                             * cfg.head_dim)
        mfu = step_flops / dt / peak
        out[f"{prefix}_step_ms"] = round(dt * 1e3, 2)
        out[f"{prefix}_tokens_per_s"] = round(toks / dt)
        out[f"{prefix}_mfu"] = round(mfu, 4)
        # Full-layer remat (measured faster than both no-remat and
        # selective policies on v5e): the device EXECUTES ~8N/6N of the
        # counted FLOPs; this is the hardware-utilization number the
        # counted MFU hides.
        out[f"{prefix}_util_with_remat"] = round(mfu * 8.0 / 6.0, 4)
        out[f"{prefix}_params_m"] = round(n_params / 1e6, 1)
        print(f"  [tpu] {prefix} step: {dt*1e3:.1f}ms, "
              f"{toks/dt:,.0f} tok/s, MFU {mfu*100:.1f}% "
              f"({n_params/1e6:.0f}M params, {dev.device_kind})",
              file=sys.stderr)
        del state, tokens

    train_bench("train", _flagship_cfg(), batch=16, iters=10)
    out["model_params_m"] = out.pop("train_params_m")  # legacy key
    # param_dtype=bf16: 1.2B params = 2.4GB + adam mu/nu 4.8GB — fp32
    # masters (14.4GB state) would not leave room for activations on a
    # 16GB v5e chip.
    cfg_1b = LlamaConfig(
        vocab_size=32000, embed_dim=2048, num_layers=16,
        num_heads=16, num_kv_heads=16, head_dim=128, mlp_dim=8192,
        max_seq_len=2048, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, attn_impl="flash", remat=True)
    train_bench("train_1b", cfg_1b, batch=8, iters=4)
    return out


def main():
    results, raw_samples = core_bench()

    ratios = []
    extras = {}
    for k, v in results.items():
        r = v / BASELINE[k]
        tag = ""
        if k in NON_COMPARABLE:
            extras[k] = {"value": round(v, 1), "ref": BASELINE[k],
                         "ratio": round(r, 2),
                         "note": "excluded from geomean (not like-for-like)"}
            tag = "  [excluded from geomean]"
        else:
            ratios.append(r)
        print(f"  {k}: {v:.1f} (ref {BASELINE[k]:.1f}, {r:.2f}x){tag}",
              file=sys.stderr)

    def geomean(rs):
        g = 1.0
        for r in rs:
            g *= r
        return g ** (1.0 / len(rs))

    geo = geomean(ratios)
    # Transparency figure: every per-metric win clipped at 4x, so one
    # architecture-advantage outlier cannot carry the headline.
    geo_capped = geomean([min(r, 4.0) for r in ratios])

    try:
        locality = locality_bench()
    except Exception as e:  # noqa: BLE001 — extra row must not kill core
        print(f"  [locality] bench failed: {e!r}", file=sys.stderr)
        locality = {"error": repr(e)}

    try:
        data_streaming = data_streaming_bench()
    except Exception as e:  # noqa: BLE001 — extra row must not kill core
        print(f"  [data_streaming] bench failed: {e!r}", file=sys.stderr)
        data_streaming = {"error": repr(e)}

    try:
        serve_latency = serve_latency_bench()
    except Exception as e:  # noqa: BLE001 — extra row must not kill core
        print(f"  [serve] bench failed: {e!r}", file=sys.stderr)
        serve_latency = {"error": repr(e)}

    try:
        recovery = recovery_bench()
    except Exception as e:  # noqa: BLE001 — extra row must not kill core
        print(f"  [recovery] bench failed: {e!r}", file=sys.stderr)
        recovery = {"error": repr(e)}

    try:
        elastic_drill = elastic_drill_bench()
    except Exception as e:  # noqa: BLE001 — extra row must not kill core
        print(f"  [elastic_drill] bench failed: {e!r}", file=sys.stderr)
        elastic_drill = {"error": repr(e)}

    try:
        push_shuffle = shuffle_bench()
    except Exception as e:  # noqa: BLE001 — extra row must not kill core
        print(f"  [shuffle] bench failed: {e!r}", file=sys.stderr)
        push_shuffle = {"error": repr(e)}

    try:
        pipeline_train = pipeline_train_bench()
    except Exception as e:  # noqa: BLE001 — extra row must not kill core
        print(f"  [pipeline_train] bench failed: {e!r}", file=sys.stderr)
        pipeline_train = {"error": repr(e)}

    try:
        impala_throughput = impala_throughput_bench()
    except Exception as e:  # noqa: BLE001 — extra row must not kill core
        print(f"  [impala_throughput] bench failed: {e!r}",
              file=sys.stderr)
        impala_throughput = {"error": repr(e)}

    try:
        disagg_serving = disagg_serving_bench()
    except Exception as e:  # noqa: BLE001 — extra row must not kill core
        print(f"  [disagg_serving] bench failed: {e!r}",
              file=sys.stderr)
        disagg_serving = {"error": repr(e)}

    try:
        tpu = tpu_bench()
    except Exception as e:  # noqa: BLE001 — device bench must not kill core
        print(f"  [tpu] device bench failed: {e!r}", file=sys.stderr)
        tpu = {"error": repr(e)}

    rows = {
        "arg_locality": locality, "data_streaming": data_streaming,
        "recovery": recovery, "elastic_drill": elastic_drill,
        "serve_latency": serve_latency, "push_shuffle": push_shuffle,
        "pipeline_train": pipeline_train,
        "impala_throughput": impala_throughput,
        "disagg_serving": disagg_serving, "tpu": tpu,
    }
    failed = sorted(k for k, v in rows.items() if "error" in v)
    print(json.dumps({
        "metric": "core_microbench_geomean_vs_reference",
        "value": round(geo, 4),
        "unit": "x (1.0 = reference-published parity)",
        "vs_baseline": round(geo, 4),
        "geomean_wins_capped_at_4x": round(geo_capped, 4),
        "contended_row_samples": raw_samples,
        "non_comparable": extras,
        "arg_locality": locality,
        "data_streaming": data_streaming,
        "recovery": recovery,
        "elastic_drill": elastic_drill,
        "serve_latency": serve_latency,
        "push_shuffle": push_shuffle,
        # Last (before the small tpu dict): the round artifact keeps the
        # TAIL of this line, and this round's A/B rows live here.
        "pipeline_train": pipeline_train,
        "impala_throughput": impala_throughput,
        "disagg_serving": disagg_serving,
        "tpu": tpu,
    }))
    if failed:
        # The line above is still printed (the rows that ran are data),
        # but a run with a failed row is a failed run.
        sys.exit(f"bench: rows failed: {failed}")


if __name__ == "__main__":
    main()
