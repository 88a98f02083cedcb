#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

Drives the two paths users drive, once, through the public entry points,
on whatever TPU ``ray_tpu.init()`` detects:

- *train*: ``JaxTrainer(...).fit()`` with one worker that owns the chip
  builds Llama-2-7B at its published widths (4096 / 32x128 / 11008 /
  vocab 32000; depth cut to 4 layers to fit one v5e — the only
  reduction), and takes 1 compile step + 5 timed steps at batch 8 x 2048.
- *serve*: after that worker has exited, ``serve.run`` of
  ``MeshShardedDecoder`` as a ``num_tpus=1`` deployment with the paged KV
  engine on, a few dozen requests of mixed prompt lengths through a
  handle, compared bitwise with ``reference_decode``.

``--chips 4`` runs instead ONLY what exists across chips: four one-chip
actors alive at once, then the same train step on an fsdp=2 x tp=2 mesh
compared with a one-device mesh in the same process.

This process never imports JAX: a chip belongs to one process at a time,
and it belongs to the worker the scheduler grants it to.  The device in
the last line is what that worker reported.  Any failed check, phase
error or timeout exits non-zero with the reason; there is no CPU mode
(tests/test_chip_smoke.py calls the phase functions at tiny sizes with
CPU workers).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import statistics
import sys
import threading
import time

# Published Llama-2-7B widths (LlamaConfig.llama2_7b), depth cut to one chip.
TRAIN_MODEL = {"preset": "llama2_7b", "num_layers": 4,
               "param_dtype": "bfloat16"}
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 2048, 5
# Flash vs reference attention, first-step loss on the same parameters,
# relative.  The v5e read 1.2e-5 and 1.0e-6 (two initialisations, PR 21);
# an attention path that dropped to bfloat16 accumulation would be ~1e-3.
# Compared on the first REF_ROWS rows of the batch, where the reference's
# (rows, 32, 2048, 2048) float32 score matrix fits beside the train state.
REF_RTOL, REF_ROWS = 1e-4, 2
# fsdp=2 x tp=2 vs one device, every step's loss, relative: the same
# arithmetic in another reduction order, drifting apart over bfloat16
# updates.  The 2x2 v5e read at most 1.4e-3 (step 4 of 6, PR 21).
MESH_RTOL = 5e-3
SERVE = dict(embed=1024, vocab=32000, kv_blocks=4096, kv_block_size=16,
             max_slots=64)
SERVE_REQUESTS = 48
DEADLINE_S = 1100  # the driver allows 1200


def _device(devs):
    """The devices of the calling process, as JAX reports them."""
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class SmokeFailure(Exception):
    """A check that did not hold; the message is the reason."""


def _require(cond, reason: str):
    if not cond:
        raise SmokeFailure(reason)


# ---------------------------------------------------------------- train --

def _train_loop(config):
    """Runs in the trainer's worker — the process that owns the chips."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import monitoring

    from ray_tpu.air import session
    from ray_tpu.models.llama import LlamaConfig, loss_fn
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train.core import (
        default_optimizer, init_train_state, make_train_step)

    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event.endswith("/cache_hits"):
            cache["hits"] += 1
        elif event.endswith("/cache_misses"):
            cache["misses"] += 1

    monitoring.register_event_listener(on_event)

    devs = jax.devices()
    model = dict(config["model"])
    preset = model.pop("preset")
    for k in ("dtype", "param_dtype"):
        if k in model:
            model[k] = jnp.dtype(model[k])
    cfg = getattr(LlamaConfig, preset)(**model)
    b, s, steps = config["batch"], config["seq"], config["steps"]
    tokens = np.random.default_rng(config["seed"]).integers(
        0, cfg.vocab_size, (b, s + 1), dtype=np.int32)
    opt = default_optimizer(lr=3e-4, warmup=0)

    def peaks():
        return [(d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in devs]

    def run(mesh):
        """1 compile step + ``steps`` timed steps on the repeated batch."""
        state = init_train_state(jax.random.PRNGKey(config["seed"]), cfg,
                                 opt, mesh=mesh)
        batch = {"tokens": jnp.asarray(tokens)}
        out = {}
        if mesh is None and config["ref_rows"]:
            # Before the first (donating) step: the reference check.
            rows = {"tokens": batch["tokens"][:config["ref_rows"]]}
            ref_cfg = dataclasses.replace(cfg, attn_impl="reference")
            for name, c in (("flash", cfg), ("reference", ref_cfg)):
                out[f"first_loss_{name}"] = float(jax.jit(
                    lambda p, t, c=c: loss_fn(p, t, c)[0])(
                        state.params, rows))
        t0 = time.perf_counter()
        compiled = make_train_step(cfg, opt, mesh=mesh).lower(
            state, batch).compile()
        out["compile_s"] = time.perf_counter() - t0
        out["flash_custom_call"] = "tpu_custom_call" in compiled.as_text()
        losses, times = [], []
        for _ in range(steps + 1):
            t0 = time.perf_counter()
            state, metrics = compiled(state, batch)
            losses.append(float(metrics["loss"]))  # blocks until ready
            times.append(time.perf_counter() - t0)
        out.update(losses=losses, step_s=times[1:], first_step_s=times[0])
        return out

    mesh_axes = config.get("mesh")
    if mesh_axes:
        # The cross-chip path, then what it is compared with: the same
        # steps on a one-device mesh, in this same process.  Peaks are
        # read in between — they only ever grow, and the one-device run
        # would put its whole state on the first chip.
        out = run(make_mesh(MeshConfig(**mesh_axes)))
        out["peak_bytes_in_use"] = peaks()
        out["one_device"] = run(make_mesh(MeshConfig(dp=1),
                                          devices=devs[:1]))
    else:
        out = run(None)
        out["peak_bytes_in_use"] = peaks()
    out.update(
        device=_device(devs),
        compile_cache={**cache,
                       "dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")},
        tokens_per_step=b * s)
    session.report(out)


def _check_losses(losses, what: str):
    import math

    _require(all(math.isfinite(x) for x in losses),
             f"{what}: non-finite loss in {losses}")
    _require(losses[-1] < losses[0],
             f"{what}: loss did not fall on the repeated batch: {losses}")


def phase_train(*, model, batch, seq, steps, chips_per_worker, seed,
                ref_rows=0, mesh=None):
    """``JaxTrainer.fit()`` with one worker owning ``chips_per_worker``
    chips (0: a CPU worker — the test tree's rehearsal).  With ``mesh``
    (axis sizes), the sharded step and its one-device comparison."""
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer

    on_chip = chips_per_worker > 0
    result = JaxTrainer(
        _train_loop,
        train_loop_config=dict(model=model, batch=batch, seq=seq,
                               steps=steps, seed=seed, ref_rows=ref_rows,
                               mesh=mesh),
        scaling_config=ScalingConfig(
            num_workers=1, tpu_chips_per_worker=chips_per_worker),
    ).fit()
    if result.error is not None:
        raise SmokeFailure(f"train: fit() failed: {result.error}")
    m = result.metrics
    dev = m["device"]
    if on_chip:
        _require(dev["platform"] == "tpu",
                 f"train: worker ran on {dev}, not the TPU")
        _require(dev["count"] == chips_per_worker,
                 f"train: worker granted {chips_per_worker} chips sees "
                 f"{dev['count']} devices")
        _require(m["flash_custom_call"],
                 "train: no tpu_custom_call in the compiled step — the "
                 "flash kernel did not compile into it")
        _require(all(m["peak_bytes_in_use"]),
                 f"train: a chip held nothing: {m['peak_bytes_in_use']}")
    _check_losses(m["losses"], "train")
    if "first_loss_reference" in m:
        a, r = m["first_loss_flash"], m["first_loss_reference"]
        _require(abs(a - r) <= REF_RTOL * abs(r),
                 f"train: first-step loss flash {a} vs reference {r} "
                 f"differ by more than {REF_RTOL} relative")
    if mesh:
        one = m["one_device"]
        _check_losses(one["losses"], "train (one device)")
        for i, (a, r) in enumerate(zip(m["losses"], one["losses"])):
            _require(abs(a - r) <= MESH_RTOL * abs(r),
                     f"train: step {i} loss on the mesh {a} vs one device "
                     f"{r} differ by more than {MESH_RTOL} relative")
        if on_chip:
            pk = m["peak_bytes_in_use"]
            _require(max(pk) <= 1.2 * min(pk),
                     f"train: per-chip peaks not within 20%: {pk}")
    return m


# ---------------------------------------------------------------- serve --

def phase_serve(*, embed, vocab, kv_blocks, kv_block_size, max_slots,
                n_requests, num_tpus, seed, timeout_s=600.0):
    """``serve.run`` of the paged decoder; requests through a handle;
    answers bitwise against ``reference_decode`` on the same replica's
    host mirrors.  Needs ``paged_kv`` on in the cluster's
    ``_system_config`` — checked through the served mode."""
    import random

    import ray_tpu as ray
    from ray_tpu import serve
    from ray_tpu.serve.tpu_replica import MeshShardedDecoder

    name = "chip_smoke_decoder"
    dep = serve.deployment(MeshShardedDecoder, name=name, num_tpus=num_tpus,
                           max_concurrency=max(64, n_requests))
    rng = random.Random(seed)
    lengths = (1, 3, kv_block_size, kv_block_size + 1, 40, 100)
    reqs = [{"prompt": [rng.randrange(vocab)
                        for _ in range(lengths[i % len(lengths)])],
             "tokens": 4 + (7 * i) % 21} for i in range(n_requests)]
    asked = sum(r["tokens"] for r in reqs)
    try:
        handle = serve.run(dep.bind(
            embed=embed, vocab=vocab, seed=seed, paged=True,
            kv_blocks=kv_blocks, kv_block_size=kv_block_size,
            max_slots=max_slots), name=name)
        t0 = time.perf_counter()
        outs = ray.get([handle.remote(r) for r in reqs], timeout=timeout_s)
        wall = time.perf_counter() - t0
        ref = handle.method("reference_decode")
        want = ray.get([ref.remote(r["prompt"], r["tokens"]) for r in reqs],
                       timeout=timeout_s)
        dev = ray.get(handle.method("device_info").remote(),
                      timeout=timeout_s)
        stats = serve.serving_stats(name)
    finally:
        serve.shutdown()
    bad = [i for i, (o, w) in enumerate(zip(outs, want)) if o != w]
    _require(not bad, f"serve: {len(bad)} of {n_requests} answers differ "
             f"from reference_decode, first: request {bad[:1]}")
    _require(stats.get("mode") == "continuous+paged",
             f"serve: mode {stats.get('mode')!r} — the paged engine did "
             "not serve (is paged_kv on in _system_config?)")
    _require(stats["kv_blocks_total"] == kv_blocks,
             f"serve: kv_blocks_total {stats['kv_blocks_total']} != "
             f"{kv_blocks}")
    _require(stats["tokens_emitted"] == asked,
             f"serve: tokens_emitted {stats['tokens_emitted']} != asked "
             f"{asked}")
    if num_tpus:
        _require(dev["platform"] == "tpu" and dev["count"] == num_tpus,
                 f"serve: replica ran on {dev}")
    return {"device": dev, "requests": n_requests, "tokens": asked,
            "wall_s": wall, "stats": stats}


# -------------------------------------------------------------- 4 chips --

def phase_actors(n: int):
    """``n`` one-chip actors alive at once, each on a chip of its own."""
    import ray_tpu as ray

    @ray.remote(num_tpus=1)
    class ChipProbe:
        def info(self):
            import jax
            import jax.numpy as jnp

            devs = jax.devices()
            total = float(jnp.ones((256, 256), jnp.float32).sum())
            nodes = set()
            for fd in os.listdir("/proc/self/fd"):
                try:
                    path = os.readlink(f"/proc/self/fd/{fd}")
                except OSError:
                    continue
                if path.startswith(("/dev/accel", "/dev/vfio/")) \
                        and path != "/dev/vfio/vfio":
                    nodes.add(path)
            return {"pid": os.getpid(), **_device(devs), "sum": total,
                    "device_nodes": sorted(nodes),
                    "granted": ray.get_runtime_context().tpu_chips}

    actors = [ChipProbe.remote() for _ in range(n)]
    try:
        infos = ray.get([a.info.remote() for a in actors], timeout=300)
        # All n hold their device NOW: ask again while all are alive.
        again = ray.get([a.info.remote() for a in actors], timeout=60)
    finally:
        for a in actors:
            ray.kill(a)
    _require([i["pid"] for i in infos] == [i["pid"] for i in again]
             and len({i["pid"] for i in infos}) == n,
             f"actors: not {n} live processes: {infos}")
    for i in infos:
        _require(i["platform"] == "tpu" and i["count"] == 1
                 and i["sum"] == 256.0 * 256.0,
                 f"actors: an actor does not hold exactly one chip: {i}")
    granted = [tuple(i["granted"]) for i in infos]
    _require(len(set(granted)) == n and all(len(g) == 1 for g in granted),
             f"actors: grants are not {n} distinct chips: {granted}")
    nodes = [tuple(i["device_nodes"]) for i in infos]
    if any(nodes):
        _require(len(set(nodes)) == n and all(len(x) == 1 for x in nodes),
                 f"actors: open device nodes are not {n} distinct chips: "
                 f"{nodes}")
    return infos


# ----------------------------------------------------------------- main --

def _stop_children(grace_s: float = 10.0):
    """Nothing this script started may outlive it."""
    me = os.getpid()

    def children():
        out = []
        for pid in filter(str.isdigit, os.listdir("/proc")):
            try:
                with open(f"/proc/{pid}/stat") as f:
                    stat = f.read()
            except OSError:
                continue
            if int(stat.rsplit(")", 1)[1].split()[1]) == me:
                out.append(int(pid))
        return out

    deadline = time.monotonic() + grace_s
    while True:
        for pid in children():
            try:
                if os.waitpid(pid, os.WNOHANG)[0] == 0 \
                        and time.monotonic() > deadline:
                    os.kill(pid, signal.SIGKILL)
            except (ChildProcessError, ProcessLookupError):
                pass
        if not children():
            return
        time.sleep(0.1)


def _say(name: str, value):
    print(f"{name}: {value}", flush=True)


def _report_train(tag: str, m):
    _say(f"{tag}_device", m["device"])
    _say(f"{tag}_compile_s", m["compile_s"])
    _say(f"{tag}_first_step_s", m["first_step_s"])
    _say(f"{tag}_step_s", m["step_s"])
    _say(f"{tag}_tokens_per_s_median_of_{len(m['step_s'])}_steps",
         m["tokens_per_step"] / statistics.median(m["step_s"]))
    _say(f"{tag}_losses", m["losses"])
    _say(f"{tag}_peak_bytes_in_use", m["peak_bytes_in_use"])
    _say(f"{tag}_flash_custom_call_in_compiled_step",
         m["flash_custom_call"])
    _say(f"{tag}_compile_cache", m["compile_cache"])


def run(chips: int, seed: int):
    import ray_tpu as ray

    ray.init(_system_config={"paged_kv": True})  # chips auto-detected
    have = int(ray.cluster_resources().get("TPU", 0))
    _require(have >= chips,
             f"no TPU: ray_tpu.init() found {have} chip(s) on this machine "
             f"(no /dev/accel* or /dev/vfio/<n> device node), need {chips}")
    if chips == 1:
        _say("phase", "train")
        m = phase_train(model=TRAIN_MODEL, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                        steps=TRAIN_STEPS, chips_per_worker=1, seed=seed,
                        ref_rows=REF_ROWS)
        _report_train("train", m)
        _say("train_first_loss_flash_vs_reference",
             (m["first_loss_flash"], m["first_loss_reference"]))
        _say("phase", "serve")
        s = phase_serve(**SERVE, n_requests=SERVE_REQUESTS, num_tpus=1,
                        seed=seed)
        _say("serve_device", s["device"])
        _say("serve_requests_tokens_wall_s_incl_replica_start",
             (s["requests"], s["tokens"], s["wall_s"]))
        _say("serve_stats", s["stats"])
        _require(s["device"] == m["device"],
                 f"serve and train saw different devices: {s['device']} "
                 f"vs {m['device']}")
    else:
        _say("phase", "actors")
        infos = phase_actors(chips)
        _say("actors", infos)
        _say("phase", "mesh_train")
        m = phase_train(model=TRAIN_MODEL, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                        steps=TRAIN_STEPS, chips_per_worker=chips, seed=seed,
                        mesh={"fsdp": 2, "tp": 2})
        _report_train("mesh_train", m)
        _say("one_device_losses", m["one_device"]["losses"])
        _say("one_device_step_s", m["one_device"]["step_s"])
    return m["device"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cross-chip path (the builder runs it)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    def expired():
        print(f"chip_smoke: FAILED: no end after {DEADLINE_S}s", flush=True)
        _stop_children(grace_s=0.0)
        os._exit(3)

    watchdog = threading.Timer(DEADLINE_S, expired)
    watchdog.daemon = True
    watchdog.start()
    device = None
    try:
        device = run(args.chips, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", flush=True)
    finally:
        import ray_tpu as ray

        ray.shutdown()
        _stop_children()
        watchdog.cancel()
    if device is None:
        return 1
    if "jax" in sys.modules:
        print("chip_smoke: FAILED: the driver process imported JAX",
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
