"""Distributed pipeline-parallel training: 1F1B stage actors over the
striped data plane.

Reference: PipeDream (SOSP'19) one-forward-one-backward scheduling and
GPipe (NeurIPS'19) micro-batching.  ``parallel/pipeline.py`` runs the
GPipe schedule INSIDE one XLA program (``lax.ppermute`` over the 'pp'
mesh axis of a single host) and documents its fill/drain bubble as
"acceptable at microbatches >> pp, 1F1B is a later optimization" — this
module is that step, taken across PROCESSES: each pipeline stage is a
long-lived restartable actor owning its stage's params on its own
devices, and the 1F1B schedule is driven by the actor call pipeline
itself.

- **Data plane**: micro-batch activations (forward) and activation
  gradients (backward) move stage-to-stage as segment images pushed
  over the PR 7 direct-put verbs (``reserve_put``/``put_range``/
  ``commit_put`` — ``ObjectPusher.push`` stripes one), exactly the
  shuffle engine's partition-push shape.  Only a tiny descriptor
  ``("__mbdescr__", kind, ident, total, home_store)`` rides the actor
  call result; no activation payload ever crosses a head message.  A
  push to one's OWN store short-circuits through ``shm_store.put_local``
  and a failed/stalled push HEDGES into the pusher's store (the consumer
  then pulls over the data plane) — one gray link never kills training.
- **Schedule**: the driver submits each stage's 1F1B call sequence
  (warmup ``min(pp-1-s, M)`` forwards, steady-state one-forward-one-
  backward, cooldown backwards) without ever blocking; per-actor FIFO
  execution realizes the schedule and at most ``pp`` activation stashes
  are live per stage.  Dependencies are carried by passing the upstream
  call's result ref (the descriptor) as the downstream call's argument,
  so arg prefetch + the per-lease pipeline bound overlap the transfer
  of micro-batch t+1 with the compute of t for free.
- **Fault story**: stages are ``max_restarts``/``max_task_retries``
  actors with PR 9 ``__ray_save__``/``__ray_restore__`` hooks — params,
  optimizer state, gradient accumulators, and the activation stash all
  checkpoint, and checkpoints always capture step-boundary params
  (params change only inside ``apply_grads``).  A killed mid-pipeline
  stage restores and the head replays its in-flight calls; a replay
  that cannot complete (its input segment was already consumed) raises,
  and the driver re-drives the WHOLE loss step — ``apply_grads`` is
  idempotent per step, so stages that already applied skip.  Replay is
  thus bounded by one loss step and the driver never sees an
  ObjectLostError (descriptors are regenerated, payloads re-pushed).
- **Fallback**: ``config.distributed_training=off`` (or a single stage,
  or no runtime) runs the byte-identical single-host path — the same
  per-micro-batch loss/grad accumulation in one jitted program, every
  counter below zero (pinned by tests).

Numerics contract: total loss is the mean over micro-batches of
``loss_fn(stage_fn∘...∘stage_fn(x_mb), target_mb)`` and gradients are
the matching mean of per-micro-batch gradients — identical, term for
term, to ``pipeline_apply`` on one device, so integer-valued float32
training matches it BITWISE (all sums exact below 2**24).

LOCK ORDER: ``_STATS_LOCK`` is an independent LEAF — it guards only the
process-local counter dict read by ``train_stats()`` (the xfer_stats
flusher / ``transfer_stats()`` merge); no other lock is ever acquired
while holding it and it is never held across serialization, a push, or
any wire call.  Pinned in tests/test_lockcheck.py next to the shuffle
stats leaf.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

import ray_tpu as ray
from ray_tpu.remote_function import _bulk_submit

# ------------------------------------------------------------- counters --
# Process-local cumulative counters.  In workers (stage actors, remote
# learners) they ride the periodic ("xfer_stats", delta) flush
# (worker_main.flush_xfer_stats looks this module up lazily); in the
# driver/head process transfer_stats() merges them directly.  All zero
# while distributed_training is off — pinned by tests.
_STATS_LOCK = threading.Lock()  # lock-order: leaf (see module docstring)
_STATS = {
    "microbatch_pushes": 0,
    "stage_restarts": 0,
    "learner_queue_stalls": 0,
}


def note(key: str, n: int = 1) -> None:
    with _STATS_LOCK:
        _STATS[key] += n


def train_stats() -> Dict[str, int]:
    """Cumulative snapshot (monotonic — the flusher ships deltas).
    Deliberately NOT named ``stats()``: protocheck's counter-survival
    rule scans worker modules' ``stats()`` providers, and this module's
    keys are aggregated through the lazy flush hook instead."""
    with _STATS_LOCK:
        return dict(_STATS)


# ----------------------------------------------------------- data plane --
_DESCR_TAG = "__mbdescr__"


def _is_descr(v) -> bool:
    return isinstance(v, tuple) and len(v) == 5 and v[0] == _DESCR_TAG


def active_config():
    """The effective config: the runtime's (carries ``_system_config``
    overrides) when one is up, else the env-derived global."""
    from ray_tpu._private import api_internal
    from ray_tpu._private.config import GLOBAL_CONFIG

    rt = api_internal.get_runtime()
    return getattr(rt, "config", None) or GLOBAL_CONFIG


def _push_value(value, store: str) -> tuple:
    """Serialize one micro-batch tensor pytree and land its segment
    image in ``store``: local short-circuit through ``put_local``, else
    a striped ``ObjectPusher.push``.  A failed/stalled/unsupported
    remote push HEDGES into the pusher's own store (the consumer pulls
    it over the data plane) — training never dies on one gray link.
    Returns ``(TAG, kind, ident, total, home_store)``."""
    from ray_tpu._private import api_internal, object_transfer, serialization
    from ray_tpu._private import shm_store as shm_mod
    from ray_tpu._private.ids import ObjectID

    rt = api_internal.require_runtime()
    res = serialization.dumps_adaptive(value, 0)  # max_inline=0: parts
    meta, bufs = res[1], res[2]
    oid_bin = ObjectID.for_put().binary()
    if store and store != rt.store_id:
        ent = rt.resolve_store_addr(store)
        if ent is not None and object_transfer.peer_accepts_puts(ent[1]):
            try:
                kind, ident, total = rt._pusher.push(
                    store, ent[0], oid_bin, meta, bufs, caps=ent[1])
                note("microbatch_pushes")
                return (_DESCR_TAG, kind, ident, total, store)
            except Exception:
                # Dead or stalled-past-deadline link (the pusher already
                # retried with backoff under the PR 14 deadline core):
                # fall through to the local hedge.
                rt.forget_store_addr(store)
    kind, ident, total = shm_mod.put_local(rt.shm, oid_bin, meta, bufs)
    note("microbatch_pushes")
    return (_DESCR_TAG, kind, ident, total, rt.store_id)


def _load_value(descr: tuple):
    """Descriptor -> value.  Locally-homed segments attach by name/path,
    deserialize, COPY (loaded arrays may be zero-copy views into the
    mapping), and unlink; hedged remote-homed ones pull over the data
    plane through the runtime's materialize path.  A segment already
    consumed (an at-least-once replay re-reading its input) raises —
    the driver's step re-drive is the recovery path."""
    import os

    from ray_tpu._private import api_internal, protocol

    _tag, kind, ident, total, store = descr
    rt = api_internal.require_runtime()
    if store == rt.store_id:
        if kind == "spilled":
            seg = rt.shm.attach_path(ident)
            try:
                value = _copy_arrays(seg.deserialize())
            finally:
                seg.close()
            try:
                os.unlink(ident)
            except OSError:
                pass
        else:
            seg = rt.shm.attach(ident)
            try:
                value = _copy_arrays(seg.deserialize())
            finally:
                seg.close()
            # Owner-routed free: releases the node byte accounting the
            # pusher's reserve_put charged.
            rt.shm.unlink(ident, total)
        return value
    pkind = protocol.SHM if kind == "shm" else protocol.SPILLED
    return rt.materialize((pkind, ident, total, store))


def _copy_arrays(tree):
    import jax

    return jax.tree.map(
        lambda v: np.array(v, copy=True) if isinstance(v, np.ndarray)
        else v, tree)


def _split_microbatches(x, num_microbatches: int) -> List[Any]:
    """Split every leaf along axis 0 into ``num_microbatches`` equal
    pieces (the GPipe micro-batching contract)."""
    import jax

    def check(v):
        if v.shape[0] % num_microbatches:
            raise ValueError(
                f"batch {v.shape[0]} % microbatches {num_microbatches}"
                " != 0")

    jax.tree.map(check, x)
    return [jax.tree.map(
        lambda v: v[i * (v.shape[0] // num_microbatches):
                    (i + 1) * (v.shape[0] // num_microbatches)], x)
        for i in range(num_microbatches)]


# ------------------------------------------------------------ the actor --
@ray.remote
class PipelineStage:
    """One pipeline stage: owns its stage's params (and optimizer
    slice), computes micro-batch forwards/backwards, pushes activations
    downstream and activation-grads upstream over the striped put path.

    Single-threaded by the actor model; FIFO call order from the driver
    IS the stage's 1F1B schedule.  Backward rematerializes the forward
    (``jax.vjp`` from the stashed INPUT) — the stash is then plain
    arrays, checkpointable and bounded at ``pp`` entries in steady
    state."""

    def __init__(self, stage_fn: Callable, loss_fn: Optional[Callable],
                 params, optimizer, stage_idx: int, num_stages: int,
                 num_microbatches: int):
        import jax

        self._stage_fn = stage_fn
        self._loss_fn = loss_fn
        self._idx = stage_idx
        self._pp = num_stages
        self._M = num_microbatches
        self._params = jax.tree.map(jax.numpy.asarray, params)
        self._optimizer = optimizer
        self._opt_state = optimizer.init(self._params)
        self._applied_step = -1
        self._last_metrics: Dict[str, float] = {}
        self._next_store = ""
        self._prev_store = ""
        self._stash: Dict[int, Any] = {}
        self._accum = None
        self._loss_sum = 0.0
        self._busy_s = 0.0

        self._jit_fwd = jax.jit(stage_fn)

        def _bwd(p, x, g):
            _, vjp = jax.vjp(stage_fn, p, x)
            return vjp(g)

        self._jit_bwd = jax.jit(_bwd)
        if loss_fn is not None:

            def _loss_bwd(p, x, target):
                def f(pp_, xx):
                    return loss_fn(stage_fn(pp_, xx), target)

                return jax.value_and_grad(f, argnums=(0, 1))(p, x)

            self._jit_loss_bwd = jax.jit(_loss_bwd)

    # -- wiring ----------------------------------------------------------
    def get_store(self) -> str:
        from ray_tpu._private import api_internal

        return api_internal.require_runtime().store_id

    def set_links(self, next_store: str, prev_store: str) -> bool:
        self._next_store = next_store
        self._prev_store = prev_store
        return True

    def ping(self) -> bool:
        return True

    def pid(self) -> int:
        import os

        return os.getpid()

    # -- schedule body ----------------------------------------------------
    def forward(self, mb: int, x, target=None):
        """Compute this stage's forward for micro-batch ``mb``.  ``x``
        is a raw array pytree on stage 0 (driver-supplied) or the
        upstream stage's push descriptor; the LAST stage also receives
        its micro-batch ``target`` and returns None (its backward seeds
        from the loss), every other stage pushes its activation into
        the successor's store and returns the descriptor."""
        import jax

        if _is_descr(x):
            x = _load_value(x)
        x = jax.tree.map(jax.numpy.asarray, x)
        t0 = time.perf_counter()
        if self._idx == self._pp - 1:
            # Loss stage: defer compute to backward (value_and_grad
            # rematerializes the forward) — stash input + target.
            self._stash[mb] = (x, target)
            self._busy_s += time.perf_counter() - t0
            return None
        y = self._jit_fwd(self._params, x)
        jax.block_until_ready(y)
        self._busy_s += time.perf_counter() - t0
        self._stash[mb] = (x, None)
        return _push_value(
            jax.tree.map(np.asarray, y), self._next_store)

    def backward(self, mb: int, g=None):
        """Compute this stage's backward for micro-batch ``mb``:
        rematerialize the forward from the stashed input, accumulate
        the param gradient, push the input gradient upstream (stages
        > 0) and return its descriptor."""
        import jax

        if mb not in self._stash:
            raise RuntimeError(
                f"stage {self._idx}: no stashed activation for "
                f"microbatch {mb} (replayed past a consumed input)")
        x, target = self._stash.pop(mb)
        if self._idx == self._pp - 1:
            t0 = time.perf_counter()
            loss, (gp, gx) = self._jit_loss_bwd(self._params, x, target)
            jax.block_until_ready(loss)
            self._busy_s += time.perf_counter() - t0
            self._loss_sum += float(loss)
        else:
            if _is_descr(g):
                g = _load_value(g)
            g = jax.tree.map(jax.numpy.asarray, g)
            t0 = time.perf_counter()
            gp, gx = self._jit_bwd(self._params, x, g)
            jax.block_until_ready(gp)
            self._busy_s += time.perf_counter() - t0
        self._accum = gp if self._accum is None else jax.tree.map(
            jax.numpy.add, self._accum, gp)
        if self._idx == 0:
            return None
        return _push_value(
            jax.tree.map(np.asarray, gx), self._prev_store)

    def apply_grads(self, step: int) -> Dict[str, float]:
        """Optimizer step over the accumulated gradients / M.
        IDEMPOTENT per ``step``: a re-driven loss step (the driver's
        replay safety net) skips stages that already applied and
        returns their cached metrics — params advance exactly once."""
        import jax
        import optax

        if self._applied_step >= step:
            return dict(self._last_metrics)
        if self._accum is None:
            raise RuntimeError(
                f"stage {self._idx}: apply_grads({step}) with no "
                "accumulated gradients")
        grads = jax.tree.map(lambda gacc: gacc / self._M, self._accum)
        updates, self._opt_state = self._optimizer.update(
            grads, self._opt_state, self._params)
        self._params = optax.apply_updates(self._params, updates)
        jax.block_until_ready(self._params)
        self._applied_step = step
        metrics = {"step": float(step),
                   "grad_norm": float(optax.global_norm(grads))}
        if self._idx == self._pp - 1:
            metrics["loss"] = self._loss_sum / self._M
        self._accum = None
        self._stash.clear()
        self._loss_sum = 0.0
        self._last_metrics = metrics
        return dict(metrics)

    def reset_step(self, step: int) -> bool:
        """Clear partial state for a re-drive of ``step``.  A stage
        that already applied ``step`` keeps its post-step params (its
        apply_grads will no-op); every other stage drops its stash and
        accumulators so the re-driven schedule starts clean."""
        if self._applied_step < step:
            self._stash.clear()
            self._accum = None
            self._loss_sum = 0.0
        return True

    # -- introspection ----------------------------------------------------
    def get_params(self):
        import jax

        return jax.tree.map(np.asarray, jax.device_get(self._params))

    def get_grad_accum(self):
        """Test hook: the raw (unscaled) gradient accumulator."""
        import jax

        if self._accum is None:
            return None
        return jax.tree.map(np.asarray, jax.device_get(self._accum))

    def stage_stats(self) -> Dict[str, float]:
        return {"busy_s": self._busy_s, "applied_step": self._applied_step,
                "stash": len(self._stash)}

    # -- checkpoint hooks (PR 9) ------------------------------------------
    def __ray_save__(self):
        import jax

        to_np = lambda t: jax.tree.map(np.asarray, jax.device_get(t))
        return {
            "params": to_np(self._params),
            "opt_state": to_np(self._opt_state),
            "applied_step": self._applied_step,
            "last_metrics": dict(self._last_metrics),
            "links": (self._next_store, self._prev_store),
            "stash": {mb: to_np(v) for mb, v in self._stash.items()},
            "accum": None if self._accum is None else to_np(self._accum),
            "loss_sum": self._loss_sum,
            "busy_s": self._busy_s,
        }

    def __ray_restore__(self, state):
        import jax

        self._params = jax.tree.map(jax.numpy.asarray, state["params"])
        self._opt_state = jax.tree.map(
            lambda v: jax.numpy.asarray(v) if isinstance(v, np.ndarray)
            else v, state["opt_state"])
        self._applied_step = state["applied_step"]
        self._last_metrics = state["last_metrics"]
        self._next_store, self._prev_store = state["links"]
        self._stash = dict(state["stash"])
        self._accum = state["accum"]
        self._loss_sum = state["loss_sum"]
        self._busy_s = state["busy_s"]
        note("stage_restarts")


# ------------------------------------------------------------ the driver --
class PipelineTrainer:
    """Drive ``num_stages`` PipelineStage actors through the 1F1B
    schedule, one ``step(x, target)`` per optimizer step.

    The driver never blocks inside a step's schedule: it submits every
    stage's call sequence in dependency order (a call becomes eligible
    the moment its upstream result ref exists), passing descriptor refs
    as args — per-actor FIFO then realizes 1F1B, and the only waits are
    on the per-stage ``apply_grads`` barriers at the end.

    ``schedule="fill_drain"`` instead drives synchronous wave barriers
    (all M forwards of stage s complete before stage s+1 starts — the
    GPipe fill/drain shape with transfers ON the critical path): the
    baseline 1F1B's gradients are held bitwise equal to.

    Falls back to the byte-identical single-host path (same micro-batch
    loss/grad accumulation in one jitted program) when
    ``config.distributed_training`` is off, a single stage is given, or
    no runtime is initialized.
    """

    def __init__(self, stage_fn: Callable, loss_fn: Callable,
                 stage_params: Sequence[Any], *, optimizer=None,
                 num_microbatches: int = 0, distributed: Optional[bool]
                 = None, max_restarts: int = 2, max_task_retries: int = -1,
                 max_redrives: int = 2, num_cpus_per_stage: int = 1):
        import optax

        self._stage_fn = stage_fn
        self._loss_fn = loss_fn
        self._pp = len(stage_params)
        if self._pp < 1:
            raise ValueError("need at least one stage")
        cfg = active_config()
        self._M = (num_microbatches or cfg.pipeline_microbatches
                   or 2 * self._pp)
        self._optimizer = optimizer or optax.sgd(1e-2)
        self._step_num = 0
        self._max_redrives = max_redrives
        if distributed is None:
            distributed = cfg.distributed_training
        self._distributed = bool(
            distributed and self._pp > 1 and self._runtime_up())
        if self._distributed:
            self._stages = [
                PipelineStage.options(
                    num_cpus=num_cpus_per_stage,
                    max_restarts=max_restarts,
                    max_task_retries=max_task_retries,
                ).remote(stage_fn, loss_fn if s == self._pp - 1 else None,
                         stage_params[s], self._optimizer, s, self._pp,
                         self._M)
                for s in range(self._pp)]
            self._wire_links()
        else:
            self._local_params = list(stage_params)
            self._local_step = self._make_local_step()

    @staticmethod
    def _runtime_up() -> bool:
        from ray_tpu._private import api_internal

        try:
            api_internal.require_runtime()
            return True
        except Exception:
            return False

    # -- wiring -----------------------------------------------------------
    def _wire_links(self):
        stores = ray.get(_bulk_submit(
            [(s.get_store, (), None) for s in self._stages]), timeout=60)
        calls = []
        for i, s in enumerate(self._stages):
            nxt = stores[i + 1] if i + 1 < self._pp else ""
            prv = stores[i - 1] if i > 0 else ""
            calls.append((s.set_links, (nxt, prv), None))
        ray.get(_bulk_submit(calls), timeout=60)

    # -- the 1F1B schedule -------------------------------------------------
    def _stage_sched(self, s: int):
        """Per-stage 1F1B call order: warmup ``min(pp-1-s, M)``
        forwards, steady-state F/B pairs, cooldown backwards — at most
        ``pp`` live stashes per stage."""
        w = min(self._pp - 1 - s, self._M)
        seq = [("F", i) for i in range(w)]
        for i in range(self._M - w):
            seq.append(("F", w + i))
            seq.append(("B", i))
        seq.extend(("B", i) for i in range(self._M - w, self._M))
        return seq

    def _submit_1f1b(self, x_mbs, t_mbs):
        pp, M = self._pp, self._M
        fwd = [[None] * M for _ in range(pp)]
        bwd = [[None] * M for _ in range(pp)]
        scheds = [self._stage_sched(s) for s in range(pp)]
        pos = [0] * pp
        while any(pos[s] < len(scheds[s]) for s in range(pp)):
            progressed = False
            for s in range(pp):
                while pos[s] < len(scheds[s]):
                    kind, i = scheds[s][pos[s]]
                    if kind == "F":
                        if s > 0 and fwd[s - 1][i] is None:
                            break
                        if s == 0:
                            arg = x_mbs[i]
                        else:
                            arg = fwd[s - 1][i]
                        tgt = t_mbs[i] if s == pp - 1 else None
                        fwd[s][i] = self._stages[s].forward.remote(
                            i, arg, tgt)
                    else:
                        if s < pp - 1 and bwd[s + 1][i] is None:
                            break
                        arg = bwd[s + 1][i] if s < pp - 1 else None
                        bwd[s][i] = self._stages[s].backward.remote(i, arg)
                    pos[s] += 1
                    progressed = True
            assert progressed, "1F1B schedule deadlocked"
        return bwd

    def _submit_fill_drain(self, x_mbs, t_mbs):
        """Synchronous GPipe fill/drain: per-stage wave barriers, so
        every activation transfer sits on the critical path (the
        baseline 1F1B is compared with)."""
        pp, M = self._pp, self._M
        prev = None
        for s in range(pp):
            refs = []
            for i in range(M):
                arg = x_mbs[i] if s == 0 else prev[i]
                tgt = t_mbs[i] if s == pp - 1 else None
                refs.append(self._stages[s].forward.remote(i, arg, tgt))
            ray.get(list(refs), timeout=300)  # wave barrier
            prev = refs
        bwd = [[None] * M for _ in range(pp)]
        prev = [None] * M
        for s in range(pp - 1, -1, -1):
            refs = [self._stages[s].backward.remote(i, prev[i])
                    for i in range(M)]
            ray.get(list(refs), timeout=300)  # wave barrier
            bwd[s] = refs
            prev = refs
        return bwd

    # -- stepping ----------------------------------------------------------
    def step(self, x, target, schedule: str = "1f1b") -> Dict[str, float]:
        """One optimizer step over batch ``(x, target)`` split into M
        micro-batches.  On any stage failure the whole step re-drives
        (bounded by ``max_redrives``); ``apply_grads`` idempotency keeps
        params exactly once-advanced."""
        if not self._distributed:
            return self._step_local(x, target)
        x_mbs = [_as_np(v) for v in _split_microbatches(x, self._M)]
        t_mbs = [_as_np(v) for v in _split_microbatches(target, self._M)]
        step = self._step_num
        last_err = None
        for _attempt in range(self._max_redrives + 1):
            try:
                if schedule == "fill_drain":
                    self._submit_fill_drain(x_mbs, t_mbs)
                else:
                    self._submit_1f1b(x_mbs, t_mbs)
                applies = _bulk_submit(
                    [(s.apply_grads, (step,), None) for s in self._stages])
                metrics = ray.get(list(applies), timeout=300)
                self._step_num += 1
                return metrics[-1]
            except Exception as e:  # noqa: BLE001 — any stage fault
                last_err = e
                self._recover(step)
        raise last_err

    def _recover(self, step: int):
        """Post-fault settle: wait out restarts (ping), refresh the
        store wiring (a restarted stage may live on a new node), and
        clear partial step state on stages that have not applied."""
        for s in self._stages:
            try:
                ray.get(s.ping.remote(), timeout=120)
            except Exception:
                pass
        try:
            self._wire_links()
            ray.get(_bulk_submit(
                [(s.reset_step, (step,), None) for s in self._stages]),
                timeout=60)
        except Exception:
            pass

    # -- single-host fallback ----------------------------------------------
    def _make_local_step(self):
        import jax
        import optax

        stage_fn, loss_fn, M = self._stage_fn, self._loss_fn, self._M

        def total_loss(params_list, x, target):
            x_mbs = _split_microbatches(x, M)
            t_mbs = _split_microbatches(target, M)
            total = 0.0
            for x_mb, t_mb in zip(x_mbs, t_mbs):
                y = x_mb
                for p in params_list:
                    y = stage_fn(p, y)
                total = total + loss_fn(y, t_mb)
            return total / M

        def step(params_list, opt_state, x, target):
            loss, grads = jax.value_and_grad(total_loss)(
                params_list, x, target)
            updates, opt_state = self._optimizer.update(
                grads, opt_state, params_list)
            params_list = optax.apply_updates(params_list, updates)
            return params_list, opt_state, loss, optax.global_norm(grads)

        self._local_opt_state = self._optimizer.init(
            list(self._local_params))
        return jax.jit(step)

    def _step_local(self, x, target) -> Dict[str, float]:
        self._local_params, self._local_opt_state, loss, gn = \
            self._local_step(list(self._local_params),
                             self._local_opt_state, x, target)
        metrics = {"step": float(self._step_num), "loss": float(loss),
                   "grad_norm": float(gn)}
        self._step_num += 1
        return metrics

    # -- introspection / lifecycle ----------------------------------------
    @property
    def distributed(self) -> bool:
        return self._distributed

    @property
    def num_microbatches(self) -> int:
        return self._M

    def get_stage_params(self) -> List[Any]:
        import jax

        if not self._distributed:
            return [jax.tree.map(np.asarray, jax.device_get(p))
                    for p in self._local_params]
        return ray.get(_bulk_submit(
            [(s.get_params, (), None) for s in self._stages]), timeout=120)

    def stage_stats(self) -> List[Dict[str, float]]:
        if not self._distributed:
            return []
        return ray.get(_bulk_submit(
            [(s.stage_stats, (), None) for s in self._stages]), timeout=60)

    def stage_pids(self) -> List[int]:
        if not self._distributed:
            return []
        return ray.get(_bulk_submit(
            [(s.pid, (), None) for s in self._stages]), timeout=60)

    def shutdown(self):
        if not self._distributed:
            return
        for s in self._stages:
            try:
                ray.kill(s)
            except Exception:
                pass


def _as_np(tree):
    import jax

    return jax.tree.map(np.asarray, tree)
