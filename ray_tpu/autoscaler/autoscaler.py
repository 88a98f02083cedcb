"""StandardAutoscaler: load signals -> node-count decisions.

Reference: ``python/ray/autoscaler/_private/autoscaler.py:168``
(StandardAutoscaler.update: read load, bin-pack demand onto node types,
launch/terminate) + ``resource_demand_scheduler.py`` (first-fit packing).
Condensed: demand comes from the runtime's queued-but-unplaced shapes
(`pending_resource_demand` — which since the elastic-pods PR also
carries parked client-lease requests, the lease-starvation signal the
task queues never show), utilization from `node_activity`, and the loop
either runs on a timer, is stepped manually (`update()`), or is woken
early by a serve-controller scale event (the head's "serve_scale"
pubsub topic).

Slice-atomicity is inherited from the provider: one launch == one whole
TPU slice; scale-down terminates whole idle slices only — and routes
through the head's drain protocol (``Runtime.drain_node``: leases
revoked, restartable actors checkpointed to a surviving store, small
sole-copy objects migrated) before ``terminate_node``, so a planned
departure is never a surprise death.  Spot/preemptible node types
(``"spot": True`` in the type spec) are preferred when they fit; after
``spot_fallback_threshold`` observed preemptions of a type the planner
falls back to its on-demand peers.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu.autoscaler.node_provider import NodeProvider


def _fits(avail: Dict[str, float], shape: Dict[str, float]) -> bool:
    return all(avail.get(k, 0.0) >= v - 1e-9 for k, v in shape.items())


def _take(avail: Dict[str, float], shape: Dict[str, float]):
    for k, v in shape.items():
        avail[k] = avail.get(k, 0.0) - v


class StandardAutoscaler:
    def __init__(self, runtime, provider: NodeProvider,
                 idle_timeout_s: float = 10.0,
                 update_interval_s: float = 2.0,
                 spot_fallback_threshold: Optional[int] = None,
                 drain_deadline_s: Optional[float] = None):
        self._rt = runtime
        self.provider = provider
        self.idle_timeout_s = idle_timeout_s
        self.update_interval_s = update_interval_s
        self._idle_since: Dict[str, float] = {}
        # Launches issued but whose node has not registered alive yet:
        # counted against caps and capacity so an async provider cannot
        # be asked twice for the same demand (reference: the pending-
        # launch accounting in StandardAutoscaler).
        self._pending_launches: Dict[str, tuple] = {}  # id -> (type, ts)
        self._launch_timeout_s = 120.0
        # Every node this scaler launched that is still provider-alive:
        # id -> type.  A tracked node that turns up dead WITHOUT us
        # terminating it was preempted — the per-type spot accounting.
        self._tracked: Dict[str, str] = {}
        cfg = getattr(runtime, "config", None)
        if cfg is None:
            from ray_tpu._private.config import GLOBAL_CONFIG as cfg
        self._drain_deadline_s = (
            float(drain_deadline_s) if drain_deadline_s is not None
            else float(getattr(cfg, "drain_deadline_s", 10.0)))
        self.spot_fallback_threshold = (
            int(spot_fallback_threshold)
            if spot_fallback_threshold is not None
            else int(getattr(cfg, "spot_fallback_threshold", 2)))
        # Observability (satellite: the silent monitor loop): errors are
        # counted + rate-limit-logged, never swallowed; surfaced next to
        # the elastic counters via stats().
        self._errors = 0
        self._last_err_log = 0.0
        self._err_log_interval_s = 5.0
        self._preemptions: Dict[str, int] = {}   # node_type -> count
        self._drains_requested = 0
        self._drains_completed = 0
        self._serve_scale_events = 0
        # Nodes whose scale-down drain is running off-thread: skipped by
        # the idle loop until the drain concludes and terminates them.
        self._draining_down: set = set()
        # One reconcile at a time (satellite): the background loop, a
        # manual update(), and the serve-event trigger must not
        # interleave — two concurrent ticks each see the same
        # unfulfilled demand and both launch for it.
        self._update_lock = threading.Lock()
        self._wake = threading.Event()
        self._stopped = False
        self._gen = 0
        self._thread: Optional[threading.Thread] = None
        self._listener_on = False

    # ------------------------------------------------------------- policy
    def _unfulfilled_demand(self) -> List[Dict[str, float]]:
        """Queued shapes that the current cluster cannot place even when
        fully free — first-fit over every alive node's TOTAL resources
        (reference: infeasible + backlog demand fed to the bin-packer).
        Draining nodes take no new placements, so they contribute no
        capacity here."""
        demand = self._rt.pending_resource_demand()
        if not demand:
            return []
        free = [dict(n["resources"]) for n in self._rt.node_activity()
                if n["alive"] and not n.get("draining")]
        # Nodes still booting count as capacity-to-be.
        for _nid, (ntype, _ts) in self._pending_launches.items():
            free.append(dict(self.provider.node_resources(ntype)))
        unfulfilled = []
        for shape in sorted(demand, key=lambda s: -sum(s.values())):
            for avail in free:
                if _fits(avail, shape):
                    _take(avail, shape)
                    break
            else:
                unfulfilled.append(shape)
        return unfulfilled

    def _type_order(self) -> List[str]:
        """Launch-preference order over the provider's catalog: healthy
        SPOT types first (cheap capacity while the preemption rate is
        tolerable), then on-demand, then spot types past the fallback
        threshold — still eligible when nothing else fits, but no
        longer preferred (reference: the spot-fallback behavior of
        cloud autoscaler node-type selection)."""
        def rank(t: str) -> int:
            if not self.provider.is_spot(t):
                return 1
            if self._preemptions.get(t, 0) >= self.spot_fallback_threshold:
                return 2
            return 0

        return sorted(self.provider.node_types, key=rank)

    def _plan_launches(self, unfulfilled) -> Dict[str, int]:
        """First-fit-decreasing the unfulfilled shapes onto fresh nodes of
        each type (reference: resource_demand_scheduler.get_nodes_for)."""
        launches: Dict[str, int] = {}
        pools: List[Dict[str, float]] = []
        counts = {t: len([n for n in self.provider.non_terminated_nodes()
                          if self.provider.node_type_of(n) == t])
                  + len([1 for _ntype, _ in self._pending_launches.values()
                         if _ntype == t])
                  for t in self.provider.node_types}
        for shape in unfulfilled:
            placed = False
            for avail in pools:
                if _fits(avail, shape):
                    _take(avail, shape)
                    placed = True
                    break
            if placed:
                continue
            # pick the first (spot-preferred) type that can hold the shape
            for t in self._type_order():
                res = self.provider.node_resources(t)
                if _fits(res, shape) and \
                        counts[t] + launches.get(t, 0) \
                        < self.provider.max_workers(t):
                    avail = dict(res)
                    _take(avail, shape)
                    pools.append(avail)
                    launches[t] = launches.get(t, 0) + 1
                    break
            # shapes no type can hold stay infeasible (reference: warn)
        return launches

    def _note_preemptions(self, alive_ids):
        """Per-type preemption accounting: a tracked node that died
        without us terminating it was taken away (agent SIGKILL, spot
        reclaim).  Counted against its type for the fallback policy,
        then cleaned out of the provider's books (terminate_node on a
        dead node is idempotent bookkeeping, as on a real cloud)."""
        for nid, ntype in list(self._tracked.items()):
            if nid in alive_ids or nid in self._pending_launches \
                    or nid in self._draining_down:
                continue
            self._tracked.pop(nid, None)
            self._preemptions[ntype] = self._preemptions.get(ntype, 0) + 1
            self._idle_since.pop(nid, None)
            try:
                self.provider.terminate_node(nid)
            except Exception:
                pass

    def _scale_down(self, nid: str):
        """Idle scale-down — through the drain protocol (leases revoked,
        actors checkpointed, small sole-copy objects migrated, agent
        released cleanly), with ``terminate_node`` as both the
        completion and the hard fallback.  The drain runs OFF-THREAD: a
        reconcile tick must stay reactive (a serve scale-up event cannot
        wait out a drain deadline), so update() reports the node
        terminated now and the terminate itself follows the drain's
        conclusion.  A runtime without ``drain_node`` gets the inline
        bare terminate."""
        # Planned departure: never let _note_preemptions count it.
        self._tracked.pop(nid, None)
        drain = getattr(self._rt, "drain_node", None)
        if drain is None:
            self.provider.terminate_node(nid)
            return
        self._drains_requested += 1
        self._draining_down.add(nid)

        def run():
            try:
                try:
                    drained = bool(drain(nid, self._drain_deadline_s,
                                         "scale_down"))
                except Exception:
                    drained = False
                if drained:
                    # Off-thread += races a concurrent drain's (and the
                    # GIL does not make LOAD/ADD/STORE atomic): count
                    # under the same lock stats() readers already see
                    # consistent state through.
                    with self._update_lock:
                        self._drains_completed += 1
                try:
                    self.provider.terminate_node(nid)
                except Exception:
                    pass
            finally:
                self._draining_down.discard(nid)

        threading.Thread(target=run, daemon=True,
                         name="ray_tpu-scale-down").start()

    def update(self) -> Dict[str, Any]:
        """One reconcile tick: launch for unfulfilled demand, terminate
        slices idle past the timeout.  Returns what it did.  Serialized
        by ``_update_lock`` — the loop, manual callers, and the serve
        trigger can never double-launch against one demand snapshot."""
        with self._update_lock:
            return self._update_locked()

    def _update_locked(self) -> Dict[str, Any]:
        # Drain the serve-event topic (the wake already happened; the
        # events themselves are the observability trail).
        poll = getattr(self._rt, "poll_events", None)
        if poll is not None:
            try:
                self._serve_scale_events += len(poll("serve_scale"))
            except Exception:
                pass
        # Reconcile pending launches first: registered or timed out.
        now0 = time.monotonic()
        alive_ids = {a["node_id"] for a in self._rt.node_activity()
                     if a["alive"]}
        for nid in list(self._pending_launches):
            ntype, ts = self._pending_launches[nid]
            if nid in alive_ids:
                self._pending_launches.pop(nid, None)
            elif now0 - ts > self._launch_timeout_s:
                # Never came up: cancel it at the provider (a stuck
                # instance left behind both leaks money and keeps
                # counting against max_workers) and stop counting it
                # against caps/capacity, so the demand it was meant to
                # cover is re-planned — the re-issue happens in the
                # launch pass below.
                self._pending_launches.pop(nid, None)
                self._tracked.pop(nid, None)
                try:
                    self.provider.terminate_node(nid)
                except Exception:
                    pass
        self._note_preemptions(alive_ids)
        launched: List[str] = []
        for node_type, n in self._plan_launches(
                self._unfulfilled_demand()).items():
            for _ in range(n):
                nid = self.provider.create_node(node_type)
                launched.append(nid)
                self._pending_launches[nid] = (node_type, now0)
                self._tracked[nid] = node_type
        # scale-down: whole idle provider nodes only (never the head)
        now = time.monotonic()
        terminated: List[str] = []
        activity = {a["node_id"]: a for a in self._rt.node_activity()}
        # Only SATISFIABLE demand vetoes scale-down: a shape no alive node
        # and no node type could ever hold must not pin idle slices.
        demand_left = [
            shape for shape in self._rt.pending_resource_demand()
            if any(_fits(a["resources"], shape)
                   for a in activity.values()
                   if a["alive"] and not a.get("draining"))
            or any(_fits(self.provider.node_resources(t), shape)
                   for t in self.provider.node_types)]
        for nid in list(self.provider.non_terminated_nodes()):
            a = activity.get(nid)
            if a is None or a["is_head"]:
                continue
            if a.get("draining") or nid in self._draining_down:
                # Already on its way out (our own off-thread scale-down,
                # or a preemption drain the head is running): a second
                # pick here would hard-terminate it mid-migration.
                self._idle_since.pop(nid, None)
                continue
            if a["busy"] or demand_left:
                self._idle_since.pop(nid, None)
                continue
            first_idle = self._idle_since.setdefault(nid, now)
            if now - first_idle >= self.idle_timeout_s:
                self._scale_down(nid)
                self._idle_since.pop(nid, None)
                terminated.append(nid)
        return {"launched": launched, "terminated": terminated}

    def stats(self) -> Dict[str, Any]:
        """Elastic observability: loop errors (satellite: the monitor
        loop no longer swallows them silently), per-type preemption
        counts feeding the spot fallback, drain outcomes, and the
        serve-event trigger count — read next to the head's
        transfer_stats() elastic counters."""
        return {
            "autoscaler_errors": self._errors,
            "preemptions_by_type": dict(self._preemptions),
            "drains_requested": self._drains_requested,
            "drains_completed": self._drains_completed,
            "serve_scale_events": self._serve_scale_events,
            "pending_launches": len(self._pending_launches),
        }

    # -------------------------------------------------------------- loop
    def request_update(self):
        """Wake the background loop for an immediate reconcile (the
        serve-controller scale-event trigger).  No-op without start()."""
        self._wake.set()

    def start(self):
        """Background monitor loop (reference: monitor.py's driver)."""
        if self._thread is not None:
            return
        self._stopped = False
        self._wake.clear()  # a stale stop()-wake must not fire an early tick
        # Serve-event trigger: a controller scale event wakes the loop
        # immediately (the listener only nudges; the tick itself drains
        # the topic and reconciles).  Registered for the loop's
        # lifetime only — stop() unhooks it, so a stopped scaler is not
        # referenced (and woken) by the runtime forever.
        if not self._listener_on:
            add_listener = getattr(self._rt, "add_event_listener", None)
            if add_listener is not None:
                try:
                    add_listener("serve_scale", self.request_update)
                    self._listener_on = True
                except Exception:
                    pass
        self._gen += 1
        gen = self._gen

        def loop():
            # Generation check: a stop()+start() inside one sleep interval
            # must not leave the superseded loop running alongside.
            while not self._stopped and self._gen == gen:
                self._wake.wait(self.update_interval_s)
                self._wake.clear()
                if self._stopped or self._gen != gen:
                    return
                try:
                    self.update()
                except Exception:
                    # Monitor loops must survive anything — but silence
                    # turned real launch failures into "the cluster just
                    # never scales": count every error and log at most
                    # one traceback per interval.
                    self._errors += 1
                    now = time.monotonic()
                    if now - self._last_err_log \
                            >= self._err_log_interval_s:
                        self._last_err_log = now
                        import sys
                        import traceback

                        print("[ray_tpu autoscaler] update failed "
                              f"({self._errors} total):",
                              file=sys.stderr)
                        traceback.print_exc()

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="ray_tpu-autoscaler")
        self._thread.start()

    def stop(self):
        self._stopped = True
        self._gen += 1
        self._wake.set()
        self._thread = None
        if self._listener_on:
            remove = getattr(self._rt, "remove_event_listener", None)
            if remove is not None:
                try:
                    remove("serve_scale", self.request_update)
                except Exception:
                    pass
            self._listener_on = False
