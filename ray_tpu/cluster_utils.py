"""Multi-node cluster testing utilities.

Reference analog: ``python/ray/cluster_utils.py:99`` — ``Cluster`` boots a
real multi-node cluster on one machine (each ``add_node`` starts a separate
raylet + object store sharing the host) so multi-node scheduling, transfer
and failover logic run with no real cluster.

Three node/head flavours:

- ``add_node()`` — in-process ``NodeState`` (shares the head's object
  store); scheduler-visible only.  Cheapest, used by most tests.
- ``add_node(external=True)`` — a REAL ``node_agent`` subprocess
  (_private/node_agent.py) with its OWN shm directory, registering over
  TCP.  Workers leased there run in processes spawned by the agent, and
  objects move between stores through the transfer path — the honest
  multi-host simulation.
- ``Cluster(external_head=True)`` — the HEAD itself runs as a
  subprocess (_private/head_main.py) on a fixed port/authkey with GCS
  snapshotting armed, and this process attaches as a CLIENT.  This is
  the head-failover drill geometry: ``kill_head()`` SIGKILLs it,
  ``restart_head()`` re-runs it with ``gcs_restore`` — surviving
  agents, workers and this client reconnect-and-replay across the blip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, Optional

import ray_tpu


class Cluster:
    def __init__(self, head_num_cpus: int = 2, head_num_tpus: int = 0,
                 external_head: bool = False,
                 head_env: Optional[Dict[str, str]] = None,
                 **init_kwargs):
        self._agents: Dict[str, subprocess.Popen] = {}
        self._agent_dirs: list = []
        self.head_proc: Optional[subprocess.Popen] = None
        self._external_head = external_head
        self._head_tail: list = []
        if not external_head:
            self.rt = ray_tpu.init(num_cpus=head_num_cpus,
                                   num_tpus=head_num_tpus, **init_kwargs)
            self._head_address = self.rt.tcp_address
            self._authkey_hex = self.rt._authkey.hex()
            return
        import socket

        sysconf = dict(init_kwargs.pop("_system_config", None) or {})
        if init_kwargs:
            raise ValueError(
                f"external_head supports configuration only via "
                f"_system_config / head_env; got {sorted(init_kwargs)}")
        if not sysconf.get("listen_port"):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                sysconf["listen_port"] = s.getsockname()[1]
        sysconf.setdefault("authkey_hex", os.urandom(16).hex())
        if not sysconf.get("gcs_snapshot_path"):
            fd, snap = tempfile.mkstemp(prefix="ray_tpu_gcs_")
            os.close(fd)
            os.unlink(snap)  # the head writes it atomically
            sysconf["gcs_snapshot_path"] = snap
        sysconf.setdefault("gcs_snapshot_interval_s", 0.2)
        self._head_cfg = sysconf
        self._head_num_cpus = head_num_cpus
        self._head_num_tpus = head_num_tpus
        self._head_env = dict(head_env or {})
        self._start_head(restore=False)
        self._head_address = f"tcp://127.0.0.1:{sysconf['listen_port']}"
        self._authkey_hex = sysconf["authkey_hex"]
        self.rt = ray_tpu.init(address=self._head_address,
                               _authkey=self._authkey_hex)

    # ------------------------------------------------------ head lifecycle
    def _start_head(self, restore: bool):
        cfg = dict(self._head_cfg)
        cfg["gcs_restore"] = restore
        env = dict(os.environ)
        env.update(self._head_env)
        env["JAX_PLATFORMS"] = "cpu"
        env["RAY_TPU_HEAD_NUM_CPUS"] = str(self._head_num_cpus)
        env["RAY_TPU_HEAD_NUM_TPUS"] = str(self._head_num_tpus)
        env["RAY_TPU_HEAD_SYSTEM_CONFIG"] = json.dumps(cfg)
        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "ray_tpu._private.head_main"],
            env=env, cwd=pkg_root, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT)
        deadline = time.time() + 60
        while time.time() < deadline:
            line = proc.stdout.readline()
            if b"RAY_TPU_HEAD_READY" in line:
                break
            if proc.poll() is not None:
                raise AssertionError(
                    f"head process exited rc={proc.poll()}: {line!r}")
        else:
            proc.kill()
            raise TimeoutError("head process never printed READY")
        # Keep the pipe drained (worker-log reprints would otherwise
        # fill it and wedge the head); retain a bounded tail for
        # debugging.
        tail = self._head_tail

        def _drain(stream=proc.stdout):
            for ln in iter(stream.readline, b""):
                tail.append(ln)
                del tail[:-200]

        threading.Thread(target=_drain, daemon=True,
                         name="ray_tpu-head-drain").start()
        self.head_proc = proc

    @property
    def head_pid(self) -> Optional[int]:
        return self.head_proc.pid if self.head_proc is not None else None

    def kill_head(self) -> Optional[int]:
        """SIGKILL the external head — no atexit, no final snapshot, no
        graceful anything: the ``os._exit``-class crash the failover
        battery drills.  Returns the dead pid."""
        if self.head_proc is None:
            raise RuntimeError("kill_head needs Cluster(external_head"
                               "=True)")
        pid = self.head_proc.pid
        self.head_proc.kill()
        self.head_proc.wait(timeout=30)
        return pid

    def restart_head(self) -> Optional[int]:
        """Re-run the head on the SAME port/authkey with gcs_restore:
        agents, workers, and this cluster's client reconnect on their
        own.  Returns the new head pid."""
        if not self._external_head:
            raise RuntimeError("restart_head needs Cluster(external_head"
                               "=True)")
        self._start_head(restore=True)
        return self.head_proc.pid

    # ------------------------------------------------------------- nodes
    def add_node(self, num_cpus: float = 1.0, num_tpus: float = 0.0,
                 resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None,
                 external: bool = False, wait: bool = True,
                 env_overrides: Optional[Dict[str, str]] = None):
        if not external:
            return self.rt.add_node(num_cpus=num_cpus, num_tpus=num_tpus,
                                    resources=resources, labels=labels)
        r = {"CPU": float(num_cpus)}
        if num_tpus:
            r["TPU"] = float(num_tpus)
        if resources:
            r.update(resources)
        shm_dir = tempfile.mkdtemp(prefix="ray_tpu_node_")
        self._agent_dirs.append(shm_dir)
        env = dict(os.environ)
        if env_overrides:
            env.update(env_overrides)
        env.update({
            "RAY_TPU_HEAD_ADDRESS": self._head_address,
            "RAY_TPU_AUTHKEY": self._authkey_hex,
            "RAY_TPU_AGENT_RESOURCES": json.dumps(r),
            "RAY_TPU_AGENT_SHM_DIR": shm_dir,
            "RAY_TPU_AGENT_LABELS": json.dumps(labels or {}),
        })
        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_agent"],
            env=env, cwd=pkg_root)
        before = {n["node_id"] for n in self.rt.list_nodes()}
        if wait:
            deadline = time.time() + 30
            while time.time() < deadline:
                now = [n for n in self.rt.list_nodes()
                       if n["node_id"] not in before and n["alive"]]
                if now:
                    node_id = now[0]["node_id"]
                    self._agents[node_id] = proc
                    return node_id
                time.sleep(0.05)
            raise TimeoutError("node agent did not register within 30s")
        return None

    def remove_node(self, node_id):
        from ray_tpu._private.ids import NodeID
        if isinstance(node_id, str):
            nid = NodeID(bytes.fromhex(node_id))
        else:
            nid = node_id
        self.rt.remove_node(nid)
        proc = self._agents.pop(
            node_id if isinstance(node_id, str) else node_id.hex(), None)
        if proc is not None:
            try:
                proc.wait(timeout=5)
            except Exception:
                proc.kill()

    def kill_agent(self, node_id: str):
        """Hard-kill a node agent process (chaos: reference
        test_utils.py:1687 kill_raylet)."""
        proc = self._agents.pop(node_id, None)
        if proc is not None:
            proc.kill()
            proc.wait(timeout=5)

    def shutdown(self):
        # Snapshot: a concurrent remove_node (an autoscaler's off-thread
        # scale-down concluding mid-teardown) pops from _agents.
        for proc in list(self._agents.values()):
            try:
                proc.terminate()
            except Exception:
                pass
        ray_tpu.shutdown()
        if self.head_proc is not None:
            try:
                self.head_proc.terminate()
                self.head_proc.wait(timeout=10)
            except Exception:
                try:
                    self.head_proc.kill()
                except Exception:
                    pass
            snap = self._head_cfg.get("gcs_snapshot_path")
            if snap:
                try:
                    os.unlink(snap)
                except OSError:
                    pass
        for proc in list(self._agents.values()):
            try:
                proc.wait(timeout=3)
            except Exception:
                proc.kill()
        self._agents.clear()
        import shutil
        for d in self._agent_dirs:
            shutil.rmtree(d, ignore_errors=True)
