"""Cluster launcher: ``ray_tpu up / down / exec / attach`` over a YAML
config, with pluggable command runners and cloud node providers.

Reference: ``python/ray/autoscaler/_private/commands.py`` (create_or_update
_cluster, teardown_cluster, exec_cluster), ``command_runner.py`` (SSH
command runner), the provider zoo under ``python/ray/autoscaler/_private/``
and the ``ray up/down/attach/exec`` CLI (``scripts.py:1247``).

TPU-native shape: worker nodes are SLICE-ATOMIC (a TPU slice joins as one
node with all chips); the cloud provider is GCP TPU-VM — optionally via
queued resources, the way TPU capacity is actually obtained — driven
through ``gcloud`` subprocesses.  A ``subprocess`` provider launches real
node agents locally so the whole up/exec/down path is testable with no
cloud.

Config (YAML):

    cluster_name: demo
    provider:
      type: subprocess            # or: gcp_tpu
      # gcp_tpu only:
      # project: my-proj
      # zone: us-central2-b
      # accelerator_type: v5litepod-4
      # runtime_version: tpu-ubuntu2204-base
      # queued_resources: true
    head:
      num_cpus: 4
      port: 46001                 # fixed so agents/clients can re-dial
    worker_types:
      v5e-4:
        resources: {CPU: 4, TPU: 4}
        min_workers: 1
        max_workers: 2
    setup_commands: []            # run on each cloud node before the agent
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

from ray_tpu.autoscaler.node_provider import NodeProvider

STATE_DIR = os.path.expanduser("~/.ray_tpu/clusters")


# ---------------------------------------------------------------- runners --
class LocalCommandRunner:
    """Run commands on this machine (subprocess provider / head host)."""

    def run(self, cmd: str, env: Optional[Dict[str, str]] = None,
            timeout: float = 600.0) -> str:
        out = subprocess.run(cmd, shell=True, capture_output=True,
                             text=True, timeout=timeout,
                             env={**os.environ, **(env or {})})
        if out.returncode != 0:
            raise RuntimeError(f"command failed ({cmd!r}): "
                               f"{out.stderr[-1000:]}")
        return out.stdout


class SSHCommandRunner:
    """Run commands on a remote host over ssh (reference:
    command_runner.py SSHCommandRunner — BatchMode so a missing key fails
    fast instead of prompting)."""

    def __init__(self, host: str, user: Optional[str] = None,
                 key_path: Optional[str] = None):
        self._target = f"{user}@{host}" if user else host
        self._opts = ["-o", "StrictHostKeyChecking=no",
                      "-o", "BatchMode=yes",
                      "-o", "ConnectTimeout=15"]
        if key_path:
            self._opts += ["-i", key_path]

    def run(self, cmd: str, env: Optional[Dict[str, str]] = None,
            timeout: float = 600.0) -> str:
        envs = " ".join(f"{k}={v}" for k, v in (env or {}).items())
        full = ["ssh", *self._opts, self._target,
                f"{envs} {cmd}".strip()]
        out = subprocess.run(full, capture_output=True, text=True,
                             timeout=timeout)
        if out.returncode != 0:
            raise RuntimeError(f"ssh {self._target} failed ({cmd!r}): "
                               f"{out.stderr[-1000:]}")
        return out.stdout


# -------------------------------------------------------------- providers --
class SubprocessAgentProvider(NodeProvider):
    """Worker 'nodes' are local ``node_agent`` subprocesses dialing the
    head over TCP — the full multi-node path (registration, remote
    stores, chunked transfer) with no cloud."""

    def __init__(self, node_types: Dict[str, Any], head_address: str,
                 authkey_hex: str):
        self.node_types = node_types
        self._head_address = head_address
        self._authkey_hex = authkey_hex
        self._procs: Dict[str, subprocess.Popen] = {}
        self._types: Dict[str, str] = {}
        self._n = 0

    def create_node(self, node_type: str) -> str:
        spec = self.node_types[node_type]
        r = dict(spec["resources"])
        self._n += 1
        node_id = f"{node_type}-{self._n}-{os.getpid()}"
        env = dict(os.environ,
                   RAY_TPU_HEAD_ADDRESS=self._head_address,
                   RAY_TPU_AUTHKEY=self._authkey_hex,
                   RAY_TPU_AGENT_RESOURCES=json.dumps(r),
                   RAY_TPU_AGENT_LABELS=json.dumps(
                       {"autoscaler_node_type": node_type,
                        "launcher_node_id": node_id}),
                   JAX_PLATFORMS="cpu")
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_agent"],
            env=env)
        self._procs[node_id] = proc
        self._types[node_id] = node_type
        return node_id

    def terminate_node(self, node_id: str) -> None:
        proc = self._procs.pop(node_id, None)
        self._types.pop(node_id, None)
        if proc is not None:
            try:
                proc.terminate()
            except Exception:
                pass

    def non_terminated_nodes(self) -> List[str]:
        return [nid for nid, p in self._procs.items()
                if p.poll() is None]

    def node_type_of(self, node_id: str) -> Optional[str]:
        return self._types.get(node_id)

    def pids(self) -> Dict[str, int]:
        return {nid: p.pid for nid, p in self._procs.items()}


class GCPTpuProvider(NodeProvider):
    """GCP TPU-VM provider driven through ``gcloud`` (reference: the
    _private/gcp provider; TPU-native twist: nodes are whole slices,
    optionally obtained via QUEUED RESOURCES — the production way to get
    TPU capacity — instead of direct create).

    Each created node runs ``setup_commands`` then joins the cluster as
    a node agent (``python -m ray_tpu.scripts agent``)."""

    def __init__(self, node_types: Dict[str, Any], conf: Dict[str, Any],
                 head_address: str, authkey_hex: str,
                 setup_commands: Optional[List[str]] = None):
        import shutil

        if shutil.which("gcloud") is None:
            raise RuntimeError(
                "GCPTpuProvider needs the gcloud CLI on PATH")
        self.node_types = node_types
        self._conf = conf
        self._head_address = head_address
        self._authkey_hex = authkey_hex
        self._setup = list(setup_commands or [])
        self._types: Dict[str, str] = {}
        self._n = 0

    def _gcloud(self, *args: str, timeout: float = 900.0) -> str:
        cmd = ["gcloud", "compute", "tpus", *args,
               f"--project={self._conf['project']}",
               f"--zone={self._conf['zone']}", "--format=json"]
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout)
        if out.returncode != 0:
            raise RuntimeError(
                f"gcloud {' '.join(args)} failed: {out.stderr[-1500:]}")
        return out.stdout

    def create_node(self, node_type: str) -> str:
        self._n += 1
        name = f"raytpu-{self._conf.get('cluster_name', 'c')}-" \
               f"{node_type}-{self._n}"
        acc = self.node_types[node_type].get(
            "accelerator_type", self._conf.get("accelerator_type"))
        rv = self._conf.get("runtime_version", "tpu-ubuntu2204-base")
        if self._conf.get("queued_resources"):
            # Queued resources: capacity arrives asynchronously — the
            # node exists only once the queue grants it, so bootstrap
            # must wait for READY (bounded; a still-queued node is left
            # tracked so `down` releases the queued resource).
            self._gcloud(
                "queued-resources", "create", name,
                f"--node-id={name}", f"--accelerator-type={acc}",
                f"--runtime-version={rv}")
            self._types[name] = node_type  # track BEFORE the wait
            self._wait_ready(name, float(self._conf.get(
                "queued_resources_timeout_s", 1800)))
        else:
            self._gcloud("tpu-vm", "create", name,
                         f"--accelerator-type={acc}",
                         f"--runtime-version={rv}")
            self._types[name] = node_type
        self._bootstrap(name, node_type)
        return name

    def _wait_ready(self, name: str, timeout_s: float):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                node = json.loads(self._gcloud("tpu-vm", "describe",
                                               name))
                if node.get("state") == "READY":
                    return
            except RuntimeError:
                pass  # not materialized yet
            time.sleep(15.0)
        raise RuntimeError(
            f"queued resource {name} not READY after {timeout_s:.0f}s "
            f"(still tracked; `ray_tpu down` releases it)")

    AUTHKEY_REMOTE_PATH = "~/.ray_tpu_authkey"

    def _push_authkey(self, name: str):
        """Deliver the cluster authkey as a 0600 file over scp.  It must
        NEVER ride the remote command line: ``--command="RAY_TPU_CLIENT_
        AUTHKEY=<hex> ..."`` lands the key in the remote shell's argv —
        visible to every local user via ``ps`` and in shell/audit logs
        on the TPU VM."""
        import tempfile

        fd, tmp = tempfile.mkstemp(prefix="rtpu-authkey-")
        try:
            with os.fdopen(fd, "w") as f:
                f.write(self._authkey_hex + "\n")
            subprocess.run(
                ["gcloud", "compute", "tpus", "tpu-vm", "scp", tmp,
                 f"{name}:{self.AUTHKEY_REMOTE_PATH}",
                 f"--project={self._conf['project']}",
                 f"--zone={self._conf['zone']}", "--worker=all"],
                capture_output=True, text=True, timeout=900.0, check=True)
        finally:
            os.unlink(tmp)

    def _bootstrap(self, name: str, node_type: str):
        """Run setup commands + start the node agent on every slice host
        (``--worker=all`` — a multi-host slice joins with one agent per
        host, each owning its local chips).  The authkey arrives as a
        0600 file (scp, above); the agent command only references the
        file, so the literal ``$(cat ...)`` — not the key — is what
        appears in process listings."""
        self._push_authkey(name)
        r = self.node_types[node_type]["resources"]
        key_file = self.AUTHKEY_REMOTE_PATH
        agent_cmd = (
            f"chmod 600 {key_file} && "
            f"RAY_TPU_CLIENT_AUTHKEY=$(cat {key_file}) "
            f"python3 -m ray_tpu.scripts agent "
            f"--address {self._head_address} "
            f"--num-cpus {r.get('CPU', 1)} "
            f"--num-tpus {r.get('TPU', 0)} "
            f"</dev/null >/tmp/ray_tpu_agent.log 2>&1 &")
        script = " && ".join(self._setup + [agent_cmd]) \
            if self._setup else agent_cmd
        if self._authkey_hex in script:  # belt + suspenders: the guard
            # must survive `python -O` (assert would be compiled out)
            raise RuntimeError(
                "cluster authkey leaked into the remote command line")
        subprocess.run(
            ["gcloud", "compute", "tpus", "tpu-vm", "ssh", name,
             f"--project={self._conf['project']}",
             f"--zone={self._conf['zone']}", "--worker=all",
             f"--command={script}"],
            capture_output=True, text=True, timeout=900.0, check=True)

    def terminate_node(self, node_id: str) -> None:
        self._types.pop(node_id, None)
        if self._conf.get("queued_resources"):
            self._gcloud("queued-resources", "delete", node_id,
                         "--force")
        else:
            self._gcloud("tpu-vm", "delete", node_id, "--quiet")

    def non_terminated_nodes(self) -> List[str]:
        nodes = json.loads(self._gcloud("tpu-vm", "list"))
        live = {n["name"].rsplit("/", 1)[-1] for n in nodes
                if n.get("state") in ("READY", "CREATING")}
        return [nid for nid in self._types if nid in live]

    def node_type_of(self, node_id: str) -> Optional[str]:
        return self._types.get(node_id)


# --------------------------------------------------------------- commands --
def _state_path(name: str) -> str:
    # The state dir holds cluster authkeys: owner-only, like ~/.ssh.
    os.makedirs(STATE_DIR, mode=0o700, exist_ok=True)
    try:
        os.chmod(STATE_DIR, 0o700)  # pre-existing dir from an older run
    except OSError:
        pass
    return os.path.join(STATE_DIR, f"{name}.json")


def _write_state(state_file: str, state: Dict[str, Any]) -> None:
    """Write the cluster state file with mode 0600: it carries the
    cluster authkey, which a world-readable file would hand to every
    local user (the cluster trusts any dialer holding it)."""
    fd = os.open(state_file, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
    with os.fdopen(fd, "w", encoding="utf-8") as f:
        json.dump(state, f)
    try:
        os.chmod(state_file, 0o600)  # file may predate this hardening
    except OSError:
        pass


def _load_config(path: str) -> Dict[str, Any]:
    import yaml

    with open(path, encoding="utf-8") as f:
        cfg = yaml.safe_load(f)
    cfg.setdefault("cluster_name", "default")
    cfg.setdefault("provider", {"type": "subprocess"})
    cfg.setdefault("head", {})
    cfg.setdefault("worker_types", {})
    return cfg


def _make_provider(cfg: Dict[str, Any], head_address: str,
                   authkey_hex: str) -> NodeProvider:
    ptype = cfg["provider"].get("type", "subprocess")
    if ptype == "subprocess":
        return SubprocessAgentProvider(cfg["worker_types"], head_address,
                                       authkey_hex)
    if ptype == "gcp_tpu":
        conf = dict(cfg["provider"],
                    cluster_name=cfg["cluster_name"])
        return GCPTpuProvider(cfg["worker_types"], conf, head_address,
                              authkey_hex,
                              cfg.get("setup_commands"))
    raise ValueError(f"unknown provider type {ptype!r}")


def up(config_path: str) -> Dict[str, Any]:
    """create_or_update_cluster: start the head process, then launch
    every worker type's min_workers (reference: commands.py:
    create_or_update_cluster -> get_or_create_head_node + updaters)."""
    cfg = _load_config(config_path)
    name = cfg["cluster_name"]
    state_file = _state_path(name)
    if os.path.exists(state_file):
        state = json.load(open(state_file, encoding="utf-8"))
        if _head_alive(state):
            print(f"cluster {name!r} already up at {state['address']}")
            return state
    ptype = cfg["provider"].get("type", "subprocess")
    bind_host = cfg["head"].get("host", "127.0.0.1")
    # The address worker nodes DIAL.  Cloud nodes cannot reach loopback:
    # require a routable advertise host rather than billing TPU VMs that
    # can never join.
    adv_host = cfg["head"].get("advertise_host", bind_host)
    if ptype == "gcp_tpu" and adv_host.startswith("127."):
        raise ValueError(
            "gcp_tpu clusters need head.host/head.advertise_host set to "
            "an address the TPU VMs can reach (and head.host should "
            "usually be 0.0.0.0)")
    authkey_hex = os.urandom(16).hex()
    port = int(cfg["head"].get("port", 0)) or _free_port()
    head_env = dict(os.environ, JAX_PLATFORMS="cpu")
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    head_env["PYTHONPATH"] = pkg_root + os.pathsep + head_env.get(
        "PYTHONPATH", "")
    head_proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.scripts", "head",
         "--num-cpus", str(cfg["head"].get("num_cpus", 4)),
         "--port", str(port), "--authkey", authkey_hex,
         "--host", bind_host],
        env=head_env)
    address = f"tcp://{adv_host}:{port}"
    local_address = f"tcp://127.0.0.1:{port}"
    _wait_head(local_address, authkey_hex, head_proc)
    # State lands BEFORE worker launches: a failed create_node must
    # leave a state file so `down` can clean up the head and any nodes
    # already created.
    state = {
        "cluster_name": name, "address": address,
        "local_address": local_address,
        "authkey": authkey_hex, "head_pid": head_proc.pid,
        "nodes": [], "config_path": os.path.abspath(config_path),
        "provider_type": ptype, "agent_pids": {},
    }
    _write_state(state_file, state)
    provider = _make_provider(cfg, address, authkey_hex)
    try:
        for node_type, spec in cfg["worker_types"].items():
            for _ in range(int(spec.get("min_workers", 0))):
                state["nodes"].append(
                    {"id": provider.create_node(node_type),
                     "type": node_type})
    finally:
        state["agent_pids"] = (
            provider.pids() if isinstance(provider,
                                          SubprocessAgentProvider)
            else {})
        _write_state(state_file, state)
    print(f"cluster {name!r} up: {address} "
          f"(head pid {head_proc.pid}, "
          f"{len(state['nodes'])} worker node(s))")
    return state


def down(config_path: str) -> None:
    """teardown_cluster (reference: commands.py teardown_cluster)."""
    cfg = _load_config(config_path)
    state_file = _state_path(cfg["cluster_name"])
    if not os.path.exists(state_file):
        print(f"cluster {cfg['cluster_name']!r} is not up")
        return
    state = json.load(open(state_file, encoding="utf-8"))
    if state.get("provider_type") == "gcp_tpu":
        provider = _make_provider(cfg, state["address"], state["authkey"])
        for n in state.get("nodes", []):
            provider._types[n["id"]] = n["type"]  # rebuild tracking
            try:
                provider.terminate_node(n["id"])
            except Exception as e:  # noqa: BLE001
                print(f"  terminate {n['id']}: {e}")
    for pid in state.get("agent_pids", {}).values():
        _kill_pid(pid)
    _kill_pid(state.get("head_pid"))
    os.unlink(state_file)
    print(f"cluster {cfg['cluster_name']!r} down")


def _cluster_env(state: Dict[str, Any]) -> Dict[str, str]:
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ,
               RAY_TPU_ADDRESS=state["address"],
               RAY_TPU_CLIENT_AUTHKEY=state["authkey"])
    env["PYTHONPATH"] = pkg_root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


def exec_cmd(config_path: str, command: str) -> int:
    """exec_cluster: run a shell command wired to the cluster
    (RAY_TPU_ADDRESS / RAY_TPU_CLIENT_AUTHKEY set, as the reference sets
    RAY_ADDRESS)."""
    return subprocess.call(command, shell=True,
                           env=_cluster_env(_require_state(config_path)))


def attach(config_path: str) -> int:
    """attach_cluster: an interactive shell wired to the cluster."""
    state = _require_state(config_path)
    env = _cluster_env(state)
    shell = os.environ.get("SHELL", "/bin/sh")
    print(f"attached to {state['cluster_name']!r} at {state['address']} "
          f"(exit the shell to detach)")
    return subprocess.call([shell], env=env)


def _require_state(config_path: str) -> Dict[str, Any]:
    cfg = _load_config(config_path)
    state_file = _state_path(cfg["cluster_name"])
    if not os.path.exists(state_file):
        raise SystemExit(f"cluster {cfg['cluster_name']!r} is not up "
                         f"(run: ray_tpu up {config_path})")
    return json.load(open(state_file, encoding="utf-8"))


def _head_alive(state: Dict[str, Any]) -> bool:
    try:
        os.kill(state["head_pid"], 0)
        return True
    except (OSError, KeyError):
        return False


def _kill_pid(pid):
    if not pid:
        return
    try:
        os.kill(pid, signal.SIGTERM)
    except OSError:
        pass


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_head(address: str, authkey_hex: str, proc,
               timeout: float = 60.0):
    from ray_tpu._private.client import client_connect

    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"head process exited with {proc.returncode}")
        try:
            rt = client_connect(address, bytes.fromhex(authkey_hex))
            rt.disconnect()
            return
        except Exception as e:  # noqa: BLE001
            last = e
            time.sleep(0.5)
    raise RuntimeError(f"head never came up at {address}: {last!r}")
