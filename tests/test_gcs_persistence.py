"""Head (GCS-analog) persistence + restart.

Reference: GCS table persistence (redis_store_client.h:28) and the
GcsInitData load-on-restart path (gcs_server.h:77): a restarted head
reloads KV/functions/named actors/jobs and the cluster resumes.
"""

import os
import signal
import socket
import subprocess
import sys
import tempfile
import textwrap
import time

import pytest

import ray_tpu as ray


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_snapshot_restore_in_process(tmp_path):
    """Snapshot written by one runtime restores into a fresh one: KV,
    functions, and the named actor come back."""
    snap = str(tmp_path / "gcs.bin")
    rt = ray.init(num_cpus=2,
                  _system_config={"gcs_snapshot_path": snap})

    @ray.remote
    class Counter:
        def __init__(self, start):
            self.n = start

        def incr(self):
            self.n += 1
            return self.n

    c = Counter.options(name="persistent_counter").remote(10)
    assert ray.get(c.incr.remote()) == 11
    rt.kv_put(b"mykey", b"myvalue")
    rt._snapshot_gcs()
    ray.shutdown()

    rt2 = ray.init(num_cpus=2,
                   _system_config={"gcs_snapshot_path": snap,
                                   "gcs_restore": True})
    try:
        assert rt2.kv_get(b"mykey") == b"myvalue"
        c2 = ray.get_actor("persistent_counter")
        # Fresh incarnation: state reset to creation args, identity kept.
        assert ray.get(c2.incr.remote(), timeout=30) == 11

        @ray.remote
        def task():
            return "works"

        assert ray.get(task.remote(), timeout=30) == "works"
    finally:
        ray.shutdown()


HEAD_SCRIPT = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, {repo!r})
    import ray_tpu as ray

    rt = ray.init(num_cpus=2, _system_config={{
        "gcs_snapshot_path": {snap!r},
        "gcs_restore": {restore},
        "gcs_snapshot_interval_s": 0.2,
        "listen_port": {port},
        "authkey_hex": {key!r},
    }})

    @ray.remote
    class KVActor:
        def __init__(self):
            self.d = {{}}
        def put(self, k, v):
            self.d[k] = v
            return len(self.d)
        def get(self, k):
            return self.d.get(k)

    if not {restore}:
        KVActor.options(name="kv_actor").remote()
        rt.kv_put(b"epoch", b"one")
    print("HEAD_READY", flush=True)
    time.sleep(600)
""")


def _start_head(snap, port, key, restore):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = HEAD_SCRIPT.format(repo=REPO, snap=snap, port=port,
                                key=key, restore=restore)
    proc = subprocess.Popen([sys.executable, "-u", "-c", script],
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT)
    deadline = time.time() + 60
    line = b""
    while time.time() < deadline:
        line = proc.stdout.readline()
        if b"HEAD_READY" in line:
            return proc
        if proc.poll() is not None:
            break
    raise AssertionError(
        f"head did not start: {line!r} rc={proc.poll()}")


def test_head_kill_restart_client_reconnect(tmp_path):
    """kill -9 the head; a restarted head (same port/authkey) restores
    the snapshot; a client re-attaches, finds the named actor, and runs
    tasks.

    Since the head-failover PR the actor's WORKER survives the head's
    death (it parks on head-conn EOF and re-registers with the restarted
    head under the adopted session), so the actor keeps its STATE across
    the blip — adoption, not a fresh incarnation."""
    snap = str(tmp_path / "gcs.bin")
    key = os.urandom(16).hex()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    head = _start_head(snap, port, key, False)
    from ray_tpu._private import api_internal

    try:
        client = ray.init(address=f"tcp://127.0.0.1:{port}", _authkey=key)
        actor = ray.get_actor("kv_actor")
        assert ray.get(actor.put.remote("a", 1), timeout=60) == 1
        # Let the snapshot loop persist the actor + kv.
        deadline = time.time() + 20
        while not os.path.exists(snap) and time.time() < deadline:
            time.sleep(0.2)
        assert os.path.exists(snap)
        client.disconnect()
        api_internal.set_global_runtime(None)

        head.send_signal(signal.SIGKILL)
        head.wait(timeout=30)

        head = _start_head(snap, port, key, True)
        client = ray.init(address=f"tcp://127.0.0.1:{port}", _authkey=key)
        actor = ray.get_actor("kv_actor")
        # The surviving worker re-registered its incarnation: state
        # SURVIVES the head restart ({"a": 1} still there -> len 2).
        assert ray.get(actor.put.remote("b", 2), timeout=60) == 2

        @ray.remote
        def sq(x):
            return x * x

        assert ray.get(sq.remote(7), timeout=60) == 49
    finally:
        rt = api_internal.get_runtime()
        if rt is not None and getattr(rt, "is_client", False):
            try:
                rt.disconnect()
            except Exception:
                pass
        api_internal.set_global_runtime(None)
        try:
            head.kill()
        except Exception:
            pass
