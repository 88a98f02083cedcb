"""Device half of a worker's environment: which platform it runs on,
which chips it may open, and where its compiled programs are cached.

One rule, applied by both spawn paths (head-local and node agent): a
worker that was granted chips runs on them or fails at JAX start-up —
``JAX_PLATFORMS`` is set, never removed, so a TPU runtime that cannot
start is an error and not a quiet fall back to the CPU (where every
pallas kernel would run interpreted).  A worker without chips is pinned
to the CPU so it can never open the TPU runtime, which belongs to one
process per chip.  Nothing here imports JAX.
"""

from __future__ import annotations

import glob
import os
import subprocess
from typing import Dict, List, Mapping, Optional, Sequence

_MESH_CONTROLLER_PORT = 8476


def compile_cache_dir() -> str:
    """Directory of JAX's persistent compilation cache.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (and is then simply
    inherited).  Otherwise ONE fixed path inside the checkout: the
    path is part of the cache key, so anything derived from a session
    id, a pid or the clock would never hit."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def detect_tpu_chips() -> int:
    """Count THIS host's TPU chips without starting the TPU runtime
    (the chips belong to workers; reference analog: GPU autodetect in
    python/ray/_private/resource_spec.py).  0 = none, or not known."""
    if os.environ.get("RAY_TPU_FORCE_NUM_TPUS"):
        return int(os.environ["RAY_TPU_FORCE_NUM_TPUS"])
    # accel nodes, or vfio devices (TPU VM)
    return len(glob.glob("/dev/accel*")
               or glob.glob("/dev/vfio/[0-9]*"))


def check_node_chips(offered: int) -> None:
    """A node offers the scheduler chips 0..offered-1 of its host: all
    of them, or a share the wiring can give one process."""
    host = detect_tpu_chips()
    if offered and host and offered not in (1, 2, host):
        raise ValueError(
            f"a node can offer 1, 2 or all {host} of its host's TPU "
            f"chips, not {offered}")


def pick_chips(free: Sequence[int], n: int,
               node_chips: int) -> Optional[List[int]]:
    """The ``n`` of a node's ``free`` chips one worker is granted, or
    None while they are not free (still attached to retiring workers).
    One process owns one chip, two NEIGHBOURS (chips 2k and 2k+1: a
    1x2 row of a 2x2 host, whatever fragmentation left free), or every
    chip the node offers; no other count is a slice libtpu can open."""
    if n not in (1, 2, node_chips):
        raise ValueError(
            f"a worker can be granted 1, 2 or all {node_chips} of its "
            f"node's TPU chips, not {n}")
    if n == 2:
        pairs = [c for c in free if c % 2 == 0 and c + 1 in free]
        return [pairs[0], pairs[0] + 1] if pairs else None
    return list(free[:n]) if len(free) >= n else None


def granted_chips(env: Optional[Mapping[str, str]] = None) -> List[int]:
    """The chips a process was granted: its ``TPU_VISIBLE_CHIPS`` (this
    process's environment unless another is given), [] for a CPU
    worker.  The one reader of what ``worker_device_env`` writes."""
    env = os.environ if env is None else env
    return [int(c) for c in env.get("TPU_VISIBLE_CHIPS", "").split(",")
            if c]


def worker_device_env(tpu_chips: Sequence[int],
                      host_chips: Optional[int] = None) -> Dict[str, str]:
    """Environment overrides for a worker granted ``tpu_chips``, built
    ON the host that starts it: ``host_chips`` is that host's physical
    chip count (detected when not given; 0 = unknown), not the count a
    node was told to offer."""
    if not tpu_chips:
        return {"JAX_PLATFORMS": "cpu"}
    if host_chips is None:
        host_chips = detect_tpu_chips()
    env = {
        "JAX_PLATFORMS": "tpu",
        "TPU_VISIBLE_CHIPS": ",".join(map(str, tpu_chips)),
        "JAX_COMPILATION_CACHE_DIR": compile_cache_dir(),
    }
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # Cache every program, not only those that took a second to
        # compile: a decode replica jits many small shapes.
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    n = len(tpu_chips)
    if n < host_chips:
        # A worker granted EVERY chip of its host keeps the host's own
        # bounds (it sees the real topology, e.g. 2x2).  A strict subset
        # is described to libtpu as a slice of its own, a 1 x n row of
        # chips, with a controller port of its own per co-resident
        # process.  libtpu reads the bounds under two spellings and TPU
        # VM images export the HOST one, so both are overridden.
        if n > 2 or (n == 2 and (tpu_chips[0] % 2
                                 or tpu_chips[1] != tpu_chips[0] + 1)):
            raise ValueError(
                f"chips {list(tpu_chips)} of a {host_chips}-chip host "
                f"are not a slice one process can open (one chip, or "
                f"neighbours 2k and 2k+1)")
        chips, procs = f"1,{n},1", "1,1,1"
        port = str(_MESH_CONTROLLER_PORT + tpu_chips[0])
        env.update({
            "TPU_CHIPS_PER_PROCESS_BOUNDS": chips,
            "TPU_CHIPS_PER_HOST_BOUNDS": chips,
            "TPU_PROCESS_BOUNDS": procs,
            "TPU_HOST_BOUNDS": procs,
            "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
            "TPU_MESH_CONTROLLER_PORT": port,
        })
    return env


def reap(proc, grace_s: float = 30.0) -> None:
    """Block until the worker process ``proc`` (a ``Popen`` that was
    already told to exit) is gone; SIGKILL it past ``grace_s``.  (On a
    v5e a SIGTERMed JAX process spends 2-11 s in libtpu's own teardown
    before it dies.)"""
    try:
        proc.wait(timeout=grace_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
