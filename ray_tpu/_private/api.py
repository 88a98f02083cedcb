"""Public API functions: init/shutdown/get/put/wait/kill/cancel/...

Reference: ``python/ray/_private/worker.py`` — ``init`` (:1045), ``get``
(:2305), ``put``, ``wait``, ``shutdown`` (:1602) — with the same semantics
on the TPU-native runtime.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Union

from ray_tpu._private import api_internal, device_env
from ray_tpu._private.config import Config
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.runtime import Runtime
from ray_tpu import exceptions as exc


def init(num_cpus: Optional[int] = None, num_tpus: Optional[int] = None,
         resources: Optional[dict] = None, namespace: str = "default",
         ignore_reinit_error: bool = False, _system_config: dict | None = None,
         address: Optional[str] = None, _authkey: Optional[str] = None,
         **_compat_kwargs):
    """Start the runtime (reference: python/ray/_private/worker.py:1045),
    or — with ``address`` — ATTACH to a running cluster in client mode
    (reference: Ray Client, ray.init("ray://...")).

    ``num_tpus`` defaults to the number of TPU chips this host has (its
    device nodes are counted; JAX is not imported); pass 0 to disable.
    """
    import os as _os

    if address is None:
        address = _os.environ.get("RAY_TPU_CLIENT_ADDRESS")
    if address:
        cur = api_internal.get_runtime()
        if cur is not None and getattr(cur, "is_client", False):
            # Honor the reinit contract in client mode too: never stack a
            # second connection under existing ObjectRefs.
            if ignore_reinit_error:
                return cur
            raise RuntimeError("ray_tpu.init() called twice "
                               "(pass ignore_reinit_error=True to allow).")
        from ray_tpu._private.client import client_connect

        key = _authkey or _os.environ.get("RAY_TPU_CLIENT_AUTHKEY")
        if not key:
            raise ValueError("client mode needs _authkey= or "
                             "RAY_TPU_CLIENT_AUTHKEY")
        rt = client_connect(address, bytes.fromhex(key))
        api_internal.set_global_runtime(rt)
        return rt
    rt = api_internal.get_runtime()
    if rt is not None:
        if isinstance(rt, Runtime) and not rt._stopped:
            if ignore_reinit_error:
                return rt
            raise RuntimeError("ray_tpu.init() called twice "
                               "(pass ignore_reinit_error=True to allow).")
    if num_tpus is None:
        num_tpus = device_env.detect_tpu_chips()
    device_env.check_node_chips(int(num_tpus))
    config = Config.from_env(_system_config)
    rt = Runtime(config, num_cpus=num_cpus, num_tpus=num_tpus,
                 resources=resources, job_name=namespace)
    api_internal.set_global_runtime(rt)
    return rt


def shutdown():
    rt = api_internal.get_runtime()
    if isinstance(rt, Runtime):
        rt.shutdown()
    elif rt is not None and getattr(rt, "is_client", False):
        rt.disconnect()
    api_internal.set_global_runtime(None)


def is_initialized() -> bool:
    rt = api_internal.get_runtime()
    return rt is not None and not getattr(rt, "_stopped", False)


def put(value: Any) -> ObjectRef:
    if isinstance(value, ObjectRef):
        raise TypeError("Calling put on an ObjectRef is not allowed "
                        "(reference parity: python/ray/_private/worker.py).")
    return api_internal.require_runtime().put_object(value)


def get(refs: Union[ObjectRef, Sequence[ObjectRef]],
        *, timeout: Optional[float] = None):
    rt = api_internal.require_runtime()
    if isinstance(refs, ObjectRef):
        return rt.get_objects([refs], timeout)[0]
    if isinstance(refs, (list, tuple)):
        for r in refs:
            if not isinstance(r, ObjectRef):
                raise TypeError(
                    f"ray_tpu.get takes ObjectRefs, got {type(r).__name__}")
        return rt.get_objects(list(refs), timeout)
    raise TypeError(
        f"ray_tpu.get takes an ObjectRef or list, got {type(refs).__name__}")


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None, fetch_local: bool = True):
    rt = api_internal.require_runtime()
    if isinstance(refs, ObjectRef):
        raise TypeError("ray_tpu.wait takes a list of ObjectRefs")
    refs = list(refs)
    if len(set(refs)) != len(refs):
        raise ValueError("ray_tpu.wait got duplicate ObjectRefs")
    if num_returns > len(refs):
        raise ValueError("num_returns > number of refs")
    return rt.wait_objects(refs, num_returns, timeout, fetch_local)


def kill(actor_handle, *, no_restart: bool = True):
    from ray_tpu.actor import ActorHandle

    if not isinstance(actor_handle, ActorHandle):
        raise TypeError("ray_tpu.kill takes an ActorHandle")
    rt = api_internal.require_runtime()
    if rt.is_worker():
        rt._request(lambda rid: ("kill_actor_req", rid,
                                 actor_handle._actor_id, no_restart))
    else:
        rt.kill_actor(actor_handle._actor_id, no_restart)


def cancel(ref: ObjectRef, *, force: bool = False, recursive: bool = True):
    rt = api_internal.require_runtime()
    if rt.is_worker():
        raise NotImplementedError("cancel from inside tasks lands in v2")
    rt.cancel_task(ref.id(), force)


def cluster_resources() -> dict:
    return api_internal.require_runtime().cluster_resources()


def available_resources() -> dict:
    return api_internal.require_runtime().available_resources()


def nodes() -> List[dict]:
    return api_internal.require_runtime().list_nodes()
