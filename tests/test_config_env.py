"""What a spawned worker inherits from the head's Config: the map
``Runtime._worker_config_env`` writes is the one ``Config.from_env``
reads back at the worker's import, for every forwarded field — no
cluster, no sleep (both spawn paths consume the same map; protocheck
RTL504 pins that they do)."""

import dataclasses
import os
import types

import pytest

from ray_tpu._private.config import HEAD_ONLY, Config, env_name
from ray_tpu._private.runtime import Runtime

_FIELDS = {f.name: f for f in dataclasses.fields(Config)}
_FORWARDED = [n for n in _FIELDS if n not in HEAD_ONLY]


def _off_default(field):
    d = field.default
    if isinstance(d, bool):
        return not d
    if isinstance(d, (int, float)):
        return d + type(d)(3)
    return d + "x"


def _worker_env(cfg):
    return Runtime._worker_config_env(types.SimpleNamespace(config=cfg))


@pytest.mark.parametrize("name", _FORWARDED,
                         ids=[env_name(n) for n in _FORWARDED])
def test_forwarded_field_round_trips_through_worker_env(name, monkeypatch):
    """One case per forwarded field, named by the variable that carries
    it (the two alias spellings, RAY_TPU_MAX_INLINE and
    RAY_TPU_POOL_BYTES, are what worker_entry and node_agent read
    directly from os.environ)."""
    value = _off_default(_FIELDS[name])
    env = _worker_env(dataclasses.replace(Config(), **{name: value}))
    assert HEAD_ONLY <= set(_FIELDS), "HEAD_ONLY names a missing field"
    assert not {env_name(h) for h in HEAD_ONLY} & set(env)
    assert len(env) == len(_FORWARDED)
    for key in [k for k in os.environ if k.startswith("RAY_TPU_")]:
        monkeypatch.delenv(key)
    for key, raw in env.items():
        monkeypatch.setenv(key, raw)
    rebuilt = Config.from_env()
    assert getattr(rebuilt, name) == value
    assert type(getattr(rebuilt, name)) is type(value)
    # Everything else arrives at its default: one field moved, one moved.
    assert dataclasses.replace(rebuilt, **{name: _FIELDS[name].default}) \
        == Config()

