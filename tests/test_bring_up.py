"""The train worker that was granted chips opens them itself, before the
user's loop, under the program's own spans (``train/backend.py::bring_up``:
``device.bring_up`` > ``jax.import``, ``jax.backend_init``), and is watched
from there on.  No chip in tier-1: the check takes fakes, and a grant is a
``TPU_VISIBLE_CHIPS`` the scheduler wrote for a process that opens nothing.
"""
import gc
import json
import os
import subprocess
import sys
import textwrap
import types

import pytest

import ray_tpu as ray
from ray_tpu.train import backend
from ray_tpu.util import tracing
from ray_tpu.util.tracing import get_task_spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _devices(n, platform="tpu"):
    return [types.SimpleNamespace(platform=platform, id=i) for i in range(n)]


# ------------------------------------------------------------ the bring-up --

def test_bring_up_is_one_span_with_two_children(monkeypatch):
    checked = []
    monkeypatch.setattr(backend, "check_devices",
                        lambda devices, chips: checked.append(
                            (len(devices), list(chips))))
    ray.init(num_cpus=2, ignore_reinit_error=True)
    try:
        with tracing.collect() as got:
            with tracing.span("probe.worker") as outer:
                backend.bring_up([0, 1])
        spans = {s["name"]: s for s in get_task_spans()
                 if s["name"] in ("device.bring_up", "jax.import",
                                  "jax.backend_init")}
    finally:
        ray.shutdown()
    import jax

    assert tracing._gc_phase in gc.callbacks  # the process is watched
    assert checked == [(len(jax.local_devices()), [0, 1])]
    summary = got.summary
    for name in ("device.bring_up", "jax.import", "jax.backend_init"):
        assert summary[name]["count"] == 1, name
    up = spans["device.bring_up"]
    assert up["parent"] == outer.id and up["args"] == {"chips": 2}
    assert spans["jax.import"]["parent"] == up["span_id"]
    assert spans["jax.backend_init"]["parent"] == up["span_id"]
    assert up["start"] <= spans["jax.import"]["start"]
    assert spans["jax.import"]["end"] <= spans["jax.backend_init"]["start"]
    assert spans["jax.backend_init"]["end"] <= up["end"]


@pytest.mark.parametrize("devices,chips,said", [
    (_devices(1), [0], None),
    (_devices(4), [0, 1, 2, 3], None),
    (_devices(3), [0, 1, 2, 3], ("granted 4", "opened 3")),
    (_devices(1, "cpu"), [0], ("granted 1", "opened 1", "cpu")),
    ([], [2], ("granted 1", "opened 0")),
], ids=["one", "four", "one-too-few", "another-platform", "none"])
def test_check_devices(devices, chips, said):
    if said is None:
        backend.check_devices(devices, chips)
        return
    with pytest.raises(RuntimeError) as e:
        backend.check_devices(devices, chips)
    for part in said:
        assert part in str(e.value)


def test_a_failed_check_fails_the_bring_up():
    """On the CPU the real check refuses what ``jax.local_devices()``
    gives: the error leaves ``bring_up`` with both spans closed."""
    with tracing.collect() as got:
        with pytest.raises(RuntimeError, match="granted 1 TPU chip"):
            backend.bring_up([0])
    assert set(got.summary) >= {"device.bring_up", "jax.import",
                                "jax.backend_init"}
    assert tracing.current_span() is None


def test_the_jax_backend_carries_the_bring_up():
    assert backend.Backend.worker_setup is None
    assert backend._JaxBackend().worker_setup is backend.bring_up
    assert backend.JaxConfig().backend_cls().worker_setup is backend.bring_up


def test_a_process_is_watched_from_its_bring_up():
    """A function jitted after the bring-up, in a process that never
    imports ``train/core.py``, has its pipeline's spans; ``jax.import`` is
    where JAX came into the process."""
    code = textwrap.dedent("""
        import json, sys
        from ray_tpu.train import backend
        from ray_tpu.util import tracing
        backend.check_devices = lambda devices, chips: None
        assert "jax" not in sys.modules
        with tracing.collect() as got:
            backend.bring_up([0])
            import jax, jax.numpy as jnp
            def probe_after(x):
                return jnp.sin(x) + 1
            jax.jit(probe_after).lower(jnp.ones(4)).compile()
        assert "ray_tpu.train.core" not in sys.modules
        s = got.summary
        print(json.dumps({n: [v["count"], v["total_s"]]
                          for n, v in s.items()}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    for name in ("jax.trace", "jax.lower", "jax.compile"):
        assert got[name][0] >= 1, name
    assert got["device.bring_up"][0] == 1
    # the import is real work here, and the bring-up holds both children
    assert got["jax.import"][1] > 0.05
    assert got["device.bring_up"][1] >= \
        got["jax.import"][1] + got["jax.backend_init"][1]


# ------------------------------------------------------- inside a fit() --

def _fit(setup, chips, loop=None):
    from ray_tpu.air import session
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer

    class ProbeBackend(backend.Backend):
        worker_setup = staticmethod(setup)

    class ProbeConfig:
        backend_cls = ProbeBackend

    def default_loop(config):
        import sys
        session.report({"ran": 1, "jax": "jax" in sys.modules})

    ray.init(num_cpus=2, num_tpus=1, ignore_reinit_error=True)
    try:
        return JaxTrainer(
            loop or default_loop, backend_config=ProbeConfig(),
            scaling_config=ScalingConfig(
                num_workers=1, tpu_chips_per_worker=chips)).fit()
    finally:
        ray.shutdown()


def test_fit_runs_the_backends_worker_setup_before_the_loop():
    def setup(chips):
        import time

        from ray_tpu.util import tracing
        with tracing.span("probe.worker_setup", chips=len(chips)):
            time.sleep(0.01)

    result = _fit(setup, chips=1)
    assert result.error is None and result.metrics["ran"] == 1
    spans = result.metrics["_spans"]
    probe = spans["probe.worker_setup"]
    assert probe["count"] == 1 and probe["total_s"] >= 0.01
    assert spans["train.session_start"]["last_end"] <= probe["first_start"]
    assert probe["last_end"] <= spans["train.loop"]["first_start"]
    # the set-up opened no JAX, so neither did the program
    assert result.metrics["jax"] is False


def test_a_worker_setup_that_raises_fails_fit_before_the_loop(tmp_path):
    ran = tmp_path / "loop_ran"

    def setup(chips):
        raise RuntimeError(
            f"this worker was granted {len(chips)} TPU chip(s) and "
            f"opened 0")

    def loop(config):
        ran.write_text("1")

    result = _fit(setup, chips=1, loop=loop)
    assert result.error is not None
    assert "granted 1 TPU chip(s) and opened 0" in str(result.error)
    assert not ran.exists()


def test_a_worker_without_a_grant_runs_no_setup():
    def setup(chips):
        raise AssertionError("no chips were granted")

    result = _fit(setup, chips=0)
    assert result.error is None and result.metrics["ran"] == 1
    assert result.metrics["jax"] is False
    assert "device.bring_up" not in result.metrics["_spans"]


def test_a_cpu_worker_of_the_jax_backend_has_no_new_spans():
    """The default backend, no chips: nothing new runs, no JAX."""
    from ray_tpu.air import session
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer

    def loop(config):
        import sys
        session.report({"jax": "jax" in sys.modules})

    ray.init(num_cpus=2, ignore_reinit_error=True)
    try:
        result = JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1)).fit()
    finally:
        ray.shutdown()
    assert result.error is None and result.metrics["jax"] is False
    assert not {"device.bring_up", "jax.import", "jax.backend_init"} \
        & set(result.metrics["_spans"])


# ------------------------------------------------------------- the grant --

@pytest.mark.parametrize("value,chips", [
    (None, []), ("", []), ("0", [0]), ("2,3", [2, 3]),
    ("0,1,2,3", [0, 1, 2, 3])])
def test_granted_chips_reads_what_worker_device_env_writes(value, chips,
                                                           monkeypatch):
    from ray_tpu._private import device_env

    env = {} if value is None else {"TPU_VISIBLE_CHIPS": value}
    assert device_env.granted_chips(env) == chips
    if value is None:
        monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    else:
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", value)
    assert device_env.granted_chips() == chips
    if chips:
        written = device_env.worker_device_env(chips, host_chips=4)
        assert device_env.granted_chips(written) == chips
    assert device_env.granted_chips(device_env.worker_device_env([])) == []
