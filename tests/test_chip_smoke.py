"""chip_smoke.py, rehearsed without the chip.

The script itself has no CPU mode: ``main()`` always asks for the chip
at the real sizes.  Its phases are functions of the model sizes and the
chips per worker, so this file calls the SAME functions at
``LlamaConfig.tiny`` sizes with CPU workers (rehearsals 1 and 2 of the
on-chip-measurement guide, kept), and runs the script once as a
subprocess on this chipless machine, where it must refuse.
"""

import os
import subprocess
import sys

import pytest

import ray_tpu as ray

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)  # workers inherit it: chip_smoke pickles by ref

import chip_smoke  # noqa: E402

TINY = {"preset": "tiny", "attn_impl": "flash"}


@pytest.fixture
def cpu_cluster():
    ray.init(num_cpus=4, num_tpus=0, _system_config={"paged_kv": True})
    yield
    ray.shutdown()


def test_train_phase_tiny_on_cpu_worker(cpu_cluster):
    m = chip_smoke.phase_train(model=TINY, batch=4, seq=64, steps=3,
                               chips_per_worker=0, seed=0, ref_rows=2)
    assert m["device"]["platform"] == "cpu"
    assert len(m["losses"]) == 4 and len(m["step_s"]) == 3
    assert m["tokens_per_step"] == 4 * 64
    # Interpret mode on the CPU: the kernel is NOT a custom call here —
    # which is exactly what the chip run refuses.
    assert m["flash_custom_call"] is False
    assert abs(m["first_loss_flash"] - m["first_loss_reference"]) < 1e-4


def test_mesh_train_phase_tiny_on_virtual_devices(cpu_cluster):
    """The --chips 4 train path (fsdp=2 x tp=2 vs a one-device mesh in
    the same process) on the CPU worker's virtual devices."""
    m = chip_smoke.phase_train(model=TINY, batch=4, seq=64, steps=3,
                               chips_per_worker=0, seed=0,
                               mesh={"fsdp": 2, "tp": 2})
    assert m["device"]["count"] >= 4
    assert len(m["one_device"]["losses"]) == len(m["losses"]) == 4


def test_serve_phase_tiny_on_cpu_worker(cpu_cluster):
    s = chip_smoke.phase_serve(embed=32, vocab=64, kv_blocks=64,
                               kv_block_size=8, max_slots=16,
                               n_requests=12, num_tpus=0, seed=0,
                               timeout_s=120)
    assert s["device"]["platform"] == "cpu"
    assert s["stats"]["mode"] == "continuous+paged"
    assert s["stats"]["tokens_emitted"] == s["tokens"] > 0


def test_serve_phase_refuses_dense_fallback():
    """With paged_kv off a paged=True replica quietly serves dense: the
    phase must call that a failure, not a pass."""
    ray.init(num_cpus=4, num_tpus=0)
    try:
        with pytest.raises(chip_smoke.SmokeFailure, match="paged"):
            chip_smoke.phase_serve(embed=32, vocab=64, kv_blocks=64,
                                   kv_block_size=8, max_slots=16,
                                   n_requests=4, num_tpus=0, seed=0,
                                   timeout_s=120)
    finally:
        ray.shutdown()


def test_script_refuses_without_a_chip():
    """`python chip_smoke.py` here: non-zero exit, the reason names the
    missing TPU, and no result line."""
    env = {k: v for k, v in os.environ.items()
           if k != "RAY_TPU_FORCE_NUM_TPUS"}
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stdout, (p.stdout, p.stderr)
    assert '"ok"' not in p.stdout
