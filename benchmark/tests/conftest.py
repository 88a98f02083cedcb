"""The benchmark's own tests: ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q``.  Not part of tier-1 (``tests/``)."""

import os
import sys

# Before any JAX backend starts in this process or its workers.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # workers inherit it
