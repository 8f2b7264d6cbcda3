"""The benchmark's own tests run on the CPU, with four host devices for the
2x2 mesh, at sizes a test run holds:

    python -m pytest benchmarks/chip/tests
"""
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "jax" not in sys.modules:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")
