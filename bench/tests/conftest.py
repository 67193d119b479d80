"""The benchmark's own tests run on the CPU, on four host devices (the 2x2
stencil's rank grid), with the program's kernels on their reference paths:

    python -m pytest bench/tests
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

# the benchmark keeps its compile cache in the checkout for the chip; the
# CPU tests write none there
jax.config.update("jax_enable_compilation_cache", False)
