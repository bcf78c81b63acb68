"""Test environment: JAX on a virtual 8-device CPU mesh.

``JAX_PLATFORMS=cpu`` and the 8-device ``XLA_FLAGS`` are set before jax is
first imported, so tests (and every subprocess they spawn) are
CPU-deterministic and never touch an accelerator.  The chip is exercised
only by ``chip_smoke.py`` through the chip tool.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent compilation cache: the big verify graphs cost tens of seconds
# of XLA CPU compile per process — cache them across test runs so the full
# suite fits in a driver budget.  Placement rule (JAX_COMPILATION_CACHE_DIR
# wins, else <checkout>/.jax_cache) lives in ONE function.
from consensus_tpu.parallel.topology import apply_compile_cache  # noqa: E402

apply_compile_cache()
