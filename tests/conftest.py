"""Test env: JAX on a virtual 8-device CPU platform.

Tier-1 runs on the CPU: `JAX_PLATFORMS=cpu` and eight virtual devices
(`--xla_force_host_platform_device_count=8`) so the mesh solvers have a
mesh to shard over.  Both are set here, before the first `import jax`,
so a bare `pytest tests/` needs nothing in the environment.  XLA_FLAGS
is read when the first CPU client is created, which has not happened
yet at conftest import time.  The chip is reached only through
`chip_smoke.py` (one process, no tests).
"""
import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The wave kernel takes tens of seconds to compile per tensor shape on
# CPU; without a persistent cache every fresh (nodes, asks) shape in the
# suite re-pays that, and timing-sensitive e2e tests flake on compile
# stalls.  Same cache, same resolution as the program's own.
import jax  # noqa: E402

from nomad_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
# the suite compiles hundreds of sub-second programs; persisting those
# too (JAX's default floor is 1 s) takes a tenth off a warm run
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
