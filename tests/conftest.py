"""Test env: force the CPU backend with 8 virtual devices BEFORE jax import,
so sharding/mesh tests run anywhere. The chip is driven by
`python chip_smoke.py` (`--chips 4` for the mesh), never by the tests;
tests/test_tpu_compile.py compiles for a described chip without one."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

from kubernetes_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
