"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Tests never claim an accelerator; sharding logic is validated on a forced
8-device CPU platform (tests/test_tpu_compile.py additionally compiles the
kernels against a chip-less TPU topology).

Must run before any `import jax` in test modules.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
