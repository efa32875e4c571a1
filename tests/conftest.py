"""Test env: CPU-only jax with 8 virtual devices so sharding/collective
tests run without real multi-chip hardware, pinned BEFORE any backend
initializes (paddle_tpu/framework/platform.py).

The persistent compilation cache is on by default and lives inside the
checkout; tests switch it off with jax's own variable so that no test's
retrace/compile counts depend on what an earlier test or an earlier run
left on disk. Tests of the cache itself turn it back on for their child
processes."""
import os

os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

from paddle_tpu.framework.platform import pin_host_platform  # noqa: E402

pin_host_platform(8)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from the tier-1 gate "
        "(-m 'not slow')")
