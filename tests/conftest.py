"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the reference's pattern of testing
the full stack single-host with self/sm/tcp transports — SURVEY.md §4), so
the suite is the same on a machine with a chip: the CPU is forced through
jax.config before any backend initializes.  The chip itself is exercised by
``chip_smoke.py``; ``tests/test_chip_compile.py`` compiles main-path
programs for a described v5e without one.
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()

try:
    import jax

    jax.config.update("jax_platforms", "cpu")
except ImportError:  # pragma: no cover
    pass

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_var_cache():
    from ompi_tpu.core import var
    yield
    var.registry.reset_cache()
