"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the role the reference's kind
cluster plays for its e2e tier, reference: testing/scripts/kind_test_all.sh)
so multi-chip sharding paths execute without TPU hardware.  The
platform is forced through jax.config before the backend initialises,
so the suite stays on the CPU wherever it is started.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", os.environ.get("SELDON_TPU_TEST_PLATFORM", "cpu"))

import pytest  # noqa: E402


@pytest.fixture
def rng():
    import numpy as np

    return np.random.default_rng(0)


def pytest_configure(config):
    config.addinivalue_line("markers", "e2e: full-stack tests spawning real processes/ports")
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy tests excluded from the default fast tier "
        "(pyproject addopts -m 'not slow'; `make test-all` runs everything)",
    )
