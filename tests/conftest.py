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
# The suite's programs are tiny and run once or twice: a run costs what
# XLA's CPU compiler costs (PR 44: 7,649 -> 5,902 s of case time, every
# case passing).  Results, lowered text and counters are decided before
# the optimiser; test_paged_kernel_mosaic.py, whose verdict is the
# compiler's own, puts it back on.
jax.config.update("jax_disable_most_optimizations", True)

import faulthandler  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402

# Seconds a case may take: three times the slowest the suite allows
# itself (ROADMAP D15).  A case that needs more is shortened.
CASE_LIMIT_S = 300


@pytest.fixture(autouse=True)
def case_limit(request):
    """A case that waits on an engine thread would wait for the
    driver's clock and take the run's verdict with it: past the limit
    every thread's stack goes to stderr and the case fails by name (an
    interval timer on the worker's main thread; the image has no
    ``pytest-timeout``)."""
    def expired(_signum, _frame):
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        pytest.fail(f"{request.node.nodeid} was still running after "
                    f"{CASE_LIMIT_S} s (CASE_LIMIT_S, tests/conftest.py); "
                    "every thread's stack is on stderr", pytrace=False)

    before = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, CASE_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture
def rng():
    import numpy as np

    return np.random.default_rng(0)


# The driver's six workers take a file at a time in the order of
# collection (``-n 6 --dist loadfile``), so a long file handed out last
# leaves five idle behind it (PR 43's tree: 345 s from 95 % to the end,
# behind ``test_wave_overlap.py``).  The files over ~60 s go first,
# longest first (ROADMAP D15's table; one that grows past that joins).
LONGEST_FIRST = (
    "test_dots3_paged.py", "test_paged_pool_in_place.py", "test_paged_kernel_mosaic.py",
    "test_wave_overlap.py", "test_smallthinker_paged.py", "test_moe_ops.py",
    "test_olmoe_paged.py", "test_prefill_attention.py", "test_longcat_paged.py",
    "test_deepseek_paged.py", "test_deepseek_ops.py", "test_dots3_prefill.py",
    "test_longcat_model.py", "test_wave_seam.py", "test_smallthinker_lanes.py",
    "test_dots3_kernels.py", "test_smallthinker_spec.py", "test_lora.py",
    "test_smallthinker_kernels.py", "test_paged_buckets.py", "test_migration.py",
    "test_slo_lifecycle.py", "test_chunked_prefill.py", "test_paged_mesh.py",
)


def pytest_collection_modifyitems(items):
    """A stable sort: a file's cases stay together and in their order,
    and every worker collects the same order."""
    rank = {name: i for i, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))


def pytest_configure(config):
    config.addinivalue_line("markers", "e2e: full-stack tests spawning real processes/ports")
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy tests excluded from the default fast tier "
        "(pyproject addopts -m 'not slow'; `make test-all` runs everything)",
    )
