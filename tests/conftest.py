"""Test harness: force an 8-device virtual CPU mesh (SURVEY.md §7).

Tests run on the CPU: all sharding/collective tests use 8 virtual CPU
devices, mirroring how the reference tests cluster logic without a
cluster (MemStore / vstart tiers, SURVEY.md §4). The chip is driven by
`chip_smoke.py` and `benchmark/run.py`, never by tests
(tests/test_tpu_compile.py only compiles for a described v5e).

pin_virtual_cpu must run before the first jax backend init (importing jax
is fine; creating devices is not). It forces the CPU platform even when
the shell leaves JAX_PLATFORMS unset, so a test never reaches for a chip.
"""
from ceph_tpu import parallel

parallel.pin_virtual_cpu(8)

import signal  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP): long thrashes and other
    # minute-scale scenarios carry @pytest.mark.slow
    config.addinivalue_line(
        "markers",
        "slow: long-running scenario excluded from tier-1 (-m 'not slow')")


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture(autouse=True)
def _sigpipe_ignored():
    """Keep CPython's SIGPIPE ignore in force for every test.

    A stray signal.signal(SIGPIPE, SIG_DFL) anywhere in the suite (e.g.
    a CLI module imported by a test) would make the NEXT write to a dead
    daemon socket kill the whole pytest process with exit 141, mid-run,
    with no summary — exactly the round-4 full-suite failure. Restore
    the disposition before each test and verify nothing left it reset."""
    prev = signal.getsignal(signal.SIGPIPE)
    signal.signal(signal.SIGPIPE, signal.SIG_IGN)
    yield
    now = signal.getsignal(signal.SIGPIPE)
    signal.signal(signal.SIGPIPE, signal.SIG_IGN)
    assert now is signal.SIG_IGN, (
        f"test left SIGPIPE disposition as {now!r}; writes to dead "
        "sockets would kill the test runner"
    )
    if prev is not signal.SIG_IGN:
        # first test after the offending import: disposition was already
        # broken on entry; it is fixed now, but flag the origin loudly
        import warnings

        warnings.warn("SIGPIPE was not SIG_IGN on test entry")
