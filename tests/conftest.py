"""Shared pytest fixtures.  NOTE: no XLA_FLAGS here — tests must see the
default single CPU device (the dry-run sets its own 512-device flag in its
own process; see src/repro/launch/dryrun.py).

Tests marked ``slow`` (multi-device subprocess runs, large statistical
sweeps) are skipped by default so ``python -m pytest -x -q`` stays fast;
pass ``--runslow`` to include them.
"""
import jax
import pytest

# tests never read or write JAX's persistent compile cache, whatever the
# environment sets: only the entry points place it (repro.compile_cache)
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="also run tests marked @pytest.mark.slow")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (skipped unless --runslow)")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="slow test: pass --runslow to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
