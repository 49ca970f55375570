"""Shared pytest config: the ``slow`` marker, and no compilation cache.

Tier-1 (``PYTHONPATH=src python -m pytest -x -q``) must finish in well under
two minutes, so anything heavier — full compile sweeps, long training runs —
is marked ``@pytest.mark.slow`` and only runs with ``--runslow``.

The entry points turn JAX's persistent compilation cache on
(`repro.utils.compile_cache`); the tests keep it off, in this process and in
the CLI subprocesses they start.
"""
import os

import pytest

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="also run tests marked @pytest.mark.slow")


def pytest_configure(config):
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    config.addinivalue_line(
        "markers", "slow: heavy test (compile sweep / long training), "
                   "skipped unless --runslow is given")


@pytest.fixture
def runslow(request):
    """For runtime skips of heavy cases inside otherwise-fast parametrized
    tests (collection-time marks can't see the fixture parameter)."""
    return request.config.getoption("--runslow")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow: pass --runslow to include")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
