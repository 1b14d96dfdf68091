"""Test harness: 8 virtual CPU devices so every sharding/collective path
(ZeRO, TP, PP, SP, EP) runs as real SPMD without TPU hardware.

Must set XLA flags BEFORE jax initializes (SURVEY.md §4).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _xla_flags  # noqa: E402  (lane flags shared with mp_child.py)

_xla_flags.apply(device_count=8)

import jax  # noqa: E402

# the suite runs on the CPU whatever the shell's JAX_PLATFORMS said
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)
# NO persistent compile cache: XLA's CPU AOT cache loader can serve an
# artifact whose recorded machine features mismatch the host
# (cpu_aot_loader "+prefer-no-scatter ... not supported" warnings) and
# that escalated to a hard `Fatal Python error: Aborted` mid-suite —
# a ~2x warm-rerun speedup is not worth a nondeterministic crash.
# Opt back in locally with DSTPU_TEST_JIT_CACHE=/some/dir.
_cache = os.environ.get("DSTPU_TEST_JIT_CACHE")
if _cache:
    jax.config.update("jax_compilation_cache_dir", _cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
else:
    # explicit None: jax_compilation_cache_dir is env-backed, and an
    # inherited JAX_COMPILATION_CACHE_DIR (e.g. from the chip tool's
    # environment) would silently re-enable the cache
    jax.config.update("jax_compilation_cache_dir", None)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="also run tests marked slow (long equivalence "
                          "tests; default selection keeps the suite fast)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running equivalence test (opt-in: --runslow)")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow: run with --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def devices():
    d = jax.devices()
    assert len(d) == 8, f"expected 8 virtual devices, got {len(d)}"
    return d


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
