"""Test harness: 8 virtual CPU devices so every sharding/collective path
(ZeRO, TP, PP, SP, EP) runs as real SPMD without TPU hardware.

Must set XLA flags BEFORE jax initializes (SURVEY.md §4).
"""

import atexit
import faulthandler
import os
import shutil
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# describing a chip loads libtpu, which by default lets ONE process a
# machine do so: the others' cases of test_aot_tpu_compile*.py would skip
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
# XLA's `cpu_aot_loader` logs two errors of 6 KB at every entry it reads
# back (the compiler's `+prefer-no-gather` / `+prefer-no-scatter` are
# tuning preferences, which no host lists among its features): a failed
# case's captured stderr would be those.  FATAL is still printed.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _xla_flags  # noqa: E402  (lane flags shared with mp_child.py)

_xla_flags.apply(device_count=8)

import jax  # noqa: E402

# the suite runs on the CPU whatever the shell's JAX_PLATFORMS said
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)
# ONE compile a program a run: JAX's persistent compilation cache is on,
# in a directory this process makes and removes.  The second engine of a
# configuration, a scenario's replay and the same tiny model in the next
# file are read back, not compiled (the suite is bound by compiling).
# A directory a worker PROCESS, never a shared one: jax 0.9.0's
# `LRUCache.put` writes an entry with a plain `write_bytes`, so a second
# writer's reader could be handed half a file; and a directory this run
# made holds only what this machine wrote in this run, which is what
# keeps XLA's `cpu_aot_loader` from an entry with another machine's
# features (the abort the cache was once switched off for).  An
# inherited JAX_COMPILATION_CACHE_DIR (the chip tool's environment sets
# one) is overridden here, never read.
JIT_CACHE_DIR = tempfile.mkdtemp(prefix="dstpu_test_jit_")
atexit.register(shutil.rmtree, JIT_CACHE_DIR, ignore_errors=True)
jax.config.update("jax_compilation_cache_dir", JIT_CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="also run tests marked slow (long equivalence "
                          "tests; default selection keeps the suite fast)")


_LOG_FD = pytest.StashKey[int]()
_STALL_S = 300


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running equivalence test (opt-in: --runslow)")
    # the process's own stderr: capture is suspended while plugins are
    # configured, and an xdist worker's stderr is its parent's
    config.stash[_LOG_FD] = os.dup(2)


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow: run with --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _a_stalled_case_names_itself(request):
    """A case still running after five minutes prints every thread's
    stack into the run's log, past pytest's capture: a run that its time
    limit cuts then names the case it stood in.  It fails nothing."""
    faulthandler.dump_traceback_later(
        _STALL_S, exit=False, file=request.config.stash[_LOG_FD])
    yield
    faulthandler.cancel_dump_traceback_later()


@pytest.fixture(scope="session")
def devices():
    d = jax.devices()
    assert len(d) == 8, f"expected 8 virtual devices, got {len(d)}"
    return d


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2; the persistent compilation cache stays off
    for the module, because an entry written for a described chip cannot
    be read back without one and the next compile warns."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe the chip
        pytest.skip(f"TPU topology cannot be described here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """Sharding on one described v5e device."""
    return jax.sharding.SingleDeviceSharding(topo.devices[0])
