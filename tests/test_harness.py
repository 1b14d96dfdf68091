"""The test harness itself (``conftest.py``): the run's compile cache is
its own, and a program compiles once a process."""

import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp

from deepspeed_tpu.devprof import BUILD_LEDGER

TESTS = os.path.dirname(os.path.abspath(__file__))


def test_the_compile_cache_is_this_processs_own():
    made = jax.config.jax_compilation_cache_dir
    assert os.path.isdir(made)
    assert os.path.dirname(made) == tempfile.gettempdir()
    assert made != os.environ.get("JAX_COMPILATION_CACHE_DIR")


def test_an_inherited_cache_directory_is_not_used(tmp_path):
    """A chip tool's ``JAX_COMPILATION_CACHE_DIR`` does not leak in: a
    process that imports ``conftest.py`` under one compiles into a
    directory it made, writes nothing into the inherited one, and takes
    its own away when it ends."""
    inherited = tmp_path / "inherited"
    inherited.mkdir()
    probe = ("import conftest, jax, jax.numpy as jnp; "
             "jax.jit(lambda x: x * 2 + 1)(jnp.ones(4)); "
             "print(jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(inherited))
    out = subprocess.run([sys.executable, "-c", probe], cwd=TESTS, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    made = out.stdout.strip().splitlines()[-1]
    assert made not in (str(inherited), jax.config.jax_compilation_cache_dir)
    assert os.listdir(inherited) == []
    assert not os.path.exists(made)


def test_a_function_jitted_twice_compiles_once():
    """Two jits of one function share nothing in memory: the second is
    read back from the directory, as a scenario's replay or the same
    tiny engine in the next case is."""
    def make():
        def dstpu_t_harness(x):
            return jnp.tanh(x) @ x
        return jax.jit(dstpu_t_harness)

    x = jnp.ones((16, 16))
    first, second = make(), make()
    first(x)
    second(x)
    hits = [e["cache_hit"] for e in BUILD_LEDGER.snapshot()["entries"]
            if e["program"] == "dstpu_t_harness"]
    assert hits == [False, True]
    entries = [f for f in os.listdir(jax.config.jax_compilation_cache_dir)
               if f.startswith("jit_dstpu_t_harness-")]
    assert len(entries) == 1
