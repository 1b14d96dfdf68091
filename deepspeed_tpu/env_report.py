"""Environment / op-compatibility report (ref: deepspeed `ds_report`
CLI — deepspeed/env_report.py, which prints torch/CUDA versions and a
green/red table of which fused ops can JIT on this machine).

TPU equivalent: package versions, the JAX backend and device inventory,
whether the Pallas kernels actually compile here, and the C++ host
runtime's build status.  Run as ``dstpu-report``.
"""

from __future__ import annotations

import importlib
import shutil
import sys


OKAY, FAIL = "[OKAY]", "[FAIL]"


def _version(mod: str) -> str:
    try:
        m = importlib.import_module(mod)
        return getattr(m, "__version__", "unknown")
    except Exception:
        return "not installed"


def _probe_backend():
    import jax

    try:
        devs = jax.devices()
        return jax.default_backend(), [str(d) for d in devs], None
    except Exception as e:  # no accelerator, plugin failed to load, ...
        return "unavailable", [], str(e)


def _probe_pallas() -> tuple:
    """Compile-and-run a trivial pallas kernel on the default backend
    (interpret mode when no accelerator is up)."""
    try:
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        interpret = jax.default_backend() not in ("tpu", "gpu")
        out = pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            interpret=interpret)(jnp.ones((8, 128), jnp.float32))
        mode = "interpret" if interpret else "compiled"
        return float(out[0, 0]) == 2.0, mode, None
    except Exception as e:
        return False, "-", str(e)


def _probe_native() -> tuple:
    try:
        from deepspeed_tpu.io.native import _ensure_lib

        lib = _ensure_lib()
        return lib is not None, None
    except Exception as e:
        return False, str(e)


def report() -> dict:
    """Collect everything; the CLI renders this dict."""
    backend, devices, backend_err = _probe_backend()
    pallas_ok, pallas_mode, pallas_err = _probe_pallas()
    native_ok, native_err = _probe_native()
    import deepspeed_tpu

    return {
        "versions": {
            "python": sys.version.split()[0],
            "deepspeed_tpu": getattr(deepspeed_tpu, "__version__", "0.x"),
            "jax": _version("jax"),
            "jaxlib": _version("jaxlib"),
            "orbax-checkpoint": _version("orbax.checkpoint"),
            "optax": _version("optax"),
            "numpy": _version("numpy"),
        },
        "backend": {"name": backend, "devices": devices,
                    "error": backend_err},
        "ops": {
            "pallas": {"ok": pallas_ok, "mode": pallas_mode,
                       "error": pallas_err},
            "csrc (aio/hostruntime)": {"ok": native_ok,
                                       "error": native_err},
            "csrc (cpu_adam)": dict(zip(("ok", "error"),
                                        _probe_cpu_adam())),
            "g++": {"ok": shutil.which("g++") is not None},
        },
    }


def _probe_cpu_adam() -> tuple:
    try:
        from deepspeed_tpu.ops.cpu_adam import native_available

        return native_available(), None
    except Exception as e:
        return False, str(e)


def main(argv=None):
    r = report()
    print("-" * 60)
    print("deepspeed_tpu environment report (ref: ds_report)")
    print("-" * 60)
    for name, ver in r["versions"].items():
        print(f"{name:>20}: {ver}")
    print("-" * 60)
    b = r["backend"]
    print(f"{'backend':>20}: {b['name']}")
    for d in b["devices"]:
        print(f"{'device':>20}: {d}")
    if b["error"]:
        print(f"{'backend error':>20}: {b['error'][:120]}")
    print("-" * 60)
    for op, st in r["ops"].items():
        tag = OKAY if st["ok"] else FAIL
        extra = st.get("mode") or ""
        print(f"{op:>24} {tag} {extra}")
        if st.get("error"):
            print(f"{'':>24}   {st['error'][:120]}")
    print("-" * 60)
    return 0 if all(st["ok"] for st in r["ops"].values()) else 1


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
