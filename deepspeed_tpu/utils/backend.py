"""Device selection for the programs that measure on the chip
(``chip_smoke.py``, ``bench.py``, ``bench_serving.py``, ``bench_fleet.py``).

Two rules, each in one place:

- a measurement names the device it ran on and fails without a TPU — it
  never falls back to the CPU (:func:`require_tpu`);
- one persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
  says, else one fixed directory inside the checkout.  The path is part
  of the cache key, so a temp name, pid or time in it never hits
  (:func:`enable_compile_cache`).
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# git-ignored; lives in the checkout so the chip tool's copy and the
# driver's checkout both resolve it without any set-up
COMPILE_CACHE_DIR = os.path.join(_REPO, ".jax_compile_cache")


def device_info() -> Dict[str, object]:
    """The device as JAX reports it — every printed result carries it."""
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def require_tpu() -> Dict[str, object]:
    """:func:`device_info`, or ``SystemExit`` when JAX found no TPU."""
    info = device_info()
    if info["platform"] != "tpu":
        raise SystemExit(
            f"this program measures on a TPU and found {info}; there is "
            "no CPU fallback (tests run on the CPU under pytest, "
            "chip_smoke.py --rehearse walks its control flow there)")
    return info


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set JAX already uses that
    directory and nothing is set here.  On the CPU backend the cache
    stays off (None): XLA's CPU AOT loader can serve an artifact built
    for other machine features and abort the process — the reason
    ``tests/conftest.py`` keeps it off for the suite."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
