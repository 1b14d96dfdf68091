"""The device as JAX reports it, its memory, its peaks, and what JAX
compiled or fetched from the persistent cache."""

import json
import os

from benchmark.harness import HERE


def info():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def require_tpu(chips):
    """``info()``, or SystemExit without a TPU or with too few chips:
    a measurement never falls back to the CPU."""
    found = info()
    if found["platform"] != "tpu" or found["count"] < chips:
        raise SystemExit(f"this cell needs {chips} TPU chip(s) and JAX "
                         f"found {found}; there is no CPU fallback "
                         "(--rehearse walks the same code at a toy size)")
    return found


def peaks(kind):
    """The published peaks of one chip; an unknown device is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in peaks.json "
                       f"(it has {sorted(table)}); add the row with its "
                       "source, never a default")
    return table[kind]


def memory(chips):
    """Peak bytes in use on the fullest of the first ``chips`` devices
    (None where the backend keeps no statistics, as on the CPU)."""
    import jax

    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.devices()[:chips]]
    return None if None in peaks_ else max(peaks_)


def enable_compile_cache():
    """One rule, the program's own (``utils/backend.py``): where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``.jax_compile_cache/`` at a
    fixed place in the checkout (the program derives it from its own
    location, which is the checkout).  Returns the directory."""
    from deepspeed_tpu.utils import backend

    return backend.enable_compile_cache()


class CompileCounter:
    """Programs fetched from the persistent cache and programs compiled,
    from JAX's own monitoring events (as ``chip_smoke.py`` counts)."""

    def __init__(self):
        import jax

        self.hits = self.compiled = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1

    def programs_built(self):
        """Every program made ready, from the cache or by the compiler:
        inside the window this must not move.  (A backend compile is
        reported for a cache hit too, so hits are not added.)"""
        return self.compiled
