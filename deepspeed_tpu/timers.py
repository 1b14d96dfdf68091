"""Synchronized timers + throughput accounting (ref: deepspeed/utils/timers.py).

The reference's ``SynchronizedWallClockTimer`` calls
``torch.cuda.synchronize`` around ``time.time``; on TPU the analogue is
``jax.block_until_ready`` on a sentinel array (XLA dispatch is async).
``ThroughputTimer`` mirrors the reference's samples/sec + TFLOPs
reporting and adds MFU against the chip's peak FLOPs.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp

# Peaks per chip, keyed by the ``device_kind`` string JAX reports:
# (bf16 FLOP/s, HBM bytes/s).  A device that is not here is an error,
# not a default — an MFU or MBU against an assumed peak is not a
# measurement.  Add a row with its source when a new chip is used.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    # HBM.  "TPU v5 lite" is what a v5e reports (chip_smoke.py, PR 21).
    "TPU v5 lite": (197e12, 819e9),
    # nominal row so the CPU tests' MFU/MBU math has a denominator;
    # nothing computed against it is a device metric
    "cpu": (1e12, 100e9),
}


def _device_peaks():
    kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no peak FLOP/s / bandwidth row for device_kind {kind!r}; "
            f"known: {sorted(DEVICE_PEAKS)} (add it to "
            "deepspeed_tpu/timers.py DEVICE_PEAKS with its source)"
        ) from None


def device_peak_flops() -> float:
    """Peak bf16 FLOP/s of the attached chip (raises for an unknown one)."""
    return _device_peaks()[0]


def device_peak_bandwidth() -> float:
    """Peak HBM bytes/s of the attached chip (raises for an unknown one)."""
    return _device_peaks()[1]


def _sync() -> None:
    """Drain the async dispatch queue so wall-clock brackets device work."""
    jax.block_until_ready(jnp.zeros(()))


class _Timer:
    """One named timer (ref: timers.py ``SynchronizedWallClockTimer.Timer``)."""

    def __init__(self, name: str):
        self.name = name
        self.started = False
        self._start = 0.0
        self._elapsed = 0.0
        self.count = 0

    def start(self) -> None:
        if self.started:
            raise RuntimeError(f"timer {self.name} already started")
        _sync()
        self._start = time.perf_counter()
        self.started = True

    def stop(self, reset: bool = False) -> None:
        if not self.started:
            raise RuntimeError(f"timer {self.name} not started")
        _sync()
        dt = time.perf_counter() - self._start
        self._elapsed = dt if reset else self._elapsed + dt
        self.count += 1
        self.started = False

    def elapsed(self, reset: bool = True) -> float:
        e = self._elapsed
        if reset:
            self._elapsed = 0.0
            self.count = 0
        return e

    def mean(self) -> float:
        return self._elapsed / max(self.count, 1)


class SynchronizedWallClockTimer:
    """Named-timer registry (ref: deepspeed/utils/timers.py)."""

    def __init__(self):
        self.timers: Dict[str, _Timer] = {}

    def __call__(self, name: str) -> _Timer:
        if name not in self.timers:
            self.timers[name] = _Timer(name)
        return self.timers[name]

    def log(self, names=None, reset: bool = True) -> str:
        names = names if names is not None else sorted(self.timers)
        parts = []
        for n in names:
            if n in self.timers:
                ms = self.timers[n].elapsed(reset=reset) * 1000.0
                parts.append(f"{n}: {ms:.2f}ms")
        msg = " | ".join(parts)
        from deepspeed_tpu.utils.logging import log_dist

        log_dist(f"time: {msg}")
        return msg


class ThroughputTimer:
    """Samples/sec, tokens/sec, TFLOPs, MFU (ref: timers.py ThroughputTimer).

    ``flops_per_sample`` (if given) enables TFLOPs + MFU reporting; use
    :func:`deepspeed_tpu.profiler.transformer_train_flops` to estimate it.
    """

    def __init__(self, batch_size: int, seq_len: int = 1,
                 flops_per_sample: Optional[float] = None,
                 start_step: int = 2):
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.flops_per_sample = flops_per_sample
        self.start_step = start_step  # skip compile/warmup steps
        self.step_count = 0
        self.total_time = 0.0
        self.total_samples = 0
        self._t0 = 0.0

    def start(self) -> None:
        _sync()
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        _sync()
        dt = time.perf_counter() - self._t0
        self.step_count += 1
        if self.step_count > self.start_step:
            self.total_time += dt
            self.total_samples += self.batch_size

    @property
    def samples_per_sec(self) -> float:
        return self.total_samples / self.total_time if self.total_time else 0.0

    @property
    def tokens_per_sec(self) -> float:
        return self.samples_per_sec * self.seq_len

    @property
    def tflops(self) -> float:
        if not self.flops_per_sample:
            return 0.0
        return self.samples_per_sec * self.flops_per_sample / 1e12

    @property
    def mfu(self) -> float:
        if not self.flops_per_sample:
            return 0.0
        return self.samples_per_sec * self.flops_per_sample / device_peak_flops()

    def summary(self) -> Dict[str, float]:
        return {
            "samples_per_sec": self.samples_per_sec,
            "tokens_per_sec": self.tokens_per_sec,
            "tflops": self.tflops,
            "mfu": self.mfu,
            "steps": float(max(self.step_count - self.start_step, 0)),
        }
