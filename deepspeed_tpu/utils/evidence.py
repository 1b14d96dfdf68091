"""Atomic JSON evidence writes, shared by every bench/evidence producer
(bench_serving.py, examples/*_offload.py).

The whole point of incremental evidence flushing is surviving a run
killed at its time limit — so the flush itself must never be the thing
a SIGKILL truncates.  Temp file + ``os.replace``: a kill mid-write leaves a stray
``.tmp`` and the PREVIOUS complete evidence intact; readers never see a
half-written JSON.
"""

from __future__ import annotations

import json
import os


def atomic_write_json(obj, path: str, indent: int = 1) -> None:
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=indent)
    os.replace(tmp, path)


def atomic_write_text(text: str, path: str) -> None:
    """Same temp + ``os.replace`` contract for plain text — the
    telemetry Prometheus exposition writer, where a scraper racing the
    write must only ever see a complete file."""
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, path)
