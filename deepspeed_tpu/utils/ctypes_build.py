"""Shared build-and-dlopen helper for the csrc ctypes bindings
(io/aio.py, io/native.py, ops/cpu_adam.py — one loader, not three
drifting copies).

The shared libraries are build outputs, not tracked files: each is
compiled from its ``csrc/*.cpp`` on first use and rebuilt when the
source changes.  "Changed" is decided by a hash of the source kept
beside the library (``<lib>.srchash``), never by file times — a copy or
a checkout resets those.

- temp path + atomic rename: concurrent builders racing the same ``-o``
  target can CDLL a half-written .so;
- rebuild-once on dlopen failure: a library carried over from another
  toolchain (e.g. a GLIBCXX version mismatch) raises OSError from CDLL
  but rebuilds from source in seconds.

Only a missing compiler returns None (callers then take their
pure-Python path), and it says so in the log.  A source that does not
compile, or a fresh build that does not load, raises.

Callers keep their own locks/caches and symbol setup; this is just the
build + load core.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
from typing import Optional, Sequence

from deepspeed_tpu.utils.logging import logger


def load_or_build(lib_path: str, src_path: str,
                  extra_flags: Sequence[str] = ()
                  ) -> Optional[ctypes.CDLL]:
    """Return the dlopened library, building/rebuilding as needed;
    None (logged) when there is no ``g++`` to build it with."""
    hash_path = lib_path + ".srchash"
    with open(src_path, "rb") as f:
        want = hashlib.sha256(f.read() + " ".join(extra_flags).encode()
                              ).hexdigest()

    def build():
        tmp = f"{lib_path}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                ["g++", "-O3", *extra_flags, "-shared", "-fPIC", "-o", tmp,
                 src_path, "-lpthread"],
                check=True, capture_output=True, text=True)
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"{src_path} does not compile:\n{e.stderr}") from e
        os.replace(tmp, lib_path)
        with open(hash_path, "w") as f:
            f.write(want)

    def built_from_this_source() -> bool:
        try:
            with open(hash_path) as f:
                return os.path.exists(lib_path) and f.read() == want
        except FileNotFoundError:
            return False

    if not built_from_this_source():
        if shutil.which("g++") is None:
            logger.warning(
                "no g++ on PATH: %s not built, its caller takes the "
                "pure-Python path", os.path.basename(lib_path))
            return None
        build()
    try:
        return ctypes.CDLL(lib_path)
    except OSError:
        build()
        return ctypes.CDLL(lib_path)
