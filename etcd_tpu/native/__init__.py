"""Native (C++) runtime components, loaded over ctypes.

The hot host-side I/O paths — WAL segment framing/CRC/fsync — are C++
(``src/walog.cc``), mirroring how the reference keeps its durable-log
machinery out of the request path's interpreted layers. No binary is
committed: the shared library is built with g++ on first import into
the git-ignored ``lib/``, named by a hash of its source, so a process
always runs the code in its own checkout — file timestamps, which a
checkout or a copy sets arbitrarily, play no part.
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src")
_LIB = os.path.join(_DIR, "lib")

_lock = threading.Lock()
_cache: dict[str, ctypes.CDLL] = {}


def _build(name: str) -> str:
    src = os.path.join(_SRC, f"{name}.cc")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    out = os.path.join(_LIB, f"lib{name}-{digest}.so")
    if os.path.exists(out):
        return out
    os.makedirs(_LIB, exist_ok=True)
    tmp = out + f".tmp.{os.getpid()}"
    cmd = [
        "g++", "-O2", "-g", "-std=c++17", "-fPIC", "-shared",
        "-Wall", "-Wextra", "-o", tmp, src,
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True)
    except OSError as e:
        raise RuntimeError(f"cannot build {src}: {e}") from e
    if proc.returncode != 0:
        raise RuntimeError(
            f"building {src} failed (rc={proc.returncode}):\n"
            f"{proc.stderr}")
    os.replace(tmp, out)
    for stale in glob.glob(os.path.join(_LIB, f"lib{name}-*.so")):
        if stale != out:
            with contextlib.suppress(FileNotFoundError):  # a peer's build won
                os.unlink(stale)
    return out


def load(name: str) -> ctypes.CDLL:
    with _lock:
        lib = _cache.get(name)
        if lib is None:
            lib = ctypes.CDLL(_build(name))
            _cache[name] = lib
        return lib
