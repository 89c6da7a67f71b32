"""Loader for the in-tree native (C++) runtime components.

The reference's native-performance pieces live out-of-tree in Ollama's
C++ runtime; ours live in ``native/`` as small C-ABI shared objects
consumed via ctypes (no pybind11 in this image). Loading is lazy and
fail-soft: the first load runs one quiet ``make``, which builds a missing
library and rebuilds one older than its source (a stale ``.so`` copied
along with a tree must not be used as it is); if the toolchain is
unavailable the caller falls back to its pure-Python path, so the
framework never *requires* the native build.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

from .log import get_logger

log = get_logger("native")

_NATIVE_DIR = os.environ.get("NATIVE_LIB_DIR") or os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native")

_lock = threading.Lock()
_cache: dict[str, object] = {}


def load(name: str) -> object | None:
    """dlopen ``native/lib<name>.so``, (re)building it when make says so.

    Returns the ctypes.CDLL or None (caller falls back to Python).
    Results (including failures) are cached per process.
    """
    with _lock:
        if name in _cache:
            return _cache[name]
        path = os.path.join(_NATIVE_DIR, f"lib{name}.so")
        # make decides whether the library is up to date. A directory
        # without a Makefile is a prebuilt NATIVE_LIB_DIR (ci.sh's
        # sanitizer trees) and is loaded as it is.
        if os.path.exists(os.path.join(_NATIVE_DIR, "Makefile")):
            try:
                subprocess.run(["make", "-C", _NATIVE_DIR, f"lib{name}.so"],
                               capture_output=True, timeout=120, check=True)
            except (OSError, subprocess.SubprocessError) as e:
                log.info("native %s unavailable (build failed: %s); "
                         "using pure-Python path", name, e)
                _cache[name] = None
                return None
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            log.info("native %s unavailable (%s); using pure-Python path",
                     name, e)
            lib = None
        _cache[name] = lib
        return lib
