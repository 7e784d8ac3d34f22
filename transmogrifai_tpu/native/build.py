"""On-demand build of the native host-kernel library.

Compiles native/*.cpp (hashing.cpp text/CSV kernels + trees.cpp
occupancy-aware tree builder) into _tmog_native.so next to this file with
the baked-in g++ toolchain. A binary is trusted only when the digest
stamped beside it equals the digest of the sources and the compile command
it would be built from now — content, not mtimes, which a copy of the tree
scrambles. When no compiler is available the callers fall back to the
NumPy/XLA paths (see ops/native_bridge.py, ops/trees_host.py).
"""
from __future__ import annotations

import hashlib
import os
import subprocess
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
SOURCES = [os.path.join(_DIR, "hashing.cpp"), os.path.join(_DIR, "trees.cpp")]
LIB = os.path.join(_DIR, "_tmog_native.so")
PYEXT_SRC = os.path.join(_DIR, "pyext.cpp")
PYEXT_LIB = os.path.join(_DIR, "_tmog_pyext.so")


def _compile(cmd) -> bool:
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=240)
    except (OSError, subprocess.TimeoutExpired):
        return False
    return proc.returncode == 0


def _digest(srcs, flags) -> str:
    h = hashlib.sha256(" ".join(flags).encode())
    for src in srcs:
        with open(src, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _build(lib: str, srcs, flags, force: bool) -> Optional[str]:
    """Compile `srcs` into `lib` unless the stamp beside it already
    matches; the new binary lands by rename, so a concurrent loader never
    maps a half-written file."""
    stamp = lib + ".sha256"
    digest = _digest(srcs, flags)
    if not force and os.path.exists(lib):
        try:
            with open(stamp) as f:
                if f.read().strip() == digest:
                    return lib
        except OSError:
            pass
    tmp = f"{lib}.{os.getpid()}.tmp"
    try:
        if not _compile(["g++"] + flags + ["-o", tmp] + srcs):
            return None
        os.replace(tmp, lib)
        with open(stamp, "w") as f:
            f.write(digest)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return lib


_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC"]


def build(force: bool = False) -> Optional[str]:
    """Build (if needed) and return the library path, or None on failure."""
    srcs = [s for s in SOURCES if os.path.exists(s)]
    if not srcs:
        return None
    return _build(LIB, srcs, _FLAGS, force)


def build_pyext(force: bool = False) -> Optional[str]:
    """Build (if needed) the CPython extension module; path or None.

    A real extension module (not ctypes): the per-PyObject loops need the
    CPython API. Linked without libpython like any wheel .so — symbols
    resolve from the host interpreter at import.
    """
    if not os.path.exists(PYEXT_SRC):
        return None
    import sysconfig
    inc = sysconfig.get_paths().get("include")
    if not inc:
        return None
    return _build(PYEXT_LIB, [PYEXT_SRC], _FLAGS + ["-I", inc], force)
