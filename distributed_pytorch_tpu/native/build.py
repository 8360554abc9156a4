"""On-demand g++ build of the native library (no pip/pybind dependency).

Builds ``dataloader.cpp`` into ``_native_<hash of the source>.so`` next to
the sources the first time it is needed.  The binary is not tracked by git,
and its name is keyed by the source's CONTENT: file times mean nothing in a
copied or freshly checked-out tree, a content hash cannot go stale.
Thread-safe across processes via atomic rename.
"""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
import tempfile

ABI_VERSION = 1
_THIS_DIR = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(_THIS_DIR, "dataloader.cpp")

CXX = os.environ.get("CXX", "g++")
CXXFLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC", "-pthread", "-Wall"]


def lib_path() -> str:
    """Where the library built from the current source lives."""
    with open(SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_THIS_DIR, f"_native_{digest}.so")


def build(force: bool = False) -> str | None:
    """Return the path to the built .so, or None if no toolchain."""
    lib = lib_path()
    if not force and os.path.exists(lib):
        return lib
    tmp = None
    try:
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_THIS_DIR)
        os.close(fd)
        subprocess.run([CXX, *CXXFLAGS, "-o", tmp, SRC], check=True,
                       capture_output=True, text=True)
        os.replace(tmp, lib)  # atomic: concurrent builders race benignly
        for stale in glob.glob(os.path.join(_THIS_DIR, "_native_*.so")):
            if stale != lib:  # builds of earlier sources
                try:
                    os.remove(stale)
                except FileNotFoundError:
                    pass  # a concurrent builder got there first
        return lib
    except (subprocess.CalledProcessError, OSError):
        # no toolchain, read-only install dir, ... -> numpy fallback
        if tmp and os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
        return None


if __name__ == "__main__":
    path = build(force=True)
    print(path or "build failed")
