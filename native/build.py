"""Build-on-import for the native components.

The image bans pip/apt installs and ships no pybind11, so native code is
plain C++ compiled with the baked-in g++ into a shared object loaded via
ctypes.  The .so is cached next to the source with a build key beside it
(source bytes + compile command + this host's CPU feature flags) and is
reused only when the key matches: the build uses ``-march=native``, so a
library that arrived with a copied tree from another CPU is a SIGILL, not
a cache hit — it is rebuilt here instead.  Concurrent builders race
benignly through atomic renames.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import tempfile
from typing import Optional

_NATIVE_DIR = os.path.dirname(os.path.abspath(__file__))


class NativeBuildError(RuntimeError):
    pass


_CXX = ["g++", "-O3", "-march=native", "-std=c++17", "-shared", "-fPIC"]


def _host_cpu() -> str:
    """What ``-march=native`` resolved against on this machine."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line
    except OSError:
        pass
    return platform.machine() + platform.processor()


def _build_key(src: str, flags: list) -> str:
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CXX + flags).encode())
    h.update(_host_cpu().encode())
    return h.hexdigest()


def build_library(name: str, *, flags: Optional[list] = None,
                  timeout: float = 120.0) -> str:
    """Compile native/{name}.cpp -> native/build/lib{name}.so; returns the
    .so path.  Raises NativeBuildError if the toolchain is unusable (callers
    fall back to the pure-Python path)."""
    src = os.path.join(_NATIVE_DIR, f"{name}.cpp")
    out_dir = os.path.join(_NATIVE_DIR, "build")
    os.makedirs(out_dir, exist_ok=True)
    so = os.path.join(out_dir, f"lib{name}.so")
    key_path = so + ".key"
    key = _build_key(src, flags or [])
    try:
        with open(key_path) as f:
            if f.read() == key and os.path.exists(so):
                return so
    except OSError:
        pass  # no key: never built here
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=out_dir)
    os.close(fd)
    cmd = _CXX + ["-o", tmp, src] + (flags or [])
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout)
    except (OSError, subprocess.TimeoutExpired) as e:  # no g++ / hang
        os.unlink(tmp)
        raise NativeBuildError(f"native build unavailable: {e}") from e
    if proc.returncode != 0:
        os.unlink(tmp)
        raise NativeBuildError(
            f"g++ failed for {name}:\n{proc.stderr[-2000:]}")
    os.replace(tmp, so)  # atomic under concurrent builds
    fd, tmp = tempfile.mkstemp(suffix=".key", dir=out_dir)
    with os.fdopen(fd, "w") as f:
        f.write(key)
    os.replace(tmp, key_path)  # key lands AFTER the library it vouches for
    return so


def load_library(name: str, *, timeout: float = 120.0) -> ctypes.CDLL:
    return ctypes.CDLL(build_library(name, timeout=timeout))
