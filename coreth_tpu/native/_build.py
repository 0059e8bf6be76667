"""Shared native-library builder: compile C++ sources to a shared object
crash/race-safely and CDLL it.

Three loaders (keccak, mpt planner, secp256k1) share this path. Each
build is keyed by a hash of its source, every header beside it and the
compiler command, and lands at `build/<name>-<key>.so`: a library is
loaded only if it was built from the sources as they are now, never
because a file with the right name happens to sit in the tree. The
compile goes to a process-unique temp file followed by os.rename — POSIX
rename is atomic, so concurrent processes (parallel test workers, a
parent and its child) can race freely: each either sees a complete .so
or replaces it with its own identical build."""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import os
import subprocess
import tempfile

# portable code generation: no -march=native, so a build is a function
# of the key alone and runs on any x86-64 host that loads it
CXX = "g++"
CXX_FLAGS = ["-O3", "-shared", "-fPIC"]
BUILD_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "build")


class NativeBuildError(RuntimeError):
    """A native library failed to compile or load."""


def build_key(src: str) -> str:
    """Hash of the source, the headers in its directory and the compile
    command — the identity of one build."""
    h = hashlib.sha256()
    h.update(" ".join([CXX, *CXX_FLAGS]).encode())
    src_dir = os.path.dirname(os.path.abspath(src))
    headers = sorted(f for f in os.listdir(src_dir) if f.endswith(".h"))
    for path in [src] + [os.path.join(src_dir, f) for f in headers]:
        h.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def build_and_load(src: str, name: str, timeout: int = 180) -> ctypes.CDLL:
    """Compile src (unless this key was already built) and dlopen it.
    Raises NativeBuildError with the compiler's stderr on failure."""
    os.makedirs(BUILD_DIR, exist_ok=True)
    lib_path = os.path.join(BUILD_DIR, f"{name}-{build_key(src)}.so")
    if not os.path.exists(lib_path):
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=BUILD_DIR)
        os.close(fd)
        cmd = [CXX, *CXX_FLAGS, "-o", tmp, src, "-lpthread"]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=timeout)
            if proc.returncode != 0:
                raise NativeBuildError(
                    f"{' '.join(cmd)} exited {proc.returncode}:\n"
                    f"{proc.stderr}")
            os.rename(tmp, lib_path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        # earlier keys of this library are dead builds (a concurrent
        # builder may have removed one first)
        for old in glob.glob(os.path.join(BUILD_DIR, f"{name}-*.so")):
            if old != lib_path:
                with contextlib.suppress(FileNotFoundError):
                    os.unlink(old)
    try:
        return ctypes.CDLL(lib_path)
    except OSError as e:
        raise NativeBuildError(f"cannot load {lib_path}: {e}") from e
