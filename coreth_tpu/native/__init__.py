"""Native (C++) host-runtime components, loaded over ctypes.

The reference's only native-adjacent pieces are its crypto deps (SURVEY.md
§2.6). Here the native layer is the fast host-side keccak used below the TPU
batch threshold and as the CPU baseline for benchmarks. Compiled lazily with
g++ on first use (native/_build.py); a failed build raises with the
compiler's output.
"""

from __future__ import annotations

import ctypes
import os
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "keccak.cpp")

_lock = threading.Lock()
_lib = None


def default_cpu_threads() -> int:
    """Worker fan-out for the native commit pipeline: the
    CORETH_TPU_CPU_THREADS env override, else min(16, cpu_count) — the
    reference's 16-goroutine cap (trie/hasher.go:124-139). One policy
    shared by the vm config default (cpu_threads=0 -> this), the
    resident mirror's host commits, and mpt_pool.h's C-side default."""
    raw = os.environ.get("CORETH_TPU_CPU_THREADS", "")
    if raw:
        try:
            v = int(raw)
            if v > 0:
                return v
        except ValueError:
            pass
    return min(16, os.cpu_count() or 1)


def load():
    """Return the ctypes lib, building it if needed."""
    global _lib
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None:
            return _lib
        from ._build import build_and_load

        lib = build_and_load(_SRC, "libkeccak", timeout=120)
        lib.keccak256.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
        ]
        lib.keccak256_batch.argtypes = [
            ctypes.c_char_p,
            np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS"),
            ctypes.c_uint64,
            ctypes.c_char_p,
        ]
        lib.keccak256_batch_mt.argtypes = lib.keccak256_batch.argtypes + [ctypes.c_int]
        _lib = lib
        return _lib


_OUT32 = ctypes.c_char * 32  # hoisted: create_string_buffer per call is
# measurable at millions of hashes (type lookup + isinstance checks)


def keccak256(data: bytes) -> bytes:
    lib = _lib if _lib is not None else load()
    out = _OUT32()
    lib.keccak256(data, len(data), out)
    return out.raw


def keccak256_batch(msgs, threads: int = 0) -> list:
    """Hash a list of byte strings on the CPU; threads=0 means single-thread."""
    n = len(msgs)
    if n == 0:
        return []
    lib = load()
    blob = b"".join(msgs)
    offsets = np.zeros(n + 1, dtype=np.uint64)
    np.cumsum(np.fromiter((len(m) for m in msgs), np.uint64, count=n), out=offsets[1:])
    out = ctypes.create_string_buffer(32 * n)
    if threads and threads > 1:
        lib.keccak256_batch_mt(blob, offsets, n, out, threads)
    else:
        lib.keccak256_batch(blob, offsets, n, out)
    raw = out.raw
    return [raw[32 * i:32 * i + 32] for i in range(n)]
