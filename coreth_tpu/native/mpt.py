"""ctypes wrapper for the native MPT commit planner (mpt.cpp).

`plan_commit(items)` builds the full device-ready segment layout for a
sorted (key32 -> value) leaf set natively — replacing the Python
walk + RLP encode that round-1 profiling showed costing more than the
entire CPU hash baseline. The plan executes either on host
(`execute_cpu`, threaded keccak — the oracle and CPU-native baseline) or
on device via ops.keccak_fused.fused_commit using the exported arrays.

Reference seams this replaces on the hot path: trie/hasher.go:195-201
(hashData), trie/trie.go:573-626 (Hash/Commit walk),
core/state/statedb.go:952 (IntermediateRoot drain).
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

from . import default_cpu_threads  # noqa: F401  (re-export: one policy)
from ..fault import failpoint
from ..fault import register as _register_failpoint
from ..metrics import phase_timer

FP_BEFORE_ABSORB = _register_failpoint(
    "resident/before_absorb",
    "fires inside the device-sync half of a resident commit, just before "
    "its digests are absorbed/synchronized: `hang` wedges a pipelined "
    "drain mid-window (the watchdog then fires and host takeover must "
    "reproduce every in-flight root bit-exactly)")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "mpt.cpp")

_lock = threading.Lock()
_lib = None

_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_u64p = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")


def load():
    global _lib
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None:
            return _lib
        from ._build import build_and_load

        lib = build_and_load(_SRC, "libmpt")
        lib.mpt_plan.restype = ctypes.c_void_p
        lib.mpt_plan.argtypes = [_u8p, _u8p, _u64p, ctypes.c_uint64]
        lib.mpt_plan_borrowed.restype = ctypes.c_void_p
        lib.mpt_plan_borrowed.argtypes = [_u8p, _u8p, _u64p, ctypes.c_uint64]
        lib.mpt_plan_last_timings.restype = None
        lib.mpt_plan_last_timings.argtypes = [
            np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
        ]
        for name in ("mpt_plan_flat_bytes", "mpt_plan_total_lanes",
                     "mpt_plan_num_segments", "mpt_plan_total_patches",
                     "mpt_plan_num_hashed", "mpt_plan_num_nodes"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_uint64
            fn.argtypes = [ctypes.c_void_p]
        lib.mpt_plan_root_pos.restype = ctypes.c_int32
        lib.mpt_plan_root_pos.argtypes = [ctypes.c_void_p]
        lib.mpt_plan_export.restype = None
        lib.mpt_plan_export.argtypes = [
            ctypes.c_void_p, _u8p, _i32p, _i32p, _i32p, _i32p, _i32p,
        ]
        lib.mpt_plan_execute_cpu.restype = None
        lib.mpt_plan_execute_cpu.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p, _u8p,
        ]
        lib.mpt_plan_msg_lens.restype = None
        lib.mpt_plan_msg_lens.argtypes = [ctypes.c_void_p, _i32p]
        lib.mpt_plan_export_word_patches.restype = None
        lib.mpt_plan_export_word_patches.argtypes = [
            ctypes.c_void_p, _i32p, _i32p, _i32p,
        ]
        lib.mpt_plan_flat_ptr.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.mpt_plan_flat_ptr.argtypes = [ctypes.c_void_p]
        lib.mpt_plan_specs.restype = None
        lib.mpt_plan_specs.argtypes = [ctypes.c_void_p, _i32p]
        lib.mpt_plan_free.restype = None
        lib.mpt_plan_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


class CommitPlan:
    """A planned trie commit: native layout, host or device execution."""

    def __init__(self, handle, lib):
        self._h = handle
        self._lib = lib
        self.num_hashed = int(lib.mpt_plan_num_hashed(handle))
        self.num_nodes = int(lib.mpt_plan_num_nodes(handle))
        self.total_lanes = int(lib.mpt_plan_total_lanes(handle))
        self.root_pos = int(lib.mpt_plan_root_pos(handle))
        self._exported = None

    def __del__(self):
        h, self._h = self._h, None
        if h:
            self._lib.mpt_plan_free(h)

    def export(self):
        """Arrays in ops.keccak_fused.fused_commit format:
        (specs tuple, flat_msgs u8, nblocks i32, patch_lane, patch_off,
        patch_child)."""
        if self._exported is not None:
            return self._exported
        lib, h = self._lib, self._h
        n_seg = int(lib.mpt_plan_num_segments(h))
        flat = np.empty(int(lib.mpt_plan_flat_bytes(h)), dtype=np.uint8)
        nblocks = np.empty(self.total_lanes, dtype=np.int32)
        n_pat = int(lib.mpt_plan_total_patches(h))
        pl = np.empty(n_pat, dtype=np.int32)
        po = np.empty(n_pat, dtype=np.int32)
        pc = np.empty(n_pat, dtype=np.int32)
        specs = np.empty((n_seg, 4), dtype=np.int32)
        lib.mpt_plan_export(h, flat, nblocks, pl, po, pc, specs.reshape(-1))
        from ..ops.keccak_fused import SegmentSpec

        spec_t = tuple(SegmentSpec(int(a), int(b), int(c), int(d))
                       for a, b, c, d in specs)
        self._exported = (spec_t, flat, nblocks, pl, po, pc)
        return self._exported

    def export_words(self):
        """u32-device-path layout (ops/keccak_planned.py):
        (specs tuple, flat_words u32[total_words], dst_word i32[P],
        child_lane i32[P], shift i32[P]) — flat bytes reinterpreted as
        little-endian words (keccak absorb order), patches in word space.

        flat_words is a ZERO-COPY view into the plan's native buffer
        (valid while this CommitPlan is alive); the only copies on the
        way to the device are the h2d transfers themselves."""
        if getattr(self, "_exported_words", None) is not None:
            return self._exported_words
        n_bytes = int(self._lib.mpt_plan_flat_bytes(self._h))
        ptr = self._lib.mpt_plan_flat_ptr(self._h)
        flat = np.ctypeslib.as_array(ptr, shape=(n_bytes,))
        flat_words = flat.view(np.uint32)
        from ..ops.keccak_fused import SegmentSpec

        n_seg = int(self._lib.mpt_plan_num_segments(self._h))
        specs_arr = np.empty((n_seg, 4), dtype=np.int32)
        self._lib.mpt_plan_specs(self._h, specs_arr.reshape(-1))
        specs = tuple(SegmentSpec(int(a), int(b), int(c), int(d))
                      for a, b, c, d in specs_arr)
        n_pat = int(self._lib.mpt_plan_total_patches(self._h))
        dst_word = np.empty(n_pat, dtype=np.int32)
        child_lane = np.empty(n_pat, dtype=np.int32)
        shift = np.empty(n_pat, dtype=np.int32)
        self._lib.mpt_plan_export_word_patches(
            self._h, dst_word, child_lane, shift
        )
        self._exported_words = (specs, flat_words, dst_word, child_lane, shift)
        return self._exported_words

    def execute_planned(self, planned=None):
        """u32 staged device execution (ops/keccak_planned.py); returns the
        32-byte root."""
        from ..ops.keccak_planned import PlannedCommit

        runner = planned if planned is not None else _default_planned()
        specs, flat_words, dst_word, child_lane, shift = self.export_words()
        root, _ = runner.run(specs, flat_words, dst_word, child_lane, shift,
                             self.root_pos)
        return root

    def execute_cpu(self, threads: int = 1) -> bytes:  # hot-path
        """Host execution (threaded keccak); returns the 32-byte root."""
        root = np.empty(32, dtype=np.uint8)
        self._lib.mpt_plan_execute_cpu(self._h, threads, None, root)
        return root.tobytes()

    def execute_cpu_digests(self, threads: int = 1):
        """Host execution returning (root32, dig uint8[total_lanes, 32],
        real_mask bool[total_lanes]) — the per-lane oracle for device
        parity checks (pad lanes are left zero and masked out). The digest
        pointer is declared c_void_p in load(), so this never mutates the
        shared prototype (thread-safe vs concurrent execute_cpu)."""
        dig = np.zeros((self.total_lanes, 32), dtype=np.uint8)
        root = np.empty(32, dtype=np.uint8)
        self._lib.mpt_plan_execute_cpu(
            self._h, threads, dig.ctypes.data, root)
        msg_len = np.empty(self.total_lanes, dtype=np.int32)
        self._lib.mpt_plan_msg_lens(self._h, msg_len)
        return root.tobytes(), dig, msg_len > 0

    def execute_device(self, impl=None) -> Tuple[bytes, np.ndarray]:
        """One fused dispatch; returns (root, dig8 uint8[total_lanes, 32])."""
        from ..ops.keccak_fused import fused_commit

        specs, flat, nblocks, pl, po, pc = self.export()
        fn = impl if impl is not None else fused_commit
        dig8 = np.asarray(fn(specs, flat, nblocks, pl, po, pc))
        return dig8[self.root_pos].tobytes(), dig8

    def execute_staged(self, staged=None, want_digests: bool = True):
        """Pipelined per-segment dispatches (ops/keccak_staged.py); returns
        (root, dig8 | None)."""
        from ..ops.keccak_staged import StagedCommit

        runner = staged if staged is not None else _default_staged()
        specs, flat, nblocks, pl, po, pc = self.export()
        return runner.run(specs, flat, nblocks, pl, po, pc, self.root_pos,
                          want_digests=want_digests)


_staged_singleton = None


def _default_staged():
    global _staged_singleton
    if _staged_singleton is None:
        from ..ops.keccak_staged import StagedCommit

        _staged_singleton = StagedCommit()
    return _staged_singleton


def _default_planned():
    # shared with the chain path: one program set, and the Pallas kernel
    # engages by default on TPU backends (keccak_planned's selection)
    from ..ops.keccak_planned import default_planned_commit

    return default_planned_commit()


def plan_commit(keys: np.ndarray, vals_blob: bytes,
                val_offsets: np.ndarray) -> CommitPlan:
    """keys: uint8[n, 32] sorted unique; vals_blob concatenated values with
    val_offsets uint64[n+1]."""
    lib = load()
    keys = np.ascontiguousarray(keys, dtype=np.uint8).reshape(-1)
    n = keys.shape[0] // 32
    if n == 0:
        raise ValueError("empty leaf set: commit of an empty trie is EMPTY_ROOT")
    blob = np.frombuffer(vals_blob, dtype=np.uint8)
    if blob.size == 0:
        blob = np.zeros(1, dtype=np.uint8)
    blob = np.ascontiguousarray(blob)
    off = np.ascontiguousarray(val_offsets, dtype=np.uint64)
    # zero-copy: the native side reads the arrays ONLY during this call
    # (Builder/Writer both run inside mpt_plan_borrowed), so no pinning
    # beyond the call is needed — saves the ~100 MB input copy at 1M
    h = lib.mpt_plan_borrowed(keys, blob, off, n)
    if not h:
        raise ValueError("mpt_plan rejected input (unsorted or duplicate keys)")
    return CommitPlan(h, lib)


def items_to_arrays(items: Sequence[Tuple[bytes, bytes]]):
    """(key32, value) pairs -> the planner's sorted array triple
    (keys u8[n,32], vals_blob, offsets u64[n+1]); duplicate keys resolve
    last-write-wins (the natural trie-update semantics)."""
    dedup = {}
    for k, v in items:
        dedup[k] = v
    items = sorted(dedup.items())
    n = len(items)
    if n == 0:
        raise ValueError("empty leaf set: commit of an empty trie is EMPTY_ROOT")
    keys = np.frombuffer(b"".join(k for k, _ in items), dtype=np.uint8).reshape(n, 32)
    vals = b"".join(v for _, v in items)
    off = np.zeros(n + 1, dtype=np.uint64)
    np.cumsum(np.fromiter((len(v) for _, v in items), np.uint64, count=n), out=off[1:])
    return keys, vals, off


def plan_from_items(items: Sequence[Tuple[bytes, bytes]]) -> CommitPlan:
    """Convenience: plan_commit over items_to_arrays(items)."""
    return plan_commit(*items_to_arrays(items))


# ---------------------------------------------------------------------------
# Incremental trie (native/mpt_inc.cpp): device-resident commits
# ---------------------------------------------------------------------------

_INC_SRC = os.path.join(_DIR, "mpt_inc.cpp")
_inc_lib = None


def load_inc():
    global _inc_lib
    if _inc_lib is not None:
        return _inc_lib
    with _lock:
        if _inc_lib is not None:
            return _inc_lib
        from ._build import build_and_load

        lib = build_and_load(_INC_SRC, "libmpt_inc")
        lib.mpt_inc_new.restype = ctypes.c_void_p
        lib.mpt_inc_new.argtypes = [_u8p, _u8p, _u64p, ctypes.c_uint64]
        lib.mpt_inc_update.restype = ctypes.c_uint64
        lib.mpt_inc_update.argtypes = [
            ctypes.c_void_p, _u8p, _u8p, _u64p, ctypes.c_uint64,
        ]
        for name in ("mpt_inc_plan", "mpt_inc_flat_bytes", "mpt_inc_num_nodes",
                     "mpt_inc_num_dirty", "mpt_inc_total_lanes",
                     "mpt_inc_total_patches"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_uint64
            fn.argtypes = [ctypes.c_void_p]
        lib.mpt_inc_root_pos.restype = ctypes.c_int32
        lib.mpt_inc_root_pos.argtypes = [ctypes.c_void_p]
        lib.mpt_inc_flat_ptr.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.mpt_inc_flat_ptr.argtypes = [ctypes.c_void_p]
        lib.mpt_inc_specs.restype = None
        lib.mpt_inc_specs.argtypes = [ctypes.c_void_p, _i32p]
        lib.mpt_inc_word_patches.restype = None
        lib.mpt_inc_word_patches.argtypes = [ctypes.c_void_p, _i32p, _i32p, _i32p]
        lib.mpt_inc_execute_cpu.restype = None
        lib.mpt_inc_execute_cpu.argtypes = [ctypes.c_void_p, ctypes.c_int, _u8p]
        lib.mpt_inc_absorb.restype = None
        lib.mpt_inc_absorb.argtypes = [ctypes.c_void_p, _u8p, _u8p]
        lib.mpt_inc_plan_res.restype = ctypes.c_uint64
        lib.mpt_inc_plan_res.argtypes = [ctypes.c_void_p]
        lib.mpt_inc_res_meta.restype = None
        lib.mpt_inc_res_meta.argtypes = [
            ctypes.c_void_p,
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ]
        lib.mpt_inc_res_specs.restype = None
        lib.mpt_inc_res_specs.argtypes = [ctypes.c_void_p, _i32p]
        lib.mpt_inc_res_cls_counts.restype = None
        lib.mpt_inc_res_cls_counts.argtypes = [ctypes.c_void_p, _i32p]
        lib.mpt_inc_res_fresh.restype = None
        lib.mpt_inc_res_fresh.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, _u8p, _i32p,
        ]
        lib.mpt_inc_res_tables.restype = None
        lib.mpt_inc_res_tables.argtypes = [
            ctypes.c_void_p, _i32p, _i32p, _i32p, _i32p, _i32p,
        ]
        lib.mpt_inc_res_mark_clean.restype = None
        lib.mpt_inc_res_mark_clean.argtypes = [ctypes.c_void_p]
        lib.mpt_inc_res_absorb.restype = None
        lib.mpt_inc_res_absorb.argtypes = [ctypes.c_void_p, _u8p, _u8p]
        lib.mpt_inc_res_absorb_lanes.restype = ctypes.c_int64
        lib.mpt_inc_res_absorb_lanes.argtypes = [
            ctypes.c_void_p, _i32p, _u8p, ctypes.c_int64,
        ]
        lib.mpt_inc_res_absorb_finish.restype = ctypes.c_int64
        lib.mpt_inc_res_absorb_finish.argtypes = [ctypes.c_void_p, _u8p]
        lib.mpt_inc_set_lean.restype = None
        lib.mpt_inc_set_lean.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.mpt_inc_res_lean_count.restype = ctypes.c_int64
        lib.mpt_inc_res_lean_count.argtypes = [ctypes.c_void_p]
        lib.mpt_inc_res_lean.restype = None
        lib.mpt_inc_res_lean.argtypes = [ctypes.c_void_p, _u8p, _i32p, _i32p]
        lib.mpt_inc_mark_all_dirty.restype = None
        lib.mpt_inc_mark_all_dirty.argtypes = [ctypes.c_void_p]
        lib.mpt_inc_res_reset.restype = None
        lib.mpt_inc_res_reset.argtypes = [ctypes.c_void_p]
        lib.mpt_inc_checkpoint.restype = None
        lib.mpt_inc_checkpoint.argtypes = [ctypes.c_void_p]
        lib.mpt_inc_discard_checkpoint.restype = None
        lib.mpt_inc_discard_checkpoint.argtypes = [ctypes.c_void_p]
        lib.mpt_inc_rollback.restype = ctypes.c_uint64
        lib.mpt_inc_rollback.argtypes = [ctypes.c_void_p]
        lib.mpt_inc_flush_oldest.restype = None
        lib.mpt_inc_flush_oldest.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.mpt_inc_root.restype = None
        lib.mpt_inc_root.argtypes = [ctypes.c_void_p, _u8p]
        lib.mpt_inc_get.restype = ctypes.c_int64
        lib.mpt_inc_get.argtypes = [
            ctypes.c_void_p, _u8p, _u8p, ctypes.c_int64,
        ]
        lib.mpt_inc_absorb_store.restype = None
        lib.mpt_inc_absorb_store.argtypes = [
            ctypes.c_void_p, _u8p, ctypes.c_int64,
        ]
        lib.mpt_inc_absorb_store_range.restype = None
        lib.mpt_inc_absorb_store_range.argtypes = [
            ctypes.c_void_p, _u8p, ctypes.c_int64, ctypes.c_int64,
        ]
        lib.mpt_inc_export_size.restype = ctypes.c_int64
        lib.mpt_inc_export_size.argtypes = [
            ctypes.c_void_p,
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ]
        lib.mpt_inc_export_nodes.restype = None
        lib.mpt_inc_export_nodes.argtypes = [
            ctypes.c_void_p, _u8p, _u8p, _u64p,
        ]
        lib.mpt_inc_export_delta_size.restype = ctypes.c_int64
        lib.mpt_inc_export_delta_size.argtypes = [
            ctypes.c_void_p,
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ]
        lib.mpt_inc_export_delta_nodes.restype = None
        lib.mpt_inc_export_delta_nodes.argtypes = [
            ctypes.c_void_p, _u8p, _u8p, _u64p,
        ]
        lib.mpt_inc_free.restype = None
        lib.mpt_inc_free.argtypes = [ctypes.c_void_p]
        _inc_lib = lib
        return _inc_lib


class DeviceWedgedError(RuntimeError):
    """The device backend did not answer within the watchdog budget (a
    hung device: even a tiny sync never returns). Callers take over on
    the host (IncrementalTrie.rehash_host)."""


def _run_with_watchdog(fn, timeout: float, what: str):
    """Run fn() on a daemon worker; DeviceWedgedError on timeout. The
    abandoned worker may finish later — callers must ensure fn touches
    only device/executor state, never shared host structures."""
    box: dict = {}
    done = threading.Event()

    def work():
        try:
            box["val"] = fn()
        except BaseException as e:  # noqa: BLE001 — crosses threads
            box["err"] = e
        finally:
            done.set()

    t = threading.Thread(target=work, daemon=True, name=f"wd-{what}")
    t.start()
    if not done.wait(timeout):
        raise DeviceWedgedError(
            f"{what} produced nothing within {timeout:g}s")
    if "err" in box:
        raise box["err"]
    return box["val"]


EMPTY_ROOT = bytes.fromhex(
    "56e81f171bcc55a6ff8345e692c0f86e5b48e01b996cadc001622fb5e363b421"
)

# Lean wire record width (native kLeanWidth): a fresh class-1 row whose
# RLP fits this many bytes ships content-only — the device re-derives
# the keccak pad bits — so a leaf costs 72 B of row payload + 4 B arena
# index + 4 B length on the wire instead of the 136 B padded row.
LEAN_ROW_WIDTH = 72


class IncrementalTrie:
    """Persistent native MPT with per-commit dirty-subtree planning.

    The TPU-native analog of the reference's warm trie + dirty-only
    re-hash (trie/trie.go:573-626 + triedb/hashdb): the tree and its
    digest cache live across commits; each commit plans, ships, and
    hashes ONLY the dirty subtree. commit_cpu() is the incremental host
    baseline/oracle; commit_device() drains the mini-plan through the
    same PlannedCommit executor the chain uses.
    """

    def __init__(self, items: Sequence[Tuple[bytes, bytes]] = ()):
        lib = load_inc()
        self._lib = lib
        keys, vals, off = items_to_arrays(items) if items else (
            np.zeros((0, 32), np.uint8), b"", np.zeros(1, np.uint64))
        blob = np.frombuffer(vals, dtype=np.uint8) if vals else np.zeros(1, np.uint8)
        self._h = lib.mpt_inc_new(
            np.ascontiguousarray(keys.reshape(-1)),
            np.ascontiguousarray(blob),
            np.ascontiguousarray(off, dtype=np.uint64),
            keys.shape[0],
        )
        if not self._h:
            raise ValueError("unsorted or duplicate keys")

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h:
            self._lib.mpt_inc_free(h)

    def update(self, items: Sequence[Tuple[bytes, bytes]]) -> int:  # hot-path
        """Apply (key32, value) updates; empty value deletes. Returns the
        number of keys that actually changed the trie."""
        n = len(items)
        if n == 0:
            return 0
        keys = np.frombuffer(b"".join(k for k, _ in items), np.uint8)
        vals = b"".join(v for _, v in items)
        blob = np.frombuffer(vals, np.uint8) if vals else np.zeros(1, np.uint8)
        off = np.zeros(n + 1, np.uint64)
        np.cumsum(np.fromiter((len(v) for _, v in items), np.uint64, count=n),
                  out=off[1:])
        return int(self._lib.mpt_inc_update(
            self._h, np.ascontiguousarray(keys), np.ascontiguousarray(blob),
            off, n))

    @property
    def num_nodes(self) -> int:
        return int(self._lib.mpt_inc_num_nodes(self._h))

    def _export_plan(self):
        from ..ops.keccak_fused import SegmentSpec

        lib, h = self._lib, self._h
        n_seg = int(lib.mpt_inc_plan(h))
        if n_seg == 0:
            return None
        specs_arr = np.empty((n_seg, 4), np.int32)
        lib.mpt_inc_specs(h, specs_arr.reshape(-1))
        specs = tuple(SegmentSpec(int(a), int(b), int(c), int(d))
                      for a, b, c, d in specs_arr)
        n_bytes = int(lib.mpt_inc_flat_bytes(h))
        ptr = lib.mpt_inc_flat_ptr(h)
        flat_words = np.ctypeslib.as_array(ptr, shape=(n_bytes,)).view(np.uint32)
        n_pat = int(lib.mpt_inc_total_patches(h))
        dst = np.empty(n_pat, np.int32)
        child = np.empty(n_pat, np.int32)
        shift = np.empty(n_pat, np.int32)
        lib.mpt_inc_word_patches(h, dst, child, shift)
        return specs, flat_words, dst, child, shift, int(lib.mpt_inc_root_pos(h))

    def commit_cpu(self, threads: int = 1) -> bytes:  # hot-path
        """Incremental host commit; returns the 32-byte root."""
        self._pin_mode("host")
        with phase_timer("resident/phase/plan"):
            n_seg = self._lib.mpt_inc_plan(self._h)
        if n_seg == 0:
            return self.root()
        out = np.empty(32, np.uint8)
        with phase_timer("resident/phase/host_hash"):
            self._lib.mpt_inc_execute_cpu(self._h, threads, out)
        return out.tobytes()

    def commit_device(self, planned=None) -> bytes:
        """Incremental device commit through ops/keccak_planned; h2d is
        O(dirty set), digests read back into the native cache."""
        self._pin_mode("host")
        exported = self._export_plan()
        if exported is None:
            return self.root()
        specs, flat_words, dst, child, shift, root_pos = exported
        if planned is None:
            from ..ops.keccak_planned import default_planned_commit

            planned = default_planned_commit()
        _root, dig = planned.run(specs, flat_words, dst, child, shift,
                                 root_pos, want_digests=True)
        dig8 = np.ascontiguousarray(dig).view(np.uint8).reshape(-1, 32)
        out = np.empty(32, np.uint8)
        self._lib.mpt_inc_absorb(
            self._h, np.ascontiguousarray(dig8.reshape(-1)), out)
        return out.tobytes()

    # ---- resident commits (deferred absorb + template residency) ----
    #
    # A trie is EITHER host-cached (commit_cpu/commit_device keep the
    # digest cache on the host) OR device-resident (digests live only in
    # the executor's store). Mixing modes would serve stale digests, so
    # the first commit pins the mode.

    def _check_mode(self, mode: str):
        cur = getattr(self, "_mode", None)
        if cur is not None and cur != mode:
            raise RuntimeError(
                f"trie is in {cur!r} commit mode; {mode!r} commits would "
                "read a stale digest cache")

    def _pin_mode(self, mode: str):
        self._check_mode(mode)
        self._mode = mode

    def export_resident_plan(self):
        """Plan the dirty subtree for a device-resident commit and export
        the upload payload (ops/keccak_resident.py's input format).
        Returns None when nothing is dirty."""
        lib, h = self._lib, self._h
        with phase_timer("resident/phase/plan"):
            n_seg = int(lib.mpt_inc_plan_res(h))
        if n_seg == (1 << 64) - 1:
            raise ValueError("node RLP wider than the resident row limit")
        if n_seg == (1 << 64) - 2:
            raise ValueError(
                "resident arena class would exceed the 2GB byte-offset "
                "range (checked before any allocation)")
        if n_seg == 0:
            return None
        with phase_timer("resident/phase/export"):
            meta = np.empty(7, np.int64)
            lib.mpt_inc_res_meta(h, meta)
            total_lanes, total_patches = int(meta[0]), int(meta[1])
            specs = np.empty((n_seg, 6), np.int32)
            lib.mpt_inc_res_specs(h, specs.reshape(-1))
            n_cls = int(meta[6])
            cls_counts = np.empty((n_cls, 2), np.int32)
            lib.mpt_inc_res_cls_counts(h, cls_counts.reshape(-1))
            rowidx = np.empty(total_lanes, np.int32)
            lane_slot = np.empty(total_lanes, np.int32)
            off = np.empty(total_patches, np.int32)
            src = np.empty(total_patches, np.int32)
            oldidx = np.empty(total_patches, np.int32)
            lib.mpt_inc_res_tables(h, rowidx, lane_slot, off, src, oldidx)
            fresh = {}
            classes = {}
            for cls in range(1, n_cls):
                n_fresh, rows_needed = int(cls_counts[cls, 0]), int(
                    cls_counts[cls, 1])
                if rows_needed > 1:
                    classes[cls] = (n_fresh, rows_needed)
                if n_fresh == 0:
                    continue
                width = cls * 136
                rows = np.empty(n_fresh * width, np.uint8)
                idx = np.empty(n_fresh, np.int32)
                lib.mpt_inc_res_fresh(h, cls, rows, idx)
                fresh[cls] = (rows.view(np.uint32).reshape(n_fresh,
                                                           width // 4),
                              idx)
            lean = None
            n_lean = int(lib.mpt_inc_res_lean_count(h))
            if n_lean:
                lrows = np.empty(n_lean * LEAN_ROW_WIDTH, np.uint8)
                lidx = np.empty(n_lean, np.int32)
                llen = np.empty(n_lean, np.int32)
                lib.mpt_inc_res_lean(h, lrows, lidx, llen)
                lean = (lrows.view(np.uint32).reshape(
                    n_lean, LEAN_ROW_WIDTH // 4), lidx, llen)
        return {
            "specs": specs,
            "classes": classes,
            "fresh": fresh,
            "lean": lean,
            "rowidx": rowidx,
            "lane_slot": lane_slot,
            "off": off,
            "src": src,
            "oldidx": oldidx,
            "total_lanes": total_lanes,
            "store_slots": int(meta[2]),
            "root_lane": int(meta[3]),
            "num_dirty": int(meta[4]),
            "fresh_bytes": int(meta[5]),
        }

    def commit_resident_timed(self, executor, timeout: Optional[float]):
        """commit_resident + synchronized root under a device watchdog.

        Raises DeviceWedgedError if the device does not produce the root
        within [timeout] seconds. The watchdog thread runs ONLY the
        executor/device half (run + sync); every native-trie mutation —
        the plan export before, res_mark_clean after — stays on the
        calling thread, so an abandoned worker that later revives can
        never race a host takeover's rehash on this trie's memory.

        timeout=None degrades to the plain synchronized commit."""
        if self.num_nodes == 0:
            # empty-path: host constant, no device op to guard
            return executor.root_bytes(self.commit_resident(executor))
        self._check_mode("resident")
        executor.check_binding(self)
        export = self.export_resident_plan()
        self._pin_mode("resident")
        executor.bind(self)
        if export is None:
            work = lambda: executor.root_bytes(executor.last_root)  # noqa: E731
        else:
            executor.prepare(export)  # compile outside the watchdog

            def work():
                return executor.root_bytes(executor.run(export))
        if timeout is None:
            root = work()
        else:
            root = _run_with_watchdog(work, timeout, "resident commit")
        if export is not None:
            self._lib.mpt_inc_res_mark_clean(self._h)
        return root

    def rehash_host(self, threads: int = 1) -> bytes:
        """Device-failure takeover: rebuild the FULL host digest cache
        with one CPU commit and re-pin the trie to host mode. After a
        resident commit history the host cache is stale (digests lived
        in the device store); marking every node dirty makes the next
        host plan a whole-trie rehash, after which commit_cpu /
        export_nodes serve the trie with no device at all."""
        self._lib.mpt_inc_mark_all_dirty(self._h)
        self._mode = "host"
        return self.commit_cpu(threads=threads)

    def rebase_residency(self) -> None:
        """Mesh-ladder demotion seam: abandon every device-side
        assignment (store slots, arena rows) and mark the whole trie
        dirty, then UNPIN the commit mode. The next resident/template
        commit re-pins its mode and re-uploads every row — exactly the
        first commit after construction — so residency can rebuild on a
        FRESH executor. Bit-exact by construction: all rows are fresh,
        so no delta patch ever reads the abandoned executor's store
        (every "old" term is the zero sentinel)."""
        self._lib.mpt_inc_res_reset(self._h)
        self._mode = None

    def commit_resident(self, executor):
        """Device-resident commit: plan, ship fresh rows + patch tables,
        dispatch, mark clean. Returns the LAZY uint32[8] root handle (use
        executor.root_bytes(...) to synchronize) so callers can pipeline
        the next commit's planning against this commit's device work."""
        if self.num_nodes == 0:
            # empty trie: nothing device-side to do, and the previous
            # last_root (if any) is stale — the root is the constant
            self._pin_mode("resident")
            executor.bind(self)
            empty = np.frombuffer(EMPTY_ROOT, np.uint8).view("<u4").copy()
            executor.last_root = empty
            return empty
        self._check_mode("resident")
        executor.check_binding(self)
        export = self.export_resident_plan()  # may raise: mode not pinned yet
        self._pin_mode("resident")
        executor.bind(self)
        if export is None:
            return executor.last_root
        root = executor.run(export)
        self._lib.mpt_inc_res_mark_clean(self._h)
        return root

    def commit_resident_dispatch(self, executor,
                                 timeout: Optional[float] = None):
        """Pipelined resident commit: plan + dispatch WITHOUT waiting for
        the device, then return a resolve() closure that synchronizes the
        root later. Between dispatch and resolve the caller may plan and
        dispatch further commits against the same executor — their patch
        tables reference this commit's still-in-flight digest store
        directly (JAX async dispatch keeps device programs ordered), so
        host planning of commit k+1 overlaps device execution of commit
        k: nodes/max(plan, transfer) instead of nodes/(plan + transfer).

        Every native-trie mutation (plan export, res_mark_clean) happens
        on the calling thread before return; resolve() touches only the
        executor handle, so a watchdog-abandoned resolve can never race
        a host takeover's rehash on this trie's memory."""
        if self.num_nodes == 0:
            root = executor.root_bytes(self.commit_resident(executor))
            return lambda: root
        self._check_mode("resident")
        executor.check_binding(self)
        export = self.export_resident_plan()
        self._pin_mode("resident")
        executor.bind(self)
        if export is None:
            handle = executor.last_root
        else:
            executor.prepare(export)  # compile outside the watchdog
            if timeout is None:
                handle = executor.run(export)
            else:
                handle = _run_with_watchdog(
                    lambda: executor.run(export), timeout,
                    "resident dispatch")
            self._lib.mpt_inc_res_mark_clean(self._h)

        def resolve() -> bytes:
            def sync():
                failpoint("resident/before_absorb")
                return executor.root_bytes(handle)

            if timeout is None:
                return sync()
            return _run_with_watchdog(sync, timeout, "resident drain")

        return resolve

    def commit_template(self, executor, timeout: Optional[float] = None,
                        full_readback: bool = False):
        """Template-resident planned commit: the device keeps this trie's
        row arenas + digest store across commits (dirty BRANCH rows are
        re-zeroed/re-patched on device, uploads carry only fresh content
        — ~70 B/leaf instead of ~320 B/dirty node), but unlike the pure
        resident mode the per-commit digest matrix IS read back and
        absorbed into the host cache. root()/export_nodes() stay valid
        every commit and a device-failure takeover needs no full rehash
        — the planned path's semantics at the resident path's h2d cost.

        Interleaving with commit_cpu would corrupt the device store
        (fresh rows reference clean children by store slot, which a host
        commit never updates), so this pins its own 'template' mode."""
        if self.num_nodes == 0:
            self._pin_mode("template")
            executor.bind(self)
            return EMPTY_ROOT
        self._check_mode("template")
        executor.check_binding(self)
        export = self.export_resident_plan()
        self._pin_mode("template")
        executor.bind(self)
        if export is None:
            return self.root()
        executor.prepare(export)  # compile outside the watchdog

        if getattr(executor, "shards", 1) > 1 and not full_readback:
            # per-shard absorb (mesh steady state): each shard's digests
            # come home straight from that shard's store partition —
            # shard-local gathers + d2h of exactly this commit's lanes,
            # never a host materialization of the replicated dig matrix.
            # full_readback=True keeps the all-gather path reachable for
            # the parity oracle (tests A/B the two absorbs bit-exactly).
            def sync():
                executor.run(export)
                failpoint("resident/before_absorb")
                with phase_timer("resident/phase/wait"):
                    return executor.shard_digests(export)

            if timeout is None:
                parts = sync()
            else:
                parts = _run_with_watchdog(sync, timeout, "template commit")
            out = np.empty(32, np.uint8)
            with phase_timer("resident/phase/absorb"):
                for lanes_k, digs_k in parts:
                    if lanes_k.shape[0] == 0:
                        continue
                    self._lib.mpt_inc_res_absorb_lanes(
                        self._h,
                        np.ascontiguousarray(lanes_k, np.int32),
                        np.ascontiguousarray(digs_k).view(
                            np.uint8).reshape(-1),
                        lanes_k.shape[0])
                missed = int(self._lib.mpt_inc_res_absorb_finish(
                    self._h, out))
            if missed:
                # unabsorbed lanes stay dirty (the next plan re-hashes
                # them), so the cache is never stale — but a partial
                # absorb here means the shard split itself is wrong
                raise RuntimeError(
                    f"per-shard absorb missed {missed} lane(s): shard "
                    "partition does not cover the commit's store slots")
            if int(export["root_lane"]) < 0:
                return self.root()  # root not among this plan's lanes
            return out.tobytes()

        def sync():
            executor.run(export)
            failpoint("resident/before_absorb")
            with phase_timer("resident/phase/wait"):
                return np.asarray(executor.last_dig)

        if timeout is None:
            dig = sync()
        else:
            dig = _run_with_watchdog(sync, timeout, "template commit")
        if getattr(executor, "shards", 1) > 1:
            # the full replicated dig matrix just materialized host-side:
            # THE measured cross-shard digest gather (parity/test path)
            executor.note_dig_gather(export)
        # strip the zero-sentinel row: the native absorb expects global
        # lane order exactly like the planned path's digest matrix
        dig8 = np.ascontiguousarray(dig[1:]).view(np.uint8).reshape(-1)
        out = np.empty(32, np.uint8)
        with phase_timer("resident/phase/absorb"):
            self._lib.mpt_inc_res_absorb(self._h, dig8, out)
        if int(export["root_lane"]) < 0:
            return self.root()  # root not among this plan's lanes
        return out.tobytes()

    # ---- checkpoint / rollback (the chain adapter's verify->reject
    # enabler: core/blockchain.go:1424 reorg, plugin/evm/block.go:173) ----

    def checkpoint(self) -> None:
        """Open an undo scope: updates applied until discard_checkpoint()
        or rollback() journal their previous state."""
        self._lib.mpt_inc_checkpoint(self._h)

    def discard_checkpoint(self) -> None:
        """Keep the scope's changes (block accepted); nested scopes merge
        into their parent."""
        self._lib.mpt_inc_discard_checkpoint(self._h)

    def rollback(self) -> int:
        """Revert every update since the last checkpoint (block rejected
        / reorg); returns the number of ops reverted. Reverted paths are
        left dirty, so the next commit re-plans them."""
        return int(self._lib.mpt_inc_rollback(self._h))

    def flush_oldest_checkpoints(self, k: int) -> None:
        """Drop the OLDEST [k] scopes, keeping their changes and freeing
        their journal memory — the tip-buffer flush (finalized history
        deeper than the retained window stops being rewindable)."""
        if k > 0:
            self._lib.mpt_inc_flush_oldest(self._h, k)

    def dirty_stats(self):
        """(dirty hashed nodes, mini-plan bytes) of the CURRENT plan —
        call right after commit planning to size the transfer."""
        return (int(self._lib.mpt_inc_num_dirty(self._h)),
                int(self._lib.mpt_inc_flat_bytes(self._h)))

    # ---- state reads + persistence export (the chain adapter's read
    # seam and 4096-interval disk flush; reference trie/trie.go:87 Get,
    # core/state_manager.go:153 interval Commit) ----

    def get(self, key: bytes) -> Optional[bytes]:
        """Value lookup by 32-byte key; None when absent."""
        if len(key) != 32:
            raise ValueError("keys are 32 bytes (keccak-hashed)")
        k = np.frombuffer(key, np.uint8)
        out = np.empty(128, np.uint8)
        n = int(self._lib.mpt_inc_get(self._h, k, out, out.shape[0]))
        if n < 0:
            return None
        if n > out.shape[0]:
            out = np.empty(n, np.uint8)
            n = int(self._lib.mpt_inc_get(self._h, k, out, out.shape[0]))
        return out[:n].tobytes()

    def absorb_store(self, store) -> None:
        """Pull device-store digests (executor.store read back to host as
        uint32[S, 8]) into the native digest cache — the explicit sync
        point before export_nodes() on a resident-committed trie."""
        arr = np.ascontiguousarray(np.asarray(store)).view(np.uint8)
        n_slots = arr.size // 32
        self._lib.mpt_inc_absorb_store(self._h, arr.reshape(-1), n_slots)

    def absorb_store_parts(self, parts) -> None:
        """Sharded variant of absorb_store: absorb per-shard contiguous
        store partitions [(slot_lo, slot_hi, uint32[rows, 8]), ...] as
        read back shard-locally by executor.store_parts() — the whole
        device store reaches the host cache without ever reassembling
        the full replicated matrix host-side."""
        for lo, hi, part in parts:
            arr = np.ascontiguousarray(np.asarray(part)).view(np.uint8)
            self._lib.mpt_inc_absorb_store_range(
                self._h, arr.reshape(-1), int(lo), int(hi))

    def set_lean(self, on: bool) -> None:
        """Enable the storage-lean wire format: fresh class-1 rows whose
        RLP fits LEAN_ROW_WIDTH bytes ship as content-only records (the
        device re-derives keccak padding). Safe to flip between commits;
        it only changes how fresh rows travel, never what the arena or
        the host cache hold."""
        self._lib.mpt_inc_set_lean(self._h, 1 if on else 0)

    def export_nodes(self, delta: bool = False):
        """Export hashed nodes as (digests uint8[N, 32], rlp bytes,
        off uint64[N+1]) for the interval disk flush. The trie must be
        clean (just committed); resident tries need absorb_store first.

        delta=True exports only nodes re-hashed since the previous export
        (full or delta) — an O(changed) overlay that, together with what
        is already on disk, forms a complete hashdb image of the current
        root (reference trie/triedb/hashdb Commit walks its dirty forest
        the same way)."""
        sz = np.empty(1, np.int64)
        size_fn = (self._lib.mpt_inc_export_delta_size if delta
                   else self._lib.mpt_inc_export_size)
        n = int(size_fn(self._h, sz))
        if n < 0:
            raise RuntimeError("trie has uncommitted changes; commit first")
        digests = np.empty((n, 32), np.uint8)
        rlp_buf = np.empty(max(int(sz[0]), 1), np.uint8)
        off = np.empty(n + 1, np.uint64)
        export_fn = (self._lib.mpt_inc_export_delta_nodes if delta
                     else self._lib.mpt_inc_export_nodes)
        export_fn(self._h, digests.reshape(-1), rlp_buf, off)
        return digests, rlp_buf[:int(sz[0])].tobytes(), off

    def root(self) -> bytes:
        if self.num_nodes == 0:
            return EMPTY_ROOT
        if getattr(self, "_mode", None) == "resident":
            # resident commits never write the host digest cache; the
            # root lives on the device (executor.last_root)
            raise RuntimeError(
                "trie is in resident mode: read the root from the "
                "executor handle returned by commit_resident()")
        out = np.empty(32, np.uint8)
        self._lib.mpt_inc_root(self._h, out)
        return out.tobytes()
