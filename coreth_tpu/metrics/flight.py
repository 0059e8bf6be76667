"""Per-block flight recorder: a bounded ring of the last N block-insert
records, always on (the cost is a handful of clock reads and counter
snapshots per block — noise next to execution/commit).

Each record is a plain dict built by core/blockchain during insert:

    {"number": int, "hash": bytes, "txs": int, "gas_used": int,
     "phases": {"recover"|"verify"|"execute"|"validate"|"commit"|"write":
                seconds, ...},
     "resident": {phase: seconds, ...},      # resident/phase/* deltas
     "counters": {name: delta, ...},         # snap + plan-cache + keccak
     "parallel": {"mode": ..., ...},         # optimistic-executor verdict
     "host_mode": bool | None,               # device vs host hashing
     "trace_id": str | None,                 # insert-… id (tracectx)
     "build": {...} | None,                  # the local build, below
     "accepted": bool, "seq": int}

`resident` and `counters` carry every name of FLIGHT_TIMERS and
FLIGHT_COUNTERS, zeros included. A timer `resident/phase/<p>` appears as
`<p>`, `planned/phase/<p>` as `planned/<p>`.

`build` is what `vm.build_block` measured for a block this node built
(None for a block from a peer), with the same key set on every build,
host-mode and failed ones included:

    {"phases": {"miner_execute": s, "preview_commit": s, "preverify": s},
     "resident": {...}, "counters": {...}}   # deltas over the build

`miner_execute` is `miner.commit_new_work()`; `preview_commit` is the
resident preview commit inside it (the block's own state commit, where
its program compiles); `preverify` is the `writes=False` insert. The
build and the later insert cover separate work, so adding a block's
build and insert counts nothing twice. A failed build lands as a
`vm/build_failed` event carrying its section.

`parallel` starts present-but-empty and `host_mode`/`counters` are
stamped in the insert's finally block, so host-fallback and
failed-before-execute records carry the same key set as the happy path
(`counters["resident/h2d_bytes"]` is an explicit 0 on host-mode
commits — bench attribution must never average over a ragged set).

The `write` phase is stamped asynchronously by the overlapped insert
tail; records are shared dicts, so readers see it once the tail worker
lands. On verify/execute failure the in-flight record is attached to the
chain's `bad_blocks` ring instead, and `debug_blockFlightRecord` serves
the accepted view over RPC.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

DEFAULT_CAPACITY = 64

# the fields of a commit program's cache key whose change is counted on
# every plan-cache miss, as `<executor>/plan_cache/miss_field/<field>`
RESIDENT_KEY_FIELDS = (
    "classes", "store_cap", "arena_caps", "g_pad", "patch_bucket",
    "tile_bucket", "fresh", "lean_bucket")
PLANNED_KEY_FIELDS = (
    "n_segments", "seg_shapes", "seg_offsets", "flat_words", "aux")

# counter/timer families snapshotted around each insert and each local
# build, so the flight record carries per-block deltas rather than
# process-cumulative values
FLIGHT_COUNTERS = (
    "state/snap/hits", "state/snap/misses", "state/snap/generating",
    "resident/plan_cache/hits", "resident/plan_cache/misses",
    "resident/compiles",
    "resident/h2d_bytes", "resident/gather_bytes",
    "resident/gather_bytes_modeled", "resident/absorb_d2h_bytes",
    "resident/lean_wire_bytes",
    "resident/keccak/lanes", "resident/keccak/rate_blocks",
    "resident/keccak/pad_lanes",
    "planned/plan_cache/hits", "planned/plan_cache/misses",
    "planned/compiles", "planned/h2d_bytes",
    "planned/keccak/lanes", "planned/keccak/rate_blocks",
    "trie/keccak/batches", "trie/keccak/batch_msgs",
) + tuple("resident/plan_cache/miss_field/" + f
          for f in RESIDENT_KEY_FIELDS) + tuple(
    "planned/plan_cache/miss_field/" + f for f in PLANNED_KEY_FIELDS)
FLIGHT_TIMERS = (
    "resident/phase/commit", "resident/phase/preview",
    "resident/phase/plan", "resident/phase/export",
    "resident/phase/compile", "resident/phase/compile_trace",
    "resident/phase/compile_lower", "resident/phase/compile_backend",
    "resident/phase/scatter", "resident/phase/patch", "resident/phase/store",
    "resident/phase/wait", "resident/phase/host_hash",
    "planned/phase/compile_trace", "planned/phase/compile_lower",
    "planned/phase/compile_backend",
)
BUILD_PHASES = ("miner_execute", "preview_commit", "preverify")


def timer_key(name: str) -> str:
    """A flight timer's key in a record's `resident` dict."""
    if name.startswith("resident/phase/"):
        return name[len("resident/phase/"):]
    return name.replace("/phase/", "/", 1)


def snapshot(registry) -> Tuple[dict, dict]:
    """The flight families' cumulative values, to subtract later."""
    return ({n: registry.counter(n).count() for n in FLIGHT_COUNTERS},
            {n: registry.timer(n).total() for n in FLIGHT_TIMERS})


def deltas(registry, snap: Tuple[dict, dict]) -> Tuple[dict, dict]:
    """(counters, resident) deltas since `snap`, every name present."""
    counters0, timers0 = snap
    counters = {n: registry.counter(n).count() - counters0[n]
                for n in FLIGHT_COUNTERS}
    resident = {timer_key(n): registry.timer(n).total() - timers0[n]
                for n in FLIGHT_TIMERS}
    return counters, resident


class BuildRecorder:
    """Measures one local block build for its flight record's `build`
    section: a clock per phase and the flight families' deltas over the
    whole build."""

    def __init__(self, registry):
        self._registry = registry
        self._snap = snapshot(registry)
        self.phases = dict.fromkeys(BUILD_PHASES, 0.0)

    @contextlib.contextmanager
    def phase(self, name: str, inner: Optional[Tuple[str, str]] = None):
        """Time one phase. inner=(phase, timer): the timer's delta over
        this phase is reported as that phase too (a part of this one)."""
        timer = self._registry.timer(inner[1]) if inner else None
        i0 = timer.total() if timer is not None else 0.0
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.phases[name] += time.monotonic() - t0
            if timer is not None:
                self.phases[inner[0]] += timer.total() - i0

    def section(self) -> Dict[str, dict]:
        counters, resident = deltas(self._registry, self._snap)
        return {"phases": dict(self.phases), "resident": resident,
                "counters": counters}


class FlightRecorder:
    """Lock-guarded bounded ring of per-block records. One instance per
    BlockChain (NOT process-global) so tests and multi-VM processes
    don't bleed into each other."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._ring: deque = deque(maxlen=max(1, int(capacity)))
        self._events: deque = deque(maxlen=max(1, int(capacity)))
        # build sections of blocks built here, by hash, until the
        # block's insert record lands
        self._builds: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        self._seq = 0

    def record(self, rec: Dict[str, object]) -> Dict[str, object]:
        """Append one block record (mutated in place later for the async
        `write` phase and the accept mark), with the block's build
        section if this node built it. Returns the same dict."""
        with self._lock:
            self._seq += 1
            rec["seq"] = self._seq
            rec.setdefault("accepted", False)
            rec["build"] = self._builds.pop(rec.get("hash"), None)
            self._ring.append(rec)
        return rec

    def note_build(self, block_hash: bytes, section: Dict[str, dict]) -> None:
        """Hold a locally built block's build section for its record."""
        with self._lock:
            self._builds[block_hash] = section
            self._builds.move_to_end(block_hash)
            while len(self._builds) > (self._ring.maxlen or 1):
                self._builds.popitem(last=False)

    def mark_accepted(self, block_hash: bytes) -> None:
        """Flip `accepted` on the record for this hash (newest match)."""
        with self._lock:
            for rec in reversed(self._ring):
                if rec.get("hash") == block_hash:
                    rec["accepted"] = True
                    return

    def last(self, n: Optional[int] = None,
             accepted_only: bool = False) -> List[Dict[str, object]]:
        """Newest-last list of the most recent records. The dicts are the
        live ones (so late `write` stamps show up); callers that marshal
        should copy."""
        with self._lock:
            recs = list(self._ring)
        if accepted_only:
            recs = [r for r in recs if r.get("accepted")]
        if n is not None:
            recs = recs[-max(0, int(n)):]
        return recs

    def note_event(self, kind: str, **fields) -> Dict[str, object]:
        """Append one out-of-band lifecycle event (device demotion/
        re-promotion, mirror quarantine, torn-tail repair, ...) to a ring
        parallel to the block records, sharing the same seq counter so
        events interleave with blocks in wall order."""
        ev = {"event": kind, "ts": time.time()}
        ev.update(fields)
        with self._lock:
            self._seq += 1
            ev["seq"] = self._seq
            self._events.append(ev)
        return ev

    def events(self, n: Optional[int] = None,
               kind: Optional[str] = None) -> List[Dict[str, object]]:
        """Newest-last list of recent lifecycle events."""
        with self._lock:
            evs = list(self._events)
        if kind is not None:
            evs = [e for e in evs if e.get("event") == kind]
        if n is not None:
            evs = evs[-max(0, int(n)):]
        return evs

    def find(self, block_hash: bytes) -> Optional[Dict[str, object]]:
        with self._lock:
            for rec in reversed(self._ring):
                if rec.get("hash") == block_hash:
                    return rec
        return None

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def capacity(self) -> int:
        with self._lock:
            return self._ring.maxlen or 0

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


def marshal_record(rec: Dict[str, object]) -> Dict[str, object]:
    """JSON-safe copy of one record (bytes hash → 0x-hex) — shared by
    debug_blockFlightRecord and debug_getBadBlocks."""
    out = dict(rec)
    h = out.get("hash")
    if isinstance(h, (bytes, bytearray)):
        out["hash"] = "0x" + bytes(h).hex()
    for k in ("phases", "counters", "resident", "parallel"):
        if isinstance(out.get(k), dict):
            out[k] = dict(out[k])
    if isinstance(out.get("build"), dict):
        out["build"] = {k: dict(v) for k, v in out["build"].items()}
    return out
