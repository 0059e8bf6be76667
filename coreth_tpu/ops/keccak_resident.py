"""Device-resident incremental trie commits: deferred absorb + template
residency (PERF.md roadmap items #1 and #2, VERDICT r3 next-round #1+#2).

The planned executor (ops/keccak_planned.py) re-ships every dirty node's
full row each commit (~800 B/dirty node at 50k churn) and reads the whole
digest matrix back so the host cache can serve the next plan. On a slow
host link that transfer IS the bottleneck; the CPU wins below ~150 MB/s.

This executor keeps both halves of that traffic on the device across
commits:

  - a digest STORE uint32[S, 8] holds every node's digest at a persistent
    slot; parents reference children by slot, so digests never return to
    the host (only the 32-byte root, on demand)
  - per-block-class row ARENAS uint32[R, W] hold each node's
    keccak-padded RLP row (blocks*34 words; the fused path pads W to
    whole 128-word lane tiles) at a persistent row index; a commit uploads only
    rows whose TEMPLATE changed (fresh nodes, structural edits) plus the
    patch tables — steady-state h2d is ~tens of bytes per dirty node
  - holes are DELTA-patched: contribution strips of (new - old) child
    digests scatter-add into the arena in wrapping u32 arithmetic. Every
    hole word is a sum of byte-disjoint contributions, so the modular
    update is exact; fresh rows carry zero holes and old = the zero
    sentinel. The old digest is store[slot] *before* this commit's store
    scatter, which runs last.

Because the host plan needs no digest values, planning commit k+1 can
overlap device execution of commit k (JAX async dispatch): steady-state
throughput is nodes/max(plan, transfer) instead of nodes/(plan+transfer).

Index conventions (mirrored by native/mpt_inc.cpp build_plan_res):
  store slot 0 = zero sentinel, slot 1 = pad-lane scratch, real slots >= 2;
  arena row 0 per class = scratch; dig row 0 = zero sentinel (gather index
  0 means "no contribution" for both dig and store).

Reference seam: the warm-trie dirty-walk of /root/reference/trie/trie.go
:573-626 + the hashdb dirty forest (trie/triedb/hashdb/database.go:94-155)
whose "absorb" step here lives permanently in device memory.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .keccak_staged import _segment_keccak

MAX_SEGMENTS = 64  # the non-fused path's segment meta table

# The fused program walks a commit's segments in planner order as tiles of
# up to TILE_LANES lanes of one segment each: a tile gathers its rows,
# patches their holes in place PATCH_CHUNK patches at a time, and hashes
# them with one keccak call. A tile row of the upload is (class position,
# gstart, lane_off, lanes, patch_start, npatch) over the commit's patches
# in tile order. The arenas are patched once, after the loop. 512 lanes:
# on a v5e, the block commits of a 50,000-account trie (716 dirty leaves)
# ran 5.1 ms a block at 512-lane tiles and 6.0 ms at 1,024.
TILE_LANES = 512
PATCH_CHUNK = 256
TILE_FIELDS = 6
# a held bucket falls back only when the need it serves drops below
# 1/SHRINK of it (a genesis-sized commit followed by block-sized ones)
SHRINK = 8
# the per-patch arrays of the fused upload, in aux order
PATCH_FIELDS = ("plane", "pcol", "prow", "src", "oldidx", "pcls")


def _pow2_bucket(n: int, floor: int = 16) -> int:
    """Round n up to a power of two (>= floor). Load-bearing for jit
    cache-key stability: every padded shape must come from this one
    policy so the set of compiled programs stays small and consistent."""
    b = floor
    while b < n:
        b <<= 1
    return b


def sticky_buckets(held: dict, needs: dict) -> dict:
    """The buckets a commit with these `needs` (field -> entries) uses,
    given the ones the executor `held`. A held bucket stays while its
    need fits; a need that outgrows it gets a new power of two with a
    quarter of headroom; a field never needed stays 0 (its part of the
    program is left out). A commit whose need of some field is SHRINK
    times below the held bucket is of another scale (a block after a
    genesis commit): every bucket is then sized anew from it."""
    if any(_pow2_bucket(n) * SHRINK <= held.get(f, 0)
           for f, n in needs.items()):
        held = {}
    out = {}
    for f, n in needs.items():
        h = held.get(f, 0)
        out[f] = h if n <= h else _pow2_bucket(n + n // 4)
    return out


def plan_tiles(export, cls_pos: dict):
    """The fused program's tile table and patch arrays for one commit.

    Returns (tiles int32[n_tiles, TILE_FIELDS], patches {field: int32[n]}).
    Tiles cut each segment into runs of at most TILE_LANES lanes, in
    planner order (leaves first, so a patch reads digests that earlier
    tiles wrote). The export's pad patches (src 0) are dropped; each real
    patch gets the lane it lands in within its tile (`plane`), its byte
    offset within the row (`pcol`), its arena row (`prow`) and its arena's
    position (`pcls`); `src` and `oldidx` are the export's."""
    rowidx = export["rowidx"]
    tiles, parts = [], []
    n = 0
    for blocks, lanes, gstart, npatch, patch_off, lane_off in \
            np.asarray(export["specs"], np.int64):
        sl = slice(patch_off, patch_off + npatch)
        src = export["src"][sl]
        real = src != 0
        off = export["off"][sl][real]
        width = blocks * 136
        prow = off // width
        # rows are unique among a segment's real lanes (its pad lanes
        # hold the scratch row 0, which no patch targets)
        rows = rowidx[lane_off:lane_off + lanes]
        order = np.argsort(rows, kind="stable")
        lane = order[np.searchsorted(rows[order], prow)]
        # a segment's patches come in lane order: each tile's are a run
        firsts = np.arange(0, lanes, TILE_LANES)
        bounds = np.searchsorted(lane, np.append(firsts, lanes))
        for k, first in enumerate(firsts):
            tiles.append((cls_pos[int(blocks)], gstart + first,
                          lane_off + first, min(TILE_LANES, lanes - first),
                          n + bounds[k], bounds[k + 1] - bounds[k]))
        parts.append((lane % TILE_LANES, off - prow * width, prow,
                      src[real], export["oldidx"][sl][real],
                      np.full(off.shape[0], cls_pos[int(blocks)])))
        n += off.shape[0]
    patches = {f: np.concatenate([p[i] for p in parts]).astype(np.int32)
               if parts else np.zeros(0, np.int32)
               for i, f in enumerate(PATCH_FIELDS)}
    return np.asarray(tiles, np.int32).reshape(-1, TILE_FIELDS), patches


def _strips(d: jax.Array, shift: jax.Array) -> jax.Array:
    """uint32[P, 8] digests + byte shifts -> uint32[P, 9] contribution
    strips (digest bytes relocated to byte offset shift within the 9-word
    destination window; all other bytes zero)."""
    p = d.shape[0]
    dpad = jnp.concatenate(
        [jnp.zeros((p, 1), jnp.uint32), d, jnp.zeros((p, 1), jnp.uint32)],
        axis=1,
    )  # [P, 10]; dpad[:, j] == D[j-1]
    lsh = (8 * shift)[:, None].astype(jnp.uint32)
    rsh = (32 - 8 * shift)[:, None]
    lo = dpad[:, :9] >> jnp.minimum(rsh, 31).astype(jnp.uint32)
    lo = jnp.where(shift[:, None] == 0, jnp.uint32(0), lo)
    hi = dpad[:, 1:] << lsh
    return lo | hi


# sharding: unsharded fallback only (non-fused run()); mesh commits go
# through the fused program, whose in/out shardings are pinned explicitly
@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(arena, rows, idx):
    """Upload fresh rows into their persistent arena slots."""
    return arena.at[idx].set(rows, mode="drop")


# sharding: unsharded fallback only (non-fused run()); mesh commits go
# through the fused program, whose in/out shardings are pinned explicitly
@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_store(store, dig, lane_slot):
    """Persist this commit's digests at their slots (pads target the
    scratch slot 1; slot 0 stays the zero sentinel forever)."""
    return store.at[lane_slot].set(dig[1:], mode="drop")


LEAN_WORDS = 18  # 72-byte lean record = 18 uint32 words (native kLeanWidth)


def _make_res_step(seg_impl, donate: bool = True):
    """Jitted per-segment step: delta-patch the arena, gather the
    segment's rows, hash, write digests into dig. Static args are shapes
    only; per-segment offsets travel in the meta row selected by seg_i."""

    # sharding: unsharded fallback only (non-fused run()); mesh commits
    # go through the fused program's explicitly pinned in/out shardings
    @functools.partial(
        jax.jit,
        static_argnames=("lanes", "blocks", "npatch"),
        donate_argnums=(0, 2) if donate else (),
    )
    def step(arena, store, dig, off_all, src_all, oldidx_all,
             rowidx_all, meta, seg_i,
             *, lanes: int, blocks: int, npatch: int):
        row = jax.lax.dynamic_slice(meta, (seg_i, 0), (1, 3))[0]
        patch_off, lane_off, gstart = row[0], row[1], row[2]
        flat = arena.reshape(-1)
        if npatch:
            off = jax.lax.dynamic_slice(off_all, (patch_off,), (npatch,))
            src = jax.lax.dynamic_slice(src_all, (patch_off,), (npatch,))
            oldidx = jax.lax.dynamic_slice(oldidx_all, (patch_off,), (npatch,))
            dstw = off >> 2            # word index + byte shift derived
            shift = off & 3            # on device (12 B/patch h2d)
            # signed source: +k = this commit's dig row k, -k = store
            # slot k, 0 = none (both gathers hit their pinned-zero row 0)
            new = jnp.where(src[:, None] > 0,
                            dig[jnp.maximum(src, 0)],
                            store[jnp.maximum(-src, 0)])  # [P, 8]
            old = store[oldidx]                           # [P, 8]
            delta = _strips(new, shift) - _strips(old, shift)
            idx = dstw[:, None] + jnp.arange(9, dtype=jnp.int32)[None, :]
            flat = flat.at[idx.reshape(-1)].add(delta.reshape(-1),
                                                mode="drop")
        arena = flat.reshape(arena.shape)
        ridx = jax.lax.dynamic_slice(rowidx_all, (lane_off,), (lanes,))
        words = arena[ridx].reshape(lanes, blocks, 34)
        out = seg_impl(words)                            # [lanes, 8]
        dig = jax.lax.dynamic_update_slice(
            dig, out, (gstart + 1, jnp.int32(0)))
        return arena, dig

    return step


def key_fields(key) -> dict:
    """A fused-program key split into the fields whose change a
    plan-cache miss counts (metrics.flight.RESIDENT_KEY_FIELDS): the
    arena classes and the capacities of the store and arenas, and the
    buckets of the lanes (`g_pad`), patches, tile rows, fresh rows per
    class and lean records."""
    (classes, store_cap, arena_caps, g_pad, patch_bucket, tile_bucket,
     fresh, lean_bucket) = key
    return {
        "classes": classes, "store_cap": store_cap,
        "arena_caps": arena_caps, "g_pad": g_pad,
        "patch_bucket": patch_bucket, "tile_bucket": tile_bucket,
        "fresh": fresh, "lean_bucket": lean_bucket,
    }


def count_miss_fields(executor: str, prev: dict, new: dict) -> None:
    """On a plan-cache miss: one `<executor>/plan_cache/miss_field/<f>`
    increment per key field that differs from the previous commit's."""
    from ..metrics import default_registry

    for name, value in new.items():
        if prev[name] != value:
            default_registry.counter(
                executor + "/plan_cache/miss_field/" + name).inc(1)


def count_keccak_work(executor: str, shapes) -> None:
    """The keccak work one commit hands its kernels, from its segments'
    (blocks, lanes): `<executor>/keccak/lanes` (Σ lanes) and
    `<executor>/keccak/rate_blocks` (Σ blocks·lanes, keccak-f[1600]
    rate blocks absorbed)."""
    from ..metrics import default_registry

    default_registry.counter(executor + "/keccak/lanes").inc(
        sum(lanes for _, lanes in shapes))
    default_registry.counter(executor + "/keccak/rate_blocks").inc(
        sum(blocks * lanes for blocks, lanes in shapes))


def compile_stages(executor: str, jitted, *args):
    """Compile a jitted commit program ahead of time through JAX's
    stages, each under its own phase timer and span
    (`<executor>/phase/compile_trace`, `compile_lower`,
    `compile_backend`), and count it in `<executor>/compiles`."""
    from ..metrics import default_registry, phase_timer

    with phase_timer(executor + "/phase/compile_trace"):
        traced = jitted.trace(*args)
    with phase_timer(executor + "/phase/compile_lower"):
        lowered = traced.lower()
    with phase_timer(executor + "/phase/compile_backend"):
        compiled = lowered.compile()
    default_registry.counter(executor + "/compiles").inc(1)
    return compiled


class ResidentExecutor:
    """Holds one trie's device-resident state (store + arenas) and runs
    resident commits exported by native/mpt_inc.cpp's resident planner.

    One executor per trie — the store/arena contents ARE that trie's
    digest cache. seg_impl: optional keccak kernel override (the Pallas
    kernel plugs in, as in ops/keccak_planned.py)."""

    def __init__(self, seg_impl=None, sharding=None, fused=None):
        impl = seg_impl if seg_impl is not None else _segment_keccak
        self._impl = impl
        self._step = _make_res_step(impl)
        self.store: Optional[jax.Array] = None
        self.arenas: dict[int, jax.Array] = {}
        self.last_root: Optional[jax.Array] = None  # uint32[8], lazy
        self._owner = None  # weakref to the one trie this store serves
        # multichip: a NamedSharding over the ROW axis (store slots /
        # arena rows) distributes the resident state across a Mesh —
        # capacities round up to the device count and GSPMD partitions
        # the step's gathers/scatters (parallel.resident_executor_over_
        # mesh builds this; dig stays replicated, it is per-commit-sized)
        self.sharding = sharding
        self._row_mult = sharding.mesh.size if sharding is not None else 1
        # explicit upload placement: per-commit payloads (rows/aux/patch
        # tables) are replicated over the mesh while the resident state
        # stays row-sharded — pinning it here (instead of letting
        # device_put infer) is what keeps chained commits reshard-free
        # across processes (SA012 sharding discipline)
        if sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            self._repl = NamedSharding(sharding.mesh, PartitionSpec())
        else:
            self._repl = None
        # fused = ONE dispatch + TWO uploads per commit (VERDICT r4 #3);
        # programs are keyed on capacities and sticky buckets only, so
        # one program serves every commit of a steady chain
        if fused is None:
            import os

            fused = os.environ.get("CORETH_TPU_RESIDENT_FUSE", "1") != "0"
        self.fused = fused
        # plan cache: compiled whole-commit programs AND their host
        # staging buffers, keyed by capacities and buckets (_signature).
        # Warm commits (steady-state chain: dirty sets inside the held
        # buckets) skip jit tracing and refill preallocated aux/rows
        # buffers in place instead of re-concatenating.
        # Staging is a RING per signature: with cross-commit pipelining
        # (pipeline_depth > 0) up to depth+1 commits' buffers may be
        # in flight at once, so each ring entry remembers the lazy root
        # of the commit that consumed it and is only rewritten once THAT
        # commit has settled — never the whole pipeline
        self._fused_cache: dict = {}
        self._staging: dict = {}
        self._last_key = None  # the previous commit's signature
        # the held buckets (sticky_buckets): "g_pad", "patch", "tile",
        # "lean", ("fresh", cls)
        self._buckets: dict = {}
        self._prepared = None  # (export, its prepare() result) until run
        # bounded in-flight window for deferred-absorb pipelining: 0 =
        # every dispatch settles the previous commit before staging reuse
        # (the pre-pipelining behaviour); k = up to k commits may still
        # be executing on device while the next one is planned/dispatched
        self.pipeline_depth = 0
        # diagnostics for PERF.md / bench: bytes actually shipped
        self.h2d_bytes = 0
        self.last_transfers = 0
        self.last_dispatches = 0
        self.last_cache_hit = False
        # mesh diagnostics, explicitly zeroed when unsharded so flight-
        # record keys stay un-ragged. Provenance split (PR 18):
        # last_gather_bytes is MEASURED — bytes of replicated digest
        # matrix actually materialized host-side (0 on the per-shard
        # absorb path); last_gather_bytes_modeled is the (n-1)/n
        # all-gather MODEL recorded every sharded commit for the A/B;
        # last_absorb_d2h_bytes counts the shard-local digest readbacks
        # that replace the gather. The trajectory sentinel only ever
        # gates on the measured counters.
        self.last_gather_bytes = 0
        self.last_gather_bytes_modeled = 0
        self.last_absorb_d2h_bytes = 0
        self.last_shard_lanes: list = []
        # lean wire diagnostics: content-only class-1 records in the
        # last commit and their wire bytes (72 content + 4 idx + 4 len)
        self.last_lean_rows = 0
        self.last_lean_wire_bytes = 0
        # full digest matrix of the last run (lazy, includes the zero-
        # sentinel row 0) — template residency absorbs it host-side
        self.last_dig: Optional[jax.Array] = None

    @property
    def shards(self) -> int:
        """Mesh shards holding the resident state (1 = unsharded)."""
        return self._row_mult

    @property
    def spans_processes(self) -> bool:
        """True when the mesh's devices belong to more than one jax
        process — the demotion ladder's local single-device rung is
        unavailable then (a unilateral local rebuild would desync the
        SPMD program on every other process)."""
        if self.sharding is None:
            return False
        return len({d.process_index
                    for d in self.sharding.mesh.devices.flat}) > 1

    def _pin(self, arr: jax.Array) -> jax.Array:
        if self.sharding is None:
            return arr
        return jax.device_put(arr, self.sharding)

    def _put(self, arr):
        """Host->device upload with an EXPLICIT placement: replicated
        over the mesh when sharded (uploads are per-commit-sized; the
        resident state itself stays row-sharded), default placement
        when unsharded (None)."""
        return jax.device_put(arr, self._repl)

    def _note_collectives(self, export) -> None:
        """Per-commit collective accounting for the flight record,
        split by provenance (PR 18). resident/gather_bytes_modeled
        records the (shards-1)/shards digest all-gather MODEL every
        sharded commit — what materializing the replicated dig matrix
        host-side would move. The MEASURED twin resident/gather_bytes
        is reset to 0 here and only incremented by note_dig_gather when
        a full dig readback actually happens; steady-state per-shard-
        absorb commits therefore record 0 measured gather bytes.
        lanes-per-shard comes from each lane's store slot, whose
        contiguous row blocks are what NamedSharding partitions.
        Unsharded commits record explicit zeros so flight-record keys
        stay un-ragged across configs."""
        from ..metrics import default_registry

        total_lanes = int(export["total_lanes"])
        n = self._row_mult
        self.last_gather_bytes = 0
        self.last_absorb_d2h_bytes = 0
        if n > 1:
            self.last_gather_bytes_modeled = total_lanes * 32 * (n - 1) // n
            per = max(1, self.store.shape[0] // n)
            owner = np.minimum(export["lane_slot"] // per, n - 1)
            self.last_shard_lanes = np.bincount(owner, minlength=n).tolist()
        else:
            self.last_gather_bytes_modeled = 0
            self.last_shard_lanes = [total_lanes]
        default_registry.counter("resident/gather_bytes_modeled").inc(
            self.last_gather_bytes_modeled)

    def note_dig_gather(self, export) -> None:
        """A full replicated dig matrix materialized host-side (the
        template full-readback path): count the MEASURED cross-shard
        gather — (shards-1)/shards of every lane's 32-byte digest had
        to cross shards to assemble the replica being read."""
        from ..metrics import default_registry

        n = self._row_mult
        if n <= 1:
            return
        self.last_gather_bytes = int(export["total_lanes"]) * 32 \
            * (n - 1) // n
        default_registry.counter("resident/gather_bytes").inc(
            self.last_gather_bytes)

    def shard_digests(self, export):
        """Per-shard digest readback for the mesh absorb: for each
        store shard, gather this commit's digest rows ON that shard
        (the store scatter already placed them — lane_slot partitions
        by owner) and read back exactly those lanes' digests. Returns
        [(global_lane_idx int32[k], digests uint32[k, 8]), ...] for
        IncrementalTrie's mpt_inc_res_absorb_lanes. No replicated-dig
        materialization, no cross-shard traffic; the d2h total lands in
        resident/absorb_d2h_bytes (measured)."""
        from ..metrics import default_registry

        lane_slot = np.asarray(export["lane_slot"])
        lanes_all = np.arange(lane_slot.shape[0], dtype=np.int32)
        real = lane_slot >= 2  # pad lanes target the scratch slot 1
        n = self._row_mult
        per = max(1, self.store.shape[0] // n)
        owner = np.minimum(lane_slot // per, n - 1)
        shards = sorted(self.store.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        parts = []
        d2h = 0
        for k, sh in enumerate(shards):
            sel = real & (owner == k)
            lanes_k = lanes_all[sel]
            if lanes_k.size == 0:
                parts.append((lanes_k, np.zeros((0, 8), np.uint32)))
                continue
            local = (lane_slot[sel] - k * per).astype(np.int32)
            digs = np.asarray(sh.data[local])  # shard-local gather+d2h
            parts.append((lanes_k, digs))
            d2h += lanes_k.size * 32
        self.last_absorb_d2h_bytes = d2h
        default_registry.counter("resident/absorb_d2h_bytes").inc(d2h)
        return parts

    def store_parts(self):
        """Shard-local store readbacks for the interval absorb:
        [(slot_lo, slot_hi, uint32[rows, 8]), ...] covering the whole
        store, one entry per shard (one entry total when unsharded).
        Pairs with IncrementalTrie.absorb_store_parts — the sharded
        replacement for reading the full store back in one host-side
        gather. Counted under resident/absorb_d2h_bytes (measured)."""
        from ..metrics import default_registry

        if self.store is None:
            return []
        if self._row_mult == 1:
            part = np.asarray(self.store)
            default_registry.counter("resident/absorb_d2h_bytes").inc(
                part.nbytes)
            return [(0, int(self.store.shape[0]), part)]
        parts = []
        d2h = 0
        for sh in sorted(self.store.addressable_shards,
                         key=lambda s: s.index[0].start or 0):
            data = np.asarray(sh.data)
            lo = int(sh.index[0].start or 0)
            parts.append((lo, lo + data.shape[0], data))
            d2h += data.nbytes
        default_registry.counter("resident/absorb_d2h_bytes").inc(d2h)
        return parts

    # ---- ownership: slot/row numbering is per-trie, so a second trie
    # sharing this executor would silently corrupt both stores ----

    def check_binding(self, tree):
        if self._owner is not None and self._owner() is not tree:
            raise RuntimeError(
                "executor already serves another trie (its store/arena "
                "slots are that trie's digest cache); create one "
                "ResidentExecutor per trie")

    def bind(self, tree):
        self.check_binding(tree)
        if self._owner is None:
            import weakref

            self._owner = weakref.ref(tree)

    # ---- capacity management (growth recompiles; keep it geometric) ----

    def _cap(self, n: int) -> int:
        m = self._row_mult
        return -(-n // m) * m

    def _ensure_store(self, slots_needed: int):
        if self.store is None:
            cap = self._cap(max(2 * slots_needed, 4096))
            self.store = self._pin(jnp.zeros((cap, 8), jnp.uint32))
        elif self.store.shape[0] < slots_needed:
            cap = self._cap(max(2 * slots_needed, 2 * self.store.shape[0]))
            pad = jnp.zeros((cap - self.store.shape[0], 8), jnp.uint32)
            self.store = self._pin(
                jnp.concatenate([self.store, pad], axis=0))

    def _arena_width(self, cls: int) -> int:
        """Words per arena row: the row's own cls*34, rounded up to whole
        128-word lane tiles on the fused path so the TPU keeps rows
        contiguous (row gathers and hole patches then need no relayout
        of the arena)."""
        width = cls * 34
        return -(-width // 128) * 128 if self.fused else width

    def _ensure_arena(self, cls: int, rows_needed: int):
        width = self._arena_width(cls)
        a = self.arenas.get(cls)
        if a is None:
            cap = self._cap(max(2 * rows_needed, 1024))
            self.arenas[cls] = self._pin(jnp.zeros((cap, width), jnp.uint32))
        elif a.shape[0] < rows_needed:
            cap = self._cap(max(2 * rows_needed, 2 * a.shape[0]))
            pad = jnp.zeros((cap - a.shape[0], width), jnp.uint32)
            self.arenas[cls] = self._pin(jnp.concatenate([a, pad], axis=0))

    # ---- fused whole-commit program (one dispatch per commit) ----

    def _fused_program(self, key):
        """Build (or fetch) the compiled whole-commit program for a key.
        The key holds capacities and buckets only; every count and
        offset of the commit travels in the aux upload, so the program
        needs (store, arenas..., rows_packed, aux) and runs fresh-row
        scatters, the tile loop (delta patches + hash) and the final
        store scatter in ONE dispatch. A miss compiles here, ahead of
        time, so the dispatch itself never waits on the compiler; it
        counts each key field that differs from the previous commit's."""
        from ..metrics import default_registry

        prev, self._last_key = self._last_key, key
        fn = self._fused_cache.get(key)
        if fn is not None:
            default_registry.counter("resident/plan_cache/hits").inc(1)
            self.last_cache_hit = True
            return fn
        default_registry.counter("resident/plan_cache/misses").inc(1)
        self.last_cache_hit = False
        if prev is not None:
            count_miss_fields("resident", key_fields(prev), key_fields(key))
        if len(self._fused_cache) >= 256:
            # bound compiled-program retention (as the planned
            # executor's program cache); dict preserves insertion order,
            # so this evicts the oldest key (and its staging)
            oldest = next(iter(self._fused_cache))
            self._fused_cache.pop(oldest)
            self._staging.pop(oldest, None)
        rows, aux = self._upload_shapes(key)
        compiled = compile_stages(
            "resident", self._fused_jit(key), self.store,
            *(self.arenas[c] for c in key[0]),
            jax.ShapeDtypeStruct(rows, jnp.uint32),
            jax.ShapeDtypeStruct(aux, jnp.int32))
        self._fused_cache[key] = compiled
        return compiled

    @staticmethod
    def _aux_layout(key):
        """Lengths of the aux upload's regions, in order: n_tiles, the
        tile table, the PATCH_FIELDS arrays (each with a chunk of slack,
        so a chunk read never clamps), rowidx (a tile of slack),
        lane_slot, the fresh-row indices per class, lean indices, lean
        lengths."""
        (_classes, _store_cap, _arena_caps, g_pad, patch_b, tile_b,
         fresh, lean_b) = key
        patch_len = patch_b + PATCH_CHUNK
        return ((1, TILE_FIELDS * tile_b)
                + (patch_len,) * len(PATCH_FIELDS)
                + (g_pad + TILE_LANES, g_pad) + tuple(fresh)
                + (lean_b, lean_b))

    @classmethod
    def _upload_shapes(cls, key):
        """(rows_packed shape, aux shape) of a key's two uploads."""
        classes, fresh, lean_b = key[0], key[6], key[7]
        n_rows = (sum(b * c * 34 for c, b in zip(classes, fresh))
                  + lean_b * LEAN_WORDS)
        return (n_rows,), (sum(cls._aux_layout(key)),)

    def _fused_jit(self, key):
        """The jitted (not yet compiled) whole-commit program of a key:
        (store, *arenas, rows_packed, aux) -> (store, *arenas, dig)."""
        (classes, _store_cap, _arena_caps, g_pad, _patch_b, tile_b,
         fresh, lean_b) = key
        impl = self._impl
        narena = len(classes)
        widths = [self._arena_width(c) for c in classes]
        lanes_t = jnp.arange(TILE_LANES, dtype=jnp.int32)
        chunk_t = jnp.arange(PATCH_CHUNK, dtype=jnp.int32)
        nine = jnp.arange(9, dtype=jnp.int32)

        jit_kwargs = dict(donate_argnums=tuple(range(1 + narena)))
        if self.sharding is not None:
            # pjit discipline for chained commits: pin matching in/out
            # axis_resources so the store and arenas stay row-sharded
            # edge to edge across every commit — nothing reshards
            # between dispatches — while the per-commit uploads and the
            # dig matrix stay replicated (patches may read any lane).
            # The only cross-shard traffic left is the digest gather.
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self.sharding.mesh, PartitionSpec())
            res = (self.sharding,) * (1 + narena)
            jit_kwargs.update(in_shardings=res + (repl, repl),
                              out_shardings=res + (repl,))

        def full_rows(rows, width):
            return jnp.pad(rows, ((0, 0), (0, width - rows.shape[1])))

        def deltas(pcol, new, old):
            """Contribution strips of (new - old) at each patch's word."""
            return (pcol >> 2, _strips(new, pcol & 3) - _strips(old, pcol & 3))

        def tile_hasher(ai, blocks):
            # one class's branch of the tile loop's switch: gather the
            # tile's rows, patch their holes in the gathered copy (the
            # arena itself is patched after the loop) and hash them
            def run(arenas, ridx, dig, pt, snew, old, pstart, npatch):
                words = arenas[ai][ridx][:, :blocks * 34]

                def chunk(c, words):
                    start = pstart + c * PATCH_CHUNK

                    def take(x):
                        return jax.lax.dynamic_slice_in_dim(
                            x, start, PATCH_CHUNK)

                    src = take(pt["src"])
                    new = jnp.where(src[:, None] > 0,
                                    dig[jnp.maximum(src, 0)], take(snew))
                    word, delta = deltas(take(pt["pcol"]), new, take(old))
                    live = c * PATCH_CHUNK + chunk_t < npatch
                    lane = jnp.where(live, take(pt["plane"]), TILE_LANES)
                    return words.at[lane[:, None],
                                    word[:, None] + nine[None, :]].add(
                        delta, mode="drop")

                n_chunks = (npatch + PATCH_CHUNK - 1) // PATCH_CHUNK
                words = jax.lax.fori_loop(0, n_chunks, chunk, words)
                return impl(words.reshape(TILE_LANES, blocks, 34))
            return run

        hashers = [tile_hasher(ai, c) for ai, c in enumerate(classes)]

        @functools.partial(jax.jit, **jit_kwargs)
        def fused(store, *rest):
            arenas = list(rest[:narena])
            rows_packed, aux = rest[narena], rest[narena + 1]
            parts, p = [], 0
            for n in self._aux_layout(key):
                parts.append(aux[p:p + n])
                p += n
            n_tiles = parts[0][0]
            table = parts[1].reshape(tile_b, TILE_FIELDS)
            pt = dict(zip(PATCH_FIELDS, parts[2:2 + len(PATCH_FIELDS)]))
            q = 2 + len(PATCH_FIELDS)
            rowidx_all, lane_slot = parts[q], parts[q + 1]
            q += 2
            rp = 0
            for ai, (cls, n_rows) in enumerate(zip(classes, fresh)):
                if not n_rows:
                    continue
                width = cls * 34
                rows = rows_packed[rp:rp + n_rows * width]
                rows = full_rows(rows.reshape(n_rows, width), widths[ai])
                rp += n_rows * width
                idx = parts[q + ai]  # pad rows -> arena scratch row 0
                arenas[ai] = arenas[ai].at[idx].set(rows, mode="drop")
            if lean_b:
                # lean wire records: zero-extend each 18-word content
                # record to a full 34-word class-1 row and re-derive the
                # keccak pad bits from the shipped RLP length (0x01 at
                # byte len, 0x80 at byte 135). Fresh rows carry zero
                # holes, so set == what the full upload would have held;
                # pad records (idx 0, len 0) land in the scratch row.
                lidx, llen = parts[q + narena], parts[q + narena + 1]
                lrows = rows_packed[rp:rp + lean_b * LEAN_WORDS]
                lrows = lrows.reshape(lean_b, LEAN_WORDS)
                full = jnp.zeros((lean_b, 34), jnp.uint32)
                full = full.at[:, :LEAN_WORDS].set(lrows)
                full = full.at[jnp.arange(lean_b), llen >> 2].add(
                    jnp.uint32(1)
                    << ((llen & 3) * 8).astype(jnp.uint32))
                full = full.at[:, 33].add(jnp.uint32(0x80) << 24)
                ai = classes.index(1)
                arenas[ai] = arenas[ai].at[lidx].set(
                    full_rows(full, widths[ai]), mode="drop")
            # store-side digests of every patch, read before the store
            # scatter: signed source -k = store slot k (0 = none: both
            # gathers hit the pinned-zero row 0); +k = this commit's dig
            # row k, read in the loop once its tile has run
            snew = store[jnp.maximum(-pt["src"], 0)]
            old = store[pt["oldidx"]]

            def tile(i, dig):
                row = jax.lax.dynamic_index_in_dim(table, i, keepdims=False)
                ai, gstart, lane_off = row[0], row[1], row[2]
                lanes, pstart, npatch = row[3], row[4], row[5]
                ridx = jax.lax.dynamic_slice(rowidx_all, (lane_off,),
                                             (TILE_LANES,))
                ridx = jnp.where(lanes_t < lanes, ridx, 0)  # pads: scratch
                out = jax.lax.switch(ai, hashers, tuple(arenas), ridx, dig,
                                     pt, snew, old, pstart, npatch)
                # a tile's pad lanes write rows that later tiles overwrite,
                # or dig's tile of slack, so the write never clamps
                return jax.lax.dynamic_update_slice(dig, out, (gstart + 1, 0))

            dig = jnp.zeros((1 + g_pad + TILE_LANES, 8), jnp.uint32)
            dig = jax.lax.fori_loop(0, n_tiles, tile, dig)[:1 + g_pad]
            # patch the arenas' holes, each patch into its own class's
            # arena; pad patches add a zero delta to scratch row 0
            new = jnp.where(pt["src"][:, None] > 0,
                            dig[jnp.maximum(pt["src"], 0)], snew)
            word, delta = deltas(pt["pcol"], new, old)
            col = word[:, None] + nine[None, :]
            for ai in range(narena):
                r = jnp.where(pt["pcls"] == ai, pt["prow"],
                              arenas[ai].shape[0])
                arenas[ai] = arenas[ai].at[r[:, None], col].add(
                    delta, mode="drop")
            store = store.at[lane_slot].set(dig[1:], mode="drop")
            return (store, *arenas, dig)

        return fused

    def prepare(self, export) -> None:
        """Grow the resident buffers and compile this commit's program on
        the calling thread. Compiling is host work that can take minutes
        for a first large commit; callers run this before they start a
        device watchdog, so the watchdog times only the device."""
        self._prepared = (export, self._prepare(export))

    def _prepare(self, export):
        specs = export["specs"]            # [n_seg, 6] int32 host array
        self._ensure_store(export["store_slots"])
        for cls, (n_fresh, rows_needed) in export["classes"].items():
            self._ensure_arena(cls, rows_needed)
        if not self.fused:
            if len(specs) > MAX_SEGMENTS:
                raise ValueError(f"{len(specs)} segments > {MAX_SEGMENTS}")
            return None
        from ..metrics import phase_timer

        with phase_timer("resident/phase/compile"):
            key, plan = self._signature(export, specs)
            return key, plan, self._fused_program(key)

    def _signature(self, export, specs):
        """The commit's program key (capacities and held buckets, see
        sticky_buckets) and its plan_tiles() result."""
        lean = export.get("lean")
        n_lean = lean[1].shape[0] if lean is not None else 0
        used = ({int(s[0]) for s in specs} | set(export["fresh"])
                | ({1} if n_lean else set()))
        for cls in used:
            self._ensure_arena(cls, 1)  # segment-only classes must exist
        classes = tuple(sorted(self.arenas))
        tiles, patches = plan_tiles(export,
                                    {c: i for i, c in enumerate(classes)})
        fresh = export["fresh"]
        needs = {"g_pad": int(export["total_lanes"]),
                 "patch": patches["src"].shape[0],
                 "tile": tiles.shape[0], "lean": n_lean}
        needs.update({("fresh", c): fresh[c][1].shape[0] if c in fresh
                      else 0 for c in classes})
        b = self._buckets = sticky_buckets(self._buckets, needs)
        key = (classes, self.store.shape[0],
               tuple(self.arenas[c].shape[0] for c in classes),
               b["g_pad"], b["patch"], b["tile"],
               tuple(b["fresh", c] for c in classes), b["lean"])
        return key, (tiles, patches)

    def _run_fused(self, export, prepared) -> jax.Array:
        from ..metrics import default_registry, phase_timer

        key, (tiles, patches), fn = prepared
        classes = key[0]
        layout = self._aux_layout(key)
        lean = export.get("lean")
        n_lean = lean[1].shape[0] if lean is not None else 0
        with phase_timer("resident/phase/scatter"):
            # staging reuse (the plan cache's host half): warm commits
            # refill this key's preallocated aux/rows buffers in place
            # instead of re-concatenating ~10 arrays. A dispatched
            # commit's program may still be consuming these exact
            # buffers (device_put can alias host memory on the CPU
            # backend), so each ring entry carries the lazy root of the
            # commit that consumed it and is only rewritten once that
            # commit has settled. Ring size pipeline_depth+1 keeps up to
            # `pipeline_depth` commits in flight without ever blocking
            # on the newest dispatch — the AlDBaran overlap window
            ring = self._staging.get(key)
            if ring is None:
                ring = self._staging[key] = []
            want = max(0, int(self.pipeline_depth)) + 1
            while len(ring) > want:  # depth was lowered: shrink the ring
                ring.pop(0)
            if len(ring) >= want:
                aux, rows_packed, busy = ring.pop(0)
                if busy is not None and hasattr(busy, "block_until_ready"):
                    with phase_timer("resident/phase/wait"):
                        busy.block_until_ready()
            else:
                (n_rows,), (n_aux,) = self._upload_shapes(key)
                aux = np.zeros(n_aux, np.int32)
                rows_packed = np.zeros(max(n_rows, 1), np.uint32)
            fresh = export["fresh"]
            none = np.zeros(0, np.int32)
            # (entries, pad value) per region; pads are 0 (no patch,
            # scratch row, npatch 0) except lane_slot's, which send pad
            # lanes to scratch slot 1
            regions = [(np.array([tiles.shape[0]]), 0),
                       (tiles.reshape(-1), 0)]
            regions += [(patches[f], 0) for f in PATCH_FIELDS]
            regions += [(export["rowidx"], 0), (export["lane_slot"], 1)]
            regions += [(fresh[c][1] if c in fresh else none, 0)
                        for c in classes]
            regions += [(lean[1] if n_lean else none, 0),
                        (lean[2] if n_lean else none, 0)]
            p = 0
            for n, (fill, pad) in zip(layout, regions):
                aux[p:p + fill.shape[0]] = fill
                aux[p + fill.shape[0]:p + n] = pad
                p += n
            rp = 0
            for cls, bucket in zip(classes, key[6]):
                w = cls * 34
                n = fresh[cls][0].shape[0] if cls in fresh else 0
                if n:
                    rows_packed[rp:rp + n * w] = fresh[cls][0].reshape(-1)
                rows_packed[rp + n * w:rp + bucket * w] = 0
                rp += bucket * w
            lean_b = key[7]
            if lean_b:
                nw = n_lean * LEAN_WORDS
                if n_lean:
                    rows_packed[rp:rp + nw] = lean[0].reshape(-1)
                rows_packed[rp + nw:rp + lean_b * LEAN_WORDS] = 0
                rp += lean_b * LEAN_WORDS
            self.last_lean_rows = n_lean
            self.last_lean_wire_bytes = n_lean * (4 * LEAN_WORDS + 8)

        # `patch` times the two uploads and the program's enqueue, not
        # its run on the device: the wait for that is resident/phase/wait
        with phase_timer("resident/phase/patch"):
            rows_d = self._put(rows_packed[:rp])
            aux_d = self._put(aux)
            outs = fn(self.store, *(self.arenas[c] for c in classes),
                      rows_d, aux_d)
        with phase_timer("resident/phase/store"):
            self.store = outs[0]
            for i, c in enumerate(classes):
                self.arenas[c] = outs[1 + i]
            dig = outs[-1]
            self.h2d_bytes = rows_packed[:rp].nbytes + aux.nbytes
            self.last_transfers = 2
            self.last_dispatches = 1
            self.last_dig = dig
            self.last_root = dig[int(export["root_lane"]) + 1]
            # return the staging buffers to the ring tagged with THIS
            # commit's lazy root — the reuse gate above blocks on it
            self._staging.setdefault(key, []).append(
                (aux, rows_packed, self.last_root))
            default_registry.counter("resident/h2d_bytes").inc(
                self.h2d_bytes)
            default_registry.counter("resident/lean_wire_bytes").inc(
                self.last_lean_wire_bytes)
            default_registry.counter("resident/keccak/pad_lanes").inc(
                tiles.shape[0] * TILE_LANES - int(tiles[:, 3].sum()))
            self._note_collectives(export)
        return self.last_root

    # ---- one commit ----

    def run(self, export) -> jax.Array:
        """Execute one resident commit. `export` is the dict produced by
        native.mpt.IncrementalTrie.export_resident_plan(). Returns the
        root digest as a LAZY uint32[8] device array — call
        np.asarray(...) (or root_bytes) to synchronize. Uses the work of
        a prepare(export) made just before, or prepares itself."""
        from .keccak_pallas import count_segments

        done, self._prepared = self._prepared, None
        prepared = (done[1] if done is not None and done[0] is export
                    else self._prepare(export))
        specs = export["specs"]
        count_segments("resident", self._impl, [int(s[1]) for s in specs])
        count_keccak_work("resident", [(int(s[0]), int(s[1]))
                                       for s in specs])
        if self.fused:
            return self._run_fused(export, prepared)

        h2d = 0
        # fresh-row uploads, one scatter per class
        for cls, (rows, idx) in export["fresh"].items():
            n = idx.shape[0]
            bucket = _pow2_bucket(n)
            if bucket != n:
                rows = np.concatenate(
                    [rows, np.zeros((bucket - n, rows.shape[1]), np.uint32)])
                idx = np.concatenate(
                    [idx, np.zeros(bucket - n, np.int32)])
            self.arenas[cls] = _scatter_rows(
                self.arenas[cls], self._put(rows), self._put(idx))
            h2d += rows.nbytes + idx.nbytes

        # lean class-1 records: the non-fused fallback expands them on
        # the host (zero-extend to 34 words + keccak pad bits) and ships
        # full rows — no wire savings here, so the diagnostics record the
        # bytes actually uploaded, not the fused-path lean envelope
        self.last_lean_rows = 0
        self.last_lean_wire_bytes = 0
        lean = export.get("lean")
        if lean is not None and lean[1].shape[0]:
            lrows, lidx, llen = lean
            n = lidx.shape[0]
            full = np.zeros((n, 34), np.uint32)
            full[:, :LEAN_WORDS] = lrows
            fb = full.view(np.uint8).reshape(n, 136)
            fb[np.arange(n), llen] ^= 0x01
            fb[:, 135] ^= 0x80
            bucket = _pow2_bucket(n)
            idx = lidx
            if bucket != n:
                full = np.concatenate(
                    [full, np.zeros((bucket - n, 34), np.uint32)])
                idx = np.concatenate(
                    [idx, np.zeros(bucket - n, np.int32)])
            self._ensure_arena(1, 1)
            self.arenas[1] = _scatter_rows(
                self.arenas[1], self._put(full), self._put(idx))
            h2d += full.nbytes + idx.nbytes
            self.last_lean_rows = n
            self.last_lean_wire_bytes = full.nbytes + idx.nbytes

        meta = np.zeros((MAX_SEGMENTS, 3), np.int32)
        for i, s in enumerate(specs):
            meta[i] = (s[4], s[5], s[2])   # patch_off, lane_off, gstart
        tables = [self._put(export[k]) for k in
                  ("off", "src", "oldidx", "rowidx")]
        h2d += sum(export[k].nbytes for k in
                   ("off", "src", "oldidx", "rowidx"))
        lane_slot = self._put(export["lane_slot"])
        h2d += export["lane_slot"].nbytes
        mt = self._put(meta)
        seg_ids = self._put(np.arange(MAX_SEGMENTS, dtype=np.int32))
        off, src, oldidx, rowidx = tables

        # bucket the dig height to a power of two: every jitted step is
        # shape-keyed on dig, so an exact per-commit lane total would
        # recompile each program for every distinct commit size
        total_lanes = int(export["total_lanes"])
        g_pad = _pow2_bucket(total_lanes)
        if g_pad != lane_slot.shape[0]:
            lane_slot = jnp.concatenate([
                lane_slot,
                jnp.ones(g_pad - lane_slot.shape[0], jnp.int32)])  # scratch
        dig = jnp.zeros((1 + g_pad, 8), jnp.uint32)
        store = self.store
        for i, s in enumerate(specs):
            blocks, lanes = int(s[0]), int(s[1])
            arena = self.arenas[blocks]
            arena, dig = self._step(
                arena, store, dig, off, src, oldidx,
                rowidx, mt, seg_ids[i],
                lanes=lanes, blocks=blocks, npatch=int(s[3]))
            self.arenas[blocks] = arena
        self.store = _scatter_store(store, dig, lane_slot)
        self.h2d_bytes = h2d
        self.last_transfers = 7 + len(export["fresh"]) * 2
        self.last_dispatches = 1 + len(specs) + len(export["fresh"])
        self.last_dig = dig
        self.last_root = dig[int(export["root_lane"]) + 1]
        from ..metrics import default_registry

        default_registry.counter("resident/h2d_bytes").inc(self.h2d_bytes)
        default_registry.counter("resident/lean_wire_bytes").inc(
            self.last_lean_wire_bytes)
        self._note_collectives(export)
        return self.last_root

    @staticmethod
    def root_bytes(root: jax.Array) -> bytes:
        """Synchronize and render a run() result as the 32-byte root;
        the wait for the device is timed as `resident/phase/wait`."""
        from ..metrics import phase_timer

        with phase_timer("resident/phase/wait"):
            host = np.asarray(root)
        return host.astype("<u4").tobytes()
