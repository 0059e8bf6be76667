"""Device-mesh parallelism for the state-commitment path.

The reference's only "distributed" hashing is a 16-goroutine fan-out per
branch node (/root/reference/trie/hasher.go:124-139). The TPU-native design
shards the *batch* instead: one level's worth of node RLP is laid out as a
dense tensor and split across every chip of a `jax.sharding.Mesh` over ICI.
Keccak lanes are independent, so the shard axis is pure data parallelism;
the only collective is the digest all-gather back to the host (and a psum
for the batch checksum used by integrity checks).

`ShardedKeccak` is the multi-chip analog of ops.keccak_jax.BatchedKeccak:
same host API (list[bytes] -> list[digest]), device batches sharded over the
mesh's 'batch' axis via NamedSharding + jit.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.keccak_jax import (
    WORDS_PER_BLOCK,
    digest_words_to_bytes,
    keccak256_blocks,
    pack_messages,
)


class MeshConfigError(ValueError):
    """A mesh request that can never produce a working sharded commit —
    raised at mesh construction with an actionable message instead of
    surfacing as an opaque shape/device error deep inside shard_map or
    GSPMD partitioning (the resident-mesh-devices knob's fail-fast)."""


# the planner buckets every segment's lane count to a multiple of this
# (ops/keccak_resident._pow2_bucket floor; mpt_inc.cpp round_lanes), so a
# mesh width must divide it for lanes to split evenly across shards
LANE_BUCKET = 16


def _check_width(n: int, what: str) -> None:
    devs = jax.devices()
    if n <= 0:
        raise MeshConfigError(
            f"{what} must be a positive device count (got {n})")
    if n > len(devs):
        raise MeshConfigError(
            f"{what} requests {n} devices but only {len(devs)} JAX "
            f"device(s) are visible on backend "
            f"{jax.default_backend()!r}; lower the width (e.g. the "
            f"resident-mesh-devices knob) or, for a virtual CPU mesh, "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"before the first jax call")
    if LANE_BUCKET % n != 0:
        raise MeshConfigError(
            f"{what} of {n} does not divide the {LANE_BUCKET}-lane "
            f"planner bucket: segment lane counts are multiples of "
            f"{LANE_BUCKET}, so shards would be uneven — use a "
            f"power-of-two width <= {LANE_BUCKET}")


def process_count() -> int:
    """Number of jax processes in this runtime (1 = single-process)."""
    return int(jax.process_count())


def mesh_spans_processes(mesh: Mesh) -> bool:
    """True when [mesh]'s devices belong to more than one jax process.

    Multi-process readiness gate: a mesh that spans processes runs one
    SPMD program per process, so any UNILATERAL local action on the
    resident state (e.g. the demotion ladder rebuilding on a local
    single device) would desync the other processes — callers must take
    the collective-safe path instead."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def make_mesh(n_devices: Optional[int] = None, axis: str = "batch") -> Mesh:
    """1-D mesh over the first n devices (all by default).

    Raises MeshConfigError (not an opaque shard_map failure) when the
    requested width exceeds the visible devices or does not divide the
    planner's lane bucketing."""
    devs = jax.devices()
    if n_devices is not None:
        _check_width(int(n_devices), "mesh width")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def make_mesh_2d(n_hosts: int, chips_per_host: int,
                 axes=("host", "batch")) -> Mesh:
    """2-D (host, chip) mesh — the multi-host deployment SHAPE.

    The intent: the outer axis is the host boundary, so its collectives
    ride DCN while the inner axis rides ICI — slow hops stay at the top
    of the reduction tree (the scaling-book layout rule). Keccak lanes
    are pure data parallelism, so the commit path shards lanes over BOTH
    axes and the only cross-host traffic is the digest gather /
    checksum psum.

    Device ordering: mesh_utils.create_device_mesh arranges devices so
    mesh rows align with the physical topology where the backend exposes
    it; the naive reshape fallback is only correct on single-host /
    virtual meshes (where this helper validates sharding LAYOUTS — on a
    real multi-host slice, prefer mesh_utils.create_hybrid_device_mesh
    with explicit per-host groupings)."""
    if n_hosts <= 0 or chips_per_host <= 0:
        raise MeshConfigError(
            f"2-D mesh extents must be positive (got {n_hosts} hosts x "
            f"{chips_per_host} chips/host)")
    want = n_hosts * chips_per_host
    _check_width(want, f"2-D mesh ({n_hosts} hosts x {chips_per_host} "
                       f"chips/host)")
    devs = jax.devices()[:want]
    try:
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_device_mesh(
            (n_hosts, chips_per_host), devices=devs)
    except Exception:  # virtual/CPU meshes: topology-agnostic reshape
        arr = np.array(devs).reshape(n_hosts, chips_per_host)
    return Mesh(arr, axes)


class ShardedKeccak:
    """Batched keccak sharded across a device mesh (data-parallel lanes).

    Host packs messages exactly like the single-chip path; the batch dim is
    padded to a multiple of (mesh size x 8 sublanes) and placed with
    NamedSharding(P('batch')) so XLA splits the scan across chips over ICI.
    """

    def __init__(self, mesh: Mesh, axis="batch"):
        # axis: str | tuple[str, ...] — a tuple shards the lane dim over
        # several mesh axes (the 2-D host x chip layout)
        self.mesh = mesh
        self.axis = axis
        self._sharding = NamedSharding(mesh, P(axis))
        self._fn = jax.jit(
            keccak256_blocks,
            in_shardings=(self._sharding, self._sharding),
            out_shardings=self._sharding,
        )

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    def digests(self, msgs: Sequence[bytes]) -> List[bytes]:
        n = len(msgs)
        if n == 0:
            return []
        words, nblocks = pack_messages(msgs)
        # power-of-two bucket (multiple of devices x 8 sublanes) so the set
        # of compiled shapes stays logarithmic in batch size
        mult = self.n_devices * 8
        target = mult
        while target < n:
            target *= 2
        pad = target - n
        if pad:
            words = np.concatenate(
                [words, np.zeros((pad,) + words.shape[1:], dtype=words.dtype)]
            )
            nblocks = np.concatenate([nblocks, np.ones(pad, dtype=nblocks.dtype)])
        out = np.asarray(
            self._fn(
                jax.device_put(jnp.asarray(words), self._sharding),
                jax.device_put(jnp.asarray(nblocks), self._sharding),
            )
        )
        return digest_words_to_bytes(out[:n])


def commit_step(mesh: Mesh, axis="batch"):
    # axis: str | tuple[str, ...] (tuple = multi-axis lane sharding)
    """Jitted sharded state-commitment step for the multi-chip dry run.

    One "training step" of this framework is a level-batched hash drain:
    hash every lane, then reduce a 32-bit checksum of the digests across the
    mesh (the integrity counter the acceptor queue records per block). The
    jnp.sum over the sharded digest tensor compiles to a real cross-chip
    reduction, so the dry run validates both the sharded compute and the
    collective path.
    """
    sharding = NamedSharding(mesh, P(axis))
    replicated = NamedSharding(mesh, P())

    # explicit in/out shardings (SA012): the checksum must come back
    # replicated and the digests stay lane-sharded — pinning both keeps
    # chained steps reshard-free when the mesh spans processes (pjit
    # multi-process recipe: never let placement be inferred per call)
    @partial(jax.jit,
             in_shardings=(sharding, sharding),
             out_shardings=(sharding, replicated))
    def step(words, nblocks):
        out = keccak256_blocks(words, nblocks)  # [B, 8] uint32, sharded on B
        checksum = jnp.sum(out, dtype=jnp.uint32)  # cross-shard reduction
        return out, checksum

    def run(words: np.ndarray, nblocks: np.ndarray):
        w = jax.device_put(jnp.asarray(words), sharding)
        nb = jax.device_put(jnp.asarray(nblocks), sharding)
        return step(w, nb)

    return run


def sharded_seg_impl(mesh: Mesh, axis: str = "batch", seg_impl=None):
    """Per-segment keccak for ops.keccak_planned.PlannedCommit with the
    lane dimension sharded across [mesh] (SURVEY §2.7: the 16-goroutine
    hasher fan-out re-landed as data parallelism over ICI).

    Composition: the planned executor's surrounding ops (patch gathers,
    scatter-add, digest updates) stay replicated — only the keccak FLOPs
    shard. Lanes are always a multiple of 16 (planner bucketing), so every
    mesh size up to 16 divides evenly.

    seg_impl=None: the XLA scan kernel, partitioned by GSPMD via sharding
    constraints. seg_impl given (e.g. keccak_pallas.staged_seg_impl): the
    kernel is mapped per-device with shard_map — a pallas_call is a custom
    call GSPMD cannot split, so each device runs the kernel on its own
    lane shard (the exact partitioning a pod would use); the impl's own
    static shape logic (Pallas for %1024-lane shards, XLA below) applies
    PER SHARD. GSPMD/shard_map inserts the digest all-gather back to
    replicated either way."""
    if seg_impl is not None:
        from jax import shard_map

        out_replicated = NamedSharding(mesh, P())

        def impl(words):
            # check_vma=False: pallas_call's out_shape carries no varying-
            # mesh-axes annotation, and the kernel is per-shard pure data
            # parallelism anyway (no cross-shard collectives to validate)
            out = shard_map(
                seg_impl, mesh=mesh,
                in_specs=(P(axis, None, None),), out_specs=P(axis, None),
                check_vma=False,
            )(words)
            # all-gather digests back to replicated, matching the GSPMD
            # branch: the planned step's surrounding ops (patch gathers
            # over arbitrary child lanes, dig updates) assume it
            return jax.lax.with_sharding_constraint(out, out_replicated)

        return impl

    from ..ops.keccak_staged import _segment_keccak

    lane_sharded = NamedSharding(mesh, P(axis, None, None))
    replicated = NamedSharding(mesh, P())

    def impl(words):
        w = jax.lax.with_sharding_constraint(words, lane_sharded)
        out = _segment_keccak(w)
        return jax.lax.with_sharding_constraint(out, replicated)

    return impl


_planned_by_mesh: dict = {}


def planned_commit_over_mesh(mesh: Mesh, axis: str = "batch"):
    """A PlannedCommit whose hashing shards across [mesh]. Cached per
    (mesh, axis) so repeated commits reuse one jit trace cache instead of
    re-tracing every segment shape per call."""
    key = (tuple(d.id for d in mesh.devices.flat), axis)
    runner = _planned_by_mesh.get(key)
    if runner is None:
        from ..ops.keccak_planned import PlannedCommit

        runner = PlannedCommit(seg_impl=sharded_seg_impl(mesh, axis))
        _planned_by_mesh[key] = runner
    return runner


def resident_executor_over_mesh(mesh: Mesh, axis: str = "batch",
                                seg_impl=None):
    """A ResidentExecutor whose device-resident state (digest store +
    row arenas) is SHARDED across [mesh] on the row axis — the
    multichip form of the deferred-absorb design: each device holds
    1/N of every arena class and of the digest store, so resident
    memory capacity and fresh-row upload bandwidth scale with the mesh
    (each host feeds its own chips' row shards over its own PCI/ICI
    link in a pod).

    Partitioning is GSPMD-driven: the step's row gathers, delta
    scatter-adds, and store scatters run over the sharded operands with
    XLA inserting the collectives; the per-commit dig matrix stays
    replicated (it is small and every later segment's patches may read
    any earlier lane). One executor per trie, as in the single-chip
    case. Validated on the virtual CPU mesh by __graft_entry__.
    dryrun_multichip's resident leg (root parity vs the host oracle
    across churn + rollback rounds).

    axis may be one mesh axis name or a tuple of names: on a 2-D
    (host, chip) mesh (make_mesh_2d), axis=("host", "batch") shards
    rows over every device — each host owns a contiguous row block, so
    fresh-row uploads stay host-local and only digest traffic crosses
    DCN."""
    from ..ops.keccak_resident import ResidentExecutor

    return ResidentExecutor(
        seg_impl=seg_impl,
        sharding=NamedSharding(mesh, P(axis, None)),
    )
