"""The commit executors' program caches, seen through their counters.

Each executor compiles a whole-commit program per key, through JAX's
stages (trace, lower, backend compile), and on a miss counts which
fields of the key changed since the previous commit. It also counts
the keccak work each commit hands its kernels.
"""

import numpy as np

from coreth_tpu.metrics import default_registry
from coreth_tpu.metrics.flight import PLANNED_KEY_FIELDS, RESIDENT_KEY_FIELDS


def _counts(prefix, fields):
    return {f: default_registry.counter(
        prefix + "/plan_cache/miss_field/" + f).count() for f in fields}


def _timers(prefix):
    return {s: default_registry.timer(prefix + "/phase/compile_" + s).count()
            for s in ("trace", "lower", "backend")}


def _resident_key(ex, spec):
    """A one-segment signature of class-1 rows: 16 lanes, 4 patches, in
    a 32-lane commit; spec = (gstart, patch_off, lane_off)."""
    gstart, patch_off, lane_off = spec
    return (((1, 16, gstart, 4, patch_off, lane_off),), (), (1,),
            ex.store.shape[0], (ex.arenas[1].shape[0],), 32, 8, 32, 0)


def test_resident_miss_counts_only_the_offsets_that_moved():
    from coreth_tpu.ops.keccak_resident import ResidentExecutor

    ex = ResidentExecutor()
    ex._ensure_store(64)
    ex._ensure_arena(1, 64)
    a = _resident_key(ex, (0, 0, 0))
    b = _resident_key(ex, (16, 4, 16))
    compiles = default_registry.counter("resident/compiles")
    misses = default_registry.counter("resident/plan_cache/misses")
    c0, m0, f0 = compiles.count(), misses.count(), \
        _counts("resident", RESIDENT_KEY_FIELDS)
    t0 = _timers("resident")
    ex._fused_program(a)              # first program: nothing to compare
    assert _counts("resident", RESIDENT_KEY_FIELDS) == f0
    ex._fused_program(b)              # same shapes, other offsets
    moved = {f: n - f0[f] for f, n in
             _counts("resident", RESIDENT_KEY_FIELDS).items() if n != f0[f]}
    assert moved == {"seg_offsets": 1}
    assert compiles.count() - c0 == 2 and misses.count() - m0 == 2
    assert {s: n - t0[s] for s, n in _timers("resident").items()} \
        == {"trace": 2, "lower": 2, "backend": 2}
    hits = default_registry.counter("resident/plan_cache/hits").count()
    assert ex._fused_program(a) is not None and ex.last_cache_hit
    assert default_registry.counter("resident/plan_cache/hits").count() \
        == hits + 1
    assert compiles.count() - c0 == 2


def test_planned_cache_hits_repeats_and_counts_what_changed():
    from coreth_tpu.native.mpt import IncrementalTrie
    from coreth_tpu.ops.keccak_planned import PlannedCommit

    rng = np.random.default_rng(5)
    items = sorted((rng.bytes(32), rng.bytes(40)) for _ in range(200))
    pc = PlannedCommit()
    lanes = default_registry.counter("planned/keccak/lanes")
    blocks = default_registry.counter("planned/keccak/rate_blocks")
    h2d = default_registry.counter("planned/h2d_bytes")
    misses = default_registry.counter("planned/plan_cache/misses")
    hits = default_registry.counter("planned/plan_cache/hits")

    def commit(batch):
        trie = IncrementalTrie(batch)
        specs = trie._export_plan()[0]
        l0, b0, h0 = lanes.count(), blocks.count(), h2d.count()
        root = trie.commit_device(planned=pc)
        assert root == IncrementalTrie(batch).commit_cpu()
        assert lanes.count() - l0 == sum(s.lanes for s in specs)
        assert blocks.count() - b0 == sum(s.blocks * s.lanes for s in specs)
        assert h2d.count() - h0 == pc.last_h2d_bytes > 0
        return specs

    m0, k0, f0 = misses.count(), hits.count(), \
        _counts("planned", PLANNED_KEY_FIELDS)
    commit(items)
    commit(items)                     # the same shapes: the same program
    assert (misses.count() - m0, hits.count() - k0) == (1, 1)
    assert _counts("planned", PLANNED_KEY_FIELDS) == f0
    commit(items[:40])                # a smaller trie: new shapes
    assert misses.count() - m0 == 2
    moved = {f for f, n in _counts("planned", PLANNED_KEY_FIELDS).items()
             if n != f0[f]}
    assert "flat_words" in moved and moved <= set(PLANNED_KEY_FIELDS)
