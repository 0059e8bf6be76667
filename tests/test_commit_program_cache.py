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


def _resident_chain(seed, n):
    """A trie of n keys committed resident once (its genesis commit),
    with a native CPU twin; returns (rng, keys, commit) where commit(batch)
    applies the batch to both and commits it resident, checks the root
    against the twin, and returns the export the executor ran."""
    from coreth_tpu.native.mpt import IncrementalTrie
    from coreth_tpu.ops.keccak_resident import ResidentExecutor

    rng = np.random.default_rng(seed)
    state = {rng.bytes(32): rng.bytes(40) for _ in range(n)}
    dev = IncrementalTrie(sorted(state.items()))
    cpu = IncrementalTrie(sorted(state.items()))
    ex = ResidentExecutor()
    exports = []
    run = ex.run
    ex.run = lambda export: (exports.append(export), run(export))[1]
    assert ex.root_bytes(dev.commit_resident(ex)) == cpu.commit_cpu()

    def commit(batch):
        dev.update(batch)
        cpu.update(batch)
        assert ex.root_bytes(dev.commit_resident(ex)) == cpu.commit_cpu()
        return ex, exports[-1]

    return rng, sorted(state), commit


def test_resident_miss_counts_only_the_offsets_that_moved():
    """Two block commits whose segment counts and offsets differ but fit
    the held buckets share one program: the second is a hit and compiles
    nothing. A commit that outgrows a bucket is one miss, counted under
    the field of each bucket it outgrew."""
    from coreth_tpu.ops.keccak_resident import key_fields

    rng, keys, commit = _resident_chain(3, 1500)

    def update(n):
        picked = rng.choice(len(keys), n, replace=False)
        return [(keys[i], rng.bytes(40)) for i in picked]

    ex, first = commit(update(60))    # block-sized: its own program
    a = ex._last_key
    compiles = default_registry.counter("resident/compiles")
    misses = default_registry.counter("resident/plan_cache/misses")
    hits = default_registry.counter("resident/plan_cache/hits")
    c0, m0, h0, f0 = compiles.count(), misses.count(), hits.count(), \
        _counts("resident", RESIDENT_KEY_FIELDS)
    t0 = _timers("resident")
    ex, second = commit(update(45))   # other counts and offsets
    assert first["specs"].tolist() != second["specs"].tolist()
    assert first["off"].shape != second["off"].shape
    assert ex.last_cache_hit and ex._last_key == a
    assert (compiles.count(), misses.count(), hits.count()) \
        == (c0, m0, h0 + 1)
    assert _counts("resident", RESIDENT_KEY_FIELDS) == f0
    assert _timers("resident") == t0
    burst = [(rng.bytes(32), rng.bytes(40)) for _ in range(400)]
    ex, _ = commit(burst)             # fresh rows and lanes outgrow
    b = ex._last_key
    grown = {f for f, v in key_fields(b).items() if key_fields(a)[f] != v}
    moved = {f: n - f0[f] for f, n in
             _counts("resident", RESIDENT_KEY_FIELDS).items() if n != f0[f]}
    assert moved == dict.fromkeys(grown, 1)
    assert {"g_pad", "fresh"} <= grown
    assert (compiles.count() - c0, misses.count() - m0) == (1, 1)
    assert {s: n - t0[s] for s, n in _timers("resident").items()} \
        == {"trace": 1, "lower": 1, "backend": 1}


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
