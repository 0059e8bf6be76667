"""Device-resident commit parity: the resident executor (persistent
device store + row arenas, delta patches — ops/keccak_resident.py +
native/mpt_inc.cpp build_plan_res) must produce bit-exact roots against
the host-cached incremental oracle and the full-rebuild planner across
arbitrary insert/update/delete sequences.

Runs on the CPU backend (tests/conftest.py pins jax to cpu); shapes and
semantics are identical on TPU. Reference semantics under test:
/root/reference/trie/trie.go:573-626 (warm-trie dirty re-hash) with the
digest cache held in device memory instead of host memory.
"""

import random

import numpy as np
import pytest

from coreth_tpu.native.mpt import (
    EMPTY_ROOT,
    IncrementalTrie,
    plan_from_items,
)

def _executor():
    from coreth_tpu.ops.keccak_resident import ResidentExecutor

    return ResidentExecutor()


def _root_bytes(executor, handle) -> bytes:
    from coreth_tpu.ops.keccak_resident import ResidentExecutor

    return ResidentExecutor.root_bytes(handle)


def _rand_items(rng, n, klen=32):
    return {rng.randbytes(klen): rng.randbytes(rng.randint(1, 90))
            for _ in range(n)}


def _full_rebuild_root(state: dict) -> bytes:
    if not state:
        return EMPTY_ROOT
    return plan_from_items(sorted(state.items())).execute_cpu()


def test_resident_single_commit_matches_oracle():
    rng = random.Random(11)
    state = _rand_items(rng, 500)
    items = sorted(state.items())
    dev = IncrementalTrie(items)
    cpu = IncrementalTrie(items)
    ex = _executor()
    root = _root_bytes(ex, dev.commit_resident(ex))
    assert root == cpu.commit_cpu()
    assert root == _full_rebuild_root(state)


def test_resident_repeated_churn_parity():
    """Many commits with mixed insert/replace/delete — every root
    bit-exact vs the host oracle; h2d shrinks to patch-table scale once
    the trie is warm."""
    rng = random.Random(12)
    state = _rand_items(rng, 2000)
    items = sorted(state.items())
    dev = IncrementalTrie(items)
    cpu = IncrementalTrie(items)
    ex = _executor()
    assert _root_bytes(ex, dev.commit_resident(ex)) == cpu.commit_cpu()

    keys = list(state)
    steady_fresh = []
    for rnd in range(12):
        batch = []
        for _ in range(150):
            r = rng.random()
            if r < 0.45:  # replace existing
                batch.append((rng.choice(keys), rng.randbytes(60)))
            elif r < 0.75:  # fresh insert
                k = rng.randbytes(32)
                keys.append(k)
                batch.append((k, rng.randbytes(50)))
            else:  # delete
                batch.append((rng.choice(keys), b""))
        dev.update(batch)
        cpu.update(batch)
        for k, v in batch:
            if v:
                state[k] = v
            else:
                state.pop(k, None)
        root_cpu = cpu.commit_cpu()
        root_dev = _root_bytes(ex, dev.commit_resident(ex))
        assert root_dev == root_cpu, f"round {rnd} root mismatch"
        steady_fresh.append(ex.h2d_bytes)
    assert _root_bytes(ex, ex.last_root) == \
        _full_rebuild_root(state)
    # template residency: steady-state uploads must be far below the
    # ~800 B/dirty-node of the non-resident path. 150-key churn dirties
    # ~400 nodes; full re-upload would be 300KB+.
    assert min(steady_fresh[2:]) < 200_000


def test_resident_value_only_churn_is_patch_dominated():
    """Replacing existing values (no structural change above the leaves)
    re-uploads leaf rows but only patch-tables for the branch spine."""
    rng = random.Random(13)
    state = _rand_items(rng, 4000)
    items = sorted(state.items())
    dev = IncrementalTrie(items)
    ex = _executor()
    dev.commit_resident(ex)
    first_h2d = ex.h2d_bytes
    keys = list(state)
    batch = [(k, rng.randbytes(60)) for k in rng.sample(keys, 200)]
    dev.update(batch)
    exp = dev.export_resident_plan()
    # branch spine above 200 random leaves in a 4000-leaf trie is ~500+
    # nodes; with template residency only the ~200 leaf rows re-upload
    n_fresh = sum(v[0] for v in exp["classes"].values())
    n_leaf_fresh = sum(idx.shape[0] for _, idx in exp["fresh"].values())
    assert exp["num_dirty"] > 300
    assert n_leaf_fresh <= 260, (n_fresh, exp["num_dirty"])
    assert exp["fresh_bytes"] < 0.25 * first_h2d


def test_resident_empty_update_reuses_last_root():
    rng = random.Random(14)
    items = sorted(_rand_items(rng, 64).items())
    dev = IncrementalTrie(items)
    ex = _executor()
    r1 = _root_bytes(ex, dev.commit_resident(ex))
    r2 = _root_bytes(ex, dev.commit_resident(ex))  # nothing dirty
    assert r1 == r2


def test_resident_delete_down_to_small_trie():
    rng = random.Random(15)
    state = _rand_items(rng, 300)
    items = sorted(state.items())
    dev = IncrementalTrie(items)
    cpu = IncrementalTrie(items)
    ex = _executor()
    assert _root_bytes(ex, dev.commit_resident(ex)) == cpu.commit_cpu()
    keys = list(state)
    rng.shuffle(keys)
    # delete in waves until only a handful remain (forces collapses,
    # merges, and hashed->embedded transitions near the root)
    while len(keys) > 3:
        drop, keys = keys[:max(1, len(keys) // 3)], keys[max(1, len(keys) // 3):]
        batch = [(k, b"") for k in drop]
        dev.update(batch)
        cpu.update(batch)
        for k in drop:
            state.pop(k, None)
        assert _root_bytes(ex, dev.commit_resident(ex)) == cpu.commit_cpu()
    assert _root_bytes(ex, ex.last_root) == \
        _full_rebuild_root(state)


def test_mode_pinning_rejects_mixed_commits():
    rng = random.Random(16)
    items = sorted(_rand_items(rng, 50).items())
    t = IncrementalTrie(items)
    ex = _executor()
    t.commit_resident(ex)
    with pytest.raises(RuntimeError, match="commit mode"):
        t.commit_cpu()
    t2 = IncrementalTrie(items)
    t2.commit_cpu()
    with pytest.raises(RuntimeError, match="commit mode"):
        t2.commit_resident(ex)


def test_resident_delete_to_empty_returns_empty_root():
    rng = random.Random(18)
    state = _rand_items(rng, 20)
    dev = IncrementalTrie(sorted(state.items()))
    cpu = IncrementalTrie(sorted(state.items()))
    ex = _executor()
    assert _root_bytes(ex, dev.commit_resident(ex)) == cpu.commit_cpu()
    batch = [(k, b"") for k in state]
    dev.update(batch)
    cpu.update(batch)
    assert _root_bytes(ex, dev.commit_resident(ex)) == EMPTY_ROOT
    assert cpu.commit_cpu() == EMPTY_ROOT
    # and an empty trie's FIRST resident commit is also the empty root
    ex2 = _executor()
    assert _root_bytes(ex2, IncrementalTrie().commit_resident(ex2)) == \
        EMPTY_ROOT


def test_executor_refuses_second_trie():
    rng = random.Random(19)
    items = sorted(_rand_items(rng, 30).items())
    a = IncrementalTrie(items)
    b = IncrementalTrie(items)
    ex = _executor()
    a.commit_resident(ex)
    with pytest.raises(RuntimeError, match="another trie"):
        b.commit_resident(ex)


def test_resident_root_accessor_guarded():
    rng = random.Random(20)
    t = IncrementalTrie(sorted(_rand_items(rng, 30).items()))
    ex = _executor()
    t.commit_resident(ex)
    with pytest.raises(RuntimeError, match="resident mode"):
        t.root()


def test_wide_node_plan_failure_leaves_mode_unpinned():
    """A >8.6KB node RLP fails resident planning; the trie must remain
    usable via the host path."""
    t = IncrementalTrie([(bytes(32), b"x" * 10_000)])
    ex = _executor()
    with pytest.raises(ValueError, match="resident row limit"):
        t.commit_resident(ex)
    assert t.commit_cpu() == plan_from_items(
        [(bytes(32), b"x" * 10_000)]).execute_cpu()


def test_resident_growth_reallocates_store_and_arenas():
    """Grow the trie past the initial store/arena capacity guesses —
    geometric growth must preserve resident contents."""
    rng = random.Random(17)
    state = _rand_items(rng, 200)
    dev = IncrementalTrie(sorted(state.items()))
    cpu = IncrementalTrie(sorted(state.items()))
    ex = _executor()
    assert _root_bytes(ex, dev.commit_resident(ex)) == cpu.commit_cpu()
    for _ in range(6):
        batch = list(_rand_items(rng, 1500).items())
        dev.update(batch)
        cpu.update(batch)
        state.update(batch)
        assert _root_bytes(ex, dev.commit_resident(ex)) == cpu.commit_cpu()
    assert _root_bytes(ex, ex.last_root) == \
        _full_rebuild_root(state)


def test_checkpoint_rollback_restores_roots():
    """Undo journal (the chain adapter's verify->reject enabler): apply a
    'block' under a checkpoint, roll back, and the next commits must
    produce the same roots as a trie that never saw the block — in BOTH
    commit modes."""
    rng = random.Random(21)
    state = _rand_items(rng, 800)
    items = sorted(state.items())

    # host mode
    t = IncrementalTrie(items)
    base_root = t.commit_cpu()
    t.checkpoint()
    batch = [(rng.choice(list(state)), rng.randbytes(50)) for _ in range(80)]
    batch += [(rng.randbytes(32), rng.randbytes(40)) for _ in range(40)]
    batch += [(k, b"") for k in rng.sample(list(state), 20)]
    t.update(batch)
    assert t.commit_cpu() != base_root
    assert t.rollback() == len(batch)
    assert t.commit_cpu() == base_root

    # resident mode: same sequence, device-side state must also recover
    dev = IncrementalTrie(items)
    ex = _executor()
    base_dev = _root_bytes(ex, dev.commit_resident(ex))
    assert base_dev == base_root
    dev.checkpoint()
    dev.update(batch)
    mid = _root_bytes(ex, dev.commit_resident(ex))
    assert mid != base_root
    dev.rollback()
    assert _root_bytes(ex, dev.commit_resident(ex)) == base_root


def test_checkpoint_discard_keeps_changes():
    rng = random.Random(22)
    state = _rand_items(rng, 200)
    t = IncrementalTrie(sorted(state.items()))
    t.commit_cpu()
    t.checkpoint()
    batch = [(rng.randbytes(32), b"v")]
    t.update(batch)
    t.discard_checkpoint()
    assert t.rollback() == 0  # no open scope: nothing reverts
    state[batch[0][0]] = b"v"
    assert t.commit_cpu() == _full_rebuild_root(state)


def test_nested_checkpoints():
    rng = random.Random(23)
    state = _rand_items(rng, 300)
    t = IncrementalTrie(sorted(state.items()))
    r0 = t.commit_cpu()
    t.checkpoint()                      # scope A
    t.update([(rng.randbytes(32), b"a")])
    r1 = t.commit_cpu()
    t.checkpoint()                      # scope B
    t.update([(rng.randbytes(32), b"b")])
    assert t.commit_cpu() != r1
    t.rollback()                        # drop B
    assert t.commit_cpu() == r1
    t.rollback()                        # drop A
    assert t.commit_cpu() == r0


def test_resident_lifecycle_fuzz():
    """Randomized end-to-end: interleaved updates, commits, checkpoints,
    rollbacks, and discards — the resident mirror must track a plain dict
    (verified via the full-rebuild oracle) through every commit."""
    rng = random.Random(31)
    state = _rand_items(rng, 600)
    dev = IncrementalTrie(sorted(state.items()))
    ex = _executor()
    assert _root_bytes(ex, dev.commit_resident(ex)) == \
        _full_rebuild_root(state)

    keys = list(state)
    # stack of state snapshots mirroring the trie's checkpoint stack
    snapshots = []
    for step in range(60):
        op = rng.random()
        if op < 0.5:  # update batch
            batch = []
            for _ in range(rng.randint(1, 40)):
                r = rng.random()
                if r < 0.4 and keys:
                    batch.append((rng.choice(keys), rng.randbytes(
                        rng.randint(1, 90))))
                elif r < 0.75:
                    k = rng.randbytes(32)
                    keys.append(k)
                    batch.append((k, rng.randbytes(40)))
                elif keys:
                    batch.append((rng.choice(keys), b""))
            dev.update(batch)
            for k, v in batch:
                if v:
                    state[k] = v
                else:
                    state.pop(k, None)
        elif op < 0.65:
            dev.checkpoint()
            snapshots.append(dict(state))
        elif op < 0.8 and snapshots:
            dev.rollback()
            state = snapshots.pop()
            keys = list(state)
        elif snapshots:
            dev.discard_checkpoint()
            snapshots.pop()
        else:
            dev.checkpoint()
            snapshots.append(dict(state))
        if rng.random() < 0.4:
            assert _root_bytes(ex, dev.commit_resident(ex)) == \
                _full_rebuild_root(state), f"fuzz step {step}"
    assert _root_bytes(ex, dev.commit_resident(ex)) == \
        _full_rebuild_root(state)


def test_plan_cache_warm_commits_hit_and_stay_exact():
    """Steady-state value-only churn repeats the same segment-shape
    tuple: the first shaped commit compiles (plan_cache miss + staging
    alloc), every later one must HIT — observable via the counters the
    phase-attribution work added — with roots still bit-exact (the hit
    path refills preallocated staging in place)."""
    from coreth_tpu.metrics import default_registry

    rng = random.Random(31)
    state = _rand_items(rng, 800)
    dev = IncrementalTrie(sorted(state.items()))
    cpu = IncrementalTrie(sorted(state.items()))
    ex = _executor()
    assert _root_bytes(ex, dev.commit_resident(ex)) == cpu.commit_cpu()

    hits = default_registry.counter("resident/plan_cache/hits")
    chosen = rng.sample(list(state), 64)  # fixed key set -> fixed shape
    h0 = hits.count()
    for rnd in range(4):
        batch = [(k, rng.randbytes(60)) for k in chosen]
        dev.update(batch)
        cpu.update(batch)
        assert _root_bytes(ex, dev.commit_resident(ex)) == cpu.commit_cpu(), \
            f"round {rnd} root mismatch"
    # round 0 may miss (new shape); rounds 1..3 repeat it exactly
    assert hits.count() - h0 >= 3
    assert ex.last_cache_hit


def test_plan_cache_shape_change_misses_then_recovers():
    """A structural burst (fresh inserts) changes the segment-shape key:
    the cache must MISS — no stale staging/compiled program may serve the
    new shape — and the new shape then warms up like any other."""
    from coreth_tpu.metrics import default_registry

    rng = random.Random(32)
    state = _rand_items(rng, 600)
    dev = IncrementalTrie(sorted(state.items()))
    cpu = IncrementalTrie(sorted(state.items()))
    ex = _executor()
    assert _root_bytes(ex, dev.commit_resident(ex)) == cpu.commit_cpu()

    chosen = rng.sample(list(state), 32)
    for _ in range(2):  # warm a value-only shape into the cache
        batch = [(k, rng.randbytes(40)) for k in chosen]
        dev.update(batch)
        cpu.update(batch)
        state.update(batch)
        assert _root_bytes(ex, dev.commit_resident(ex)) == cpu.commit_cpu()
    assert ex.last_cache_hit

    misses = default_registry.counter("resident/plan_cache/misses")
    m0 = misses.count()
    burst = [(rng.randbytes(32), rng.randbytes(50)) for _ in range(300)]
    dev.update(burst)
    cpu.update(burst)
    for k, v in burst:
        state[k] = v
    assert _root_bytes(ex, dev.commit_resident(ex)) == cpu.commit_cpu()
    assert not ex.last_cache_hit, "structural shape change must miss"
    assert misses.count() == m0 + 1
    assert _root_bytes(ex, ex.last_root) == _full_rebuild_root(state)


def test_threaded_commit_cpu_bit_exact_vs_single_thread():
    """The pooled native hasher (explicitly oversubscribed — CI may have
    one core) must be bit-exact vs the single-thread oracle across
    randomized churn, including the full-rebuild planner as a third
    opinion."""
    rng = random.Random(33)
    state = _rand_items(rng, 1500)
    mt = IncrementalTrie(sorted(state.items()))
    st = IncrementalTrie(sorted(state.items()))
    assert mt.commit_cpu(threads=8) == st.commit_cpu(threads=1)

    keys = list(state)
    for rnd in range(5):
        batch = []
        for _ in range(200):
            r = rng.random()
            if r < 0.4:
                batch.append((rng.choice(keys), rng.randbytes(60)))
            elif r < 0.75:
                k = rng.randbytes(32)
                keys.append(k)
                batch.append((k, rng.randbytes(45)))
            else:
                batch.append((rng.choice(keys), b""))
        mt.update(batch)
        st.update(batch)
        for k, v in batch:
            if v:
                state[k] = v
            else:
                state.pop(k, None)
        r_mt = mt.commit_cpu(threads=8)
        assert r_mt == st.commit_cpu(threads=1), f"round {rnd} mismatch"
        assert r_mt == _full_rebuild_root(state), f"round {rnd} vs rebuild"
