"""IncrementalTrie (native/mpt_inc.cpp) parity vs the Python trie oracle.

The incremental planner must produce bit-exact roots through arbitrary
insert/replace/delete sequences while re-hashing ONLY dirty subtrees —
the reference's warm-trie semantics (trie/trie.go:573-626) on the
planned-executor seam.
"""

import random


from coreth_tpu.native.mpt import EMPTY_ROOT, IncrementalTrie
from coreth_tpu.trie.hasher import Hasher
from coreth_tpu.trie.trie import Trie

def oracle_root(items: dict) -> bytes:
    t = Trie()
    for k, v in sorted(items.items()):
        t.update(k, v)
    if t.root is None:
        return EMPTY_ROOT
    h, _ = Hasher().hash(t.root, True)
    return bytes(h)


def test_initial_commit_matches_oracle():
    rng = random.Random(1)
    items = {rng.randbytes(32): rng.randbytes(rng.randint(1, 90))
             for _ in range(500)}
    it = IncrementalTrie(sorted(items.items()))
    assert it.commit_cpu() == oracle_root(items)


def test_incremental_updates_and_deletes():
    rng = random.Random(2)
    items = {rng.randbytes(32): rng.randbytes(rng.randint(1, 90))
             for _ in range(400)}
    it = IncrementalTrie(sorted(items.items()))
    assert it.commit_cpu() == oracle_root(items)

    keys = list(items)
    for step in range(6):
        batch = []
        for _ in range(40):  # replace existing
            k = rng.choice(keys)
            v = rng.randbytes(rng.randint(1, 90))
            items[k] = v
            batch.append((k, v))
        for _ in range(15):  # insert new
            k = rng.randbytes(32)
            v = rng.randbytes(rng.randint(1, 90))
            items[k] = v
            keys.append(k)
            batch.append((k, v))
        for _ in range(12):  # delete
            k = rng.choice(keys)
            if k in items:
                del items[k]
                batch.append((k, b""))
        it.update(batch)
        assert it.commit_cpu() == oracle_root(items), f"step {step}"


def test_dirty_set_is_small_for_small_churn():
    rng = random.Random(3)
    items = {rng.randbytes(32): rng.randbytes(60) for _ in range(4000)}
    it = IncrementalTrie(sorted(items.items()))
    it.commit_cpu()
    total = it.num_nodes

    batch = []
    for k in rng.sample(list(items), 20):
        v = rng.randbytes(60)
        items[k] = v
        batch.append((k, v))
    it.update(batch)
    root = it.commit_cpu()
    dirty, _ = it.dirty_stats()
    assert root == oracle_root(items)
    # 20 touched leaves on a 4000-leaf trie: dirty must be a sliver
    assert dirty < total * 0.1, (dirty, total)
    assert dirty >= 20


def test_device_commit_parity():
    """The mini-plan drains through the SAME PlannedCommit executor the
    chain uses; digests absorb back into the native cache."""
    rng = random.Random(4)
    items = {rng.randbytes(32): rng.randbytes(rng.randint(40, 90))
             for _ in range(300)}
    it = IncrementalTrie(sorted(items.items()))
    assert it.commit_device() == oracle_root(items)

    # churn a few leaves; device commit again (incremental this time)
    batch = []
    for k in rng.sample(list(items), 25):
        v = rng.randbytes(rng.randint(40, 90))
        items[k] = v
        batch.append((k, v))
    new_key = rng.randbytes(32)
    items[new_key] = b"\x42" * 50
    batch.append((new_key, items[new_key]))
    it.update(batch)
    assert it.commit_device() == oracle_root(items)
    dirty, _ = it.dirty_stats()
    assert dirty < it.num_nodes


def test_mixed_cpu_device_commits_share_cache():
    rng = random.Random(5)
    items = {rng.randbytes(32): rng.randbytes(50) for _ in range(200)}
    it = IncrementalTrie(sorted(items.items()))
    assert it.commit_cpu() == oracle_root(items)
    batch = []
    for k in rng.sample(list(items), 10):
        items[k] = rng.randbytes(50)
        batch.append((k, items[k]))
    it.update(batch)
    assert it.commit_device() == oracle_root(items)
    batch = []
    for k in rng.sample(list(items), 10):
        del items[k]
        batch.append((k, b""))
    it.update(batch)
    assert it.commit_cpu() == oracle_root(items)


def test_empty_and_single():
    it = IncrementalTrie()
    assert it.root() == EMPTY_ROOT
    it.update([(b"\x55" * 32, b"hello-world-value-123456789012345678")])
    assert it.commit_cpu() == oracle_root(
        {b"\x55" * 32: b"hello-world-value-123456789012345678"})
    it.update([(b"\x55" * 32, b"")])
    assert it.commit_cpu() == EMPTY_ROOT


def test_noop_update_keeps_clean():
    rng = random.Random(6)
    items = {rng.randbytes(32): rng.randbytes(50) for _ in range(100)}
    it = IncrementalTrie(sorted(items.items()))
    r1 = it.commit_cpu()
    k = next(iter(items))
    changed = it.update([(k, items[k])])  # same value: no-op
    assert changed == 0
    assert it.commit_cpu() == r1
