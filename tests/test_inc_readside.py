"""IncrementalTrie read/persistence seams: get(), absorb_store(),
export_nodes().

These are the chain-adapter building blocks (trie/resident_mirror.py):
reads served straight from the native trie (reference trie/trie.go:87
Get), and the 4096-interval disk flush exporting (digest, RLP) node
pairs after a device-store sync (reference trie/triedb/hashdb Commit via
core/state_manager.go:153).
"""

import random

import numpy as np
import pytest

from coreth_tpu.crypto import keccak256
from coreth_tpu.native.mpt import IncrementalTrie

def _items(rng, n):
    d = {rng.randbytes(32): rng.randbytes(rng.randint(1, 90))
         for _ in range(n)}
    return d


def test_get_present_and_absent():
    rng = random.Random(7)
    state = _items(rng, 300)
    t = IncrementalTrie(sorted(state.items()))
    for k, v in list(state.items())[:50]:
        assert t.get(k) == v
    for _ in range(20):
        assert t.get(rng.randbytes(32)) is None


def test_get_tracks_updates_and_deletes():
    rng = random.Random(8)
    state = _items(rng, 200)
    t = IncrementalTrie(sorted(state.items()))
    keys = list(state)
    t.update([(keys[0], b"replaced"), (keys[1], b"")])
    assert t.get(keys[0]) == b"replaced"
    assert t.get(keys[1]) is None
    # values longer than the fast-path buffer (128 B) still round-trip
    big = bytes(range(256)) * 2
    t.update([(keys[2], big)])
    assert t.get(keys[2]) == big


def test_export_nodes_digests_match_rlp():
    rng = random.Random(9)
    state = _items(rng, 400)
    t = IncrementalTrie(sorted(state.items()))
    root = t.commit_cpu()
    digs, blob, off = t.export_nodes()
    assert digs.shape[0] > 0
    for i in range(digs.shape[0]):
        enc = blob[int(off[i]):int(off[i + 1])]
        assert len(enc) >= 32
        assert keccak256(enc) == digs[i].tobytes()
    assert any(digs[i].tobytes() == root for i in range(digs.shape[0]))


def test_export_refuses_dirty_trie():
    rng = random.Random(10)
    state = _items(rng, 50)
    t = IncrementalTrie(sorted(state.items()))
    t.commit_cpu()
    t.update([(next(iter(state)), b"dirty")])
    with pytest.raises(RuntimeError):
        t.export_nodes()


def test_exported_nodes_resolve_from_root():
    """The exported node set is a complete hashdb image: walking from the
    root digest through hash references reaches every exported node."""
    rng = random.Random(11)
    state = _items(rng, 300)
    t = IncrementalTrie(sorted(state.items()))
    root = t.commit_cpu()
    digs, blob, off = t.export_nodes()
    db = {digs[i].tobytes(): blob[int(off[i]):int(off[i + 1])]
          for i in range(digs.shape[0])}

    from coreth_tpu import rlp

    seen = set()

    def walk(ref):
        if ref not in db or ref in seen:
            return
        seen.add(ref)
        items = rlp.decode(db[ref])
        if len(items) == 17:
            children = items[:16]
        else:
            children = [items[1]]
        for c in children:
            if isinstance(c, bytes) and len(c) == 32:
                walk(c)
            elif isinstance(c, list):
                # embedded node: its hashed children still need visits
                for cc in c[:16] if len(c) == 17 else [c[1]]:
                    if isinstance(cc, bytes) and len(cc) == 32:
                        walk(cc)

    walk(root)
    assert seen == set(db), "every exported node reachable from the root"


def test_export_delta_overlay_completeness():
    """Delta exports only nodes re-hashed since the previous export, and
    disk = (previous image + delta) is a complete hashdb overlay for the
    new root (reference trie/triedb/hashdb Commit semantics)."""
    rng = random.Random(13)
    state = _items(rng, 400)
    t = IncrementalTrie(sorted(state.items()))
    t.commit_cpu()
    d0, b0, o0 = t.export_nodes()  # full image clears pending deltas
    assert t.export_nodes(delta=True)[0].shape[0] == 0

    keys = list(state)
    t.update([(keys[i], rng.randbytes(40)) for i in range(0, 60, 2)])
    root2 = t.commit_cpu()
    d1, b1, o1 = t.export_nodes(delta=True)
    assert 0 < d1.shape[0] < d0.shape[0]
    # digest-exact
    for i in range(d1.shape[0]):
        assert keccak256(b1[int(o1[i]):int(o1[i + 1])]) == d1[i].tobytes()
    # overlay completeness: walk root2 through old image + delta
    db = {d0[i].tobytes(): b0[int(o0[i]):int(o0[i + 1])]
          for i in range(d0.shape[0])}
    db.update({d1[i].tobytes(): b1[int(o1[i]):int(o1[i + 1])]
               for i in range(d1.shape[0])})

    from coreth_tpu import rlp

    def refs_of(items):
        """Child references of a decoded node; a LEAF's second item is a
        value (which can itself be 32 bytes long), not a reference —
        the hex-prefix flag (0x20) distinguishes it."""
        if len(items) == 17:
            return items[:16]
        if items[0] and items[0][0] & 0x20:
            return []  # leaf
        return [items[1]]

    def walk(ref):
        assert ref in db, "missing node in overlay"
        stack = list(refs_of(rlp.decode(db[ref])))
        while stack:
            c = stack.pop()
            if isinstance(c, bytes) and len(c) == 32:
                walk(c)
            elif isinstance(c, list):
                stack.extend(refs_of(c))

    walk(root2)
    # a second delta is empty until something changes again
    assert t.export_nodes(delta=True)[0].shape[0] == 0


def test_export_delta_after_rollback_stays_consistent():
    """Rollback replays through the updater, so rolled-back paths re-hash
    and re-export: the overlay still resolves the restored root."""
    rng = random.Random(14)
    state = _items(rng, 200)
    t = IncrementalTrie(sorted(state.items()))
    root1 = t.commit_cpu()
    d0, b0, o0 = t.export_nodes()
    keys = list(state)
    t.checkpoint()
    t.update([(keys[0], b"speculative"), (keys[1], b"")])
    t.commit_cpu()
    t.rollback()
    root_back = t.commit_cpu()
    assert root_back == root1
    d1, b1, o1 = t.export_nodes(delta=True)
    for i in range(d1.shape[0]):
        enc = b1[int(o1[i]):int(o1[i + 1])]
        assert keccak256(enc) == d1[i].tobytes()


def test_absorb_store_syncs_resident_digests():
    rng = random.Random(12)
    state = _items(rng, 250)
    oracle = IncrementalTrie(sorted(state.items()))
    t = IncrementalTrie(sorted(state.items()))

    from coreth_tpu.ops.keccak_resident import ResidentExecutor

    ex = ResidentExecutor()
    root = ex.root_bytes(t.commit_resident(ex))
    assert root == oracle.commit_cpu()

    keys = list(state)
    ups = [(keys[i], rng.randbytes(40)) for i in range(0, 120, 3)]
    oracle.update(ups)
    t.update(ups)
    root2 = ex.root_bytes(t.commit_resident(ex))
    assert root2 == oracle.commit_cpu()

    # sync point: digests return to the host cache; the export is a
    # bit-exact hashdb image of the resident trie
    t.absorb_store(np.asarray(ex.store))
    digs, blob, off = t.export_nodes()
    for i in range(digs.shape[0]):
        enc = blob[int(off[i]):int(off[i + 1])]
        assert keccak256(enc) == digs[i].tobytes()
    assert any(digs[i].tobytes() == root2 for i in range(digs.shape[0]))
    # reads unaffected by commits
    assert t.get(ups[0][0]) == ups[0][1]
