"""Branch-aware resident mirror: sibling competition, reorgs, finality
flushes — roots bit-exact vs independent full-rebuild oracles per branch
state (the verify/accept/reject semantics of core/blockchain.go +
plugin/evm/block.go driven against the device-resident trie)."""

import random

import pytest

from coreth_tpu.native.mpt import plan_from_items
from coreth_tpu.trie.resident_mirror import MirrorError, ResidentAccountMirror

@pytest.fixture(autouse=True)
def _pin_device_path(monkeypatch):
    # these oracle tests exercise the resident EXECUTOR; the CPU-backend
    # host fast path would silently bypass it on non-TPU test machines
    monkeypatch.setenv("CORETH_TPU_RESIDENT_HOST", "0")


def _rand_items(rng, n):
    return {rng.randbytes(32): rng.randbytes(rng.randint(1, 90))
            for _ in range(n)}


def _oracle(state: dict) -> bytes:
    return plan_from_items(sorted(state.items())).execute_cpu()


def _apply(state: dict, batch):
    out = dict(state)
    for k, v in batch:
        if v:
            out[k] = v
        else:
            out.pop(k, None)
    return out


def _batch(rng, state, n):
    keys = list(state)
    out = []
    for _ in range(n):
        r = rng.random()
        if r < 0.5 and keys:
            out.append((rng.choice(keys), rng.randbytes(60)))
        elif r < 0.85:
            out.append((rng.randbytes(32), rng.randbytes(40)))
        elif keys:
            out.append((rng.choice(keys), b""))
    return out


def test_linear_chain_with_finality_flush():
    rng = random.Random(41)
    genesis = _rand_items(rng, 400)
    m = ResidentAccountMirror(sorted(genesis.items()))
    assert m.root_of(m.GENESIS) == _oracle(genesis)

    state = genesis
    m.TIP_BUFFER = 4  # window semantics, not size: keep the test light
    n_blocks = m.TIP_BUFFER + 6
    roots = {}
    for i in range(n_blocks):
        h = bytes([i + 1]) * 32
        parent = m.head
        batch = _batch(rng, state, 30)
        state = _apply(state, batch)
        root = m.verify(parent, h, batch)
        assert root == _oracle(state), f"block {i}"
        roots[h] = root
        m.accept(h)
        assert m.head == h
        # steady state: finalized history deeper than the tip buffer
        # flushes; the stack stays a rolling TIP_BUFFER+1 window
        assert len(m._applied) <= m.TIP_BUFFER + 1
    # blocks beyond the window are forgotten, recent ones retained
    assert m.root_of(bytes([1]) * 32) is None
    recent = bytes([n_blocks - 1]) * 32  # one behind head
    assert m.root_of(recent) == roots[recent]
    # and their state is still readable (tip-buffer rewind)
    assert m.read(roots[recent], next(iter(state))) is not None


def test_sibling_competition_and_reorg():
    """A and B verify against the same parent; B accepts, A rejects —
    the mirror must serve both roots during competition and land on B."""
    rng = random.Random(42)
    genesis = _rand_items(rng, 300)
    m = ResidentAccountMirror(sorted(genesis.items()))
    state = genesis

    # common block 1
    b1 = b"\x01" * 32
    batch1 = _batch(rng, state, 25)
    state1 = _apply(state, batch1)
    assert m.verify(m.GENESIS, b1, batch1) == _oracle(state1)

    # siblings at height 2
    a, b = b"\x0a" * 32, b"\x0b" * 32
    batch_a = _batch(rng, state1, 20)
    batch_b = _batch(rng, state1, 20)
    state_a = _apply(state1, batch_a)
    state_b = _apply(state1, batch_b)
    assert m.verify(b1, a, batch_a) == _oracle(state_a)
    # verifying B forces a rewind of A and replay onto b1
    assert m.verify(b1, b, batch_b) == _oracle(state_b)
    # and a child on top of the LOSING branch still verifies (rewind back)
    a2 = b"\x2a" * 32
    batch_a2 = _batch(rng, state_a, 10)
    state_a2 = _apply(state_a, batch_a2)
    assert m.verify(a, a2, batch_a2) == _oracle(state_a2)

    # consensus decides: B accepts, A (and its child) reject
    assert m.verify(b1, b, batch_b) == _oracle(state_b)  # switch back to B
    m.accept(b1)
    m.accept(b)
    m.reject(a)  # A was rewound off already; its records drop
    assert m.root_of(a) is None and m.root_of(a2) is None

    # the chain continues on B
    b3 = b"\x03" * 32
    batch3 = _batch(rng, state_b, 15)
    state3 = _apply(state_b, batch3)
    assert m.verify(b, b3, batch3) == _oracle(state3)


def test_reject_applied_branch_rewinds():
    rng = random.Random(43)
    genesis = _rand_items(rng, 200)
    m = ResidentAccountMirror(sorted(genesis.items()))
    b1, b2 = b"\x01" * 32, b"\x02" * 32
    batch1 = _batch(rng, genesis, 20)
    s1 = _apply(genesis, batch1)
    m.verify(m.GENESIS, b1, batch1)
    batch2 = _batch(rng, s1, 20)
    m.verify(b1, b2, batch2)
    # rejecting b1 rewinds b2 with it
    m.reject(b1)
    assert m.head == m.GENESIS
    assert m.root_of(b2) is None
    # and the mirror still commits correctly afterwards
    b1b = b"\x11" * 32
    batch1b = _batch(rng, genesis, 10)
    assert m.verify(m.GENESIS, b1b, batch1b) == \
        _oracle(_apply(genesis, batch1b))


def test_flushed_history_is_final():
    """Below the tip buffer, finalized history loses its records: a
    sibling branching there is refused (within the buffer, accepted
    blocks stay rewindable for reads — reference tip-buffer semantics)."""
    rng = random.Random(44)
    genesis = _rand_items(rng, 100)
    m = ResidentAccountMirror(sorted(genesis.items()))
    m.TIP_BUFFER = 4  # window semantics, not size: keep the test light
    state = genesis
    for i in range(m.TIP_BUFFER + 2):
        h = bytes([i + 1]) * 32
        batch = _batch(rng, state, 10)
        state = _apply(state, batch)
        m.verify(m.head, h, batch)
        m.accept(h)
    # genesis is beyond the retained window now
    with pytest.raises(MirrorError, match="unknown parent"):
        m.verify(m.GENESIS, b"\x0f" * 32, [])
    # a sibling of a RETAINED accepted block applies mechanically
    # (consensus will reject it; the mirror just serves its state)
    parent = bytes([m.TIP_BUFFER]) * 32
    sib = b"\xee" * 32  # distinct from every bytes([i+1])*32 block hash
    sib_root = m.verify(parent, sib, [])
    assert sib_root == m.root_of(parent)
    m.reject(sib)


def test_failed_export_write_degrades_to_full_image():
    """The native delta export clears its changed-node marks as it
    walks, so a failed disk write must NOT lose those nodes: the next
    export degrades to a full image that supersedes the lost delta."""
    from coreth_tpu.ethdb import MemoryDB

    rng = random.Random(46)
    genesis = _rand_items(rng, 120)
    m = ResidentAccountMirror(sorted(genesis.items()))
    db = MemoryDB()
    n0 = m.export_to(db)
    assert n0 > 0

    keys = list(genesis)
    m.verify(m.head, b"\x01" * 32, [(keys[0], b"changed")])

    class FailingBatch:
        def put(self, k, v):
            pass

        def write(self):
            raise OSError("disk full")

    class FailAtWrite:
        def new_batch(self):
            return FailingBatch()

    with pytest.raises(OSError):
        m.export_to(FailAtWrite())
    # repair: the next (successful) export is a FULL image — every node
    # of the current tree lands, including the ones whose marks the
    # failed export consumed
    db2 = MemoryDB()
    n_repair = m.export_to(db2)
    assert n_repair >= n0, (n_repair, n0)
    # and the current root's node is present in the repaired image
    root = m.root_of(b"\x01" * 32)
    assert db2.get(root) is not None
    # afterwards deltas are trusted again (nothing changed -> no-op)
    assert m.export_to(MemoryDB()) == 0


def test_unknown_parent_rejected():
    rng = random.Random(45)
    m = ResidentAccountMirror(sorted(_rand_items(rng, 50).items()))
    with pytest.raises(MirrorError, match="unknown parent"):
        m.verify(b"\x77" * 32, b"\x78" * 32, [])
