"""Cross-commit device pipelining + template residency: pipelined
commits must be bit-exact vs the serial mirror, the C++ host executor
oracle, and the pure-Python reference trie, across accept/reject/reorg
interleavings; a mid-pipeline device wedge must land the whole in-flight
window on the host with identical roots (the PR 6 soft landing, now
window-deep); the periodic spot-check must settle the window before
reading the device store back."""

import random
import threading

import pytest

from coreth_tpu import fault
from coreth_tpu.metrics import default_registry
from coreth_tpu.native.mpt import plan_from_items
from coreth_tpu.trie.resident_mirror import MirrorError, ResidentAccountMirror
from coreth_tpu.trie.trie import Trie

@pytest.fixture(autouse=True)
def _pin_device_path(monkeypatch):
    # these oracle tests exercise the resident EXECUTOR; the CPU-backend
    # host fast path would silently bypass it on non-TPU test machines
    monkeypatch.setenv("CORETH_TPU_RESIDENT_HOST", "0")


@pytest.fixture(autouse=True)
def _clear_failpoints():
    yield
    fault.clear_all()


def _rand_items(rng, n):
    return {rng.randbytes(32): rng.randbytes(rng.randint(1, 90))
            for _ in range(n)}


def _oracle(state: dict) -> bytes:
    return plan_from_items(sorted(state.items())).execute_cpu()


def _py_oracle(state: dict) -> bytes:
    t = Trie()
    for k, v in sorted(state.items()):
        t.update(k, v)
    return t.hash()


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


def _hash(i: int) -> bytes:
    return bytes([i & 0xFF, (i >> 8) & 0xFF]) * 16


# ---- bit-exactness: pipelined vs serial vs both oracles -----------------


@pytest.mark.parametrize("depth", [1, 2])
def test_pipelined_linear_chain_matches_oracles(depth):
    """Every pipelined commit's deferred device-root compare passes when
    the header root is truthful, and the roots equal the C++ host
    executor oracle at every block plus the pure-Python reference trie
    at the endpoints."""
    rng = random.Random(1300 + depth)
    genesis = _rand_items(rng, 120)
    m = ResidentAccountMirror(sorted(genesis.items()),
                              pipeline_depth=depth)
    assert not m.host_mode and m._pipelining()
    assert m.root_of(m.GENESIS) == _oracle(genesis)
    assert m.root_of(m.GENESIS) == _py_oracle(genesis)

    state = genesis
    parent = m.GENESIS
    for i in range(1, 7):
        h = _hash(i)
        batch = _batch(rng, state, 10)
        state = _apply(state, batch)
        expected = _oracle(state)
        root = m.verify(parent, h, batch, expected_root=expected)
        assert root == expected, f"block {i}"
        if i % 3 == 0:
            m.accept(h)  # drains up to h; later dispatches keep flying
        parent = h
    # final settle: the full window's deferred compares must all pass
    m._drain_pipeline()
    assert m._inflight == []
    assert m.root_of(parent) == _oracle(state) == _py_oracle(state)
    # reads through the settled head agree with the model
    for k in list(state)[:10]:
        assert m.read(m.root_of(parent), k) == state[k]


@pytest.mark.parametrize("depth", [2])
def test_pipelined_fuzz_interleaved_lifecycle(depth, monkeypatch):
    """Seeded fuzz over an N-commit chain with interleaved
    accept/reject/reorg: a pipelined device mirror and a serial host
    twin (the PR 6 oracle path) fed the identical op sequence stay
    root-identical at every step, both matching the host-executor
    oracle. The pipelined mirror's own deferred compares enforce
    device-root == header-root at every drain on top."""
    rng = random.Random(7700 + depth)
    genesis = _rand_items(rng, 100)
    # the serial twin runs host-mode: same lifecycle machinery, CPU
    # hashing — one device executor in the test, not two
    monkeypatch.setenv("CORETH_TPU_RESIDENT_HOST", "1")
    serial = ResidentAccountMirror(sorted(genesis.items()))
    monkeypatch.setenv("CORETH_TPU_RESIDENT_HOST", "0")
    pipe = ResidentAccountMirror(sorted(genesis.items()),
                                 pipeline_depth=depth)
    assert pipe._pipelining() and not serial._pipelining()

    states = {pipe.GENESIS: genesis}
    children = {}  # parent -> verified child hashes still alive
    alive = [pipe.GENESIS]
    nxt = 1
    for step in range(16):
        r = rng.random()
        if r < 0.60 or len(alive) == 1:
            # verify a new block on a random alive parent (non-head
            # parents exercise the reorg/branch-switch drain barrier)
            parent = rng.choice(alive)
            h = _hash(nxt)
            nxt += 1
            batch = _batch(rng, states[parent], 8)
            states[h] = _apply(states[parent], batch)
            expected = _oracle(states[h])
            got_p = pipe.verify(parent, h, batch, expected_root=expected)
            got_s = serial.verify(parent, h, batch)
            assert got_p == got_s == expected, f"step {step}"
            alive.append(h)
            children.setdefault(parent, []).append(h)
        elif r < 0.80:
            # reject a random non-genesis leaf (no verified children)
            leaves = [h for h in alive[1:] if not children.get(h)]
            if not leaves:
                continue
            h = rng.choice(leaves)
            try:
                pipe.reject(h)
                serial.reject(h)
            except MirrorError:
                continue  # accepted meanwhile; same answer both sides
            alive.remove(h)
            for c in children.values():
                if h in c:
                    c.remove(h)
        else:
            # accept the oldest unaccepted block on the canonical spine
            h = alive[1] if len(alive) > 1 else alive[0]
            if h in pipe._accepted:
                continue
            pipe.accept(h)
            serial.accept(h)
    pipe._drain_pipeline()
    assert pipe._inflight == []
    assert pipe.head == serial.head or (
        pipe.root_of(pipe.head) == serial.root_of(serial.head))
    for h in alive:
        assert pipe.root_of(h) == serial.root_of(h) == _oracle(states[h])


def test_pipeline_divergence_rewinds_and_recovers():
    """A commit recorded under a WRONG header root fails its deferred
    compare at the drain: the offending block (and its in-flight
    descendants) rewind, MirrorError surfaces, and the mirror keeps
    serving the surviving prefix with correct roots."""
    rng = random.Random(99)
    genesis = _rand_items(rng, 100)
    m = ResidentAccountMirror(sorted(genesis.items()), pipeline_depth=2)
    state = genesis

    b1 = _batch(rng, state, 10)
    s1 = _apply(state, b1)
    r1 = m.verify(m.GENESIS, _hash(1), b1, expected_root=_oracle(s1))

    b2 = _batch(rng, s1, 10)
    s2 = _apply(s1, b2)
    bogus = b"\xde\xad" * 16
    assert m.verify(_hash(1), _hash(2), b2, expected_root=bogus) == bogus

    before = default_registry.counter(
        "state/resident/pipeline_divergences").count()
    with pytest.raises(MirrorError):
        m._drain_pipeline()
    assert default_registry.counter(
        "state/resident/pipeline_divergences").count() == before + 1
    assert m._inflight == []
    # block 1 survived (its compare passed before the divergence);
    # block 2 is gone and the device image is back at block 1's state
    assert m.head == _hash(1)
    assert m.root_of(_hash(1)) == r1 == _oracle(s1)
    assert m.root_of(_hash(2)) is None
    assert not m.host_mode  # divergence is per-block, not a takeover
    # the same block re-verifies fine with its true root
    assert m.verify(_hash(1), _hash(2), b2,
                    expected_root=_oracle(s2)) == _oracle(s2)
    m._drain_pipeline()
    assert m.root_of(_hash(2)) == _oracle(s2)


# ---- spot-check vs in-flight window (the race regression) ---------------


def test_spot_check_settles_inflight_window_first():
    """Regression: spot_check used to read the device store back while
    pipelined commits were still in flight, cross-checking roots that
    had never been compared. It must drain (settling the deferred
    compares, per-block attribution) before touching the store."""
    rng = random.Random(55)
    genesis = _rand_items(rng, 100)
    m = ResidentAccountMirror(sorted(genesis.items()), pipeline_depth=2)
    state, parent = genesis, m.GENESIS
    for i in range(1, 3):
        batch = _batch(rng, state, 8)
        state = _apply(state, batch)
        m.verify(parent, _hash(i), batch, expected_root=_oracle(state))
        parent = _hash(i)
    assert len(m._inflight) > 0  # the window is genuinely populated
    assert m.spot_check() is True
    assert m._inflight == []  # drained, then cross-checked
    assert m.root_of(parent) == _oracle(state)


def test_spot_check_reports_inflight_divergence_as_failure():
    """If a block in the window was wrong, spot_check must report False
    (the chain quarantines) instead of mis-attributing the divergence
    to the device store image."""
    rng = random.Random(56)
    genesis = _rand_items(rng, 80)
    m = ResidentAccountMirror(sorted(genesis.items()), pipeline_depth=2)
    b1 = _batch(rng, genesis, 8)
    m.verify(m.GENESIS, _hash(1), b1, expected_root=b"\xbb" * 32)
    before = default_registry.counter(
        "state/resident/spot_check_failures").count()
    assert m.spot_check() is False
    assert default_registry.counter(
        "state/resident/spot_check_failures").count() == before + 1
    assert m._inflight == []


# ---- failpoint drill: device hang mid-pipeline --------------------------


def test_mid_pipeline_hang_drains_on_host_bit_exact():
    """Deterministic drill (resident/before_absorb = hang): with two
    commits in flight, the device stops answering. The drain must take
    over on the host and recompute the ENTIRE window there, bit-exact
    against each block's header root, so callers never see the wedge."""
    rng = random.Random(77)
    genesis = _rand_items(rng, 100)
    # generous watchdog while XLA compiles the commit programs; tightened
    # right before the hang is armed so only the drill trips it
    m = ResidentAccountMirror(sorted(genesis.items()), pipeline_depth=2,
                              device_timeout=60.0)
    reasons = []
    m.on_takeover = reasons.append

    state, parent, expect = genesis, m.GENESIS, {}
    for i in range(1, 3):
        batch = _batch(rng, state, 12)
        state = _apply(state, batch)
        expect[_hash(i)] = _oracle(state)
        root = m.verify(parent, _hash(i), batch,
                        expected_root=expect[_hash(i)])
        assert root == expect[_hash(i)]
        parent = _hash(i)
    assert len(m._inflight) == 2

    m.device_timeout = 0.4
    fault.set_failpoint("resident/before_absorb", "hang")
    m.accept(_hash(1))  # drain hits the parked resolve -> wedge
    fault.clear_all()

    assert m.host_mode, "wedge mid-drain must land on the host"
    assert reasons, "on_takeover hook never fired"
    assert m._inflight == []
    # the host recompute of the window matched every header root
    for h, r in expect.items():
        assert m.root_of(h) == r
    assert m.head == _hash(2)
    # life goes on, CPU-resident: further commits stay oracle-exact
    batch = _batch(rng, state, 12)
    state = _apply(state, batch)
    assert m.verify(parent, _hash(3), batch) == _oracle(state)


def test_dispatch_wedge_lands_current_block_on_host(monkeypatch):
    """A wedge at DISPATCH time (not drain): the current block's open
    scope sits on top of the window's scopes. The mirror must fold it
    away, land the window, then re-apply and commit this block on the
    host — returning its true root."""
    rng = random.Random(78)
    genesis = _rand_items(rng, 90)
    m = ResidentAccountMirror(sorted(genesis.items()), pipeline_depth=2,
                              device_timeout=60.0)
    b1 = _batch(rng, genesis, 10)
    s1 = _apply(genesis, b1)
    m.verify(m.GENESIS, _hash(1), b1, expected_root=_oracle(s1))

    # wedge the NEXT dispatch: the device never answers the program,
    # and the window's pending sync parks on the failpoint
    monkeypatch.setattr(type(m.ex), "run",
                        lambda self, export: threading.Event().wait())
    m.device_timeout = 0.4
    fault.set_failpoint("resident/before_absorb", "hang")
    b2 = _batch(rng, s1, 10)
    s2 = _apply(s1, b2)
    root = m.verify(_hash(1), _hash(2), b2, expected_root=_oracle(s2))
    fault.clear_all()
    assert root == _oracle(s2)
    assert m.host_mode and m._inflight == []
    assert m.root_of(_hash(1)) == _oracle(s1)


# ---- template residency -------------------------------------------------


def test_template_residency_parity_and_instant_export():
    """Template commits (device re-zeroes/re-patches resident rows;
    uploads carry only fresh leaf content) produce bit-exact roots, and
    the per-commit digest absorb keeps the host cache warm: root() and
    spot_check work without a store readback."""
    rng = random.Random(31)
    genesis = _rand_items(rng, 120)
    m = ResidentAccountMirror(sorted(genesis.items()),
                              template_residency=True, pipeline_depth=2)
    assert m.template
    assert m.pipeline_depth == 0  # the absorb IS a sync; no pipelining
    assert not m._pipelining()
    assert m.root_of(m.GENESIS) == _oracle(genesis) == _py_oracle(genesis)

    state, parent = genesis, m.GENESIS
    for i in range(1, 5):
        batch = _batch(rng, state, 10)
        state = _apply(state, batch)
        # expected_root given but template forces the serial path
        root = m.verify(parent, _hash(i), batch,
                        expected_root=_oracle(state))
        assert root == _oracle(state), f"block {i}"
        parent = _hash(i)
    assert _py_oracle(state) == m.root_of(parent)
    # absorb kept the host digest cache current: root() is serviceable
    # without any device readback
    assert m.trie.root() == m.root_of(parent)
    assert m.spot_check() is True
    for k in list(state)[:8]:
        assert m.read(m.root_of(parent), k) == state[k]


def test_template_reorg_and_reject():
    """Branch switches under template residency: rollback + replay land
    on oracle-exact roots (replayed template commits re-absorb)."""
    rng = random.Random(32)
    genesis = _rand_items(rng, 100)
    m = ResidentAccountMirror(sorted(genesis.items()),
                              template_residency=True)
    b1 = _batch(rng, genesis, 10)
    s1 = _apply(genesis, b1)
    m.verify(m.GENESIS, _hash(1), b1)
    # sibling off genesis -> rewind through block 1, then replay back
    b2 = _batch(rng, genesis, 10)
    s2 = _apply(genesis, b2)
    assert m.verify(m.GENESIS, _hash(2), b2) == _oracle(s2)
    assert m.root_of(_hash(1)) == _oracle(s1)
    m.reject(_hash(2))
    b3 = _batch(rng, s1, 10)
    s3 = _apply(s1, b3)
    assert m.verify(_hash(1), _hash(3), b3) == _oracle(s3)
    assert m.trie.root() == _oracle(s3)


def test_template_wedge_takeover_drops_template_mode():
    """A wedged template commit takes over on the host; template mode
    ends with residency (host commits absorb by construction)."""
    rng = random.Random(33)
    genesis = _rand_items(rng, 100)
    m = ResidentAccountMirror(sorted(genesis.items()),
                              template_residency=True, device_timeout=60.0)
    assert m.template
    m.device_timeout = 0.4
    fault.set_failpoint("resident/before_absorb", "hang")
    b1 = _batch(rng, genesis, 10)
    s1 = _apply(genesis, b1)
    root = m.verify(m.GENESIS, _hash(1), b1)
    fault.clear_all()
    assert root == _oracle(s1)
    assert m.host_mode and not m.template
    b2 = _batch(rng, s1, 10)
    s2 = _apply(s1, b2)
    assert m.verify(_hash(1), _hash(2), b2) == _oracle(s2)


# ---- accounting: h2d bytes + overlap fraction ---------------------------


def test_h2d_counter_and_overlap_accounting():
    rng = random.Random(61)
    genesis = _rand_items(rng, 120)
    c = default_registry.counter("resident/h2d_bytes")
    before = c.count()
    m = ResidentAccountMirror(sorted(genesis.items()), pipeline_depth=1)
    assert c.count() > before  # the genesis commit uploaded something
    state, parent = genesis, m.GENESIS
    mid = c.count()
    for i in range(1, 4):
        batch = _batch(rng, state, 10)
        state = _apply(state, batch)
        m.verify(parent, _hash(i), batch, expected_root=_oracle(state))
        parent = _hash(i)
    m._drain_pipeline()
    assert c.count() > mid
    # at least one drained entry recorded its overlap (any value in
    # [0,1] is legitimate on a CPU stand-in backend)
    assert 0.0 <= m.last_overlap_fraction <= 1.0
    assert 0.0 <= default_registry.gauge(
        "resident/overlap_fraction").value() <= 1.0


def test_chain_flight_record_surfaces_pipeline_metrics():
    """Chain integration: with resident-pipeline-depth on, every block's
    flight record carries its exact h2d upload delta and (once the first
    drain lands) the measured overlap fraction — the per-block data
    debug_blockFlightRecord serves."""
    from coreth_tpu import params
    from coreth_tpu.consensus.dummy import new_dummy_engine
    from coreth_tpu.core.blockchain import BlockChain, CacheConfig
    from coreth_tpu.core.chain_makers import generate_chain
    from coreth_tpu.core.genesis import Genesis, GenesisAccount
    from coreth_tpu.core.types import Signer, Transaction
    from coreth_tpu.crypto.secp256k1 import priv_to_address
    from coreth_tpu.ethdb import MemoryDB
    from coreth_tpu.state.database import Database
    from coreth_tpu.trie.triedb import TrieDatabase

    key = b"\x11" * 32
    addr = priv_to_address(key)

    def make(resident, depth=0):
        diskdb = MemoryDB()
        return BlockChain(
            diskdb,
            CacheConfig(pruning=True, resident_account_trie=resident,
                        resident_prefer_host=False,
                        resident_pipeline_depth=depth),
            params.TEST_CHAIN_CONFIG,
            Genesis(config=params.TEST_CHAIN_CONFIG,
                    gas_limit=params.CORTINA_GAS_LIMIT,
                    alloc={addr: GenesisAccount(balance=10**22)}),
            new_dummy_engine(),
            state_database=Database(TrieDatabase(diskdb)),
        )

    signer = Signer(43112)

    def gen(i, bg):
        bf = bg.base_fee() or params.APRICOT_PHASE3_INITIAL_BASE_FEE
        tx = Transaction(type=2, chain_id=43112, nonce=i, max_fee=bf * 2,
                         max_priority_fee=0, gas=21000,
                         to=b"\x22" * 20, value=1000 + i)
        bg.add_tx(signer.sign(tx, key))

    default = make(resident=False)
    blocks, _ = generate_chain(default.config, default.current_block,
                               default.engine, default.state_database,
                               4, gen=gen)
    chain = make(resident=True, depth=1)
    try:
        assert chain.mirror is not None and chain.mirror.pipeline_depth == 1
        for b in blocks:
            chain.insert_block(b)  # raises on any root mismatch
        recs = chain.flight_recorder.last()
        assert recs
        assert any(
            r.get("counters", {}).get("resident/h2d_bytes", 0) > 0
            for r in recs), "per-block h2d delta never surfaced"
        assert any(
            "overlap_fraction" in r.get("resident", {}) for r in recs), \
            "overlap fraction never surfaced in a flight record"
        for b in blocks:
            chain.accept(b)
        chain.drain_acceptor_queue()
        assert chain.acceptor_error is None
        assert chain.mirror._inflight == []
    finally:
        chain.stop()
        default.stop()


def test_template_uploads_less_than_planned_full_rows():
    """The A/B the bench artifact records, in miniature: for an
    identical incremental batch, template residency's upload (fresh leaf
    content + patch tables, ~70 B/leaf) undercuts the planned device
    path's full dirty-node rows (~320 B/dirty node) — at identical
    roots."""
    from coreth_tpu.native.mpt import IncrementalTrie
    from coreth_tpu.ops.keccak_planned import default_planned_commit
    from coreth_tpu.ops.keccak_resident import ResidentExecutor

    rng = random.Random(62)
    genesis = _rand_items(rng, 250)
    # update-heavy batch on EXISTING keys: the dirty interior set (what
    # the planned path re-uploads whole) dwarfs the fresh-leaf payload
    keys = list(genesis)
    batch = [(rng.choice(keys), rng.randbytes(60)) for _ in range(30)]
    final = _apply(genesis, batch)

    planned_trie = IncrementalTrie(sorted(genesis.items()))
    planned_trie.commit_cpu()
    planned_trie.update(batch)
    planned = default_planned_commit()
    planned_root = planned_trie.commit_device(planned)
    planned_bytes = planned.last_h2d_bytes

    c = default_registry.counter("resident/h2d_bytes")
    tmpl_trie = IncrementalTrie(sorted(genesis.items()))
    ex = ResidentExecutor()
    tmpl_trie.commit_template(ex)  # genesis upload (not measured)
    tmpl_trie.update(batch)
    b0 = c.count()
    tmpl_root = tmpl_trie.commit_template(ex)
    tmpl_bytes = c.count() - b0

    assert planned_root == tmpl_root == _oracle(final)
    assert 0 < tmpl_bytes < planned_bytes
