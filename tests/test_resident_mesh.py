"""Mesh-sharded resident commit (the promoted 8-device dryrun):
store/arena rows sharded PartitionSpec('batch', None) across the virtual
CPU mesh (tests/conftest.py forces 8 host devices) must be bit-exact vs
the C++ host executor oracle and the pure-Python reference trie at every
width in {1, 2, 4, 8}, through rollback/reject, reorg, pipelining, and
the NEW degradation-ladder rung: a wedge on a mesh-sharded executor
demotes to a single-device resident rebuild (host-oracle-anchored)
before the one-way host takeover."""

import random
import threading

import pytest

from coreth_tpu import fault
from coreth_tpu.metrics import default_registry
from coreth_tpu.native.mpt import (DeviceWedgedError,
                                   plan_from_items)
from coreth_tpu.trie.resident_mirror import ResidentAccountMirror
from coreth_tpu.trie.trie import Trie

WIDTHS = (1, 2, 4, 8)


@pytest.fixture(autouse=True)
def _pin_device_path(monkeypatch):
    # mesh sharding lives in the resident EXECUTOR; the CPU-backend host
    # fast path would silently bypass it on non-TPU test machines
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


class _Wedgy:
    """Proxies the mirror's executor; when armed, the next run() raises
    DeviceWedgedError once — an instant wedge that leaves the watchdog
    budget intact for the demotion's single-device rebuild."""

    def __init__(self, real):
        object.__setattr__(self, "_real", real)
        object.__setattr__(self, "wedge_next", False)

    def run(self, export):
        if self.wedge_next:
            object.__setattr__(self, "wedge_next", False)
            raise DeviceWedgedError("injected mesh wedge")
        return self._real.run(export)

    def __getattr__(self, name):
        return getattr(self._real, name)

    def __setattr__(self, name, value):
        if name == "wedge_next":
            object.__setattr__(self, name, value)
        else:
            setattr(self._real, name, value)


# ---- bit-exactness across the width sweep -------------------------------


@pytest.mark.parametrize("width", WIDTHS)
def test_mesh_width_matches_oracles(width):
    """Linear chain + reject + reorg at every mesh width: roots equal
    the C++ host oracle at every block and the pure-Python trie at the
    endpoints; the executor really is sharded [width] ways."""
    rng = random.Random(4100 + width)
    genesis = _rand_items(rng, 100)
    m = ResidentAccountMirror(sorted(genesis.items()), mesh_devices=width)
    assert not m.host_mode and m.shards == width
    if width > 1:
        # the store must actually live on [width] devices
        assert len(m.ex.store.sharding.device_set) == width
    assert m.root_of(m.GENESIS) == _oracle(genesis) == _py_oracle(genesis)

    state, parent = genesis, m.GENESIS
    states = {parent: genesis}
    for i in range(1, 5):
        h = _hash(i)
        batch = _batch(rng, state, 8)
        state = _apply(state, batch)
        states[h] = state
        assert m.verify(parent, h, batch) == _oracle(state), f"block {i}"
        parent = h
    # reject the head (rollback on the sharded image)
    m.reject(_hash(4))
    assert m.root_of(_hash(3)) == _oracle(states[_hash(3)])
    # reorg: a sibling of block 3 on top of block 2 (rewind + replay)
    fork = _batch(rng, states[_hash(2)], 8)
    fork_state = _apply(states[_hash(2)], fork)
    assert m.verify(_hash(2), _hash(99), fork) == _oracle(fork_state)
    assert m.root_of(_hash(99)) == _py_oracle(fork_state)
    # gather accounting (PR 18 provenance split): the MEASURED counter
    # stays 0 — the mirror's commit path never materializes the
    # replicated dig matrix host-side — while the MODELED cross-shard
    # cost is nonzero exactly when sharded; the per-shard lane histogram
    # sums to the commit
    assert m.ex.last_gather_bytes == 0
    if width == 1:
        assert m.ex.last_gather_bytes_modeled == 0
        assert len(m.ex.last_shard_lanes) == 1
    else:
        assert m.ex.last_gather_bytes_modeled > 0
        assert len(m.ex.last_shard_lanes) == width
    assert sum(m.ex.last_shard_lanes) > 0


def test_mesh_mid_window_host_landing():
    """A wedge while a depth-2 pipeline window is in flight on an
    8-shard mesh: the whole window must land bit-exactly on a LOWER
    rung (single-device resident when the rebuild beats the watchdog,
    host otherwise — both are correct ladder landings)."""
    rng = random.Random(4200)
    genesis = _rand_items(rng, 100)
    m = ResidentAccountMirror(sorted(genesis.items()), mesh_devices=8,
                              pipeline_depth=2, device_timeout=60.0)
    assert m._pipelining() and m.shards == 8
    state, parent = genesis, m.GENESIS
    expected = {}
    for i in range(1, 3):
        h = _hash(i)
        batch = _batch(rng, state, 8)
        state = _apply(state, batch)
        expected[h] = _oracle(state)
        assert m.verify(parent, h, batch,
                        expected_root=expected[h]) == expected[h]
        parent = h
    assert m._inflight  # a window is genuinely in flight
    # wedge the drain: the dispatched commits' resolve() hangs, the
    # watchdog fires, and _drain_on_host lands the window one rung down
    fault.set_failpoint("resident/before_absorb", "hang")
    m.device_timeout = 0.4
    m._drain_pipeline()
    fault.clear_all()
    m.device_timeout = 60.0
    assert m._inflight == []
    assert m.shards < 8, "the mesh rung must have been abandoned"
    for h, root in expected.items():
        assert m.root_of(h) == root
    # the landing rung keeps serving: another block, still bit-exact
    batch = _batch(rng, state, 8)
    state = _apply(state, batch)
    assert m.verify(parent, _hash(3), batch) == _oracle(state)


# ---- the mesh -> single-device -> host ladder ---------------------------


def test_mesh_ladder_demotion_bit_exact():
    """The new ladder rung end to end: first wedge demotes the 8-shard
    mesh to a single-device resident rebuild (host_mode stays False, no
    takeover counted, roots bit-exact); second wedge walks the last
    rung to the host. Every root along the way equals the oracle."""
    rng = random.Random(4300)
    genesis = _rand_items(rng, 120)
    m = ResidentAccountMirror(sorted(genesis.items()), mesh_devices=8)
    assert m.shards == 8
    state = genesis
    b1 = _batch(rng, state, 10)
    s1 = _apply(state, b1)
    assert m.verify(m.GENESIS, _hash(1), b1) == _oracle(s1)

    w = _Wedgy(m.ex)
    m.ex = w
    dem0 = default_registry.counter(
        "state/resident/mesh_demotions").count()
    to0 = default_registry.counter(
        "state/resident/device_takeovers").count()

    w.wedge_next = True
    b2 = _batch(rng, s1, 10)
    s2 = _apply(s1, b2)
    assert m.verify(_hash(1), _hash(2), b2) == _oracle(s2)
    assert not m.host_mode, "mesh wedge must demote, not take over"
    assert m.shards == 1
    assert default_registry.counter(
        "state/resident/mesh_demotions").count() == dem0 + 1
    assert default_registry.counter(
        "state/resident/device_takeovers").count() == to0

    # the single-device rung keeps committing bit-exactly
    b3 = _batch(rng, s2, 10)
    s3 = _apply(s2, b3)
    assert m.verify(_hash(2), _hash(3), b3) == _oracle(s3)
    # rollback across the demotion boundary: reject back to block 2
    m.reject(_hash(3))
    assert m.root_of(_hash(2)) == _oracle(s2)

    # second wedge: bottom device rung -> host (the PR 6 landing)
    w2 = _Wedgy(m.ex)
    m.ex = w2
    w2.wedge_next = True
    b4 = _batch(rng, s2, 10)
    s4 = _apply(s2, b4)
    assert m.verify(_hash(2), _hash(4), b4) == _oracle(s4)
    assert m.host_mode and m.shards == 1
    assert default_registry.counter(
        "state/resident/device_takeovers").count() == to0 + 1
    assert m.root_of(_hash(4)) == _py_oracle(s4)


def test_mesh_demotion_rebuild_wedge_escalates_to_host(monkeypatch):
    """When the single-device rebuild inside the demotion ALSO wedges
    (a dead backend, not a dead mesh), the ladder walks straight
    through to the host with the same commit still answered
    bit-exactly."""
    rng = random.Random(4400)
    genesis = _rand_items(rng, 100)
    m = ResidentAccountMirror(sorted(genesis.items()), mesh_devices=8,
                              device_timeout=60.0)
    state = genesis
    b1 = _batch(rng, state, 8)
    s1 = _apply(state, b1)
    assert m.verify(m.GENESIS, _hash(1), b1) == _oracle(s1)
    # a dead backend: every executor's dispatch hangs, so the
    # demotion's own single-device recommit wedges too, _demote_mesh
    # returns False, and the host takeover finishes the job
    fail0 = default_registry.counter(
        "state/resident/mesh_demotion_failures").count()
    monkeypatch.setattr(type(m.ex), "run",
                        lambda self, export: threading.Event().wait())
    m.device_timeout = 0.2
    b2 = _batch(rng, s1, 8)
    s2 = _apply(s1, b2)
    assert m.verify(_hash(1), _hash(2), b2) == _oracle(s2)
    assert m.host_mode
    assert default_registry.counter(
        "state/resident/mesh_demotion_failures").count() == fail0 + 1


# ---- mesh + pipeline fuzz vs the serial host twin (satellite 5) ---------


def test_mesh_pipeline_fuzz_vs_host_twin(monkeypatch):
    """Seeded lifecycle fuzz (verify/reject/accept on random parents —
    reorgs ride the branch switches) at pipeline depth 2 over an
    8-shard mesh vs a serial host-twin mirror fed the identical op
    sequence: root-identical at every step, both matching the host
    executor oracle."""
    rng = random.Random(8800)
    genesis = _rand_items(rng, 100)
    monkeypatch.setenv("CORETH_TPU_RESIDENT_HOST", "1")
    serial = ResidentAccountMirror(sorted(genesis.items()))
    monkeypatch.setenv("CORETH_TPU_RESIDENT_HOST", "0")
    mesh = ResidentAccountMirror(sorted(genesis.items()),
                                 mesh_devices=8, pipeline_depth=2)
    assert mesh._pipelining() and mesh.shards == 8
    assert not serial._pipelining()

    states = {mesh.GENESIS: genesis}
    children = {}
    alive = [mesh.GENESIS]
    nxt = 1
    for step in range(12):
        r = rng.random()
        if r < 0.60 or len(alive) == 1:
            parent = rng.choice(alive)
            h = _hash(nxt)
            nxt += 1
            batch = _batch(rng, states[parent], 8)
            states[h] = _apply(states[parent], batch)
            expected = _oracle(states[h])
            got_m = mesh.verify(parent, h, batch, expected_root=expected)
            got_s = serial.verify(parent, h, batch)
            assert got_m == got_s == expected, f"step {step}"
            alive.append(h)
            children.setdefault(parent, []).append(h)
        elif r < 0.80:
            leaves = [h for h in alive[1:] if not children.get(h)]
            if not leaves:
                continue
            h = rng.choice(leaves)
            mesh.reject(h)
            serial.reject(h)
            alive.remove(h)
            for c in children.values():
                if h in c:
                    c.remove(h)
        else:
            # periodic spot-check settles the window and cross-checks
            # the sharded store against the host keccak oracle
            assert mesh.spot_check()
    mesh._drain_pipeline()
    assert mesh._inflight == []
    assert not mesh.host_mode and mesh.shards == 8
    for h in alive:
        assert mesh.root_of(h) == serial.root_of(h) == _oracle(states[h])


# ---- chain-level flight record (un-ragged keys) -------------------------


def test_chain_flight_record_mesh_keys_unragged():
    """Every insert's flight record must carry resident/shards and
    resident/gather_bytes EXPLICITLY — an unsharded (here host-mode)
    chain says shards=1 / gather_bytes=0 rather than omitting the keys,
    the PR 12 h2d discipline extended to the mesh columns."""
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
    diskdb = MemoryDB()
    chain = BlockChain(
        diskdb,
        CacheConfig(pruning=True, resident_account_trie=True,
                    resident_prefer_host=True),  # cheap CPU-only legs
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

    try:
        blocks, _ = generate_chain(chain.config, chain.current_block,
                                   chain.engine, chain.state_database,
                                   2, gen=gen)
        for b in blocks:
            chain.insert_block(b)
        recs = chain.flight_recorder.last()
        assert recs
        for r in recs:
            assert r["resident"]["shards"] == 1
            assert r["counters"]["resident/gather_bytes"] == 0
            assert r["counters"]["resident/gather_bytes_modeled"] == 0
            assert "resident/absorb_d2h_bytes" in r["counters"]
            assert "resident/lean_wire_bytes" in r["counters"]
            assert "resident/h2d_bytes" in r["counters"]
    finally:
        chain.stop()
