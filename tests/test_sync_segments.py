"""Segmented trie sync: concurrent key-range segments with per-segment
resume markers (capability of /root/reference/sync/statesync/
trie_segments.go:65-417).

Covers: the large-trie switch into segments, bit-exact rebuild over the
full keyspace, kill/resume mid-segment (markered ranges are NOT
refetched), and the small-trie path staying single-stream.
"""

import threading

import pytest

from coreth_tpu.ethdb import MemoryDB
from coreth_tpu.native import keccak256
from coreth_tpu.peer.network import Network
from coreth_tpu.state.database import Database
from coreth_tpu.state.statedb import StateDB
from coreth_tpu.sync.client import SyncClient
from coreth_tpu.sync.handlers import LeafsRequestHandler
from coreth_tpu.sync.statesync import (
    NUM_SEGMENTS,
    SYNC_LEAF_PREFIX,
    SYNC_SEGMENT_PREFIX,
    StateSyncer,
    sync_segment_key,
    _segment_bounds,
)
from coreth_tpu.trie.node import EMPTY_ROOT
from coreth_tpu.trie.triedb import TrieDatabase


def _populate_accounts(st, n_accounts: int) -> None:
    for i in range(1, n_accounts + 1):
        st.add_balance(i.to_bytes(20, "big"), 10**15 + i)


def build_server_state(n_accounts: int):
    diskdb = MemoryDB()
    tdb = TrieDatabase(diskdb)
    st = StateDB(EMPTY_ROOT, Database(tdb))
    _populate_accounts(st, n_accounts)
    root = st.commit()
    tdb.commit(root)
    return tdb, root


class _LeafsOnlyHandler:
    """Adapter: serve leafs requests over the Network wire."""

    def __init__(self, tdb):
        self.h = LeafsRequestHandler(tdb)

    def handle(self, sender, req_bytes):
        from coreth_tpu.sync.messages import LeafsRequest, decode_message

        msg = decode_message(req_bytes)
        assert isinstance(msg, LeafsRequest)
        return self.h.on_leafs_request(msg).encode()


def make_client(tdb):
    net = Network(self_id=b"client")
    handler = _LeafsOnlyHandler(tdb)
    net.connect(b"server", lambda sender, req: handler.handle(sender, req))
    return SyncClient(net)


class CountingClient:
    """Wraps SyncClient counting get_leafs calls + leaves; optionally dies
    after a call budget (the kill half of kill/resume)."""

    def __init__(self, inner, die_after: int = 0):
        self._inner = inner
        self.calls = 0
        self.leaves = 0
        self.die_after = die_after
        self._lock = threading.Lock()

    def get_leafs(self, *a, **kw):
        with self._lock:
            self.calls += 1
            if self.die_after and self.calls > self.die_after:
                raise ConnectionError("simulated crash mid-sync")
        resp = self._inner.get_leafs(*a, **kw)
        with self._lock:
            self.leaves += len(resp.keys)
        return resp

    def __getattr__(self, name):
        return getattr(self._inner, name)


def run_sync(tdb, root, client_db, client, **kw):
    s = StateSyncer(client, client_db, root, **kw)

    def on_leaf(k, v, batch):
        pass

    return s._sync_trie(root, on_leaf), s


N_BIG = 3500  # > 2 * leaf limit: triggers segmentation


def test_large_trie_syncs_segmented_and_bit_exact():
    tdb, root = build_server_state(N_BIG)
    client_db = MemoryDB()
    counting = CountingClient(make_client(tdb))
    count, _ = run_sync(tdb, root, client_db, counting)
    assert count == N_BIG
    # every trie node reachable from the root landed in the client db
    assert client_db.get(root) is not None
    ctdb = TrieDatabase(client_db)
    t = ctdb.open_trie(root)
    found = sum(1 for _ in _leaves(t))
    assert found == N_BIG
    # buffer and markers cleaned up
    assert not list(client_db.iterate(SYNC_LEAF_PREFIX))
    assert not list(client_db.iterate(SYNC_SEGMENT_PREFIX))
    # concurrency actually sharded the keyspace: more than one range seen
    assert counting.calls >= NUM_SEGMENTS


@pytest.mark.parametrize("backend", ["memory", "sqlite"])
def test_kill_and_resume_mid_segment(backend, tmp_path):
    tdb, root = build_server_state(N_BIG)
    if backend == "sqlite":
        # the production disk backend: one WAL connection serialized under
        # an RLock — four segment threads write batches concurrently
        from coreth_tpu.ethdb.sqlitedb import SQLiteDB

        client_db = SQLiteDB(str(tmp_path / "sync.db"), sync=False)
    else:
        client_db = MemoryDB()

    # first attempt dies after enough calls to have markered some ranges
    dying = CountingClient(make_client(tdb), die_after=2)
    with pytest.raises(ConnectionError):
        run_sync(tdb, root, client_db, dying)

    # crash left segment markers + buffered leaves behind
    markers = list(client_db.iterate(SYNC_SEGMENT_PREFIX))
    assert markers, "no resume markers persisted before the crash"
    buffered_before = len(list(client_db.iterate(SYNC_LEAF_PREFIX)))
    assert buffered_before > 0

    # second attempt on the SAME db resumes; markered leaves not refetched
    resuming = CountingClient(make_client(tdb))
    count, _ = run_sync(tdb, root, client_db, resuming)
    assert count == N_BIG
    assert resuming.leaves < N_BIG, (
        "resume refetched the whole trie (markers ignored): "
        f"{resuming.leaves} >= {N_BIG}"
    )
    ctdb = TrieDatabase(client_db)
    t = ctdb.open_trie(root)
    assert sum(1 for _ in _leaves(t)) == N_BIG
    assert not list(client_db.iterate(SYNC_SEGMENT_PREFIX))


def test_small_trie_stays_single_stream():
    tdb, root = build_server_state(300)
    client_db = MemoryDB()
    counting = CountingClient(make_client(tdb))
    count, _ = run_sync(tdb, root, client_db, counting)
    assert count == 300
    assert counting.calls == 1
    assert not list(client_db.iterate(SYNC_SEGMENT_PREFIX))


def test_segment_bounds_cover_keyspace():
    bounds = _segment_bounds(NUM_SEGMENTS)
    assert bounds[0] == b"\x00" * 32
    assert len(set(bounds)) == NUM_SEGMENTS
    from coreth_tpu.sync.statesync import _segment_ends

    ends = _segment_ends(bounds)
    assert ends[-1] == b"\xff" * 32
    for i in range(NUM_SEGMENTS - 1):
        assert int.from_bytes(ends[i], "big") + 1 == int.from_bytes(
            bounds[i + 1], "big")


def test_tampered_segment_rebuild_rejected_then_self_heals():
    """A poisoned leaf buffer (phantom key smuggled in) must fail the
    full-keyspace root check, undo the phantom's side effects, reset the
    segment state — and the NEXT attempt must succeed from scratch."""
    tdb, root = build_server_state(N_BIG)
    client_db = MemoryDB()
    dying = CountingClient(make_client(tdb), die_after=3)
    with pytest.raises(ConnectionError):
        run_sync(tdb, root, client_db, dying)
    # smuggle a PHANTOM leaf (key not in the real trie) into the buffer
    entries = list(client_db.iterate(SYNC_LEAF_PREFIX))
    assert entries
    k0, v0 = entries[0]
    phantom = k0[:-1] + bytes([k0[-1] ^ 0xFF])
    client_db.put(phantom, v0)
    from coreth_tpu.sync.statesync import StateSyncError, SYNC_LEAF_PREFIX as P

    side = {}

    def on_leaf(k, v, batch):
        side[k] = v

    def on_unleaf(k, batch):
        side.pop(k, None)

    s = StateSyncer(make_client(tdb), client_db, root)
    with pytest.raises(StateSyncError, match="mismatch"):
        s._sync_trie(root, on_leaf, on_unleaf=on_unleaf)
    # side effects undone for every discarded buffered leaf (incl. phantom)
    assert phantom[len(P + root):] not in side
    # segment state fully reset
    assert not list(client_db.iterate(SYNC_SEGMENT_PREFIX))
    assert not list(client_db.iterate(SYNC_LEAF_PREFIX))
    # an honest retry completes
    count, _ = run_sync(tdb, root, client_db, make_client(tdb))
    assert count == N_BIG


def test_crash_before_rebuild_replays_side_effects():
    """A sync that crashes AFTER fetching all segments but BEFORE the
    rebuild must, on resume, replay on_leaf over the buffered leaves —
    re-deriving the storage/code tasks the dead process held in memory."""
    tdb, root = build_server_state(N_BIG)
    client_db = MemoryDB()

    crashed = StateSyncer(CountingClient(make_client(tdb)), client_db, root)
    orig_rebuild = StateSyncer._rebuild_from_buffer

    def boom(self, *a, **kw):
        raise ConnectionError("crash between fetch and rebuild")

    StateSyncer._rebuild_from_buffer = boom
    try:
        with pytest.raises(ConnectionError):
            crashed._sync_trie(root, lambda k, v, b: None)
    finally:
        StateSyncer._rebuild_from_buffer = orig_rebuild

    # all markers still present (nothing cleaned up)
    assert list(client_db.iterate(SYNC_SEGMENT_PREFIX))

    seen = []
    resumed = StateSyncer(CountingClient(make_client(tdb)), client_db, root)
    count = resumed._sync_trie(root, lambda k, v, b: seen.append(k))
    assert count == N_BIG
    # the rebuild replayed EVERY leaf through on_leaf despite the fetch
    # phase having nothing left to download
    assert len(seen) >= N_BIG
    assert not list(client_db.iterate(SYNC_SEGMENT_PREFIX))
    assert not list(client_db.iterate(SYNC_LEAF_PREFIX))


def test_full_sync_orchestration_with_segments_storage_and_code():
    """StateSyncer.sync() end-to-end over a LARGE account trie (segmented
    path) with storage tries and contract code: every layer — segments,
    storage tasks, code fetch, snapshot writes — lands coherently."""
    from coreth_tpu.core import rawdb
    from coreth_tpu.state.snapshot import (account_snapshot_key,
                                           storage_snapshot_key)
    from coreth_tpu.state.statedb import StateDB
    from coreth_tpu.sync.handlers import SyncHandler

    diskdb = MemoryDB()
    tdb = TrieDatabase(diskdb)
    st = StateDB(EMPTY_ROOT, Database(tdb))
    _populate_accounts(st, N_BIG)
    # a few contracts with storage + code
    code = bytes([0x60, 0x01, 0x60, 0x00, 0x55, 0x00])
    contracts = [(0xC0DE00 + j).to_bytes(20, "big") for j in range(5)]
    for j, ca in enumerate(contracts):
        st.set_code(ca, code + bytes([j]))
        for s in range(8):
            st.set_state(ca, s.to_bytes(32, "big"),
                         (j * 100 + s + 1).to_bytes(32, "big"))
    root = st.commit()
    tdb.commit(root)

    # serve over the full SyncHandler wire (leafs + code requests)
    class _Chain:
        def get_block(self, h):
            return None

    handler = SyncHandler(_Chain(), tdb, diskdb)
    net = Network(self_id=b"client")
    net.connect(b"server", lambda sender, req: handler.handle(sender, req))

    client_db = MemoryDB()
    syncer = StateSyncer(SyncClient(net), client_db, root)
    syncer.sync()

    # account trie fully rebuilt (segmented: N_BIG > threshold)
    ctdb = TrieDatabase(client_db)
    cst = StateDB(root, Database(ctdb))
    assert cst.get_balance((7).to_bytes(20, "big")) == 10**15 + 7
    for j, ca in enumerate(contracts):
        assert rawdb.read_code(client_db, keccak256(code + bytes([j])))
        for s in range(8):
            assert cst.get_state(ca, s.to_bytes(32, "big")) == (
                (j * 100 + s + 1).to_bytes(32, "big"))
    # snapshot entries landed for accounts and storage
    ah = keccak256((7).to_bytes(20, "big"))
    assert client_db.get(account_snapshot_key(ah)) is not None
    ch = keccak256(contracts[0])
    sh = keccak256((0).to_bytes(32, "big"))
    assert client_db.get(storage_snapshot_key(ch, sh)) is not None
    # no sync debris
    assert not list(client_db.iterate(SYNC_SEGMENT_PREFIX))
    assert not list(client_db.iterate(SYNC_LEAF_PREFIX))


def test_two_vm_segmented_state_sync(monkeypatch):
    """Two REAL VMs: a genesis alloc of >SEGMENT_THRESHOLD accounts makes
    the server's account trie large enough that the production
    syncervm path (StateSyncClient -> StateSyncer defaults) takes the
    segmented route, and the client VM lands on the synced block with
    the full state readable. Server/wiring come from test_sync.py's
    shared helpers."""
    from test_sync import build_server_vm, wire_network

    from coreth_tpu.core.genesis import GenesisAccount
    from coreth_tpu.vm.shared_memory import Memory
    from coreth_tpu.vm.syncervm import StateSyncClient, StateSyncServer
    from coreth_tpu.vm.vm import VM, SnowContext, VMConfig

    # > SEGMENT_THRESHOLD accounts straight from genesis (no block cost)
    extra = {i.to_bytes(20, "big"): GenesisAccount(balance=10**12 + i)
             for i in range(1, 2600)}
    server, _mem = build_server_vm(n_blocks=4, txs_per_block=1,
                                   extra_alloc=extra)

    sync_server = StateSyncServer(server.blockchain, syncable_interval=4)
    summary = sync_server.get_last_state_summary()
    assert summary is not None

    # client shares the server's EXACT genesis object (no drift possible)
    client_vm = VM()
    client_vm.initialize(SnowContext(shared_memory=Memory()), MemoryDB(),
                         server.test_genesis, VMConfig())
    net = wire_network(server)

    # spy: the production path must take the segmented route (the raw
    # request count can legitimately be tiny — segments already covered
    # by the buffered single-stream prefix are never refetched)
    seg_calls = {}
    orig_seg = StateSyncer._sync_trie_segmented

    def spy(self, *a, **kw):
        seg_calls["yes"] = True
        return orig_seg(self, *a, **kw)

    monkeypatch.setattr(StateSyncer, "_sync_trie_segmented", spy)
    counting = CountingClient(SyncClient(net))
    StateSyncClient(client_vm, counting).accept_summary(summary)

    assert client_vm.blockchain.last_accepted.hash() == summary.block_hash
    st = client_vm.blockchain.state()
    from test_sync import DEST

    assert st.get_balance(DEST) == 4 * 1 * 3  # blocks x txs x value
    assert st.get_balance((1717).to_bytes(20, "big")) == 10**12 + 1717
    assert seg_calls.get("yes"), "segmented route never engaged"
    # the sync actually crossed the wire (not served from local genesis)
    assert counting.calls > 0 and counting.leaves >= 2600
    # no sync debris in the client db
    assert not list(client_vm.blockchain.diskdb.iterate(SYNC_SEGMENT_PREFIX))
    assert not list(client_vm.blockchain.diskdb.iterate(SYNC_LEAF_PREFIX))
    client_vm.shutdown()
    server.shutdown()


def test_two_vm_sync_into_resident_client():
    """State sync landing in a RESIDENT-mode client: after the synced
    block is accepted, the mirror reboots over the synced root
    (syncervm _finish -> chain.reboot_mirror) and subsequent blocks
    verify through the device-resident path — including one mined by
    the server and fed across."""
    from test_sync import DEST, KEY, build_server_vm, wire_network

    from coreth_tpu.core.genesis import GenesisAccount
    from coreth_tpu.core.state_manager import ResidentTrieWriter
    from coreth_tpu.core.types import Signer, Transaction
    from coreth_tpu.vm.shared_memory import Memory
    from coreth_tpu.vm.syncervm import StateSyncClient, StateSyncServer
    from coreth_tpu.vm.vm import VM, SnowContext, VMConfig

    extra = {i.to_bytes(20, "big"): GenesisAccount(balance=10**12 + i)
             for i in range(1, 1200)}
    server, _mem = build_server_vm(n_blocks=4, txs_per_block=1,
                                   extra_alloc=extra)
    sync_server = StateSyncServer(server.blockchain, syncable_interval=4)
    summary = sync_server.get_last_state_summary()
    assert summary is not None

    client_vm = VM()
    client_vm.initialize(
        SnowContext(shared_memory=Memory()), MemoryDB(),
        server.test_genesis,
        VMConfig(resident_account_trie=True))
    assert client_vm.blockchain.mirror is not None
    pre_sync_mirror = client_vm.blockchain.mirror
    net = wire_network(server)
    StateSyncClient(client_vm, SyncClient(net)).accept_summary(summary)

    chain = client_vm.blockchain
    assert chain.last_accepted.hash() == summary.block_hash
    # mirror rebooted over the synced root
    assert chain.mirror is not pre_sync_mirror
    assert isinstance(chain.trie_writer, ResidentTrieWriter)
    assert chain.mirror.root_of(summary.block_hash) == chain.last_accepted.root
    # reads at the synced state go through the resident facade
    tr = chain.state_database.open_trie(chain.last_accepted.root)
    assert getattr(tr, "resident", False)
    st = chain.state()
    assert st.get_balance(DEST) == 4 * 1 * 3
    assert st.get_balance((777).to_bytes(20, "big")) == 10**12 + 777

    # the chain keeps extending through the mirror: the server mines one
    # more block; the client parses, verifies, and accepts it
    signer = Signer(43112)
    t = Transaction(type=2, chain_id=43112, nonce=4, max_fee=10**12,
                    max_priority_fee=10**9, gas=21000, to=DEST, value=3)
    server.issue_tx(signer.sign(t, KEY))
    blk = server.build_block()
    blk.verify()
    blk.accept()
    server.blockchain.drain_acceptor_queue()

    client_blk = client_vm.parse_block(blk.eth_block.encode())
    client_blk.verify()
    client_blk.accept()
    chain.drain_acceptor_queue()
    assert chain.acceptor_error is None
    assert chain.last_accepted.hash() == blk.eth_block.hash()
    assert chain.mirror.root_of(blk.eth_block.hash()) is not None, (
        "post-sync block did not go through the mirror")
    assert chain.state().get_balance(DEST) == 5 * 1 * 3
    client_vm.shutdown()
    server.shutdown()


def _leaves(trie):
    from coreth_tpu.trie.iterator import iterate_leaves

    return iterate_leaves(trie, None)
