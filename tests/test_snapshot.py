"""Flat snapshot tree tests (modeled on /root/reference/core/state/
snapshot/snapshot_test.go + the blockHash-keyed coreth semantics)."""

import pytest

from coreth_tpu import params
from coreth_tpu.consensus.dummy import new_dummy_engine
from coreth_tpu.core.blockchain import BlockChain, CacheConfig
from coreth_tpu.core.chain_makers import generate_chain
from coreth_tpu.core.genesis import Genesis, GenesisAccount
from coreth_tpu.core.types import Signer, Transaction
from coreth_tpu.crypto.secp256k1 import priv_to_address
from coreth_tpu.ethdb import MemoryDB
from coreth_tpu.state.database import Database
from coreth_tpu.state.snapshot import SnapshotError, Tree
from coreth_tpu.state.statedb import StateDB
from coreth_tpu.trie.node import EMPTY_ROOT
from coreth_tpu.trie.triedb import TrieDatabase

KEY = b"\x11" * 32
ADDR = priv_to_address(KEY)
DEST = b"\xbb" * 20
FUND = 10**22


def tx(nonce, value=1000):
    t = Transaction(type=2, chain_id=43112, nonce=nonce, max_fee=10**12,
                    max_priority_fee=10**9, gas=21000, to=DEST, value=value)
    return Signer(43112).sign(t, KEY)


def snapshot_chain():
    diskdb = MemoryDB()
    sdb = Database(TrieDatabase(diskdb))
    genesis = Genesis(
        config=params.TEST_CHAIN_CONFIG,
        gas_limit=params.CORTINA_GAS_LIMIT,
        alloc={ADDR: GenesisAccount(balance=FUND)},
    )
    chain = BlockChain(
        diskdb, CacheConfig(snapshot_limit=256), params.TEST_CHAIN_CONFIG,
        genesis, new_dummy_engine(), state_database=sdb,
    )
    return chain


class TestTree:
    def test_generation_from_trie(self):
        chain = snapshot_chain()
        assert chain.snaps is not None
        layer = chain.snaps.snapshot(chain.genesis_block.root)
        assert layer is not None
        from coreth_tpu.native import keccak256

        slim = layer.account(keccak256(ADDR))
        assert slim is not None and len(slim) > 0
        # integrity: rebuild the root from the flat data
        assert chain.snaps.verify_root(chain.genesis_block.root)
        chain.stop()

    def test_diff_layer_and_flatten(self):
        chain = snapshot_chain()
        blocks, _ = generate_chain(
            chain.config, chain.genesis_block, chain.engine,
            chain.state_database, 3,
            gen=lambda i, bg: bg.add_tx(tx(i)),
        )
        for b in blocks:
            chain.insert_block(b)
            # each insert registers a diff layer keyed by block hash
            # (attached by the insert-tail worker — join before looking)
            chain.join_tail()
            assert chain.snaps.get_block_snapshot(b.hash()) is not None
        for b in blocks:
            chain.accept(b)
        chain.drain_acceptor_queue()
        # all layers flattened into the disk layer
        assert chain.snaps.disk_layer.root == blocks[-1].root
        assert chain.snaps.verify_root(blocks[-1].root)
        chain.stop()

    def test_snapshot_reads_match_trie(self):
        chain = snapshot_chain()
        blocks, _ = generate_chain(
            chain.config, chain.genesis_block, chain.engine,
            chain.state_database, 1, gen=lambda i, bg: bg.add_tx(tx(0, 777)),
        )
        chain.insert_block(blocks[0])
        # read through the snapshot-backed state
        st = chain.state_at(blocks[0].root)
        assert st.snap is not None
        assert st.get_balance(DEST) == 777
        chain.stop()

    def test_sibling_dropped_on_flatten(self):
        chain = snapshot_chain()
        fork_a, _ = generate_chain(
            chain.config, chain.genesis_block, chain.engine,
            chain.state_database, 1, gen=lambda i, bg: bg.add_tx(tx(0, 1)),
        )
        fork_b, _ = generate_chain(
            chain.config, chain.genesis_block, chain.engine,
            chain.state_database, 1, gap=30,
            gen=lambda i, bg: bg.add_tx(tx(0, 2)),
        )
        chain.insert_block(fork_a[0])
        chain.insert_block(fork_b[0])
        chain.join_tail()
        assert chain.snaps.get_block_snapshot(fork_a[0].hash()) is not None
        assert chain.snaps.get_block_snapshot(fork_b[0].hash()) is not None
        chain.accept(fork_b[0])
        chain.drain_acceptor_queue()
        # loser branch dropped, winner flattened
        assert chain.snaps.get_block_snapshot(fork_a[0].hash()) is None
        assert chain.snaps.disk_layer.root == fork_b[0].root
        chain.stop()

    def test_destructed_account_reads_deleted(self):
        diskdb = MemoryDB()
        tdb = TrieDatabase(diskdb)
        sdb = Database(tdb)
        st = StateDB(EMPTY_ROOT, sdb)
        st.add_balance(ADDR, 100)
        root = st.commit()
        tdb.commit(root)
        tree = Tree(diskdb, tdb, root)
        from coreth_tpu.native import keccak256

        ah = keccak256(ADDR)
        assert tree.snapshot(root).account(ah)
        # new layer destructs the account
        tree.update(b"\x01" * 32, root, {ah}, {}, {})
        layer = tree.snapshot(b"\x01" * 32)
        assert layer.account(ah) == b""  # deleted marker

    @pytest.mark.parametrize("first", [b"a", b"o"])
    def test_generation_keeps_nodes_sharing_a_prefix_byte(self, first):
        """Trie nodes are keyed by 32-byte hash, so about 1 in 128 starts
        with a snapshot prefix byte: generation wipes stale snapshot
        entries and must leave those nodes on disk, and the disk layer's
        account iterator must not yield them."""
        diskdb = MemoryDB()
        tdb = TrieDatabase(diskdb)
        st = StateDB(EMPTY_ROOT, Database(tdb))
        st.add_balance(ADDR, 100)
        root = st.commit()
        tdb.commit(root)
        node_key = first + b"\x5a" * 31
        diskdb.put(node_key, b"node blob")
        tree = Tree(diskdb, tdb, root)
        assert diskdb.get(node_key) == b"node blob"
        keys = [k for k, _ in tree.account_iterator(root)]
        assert keys and all(len(k) == 32 for k in keys)
        assert tree.verify_root(root)

    def test_missing_parent_rejected(self):
        diskdb = MemoryDB()
        tdb = TrieDatabase(diskdb)
        tree = Tree(diskdb, tdb, EMPTY_ROOT)
        with pytest.raises(SnapshotError):
            tree.update(b"\x01" * 32, b"\x77" * 32, set(), {}, {})


class TestIterators:
    def _tree_with_layers(self):
        """disk layer {a1, a2, a3} + diff1 (update a2, add a4) + diff2
        (destruct a1, delete a4-... )"""
        diskdb = MemoryDB()
        tdb = TrieDatabase(diskdb)
        sdb = Database(tdb)
        st = StateDB(EMPTY_ROOT, sdb)
        addrs = [b"\x01" * 20, b"\x02" * 20, b"\x03" * 20]
        for i, a in enumerate(addrs):
            st.add_balance(a, 100 + i)
        root = st.commit()
        tdb.commit(root)
        tree = Tree(diskdb, tdb, root)
        from coreth_tpu.native import keccak256

        hashes = sorted(keccak256(a) for a in addrs)
        return tree, root, hashes

    def test_account_iterator_disk_only(self):
        tree, root, hashes = self._tree_with_layers()
        got = [k for k, _ in tree.account_iterator(root)]
        assert got == hashes
        # start bound is inclusive and ascending
        got2 = [k for k, _ in tree.account_iterator(root, start=hashes[1])]
        assert got2 == hashes[1:]

    def test_account_iterator_merges_diff_layers(self):
        tree, root, hashes = self._tree_with_layers()
        # diff1: overwrite hashes[0], add new account; diff2: destruct hashes[1]
        new_hash = b"\x7f" * 32
        r1, r2 = b"\x01" * 32, b"\x02" * 32
        tree.update(r1, root, set(), {hashes[0]: b"young", new_hash: b"added"}, {})
        tree.update(r2, r1, {hashes[1]}, {}, {})
        items = dict(tree.account_iterator(r2))
        assert items[hashes[0]] == b"young"        # youngest layer wins
        assert hashes[1] not in items              # destructed
        assert items[new_hash] == b"added"
        assert hashes[2] in items                  # disk shows through
        # iterating the PARENT root is unaffected by the child diff
        items1 = dict(tree.account_iterator(r1))
        assert hashes[1] in items1

    def test_storage_iterator(self):
        diskdb = MemoryDB()
        tdb = TrieDatabase(diskdb)
        sdb = Database(tdb)
        st = StateDB(EMPTY_ROOT, sdb)
        a = b"\x05" * 20
        st.add_balance(a, 1)
        # keys chosen to survive normalize_state_key (bit 0 of byte 0 cleared)
        slots = {(b"\x02" + b"\x00" * 31): b"\x11", (b"\x04" + b"\x00" * 31): b"\x22"}
        for k, v in slots.items():
            st.set_state(a, k, v.rjust(32, b"\x00"))
        root = st.commit()
        tdb.commit(root)
        tree = Tree(diskdb, tdb, root)
        from coreth_tpu.native import keccak256

        ah = keccak256(a)
        got = list(tree.storage_iterator(root, ah))
        assert len(got) == 2
        want = sorted(keccak256(k) for k in slots)
        assert [k for k, _ in got] == want

    def test_unknown_root_raises(self):
        tree, root, _ = self._tree_with_layers()
        with pytest.raises(SnapshotError):
            list(tree.account_iterator(b"\x99" * 32))


class TestAsyncGeneration:
    def test_background_generation(self):
        diskdb = MemoryDB()
        tdb = TrieDatabase(diskdb)
        sdb = Database(tdb)
        st = StateDB(EMPTY_ROOT, sdb)
        for i in range(1, 200):
            st.add_balance(i.to_bytes(20, "big"), i)
        root = st.commit()
        tdb.commit(root)
        tree = Tree(diskdb, tdb, root, async_generate=True)
        # generation may still be running; a not-ready read raises so
        # callers fall back to the trie
        assert tree.wait_generation(timeout=60)
        from coreth_tpu.native import keccak256

        assert tree.disk_layer.account(keccak256((5).to_bytes(20, "big")))
        assert tree.verify_root(root)

    def test_not_ready_reads_raise(self):
        from coreth_tpu.state.snapshot import DiskLayer

        layer = DiskLayer(MemoryDB(), b"\x00" * 32, b"\x00" * 32, ready=False)
        with pytest.raises(SnapshotError):
            layer.account(b"\x01" * 32)
        layer.ready = True
        assert layer.account(b"\x01" * 32) is None
