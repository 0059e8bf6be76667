"""Chain integration of the device-resident account trie
(CacheConfig.resident_account_trie): the account-trie lifecycle rides
trie/resident_mirror.py through insert/accept/reject/reorg, with reads
served by the native IncrementalTrie and changed nodes flushed to disk
at the commit interval.

Reference behaviors mirrored: blockchain.go insert/accept/reject +
reorg (core/blockchain.go:1234,1034,1067,1424), hashdb interval commit
(core/state_manager.go:126-186), statedb.go IntermediateRoot/Commit
(statedb.go:952,1040)."""

from coreth_tpu import params
from coreth_tpu.consensus.dummy import new_dummy_engine
from coreth_tpu.core.blockchain import BlockChain, CacheConfig
from coreth_tpu.core.chain_makers import generate_chain
from coreth_tpu.core.genesis import Genesis, GenesisAccount
from coreth_tpu.core.state_manager import ResidentTrieWriter
from coreth_tpu.core.types import Signer, Transaction
from coreth_tpu.crypto.secp256k1 import priv_to_address
from coreth_tpu.ethdb import MemoryDB
from coreth_tpu.state.database import Database
from coreth_tpu.state.statedb import StateDB
from coreth_tpu.trie.triedb import TrieDatabase

KEY1 = b"\x11" * 32
KEY2 = b"\x22" * 32
ADDR1 = priv_to_address(KEY1)
ADDR2 = priv_to_address(KEY2)
FUND = 10**22


def make_chain(diskdb=None, resident=True, commit_interval=4096,
               prefer_host=False, spot_check_interval=0):
    # prefer_host=False pins the DEVICE path: these tests exercise the
    # resident executor (and its failover), which the CPU-backend host
    # fast path would otherwise bypass on non-TPU test machines.
    cfg = params.TEST_CHAIN_CONFIG
    diskdb = diskdb if diskdb is not None else MemoryDB()
    state_db = Database(TrieDatabase(diskdb))
    genesis = Genesis(
        config=cfg,
        gas_limit=params.CORTINA_GAS_LIMIT,
        alloc={ADDR1: GenesisAccount(balance=FUND),
               ADDR2: GenesisAccount(balance=FUND)},
    )
    return BlockChain(
        diskdb,
        CacheConfig(pruning=True, resident_account_trie=resident,
                    commit_interval=commit_interval,
                    resident_prefer_host=prefer_host,
                    resident_spot_check_interval=spot_check_interval),
        cfg,
        genesis,
        new_dummy_engine(),
        state_database=state_db,
    )


def transfer_tx(nonce, to, key, base_fee, value=1000, chain_id=43112):
    tx = Transaction(
        type=2, chain_id=chain_id, nonce=nonce, max_fee=base_fee * 2,
        max_priority_fee=0, gas=21000, to=to, value=value,
    )
    return Signer(chain_id).sign(tx, key)


def build_blocks(chain, n, gen):
    blocks, _ = generate_chain(
        chain.config, chain.current_block, chain.engine,
        chain.state_database, n, gen=gen,
    )
    return blocks


def tx_gen(counts=None):
    counts = {} if counts is None else counts
    base = params.APRICOT_PHASE3_INITIAL_BASE_FEE

    def gen(i, bg):
        nonce = counts.get(ADDR1, 0)
        bg.add_tx(transfer_tx(nonce, ADDR2, KEY1, bg.base_fee() or base,
                              value=1000 + i))
        counts[ADDR1] = nonce + 1

    return gen


class TestResidentLinearChain:
    def test_writer_and_facade_installed(self):
        chain = make_chain()
        assert isinstance(chain.trie_writer, ResidentTrieWriter)
        assert chain.state_database.mirror is not None
        tr = chain.state_database.open_trie(chain.last_accepted.root)
        assert getattr(tr, "resident", False)
        chain.stop()

    def test_roots_match_default_mode(self):
        """The defining parity check: identical blocks produce identical
        roots through the resident path and the default Python path (the
        insert itself asserts root == header.root, computed default-side
        at generation time)."""
        default = make_chain(resident=False)
        blocks = build_blocks(default, 5, tx_gen())
        resident = make_chain()
        for b in blocks:
            default.insert_block(b)
            resident.insert_block(b)  # raises on any root mismatch
            assert resident.current_block.hash() == b.hash()
        for b in blocks:
            default.accept(b)
            resident.accept(b)
        default.drain_acceptor_queue()
        resident.drain_acceptor_queue()
        assert resident.acceptor_error is None
        s_def, s_res = default.state(), resident.state()
        for addr in (ADDR1, ADDR2):
            assert s_res.get_balance(addr) == s_def.get_balance(addr)
            assert s_res.get_nonce(addr) == s_def.get_nonce(addr)
        default.stop()
        resident.stop()

    def test_reads_through_facade(self):
        chain = make_chain()
        blocks = build_blocks(chain, 3, tx_gen())
        for b in blocks:
            chain.insert_block(b)
            chain.accept(b)
        chain.drain_acceptor_queue()
        st = chain.state()
        assert st.get_balance(ADDR2) == FUND + 1000 + 1001 + 1002
        assert st.get_nonce(ADDR1) == 3
        # absent account reads miss cleanly through the native trie
        assert st.get_balance(b"\x99" * 20) == 0
        chain.stop()


class TestResidentReorg:
    def _two_forks(self, chain):
        base = params.APRICOT_PHASE3_INITIAL_BASE_FEE

        def gen_a(i, bg):
            bg.add_tx(transfer_tx(0, ADDR2, KEY1, bg.base_fee() or base,
                                  value=111))

        def gen_b(i, bg):
            bg.add_tx(transfer_tx(0, ADDR1, KEY2, bg.base_fee() or base,
                                  value=222))

        a = build_blocks(chain, 1, gen_a)
        b = build_blocks(chain, 1, gen_b)
        return a[0], b[0]

    def test_sibling_verify_and_reject(self):
        chain = make_chain()
        blk_a, blk_b = self._two_forks(chain)
        chain.insert_block(blk_a)
        chain.insert_block_manual(blk_b, writes=True)
        # both siblings' states are resident and readable
        assert chain.has_state(blk_a.root)
        assert chain.has_state(blk_b.root)
        sa = chain.state_at(blk_a.root)
        sb = chain.state_at(blk_b.root)
        assert sa.get_balance(ADDR2) == FUND + 111
        assert sb.get_balance(ADDR1) == FUND + 222
        # accept A, reject B (the mirror rewinds the losing branch)
        chain.accept(blk_a)
        chain.drain_acceptor_queue()
        chain.reject(blk_b)
        assert chain.state().get_balance(ADDR2) == FUND + 111
        assert chain.state_database.mirror.root_of(blk_b.hash()) is None
        chain.stop()

    def test_accept_non_canonical(self):
        chain = make_chain()
        blk_a, blk_b = self._two_forks(chain)
        chain.insert_block(blk_a)
        chain.insert_block_manual(blk_b, writes=True)
        assert chain.current_block.hash() == blk_a.hash()
        # consensus accepts the non-preferred sibling: reorg
        chain.accept(blk_b)
        chain.drain_acceptor_queue()
        assert chain.acceptor_error is None
        chain.reject(blk_a)
        assert chain.current_block.hash() == blk_b.hash()
        assert chain.state().get_balance(ADDR1) == FUND + 222
        chain.stop()


class TestResidentPersistence:
    def test_interval_export_and_restart(self):
        """Every commit_interval accepts, changed account nodes flush to
        disk; a fresh chain over the same diskdb boots the mirror from
        that image (crash recovery re-executes any tail past the last
        export)."""
        diskdb = MemoryDB()
        chain = make_chain(diskdb=diskdb, commit_interval=2)
        counts = {}
        blocks = build_blocks(chain, 4, tx_gen(counts))
        for b in blocks:
            chain.insert_block(b)
            chain.accept(b)
        chain.drain_acceptor_queue()
        assert chain.acceptor_error is None
        tip = chain.last_accepted
        chain.stop()  # shutdown export lands the tip image

        chain2 = make_chain(diskdb=diskdb, commit_interval=2)
        assert chain2.last_accepted.hash() == tip.hash()
        st = chain2.state()
        assert st.get_balance(ADDR2) == FUND + 1000 + 1001 + 1002 + 1003
        assert st.get_nonce(ADDR1) == 4
        chain2.stop()

    def test_historical_state_after_export(self):
        """Exported historical roots open as regular disk tries (the
        mirror only holds the live window)."""
        diskdb = MemoryDB()
        chain = make_chain(diskdb=diskdb, commit_interval=1)
        blocks = build_blocks(chain, 3, tx_gen())
        for b in blocks:
            chain.insert_block(b)
            chain.accept(b)
            chain.drain_acceptor_queue()
        st = chain.state_at(blocks[0].root)
        assert st.get_balance(ADDR2) == FUND + 1000
        # with commit_interval=1 every accepted root was exported: the
        # root must open as a plain (non-resident) trie straight from the
        # triedb/disk image and serve account data without the mirror
        from coreth_tpu.state.account import Account

        tr = chain.state_database.triedb.open_state_trie(blocks[0].root)
        assert not getattr(tr, "resident", False)
        acct = Account.decode(tr.get(ADDR2))
        assert acct.balance == FUND + 1000
        chain.stop()


class TestResidentStorageContracts:
    def test_storage_heavy_blocks_match_default(self):
        """Blocks that create dirty STORAGE tries (contract deployments
        SSTOREing several slots) through the resident path: account roots
        come from the mirror while storage tries ride the normal
        committer — roots, storage reads, and receipts must match the
        default path block for block."""
        from coreth_tpu.core.types import create_address

        n_senders = 24
        keys = [i.to_bytes(1, "big") * 32 for i in range(1, n_senders + 1)]
        addrs = [priv_to_address(k) for k in keys]
        base = params.APRICOT_PHASE3_INITIAL_BASE_FEE
        signer = Signer(43112)

        def storage_init_code(seed: int) -> bytes:
            code = bytearray()
            for s in range(6):
                v = (seed * 31 + s * 7 + 1) % 256 or 1
                code += bytes([0x60, v, 0x60, s, 0x55])
            code += bytes([0x60, 0x00, 0x60, 0x00, 0xF3])
            return bytes(code)

        def build(resident):
            diskdb = MemoryDB()
            genesis = Genesis(
                config=params.TEST_CHAIN_CONFIG,
                gas_limit=params.CORTINA_GAS_LIMIT,
                alloc={a: GenesisAccount(balance=FUND) for a in addrs},
            )
            return BlockChain(
                diskdb,
                CacheConfig(pruning=True, resident_account_trie=resident,
                            resident_prefer_host=False),
                params.TEST_CHAIN_CONFIG, genesis, new_dummy_engine(),
                state_database=Database(TrieDatabase(diskdb)),
            )

        default = build(False)
        resident = build(True)
        assert resident.state_database.mirror is not None

        def gen(i, bg):
            bf = bg.base_fee() or base
            for j in range(n_senders):
                tx = Transaction(
                    type=2, chain_id=43112, nonce=i, max_fee=bf * 2,
                    max_priority_fee=0, gas=200_000, to=None, value=0,
                    data=storage_init_code(i * n_senders + j),
                )
                bg.add_tx(signer.sign(tx, keys[j]))

        blocks, _ = generate_chain(
            default.config, default.current_block, default.engine,
            default.state_database, 2, gen=gen)
        for b in blocks:
            default.insert_block(b)   # root check inside
            resident.insert_block(b)  # raises on any mirror root mismatch
            default.accept(b)
            resident.accept(b)
        default.drain_acceptor_queue()
        resident.drain_acceptor_queue()
        assert resident.acceptor_error is None

        s_def, s_res = default.state(), resident.state()
        for j in range(n_senders):
            caddr = create_address(addrs[j], 0)
            for slot in range(6):
                k = slot.to_bytes(32, "big")
                assert s_res.get_state(caddr, k) == s_def.get_state(
                    caddr, k), (j, slot)
        default.stop()
        resident.stop()


class TestResidentStorageBatch:
    def test_storage_tries_batch_into_one_planned_program(self, monkeypatch):
        """With the planned device marker installed, a resident block's
        dirty storage tries hash in ONE planned program (storage-only —
        the account trie rides the mirror), and the roots still match
        the headers produced by the default path."""
        from coreth_tpu.ops.device import get_batch_keccak
        from coreth_tpu.trie import planned as planned_mod

        runs = {"n": 0, "account": 0}
        orig = planned_mod.PlannedGraphBuilder.run

        def counted(selfb, *a, **kw):
            runs["n"] += 1
            if selfb._account is not None:
                runs["account"] += 1
            return orig(selfb, *a, **kw)

        monkeypatch.setattr(planned_mod.PlannedGraphBuilder, "run", counted)

        n_senders = 24
        keys = [i.to_bytes(1, "big") * 32 for i in range(1, n_senders + 1)]
        addrs = [priv_to_address(k) for k in keys]
        signer = Signer(43112)
        base = params.APRICOT_PHASE3_INITIAL_BASE_FEE

        def storage_init_code(seed: int) -> bytes:
            code = bytearray()
            for s in range(6):
                v = (seed * 31 + s * 7 + 1) % 256 or 1
                code += bytes([0x60, v, 0x60, s, 0x55])
            code += bytes([0x60, 0x00, 0x60, 0x00, 0xF3])
            return bytes(code)

        genesis_alloc = {a: GenesisAccount(balance=FUND) for a in addrs}

        def build(resident):
            diskdb = MemoryDB()
            genesis = Genesis(
                config=params.TEST_CHAIN_CONFIG,
                gas_limit=params.CORTINA_GAS_LIMIT, alloc=genesis_alloc)
            marker = get_batch_keccak("planned") if resident else None
            return BlockChain(
                diskdb,
                CacheConfig(pruning=True, resident_account_trie=resident,
                            resident_prefer_host=False),
                params.TEST_CHAIN_CONFIG, genesis, new_dummy_engine(),
                state_database=Database(
                    TrieDatabase(diskdb, batch_keccak=marker)),
            )

        default = build(False)
        resident = build(True)

        def gen(i, bg):
            bf = bg.base_fee() or base
            for j in range(n_senders):
                tx = Transaction(
                    type=2, chain_id=43112, nonce=i, max_fee=bf * 2,
                    max_priority_fee=0, gas=200_000, to=None, value=0,
                    data=storage_init_code(i * n_senders + j),
                )
                bg.add_tx(signer.sign(tx, keys[j]))

        blocks, _ = generate_chain(
            default.config, default.current_block, default.engine,
            default.state_database, 1, gen=gen)
        resident.insert_block(blocks[0])  # root check inside
        assert runs["n"] >= 1, "storage batch program never ran"
        assert runs["account"] == 0, (
            "resident mode must not build an account-trie planned program")
        resident.accept(blocks[0])
        resident.drain_acceptor_queue()
        assert resident.acceptor_error is None
        default.stop()
        resident.stop()


class TestResidentCrashRecovery:
    def test_unclean_shutdown_reprocesses_tail(self):
        """Crash mid-interval (no shutdown export): boot finds the tip
        state missing, re-executes from the last exported root through
        the DEFAULT path, then installs the mirror over the healed tip
        (blockchain.go:679,1745 loadLastState -> reprocessState)."""
        diskdb = MemoryDB()
        chain = make_chain(diskdb=diskdb, commit_interval=3)
        counts = {}
        blocks = build_blocks(chain, 5, tx_gen(counts))
        for b in blocks:
            chain.insert_block(b)
            chain.accept(b)
        chain.drain_acceptor_queue()
        assert chain.acceptor_error is None
        tip = chain.last_accepted
        # simulate a crash: no chain.stop(), so no shutdown export —
        # disk has the interval export at block 3 plus block bodies
        chain._acceptor_queue.put(None)

        reopened = make_chain(diskdb=diskdb, commit_interval=3)
        assert reopened.last_accepted.hash() == tip.hash()
        assert reopened.state_database.mirror is not None
        st = reopened.state()
        assert st.get_balance(ADDR2) == FUND + sum(1000 + i for i in range(5))
        assert st.get_nonce(ADDR1) == 5
        # the healed chain keeps extending through the mirror
        more = build_blocks(reopened, 2, tx_gen(counts))
        for b in more:
            reopened.insert_block(b)
            reopened.accept(b)
        reopened.drain_acceptor_queue()
        assert reopened.acceptor_error is None
        assert reopened.state().get_nonce(ADDR1) == 7
        reopened.stop()


class TestResidentReorgFuzz:
    def test_random_fork_lifecycle_matches_default(self):
        """Randomized fork/accept/reject rounds driven identically into a
        resident chain and a default-path chain: every accepted head's
        state must agree (insert itself enforces root==header.root, so
        any divergence in the mirror's rewind/replay surfaces here)."""
        import random as _random

        rng = _random.Random(1234)
        resident = make_chain()
        default = make_chain(resident=False)
        base = params.APRICOT_PHASE3_INITIAL_BASE_FEE
        nonces = {ADDR1: 0, ADDR2: 0}

        def fork(chain, parent, sender_key, sender, value):
            def gen(i, bg):
                bg.add_tx(transfer_tx(
                    nonces[sender], ADDR2 if sender == ADDR1 else ADDR1,
                    sender_key, bg.base_fee() or base, value=value))

            blocks, _ = generate_chain(
                chain.config, parent, chain.engine,
                chain.state_database, 1, gen=gen)
            return blocks[0]

        for rnd in range(8):
            # two competing children of the current head, different txs
            parent_r = resident.last_accepted
            parent_d = default.last_accepted
            assert parent_r.hash() == parent_d.hash()
            val_a, val_b = 100 + rnd, 200 + rnd
            key, sender = ((KEY1, ADDR1) if rng.random() < 0.5
                           else (KEY2, ADDR2))
            blk_a = fork(default, parent_d, key, sender, val_a)
            blk_b = fork(default, parent_d, key, sender, val_b)
            for chain in (resident, default):
                chain.insert_block_manual(blk_a, writes=True)
                chain.insert_block_manual(blk_b, writes=True)
            # both sibling states readable on the resident chain
            assert resident.state_at(blk_a.root).get_balance(
                ADDR2) == default.state_at(blk_a.root).get_balance(ADDR2)
            winner, loser = ((blk_a, blk_b) if rng.random() < 0.5
                             else (blk_b, blk_a))
            for chain in (resident, default):
                chain.accept(winner)
                chain.drain_acceptor_queue()
                assert chain.acceptor_error is None, chain.acceptor_error
                chain.reject(loser)
            nonces[sender] += 1
            s_r, s_d = resident.state(), default.state()
            for addr in (ADDR1, ADDR2):
                assert s_r.get_balance(addr) == s_d.get_balance(addr), rnd
                assert s_r.get_nonce(addr) == s_d.get_nonce(addr), rnd
        resident.stop()
        default.stop()


class TestResidentPruner:
    def test_offline_prune_then_reopen_resident(self):
        """The resident path's interval exports write content-addressed
        nodes straight to disk (including abandoned side-branch nodes);
        the offline mark-sweep pruner must keep the live image intact
        and a reopened resident chain must boot and extend over it."""
        from coreth_tpu.core.pruner import Pruner

        diskdb = MemoryDB()
        chain = make_chain(diskdb=diskdb, commit_interval=2)
        counts = {}
        blocks = build_blocks(chain, 4, tx_gen(counts))
        for b in blocks:
            chain.insert_block(b)
            chain.accept(b)
        chain.drain_acceptor_queue()
        chain.stop()  # shutdown export: tip image on disk

        tip = blocks[-1]
        pruner = Pruner(diskdb, TrieDatabase(diskdb))
        pruner.prune(tip.root, chain.genesis_block.root)
        # tip state fully readable from the pruned disk
        st = StateDB(tip.root, Database(TrieDatabase(diskdb)))
        assert st.get_balance(ADDR2) == FUND + 1000 + 1001 + 1002 + 1003

        reopened = make_chain(diskdb=diskdb, commit_interval=2)
        assert reopened.last_accepted.hash() == tip.hash()
        more = build_blocks(reopened, 2, tx_gen(counts))
        for b in more:
            reopened.insert_block(b)
            reopened.accept(b)
        reopened.drain_acceptor_queue()
        assert reopened.acceptor_error is None
        assert reopened.state().get_nonce(ADDR1) == 6
        reopened.stop()


class TestResidentVM:
    def test_vm_end_to_end_with_proof(self):
        """The VM knob (config.go-style JSON -> resident-account-trie)
        drives the whole pipeline: raw tx in, block built + verified +
        accepted through the resident mirror, and eth_getProof at the
        resident head serves a proof that verifies against the header
        root (the delta export backs the proof)."""
        import json

        from coreth_tpu.native import keccak256
        from coreth_tpu.state.account import Account
        from coreth_tpu.trie.proof import verify_proof
        from coreth_tpu.vm.api import create_handlers
        from coreth_tpu.vm.shared_memory import Memory
        from coreth_tpu.vm.vm import SnowContext, VM

        vm = VM()
        genesis = Genesis(
            config=params.TEST_CHAIN_CONFIG,
            gas_limit=params.CORTINA_GAS_LIMIT,
            alloc={ADDR1: GenesisAccount(balance=FUND)},
        )
        vm.initialize(
            SnowContext(shared_memory=Memory()), MemoryDB(), genesis,
            config=None,
            config_bytes=json.dumps(
                {"resident-account-trie": True}).encode(),
        )

        def tick():
            return vm.blockchain.current_block.time + 2

        vm.config.clock = tick
        vm.miner.clock = tick
        assert isinstance(vm.blockchain.trie_writer, ResidentTrieWriter)
        server = create_handlers(vm)

        def rpc(method, *params_):
            resp = json.loads(vm and server.handle_raw(json.dumps(
                {"jsonrpc": "2.0", "id": 1, "method": method,
                 "params": list(params_)}).encode()))
            assert "error" not in resp, resp
            return resp["result"]

        base = params.APRICOT_PHASE3_INITIAL_BASE_FEE
        tx = transfer_tx(0, ADDR2, KEY1, base, value=12345)
        rpc("eth_sendRawTransaction", "0x" + tx.encode().hex())
        blk = vm.build_block()
        blk.verify()
        blk.accept()
        vm.blockchain.drain_acceptor_queue()
        assert vm.blockchain.acceptor_error is None
        assert int(rpc("eth_getBalance", "0x" + ADDR2.hex(), "latest"),
                   16) == 12345

        res = rpc("eth_getProof", "0x" + ADDR2.hex(), [], "latest")
        root = vm.blockchain.last_accepted_block().root
        proof_db = {}
        for blob_hex in res["accountProof"]:
            blob = bytes.fromhex(blob_hex[2:])
            proof_db[keccak256(blob)] = blob
        val = verify_proof(root, keccak256(ADDR2), proof_db)
        assert val is not None, "account proof did not verify"
        assert Account.decode(val).balance == 12345
        vm.shutdown()


class TestResidentMiner:
    def test_worker_builds_and_chain_adopts(self):
        """The miner commits an anonymous preview; insert re-executes and
        the mirror adopts it (one device commit, not two)."""
        from coreth_tpu.miner.worker import Worker

        chain = make_chain()
        worker = Worker(
            chain.config, chain.engine, chain,
            clock=lambda: chain.current_block.time + 2,
        )
        base = params.APRICOT_PHASE3_INITIAL_BASE_FEE
        pending = {ADDR1: [transfer_tx(0, ADDR2, KEY1, base, value=777)]}
        block = worker.commit_new_work(pending)
        assert block.transactions
        chain.insert_block(block)
        chain.accept(block)
        chain.drain_acceptor_queue()
        assert chain.acceptor_error is None
        assert chain.state().get_balance(ADDR2) == FUND + 777
        chain.stop()


class TestResidentCpuFastPath:
    def test_auto_host_mode_on_cpu_backend(self):
        """resident_prefer_host='auto' on a CPU backend must boot the
        mirror HOST-resident (the config-10 regression fix: XLA-CPU is
        no device — commits run the threaded native hasher) with roots
        bit-exact vs the default path, observable via the
        state/resident/cpu_fastpath counter and host_mode."""
        from coreth_tpu.metrics import default_registry

        c0 = default_registry.counter("state/resident/cpu_fastpath").count()
        default = make_chain(resident=False)
        blocks = build_blocks(default, 3, tx_gen())
        chain = make_chain(prefer_host="auto")
        assert chain.mirror is not None
        assert chain.mirror.host_mode, "CPU backend must start host-resident"
        assert chain.mirror.ex is None, "no executor built on the fast path"
        assert default_registry.counter(
            "state/resident/cpu_fastpath").count() == c0 + 1
        for b in blocks:
            # insert_block itself asserts mirror root == header.root
            # (headers were produced default-side at generation time)
            chain.insert_block(b)
            chain.accept(b)
        chain.drain_acceptor_queue()
        assert chain.acceptor_error is None
        assert chain.mirror.host_mode
        s_def = default.state_at(blocks[-1].root)
        s_res = chain.state_at(blocks[-1].root)
        assert s_res.get_balance(ADDR2) == s_def.get_balance(ADDR2)
        default.stop()
        chain.stop()

    def test_pinned_device_path_still_boots_executor(self):
        """prefer_host=False (what every device-path test in this file
        uses) must keep constructing the resident executor."""
        chain = make_chain()  # make_chain pins prefer_host=False
        assert chain.mirror is not None
        assert not chain.mirror.host_mode
        assert chain.mirror.ex is not None
        chain.stop()


class TestSpotCheck:
    """Periodic resident-mirror spot check (ROBUSTNESS.md): the device
    image is cross-checked against the host keccak oracle every
    resident_spot_check_interval committed inserts; a divergence
    QUARANTINES the mirror (rebuilt from last-accepted disk state)."""

    def test_clean_mirror_passes_spot_checks(self):
        from coreth_tpu.metrics import default_registry

        chain = make_chain(spot_check_interval=1)
        checks = default_registry.counter("state/resident/spot_checks")
        quarantines = default_registry.counter("chain/mirror/quarantines")
        c0, q0 = checks.count(), quarantines.count()
        blocks = build_blocks(chain, 3, tx_gen())
        for b in blocks:
            chain.insert_block(b)
            chain.accept(b)
        chain.drain_acceptor_queue()
        assert checks.count() == c0 + 3
        assert quarantines.count() == q0
        chain.stop()

    def test_chaos_forced_divergence_quarantines_and_recovers(self):
        """failpoint-forced spot-check failure: the mirror is rebuilt in
        place and the chain keeps inserting with correct roots (every
        insert re-verifies root == header.root)."""
        from coreth_tpu import fault
        from coreth_tpu.metrics import default_registry

        chain = make_chain(spot_check_interval=1)
        quarantines = default_registry.counter("chain/mirror/quarantines")
        q0 = quarantines.count()
        gen = tx_gen()
        blocks = build_blocks(chain, 4, gen)

        fault.set_failpoint("state/resident/spot_check", "raise*1")
        chain.insert_block(blocks[0])  # spot check fires -> quarantine
        assert quarantines.count() == q0 + 1
        evs = chain.flight_recorder.events(kind="mirror/quarantine")
        assert evs, "quarantine never reached the flight recorder"
        assert chain.state_database.mirror is not None  # rebuilt, not dead

        # the quarantine rebuilt the mirror from the last-ACCEPTED state,
        # dropping the unaccepted block it was mid-insert on — consensus
        # re-delivers that suffix, and the re-insert re-verifies it
        # through the rebuilt mirror
        chain.insert_block(blocks[0])
        chain.accept(blocks[0])

        # the rebuilt mirror carries the chain forward, bit-exact
        for b in blocks[1:]:
            chain.insert_block(b)  # raises on any mirror root mismatch
            chain.accept(b)
        chain.drain_acceptor_queue()
        assert chain.acceptor_error is None
        assert quarantines.count() == q0 + 1  # one-shot fault: no repeats
        assert chain.state().get_nonce(ADDR1) == 4
        chain.stop()
