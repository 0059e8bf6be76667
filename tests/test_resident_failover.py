"""Device-failure takeover chaos tests (VERDICT r4 #4): kill the device
backend mid-commit and mid-interval-export; the chain must CONTINUE with
bit-identical roots (insert_block itself asserts mirror root ==
header.root, computed default-side at generation time), the takeover
must be observable (counter + host_mode), exports must keep landing so a
restart recovers, and reads must keep serving.

The "device" here is the resident executor; the wedge is simulated at
the exact seams a hung device blocks in production: executor.run's
dispatch and the store readback's np.asarray sync. The watchdog
(ResidentAccountMirror device_timeout -> IncrementalTrie
commit_resident_timed) detects both; _take_over_host rebuilds the full
host digest cache (native mpt_inc_mark_all_dirty + commit_cpu) and the
mirror continues host-resident. Reference analog: the lifecycle
invariants around core/blockchain.go:1361-1365 assume the state backend
never vanishes — here it can, without stalling consensus."""

import threading

from coreth_tpu.metrics import default_registry

from test_resident_chain import (ADDR1, ADDR2, FUND, build_blocks,
                                 make_chain, tx_gen)


class _BlockingArray:
    """np.asarray on this blocks forever — a wedged d2h sync."""

    def __array__(self, *a, **kw):
        threading.Event().wait()


class WedgyExecutor:
    """Delegates to the real executor until a wedge flag flips; then the
    flagged seam blocks forever, exactly like a hung device."""

    def __init__(self, real):
        self._real = real
        self.wedge_run = False
        self.wedge_store = False

    def prepare(self, export):
        self._real.prepare(export)

    def run(self, export):
        if self.wedge_run:
            threading.Event().wait()
        return self._real.run(export)

    def root_bytes(self, root):
        return self._real.root_bytes(root)

    @property
    def store(self):
        if self.wedge_store:
            return _BlockingArray()
        return self._real.store

    @property
    def last_root(self):
        return self._real.last_root

    @last_root.setter
    def last_root(self, v):
        self._real.last_root = v

    def bind(self, tree):
        self._real.bind(tree)

    def check_binding(self, tree):
        self._real.check_binding(tree)


def arm(chain, timeout=0.5):
    """Install the wedgeable executor + a short watchdog on a live
    resident chain; returns the wedge controller."""
    mirror = chain.mirror
    assert mirror is not None
    w = WedgyExecutor(mirror.ex)
    mirror.ex = w
    mirror.device_timeout = timeout
    return w


def takeovers():
    return default_registry.counter("state/resident/device_takeovers").count()


def test_wedge_mid_commit_chain_continues():
    default = make_chain(resident=False)
    blocks = build_blocks(default, 6, tx_gen())
    chain = make_chain(commit_interval=2)
    w = arm(chain)

    for b in blocks[:3]:  # healthy device
        chain.insert_block(b)
        chain.accept(b)
        chain.drain_acceptor_queue()
    assert not chain.mirror.host_mode

    base = takeovers()
    w.wedge_run = True  # the device dies NOW
    for b in blocks[3:]:  # same blocks, roots asserted by insert_block
        chain.insert_block(b)
        chain.accept(b)
        chain.drain_acceptor_queue()
    assert chain.mirror.host_mode, "watchdog must have taken over"
    assert takeovers() == base + 1  # one takeover, then plain host mode
    assert chain.current_block.hash() == blocks[-1].hash()

    # reads still serve through the (now host-resident) mirror
    st = chain.state()
    assert st.get_balance(ADDR2) == FUND + sum(1000 + i for i in range(6))
    chain.stop()


def test_wedge_mid_commit_restart_recovers(tmp_path):
    """Exports keep landing after the takeover (host-side export path),
    so a fresh process over the same database recovers the tip state."""
    from coreth_tpu.ethdb import MemoryDB

    diskdb = MemoryDB()
    default = make_chain(resident=False)
    blocks = build_blocks(default, 4, tx_gen())
    chain = make_chain(diskdb=diskdb, commit_interval=2)
    w = arm(chain)
    chain.insert_block(blocks[0])
    chain.accept(blocks[0])
    chain.drain_acceptor_queue()
    w.wedge_run = True
    for b in blocks[1:]:
        chain.insert_block(b)
        chain.accept(b)
        chain.drain_acceptor_queue()
    assert chain.mirror.host_mode
    chain.stop()  # shutdown export runs on the host path

    chain2 = make_chain(diskdb=diskdb, commit_interval=2)
    assert chain2.last_accepted.hash() == blocks[-1].hash()
    st = chain2.state()
    assert st.get_balance(ADDR2) == FUND + sum(1000 + i for i in range(4))
    chain2.stop()


def test_wedge_mid_export_chain_continues(tmp_path):
    """The OTHER wedge seam: commits stay healthy but the store readback
    hangs during the interval export. The export takes over, writes the
    full host image, and the chain (and a restart) continue."""
    from coreth_tpu.ethdb import MemoryDB

    diskdb = MemoryDB()
    default = make_chain(resident=False)
    blocks = build_blocks(default, 4, tx_gen())
    chain = make_chain(diskdb=diskdb, commit_interval=2)
    w = arm(chain)
    base = takeovers()
    chain.insert_block(blocks[0])
    chain.accept(blocks[0])
    chain.drain_acceptor_queue()

    w.wedge_store = True  # d2h dies; dispatch still "works"
    chain.insert_block(blocks[1])
    chain.accept(blocks[1])            # height 2: interval export fires
    chain.drain_acceptor_queue()
    assert chain.mirror.host_mode, "export wedge must take over"
    assert takeovers() == base + 1

    for b in blocks[2:]:               # chain continues host-resident
        chain.insert_block(b)
        chain.accept(b)
        chain.drain_acceptor_queue()
    assert chain.current_block.hash() == blocks[-1].hash()
    chain.stop()

    chain2 = make_chain(diskdb=diskdb, commit_interval=2)
    assert chain2.last_accepted.hash() == blocks[-1].hash()
    assert chain2.state().get_balance(ADDR2) == \
        FUND + sum(1000 + i for i in range(4))
    chain2.stop()


def test_random_fork_lifecycle_with_midstream_wedge():
    """The reorg fuzz (test_resident_chain.TestResidentReorgFuzz) with a
    device wedge injected at a RANDOM round: the takeover must land in
    the middle of sibling competition and every later fork/accept/
    reject round must still match the default chain exactly."""
    import random as _random

    from coreth_tpu import params
    from coreth_tpu.core.chain_makers import generate_chain

    from test_resident_chain import KEY1, transfer_tx

    for seed in (7, 21):
        rng = _random.Random(seed)
        resident = make_chain(commit_interval=3)
        default = make_chain(resident=False)
        w = arm(resident)
        wedge_round = rng.randrange(1, 5)
        base = params.APRICOT_PHASE3_INITIAL_BASE_FEE
        nonces = {ADDR1: 0}

        def fork(chain, parent, value):
            def gen(i, bg):
                bg.add_tx(transfer_tx(nonces[ADDR1], ADDR2, KEY1,
                                      bg.base_fee() or base, value=value))

            blocks, _ = generate_chain(chain.config, parent, chain.engine,
                                       chain.state_database, 1, gen=gen)
            return blocks[0]

        for rnd in range(6):
            if rnd == wedge_round:
                w.wedge_run = True  # device dies between rounds
            parent_d = default.last_accepted
            assert resident.last_accepted.hash() == parent_d.hash()
            blk_a = fork(default, parent_d, 100 + rnd)
            blk_b = fork(default, parent_d, 200 + rnd)
            for chain in (resident, default):
                chain.insert_block_manual(blk_a, writes=True)
                chain.insert_block_manual(blk_b, writes=True)
            winner, loser = ((blk_a, blk_b) if rng.random() < 0.5
                             else (blk_b, blk_a))
            for chain in (resident, default):
                chain.accept(winner)
                chain.drain_acceptor_queue()
                assert chain.acceptor_error is None, chain.acceptor_error
                chain.reject(loser)
            nonces[ADDR1] += 1
            s_r, s_d = resident.state(), default.state()
            for addr in (ADDR1, ADDR2):
                assert s_r.get_balance(addr) == s_d.get_balance(addr), \
                    (seed, rnd)
        assert resident.mirror.host_mode, "wedge must have taken over"
        resident.stop()
        default.stop()


def test_takeover_preserves_reorg_capability():
    """After the takeover the mirror's branch logic still works: verify a
    sibling block against an older parent (rewind+replay on the host)."""
    default = make_chain(resident=False)
    blocks = build_blocks(default, 3, tx_gen())
    chain = make_chain(commit_interval=100)
    w = arm(chain)
    chain.insert_block(blocks[0])
    w.wedge_run = True
    chain.insert_block(blocks[1])      # takeover happens here
    assert chain.mirror.host_mode
    chain.insert_block(blocks[2])
    # sibling of blocks[1]: same parent, different txs — forces a rewind
    # through host-mode rollback + replay
    sib_default = make_chain(resident=False)
    sib_default.insert_block(blocks[0])
    sib_default.accept(blocks[0])
    sib_default.drain_acceptor_queue()
    sib = build_blocks(sib_default, 1, tx_gen({ADDR1: 1}))[0]
    chain.insert_block(sib)            # root asserted internally
    # the sibling verified against the older parent (host-mode rewind +
    # replay) and its state is registered; the canonical head is
    # unchanged (consensus would have to prefer/accept it to reorg)
    assert chain.mirror.root_of(sib.hash()) == sib.root
    assert chain.current_block.hash() == blocks[-1].hash()
    chain.stop()
