"""Device degradation ladder: watchdogged dispatch, bounded retry,
mid-run demotion to the bit-exact host path, probe-driven re-promotion —
plus the ISSUE acceptance chaos drill (failpoint-forced device hang
during a multi-block insert; roots bit-exact vs a no-fault chain;
demote/promote events in the flight recorder)."""

import threading
import time

import pytest

from coreth_tpu import fault
from coreth_tpu.native import keccak256_batch
from coreth_tpu.ops import device
from coreth_tpu.ops.device import (DeviceDegradedError, DeviceLadder,
                                   LadderedKeccak, PlannedModeKeccak)


def fake_device_fn(msgs):
    """Stands in for BatchedKeccak().digests: bit-exact, no XLA."""
    return keccak256_batch([bytes(m) for m in msgs])


def _collect(events):
    def listener(kind, fields):
        events.append((kind, fields))
    return listener


class TestDispatch:
    def test_passthrough(self):
        lad = DeviceLadder()
        assert lad.dispatch(lambda a, b: a + b, "add", 40, 2) == 42
        assert lad.healthy

    def test_transient_error_retried(self):
        lad = DeviceLadder()
        lad.configure(max_retries=2)
        lad.retry_base = 0.001
        events = []
        lad.add_listener(_collect(events))
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("transient")
            return "ok"

        assert lad.dispatch(flaky, "flaky op") == "ok"
        assert lad.healthy
        assert [k for k, _ in events] == ["retry"]
        assert events[0][1]["what"] == "flaky op"

    def test_exhaustion_demotes(self):
        lad = DeviceLadder()
        lad.configure(max_retries=1)
        lad.retry_base = 0.001
        events = []
        lad.add_listener(_collect(events))

        def broken():
            raise RuntimeError("device on fire")

        with pytest.raises(DeviceDegradedError, match="after 2 attempt"):
            lad.dispatch(broken, "broken op")
        assert lad.state == DeviceLadder.DEMOTED
        assert "device on fire" in lad.last_error
        assert [k for k, _ in events] == ["retry", "demote"]

    def test_demote_is_idempotent(self):
        lad = DeviceLadder()
        events = []
        lad.add_listener(_collect(events))
        lad.demote("first")
        lad.demote("second")
        assert [k for k, _ in events] == ["demote"]
        assert lad.last_error == "second"

    def test_watchdog_deadline_demotes_a_hung_call(self):
        lad = DeviceLadder()
        lad.configure(call_timeout=0.3, max_retries=0)
        parked = threading.Event()

        def hung():
            parked.wait(10)  # never set: the call wedges

        t0 = time.monotonic()
        with pytest.raises(DeviceDegradedError):
            lad.dispatch(hung, "wedged op")
        assert time.monotonic() - t0 < 5  # deadline, not the full park
        assert lad.state == DeviceLadder.DEMOTED
        parked.set()

    def test_failpoint_hang_trips_the_watchdog(self):
        """The dispatch failpoint runs on the watchdog worker thread, so
        `hang` exercises the deadline exactly like a wedged device."""
        lad = DeviceLadder()
        lad.configure(call_timeout=0.3, max_retries=0)
        fault.set_failpoint("ops/device/dispatch", "hang")
        with pytest.raises(DeviceDegradedError):
            lad.dispatch(lambda: 1, "hung by failpoint")
        assert lad.state == DeviceLadder.DEMOTED
        fault.clear_all()  # release the parked worker


class TestHostFallback:
    MSGS = [b"a", b"bb" * 40, b"", b"\x00" * 137]

    def test_demoted_seam_is_bit_exact(self):
        lad = DeviceLadder()
        lk = LadderedKeccak(fake_device_fn, ladder=lad)
        healthy_out = lk(self.MSGS)
        lad.demote("test")
        assert lk(self.MSGS) == healthy_out == keccak256_batch(self.MSGS)

    def test_mid_call_demotion_falls_back(self):
        """A device error inside the call itself: dispatch demotes, the
        seam answers from the host — the caller never sees the error."""
        lad = DeviceLadder()
        lad.configure(max_retries=0)

        def broken(msgs):
            raise RuntimeError("device wedged")

        lk = LadderedKeccak(broken, ladder=lad)
        assert lk(self.MSGS) == keccak256_batch(self.MSGS)
        assert lad.state == DeviceLadder.DEMOTED

    def test_planned_marker_flips_with_ladder(self):
        lad = DeviceLadder()
        pm = PlannedModeKeccak(fake_device_fn, ladder=lad)
        assert pm.planned is True
        lad.demote("test")
        assert pm.planned is False
        lad.promote()
        assert pm.planned is True
        # still a plain callable either way (proof verification etc.)
        assert pm(self.MSGS) == keccak256_batch(self.MSGS)


class TestProbes:
    def test_repromotion_after_consecutive_healthy_probes(self, monkeypatch):
        monkeypatch.setitem(device._cached, "fn", fake_device_fn)
        lad = DeviceLadder()
        lad.configure(probe_interval=0.02, promote_after=2)
        events = []
        lad.add_listener(_collect(events))
        lad.demote("test")
        deadline = time.monotonic() + 15
        # listeners hear "promote" just after the state flips to healthy
        while not (lad.healthy and events and events[-1][0] == "promote") \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert lad.healthy, f"never re-promoted: {lad.status()}"
        kinds = [k for k, _ in events]
        assert kinds[0] == "demote"
        assert "probation" in kinds and kinds[-1] == "promote"
        lad.reset()

    def test_failing_probes_keep_it_demoted(self, monkeypatch):
        monkeypatch.setitem(device._cached, "fn", fake_device_fn)
        lad = DeviceLadder()
        lad.configure(probe_interval=0.02, promote_after=1)
        fault.set_failpoint("ops/device/probe", "raise")
        lad.demote("test")
        time.sleep(0.3)  # many probe intervals
        assert not lad.healthy
        # the road back opens when the fault clears
        fault.clear_all()
        deadline = time.monotonic() + 15
        while not lad.healthy and time.monotonic() < deadline:
            time.sleep(0.01)
        assert lad.healthy
        lad.reset()

    def test_no_probe_fn_means_permanent_demotion(self, monkeypatch):
        monkeypatch.setitem(device._cached, "fn", None)
        lad = DeviceLadder()
        lad.configure(probe_interval=0.01, promote_after=1)
        lad.demote("test")
        time.sleep(0.1)
        assert lad.state == DeviceLadder.DEMOTED


class TestResolution:
    def test_resolve_failure_is_loud_but_soft_for_auto(self, monkeypatch):
        monkeypatch.setattr(device, "_cached", {})
        from coreth_tpu.metrics import default_registry

        before = default_registry.counter("ops/device/resolve_fail").count()
        fault.set_failpoint("ops/device/resolve", "raise:no backend")
        assert device.get_batch_keccak("auto") is None
        assert default_registry.counter(
            "ops/device/resolve_fail").count() == before + 1
        assert "no backend" in device.resolution_error()
        # forced modes refuse to degrade quietly
        with pytest.raises(RuntimeError, match="forced"):
            device.get_batch_keccak("planned")


# --------------------------------------------------------- the chaos drill

from coreth_tpu import params  # noqa: E402
from coreth_tpu.consensus.dummy import new_dummy_engine  # noqa: E402
from coreth_tpu.core.blockchain import BlockChain, CacheConfig  # noqa: E402
from coreth_tpu.core.chain_makers import generate_chain  # noqa: E402
from coreth_tpu.core.genesis import Genesis, GenesisAccount  # noqa: E402
from coreth_tpu.core.types import Signer, Transaction  # noqa: E402
from coreth_tpu.crypto.secp256k1 import priv_to_address  # noqa: E402
from coreth_tpu.ethdb import MemoryDB  # noqa: E402
from coreth_tpu.state.database import Database  # noqa: E402
from coreth_tpu.trie.triedb import TrieDatabase  # noqa: E402

N_SENDERS = 120  # >= BATCH_THRESHOLD dirty accounts: the seam engages
KEYS = [i.to_bytes(1, "big") * 32 for i in range(1, N_SENDERS + 1)]
ADDRS = [priv_to_address(k) for k in KEYS]


def make_chain(batch_keccak, cache_config=None):
    cfg = params.TEST_CHAIN_CONFIG
    diskdb = MemoryDB()
    state_db = Database(TrieDatabase(diskdb, batch_keccak=batch_keccak))
    genesis = Genesis(
        config=cfg, gas_limit=params.CORTINA_GAS_LIMIT,
        alloc={a: GenesisAccount(balance=10**21) for a in ADDRS},
    )
    return BlockChain(diskdb, cache_config or CacheConfig(pruning=True),
                      cfg, genesis, new_dummy_engine(),
                      state_database=state_db)


def transfer_tx(nonce, to, key, base_fee):
    tx = Transaction(type=2, chain_id=43112, nonce=nonce,
                     max_fee=base_fee * 2, max_priority_fee=0, gas=21000,
                     to=to, value=1000)
    return Signer(43112).sign(tx, key)


def test_chaos_drill_hang_demote_bitexact_repromote(monkeypatch):
    """Acceptance drill: arm `hang` on the device dispatch, insert a
    block sequence. The watchdog demotes to host within its deadline, the
    inserts complete with roots bit-exact vs a no-fault CPU chain, and
    the demotion + re-promotion both land in the flight recorder."""
    monkeypatch.setitem(device._cached, "fn", fake_device_fn)
    lad = device.default_ladder()

    # no-fault chain first (its default CacheConfig would otherwise
    # overwrite the drill chain's ladder knobs — the ladder is process-
    # global, configured by whichever chain constructed last)
    clean_chain = make_chain(None)
    drill_chain = make_chain(
        LadderedKeccak(fake_device_fn, ladder=lad),
        CacheConfig(pruning=True, device_call_timeout=0.5,
                    device_max_retries=0, device_probe_interval=0.05,
                    device_promote_after=2))

    base_fee = params.APRICOT_PHASE3_INITIAL_BASE_FEE

    def gen(i, bg):
        bf = bg.base_fee() or base_fee
        for j, key in enumerate(KEYS):
            to = (0x7000 + i * N_SENDERS + j).to_bytes(20, "big")
            bg.add_tx(transfer_tx(i, to, key, bf))

    blocks, _ = generate_chain(
        clean_chain.config, clean_chain.current_block, clean_chain.engine,
        clean_chain.state_database, 3, gen=gen)

    # wedge the device: every dispatch parks until the watchdog fires;
    # probes hang too, so the ladder cannot re-promote mid-drill
    fault.set_failpoint("ops/device/dispatch", "hang")
    fault.set_failpoint("ops/device/probe", "hang")
    t0 = time.monotonic()
    for b in blocks:
        drill_chain.insert_block(b)
        drill_chain.accept(b)
    drill_chain.drain_acceptor_queue()
    elapsed = time.monotonic() - t0

    assert not lad.healthy, "the hang never demoted the device"
    # one watchdog deadline (0.5s) bought the whole demotion; everything
    # after ran host-side — nowhere near N_dispatches * deadline
    assert elapsed < 60
    from coreth_tpu.metrics import default_registry
    assert default_registry.counter("ops/device/demotions").count() >= 1

    # the no-fault chain accepts the same blocks: state roots bit-exact
    # (each chain's validate_state recomputes every root on its own path)
    for b in blocks:
        clean_chain.insert_block(b)
        clean_chain.accept(b)
    clean_chain.drain_acceptor_queue()
    assert drill_chain.current_block.hash() == clean_chain.current_block.hash()
    assert drill_chain.current_block.root == clean_chain.current_block.root

    # clear the fault: probes go healthy, the ladder re-promotes
    fault.clear_all()
    deadline = time.monotonic() + 20
    while not lad.healthy and time.monotonic() < deadline:
        time.sleep(0.01)
    assert lad.healthy, f"never re-promoted: {lad.status()}"

    kinds = [e["event"] for e in drill_chain.flight_recorder.events()]
    assert "device/demote" in kinds
    assert "device/promote" in kinds
    drill_chain.stop()
    clean_chain.stop()
