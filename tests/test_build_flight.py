"""A locally built block's flight record carries its own build.

`vm.build_block` measures the miner's execution (with the resident
preview commit inside it, where the block's state commit compiles) and
the `writes=False` pre-verification insert, and the block's flight
record serves that as `build` beside the later insert's own numbers.
Driven through a real VM on the CPU with the account trie resident on
the (CPU) device, and once in host mode for the key set.
"""

import json

import pytest

from coreth_tpu import params
from coreth_tpu.core.genesis import Genesis, GenesisAccount
from coreth_tpu.core.types import Signer, Transaction
from coreth_tpu.crypto.secp256k1 import priv_to_address
from coreth_tpu.ethdb import MemoryDB
from coreth_tpu.metrics.flight import (BUILD_PHASES, FLIGHT_COUNTERS,
                                       FLIGHT_TIMERS, timer_key)
from coreth_tpu.vm.shared_memory import Memory
from coreth_tpu.vm.vm import VM, SnowContext, VMConfig

CHAIN_ID = 43112
KEY = b"\x21" * 32
TO = b"\x42" * 20
COMPILE_KEYS = ("compile_trace", "compile_lower", "compile_backend")


def _vm(prefer_host: bool) -> VM:
    # enough accounts that the genesis commit's program is sized for a
    # far larger commit than a block's: the first block compiles its
    # own program, and the blocks after it reuse that one
    alloc = {priv_to_address(KEY): GenesisAccount(balance=10**24)}
    for i in range(1, 400):
        alloc[i.to_bytes(20, "big")] = GenesisAccount(balance=10**18 + i)
    genesis = Genesis(config=params.TEST_CHAIN_CONFIG,
                      gas_limit=params.CORTINA_GAS_LIMIT, alloc=alloc)
    vm = VM()

    def tick():
        return vm.blockchain.current_block.time + 2

    vm.initialize(SnowContext(shared_memory=Memory()), MemoryDB(), genesis,
                  VMConfig(clock=tick),
                  config_bytes=json.dumps({
                      "resident-account-trie": True,
                      "resident-prefer-host": prefer_host}).encode())
    return vm


def _drive(vm: VM, nonce: int):
    """One block of one transfer, the same two accounts every time, so
    each block after the first dirties the same trie paths."""
    tx = Transaction(type=2, chain_id=CHAIN_ID, nonce=nonce,
                     max_fee=10**13, max_priority_fee=10**9, gas=21_000,
                     to=TO, value=1000)
    vm.issue_tx(Signer(CHAIN_ID).sign(tx, KEY))
    blk = vm.build_block()
    blk.verify()
    blk.accept()
    vm.blockchain.drain_acceptor_queue()
    return vm.blockchain.flight_recorder.find(blk.id())


def _key_set(build: dict):
    return {k: sorted(v) for k, v in build.items()}


@pytest.fixture(scope="module")
def device_records():
    vm = _vm(prefer_host=False)
    try:
        assert vm.blockchain.state_database.mirror.host_mode is False
        yield [_drive(vm, n) for n in range(3)]
    finally:
        vm.shutdown()


def test_build_section_shape(device_records):
    for rec in device_records:
        build = rec["build"]
        assert set(build) == {"phases", "resident", "counters"}
        assert sorted(build["phases"]) == sorted(BUILD_PHASES)
        assert sorted(build["resident"]) == sorted(
            timer_key(n) for n in FLIGHT_TIMERS)
        assert sorted(build["counters"]) == sorted(FLIGHT_COUNTERS)
        # the build's parts hold its preview commit, not the insert's
        phases = build["phases"]
        assert 0 < phases["preview_commit"] <= phases["miner_execute"]
        assert phases["preverify"] > 0


def test_a_miss_splits_its_compile_and_a_hit_compiles_nothing(
        device_records):
    first = device_records[0]["build"]
    assert first["counters"]["resident/plan_cache/misses"] == 1
    assert first["counters"]["resident/compiles"] == 1
    for k in COMPILE_KEYS:
        assert first["resident"][k] > 0, k
    # the split adds up to the enclosing compile phase
    split = sum(first["resident"][k] for k in COMPILE_KEYS)
    assert split <= first["resident"]["compile"]
    hits = [r["build"] for r in device_records[1:]
            if r["build"]["counters"]["resident/plan_cache/hits"]]
    assert hits, "a repeated signature never hit the plan cache"
    for build in hits:
        assert build["counters"]["resident/compiles"] == 0
        for k in COMPILE_KEYS:
            assert build["resident"][k] == 0, k


def test_build_and_insert_count_separate_work(device_records):
    """The insert adopts the build's preview: its own record commits
    and compiles nothing, and the keccak work is counted once."""
    for rec in device_records:
        assert rec["counters"]["resident/compiles"] == 0
        assert rec["counters"]["resident/keccak/lanes"] == 0
        assert rec["build"]["counters"]["resident/keccak/lanes"] > 0
        assert rec["build"]["counters"]["resident/keccak/rate_blocks"] \
            >= rec["build"]["counters"]["resident/keccak/lanes"]


def test_a_host_mode_build_has_the_same_key_set(device_records):
    vm = _vm(prefer_host=True)
    try:
        assert vm.blockchain.state_database.mirror.host_mode is True
        rec = _drive(vm, 0)
    finally:
        vm.shutdown()
    assert _key_set(rec["build"]) == _key_set(device_records[0]["build"])
    assert set(rec) == set(device_records[0])
    assert rec["build"]["counters"]["resident/compiles"] == 0


def test_debug_rpc_serves_the_build(device_records):
    from coreth_tpu.metrics.flight import marshal_record

    out = marshal_record(device_records[0])
    assert out["build"] == device_records[0]["build"]
    assert out["build"]["phases"] is not device_records[0]["build"]["phases"]
    json.dumps(out)
