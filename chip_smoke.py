"""On-chip smoke run of the C-Chain VM's main path.

Run it from the repository root on a host with a TPU:

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # mirror sharded over a 4-chip mesh

One process drives the node through its user entry points:
``VM.initialize`` over a genesis of ``--accounts`` funded accounts (made
from ``--seed``), then ``issue_tx`` -> ``build_block`` -> ``verify`` ->
``accept`` for a few blocks of signed value transfers (one block filled to
the 15M gas limit), then RPC reads through ``vm.api.create_handlers``.
Every result is checked against an oracle that never touches the device:

  - the genesis root against the native planner's host execution over the
    same secure-keyed account leaves;
  - every block against a second VM with ``device-hasher: off`` and the
    resident mirror off, which re-verifies the same block bytes: header
    roots, receipts roots and balances must agree;
  - an ``eth_getProof`` against the header root.

The device must carry the path: the script fails when JAX finds no TPU,
when the resident mirror is not on the device, or when any of the
counters that record a quiet move to the host (``FALLBACK_COUNTERS``)
moved. Phase lines go to stdout as ``phase ...`` records; the last line
is the JSON verdict ``{"ok": true, "device": {...}}``, printed only when
every phase passed.
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import time

# counters that move when the commit path leaves the device without
# failing the call: each must read 0 at the end of the run
FALLBACK_COUNTERS = (
    "ops/device/resolve_fail",
    "ops/device/demotions",
    "state/resident/device_takeovers",
    "state/resident/cpu_fastpath",
    "state/resident/mesh_demotions",
    "chain/mirror/quarantines",
    "trie/planned/too_many_segments",
)
# kernel-choice counters (reported; the split is decided by segment shape)
KERNEL_COUNTERS = (
    "planned/segments/pallas",
    "planned/segments/xla",
    "resident/segments/pallas",
    "resident/segments/xla",
)
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
GAS_PER_TRANSFER = 21_000
CHAIN_ID = 43112


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class Phases:
    """Wall time per phase with the JAX compile time inside it split
    out (trace + lower + backend compile, from jax.monitoring)."""

    def __init__(self, out=sys.stdout):
        import jax

        self._out = out
        self._compile = 0.0
        self.rows = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_kw):
        if name in _COMPILE_EVENTS:
            self._compile += secs

    def run(self, name: str, fn, *args, **kw):
        c0, t0 = self._compile, time.perf_counter()
        out = fn(*args, **kw)
        wall = time.perf_counter() - t0
        comp = self._compile - c0
        self.rows.append((name, wall, comp))
        self.emit("phase", name=name, wall_s=wall, compile_s=comp)
        return out

    def emit(self, tag: str, **fields) -> None:
        print(tag, json.dumps(fields, sort_keys=True), file=self._out,
              flush=True)


def _derive_key(seed: int, i: int) -> bytes:
    from coreth_tpu.native import keccak256

    return keccak256(b"chip-smoke-sender" + seed.to_bytes(8, "big")
                     + i.to_bytes(8, "big"))


def make_genesis(n_accounts: int, seed: int, n_senders: int):
    """Chain genesis: n_accounts funded accounts, the first n_senders of
    them controlled by keys derived from the seed."""
    import numpy as np

    from coreth_tpu import params
    from coreth_tpu.core.genesis import Genesis, GenesisAccount
    from coreth_tpu.crypto.secp256k1 import priv_to_address

    rng = np.random.default_rng(seed)
    keys = [_derive_key(seed, i) for i in range(n_senders)]
    senders = [priv_to_address(k) for k in keys]
    raw = rng.integers(0, 256, size=(n_accounts - n_senders, 20),
                       dtype=np.uint8).tobytes()
    # balances in [1, 2^60) wei: random, well above any transfer below
    bal = rng.integers(1, 1 << 60, size=n_accounts - n_senders,
                       dtype=np.int64).tolist()
    alloc = {a: GenesisAccount(balance=10**24) for a in senders}
    for i in range(n_accounts - n_senders):
        alloc[raw[20 * i:20 * i + 20]] = GenesisAccount(balance=bal[i])
    check(len(alloc) == n_accounts, "duplicate genesis address drawn")
    genesis = Genesis(config=params.TEST_CHAIN_CONFIG,
                      gas_limit=params.CORTINA_GAS_LIMIT, alloc=alloc)
    return genesis, keys, senders


class LeafOracle:
    """The account trie's root by the native planner's host execution
    over secure-keyed account leaves: no device, no chain code between
    the account values and the root."""

    def __init__(self, genesis, threads: int):
        from coreth_tpu.native import keccak256_batch
        from coreth_tpu.state.account import Account

        self.threads = threads
        addrs = list(genesis.alloc)
        hashed = keccak256_batch(addrs, threads=threads)
        self.leaves = {h: Account(balance=genesis.alloc[a].balance).encode()
                       for h, a in zip(hashed, addrs)}

    def update(self, state, addrs) -> None:
        """Take these accounts' values from a state (plain EOAs)."""
        from coreth_tpu.native import keccak256_batch
        from coreth_tpu.state.account import Account

        for h, a in zip(keccak256_batch(addrs, threads=self.threads),
                        addrs):
            self.leaves[h] = Account(nonce=state.get_nonce(a),
                                     balance=state.get_balance(a)).encode()

    def root(self) -> bytes:
        from coreth_tpu.native.mpt import items_to_arrays, plan_commit

        return plan_commit(*items_to_arrays(self.leaves.items())).execute_cpu(
            threads=self.threads)


def new_vm(genesis, config_json: dict):
    from coreth_tpu.ethdb import MemoryDB
    from coreth_tpu.vm.shared_memory import Memory
    from coreth_tpu.vm.vm import VM, SnowContext, VMConfig

    vm = VM()

    def tick():
        # one block per 2 s of chain time: the C-Chain target rate
        return vm.blockchain.current_block.time + 2

    vm.initialize(SnowContext(shared_memory=Memory()), MemoryDB(), genesis,
                  VMConfig(clock=tick),
                  config_bytes=json.dumps(config_json).encode())
    return vm


def plan_blocks(rng: random.Random, keys, existing, n_blocks: int,
                small_txs: int, gas_limit: int):
    """Transfers for n_blocks blocks: the first fills the gas limit, the
    rest carry small_txs each. Recipients alternate between existing
    genesis accounts and fresh addresses."""
    full = gas_limit // GAS_PER_TRANSFER
    plan = []
    for b in range(n_blocks):
        count = full if b == 0 else small_txs
        txs = []
        for j in range(count):
            to = (existing[rng.randrange(len(existing))] if j % 2 == 0
                  else rng.randbytes(20))
            txs.append((rng.randrange(len(keys)), to,
                        rng.randrange(1, 10**15)))
        plan.append(txs)
    return plan


def sign_block(txs, keys, nonces):
    from coreth_tpu.core.types import Signer, Transaction

    signer = Signer(CHAIN_ID)
    out = []
    for sender, to, value in txs:
        tx = Transaction(type=2, chain_id=CHAIN_ID, nonce=nonces[sender],
                         max_fee=10**13, max_priority_fee=10**9,
                         gas=GAS_PER_TRANSFER, to=to, value=value)
        nonces[sender] += 1
        out.append(signer.sign(tx, keys[sender]))
    return out


def rpc(server, method, *params):
    resp = json.loads(server.handle_raw(json.dumps(
        {"jsonrpc": "2.0", "id": 1, "method": method,
         "params": list(params)}).encode()))
    if "error" in resp:
        raise SmokeFailure(f"{method}: {resp['error']}")
    return resp["result"]


def counters(names):
    from coreth_tpu.metrics import default_registry

    return {n: default_registry.counter(n).count() for n in names}


def fallbacks_since(base: dict) -> dict:
    """How far each FALLBACK_COUNTERS entry moved since `base`."""
    return {k: v - base[k] for k, v in counters(FALLBACK_COUNTERS).items()}


def check_no_fallback(moved: dict) -> None:
    check(not any(moved.values()), "fallback counters moved: "
          f"{ {k: v for k, v in moved.items() if v} }")


def run(n_accounts: int = 1_000_000, seed: int = 0, chips: int = 1,
        n_blocks: int = 4, small_txs: int = 64, n_senders: int = 64,
        config_json: dict | None = None, expect_tpu: bool = True,
        out=sys.stdout) -> dict:
    """Drive the VM's main path and check it; raises on any failure.

    chips > 1 puts the resident mirror on a chips-wide mesh and runs only
    genesis, the blocks and the root parity. config_json adds VM config
    keys on top of the defaults (tests steer the CPU run through it)."""
    import jax

    from coreth_tpu.native import default_cpu_threads

    phases = Phases(out)
    threads = default_cpu_threads()
    base0 = counters(FALLBACK_COUNTERS)
    cfg = dict(config_json or {})
    if chips > 1:
        cfg["resident-mesh-devices"] = chips

    genesis, keys, senders = phases.run(
        "make_genesis", make_genesis, n_accounts, seed, n_senders)
    vm = phases.run("vm_initialize", new_vm, genesis, cfg)
    chain = vm.blockchain
    try:
        def genesis_oracle():
            oracle = LeafOracle(genesis, threads)
            return oracle, oracle.root()

        leaf_oracle, oracle_root = phases.run("genesis_oracle",
                                              genesis_oracle)
        check(chain.genesis_block.root == oracle_root,
              f"genesis root {chain.genesis_block.root.hex()} != native "
              f"host oracle {oracle_root.hex()}")
        phases.emit("genesis", accounts=n_accounts,
                    root=oracle_root.hex(), oracle="native execute_cpu")

        from coreth_tpu.core.state_manager import ResidentTrieWriter

        mirror = chain.state_database.mirror
        check(isinstance(chain.trie_writer, ResidentTrieWriter),
              f"trie_writer is {type(chain.trie_writer).__name__}, "
              "not ResidentTrieWriter")
        check(mirror is not None, "state_database.mirror is not set")
        check(mirror.host_mode is False, "resident mirror is in host mode")
        from coreth_tpu.core import exec_shards

        # exec shards fork worker processes; a child cannot use the chip
        check(exec_shards.effective_shards(chain.processor.exec_shards) == 0,
              "exec shards are on")
        if chips > 1:
            check(mirror.shards == chips,
                  f"mirror spans {mirror.shards} shard(s), not {chips}")
            placed = {"store": mirror.ex.store}
            placed.update({f"arena{c}": a
                           for c, a in mirror.ex.arenas.items()})
            for name, arr in placed.items():
                n_dev = len(arr.sharding.device_set)
                check(n_dev == chips,
                      f"{name} rows sit on {n_dev} device(s), not {chips}")
            phases.emit("mesh", shards=mirror.shards,
                        devices=sorted(str(d) for d in
                                       mirror.ex.store.sharding.device_set))

        rng = random.Random(seed)
        existing = list(genesis.alloc)[n_senders:]
        plan = plan_blocks(rng, keys, existing, n_blocks, small_txs,
                           genesis.gas_limit)
        nonces = [0] * len(keys)
        accepted = []

        def drive_block(txs):
            for tx in sign_block(txs, keys, nonces):
                vm.issue_tx(tx)
            blk = vm.build_block()
            blk.verify()
            blk.accept()
            chain.drain_acceptor_queue()
            return blk

        for b, txs in enumerate(plan):
            blk = phases.run(f"block_{b + 1}", drive_block, txs)
            hdr = blk.eth_block.header
            check(len(blk.eth_block.transactions) == len(txs),
                  f"block {b + 1} carries {len(blk.eth_block.transactions)}"
                  f" of {len(txs)} txs")
            if b == 0:
                check(hdr.gas_used + GAS_PER_TRANSFER > hdr.gas_limit,
                      f"block 1 used {hdr.gas_used} of {hdr.gas_limit} gas")
            check(mirror.host_mode is False,
                  f"mirror left the device at block {b + 1}")
            accepted.append(blk)
            phases.emit("block", number=hdr.number, txs=len(txs),
                        gas_used=hdr.gas_used, gas_limit=hdr.gas_limit,
                        root=hdr.root.hex())
            if chips > 1:
                phases.emit("parity", **phases.run(
                    f"block_{b + 1}_oracle", check_block_root, leaf_oracle,
                    blk, txs, senders, chain))

        touched = sorted({to for txs in plan for _, to, _ in txs}
                         | set(senders))
        if chips == 1:
            phases.emit("rpc", **phases.run(
                "rpc", check_rpc, vm, accepted, plan, genesis, senders,
                touched))
            phases.emit("parity", **phases.run(
                "oracle_replay", check_parity, genesis, accepted, chain,
                touched))
        else:
            # the mirror's own host keccak check of every device digest,
            # read back from every store shard
            ok = phases.run("spot_check", mirror.spot_check)
            check(ok, "device digests disagree with host keccak")
            phases.emit("spot_check", nodes=mirror.trie.num_nodes)
    finally:
        vm.shutdown()

    moved = fallbacks_since(base0)
    phases.emit("fallback_counters", **moved)
    phases.emit("kernel_segments", **counters(KERNEL_COUNTERS))
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    phases.emit("device", platform=dev.platform, kind=dev.device_kind,
                count=len(jax.devices()),
                peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    check_no_fallback(moved)
    if expect_tpu:
        check(dev.platform == "tpu", f"ran on {dev.platform}, not tpu")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "phases": phases.rows}


def check_rpc(vm, accepted, plan, genesis, senders, touched):
    """eth_blockNumber, eth_getBalance on recipients, and an
    eth_getProof at latest verified against the header root."""
    from coreth_tpu.native import keccak256
    from coreth_tpu.state.account import Account
    from coreth_tpu.trie.proof import verify_proof
    from coreth_tpu.vm.api import create_handlers

    server = create_handlers(vm)
    try:
        check(int(rpc(server, "eth_blockNumber"), 16) == len(accepted),
              "eth_blockNumber disagrees with the accepted height")
        received = {}
        for txs in plan:
            for _, to, value in txs:
                received[to] = received.get(to, 0) + value
        sender_set = set(senders)
        checked = 0
        for addr, got in received.items():
            if addr in sender_set:
                continue
            start = genesis.alloc[addr].balance if addr in genesis.alloc else 0
            bal = int(rpc(server, "eth_getBalance", "0x" + addr.hex(),
                          "latest"), 16)
            check(bal == start + got,
                  f"eth_getBalance({addr.hex()}) = {bal}, want "
                  f"{start + got}")
            checked += 1
        head = vm.blockchain.last_accepted_block()
        proved = 0
        for addr in (touched[0], touched[-1], senders[0]):
            res = rpc(server, "eth_getProof", "0x" + addr.hex(), [],
                      "latest")
            proof = {}
            for blob_hex in res["accountProof"]:
                blob = bytes.fromhex(blob_hex[2:])
                proof[keccak256(blob)] = blob
            val = verify_proof(head.root, keccak256(addr), proof)
            check(val is not None, f"eth_getProof({addr.hex()}) proves "
                  "absence of a touched account")
            check(Account.decode(val).balance == int(res["balance"], 16),
                  f"eth_getProof({addr.hex()}) balance disagrees with "
                  "its proof")
            proved += 1
    finally:
        server.stop()
    return {"balances_checked": checked, "proofs_verified": proved,
            "root": head.root.hex()}


def check_block_root(oracle, blk, txs, senders, chain):
    """Mesh run: the block's root against the native host oracle fed the
    accounts the block changed (senders, recipients, the coinbase)."""
    hdr = blk.eth_block.header
    changed = {hdr.coinbase} | {a for i, to, _ in txs
                                for a in (senders[i], to)}
    oracle.update(chain.state_at(hdr.root), sorted(changed))
    root = oracle.root()
    check(root == hdr.root, f"block {hdr.number} root {hdr.root.hex()} "
          f"!= native host oracle {root.hex()}")
    return {"number": hdr.number, "root": root.hex(),
            "oracle": "native execute_cpu"}


def check_parity(genesis, accepted, chain, touched):
    """Replay the accepted blocks into a host-only VM and compare."""
    from coreth_tpu.core.types import derive_sha

    oracle = new_vm(genesis, {"device-hasher": "off",
                              "resident-account-trie": False})
    try:
        ochain = oracle.blockchain
        check(ochain.state_database.mirror is None,
              "host oracle booted a resident mirror")
        check(ochain.genesis_block.hash() == chain.genesis_block.hash(),
              "host oracle genesis differs")
        for blk in accepted:
            ob = oracle.parse_block(blk.bytes())
            ob.verify()
            ob.accept()
            ochain.drain_acceptor_queue()
            hdr = blk.eth_block.header
            ohdr = ochain.get_block(blk.id()).header
            check(ohdr.root == hdr.root, f"block {hdr.number} root differs")
            receipts = ochain.get_receipts(blk.id())
            check(derive_sha(receipts) == hdr.receipt_hash,
                  f"block {hdr.number} receipts root differs")
        head = chain.last_accepted_block().root
        dev_state = chain.state_at(head)
        host_state = ochain.state_at(ochain.last_accepted_block().root)
        for addr in touched:
            check(dev_state.get_balance(addr) == host_state.get_balance(addr),
                  f"balance of {addr.hex()} differs from the host oracle")
    finally:
        oracle.shutdown()
    return {"blocks": len(accepted), "accounts_compared": len(touched),
            "root": head.hex(), "oracle": "host-only VM replay"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--accounts", type=int, default=1_000_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--blocks", type=int, default=4)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    if args.blocks < 4:
        print("chip_smoke: at least 4 blocks", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    try:
        res = run(args.accounts, args.seed, args.chips, args.blocks)
    except Exception:
        import traceback

        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print("total", json.dumps({"wall_s": time.perf_counter() - t0}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": res["platform"], "kind": res["kind"],
        "count": res["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
