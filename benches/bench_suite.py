"""BASELINE.json bench suite — one JSON line per config.

    python benches/bench_suite.py            # all configs
    python benches/bench_suite.py 2 3        # selected configs

Configs (BASELINE.md "measurable baselines"):
  1  trie-commit on the parity workload (200k leaves; the headline
     bench.py runs this same path — included for completeness)
  2  1M-account IntermediateRoot-scale commit (the north-star workload)
  3  1k-tx block processing incl. batched sender recovery
  4  state-sync range-proof verification throughput
  5  batched keccak256 via the tpu_keccak stateful precompile (64KiB)
  6-9  (see each bench_N docstring: sync e2e, bench.py legs, log filter,
     resident commit)
  10 chain-level insert with the RESIDENT account trie vs default —
     the end-to-end number for the resident chain integration
  11-12 (dispatch-fusion A/B; interpreter dispatch micro-bench)
  13 chain-level insert with state-backend=bintrie-shadow — dual-root
     commitment overhead, per-backend chain/commit/{mpt,bintrie} timers
  14 serial vs optimistic-parallel (Block-STM) execution worker sweep
  15 staged insert-pipeline depth sweep {0,1,2,3} — recover/execute of
     block k+1 overlapped with commit/write of block k, CPU legs first
  16 resident mesh-width sweep {1,2,4,8} — store/arena rows sharded over
     a device mesh (resident-mesh-devices), CPU default leg first;
     per-shard lane counts + gather bytes ride the flight records
  17 verify-on-read overhead A/B (storage fault armor)
  18 open-loop read-traffic storm A/B (bench_storm.py): lock-free
     ReadView reads vs the chainmu-locked foil under concurrent
     pipelined insert load — saturation goodput + per-method p99
  19 forked execution-shard sweep {1,2,4} vs serial — GIL-free worker
     processes shipping speculative write-sets; conflict-corpus and
     pipelined (depth-2) legs; cores stamped for honest provenance
  20 bytes-per-commit envelope A/B — storage-lean node rows (80 B/leaf
     wire records) vs template full rows vs the planned path's modeled
     upload, roots checked against the CPU host oracle every round
  21 sampling-profiler overhead A/B — profiler off vs 25 Hz vs 100 Hz
     over the config-10-shaped insert leg and the config-18 storm leg;
     mean overhead at 25 Hz gated <= 2% here (the trajectory sentinel
     reports the "overhead" series without gating)

Each line: {"metric", "value", "unit", "vs_baseline", "config"} where
vs_baseline compares the accelerated path against the host baseline of
the same config (>1 is a win; configs with no device leg report 1.0 and
the host number IS the baseline measurement)."""

from __future__ import annotations

import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _emit(config: int, metric: str, value: float, unit: str, vs: float):
    print(json.dumps({
        "config": config,
        "metric": metric,
        "value": round(value, 1),
        "unit": unit,
        "vs_baseline": round(vs, 3),
    }), flush=True)


def _commit_rates(n_leaves: int, repeats: int = 3):
    from bench import build_workload
    from coreth_tpu.native.mpt import plan_commit

    keys, vals, off = build_workload(n_leaves)
    plan = plan_commit(keys, vals, off)
    nodes = plan.num_nodes
    plan.execute_planned()  # device warm-up / compile

    def best(fn):
        b, root = float("inf"), None
        for _ in range(repeats):
            p = plan_commit(keys, vals, off)
            t0 = time.perf_counter()
            r = fn(p)
            b = min(b, time.perf_counter() - t0)
            assert root is None or r == root
            root = r
        return b, root

    cpu_s, cpu_root = best(lambda p: p.execute_cpu(threads=os.cpu_count() or 1))
    dev_s, dev_root = best(lambda p: p.execute_planned())
    assert cpu_root == dev_root
    return nodes, nodes / cpu_s, nodes / dev_s


def bench_1():
    nodes, cpu, dev = _commit_rates(
        int(os.environ.get("CORETH_TPU_BENCH_LEAVES", "200000")))
    _emit(1, "trie_commit_nodes_per_sec", dev, "nodes/s", dev / cpu)


def bench_2():
    nodes, cpu, dev = _commit_rates(
        int(os.environ.get("CORETH_TPU_BENCH_1M_LEAVES", "1000000")),
        repeats=2)
    _emit(2, "intermediate_root_1m_nodes_per_sec", dev, "nodes/s", dev / cpu)


def _block_insert_rate(resident: bool = False, state_backend: str = "mpt",
                       parallel_workers: int = 0, pipeline_depth: int = 0,
                       template_residency: bool = False,
                       insert_pipeline_depth: int = 0,
                       per_block: int = 500, mesh_devices: int = 0,
                       db_verify_on_read: bool = False,
                       exec_shards: int = 0,
                       conflict_corpus: bool = False):
    """1k-tx block processing: build the blocks, then time insert_block
    (ecrecover via the native batch + EVM + state commit). Returns
    (n_txs, txs_per_sec). resident=True routes the account trie through
    the device-resident mirror (CacheConfig.resident_account_trie);
    pipeline_depth>0 lets that many verified commits stay in flight on
    the device (config-10's pipelined A/B leg); template_residency=True
    runs the planned-semantics/resident-cost template mode;
    state_backend="bintrie-shadow" mounts the dual-root commitment
    shadow (config-13 measures its overhead); parallel_workers>0 runs
    the optimistic Block-STM executor (config-14 A/Bs it vs serial);
    insert_pipeline_depth>0 mounts the staged insert pipeline (config-15
    overlaps recover/execute of block k+1 with commit/write of block k —
    the timed region includes the pipeline drain so queued speculation
    can't flatter the rate). per_block sets txs per generated block
    (smaller blocks -> more blocks -> more stage handoffs to overlap).
    exec_shards>0 dispatches speculation to forked GIL-free worker
    processes (config-19 A/Bs it vs serial); conflict_corpus=True makes
    every 4th tx a shared-slot contract call, the shape whose stale
    shipped reads force parent-side re-execution."""
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

    n_txs = int(os.environ.get("CORETH_TPU_BENCH_BLOCK_TXS", "1000"))
    keys = [i.to_bytes(2, "big") * 16 for i in range(1, n_txs + 1)]
    addrs = [priv_to_address(k) for k in keys]
    signer = Signer(43112)

    # sstore(calldata[0], calldata[32]); sstore(0, sload(0)+1); stop —
    # every call bumps slot 0, the conflict shape config-19's leg needs
    counter_code = bytes.fromhex(
        "6000356020359055600054600101600055") + b"\x00"
    counter_addr = b"\xc0" * 19 + b"\x01"
    alloc = {a: GenesisAccount(balance=10**21) for a in addrs}
    if conflict_corpus:
        alloc[counter_addr] = GenesisAccount(balance=0, code=counter_code)

    diskdb = MemoryDB()
    genesis = Genesis(
        config=params.TEST_CHAIN_CONFIG,
        gas_limit=params.CORTINA_GAS_LIMIT,
        alloc=alloc,
    )
    chain = BlockChain(
        diskdb,
        CacheConfig(pruning=True, resident_account_trie=resident,
                    state_backend=state_backend,
                    evm_parallel_workers=parallel_workers,
                    evm_exec_shards=exec_shards,
                    resident_pipeline_depth=pipeline_depth,
                    resident_template_residency=template_residency,
                    insert_pipeline_depth=insert_pipeline_depth,
                    resident_mesh_devices=mesh_devices,
                    db_verify_on_read=db_verify_on_read),
        params.TEST_CHAIN_CONFIG,
        genesis, new_dummy_engine(),
        state_database=Database(TrieDatabase(diskdb)),
    )
    if resident and chain.mirror is None:
        # silent fallback (no native incremental planner) would time the
        # default path twice and report a bogus ~1.0 "parity"
        chain.stop()
        raise RuntimeError("resident mode unavailable (native planner)")
    _LAST_INSERT_INFO["host_mode"] = (
        chain.mirror.host_mode if chain.mirror is not None else None)

    # gas limits cap a block well under 1k transfers; the workload
    # spans ceil(n/per_block) full blocks (core/bench_test.go ring1000
    # shape), timed over all inserts
    n_blocks = (n_txs + per_block - 1) // per_block
    if resident and n_blocks < 2:
        # the resident mirror runs one commit behind the chain head: a
        # single-block leg never flushes a steady-state commit, so its
        # flight record shows zero device bytes — which would be recorded
        # as a real (and spectacular) measurement. Refuse instead.
        chain.stop()
        raise ValueError(
            f"resident leg needs >= 2 blocks to measure a steady-state "
            f"commit (n_txs={n_txs}, per_block={per_block} -> "
            f"{n_blocks} block); raise CORETH_TPU_BENCH_BLOCK_TXS or "
            f"lower per_block")

    def gen(i, bg):
        bf = bg.base_fee() or params.APRICOT_PHASE3_INITIAL_BASE_FEE
        for j in range(i * per_block, min((i + 1) * per_block, n_txs)):
            if conflict_corpus and j % 4 == 0:
                data = (j % 2).to_bytes(32, "big") + j.to_bytes(32, "big")
                tx = Transaction(
                    type=2, chain_id=43112, nonce=0, max_fee=bf * 2,
                    max_priority_fee=0, gas=100_000, to=counter_addr,
                    value=0, data=data,
                )
            else:
                tx = Transaction(
                    type=2, chain_id=43112, nonce=0, max_fee=bf * 2,
                    max_priority_fee=0, gas=21000,
                    to=(0x8000 + j).to_bytes(20, "big"), value=1,
                )
            bg.add_tx(signer.sign(tx, keys[j]))

    blocks, _ = generate_chain(
        chain.config, chain.current_block, chain.engine,
        chain.state_database, n_blocks, gen=gen,
    )
    for b in blocks:
        for t in b.transactions:
            t._sender = None  # generation cached senders; clear so
            # insert_block pays the real batched-ecrecover cost

    t0 = time.perf_counter()
    for b in blocks:
        chain.insert_block(b)
    if chain.pipeline is not None:
        chain.pipeline.drain()  # inserts are async under the pipeline
    dt = time.perf_counter() - t0
    chain.stop()  # drains the write tail, so "write" stamps are final
    _LAST_INSERT_INFO["flight"] = chain.flight_recorder.last()
    _LAST_INSERT_INFO["shards"] = (
        chain.mirror.shards if chain.mirror is not None else None)
    _LAST_INSERT_INFO["shard_lanes"] = (
        list(getattr(chain.mirror.ex, "last_shard_lanes", []))
        if chain.mirror is not None and chain.mirror.ex is not None
        else None)
    shadow = getattr(chain.state_database, "shadow", None)
    _LAST_INSERT_INFO["shadow"] = (
        shadow.status() if shadow is not None else None)
    return n_txs, n_txs / dt


_DEFAULT_INSERT_RATE = None  # bench_3 result, reused by bench_10
_LAST_INSERT_INFO: dict = {}  # mirror mode of the last _block_insert_rate


def bench_3():
    global _DEFAULT_INSERT_RATE
    n_txs, rate = _block_insert_rate()
    _DEFAULT_INSERT_RATE = rate
    _emit(3, "block_insert_1k_txs_per_sec", rate, "txs/s", 1.0)


def bench_4():
    """Range-proof verification throughput (sync client hot loop)."""
    from coreth_tpu.ethdb import MemoryDB
    from coreth_tpu.native import keccak256
    from coreth_tpu.state.database import Database
    from coreth_tpu.state.statedb import StateDB
    from coreth_tpu.sync.handlers import LeafsRequestHandler
    from coreth_tpu.sync.messages import LeafsRequest
    from coreth_tpu.trie.node import EMPTY_ROOT
    from coreth_tpu.trie.proof_range import verify_range_proof
    from coreth_tpu.trie.triedb import TrieDatabase

    n = int(os.environ.get("CORETH_TPU_BENCH_PROOF_ACCOUNTS", "20000"))
    diskdb = MemoryDB()
    tdb = TrieDatabase(diskdb)
    st = StateDB(EMPTY_ROOT, Database(tdb))
    for i in range(1, n + 1):
        st.add_balance(i.to_bytes(20, "big"), 10**15 + i)
    root = st.commit()
    tdb.commit(root)
    handler = LeafsRequestHandler(tdb)

    # fetch all 1024-leaf batches once, then time pure verification
    batches = []
    start = b""
    while True:
        resp = handler.on_leafs_request(LeafsRequest(root=root, start=start))
        proof_db = {keccak256(b): b for b in resp.proof_vals} or None
        batches.append((start, resp, proof_db))
        if not resp.more:
            break
        start = (int.from_bytes(resp.keys[-1], "big") + 1).to_bytes(32, "big")

    t0 = time.perf_counter()
    leaves = 0
    for start, resp, proof_db in batches:
        first = start if start else (resp.keys[0] if resp.keys else b"\x00" * 32)
        verify_range_proof(root, first, resp.keys[-1] if resp.keys else first,
                           resp.keys, resp.vals, proof_db)
        leaves += len(resp.keys)
    dt = time.perf_counter() - t0
    _emit(4, "range_proof_verify_leaves_per_sec", leaves / dt, "leaves/s", 1.0)


def bench_5():
    """tpu_keccak precompile over the 64KiB workload: device batch path
    vs the threaded host keccak on identical calls."""
    import dataclasses

    from coreth_tpu import params
    from coreth_tpu.accounts.abi import ABI
    from coreth_tpu.precompile import TPU_KECCAK_ADDR, TpuKeccakConfig
    from coreth_tpu.precompile import tpu_keccak as tk

    n_msgs = int(os.environ.get("CORETH_TPU_BENCH_PRECOMPILE_MSGS", "128"))
    msg_len = int(os.environ.get("CORETH_TPU_BENCH_PRECOMPILE_LEN", "512"))
    rng = random.Random(3)
    msgs = [rng.randbytes(msg_len) for _ in range(n_msgs)]
    abi = ABI([{
        "type": "function", "name": "keccak256Batch",
        "inputs": [{"name": "m", "type": "bytes[]"}],
        "outputs": [{"name": "d", "type": "bytes32[]"}],
    }])
    packed = abi.pack("keccak256Batch", msgs)
    cfg = dataclasses.replace(
        params.TEST_CHAIN_CONFIG,
        precompile_upgrades=(TpuKeccakConfig(timestamp=0),),
    )
    contract = cfg.precompile_upgrades[0].contract()

    def run_call():
        ret, _ = contract.run(None, b"\xcc" * 20, TPU_KECCAK_ADDR, packed,
                              10**9, True)
        return ret

    # warm both paths
    ref = run_call()
    saved_thresh = tk.DEVICE_THRESHOLD

    def best(repeats=5):
        b = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            assert run_call() == ref
            b = min(b, time.perf_counter() - t0)
        return b

    dev_s = best()
    tk.DEVICE_THRESHOLD = 10**9  # force the host path
    try:
        cpu_s = best()
    finally:
        tk.DEVICE_THRESHOLD = saved_thresh
    total_bytes = n_msgs * msg_len
    _emit(5, "precompile_keccak_mb_per_sec",
          total_bytes / dev_s / 1e6, "MB/s", cpu_s / dev_s)


def bench_6():
    """Chain-level blocks/sec through insert_block: device_hasher=planned
    vs the CPU recursive hasher, identical blocks (VERDICT r2 #1's chain
    bench — measures the production path, not a standalone commit)."""
    from coreth_tpu import params
    from coreth_tpu.consensus.dummy import new_dummy_engine
    from coreth_tpu.core.blockchain import BlockChain, CacheConfig
    from coreth_tpu.core.chain_makers import generate_chain
    from coreth_tpu.core.genesis import Genesis, GenesisAccount
    from coreth_tpu.core.types import Signer, Transaction
    from coreth_tpu.crypto.secp256k1 import priv_to_address
    from coreth_tpu.ethdb import MemoryDB
    from coreth_tpu.ops.device import PlannedModeKeccak
    from coreth_tpu.ops.keccak_jax import BatchedKeccak
    from coreth_tpu.state.database import Database
    from coreth_tpu.trie.triedb import TrieDatabase

    n_senders = int(os.environ.get("CORETH_TPU_BENCH_CHAIN_SENDERS", "400"))
    n_blocks = int(os.environ.get("CORETH_TPU_BENCH_CHAIN_BLOCKS", "4"))
    keys = [i.to_bytes(2, "big") * 16 for i in range(1, n_senders + 1)]
    addrs = [priv_to_address(k) for k in keys]
    signer = Signer(43112)

    def make_chain(marker):
        diskdb = MemoryDB()
        genesis = Genesis(
            config=params.TEST_CHAIN_CONFIG,
            gas_limit=params.CORTINA_GAS_LIMIT,
            alloc={a: GenesisAccount(balance=10**21) for a in addrs},
        )
        return BlockChain(
            diskdb, CacheConfig(pruning=True), params.TEST_CHAIN_CONFIG,
            genesis, new_dummy_engine(),
            state_database=Database(TrieDatabase(diskdb, batch_keccak=marker)),
        )

    def gen(i, bg):
        bf = bg.base_fee() or params.APRICOT_PHASE3_INITIAL_BASE_FEE
        for j, key in enumerate(keys):
            tx = Transaction(
                type=2, chain_id=43112, nonce=i, max_fee=bf * 2,
                max_priority_fee=0, gas=21000,
                to=(0xA000 + i * n_senders + j).to_bytes(20, "big"), value=1,
            )
            bg.add_tx(signer.sign(tx, key))

    seed_chain = make_chain(None)
    blocks, _ = generate_chain(
        seed_chain.config, seed_chain.current_block, seed_chain.engine,
        seed_chain.state_database, n_blocks, gen=gen,
    )
    seed_chain.stop()

    def run(marker):
        chain = make_chain(marker)
        t0 = time.perf_counter()
        for b in blocks:
            chain.insert_block(b)
        dt = time.perf_counter() - t0
        tip = chain.current_block
        chain.stop()
        return dt, tip.root

    planned_marker = PlannedModeKeccak(BatchedKeccak().digests)
    run(planned_marker)  # warm compile
    dev_s, dev_root = run(planned_marker)
    cpu_s, cpu_root = run(None)
    assert dev_root == cpu_root
    _emit(6, "chain_insert_blocks_per_sec", n_blocks / dev_s, "blocks/s",
          cpu_s / dev_s)


def bench_7():
    """Incremental churn commits on a warm 1M trie (bench.py's
    incremental leg as a standalone config)."""
    from bench import PhaseWatchdog, run_incremental

    wd = PhaseWatchdog(time.monotonic() + 1800)
    out = run_incremental(wd, None)
    wd.cancel()
    if "inc_tpu_nodes_per_sec" in out:
        _emit(7, "incremental_commit_nodes_per_sec",
              out["inc_tpu_nodes_per_sec"], "nodes/s", out["inc_vs_cpu"])
    else:
        print(json.dumps({"config": 7, **out}), flush=True)


def bench_8():
    """Log-filter throughput over the bloom-bit index (BASELINE row
    'Log-filter throughput', reference harness eth/filters/bench_test.go):
    build a chain of log-emitting blocks, then time repeated topic-
    filtered eth_getLogs over the whole range."""
    from coreth_tpu import params
    from coreth_tpu.core.genesis import Genesis, GenesisAccount
    from coreth_tpu.core.types import Signer, Transaction
    from coreth_tpu.crypto.secp256k1 import priv_to_address
    from coreth_tpu.ethdb import MemoryDB
    from coreth_tpu.evm import opcodes as OP
    from coreth_tpu.vm.api import create_handlers
    from coreth_tpu.vm.shared_memory import Memory
    from coreth_tpu.vm.vm import VM, SnowContext, VMConfig

    n_blocks = int(os.environ.get("CORETH_TPU_BENCH_LOG_BLOCKS", "48"))
    txs_per_block = int(os.environ.get("CORETH_TPU_BENCH_LOG_TXS", "8"))
    key = b"\x31" * 32
    addr = priv_to_address(key)
    topic = (0x1234).to_bytes(32, "big")
    emitter = bytes([
        OP.PUSH1, 0x42, OP.PUSH1, 0x00, OP.MSTORE,
        OP.PUSH32]) + topic + bytes([
        OP.PUSH1, 0x20, OP.PUSH1, 0x00, OP.LOG0 + 1, OP.STOP])

    vm = VM()
    genesis = Genesis(
        config=params.TEST_CHAIN_CONFIG, gas_limit=params.CORTINA_GAS_LIMIT,
        alloc={addr: GenesisAccount(balance=10**21),
               b"\xee" * 20: GenesisAccount(code=emitter, balance=0)},
    )
    clock = [0]

    def tick():
        clock[0] = vm.blockchain.current_block.time + 2
        return clock[0]

    vm.initialize(SnowContext(shared_memory=Memory()), MemoryDB(), genesis,
                  VMConfig(clock=tick))
    # shrink the bloom-bit index section so the bench's chain COMPLETES
    # sections (default 4096 blocks would leave the index forever cold and
    # this bench would silently measure only the header-bloom fallback)
    from coreth_tpu.core.bloom_index import BloomIndexer

    vm.blockchain.bloom_indexer = BloomIndexer(
        vm.blockchain.diskdb, section_size=16)
    signer = Signer(43112)
    nonce = 0
    for _ in range(n_blocks):
        for _ in range(txs_per_block):
            tx = Transaction(type=2, chain_id=43112, nonce=nonce,
                             max_fee=10**12, max_priority_fee=10**9,
                             gas=100_000, to=b"\xee" * 20, value=0)
            vm.issue_tx(signer.sign(tx, key))
            nonce += 1
        blk = vm.build_block()
        blk.verify()
        blk.accept()
    vm.blockchain.drain_acceptor_queue()

    server = create_handlers(vm)
    # from block 0 (section-aligned) so indexed sections actually serve
    crit = {"fromBlock": "0x0", "toBlock": hex(n_blocks),
            "topics": ["0x" + topic.hex()]}
    # prove the index engages: count candidate-resolution calls
    idx = vm.blockchain.bloom_indexer
    calls = [0]
    orig_candidates = idx.candidates

    def counted(*a, **kw):
        calls[0] += 1
        return orig_candidates(*a, **kw)

    idx.candidates = counted

    def query():
        raw = server.handle_raw(json.dumps(
            {"jsonrpc": "2.0", "id": 1, "method": "eth_getLogs",
             "params": [crit]}).encode())
        resp = json.loads(raw)
        assert "error" not in resp, resp.get("error")
        return resp["result"]

    logs = query()  # warm caches/index
    total = len(logs)
    assert total == n_blocks * txs_per_block, (total, n_blocks * txs_per_block)
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        got = query()
        best = min(best, time.perf_counter() - t0)
        assert len(got) == total
    assert calls[0] > 0, "bloom-bit index never engaged; bench is mislabeled"
    vm.shutdown()
    _emit(8, "log_filter_logs_per_sec", total / best, "logs/s", 1.0)


def bench_9():
    """Device-resident pipelined commits (bench.py's resident leg:
    deferred absorb + template residency — the round-4 design)."""
    from bench import PhaseWatchdog, run_resident

    wd = PhaseWatchdog(time.monotonic() + 1800)
    out = run_resident(wd)
    wd.cancel()
    if "res_tpu_nodes_per_sec" in out:
        _emit(9, "resident_commit_nodes_per_sec",
              out["res_tpu_nodes_per_sec"], "nodes/s", out["res_vs_cpu"])
        print(json.dumps({"config": 9, **{
            k: v for k, v in out.items()
            if k.startswith(("res_h2d", "res_modeled", "res_overlap",
                             "res_template"))
        }}), flush=True)
    else:
        print(json.dumps({"config": 9, **out}), flush=True)


_PLAN_CACHE = ("resident/plan_cache/hits", "resident/plan_cache/misses")
_SNAP_COUNTERS = (
    "state/snap/hits", "state/snap/misses", "state/snap/generating",
)


def _flight_attribution(recs):
    """Per-leg attribution aggregated from the chain's flight recorder —
    the same per-block records debug_blockFlightRecord serves, summed
    over the leg. Replaces the PR-2-era raw registry scrape: the records
    are per-chain, so consecutive legs in one process can't bleed into
    each other's deltas."""
    phases: dict = {}
    resident: dict = {}
    counters: dict = {}
    overlaps: list = []
    shards: list = []
    for rec in recs:
        for k, v in rec.get("phases", {}).items():
            phases[k] = phases.get(k, 0.0) + v
        for k, v in rec.get("resident", {}).items():
            if k == "overlap_fraction":  # a ratio, not a duration
                overlaps.append(v)
                continue
            if k == "shards":  # a width, not a duration
                shards.append(v)
                continue
            resident[k] = resident.get(k, 0.0) + v
        for k, v in rec.get("counters", {}).items():
            counters[k] = counters.get(k, 0) + v
    out = {}
    for k in sorted(resident):
        if resident[k] > 0:
            out[k + "_s"] = round(resident[k], 4)
    if overlaps:
        out["overlap_fraction_mean"] = round(
            sum(overlaps) / len(overlaps), 4)
        out["overlap_fraction_max"] = round(max(overlaps), 4)
    # always emitted: a host-mode leg moves no bytes over the link and
    # must say so explicitly (0.0) — a ragged key set here makes the
    # cross-leg comparison average over different columns
    h2d = counters.get("resident/h2d_bytes", 0)
    out["h2d_mb"] = round(h2d / 1e6, 2)
    out["h2d_bytes_per_block"] = int(h2d / max(len(recs), 1))
    # same un-ragged discipline for the mesh columns: an unsharded leg
    # says shards=1 / zero gather bytes, never a missing key
    gather = counters.get("resident/gather_bytes", 0)
    out["gather_mb"] = round(gather / 1e6, 2)
    out["gather_bytes_per_block"] = int(gather / max(len(recs), 1))
    # provenance split (PR 18): gather_bytes above is MEASURED host
    # materialization only; the modeled column is the analytic cross-
    # shard cost ((n-1)/n of the digest store per sharded commit) and
    # absorb_d2h is the measured per-shard readback that replaced the
    # full gather — all three always emitted so a lean/per-shard win
    # shows up as measured 0.0 next to a nonzero model, never as a
    # silently missing key
    gather_mod = counters.get("resident/gather_bytes_modeled", 0)
    out["gather_modeled_mb"] = round(gather_mod / 1e6, 2)
    out["gather_modeled_bytes_per_block"] = int(gather_mod / max(len(recs), 1))
    absorb = counters.get("resident/absorb_d2h_bytes", 0)
    out["absorb_d2h_mb"] = round(absorb / 1e6, 2)
    lean_wire = counters.get("resident/lean_wire_bytes", 0)
    out["lean_wire_mb"] = round(lean_wire / 1e6, 2)
    if shards:
        out["shards"] = int(max(shards))
    for k in sorted(phases):
        if phases[k] > 0:
            out["chain_" + k + "_s"] = round(phases[k], 4)
    for c in _PLAN_CACHE:
        if counters.get(c, 0) > 0:
            out["plan_cache_" + c.rsplit("/", 1)[1]] = int(counters[c])
    for c in _SNAP_COUNTERS:
        if counters.get(c, 0) > 0:
            out["snap_" + c.rsplit("/", 1)[1]] = int(counters[c])
    return out


def bench_10():
    """Chain-level resident-mode insert throughput vs the default path —
    the end-to-end evidence for the resident chain integration (same
    workload as config 3; vs_baseline = resident / default). Reuses
    bench_3's default-leg measurement when it already ran this process
    (a whole-suite run would otherwise pay the 1k pure-Python signings
    a third time). Each leg carries its per-phase attribution summed
    from the chain's flight recorder, so a regression names the phase
    that ate the time instead of just the headline tx/s."""
    from coreth_tpu.native import default_cpu_threads

    # CPU legs land FIRST (before any device op warps process state):
    # the default-path baseline, reused from bench_3 when available
    base_rate = _DEFAULT_INSERT_RATE
    if base_rate is None:
        _, base_rate = _block_insert_rate(resident=False)
    try:
        # cold pass seeds the per-segment-shape jit compiles (persisted by
        # the compilation cache; a node restart reuses them) — the warm
        # pass is the steady-state number. Both are reported.
        _, cold_rate = _block_insert_rate(resident=True)
        cold_phases = _flight_attribution(_LAST_INSERT_INFO.get("flight", []))
        n_txs, res_rate = _block_insert_rate(resident=True)
        warm_phases = _flight_attribution(_LAST_INSERT_INFO.get("flight", []))
    except RuntimeError as e:
        print(json.dumps({"config": 10, "skipped": str(e)}), flush=True)
        return
    _emit(10, "resident_block_insert_txs_per_sec", res_rate, "txs/s",
          res_rate / base_rate)
    print(json.dumps({
        "config": 10,
        "cold_txs_per_sec": round(cold_rate, 1),
        "warm_txs_per_sec": round(res_rate, 1),
        "cpu_threads": default_cpu_threads(),
        "host_mode": _LAST_INSERT_INFO.get("host_mode"),
        "phases_cold": cold_phases,
        "phases_warm": warm_phases,
        "note": "cold = first-ever run compiling per-segment-shape device "
                "programs (persisted; restarts reuse them)",
    }), flush=True)

    # A/B legs: cross-commit pipelining (depth 2) and template
    # residency vs the serial resident leg above. Warm numbers (one
    # cold pass each to land compiles); the flight attribution carries
    # h2d bytes per block and the measured overlap fraction — the
    # artifact for "pipelining buys nodes/max(plan, transfer)".
    try:
        _block_insert_rate(resident=True, pipeline_depth=2)
        _, pipe_rate = _block_insert_rate(resident=True, pipeline_depth=2)
        pipe_phases = _flight_attribution(
            _LAST_INSERT_INFO.get("flight", []))
        _block_insert_rate(resident=True, template_residency=True)
        _, tmpl_rate = _block_insert_rate(resident=True,
                                          template_residency=True)
        tmpl_phases = _flight_attribution(
            _LAST_INSERT_INFO.get("flight", []))
    except RuntimeError as e:
        print(json.dumps({"config": 10, "ab_skipped": str(e)}), flush=True)
        return
    print(json.dumps({
        "config": 10,
        "ab": "pipelined-depth-2 / template-residency vs serial resident",
        # host_mode=True means the CPU fast path auto-engaged (no TPU
        # backend): pipelining/template are inert and the A/B reads ~1.0
        # by construction — the device-side artifact is config 9's.
        "host_mode": _LAST_INSERT_INFO.get("host_mode"),
        "pipelined_txs_per_sec": round(pipe_rate, 1),
        "pipelined_vs_serial_resident": round(pipe_rate / res_rate, 3),
        "template_txs_per_sec": round(tmpl_rate, 1),
        "template_vs_serial_resident": round(tmpl_rate / res_rate, 3),
        "phases_pipelined": pipe_phases,
        "phases_template": tmpl_phases,
    }), flush=True)


def bench_11():
    """Dispatch-fusion A/B (VERDICT r4 #3): the same 20k-leaf planned
    commit through the old per-segment dispatches vs the fused
    single-dispatch program, roots asserted against the host oracle.
    vs_baseline = per-segment time / fused time (>1 = fusion wins; the
    gap scales with link latency, so the hardware number is the
    meaningful one — per-segment pays ~n_segments round trips, fused
    pays one)."""
    from bench import best_of, build_workload
    from coreth_tpu.native.mpt import plan_commit
    from coreth_tpu.ops.keccak_planned import PlannedCommit

    keys, vals, off = build_workload(20000)
    plan = plan_commit(keys, vals, off)
    cpu_root = plan.execute_cpu(threads=os.cpu_count() or 1)
    fused = PlannedCommit(fused=True)
    perseg = PlannedCommit(fused=False)

    # plan ONCE outside the timer (matching _commit_rates): the timed
    # region is transfers + dispatch + kernel only, so the fused/per-seg
    # ratio isolates the dispatch cost this config exists to measure
    def run(runner):
        root = plan.execute_planned(runner)
        assert root == cpu_root, "device root mismatch"

    run(fused)
    run(perseg)  # compiles
    t_fused, _ = best_of(lambda: run(fused), 3)
    t_seg, _ = best_of(lambda: run(perseg), 3)
    print(json.dumps({
        "config": 11,
        "fused_dispatches": fused.last_dispatches,
        "fused_transfers": fused.last_transfers,
        "per_segment_dispatches": perseg.last_dispatches,
        "per_segment_transfers": perseg.last_transfers,
        "per_segment_nodes_per_sec": round(plan.num_nodes / t_seg, 1),
    }), flush=True)
    _emit(11, "fused_commit_nodes_per_sec",
          round(plan.num_nodes / t_fused, 1), "nodes/s",
          round(t_seg / t_fused, 3))


def bench_12():
    """Interpreter dispatch micro-bench (benches/bench_evm.py): ops/s
    for a hot-loop contract, legacy dict dispatch vs the fast
    instruction-stream loop (cold + warm stream cache). vs_baseline =
    warm-fast / legacy — the per-opcode dispatch speedup, tracked per
    round like trie_commit_nodes_per_sec."""
    import bench_evm

    res = bench_evm.measure()
    print(json.dumps(dict(config=12, **res)), flush=True)
    _emit(12, "evm_fast_dispatch_ops_per_sec",
          res["fast_warm_ops_per_sec"], "ops/s",
          res["speedup_warm_vs_legacy"])


def bench_13():
    """Dual-root shadow overhead (COMMITMENT.md): the config-3 insert
    workload with state-backend=bintrie-shadow — every commit advances
    BOTH the consensus MPT root and the experimental binary-Merkle root,
    with divergence checks live. Reports the per-backend commit-timer
    split (chain/commit/{mpt,bintrie}) and vs_baseline = shadow txs/s /
    plain txs/s (<1; the gap IS the dual-commit overhead). The leg must
    finish with zero quarantines — a quarantine here is a correctness
    regression in the bintrie, not a perf number."""
    from coreth_tpu.metrics import default_registry

    def _commit_totals():
        out = {}
        for name in ("chain/commit/mpt", "chain/commit/bintrie"):
            t = default_registry.timer(name)
            out[name] = (t.count(), t.total())
        return out

    before = _commit_totals()
    n_txs, shadow_rate = _block_insert_rate(state_backend="bintrie-shadow")
    after = _commit_totals()
    shadow_status = _LAST_INSERT_INFO.get("shadow") or {}
    base_rate = _DEFAULT_INSERT_RATE
    if base_rate is None:
        _, base_rate = _block_insert_rate()
    timers = {}
    for name in ("chain/commit/mpt", "chain/commit/bintrie"):
        c0, t0 = before[name]
        c1, t1 = after[name]
        timers[name.rsplit("/", 1)[1]] = {
            "commits": c1 - c0, "total_s": round(t1 - t0, 4),
        }
    quarantines = 1 if shadow_status.get("quarantined") else 0
    print(json.dumps({
        "config": 13,
        "commit_timers": timers,
        "shadow": shadow_status,
        "quarantines": quarantines,
    }), flush=True)
    _emit(13, "shadow_block_insert_txs_per_sec", shadow_rate, "txs/s",
          shadow_rate / base_rate)


def bench_14():
    """Serial vs optimistic-parallel execution A/B (PERF.md r9): the
    config-3 insert workload (disjoint-sender transfers — the
    best-case, conflict-free shape) run serial then under a worker
    sweep. Reports per-worker txs/s, the exec/parallel/* counter deltas
    (conflicts/reexecs/fallbacks — all must be 0 on this workload: a
    nonzero fallback means the engine bailed and the A/B is measuring
    serial twice), and the chain/execute/{schedule,execute,validate,
    fold} phase split. vs_baseline = best parallel txs/s / serial
    txs/s. On a GIL-bound single-core host the win comes from the
    journal-free view + fold, not thread parallelism — expect a modest
    ratio here and report it honestly."""
    from coreth_tpu.metrics import default_registry

    counter_names = ("exec/parallel/conflicts", "exec/parallel/reexecs",
                     "exec/parallel/fallbacks")
    phase_names = ("chain/execute/schedule", "chain/execute/execute",
                   "chain/execute/validate", "chain/execute/fold")

    def _snap():
        counters = {n: default_registry.counter(n).count()
                    for n in counter_names}
        phases = {n: default_registry.timer(n).total() for n in phase_names}
        return counters, phases

    _, serial_rate = _block_insert_rate()
    sweep = {}
    best_rate = 0.0
    for workers in (1, 2, 4):
        c0, p0 = _snap()
        _, rate = _block_insert_rate(parallel_workers=workers)
        c1, p1 = _snap()
        modes = [r.get("parallel", {}).get("mode")
                 for r in _LAST_INSERT_INFO.get("flight", [])]
        sweep[workers] = {
            "txs_per_sec": round(rate, 1),
            "ratio_vs_serial": round(rate / serial_rate, 3),
            "parallel_blocks": modes.count("parallel"),
            "serial_blocks": len(modes) - modes.count("parallel"),
            "counters": {n.rsplit("/", 1)[1]: c1[n] - c0[n]
                         for n in counter_names},
            "phases_s": {n.rsplit("/", 1)[1]: round(p1[n] - p0[n], 4)
                         for n in phase_names},
        }
        best_rate = max(best_rate, rate)
    print(json.dumps({
        "config": 14,
        "serial_txs_per_sec": round(serial_rate, 1),
        "workers": sweep,
    }), flush=True)
    _emit(14, "parallel_block_insert_txs_per_sec", best_rate, "txs/s",
          best_rate / serial_rate)


def bench_15():
    """Staged insert-pipeline A/B (config-15, ROADMAP item 4a): the
    config-3 insert workload at per_block=125 (more, smaller blocks —
    more commit/speculate handoffs for the pipeline to overlap), swept
    over insert-pipeline-depth {0,1,2,3}. All legs are CPU and land
    first; a resident device leg at the best depth follows only when
    the native planner is mounted. Per depth reports txs/s, the
    spec/fallback block split from the flight records, and the mean
    chain-level overlap fraction (speculation time of block k+1 inside
    block k's commit interval). On this GIL-bound single-core host the
    overlap is concurrency, not parallelism — expect fractions well
    above 0 but a modest rate ratio, and report both honestly.
    vs_baseline = best pipelined txs/s / depth-0 txs/s."""
    per_block = 125
    _, serial_rate = _block_insert_rate(per_block=per_block)
    sweep = {}
    best_rate = serial_rate
    best_depth = 0
    for depth in (1, 2, 3):
        _, rate = _block_insert_rate(insert_pipeline_depth=depth,
                                     per_block=per_block)
        pipes = [r.get("pipeline", {})
                 for r in _LAST_INSERT_INFO.get("flight", [])]
        modes = [p.get("mode") for p in pipes]
        overlaps = [p.get("overlap_fraction", 0.0) or 0.0 for p in pipes]
        sweep[depth] = {
            "txs_per_sec": round(rate, 1),
            "ratio_vs_serial": round(rate / serial_rate, 3),
            "spec_blocks": modes.count("spec"),
            "fallback_blocks": modes.count("serial-fallback"),
            "mean_overlap_fraction": round(
                sum(overlaps) / len(overlaps), 4) if overlaps else 0.0,
        }
        if rate > best_rate:
            best_rate, best_depth = rate, depth
    report = {
        "config": 15,
        "serial_txs_per_sec": round(serial_rate, 1),
        "depths": sweep,
        "best_depth": best_depth,
    }
    # optional device leg, strictly after every CPU leg is recorded:
    # pipelined insert + resident mirror exercises the chain-level
    # overlap the mirror window was built for
    try:
        _, res_rate = _block_insert_rate(
            resident=True, insert_pipeline_depth=max(best_depth, 1),
            per_block=per_block)
        report["resident_txs_per_sec"] = round(res_rate, 1)
        report["resident_host_mode"] = _LAST_INSERT_INFO.get("host_mode")
    except RuntimeError as e:
        report["resident_skipped"] = str(e)
    print(json.dumps(report), flush=True)
    _emit(15, "pipelined_block_insert_txs_per_sec", best_rate, "txs/s",
          best_rate / serial_rate)


def bench_16():
    """Resident mesh-width sweep (config-16, ROADMAP item 2 landed): the
    block-insert workload through the mesh-sharded resident mirror at
    resident-mesh-devices {1,2,4,8}. The CPU default-path leg lands
    FIRST (a hung device still leaves the host number in the
    artifact); each width leg then pins
    the device path (CORETH_TPU_RESIDENT_HOST=0) and reports txs/s plus
    the per-shard lane counts of its last commit and the summed gather
    bytes from the flight records. A width the backend cannot host
    (fewer visible devices — the virtual CPU mesh needs
    XLA_FLAGS=--xla_force_host_platform_device_count=8 before the first
    jax call) is recorded as skipped with the typed MeshConfigError
    message instead of wedging deep inside GSPMD. The workload is
    scaled down vs config 3 (CORETH_TPU_BENCH_MESH_TXS, default 400)
    because XLA-CPU sharded compiles dominate at standin widths; the
    CPU baseline leg uses the SAME scaled workload, so the ratio stays
    apples-to-apples. vs_baseline = best mesh txs/s / CPU default."""
    import jax

    n_txs = os.environ.get("CORETH_TPU_BENCH_MESH_TXS", "400")
    old_txs = os.environ.get("CORETH_TPU_BENCH_BLOCK_TXS")
    old_host = os.environ.get("CORETH_TPU_RESIDENT_HOST")
    os.environ["CORETH_TPU_BENCH_BLOCK_TXS"] = n_txs
    # at least 2 blocks per leg: the dispatch path resolves one commit
    # behind, so a 1-block run lands its only device commit at stop()
    # and the flight records show zero gather/h2d bytes
    per_block = max(50, int(n_txs) // 2)
    try:
        _, base_rate = _block_insert_rate(per_block=per_block)
        sweep: dict = {}
        best_rate, best_width = 0.0, 0
        os.environ["CORETH_TPU_RESIDENT_HOST"] = "0"
        for width in (1, 2, 4, 8):
            try:
                _, rate = _block_insert_rate(resident=True,
                                             mesh_devices=width,
                                             per_block=per_block)
            except Exception as e:  # MeshConfigError / planner absent
                sweep[width] = {"skipped": str(e)}
                continue
            attr = _flight_attribution(_LAST_INSERT_INFO.get("flight", []))
            sweep[width] = {
                "txs_per_sec": round(rate, 1),
                "ratio_vs_default": round(rate / base_rate, 3),
                "shards": _LAST_INSERT_INFO.get("shards"),
                "last_shard_lanes": _LAST_INSERT_INFO.get("shard_lanes"),
                "gather_mb": attr.get("gather_mb"),
                "gather_bytes_per_block": attr.get(
                    "gather_bytes_per_block"),
                "gather_modeled_mb": attr.get("gather_modeled_mb"),
                "gather_modeled_bytes_per_block": attr.get(
                    "gather_modeled_bytes_per_block"),
                "absorb_d2h_mb": attr.get("absorb_d2h_mb"),
                "h2d_mb": attr.get("h2d_mb"),
            }
            if rate > best_rate:
                best_rate, best_width = rate, width
    finally:
        if old_txs is None:
            os.environ.pop("CORETH_TPU_BENCH_BLOCK_TXS", None)
        else:
            os.environ["CORETH_TPU_BENCH_BLOCK_TXS"] = old_txs
        if old_host is None:
            os.environ.pop("CORETH_TPU_RESIDENT_HOST", None)
        else:
            os.environ["CORETH_TPU_RESIDENT_HOST"] = old_host
    print(json.dumps({
        "config": 16,
        "devices_visible": len(jax.devices()),
        "n_txs": int(n_txs),
        "cpu_default_txs_per_sec": round(base_rate, 1),
        "widths": sweep,
        "best_width": best_width,
    }), flush=True)
    if best_width:
        _emit(16, "mesh_block_insert_txs_per_sec", best_rate, "txs/s",
              best_rate / base_rate)
    else:
        print(json.dumps({
            "config": 16,
            "skipped": "no mesh width ran (see widths for reasons)",
        }), flush=True)


def bench_17():
    """Verify-on-read overhead A/B (config-17, storage fault armor):
    the config-3 insert workload with db-verify-on-read off (baseline)
    then on — every hash-addressed payload read back from disk pays a
    keccak recompute at the storage boundary. Both legs are CPU and the
    baseline lands first (the wedge-proof bench.py policy). The armor
    leg also reports the db/verify_failures delta, which must be 0 on a
    clean run: a nonzero delta means the bench corrupted its own reads
    and the ratio is measuring error handling, not verification.
    vs_baseline = verify-on txs/s / verify-off txs/s — the price of the
    armor, expected close to 1.0 on the MemoryDB insert path (inserts
    are write-heavy; the verify tax lands on the read side)."""
    from coreth_tpu.core import rawdb
    from coreth_tpu.metrics import default_registry

    _, off_rate = _block_insert_rate()
    failures0 = default_registry.counter("db/verify_failures").count()
    try:
        _, on_rate = _block_insert_rate(db_verify_on_read=True)
    finally:
        # the knob mounts into a process-wide rawdb flag at chain boot;
        # leave the suite's later configs unarmored
        rawdb.set_verify_on_read(False)
    failures = default_registry.counter("db/verify_failures").count() \
        - failures0
    print(json.dumps({
        "config": 17,
        "verify_off_txs_per_sec": round(off_rate, 1),
        "verify_on_txs_per_sec": round(on_rate, 1),
        "verify_failures": failures,
    }), flush=True)
    _emit(17, "verify_on_read_block_insert_txs_per_sec", on_rate, "txs/s",
          on_rate / off_rate)


def bench_18():
    """Open-loop read-traffic storm (PR 16, BENCH_STORM config): the
    lock-free ReadView read tier vs the chainmu-locked foil, both under
    a concurrent pipelined insert load drawn from a pregenerated block
    corpus. The suite runs bench_storm's abbreviated ladder (the full
    artifact run is `python benches/bench_storm.py --round NN`); the
    emitted metric is the view leg's saturation goodput and vs_baseline
    is view/locked — the lock-discipline win, >1 means the lock-free
    tier saturates higher. Host-concurrency bench: CPU-only by design,
    no device leg."""
    import bench_storm

    result = bench_storm.main(["--duration", "1.0",
                               "--rates", "1000", "2000", "4000", "8000",
                               "--corpus", "200"])
    _emit(18, "storm_view_saturation_per_sec",
          result["legs"]["view"]["saturation_per_sec"], "req/s",
          result["view_vs_locked_saturation"])


def bench_19():
    """Forked execution-shard A/B (config-19, PERF.md r14): the
    config-14 disjoint-sender insert workload, CPU serial leg FIRST,
    then under exec-shard counts {1,2,4} — GIL-free forked workers
    executing speculative txs and shipping write-sets back over pipes.
    Counter deltas (dispatches/fallbacks/crashes/respawns) guard against
    the engine silently bailing: a sweep whose blocks all fell back is
    measuring serial twice, and the per-leg shard/serial block split
    says so. Two extra legs: the conflict-shaped corpus (every 4th tx a
    shared-slot contract call — stale shipped reads force parent-side
    re-execution, the honest cost of speculation) and the config-15
    depth-2 pipeline rerun with shards in the submit stage. The
    companion line stamps os.cpu_count() as provenance: on a single-core
    box the honest expectation is ~1.0x (fork + pipe overhead buys no
    parallelism), and the number is reported, not gated away."""
    from coreth_tpu.metrics import default_registry

    counter_names = ("exec/shard/dispatches", "exec/shard/fallbacks",
                     "exec/shard/crashes", "exec/shard/respawns")

    def _snap():
        return {n: default_registry.counter(n).count()
                for n in counter_names}

    _, serial_rate = _block_insert_rate()
    sweep = {}
    best_rate, best_width = 0.0, 0
    for shards in (1, 2, 4):
        c0 = _snap()
        _, rate = _block_insert_rate(exec_shards=shards)
        c1 = _snap()
        modes = [r.get("parallel", {}).get("mode")
                 for r in _LAST_INSERT_INFO.get("flight", [])]
        sweep[shards] = {
            "txs_per_sec": round(rate, 1),
            "ratio_vs_serial": round(rate / serial_rate, 3),
            "shard_blocks": modes.count("shards"),
            "serial_blocks": len(modes) - modes.count("shards"),
            "counters": {n.rsplit("/", 1)[1]: c1[n] - c0[n]
                         for n in counter_names},
        }
        if rate > best_rate:
            best_rate, best_width = rate, shards
    # conflict-shaped corpus at the best width (smaller blocks keep the
    # call-heavy shape under the block gas limit)
    _, c_serial = _block_insert_rate(per_block=250, conflict_corpus=True)
    _, c_rate = _block_insert_rate(per_block=250, conflict_corpus=True,
                                   exec_shards=max(best_width, 2))
    # config-15 rerun: depth-2 pipeline with the shard submit stage
    _, p_serial = _block_insert_rate(insert_pipeline_depth=2, per_block=125)
    _, p_rate = _block_insert_rate(insert_pipeline_depth=2, per_block=125,
                                   exec_shards=max(best_width, 2))
    print(json.dumps({
        "config": 19,
        "host_mode": True,  # CPU-process bench: no device leg by design
        "cores": os.cpu_count(),
        "serial_txs_per_sec": round(serial_rate, 1),
        "shards": sweep,
        "conflict_leg": {
            "serial_txs_per_sec": round(c_serial, 1),
            "sharded_txs_per_sec": round(c_rate, 1),
            "ratio_vs_serial": round(c_rate / c_serial, 3),
        },
        "pipelined_leg": {
            "depth2_txs_per_sec": round(p_serial, 1),
            "depth2_sharded_txs_per_sec": round(p_rate, 1),
            "ratio": round(p_rate / p_serial, 3),
        },
    }), flush=True)
    _emit(19, "sharded_block_insert_txs_per_sec", best_rate, "txs/s",
          best_rate / serial_rate)


def bench_20():
    """Bytes-per-commit envelope A/B (config-20, PR 18 storage-lean node
    rows): the PERF.md template workload (20k leaves, 2k-leaf churn
    rounds) priced three ways — the PLANNED path's modeled upload (every
    dirty node ships its full row, sum(blocks*lanes*136) over the plan's
    segments, a MODEL not a measurement), the TEMPLATE leg's measured
    h2d (fresh rows at 136 B content + 4 B index), and the LEAN leg's
    measured h2d (fresh class-1 rows <= 72 B RLP ship as 72 B content +
    4 B index + 4 B length; the device re-derives the keccak padding).
    CPU host-oracle leg lands FIRST (wedge-proof policy) and every
    device-leg root must match it bit-exactly every round. The headline
    metric is the lean record's wire bytes per leaf (80 B vs the
    template's 140 B full record); the companion line carries the whole
    envelope plus the digest-slot-addressed rawdb footprint A/B of the
    same node set, with the modeled column named as such so the
    trajectory sentinel reports it without gating."""
    import jax

    from coreth_tpu.core import rawdb
    from coreth_tpu.ethdb import MemoryDB
    from coreth_tpu.native.mpt import IncrementalTrie
    from coreth_tpu.ops.keccak_resident import LEAN_WORDS, ResidentExecutor

    n_leaves = int(os.environ.get("CORETH_TPU_BENCH_LEAN_LEAVES", "20000"))
    churn = int(os.environ.get("CORETH_TPU_BENCH_LEAN_CHURN", "2000"))
    rounds = int(os.environ.get("CORETH_TPU_BENCH_LEAN_ROUNDS", "3"))
    if rounds < 2:
        # same footgun the resident block legs guard: the first churn
        # round still carries bootstrap compile/residue effects, so a
        # single round has no steady-state commit to measure
        raise ValueError(
            f"config-20 needs >= 2 churn rounds (got {rounds}); raise "
            f"CORETH_TPU_BENCH_LEAN_ROUNDS")

    rng = random.Random(20)
    state = {rng.randbytes(32): rng.randbytes(32) for _ in range(n_leaves)}
    boot = sorted(state.items())
    keys = sorted(state)
    batches = [[(k, rng.randbytes(32)) for k in rng.sample(keys, churn)]
               for _ in range(rounds)]
    threads = os.cpu_count() or 1

    # CPU host-oracle leg FIRST: the root sequence every device leg must
    # reproduce bit-exactly (a hung device still leaves this in the
    # artifact)
    oracle = IncrementalTrie(boot)
    oracle_roots = [oracle.commit_cpu(threads=threads)]
    for b in batches:
        oracle.update(b)
        oracle_roots.append(oracle.commit_cpu(threads=threads))

    # planned-path MODEL (host-only replay, no device): export each
    # round's resident plan and price what the planned path would upload
    # — the full row of every dirty node, blocks*136 bytes per lane
    planned_bytes, dirty_nodes = [], []
    trie_plan = IncrementalTrie(boot)
    trie_plan.commit_cpu(threads=threads)
    for b in batches:
        trie_plan.update(b)
        exp = trie_plan.export_resident_plan()
        planned_bytes.append(
            sum(int(s[0]) * int(s[1]) * 136 for s in exp["specs"]))
        dirty_nodes.append(int(exp["num_dirty"]))
        trie_plan.commit_cpu(threads=threads)

    def device_leg(lean: bool):
        trie = IncrementalTrie(boot)
        if lean:
            trie.set_lean(True)
        ex = ResidentExecutor()
        roots = [trie.commit_template(ex)]
        h2d, lean_rows, lean_wire = [], [], []
        for b in batches:
            trie.update(b)
            roots.append(trie.commit_template(ex))
            h2d.append(ex.h2d_bytes)
            lean_rows.append(ex.last_lean_rows)
            lean_wire.append(ex.last_lean_wire_bytes)
        if roots != oracle_roots:
            raise RuntimeError(
                f"{'lean' if lean else 'template'} leg diverged from the "
                f"host oracle")
        return trie, h2d, lean_rows, lean_wire

    try:
        _, tmpl_h2d, _, _ = device_leg(lean=False)
        lean_trie, lean_h2d, lean_rows, lean_wire = device_leg(lean=True)
    except (RuntimeError, ValueError) as e:
        print(json.dumps({"config": 20, "skipped": str(e)}), flush=True)
        return

    mean = lambda xs: sum(xs) / max(len(xs), 1)  # noqa: E731
    lean_record = 4 * LEAN_WORDS + 8   # 72 B content + idx + len
    tmpl_record = 136 + 4              # full row content + idx
    total_lean_rows = sum(lean_rows)

    # rawdb footprint A/B over the lean leg's final delta: the same node
    # set stored hash-addressed (32 B key + rlp) vs digest-slot-addressed
    # (N + slot(4) -> digest(32) + rlp), round-tripped through the real
    # codec so verify-on-read stays exercised
    digests, rlp_blob, off = lean_trie.export_nodes(delta=True)
    db = MemoryDB()
    hash_disk = 0
    for i in range(digests.shape[0]):
        node_rlp = rlp_blob[int(off[i]):int(off[i + 1])]
        hash_disk += 32 + len(node_rlp)
        rawdb.write_lean_node(db, i, digests[i].tobytes(), node_rlp)
    lean_disk = rawdb.lean_nodes_footprint(db)

    print(json.dumps({
        "config": 20,
        "platform": jax.devices()[0].platform,
        "n_leaves": n_leaves, "churn": churn, "rounds": rounds,
        "planned_modeled_bytes_per_commit": int(mean(planned_bytes)),
        "planned_modeled_bytes_per_dirty_node": round(
            sum(planned_bytes) / max(sum(dirty_nodes), 1), 1),
        "template_h2d_bytes_per_commit": int(mean(tmpl_h2d)),
        "lean_h2d_bytes_per_commit": int(mean(lean_h2d)),
        "lean_rows_per_commit": int(mean(lean_rows)),
        "lean_wire_bytes_per_commit": int(mean(lean_wire)),
        "lean_record_bytes": lean_record,
        "template_record_bytes": tmpl_record,
        "disk_nodes": lean_disk["count"],
        "disk_hash_addressed_bytes": hash_disk,
        "disk_lean_slot_bytes": lean_disk["bytes"],
        "note": "planned_* is a MODEL (sum blocks*lanes*136 over the "
                "plan), template/lean h2d are measured uploads; lean "
                "rows only flow on the fused path (the non-fused "
                "fallback expands them host-side and reports the full "
                "bytes it actually shipped)",
    }), flush=True)
    if total_lean_rows:
        _emit(20, "lean_row_wire_bytes_per_leaf",
              sum(lean_wire) / total_lean_rows, "B/leaf",
              tmpl_record / lean_record)
        _emit(20, "lean_h2d_bytes_per_commit", mean(lean_h2d), "B/commit",
              mean(tmpl_h2d) / max(mean(lean_h2d), 1.0))
    else:
        print(json.dumps({
            "config": 20,
            "skipped": "no lean rows flowed (non-fused executor or no "
                       "lean-eligible leaves)",
        }), flush=True)


def bench_21():
    """Sampling-profiler overhead A/B (config-21, PR 20): the
    metrics/profiler.py stack sampler off vs on at 25 Hz and 100 Hz,
    over two legs — the config-10-shaped block-insert leg
    (_block_insert_rate, ecrecover + EVM + commit) and the config-18
    storm leg (abbreviated bench_storm ladder, lock-free view reads
    under insert load). Each (leg, hz) cell is the best of two runs so
    a single descheduling blip on the shared box doesn't masquerade as
    sampler cost. Overhead is 1 - on/off per leg; the gate is the mean
    across legs at 25 Hz, budget 2%, enforced HERE where the A/B runs
    back-to-back — the emitted metric name carries "overhead" so the
    trajectory sentinel reports the cross-round series without gating
    (round-to-round wall-clock noise on a 1-core container swamps a
    sub-2% effect). Raw (possibly negative) overheads are reported,
    not clamped: a faster-with-profiler leg is noise and says so."""
    import bench_storm
    from coreth_tpu.metrics.profiler import (get_profiler, start_profiler,
                                             stop_profiler)

    def insert_leg():
        _, rate = _block_insert_rate()
        return rate

    def storm_leg():
        result = bench_storm.main(["--duration", "0.6",
                                   "--rates", "2000", "4000",
                                   "--corpus", "100"])
        return result["legs"]["view"]["saturation_per_sec"]

    legs = (("insert", insert_leg), ("storm", storm_leg))
    insert_leg()  # warm-up: compile/caches stay out of the A/B
    rates = {}
    samples = {}

    def measure(hz):
        if hz:
            start_profiler(float(hz), ring_size=4096)
        for name, fn in legs:
            prev = rates.get((name, hz), 0.0)
            rates[(name, hz)] = max(prev, fn(), fn())
        if hz:
            prof = get_profiler()
            if prof is not None:
                samples[hz] = samples.get(hz, 0) + \
                    prof.dump()["samples_total"]
            stop_profiler()

    for hz in (0, 25, 100):
        measure(hz)

    def mean_overhead(hz):
        return sum(1.0 - rates[(n, hz)] / rates[(n, 0)]
                   for n, _ in legs) / len(legs)

    if mean_overhead(25) > 0.02:
        # one re-measure of the baseline and the 25 Hz cells before
        # judging: best-of pools across passes
        measure(0)
        measure(25)
    mean_25 = mean_overhead(25)
    mean_100 = mean_overhead(100)
    gate_pass = mean_25 <= 0.02
    print(json.dumps({
        "config": 21,
        "host_mode": True,  # CPU wall-clock A/B: no device leg by design
        "cores": os.cpu_count(),
        "legs": {name: {f"{hz}hz": round(rates[(name, hz)], 1)
                        for hz in (0, 25, 100)} for name, _ in legs},
        "profiler_samples": {f"{hz}hz": samples.get(hz, 0)
                             for hz in (25, 100)},
        "overhead_pct": {
            f"{hz}hz": {n: round(100.0 * (1.0 - rates[(n, hz)]
                                          / rates[(n, 0)]), 2)
                        for n, _ in legs} for hz in (25, 100)},
        "gate_max_pct_25hz": 2.0,
        "gate_pass": gate_pass,
    }), flush=True)
    _emit(21, "profiler_overhead_pct_25hz", 100.0 * mean_25, "%",
          1.0 - mean_25)
    if not gate_pass:
        raise RuntimeError(
            f"config-21 gate: sampling-profiler overhead at 25 Hz is "
            f"{100.0 * mean_25:.2f}% > 2.0% budget")


def main():
    from coreth_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    # a hung device would stall the device-leg configs forever; bench.py's
    # phase watchdog emits a diagnostic line and exits instead
    from bench import REPORT, PhaseWatchdog

    REPORT["suite"] = "bench_suite"
    watchdog = PhaseWatchdog(
        time.monotonic() + float(os.environ.get("CORETH_TPU_BENCH_WATCHDOG",
                                                "1800")))
    picks = [int(a) for a in sys.argv[1:]] or list(range(1, 22))
    for i in picks:
        # configs 7/9 run bench.py legs under their own phase watchdogs
        # with larger budgets (900s cold warmup); the outer arm must not
        # undercut them
        watchdog.arm(f"config-{i}", 1500 if i in (7, 9) else 600)
        globals()[f"bench_{i}"]()
    watchdog.cancel()


if __name__ == "__main__":
    main()
