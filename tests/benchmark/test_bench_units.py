"""The benchmark's arithmetic, generator and reference parts, each alone.

The program under test appears here only as a second witness: the
benchmark's keccak, trie and signer must agree with it bit for bit.
"""

import json
import os
import random

import pytest

import benchtools
from benchmark import harness, kernels, mpt, rlp, signer, state, traffic
from benchmark.keccak import keccak256, keccak256_batch
from benchmark.reference import Reference, ReferenceError_
from benchmark.trace_reduce import _label_gaps, reduce_file, union


# ---- rate and percentile arithmetic -------------------------------------

@pytest.mark.parametrize("n,want", [
    (1, 1), (2, 2), (19, 19), (20, 19), (21, 20), (40, 38), (100, 95)])
def test_nearest_rank_p95(n, want):
    values = list(range(1, n + 1))
    random.Random(n).shuffle(values)
    assert harness.nearest_rank(values, 0.95) == want


def test_gas_rate_counts_all_gas_over_the_whole_window():
    blocks = [{"gas_used": 15_000_000}, {"gas_used": 9_000_000},
              {"gas_used": 6_000_000}]
    assert harness.gas_rate(blocks, 20.0) == pytest.approx(1.5)


def _record(blocks, window_s):
    rec = harness.RunRecord()
    rec.blocks, rec.window_s = blocks, window_s
    return rec


@pytest.mark.parametrize("metric,want", [
    ("mgas_per_s", 30.0 / 40.0),
    ("block_p95_ms", 25_000.0),
    ("vm.build_ms", 1000 * (9 + 19 + 4) / 3),
    ("vm.accept_ms", 1000 * (1 + 1 + 1) / 3),
])
def test_readers_on_a_known_window(metric, want):
    blocks = [{"gas_used": 10_000_000, "block_s": 10.0, "build_s": 9.0,
               "accept_s": 1.0},
              {"gas_used": 15_000_000, "block_s": 20.0 + 5.0,
               "build_s": 19.0, "accept_s": 1.0},
              {"gas_used": 5_000_000, "block_s": 5.0, "build_s": 4.0,
               "accept_s": 1.0}]
    cell = harness.Cell(benchtools.REPO, "transfers-sat")
    assert cell.reader(metric)(_record(blocks, 40.0)) == pytest.approx(want)


def test_readers_that_find_nothing_return_nothing():
    cell = harness.Cell(benchtools.REPO, "transfers-sat")
    rec = _record([{"gas_used": 1, "block_s": 1.0}], 1.0)
    for name in ("kernel.commit_program_ms_per_block",
                 "kernel.commit_program_roofline",
                 "device.idle_share", "chain.execute_ms"):
        assert cell.reader(name)(rec) is None


def test_roofline_bytes_from_shapes():
    # 10 lanes of 2 blocks: 20 blocks absorbed at 136 B, 10 digests
    assert kernels.keccak_bytes({"lanes": 10, "blocks": 20}) \
        == 20 * 136 + 10 * 32


# ---- the cells resolve by name ------------------------------------------

def test_every_cell_resolves_its_files():
    with open(os.path.join(benchtools.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.Cell(benchtools.REPO, w["name"])
        assert cell.mix["txs_per_block"] > 0
        for kind in ("end_to_end", "per_layer"):
            for m in cell.metrics(kind):
                assert callable(cell.reader(m["name"]))


def test_new_files_and_entries_are_picked_up_by_name(tmp_path):
    root = benchtools.tiny_root(tmp_path)
    cfg = benchtools.tiny_config()
    cfg["name"] = "another-deployment"
    benchtools.write(root, "benchmark/configs/another-deployment.json", cfg)
    mix = benchtools.tiny_mix()
    mix["txs_per_block"] = 3
    benchtools.write(root, "benchmark/traffic/another-mix.json", mix)
    with open(os.path.join(root, "benchmark", "metrics",
                           "extra.txs_per_block.py"), "w") as f:
        f.write("def read(run):\n    return 1.0 * len(run.blocks)\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "another-deployment", "source": "x",
                             "file": "benchmark/configs/"
                                     "another-deployment.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "another-cell",
                               "config": "another-deployment",
                               "traffic": "another-mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "extra.txs_per_block", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "VM", "moves": "mgas_per_s",
                               "workloads": ["another-cell"]})
    benchtools.write(root, "BENCHMARK.json", bench)
    cell = harness.Cell(root, "another-cell")
    assert cell.config["name"] == "another-deployment"
    assert cell.mix["txs_per_block"] == 3
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert "extra.txs_per_block" in names
    assert cell.reader("extra.txs_per_block")(_record([{}, {}], 1.0)) == 2.0
    assert "extra.txs_per_block" not in [
        m["name"] for m in harness.Cell(root, "tiny-transfers")
        .metrics("per_layer")]


# ---- traffic -------------------------------------------------------------

BIG = 2 ** 31 + 12345  # seeds beyond 32 signed bits


@pytest.fixture(scope="module")
def genesis():
    return state.Genesis(benchtools.tiny_config(), 1)


@pytest.mark.parametrize("seed", [BIG, 2 ** 40 + 3])
def test_traffic_same_seed_same_different_seed_different(genesis, seed):
    def blocks(seed):
        s = traffic.TxStream(genesis, benchtools.tiny_mix(), seed)
        return [s.block() for _ in range(3)]

    assert blocks(seed) == blocks(seed)
    assert blocks(seed) != blocks(seed + 1)


@pytest.mark.parametrize("seed", [2 ** 33 + 5, 17])
def test_genesis_values_follow_the_seed_and_its_shape_the_config(seed):
    cfg = benchtools.tiny_config()
    a, b, c = (state.Genesis(cfg, s) for s in (seed, seed, seed + 1))
    assert a.alloc == b.alloc
    assert a.alloc != c.alloc
    # the same addresses, so tries of the same shape
    assert list(a.alloc) == list(c.alloc)
    assert len(a.alloc) == cfg["accounts"]


def test_nonces_run_in_order_across_a_reseed(genesis):
    s = traffic.TxStream(genesis, benchtools.tiny_mix(), 0)
    items = s.block()
    s.reseed(99)
    items += s.block() + s.block()
    seen = {}
    for sender, nonce, *_ in items:
        assert nonce == seen.get(sender, 0)
        seen[sender] = nonce + 1


def test_transfers_walk_the_ring(genesis):
    ring = genesis.senders
    items = traffic.TxStream(genesis, benchtools.tiny_mix(), 5).block()
    for a, b in zip(items, items[1:]):
        # each transfer pays the next ring account, which sends next
        assert a[5] == ring[b[0]] == ring[(a[0] + 1) % len(ring)]
    assert all(it[7] == b"" and it[4] == 21_000 for it in items)


@pytest.mark.parametrize("seed", [0, 1, BIG])
def test_every_seed_sends_the_same_sizes(genesis, seed):
    mix = benchtools.tiny_mix()
    items = traffic.TxStream(genesis, mix, seed).block()
    # one transfer per distinct sender, each to an account of the ring
    assert len({it[0] for it in items}) == mix["txs_per_block"]
    assert {it[5] for it in items} <= set(genesis.senders)


def test_the_reference_refuses_a_contract_creation(genesis):
    from benchmark import rlp

    key = genesis.senders[0]
    tx = bytes([2]) + rlp.encode([43112, 0, 1, 10 ** 12, 60_000, b"", 0,
                                  b"\x60", [], 0, 1, 1])
    header = [b"", b"", bytes(20), b"", b"", b"", b"", 0, 1, 15_000_000, 0,
              0, b"", b"", b"", b"", 25 * 10 ** 9]
    with pytest.raises(ReferenceError_):
        Reference(genesis).apply_block(rlp.encode([header, [tx]]),
                                       lambda raw: key)


# ---- keccak, RLP, trie and signer against known values and the program ---

@pytest.mark.parametrize("msg,digest", [
    (b"", "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470"),
    (b"abc",
     "4e03657aea45a94fc7d47ba826c8d667c0d1e6e33a64a036ec44f58fa12d6c45"),
    (b"\x80",
     "56e81f171bcc55a6ff8345e692c0f86e5b48e01b996cadc001622fb5e363b421"),
])
def test_keccak_vectors(msg, digest):
    assert keccak256(msg).hex() == digest


def test_keccak_batch_matches_the_program_across_block_counts():
    from coreth_tpu.native import keccak256 as program_keccak

    rng = random.Random(1)
    msgs = [rng.randbytes(n) for n in (0, 1, 135, 136, 137, 271, 272, 600)]
    assert keccak256_batch(msgs) == [program_keccak(m) for m in msgs]


@pytest.mark.parametrize("item", [
    b"", b"\x00", b"\x7f", b"\x80", b"a" * 55, b"a" * 56, 0, 1, 127, 128,
    2 ** 64, [], [b"", [0, [b"x" * 60]]]])
def test_rlp_round_trip(item):
    def norm(x):
        if isinstance(x, int):
            return rlp.int_bytes(x)
        if isinstance(x, list):
            return [norm(y) for y in x]
        return x

    assert rlp.decode(rlp.encode(item)) == norm(item)


def _program_root(items):
    from coreth_tpu.trie.trie import Trie

    t = Trie()
    for k, v in items:
        t.update(k, v)
    return t.hash()


@pytest.mark.parametrize("n", [1, 2, 17, 400])
def test_trie_root_matches_the_program(n):
    rng = random.Random(n)
    items = [(rng.randbytes(32), rng.randbytes(rng.randrange(1, 90)))
             for _ in range(n)]
    trie = mpt.Trie(items[: n // 2])
    for k, v in items[n // 2:]:
        trie.put(k, v)
    assert trie.root() == mpt.trie_root(items) == _program_root(items)


def test_trie_root_with_embedded_nodes_matches_the_program():
    items = [(rlp.encode(i), bytes([i % 7 + 1])) for i in range(150)]
    assert mpt.trie_root(items) == _program_root(items)


def test_signed_txs_recover_to_their_senders_in_the_program():
    from coreth_tpu.core.types import Signer, Transaction

    keys = signer.derive_keys(b"unit", 4)
    addrs = signer.addresses(keys)
    items = [(i % 4, i, 10 ** 9, 10 ** 12, 21000, bytes([i]) * 20, i + 1,
              b"\x01" * i) for i in range(12)]
    for item, raw in zip(items, signer.sign_dynamic_fee_txs(43112, keys,
                                                             items)):
        tx = Transaction.decode(raw)
        assert tx.encode() == raw
        assert Signer(43112).sender(tx) == addrs[item[0]]


# ---- trace reduction helpers ---------------------------------------------

def test_union_merges_overlaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_gaps_go_to_the_innermost_span():
    spans = [(0, 100, "vm.build_block"), (10, 40, "compile"),
             (100, 120, "vm.verify_accept")]
    gaps = [(0, 50), (110, 130)]
    got = _label_gaps(gaps, spans)
    assert got == pytest.approx({"vm.build_block": 20e-9, "compile": 30e-9,
                                 "vm.verify_accept": 10e-9,
                                 "other": 10e-9})


def test_reduction_of_a_recorded_v5e_trace():
    # recorded on one v5e chip: three rounds of the harness's spans, each
    # `vm.build_block` running one small jitted program named `fused`
    s = reduce_file(os.path.join(benchtools.BENCH, "testdata",
                                 "v5e_sample.xplane.pb"))
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.129070651)
    assert s.busy_s == pytest.approx(5.4532e-05)
    assert s.top_ops[0][0].startswith("%fusion.23 = u32[1048576]")
    assert s.module_seconds(kernels.COMMIT_PROGRAMS) \
        == pytest.approx(5.4543e-05)
    gaps = dict(s.idle_gaps)
    assert set(gaps) == {"bench.pool_top_up", "vm.build_block",
                         "vm.verify_accept", "other"}
    assert gaps["bench.pool_top_up"] == pytest.approx(0.062392762)
    assert s.busy_s + sum(gaps.values()) == pytest.approx(s.window_s)
    rec = _record([{}, {}, {}], s.window_s)
    rec.trace = s
    cell = harness.Cell(benchtools.REPO, "transfers-sat")
    assert cell.reader("device.idle_share")(rec) == pytest.approx(
        100 * (1 - 5.4532e-05 / 0.129070651))
    assert cell.reader("kernel.commit_program_ms_per_block")(rec) \
        == pytest.approx(1000 * 5.4543e-05 / 3)


def test_peaks_table_names_its_source_and_the_missing_peak():
    with open(os.path.join(benchtools.BENCH, "peaks.json")) as f:
        v5e = json.load(f)["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    assert v5e["vpu_int32_op_per_s"] is None
    assert "vpu_int32_op_per_s" in v5e["missing"]
    rec = harness.RunRecord()
    rec.device_kind, rec.peaks_table = "some other chip", {"TPU v5 lite": {}}
    with pytest.raises(KeyError):
        rec.peaks()
