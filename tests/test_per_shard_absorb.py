"""Per-shard digest absorb + storage-lean rows (PR 18): the template
commit's host-cache absorb must be bit-exact whether the digests come
home via the per-shard path (each mesh shard's lanes read straight from
that shard's store partition — zero MEASURED gather bytes) or the full
replicated-dig readback (the parity oracle, which IS a measured gather),
at every mesh width and across the demotion ladder; and the lean wire
format (72 B content records for short class-1 rows, keccak padding
re-derived on device) must change only how fresh rows travel, never the
roots or the host cache.

Runs on the virtual 8-device CPU mesh (tests/conftest.py forces
--xla_force_host_platform_device_count=8)."""

import random

import numpy as np
import pytest

from coreth_tpu.native.mpt import IncrementalTrie

# widths 2 and 8 ride the slow tier: the parity sweep compiles two
# fused mesh programs per width, and tier-1's budget holds widths
# {1, 4} (test_resident_mesh already pins {2, 8} bit-exactness there)
WIDTHS = (1,
          pytest.param(2, marks=pytest.mark.slow),
          4,
          pytest.param(8, marks=pytest.mark.slow))


def _mesh_executor(width):
    from coreth_tpu.ops.keccak_resident import ResidentExecutor
    from coreth_tpu.parallel import make_mesh, resident_executor_over_mesh

    if width == 1:
        return ResidentExecutor()
    return resident_executor_over_mesh(make_mesh(width))


def _rand_items(rng, n):
    return {rng.randbytes(32): rng.randbytes(rng.randint(1, 90))
            for _ in range(n)}


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


def _node_set(trie):
    digests, rlp, off = trie.export_nodes()
    return set(map(bytes, digests)), rlp


def _workload(seed, n=400, rounds=3, churn=60):
    rng = random.Random(seed)
    state = _rand_items(rng, n)
    boot = sorted(state.items())
    batches = []
    for _ in range(rounds):
        b = _batch(rng, state, churn)
        batches.append(b)
        for k, v in b:
            if v:
                state[k] = v
            else:
                state.pop(k, None)
    return boot, batches


# ---- per-shard absorb vs full readback, width sweep ---------------------


@pytest.mark.parametrize("width", WIDTHS)
def test_per_shard_absorb_matches_full_readback(width):
    """Same workload through three tries: the CPU oracle, a template
    trie absorbing per shard (the steady-state path), and a template
    trie forcing the full replicated-dig readback. Roots match every
    round and the final host caches are node-for-node identical; only
    the full-readback leg records MEASURED gather bytes."""
    boot, batches = _workload(1800 + width)
    oracle = IncrementalTrie(boot)
    shard_trie = IncrementalTrie(boot)
    full_trie = IncrementalTrie(boot)
    ex_shard = _mesh_executor(width)
    ex_full = _mesh_executor(width)

    assert oracle.commit_cpu() == shard_trie.commit_template(ex_shard) \
        == full_trie.commit_template(ex_full, full_readback=True)
    for rnd, b in enumerate(batches):
        oracle.update(b)
        shard_trie.update(b)
        full_trie.update(b)
        want = oracle.commit_cpu()
        assert shard_trie.commit_template(ex_shard) == want, f"round {rnd}"
        assert full_trie.commit_template(
            ex_full, full_readback=True) == want, f"round {rnd}"
        if width > 1:
            # the whole point: per-shard absorb materializes nothing
            # host-side beyond its own lanes
            assert ex_shard.last_gather_bytes == 0
            assert ex_shard.last_absorb_d2h_bytes > 0
            assert ex_full.last_gather_bytes > 0

    shard_nodes, shard_rlp = _node_set(shard_trie)
    full_nodes, full_rlp = _node_set(full_trie)
    oracle_nodes, oracle_rlp = _node_set(oracle)
    assert shard_nodes == full_nodes == oracle_nodes
    assert shard_rlp == full_rlp == oracle_rlp


def test_per_shard_absorb_d2h_accounting():
    """The per-shard readback moves exactly the commit's lanes (32 B
    each), split across shards per the lane histogram."""
    boot, batches = _workload(1900, rounds=1)
    trie = IncrementalTrie(boot)
    ex = _mesh_executor(4)
    trie.commit_template(ex)
    trie.update(batches[0])
    trie.commit_template(ex)
    total_lanes = sum(ex.last_shard_lanes)
    assert total_lanes > 0
    assert len(ex.last_shard_lanes) == 4
    # only store-slot-addressed lanes ride the readback (scratch-slot
    # lanes never leave the device), 32 B per lane
    d2h = ex.last_absorb_d2h_bytes
    assert 0 < d2h <= total_lanes * 32
    assert d2h % 32 == 0
    # modeled vs measured: the model prices the cross-shard share, the
    # measured counter saw no full-dig materialization at all
    assert ex.last_gather_bytes == 0
    assert ex.last_gather_bytes_modeled == total_lanes * 32 * 3 // 4


def test_per_shard_absorb_across_demotion_ladder():
    """Mesh width 4 -> rebase -> single device (the PR 14 demotion
    rung): the re-pinned template commit rebuilds device residency and
    the host cache stays bit-exact with the oracle through the hop."""
    boot, batches = _workload(2000)
    oracle = IncrementalTrie(boot)
    trie = IncrementalTrie(boot)
    ex = _mesh_executor(4)
    assert oracle.commit_cpu() == trie.commit_template(ex)
    oracle.update(batches[0])
    trie.update(batches[0])
    assert oracle.commit_cpu() == trie.commit_template(ex)

    # demote: abandon the sharded residency, land on one device
    trie.rebase_residency()
    ex_single = _mesh_executor(1)
    assert trie.commit_template(ex_single) == oracle.commit_cpu()
    for b in batches[1:]:
        oracle.update(b)
        trie.update(b)
        assert trie.commit_template(ex_single) == oracle.commit_cpu()
    assert _node_set(trie) == _node_set(oracle)


# ---- storage-lean wire format -------------------------------------------


@pytest.mark.parametrize("width", (1, 4))
def test_lean_rows_roots_and_wire_bytes(width):
    """set_lean(True) must leave every root bit-exact vs the oracle
    while short fresh class-1 rows travel as 80 B records (72 B content
    + 4 B arena index + 4 B byte length) on the fused path. The churn
    values are 32 B, so the leaves' RLP fits the 72 B lean width."""
    rng = random.Random(2100 + width)
    state = {rng.randbytes(32): rng.randbytes(32) for _ in range(400)}
    boot = sorted(state.items())
    oracle = IncrementalTrie(boot)
    trie = IncrementalTrie(boot)
    trie.set_lean(True)
    ex = _mesh_executor(width)
    assert oracle.commit_cpu() == trie.commit_template(ex)
    keys = sorted(state)
    saw_lean = 0
    for _ in range(3):
        b = [(k, rng.randbytes(32)) for k in rng.sample(keys, 60)]
        oracle.update(b)
        trie.update(b)
        assert oracle.commit_cpu() == trie.commit_template(ex)
        if ex.last_lean_rows:
            saw_lean += ex.last_lean_rows
            if getattr(ex, "fused", True):
                assert ex.last_lean_wire_bytes == ex.last_lean_rows * 80
    assert saw_lean > 0, "no lean rows flowed on a lean-eligible workload"
    assert _node_set(trie) == _node_set(oracle)


def test_lean_toggle_between_commits():
    """set_lean flips between commits without disturbing residency: a
    lean commit followed by a non-lean one (and back) stays on-oracle."""
    boot, batches = _workload(2200, n=300, rounds=3, churn=40)
    oracle = IncrementalTrie(boot)
    trie = IncrementalTrie(boot)
    ex = _mesh_executor(1)
    assert oracle.commit_cpu() == trie.commit_template(ex)
    for i, b in enumerate(batches):
        trie.set_lean(i % 2 == 0)
        oracle.update(b)
        trie.update(b)
        assert oracle.commit_cpu() == trie.commit_template(ex)
