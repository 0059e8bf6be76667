"""Multi-chip sharding tests on the virtual 8-device CPU mesh (conftest.py).

The reference's parallel hashing is a 16-goroutine fan-out per branch node
(/root/reference/trie/hasher.go:124-139); the TPU-native analog shards the
batch over a jax.sharding.Mesh. These tests validate digest bit-exactness
and the cross-shard collective on the same virtual mesh the driver's
dryrun_multichip uses.
"""

import jax
import numpy as np
import pytest

from coreth_tpu.ops.keccak_jax import (digest_words_to_bytes,
                                       keccak256_blocks, pack_messages)
from coreth_tpu.ops.keccak_ref import keccak256 as ref_keccak
from coreth_tpu.parallel import ShardedKeccak, commit_step, make_mesh


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    return make_mesh(8)


class TestShardedKeccak:
    def test_digest_parity_mixed_lengths(self, mesh):
        sk = ShardedKeccak(mesh)
        msgs = [bytes([i % 256]) * (1 + 11 * i) for i in range(50)]
        got = sk.digests(msgs)
        assert got == [ref_keccak(m) for m in msgs]

    def test_empty_and_single(self, mesh):
        sk = ShardedKeccak(mesh)
        assert sk.digests([]) == []
        assert sk.digests([b""]) == [ref_keccak(b"")]

    def test_batch_not_divisible_by_mesh(self, mesh):
        # 13 lanes over 8 devices: padding must keep results exact
        sk = ShardedKeccak(mesh)
        msgs = [b"x" * (140 * i + 1) for i in range(13)]
        assert sk.digests(msgs) == [ref_keccak(m) for m in msgs]

    def test_output_is_sharded(self, mesh):
        # the device batch really is split across the mesh (not replicated)
        sk = ShardedKeccak(mesh)
        msgs = [bytes([i]) * 40 for i in range(64)]
        words, nblocks = pack_messages(msgs)
        out = sk._fn(
            jax.device_put(np.asarray(words), sk._sharding),
            jax.device_put(np.asarray(nblocks), sk._sharding),
        )
        assert len(out.sharding.device_set) == 8


class TestCommitStep:
    def test_checksum_collective(self, mesh):
        step = commit_step(mesh)
        msgs = [bytes([i]) * (1 + 7 * i) for i in range(32)]
        words, nblocks = pack_messages(msgs)
        out, checksum = step(words, nblocks)
        out = np.asarray(out)
        digests = digest_words_to_bytes(out)
        assert digests == [ref_keccak(m) for m in msgs]
        # the psum-style reduction over the sharded digest tensor matches host
        assert int(np.asarray(checksum)) == int(np.sum(out, dtype=np.uint32))


class TestMeshConfigErrors:
    """The resident-mesh-devices fail-fast: impossible widths must raise
    the typed MeshConfigError with an actionable message at construction,
    never an opaque shape/device error deep inside GSPMD."""

    def test_width_past_visible_devices_names_the_fix(self):
        from coreth_tpu.parallel import MeshConfigError, make_mesh

        n = len(jax.devices())
        with pytest.raises(MeshConfigError) as ei:
            make_mesh(16 if n < 16 else n * 2)
        msg = str(ei.value)
        assert f"only {n} JAX device(s) are visible" in msg
        assert "XLA_FLAGS=--xla_force_host_platform_device_count" in msg
        assert "resident-mesh-devices" in msg

    def test_width_must_divide_lane_bucket(self):
        from coreth_tpu.parallel import MeshConfigError, make_mesh

        with pytest.raises(MeshConfigError) as ei:
            make_mesh(3)  # 3 visible devices exist, but 16 % 3 != 0
        assert "does not divide the 16-lane planner bucket" in str(ei.value)

    @pytest.mark.parametrize("bad", [0, -1])
    def test_width_must_be_positive(self, bad):
        from coreth_tpu.parallel import MeshConfigError, make_mesh

        with pytest.raises(MeshConfigError, match="positive device count"):
            make_mesh(bad)

    def test_2d_mesh_extents_must_be_positive(self):
        from coreth_tpu.parallel import MeshConfigError, make_mesh_2d

        with pytest.raises(MeshConfigError, match="positive"):
            make_mesh_2d(0, 2)

    def test_mesh_config_error_is_a_value_error(self):
        # callers that predate the typed error (CacheConfig plumbing,
        # bench sweeps) catch ValueError and keep working
        from coreth_tpu.parallel import MeshConfigError

        assert issubclass(MeshConfigError, ValueError)


class TestMultiHostMesh:
    """2-D (host, chip) mesh — the multi-host deployment layout: lanes
    shard over BOTH axes (P(('host','batch'))), so on real hardware the
    outer axis's collectives ride DCN and the inner axis rides ICI."""

    @pytest.fixture(scope="class")
    def mesh2d(self):
        from coreth_tpu.parallel import make_mesh_2d

        return make_mesh_2d(2, 4)  # 2 "hosts" x 4 chips on the virtual mesh

    def test_digest_parity_over_2d_mesh(self, mesh2d):
        sk = ShardedKeccak(mesh2d, axis=("host", "batch"))
        msgs = [bytes([i % 251]) * (1 + 7 * i) for i in range(64)]
        assert sk.digests(msgs) == [ref_keccak(m) for m in msgs]

    def test_commit_step_collective_spans_hosts(self, mesh2d):
        # the PRODUCTION step over the 2-D mesh (not a hand-rolled copy)
        step = commit_step(mesh2d, axis=("host", "batch"))
        msgs = [bytes([i]) * (1 + 5 * i) for i in range(32)]
        words, nblocks = pack_messages(msgs)
        out, checksum = step(words, nblocks)
        digests = digest_words_to_bytes(np.asarray(out))
        assert digests == [ref_keccak(m) for m in msgs]
        # the checksum reduces across the host AND chip axes
        assert int(np.asarray(checksum)) == int(
            np.sum(np.asarray(out), dtype=np.uint32))

    def test_2d_mesh_shape_validation(self):
        from coreth_tpu.parallel import make_mesh_2d

        n = len(jax.devices())
        with pytest.raises(ValueError):
            make_mesh_2d(n, 2)  # 2n devices: more than any config has


def test_planned_commit_sharded_over_mesh():
    """The full planned commit (patch chains included) with its keccak
    sharded across the 8-device mesh must reproduce the host oracle's
    root bit-exactly."""
    import random

    from coreth_tpu.native.mpt import plan_from_items
    from coreth_tpu.parallel import make_mesh, planned_commit_over_mesh

    rng = random.Random(31)
    items = [(rng.randbytes(32), rng.randbytes(rng.randint(40, 90)))
             for _ in range(900)]
    plan = plan_from_items(items)
    mesh = make_mesh(8)
    runner = planned_commit_over_mesh(mesh)
    root = plan.execute_planned(runner)
    assert root == plan.execute_cpu()


def test_resident_executor_sharded_over_mesh():
    """The device-resident executor with its digest store + row arenas
    SHARDED across the 8-device mesh: warm-trie churn commits and a
    rollback must stay bit-exact vs the host-incremental oracle, with
    the resident state actually spanning every device."""
    import random

    from coreth_tpu.native.mpt import IncrementalTrie
    from coreth_tpu.parallel import make_mesh, resident_executor_over_mesh

    rng = random.Random(32)
    items = sorted(
        {rng.randbytes(32): rng.randbytes(rng.randint(1, 90))
         for _ in range(800)}.items())
    keys = [k for k, _ in items]
    mesh = make_mesh(8)
    ex = resident_executor_over_mesh(mesh)
    dev = IncrementalTrie(items)
    oracle = IncrementalTrie(items)
    assert ex.root_bytes(dev.commit_resident(ex)) == oracle.commit_cpu()
    assert len(ex.store.sharding.device_set) == 8
    for rnd in range(2):
        ups = [(keys[rng.randrange(len(keys))], rng.randbytes(40))
               for _ in range(100)]
        dev.update(ups)
        oracle.update(ups)
        assert ex.root_bytes(dev.commit_resident(ex)) == oracle.commit_cpu()
    dev.checkpoint()
    dev.update([(keys[0], b"speculative"), (keys[1], b"")])
    ex.root_bytes(dev.commit_resident(ex))
    dev.rollback()
    assert ex.root_bytes(dev.commit_resident(ex)) == oracle.commit_cpu()


def test_resident_executor_sharded_over_2d_mesh():
    """Resident state sharded over a (host, chip) mesh: rows partition
    over BOTH axes (host-contiguous blocks), roots stay bit-exact."""
    import random

    from coreth_tpu.native.mpt import IncrementalTrie
    from coreth_tpu.parallel import make_mesh_2d, resident_executor_over_mesh

    rng = random.Random(33)
    items = sorted(
        {rng.randbytes(32): rng.randbytes(50) for _ in range(500)}.items())
    keys = [k for k, _ in items]
    mesh2d = make_mesh_2d(4, 2)
    ex = resident_executor_over_mesh(mesh2d, axis=("host", "batch"))
    dev = IncrementalTrie(items)
    oracle = IncrementalTrie(items)
    assert ex.root_bytes(dev.commit_resident(ex)) == oracle.commit_cpu()
    assert len(ex.store.sharding.device_set) == 8
    ups = [(keys[rng.randrange(len(keys))], rng.randbytes(40))
           for _ in range(80)]
    dev.update(ups)
    oracle.update(ups)
    assert ex.root_bytes(dev.commit_resident(ex)) == oracle.commit_cpu()


def test_pallas_seg_impl_shards_structurally(mesh):
    """The Pallas kernel routed through shard_map: per-shard shapes and
    the pallas_call must survive tracing/lowering (full interpret-mode
    numerics are minutes of XLA-CPU compile — the slow test below and
    tools/pallas_shard_parity.py's committed artifact cover them)."""
    from coreth_tpu.ops.keccak_pallas import staged_seg_impl
    from coreth_tpu.parallel import sharded_seg_impl

    impl = sharded_seg_impl(mesh, seg_impl=staged_seg_impl(interpret=True))
    closed = jax.make_jaxpr(impl)(np.zeros((8 * 1024, 1, 34), np.uint32))
    assert closed.out_avals[0].shape == (8 * 1024, 8)
    jaxpr = str(closed)
    assert "pallas_call" in jaxpr
    assert "shard_map" in jaxpr
    # sub-grid per-shard lane counts fall back to the XLA kernel PER SHARD
    small = str(jax.make_jaxpr(impl)(np.zeros((8 * 16, 1, 34), np.uint32)))
    assert "pallas_call" not in small


@pytest.mark.slow
def test_pallas_seg_impl_sharded_numeric_parity(mesh):
    """Full interpret-mode numerics under shard_map (minutes of compile;
    run with -m slow). Same check tools/pallas_shard_parity.py records as
    MULTICHIP_PALLAS_r{N}.json once per round."""
    from coreth_tpu.ops.keccak_pallas import staged_seg_impl
    from coreth_tpu.ops.keccak_staged import _segment_keccak
    from coreth_tpu.parallel import sharded_seg_impl

    rng = np.random.default_rng(5)
    words = rng.integers(0, 2**32, size=(8 * 1024, 1, 34), dtype=np.uint32)
    impl = sharded_seg_impl(mesh, seg_impl=staged_seg_impl(interpret=True))
    assert (np.asarray(impl(words)) == np.asarray(_segment_keccak(words))).all()
