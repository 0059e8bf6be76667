"""Native MPT commit planner parity tests.

The planner (native/mpt.cpp) must reproduce the Python Trie's root
bit-exactly on both its host (threaded keccak) and device (fused_commit)
execution paths — the CPU-vs-TPU parity discipline of SURVEY.md §4
(trie/trie_test.go:601 TestRandom, :837 TestCommitSequence seeds).
"""

import random

import pytest

from coreth_tpu.native.mpt import plan_from_items
from coreth_tpu.trie.trie import Trie


def _random_items(n, vmin, vmax, seed):
    rng = random.Random(seed)
    items = {}
    for _ in range(n):
        items[rng.randbytes(32)] = rng.randbytes(rng.randint(vmin, vmax))
    return list(items.items())


def _trie_root(items):
    t = Trie()
    for k, v in items:
        t.update(k, v)
    return t.hash()


class TestNativePlanParity:
    @pytest.mark.parametrize("n,vmin,vmax,seed", [
        (1, 1, 40, 0),
        (2, 1, 4, 1),
        (50, 1, 10, 2),       # many embedded (<32B) nodes
        (500, 40, 90, 3),     # account-sized values
        (2000, 1, 200, 4),    # mixed incl. multi-block leaves
    ])
    def test_cpu_root_matches_python_trie(self, n, vmin, vmax, seed):
        items = _random_items(n, vmin, vmax, seed)
        plan = plan_from_items(items)
        assert plan.execute_cpu(threads=1) == _trie_root(items)

    def test_threaded_matches_single(self):
        items = _random_items(3000, 30, 100, 9)
        plan = plan_from_items(items)
        assert plan.execute_cpu(threads=1) == plan.execute_cpu(threads=8)

    @pytest.mark.parametrize("threads", [2, 5, 16])
    def test_threaded_random_tries_bit_exact(self, threads):
        """Worker-pool hashing across randomized trie shapes — sized to
        straddle the parallel threshold both ways — must match the
        single-thread oracle AND the Python trie bit-exactly. Thread
        counts deliberately oversubscribe 1-core CI so the pooled path
        (not the serial guard) is what runs."""
        rng = random.Random(100 + threads)
        for trial in range(4):
            n = rng.choice([40, 300, 1200, 4000])
            items = _random_items(n, 1, 120, rng.randrange(1 << 30))
            r1 = plan_from_items(items).execute_cpu(threads=1)
            assert plan_from_items(items).execute_cpu(
                threads=threads) == r1, (threads, trial, n)
            if n <= 300:  # keep the Python-trie oracle leg cheap
                assert r1 == _trie_root(items)

    def test_threaded_batch_keccak_matches_serial(self):
        """keccak256_batch with a pooled thread count must equal the
        serial path message-for-message (mixed sizes incl. multi-block
        and empty messages)."""
        from coreth_tpu.native import keccak256_batch

        rng = random.Random(55)
        msgs = [rng.randbytes(rng.choice([0, 1, 55, 136, 137, 500, 4000]))
                for _ in range(300)]
        assert keccak256_batch(msgs, threads=1) == \
            keccak256_batch(msgs, threads=7)

    def test_device_root_matches_cpu(self):
        items = _random_items(1500, 1, 120, 11)
        plan = plan_from_items(items)
        root_cpu = plan.execute_cpu()
        root_dev, dig8 = plan.execute_device()
        assert root_dev == root_cpu
        assert dig8.shape[1] == 32

    def test_single_leaf_and_tiny_values(self):
        for items in ([(b"\x11" * 32, b"v")],
                      [(b"\x00" * 32, b"\x01"), (b"\xff" * 32, b"\x02")]):
            plan = plan_from_items(items)
            assert plan.execute_cpu() == _trie_root(items)

    def test_empty_set_rejected(self):
        with pytest.raises(ValueError):
            plan_from_items([])

    def test_duplicate_keys_last_write_wins(self):
        k = b"\x42" * 32
        items = [(k, b"first"), (b"\x01" * 32, b"x"), (k, b"second")]
        plan = plan_from_items(items)
        assert plan.execute_cpu() == _trie_root([(b"\x01" * 32, b"x"),
                                                 (k, b"second")])

    def test_unsorted_input_rejected_by_plan_commit(self):
        import numpy as np

        from coreth_tpu.native.mpt import plan_commit

        keys = np.frombuffer(b"\xff" * 32 + b"\x00" * 32, dtype=np.uint8).reshape(2, 32)
        off = np.array([0, 1, 2], dtype=np.uint64)
        with pytest.raises(ValueError):
            plan_commit(keys, b"ab", off)


class TestPlannedU32Executor:
    """The u32 planned executor (ops/keccak_planned.py) — strip-gather
    patching, device-resident chaining — must be bit-exact vs the host
    oracle on every shift/overlap/embedding shape."""

    @pytest.mark.parametrize("n,vmin,vmax,seed", [
        (50, 1, 10, 21),      # deep embedding, tiny values
        (700, 40, 90, 22),    # account-shaped
        (1500, 1, 220, 23),   # mixed, multi-block leaves
    ])
    def test_planned_root_matches_cpu(self, n, vmin, vmax, seed):
        items = _random_items(n, vmin, vmax, seed)
        plan = plan_from_items(items)
        assert plan.execute_planned() == plan.execute_cpu()

    def test_planned_digests_match_cpu_per_lane(self):
        """Per-lane diff (SURVEY §7 hard-part 2: diff per node, not just
        per root)."""
        import numpy as np

        from coreth_tpu.ops.keccak_planned import PlannedCommit

        items = _random_items(900, 1, 150, 24)
        plan = plan_from_items(items)
        specs, flat_words, dst_word, child_lane, shift = plan.export_words()
        root, dig = PlannedCommit().run(
            specs, flat_words, dst_word, child_lane, shift, plan.root_pos,
            want_digests=True,
        )
        cpu_dig = np.empty((plan.total_lanes, 32), np.uint8)
        root_cpu = np.empty(32, np.uint8)
        plan._lib.mpt_plan_execute_cpu(
            plan._h, 1,
            cpu_dig.ctypes.data_as(__import__("ctypes").c_void_p),
            root_cpu,
        )
        got = dig.astype("<u4").view(np.uint8).reshape(plan.total_lanes, 32)
        # only real lanes carry digests; scratch/pad lanes differ (host
        # leaves them zero, device hashes the padded zero rows)
        lens = np.empty(plan.total_lanes, np.int32)
        plan._lib.mpt_plan_msg_lens(plan._h, lens)
        real = lens > 0
        assert (got[real] == cpu_dig[real]).all()
        assert root == root_cpu.tobytes()

    def test_word_patch_export_consistent_with_byte_patches(self):
        import numpy as np

        items = _random_items(400, 1, 100, 25)
        plan = plan_from_items(items)
        specs, flat, nblocks, pl, po, pc = plan.export()
        _, _, dst_word, child_lane, shift = plan.export_words()
        # walk segments to rebuild byte offsets from (lane, off)
        byte_base = 0
        k = 0
        for s in specs:
            width = s.blocks * 136
            for _ in range(s.n_patches):
                if child_lane[k] >= 0:
                    off = byte_base + pl[k] * width + po[k]
                    assert dst_word[k] == off // 4
                    assert shift[k] == off % 4
                    assert child_lane[k] == pc[k]
                k += 1
            byte_base += s.lanes * width
        assert k == len(dst_word)

    def test_cpu_then_planned_same_plan(self):
        """execute_cpu must leave the shared flat buffer pristine (it
        patches digests in place and restores them), so cross-checking
        both paths on ONE plan is legal in either order."""
        items = _random_items(600, 1, 120, 26)
        plan = plan_from_items(items)
        root_cpu = plan.execute_cpu()
        assert plan.execute_planned() == root_cpu
        assert plan.execute_cpu() == root_cpu  # and back again


def test_pool_reuse_growing_sizes():
    """Buffer-pool regression: plans of growing size through the pool must
    never hand out an undersized buffer (review r3: capacity accounting)."""
    import random

    from coreth_tpu.native.mpt import plan_from_items

    from coreth_tpu.trie.hasher import Hasher
    from coreth_tpu.trie.trie import Trie

    rng = random.Random(55)
    for n in (500, 900, 1400, 2000, 700):
        items = [(rng.randbytes(32), rng.randbytes(60)) for _ in range(n)]
        p = plan_from_items(items)
        got = p.execute_cpu()
        del p  # releases into the pool for the next (bigger) plan
        t = Trie()
        for k, v in dict(items).items():
            t.update(k, v)
        h, _ = Hasher().hash(t.root, True)
        assert got == bytes(h), f"pool-reused plan produced a wrong root at n={n}"


def test_giant_value_many_blocks():
    """A leaf value far beyond 64 keccak blocks must still hash exactly
    (review r3: no block-count clamp)."""
    import random

    from coreth_tpu.native.mpt import plan_from_items
    from coreth_tpu.trie.hasher import Hasher
    from coreth_tpu.trie.trie import Trie

    rng = random.Random(56)
    items = [(rng.randbytes(32), rng.randbytes(60)) for _ in range(50)]
    items.append((rng.randbytes(32), rng.randbytes(20_000)))
    p = plan_from_items(items)
    t = Trie()
    for k, v in dict(items).items():
        t.update(k, v)
    h, _ = Hasher().hash(t.root, True)
    assert p.execute_cpu() == bytes(h)
