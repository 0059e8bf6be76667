"""The resident executor's whole-commit program is keyed on capacities
and buckets only: every count and offset of a commit travels as data.

A chain of block commits therefore compiles once. The tile loop that
replaced the per-segment program must hash every lane exactly as the
per-segment (non-fused) path does, including where a tile runs past the
commit's lanes, where a segment has no patches, where rows travel as
lean records and where an arena class gets no fresh rows.
"""

import random

import numpy as np
import pytest

from coreth_tpu.metrics import default_registry
from coreth_tpu.native.mpt import IncrementalTrie
from coreth_tpu.ops.keccak_resident import (TILE_LANES, ResidentExecutor,
                                            plan_tiles)


def _state(rng, n, vlen=(20, 60)):
    return {rng.randbytes(32): rng.randbytes(rng.randint(*vlen))
            for _ in range(n)}


def _updates(rng, keys, n, vlen=(20, 60)):
    return [(k, rng.randbytes(rng.randint(*vlen)))
            for k in rng.sample(keys, n)]


def _recorded(ex):
    """Keep every export the executor runs, newest last."""
    exports = []
    run = ex.run
    ex.run = lambda export: (exports.append(export), run(export))[1]
    return exports


def test_a_chain_of_block_commits_compiles_once():
    """Ten block commits of random dirty sets, whose counts and offsets
    vary inside the first block's buckets, run one compiled program;
    every root is the native CPU commit's."""
    rng = random.Random(41)
    state = _state(rng, 1500)
    dev = IncrementalTrie(sorted(state.items()))
    cpu = IncrementalTrie(sorted(state.items()))
    ex = ResidentExecutor()
    exports = _recorded(ex)
    assert ex.root_bytes(dev.commit_resident(ex)) == cpu.commit_cpu()
    compiles = default_registry.counter("resident/compiles")
    c0 = compiles.count()
    keys = sorted(state)
    for n in [64] + [rng.randint(24, 56) for _ in range(9)]:
        batch = _updates(rng, keys, n)
        dev.update(batch)
        cpu.update(batch)
        assert ex.root_bytes(dev.commit_resident(ex)) == cpu.commit_cpu()
    assert compiles.count() - c0 == 1
    blocks = exports[1:]
    assert len({e["specs"].tobytes() for e in blocks}) > 1
    assert len({e["off"].shape[0] for e in blocks}) > 1
    assert ex.last_cache_hit


def _scenario(name, rng):
    """(boot state, batches, lean) for one parametrized case."""
    state = _state(rng, 700, (32, 32) if name == "lean" else (20, 60))
    keys = sorted(state)
    batches = []
    for _ in range(3):
        b = _updates(rng, keys, 60, (32, 32) if name == "lean" else (20, 60))
        if name == "structural":
            b += [(rng.randbytes(32), rng.randbytes(40)) for _ in range(20)]
            b += [(k, b"") for k in rng.sample(keys, 10)]
        batches.append(b)
    return state, batches, name == "lean"


@pytest.mark.parametrize("case", ["tail_past_g_pad", "zero_patch_segment",
                                  "lean", "class_without_fresh_rows",
                                  "structural"])
def test_every_lane_digest_matches_the_per_segment_path(case):
    """The fused tile loop and the per-segment path commit the same
    batches from the same boot state: every lane's digest, not only the
    root, is equal, and the case's condition held in some commit."""
    rng = random.Random(sum(map(ord, case)))
    state, batches, lean = _scenario(case, rng)
    tries, execs = [], []
    for fused in (True, False):
        t = IncrementalTrie(sorted(state.items()))
        t.set_lean(lean)
        tries.append(t)
        execs.append(ResidentExecutor(fused=fused))
    exports = _recorded(execs[0])
    seen = False
    for batch in [[]] + batches:
        digs = []
        for t, ex in zip(tries, execs):
            t.update(batch)
            t.commit_resident(ex)
            digs.append(np.asarray(ex.last_dig))
        export = exports[-1]
        # pad lanes hash the scratch row, whose content the two paths
        # leave differently; their digests land in the scratch slot
        real = 1 + np.flatnonzero(export["lane_slot"] >= 2)
        assert np.array_equal(digs[0][real], digs[1][real])
        key = execs[0]._last_key
        classes, g_pad, fresh = key[0], key[3], export["fresh"]
        tiles, _ = plan_tiles(export, {c: i for i, c in enumerate(classes)})
        if case == "tail_past_g_pad":
            seen |= bool(int(tiles[-1, 1]) + TILE_LANES > g_pad)
        elif case == "zero_patch_segment":
            seen |= bool((export["specs"][:, 3] == 0).any())
        elif case == "lean":
            seen |= execs[0].last_lean_rows > 0
        elif case == "class_without_fresh_rows":
            seen |= bool(fresh) and any(c not in fresh for c in classes)
        else:
            seen |= len(fresh) > 1
    assert seen, case
    want = IncrementalTrie(sorted(_apply_all(state, batches).items()))
    assert execs[0].root_bytes(execs[0].last_root) == want.commit_cpu()


def _apply_all(state, batches):
    out = dict(state)
    for b in batches:
        for k, v in b:
            if v:
                out[k] = v
            else:
                out.pop(k, None)
    return out


def test_pad_lanes_count_the_tiles_past_the_real_lanes():
    """`resident/keccak/pad_lanes` is the lanes the tile loop hashed
    beyond the segments' own: tiles times TILE_LANES minus Σ lanes."""
    rng = random.Random(43)
    state = _state(rng, 600)
    dev = IncrementalTrie(sorted(state.items()))
    ex = ResidentExecutor()
    exports = _recorded(ex)
    dev.commit_resident(ex)
    pad = default_registry.counter("resident/keccak/pad_lanes")
    lanes = default_registry.counter("resident/keccak/lanes")
    p0, l0 = pad.count(), lanes.count()
    dev.update(_updates(rng, sorted(state), 40))
    dev.commit_resident(ex)
    specs = exports[-1]["specs"]
    tiles = sum(-(-int(s[1]) // TILE_LANES) for s in specs)
    assert lanes.count() - l0 == int(specs[:, 1].sum())
    assert pad.count() - p0 == tiles * TILE_LANES - int(specs[:, 1].sum())
    assert pad.count() - p0 > 0


def test_a_sharded_executor_stays_root_exact():
    """The fused program over a 4-device mesh (store and arenas
    row-sharded, pinned shardings) keeps every root exact across three
    block commits and keeps its state sharded."""
    from coreth_tpu.parallel import make_mesh, resident_executor_over_mesh

    rng = random.Random(44)
    state = _state(rng, 800)
    dev = IncrementalTrie(sorted(state.items()))
    cpu = IncrementalTrie(sorted(state.items()))
    ex = resident_executor_over_mesh(make_mesh(4))
    assert ex.root_bytes(dev.commit_resident(ex)) == cpu.commit_cpu()
    keys = sorted(state)
    for n in (48, 36, 52):
        batch = _updates(rng, keys, n)
        dev.update(batch)
        cpu.update(batch)
        assert ex.root_bytes(dev.commit_resident(ex)) == cpu.commit_cpu()
    for arr in [ex.store, *ex.arenas.values()]:
        assert len(arr.sharding.device_set) == 4
