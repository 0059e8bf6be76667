"""chip_smoke.py on the CPU: the phases at a tiny size, and the refusal.

The script itself refuses to run without a TPU, so the phases are driven
through chip_smoke.run with the resident mirror forced onto the (CPU)
device: genesis vs the native host oracle, the full 15M-gas block and
three more, RPC reads with a verified eth_getProof, and the replay into
the host-only oracle VM. The --chips 4 branch runs on four devices of
the virtual CPU mesh that conftest sets up.
"""

import io
import json
import os
import subprocess
import sys

import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smoke_phases_pass_at_small_size():
    out = io.StringIO()
    res = chip_smoke.run(
        n_accounts=300, seed=3, small_txs=4, n_senders=64,
        config_json={"resident-account-trie": True,
                     "resident-prefer-host": False},
        expect_tpu=False, out=out)
    lines = out.getvalue().splitlines()
    blocks = [json.loads(l.split(" ", 1)[1]) for l in lines
              if l.startswith("block ")]
    assert [b["number"] for b in blocks] == [1, 2, 3, 4]
    assert blocks[0]["gas_used"] + chip_smoke.GAS_PER_TRANSFER \
        > blocks[0]["gas_limit"]
    moved = json.loads(next(l for l in lines
                            if l.startswith("fallback_counters "))
                       .split(" ", 1)[1])
    assert set(moved) == set(chip_smoke.FALLBACK_COUNTERS)
    assert not any(moved.values())
    names = [r[0] for r in res["phases"]]
    assert names[:2] == ["make_genesis", "vm_initialize"]
    assert "rpc" in names and names[-1] == "oracle_replay"


def _records(lines, tag):
    return [json.loads(l.split(" ", 1)[1]) for l in lines
            if l.startswith(tag + " ")]


def test_smoke_mesh_branch_on_four_devices():
    """The --chips 4 path on four devices of the virtual CPU mesh: store
    and arena rows on 4 devices, every block root equal to the native
    host oracle, and the mirror's spot check over every store shard."""
    out = io.StringIO()
    res = chip_smoke.run(
        n_accounts=300, seed=5, chips=4, small_txs=4, n_senders=64,
        config_json={"resident-account-trie": True,
                     "resident-prefer-host": False},
        expect_tpu=False, out=out)
    lines = out.getvalue().splitlines()
    (mesh,) = _records(lines, "mesh")
    assert mesh["shards"] == 4 and len(set(mesh["devices"])) == 4
    blocks = _records(lines, "block")
    parity = _records(lines, "parity")
    assert [b["number"] for b in blocks] == [1, 2, 3, 4]
    assert [p["number"] for p in parity] == [1, 2, 3, 4]
    assert [p["root"] for p in parity] == [b["root"] for b in blocks]
    assert all(p["oracle"] == "native execute_cpu" for p in parity)
    (spot,) = _records(lines, "spot_check")
    assert spot["nodes"] > 300
    (moved,) = _records(lines, "fallback_counters")
    assert not any(moved.values())
    names = [r[0] for r in res["phases"]]
    assert names[-1] == "spot_check"
    assert "rpc" not in names and "oracle_replay" not in names
    assert [n for n in names if n.endswith("_oracle")] == [
        "genesis_oracle"] + [f"block_{b}_oracle" for b in range(1, 5)]


def test_mesh_block_root_mismatch_fails():
    class Header:
        number, coinbase, root = 7, b"\x01" * 20, b"\xaa" * 32

    class Block:
        class eth_block:
            header = Header

    class Oracle:
        def update(self, state, addrs):
            self.addrs = addrs

        def root(self):
            return b"\xbb" * 32

    class Chain:
        def state_at(self, root):
            return None

    oracle = Oracle()
    with pytest.raises(chip_smoke.SmokeFailure, match="block 7 root"):
        chip_smoke.check_block_root(oracle, Block, [(0, b"\x02" * 20, 1)],
                                    [b"\x03" * 20], Chain())
    assert oracle.addrs == sorted([b"\x01" * 20, b"\x02" * 20, b"\x03" * 20])


@pytest.mark.parametrize("name", chip_smoke.FALLBACK_COUNTERS)
def test_any_fallback_counter_fails_the_run(name):
    from coreth_tpu.metrics import default_registry

    base = chip_smoke.counters(chip_smoke.FALLBACK_COUNTERS)
    chip_smoke.check_no_fallback(chip_smoke.fallbacks_since(base))
    default_registry.counter(name).inc()
    with pytest.raises(chip_smoke.SmokeFailure, match=name):
        chip_smoke.check_no_fallback(chip_smoke.fallbacks_since(base))


def test_script_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
