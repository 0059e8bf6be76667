"""A tiny run with the timed path broken underneath must read `correct`
false, once for each fault a cell of this benchmark can have:

  - a commit that returns its state unchanged (the root before the block);
  - half of the batch left out (the mirror commits every other dirty leaf);
  - an answer altered where it is produced (a bit of the device root, or
    the receipts the chain commits to);
  - a device path that falls back to the host (a fallback counter moves).
The exchange between chips has no fault here: every cell runs on one.
"""

import io

import jax.numpy as jnp
import pytest

import benchtools
from benchmark import harness


def _stale_root(monkeypatch):
    from coreth_tpu.ops.keccak_resident import ResidentExecutor

    run, before = ResidentExecutor.run, {}

    def unchanged(ex, export):
        root = run(ex, export)
        prev = before.get(id(ex), root)
        before[id(ex)] = root
        return prev

    monkeypatch.setattr(ResidentExecutor, "run", unchanged)


def _half_batch(monkeypatch):
    from coreth_tpu.state.resident_trie import MirrorStateTrie

    batch = MirrorStateTrie._batch
    monkeypatch.setattr(MirrorStateTrie, "_batch",
                        lambda self: batch(self)[::2])


def _flipped_root(monkeypatch):
    from coreth_tpu.ops.keccak_resident import ResidentExecutor

    run = ResidentExecutor.run
    monkeypatch.setattr(ResidentExecutor, "run", lambda ex, export: run(
        ex, export) ^ jnp.array([1, 0, 0, 0, 0, 0, 0, 0], jnp.uint32))


def _altered_receipt(monkeypatch):
    from coreth_tpu.core.types import Receipt

    encode = Receipt.encode
    monkeypatch.setattr(Receipt, "encode",
                        lambda self: encode(self)[:-1] + b"\x01")


def _fallback(monkeypatch):
    from coreth_tpu.metrics import default_registry
    from coreth_tpu.ops.keccak_resident import ResidentExecutor

    run = ResidentExecutor.run

    def falls_back(ex, export):
        default_registry.counter("ops/device/demotions").inc(1)
        return run(ex, export)

    monkeypatch.setattr(ResidentExecutor, "run", falls_back)


@pytest.mark.parametrize("fault", [
    _stale_root, _half_batch, _flipped_root, _altered_receipt, _fallback])
def test_a_broken_timed_path_reads_incorrect(tmp_path, monkeypatch, fault):
    root = benchtools.tiny_root(tmp_path)
    fault(monkeypatch)
    res = harness.run_cell("tiny-transfers", 21, 0.0, False, root=root,
                           require_tpu=False, out=io.StringIO(),
                           err=io.StringIO())
    assert res["correct"] is False
    assert res["failed"] >= 1
    failing = [k for k, c in res["checks"].items() if not harness.within(c)]
    assert failing
