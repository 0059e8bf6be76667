"""Native batched secp256k1 recovery vs the pure-Python oracle
(reference seam: core/sender_cacher.go:88-115 over cgo libsecp256k1;
here secp256k1.cpp over ctypes, crypto/secp256k1.py as the oracle)."""

import random

import pytest

from coreth_tpu.core.types import Signer, Transaction
from coreth_tpu.crypto import secp256k1 as py_secp
from coreth_tpu.native import secp

def test_recover_batch_parity_random():
    rng = random.Random(7)
    items, expect = [], []
    for _ in range(64):
        priv = rng.randrange(1, 2**255).to_bytes(32, "big")
        mh = rng.randbytes(32)
        v, r, s = py_secp.sign(mh, priv)
        items.append((mh, v, r, s))
        expect.append(py_secp.priv_to_address(priv))
    got = secp.recover_batch(items)
    assert got == expect


def test_recover_batch_flags_invalid():
    rng = random.Random(8)
    priv = rng.randrange(1, 2**255).to_bytes(32, "big")
    mh = rng.randbytes(32)
    v, r, s = py_secp.sign(mh, priv)
    good = py_secp.priv_to_address(priv)
    items = [
        (mh, v, r, s),
        (mh, v, 0, s),                  # r == 0
        (mh, v, r, py_secp.N),          # s out of range
        (mh, 9, r, s),                  # recid out of range
        (mh, v, 2**256 + 5, s),         # r overflows 32 bytes
        (rng.randbytes(32), v, r, s),   # wrong hash -> wrong (but valid) key
    ]
    got = secp.recover_batch(items)
    assert got[0] == good
    assert got[1] is None and got[2] is None and got[3] is None and got[4] is None
    assert got[5] is not None and got[5] != good


def test_recover_matches_oracle_on_high_recid():
    """recid>=2 (x = r + n) is astronomically rare in the wild; exercise
    the code path directly: any r where r+n < p admits recid 2/3."""
    # small r keeps r + n < p
    r = 0x1234567890ABCDEF
    for recid in (0, 1, 2, 3):
        mh = b"\x01" * 32
        s = 0x5DEECE66D
        want = py_secp.recover_address(mh, recid, r, s)
        got = secp.recover_batch([(mh, recid, r, s)])[0]
        assert got == want


def test_signer_sender_batch_caches():
    signer = Signer(43112)
    rng = random.Random(9)
    txs, addrs = [], []
    for i in range(16):
        priv = rng.randrange(1, 2**255).to_bytes(32, "big")
        tx = Transaction(type=2, chain_id=43112, nonce=i, max_fee=10**10,
                         max_priority_fee=1, gas=21000, to=b"\xaa" * 20,
                         value=1)
        signer.sign(tx, priv)
        tx._sender = None
        txs.append(tx)
        addrs.append(py_secp.priv_to_address(priv))
    # one corrupted signature: stays uncached, sender() raises later
    txs[5].r = 0
    signer.sender_batch(txs)
    for i, tx in enumerate(txs):
        if i == 5:
            assert tx._sender is None
            with pytest.raises(ValueError):
                signer.sender(tx)
        else:
            assert tx._sender == addrs[i]
            assert signer.sender(tx) == addrs[i]  # cache hit


def test_sender_cacher_drains_through_batch():
    from coreth_tpu.core.sender_cacher import TxSenderCacher

    signer = Signer(43112)
    rng = random.Random(10)
    txs, addrs = [], []
    for i in range(20):
        priv = rng.randrange(1, 2**255).to_bytes(32, "big")
        tx = Transaction(type=2, chain_id=43112, nonce=i, max_fee=10**10,
                         max_priority_fee=1, gas=21000, to=b"\xbb" * 20,
                         value=1)
        signer.sign(tx, priv)
        tx._sender = None
        txs.append(tx)
        addrs.append(py_secp.priv_to_address(priv))
    cacher = TxSenderCacher()
    cacher.recover(signer, txs)
    cacher.wait()
    assert [tx._sender for tx in txs] == addrs
    cacher.shutdown()
