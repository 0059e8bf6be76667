"""Client-side transaction signing, kept apart from the program under test.

EIP-1559 (type 2) transactions are encoded with the benchmark's own RLP,
hashed with its own keccak and signed over secp256k1. The curve's scalar
multiplication comes from `cryptography` (OpenSSL); the nonce k is
derived from the key and the message (HMAC-SHA256), so one seed always
gives the same signed bytes. Signatures are low-s with the recovery id
as `v`, as go-ethereum requires.

`SignerPool` runs the signing in worker processes started with the
`spawn` method. The workers import this module, NumPy and `cryptography`
only, never JAX, so they can be started before the parent touches the
chip and never compete for it.
"""

from __future__ import annotations

import hashlib
import hmac
import multiprocessing
import queue
import time

from . import rlp
from .keccak import keccak256_batch

N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141


def _point(k: int):
    from cryptography.hazmat.primitives.asymmetric import ec

    nums = ec.derive_private_key(k, ec.SECP256K1()).public_key() \
        .public_numbers()
    return nums.x, nums.y


def derive_keys(tag: bytes, n: int) -> list:
    """n private keys (ints in [1, N)) from a tag."""
    digests = keccak256_batch(tag + i.to_bytes(8, "big") for i in range(n))
    return [int.from_bytes(d, "big") % (N - 1) + 1 for d in digests]


def addresses(keys) -> list:
    pubs = [_point(k) for k in keys]
    return [d[12:] for d in keccak256_batch(
        x.to_bytes(32, "big") + y.to_bytes(32, "big") for x, y in pubs)]


def _sign_hash(z: bytes, d: int):
    zi = int.from_bytes(z, "big")
    nonce = hmac.new(d.to_bytes(32, "big"), z, hashlib.sha256).digest()
    k = int.from_bytes(nonce, "big") % (N - 1) + 1
    while True:
        rx, ry = _point(k)
        r = rx % N
        s = pow(k, -1, N) * (zi + r * d) % N
        if r and s and rx < N:
            break
        k = k % (N - 1) + 1
    v = ry & 1
    if s > N // 2:
        s, v = N - s, v ^ 1
    return v, r, s


def sign_dynamic_fee_txs(chain_id: int, keys, items) -> list:
    """Raw signed type-2 txs. Each item is (sender index, nonce, tip,
    max fee, gas, to, value, data)."""
    bodies = [[chain_id, nonce, tip, max_fee, gas, to, value, data, []]
              for _, nonce, tip, max_fee, gas, to, value, data in items]
    hashes = keccak256_batch(b"\x02" + rlp.encode(b) for b in bodies)
    out = []
    for item, body, z in zip(items, bodies, hashes):
        v, r, s = _sign_hash(z, keys[item[0]])
        out.append(b"\x02" + rlp.encode(body + [v, r, s]))
    return out


def _worker(keys, chain_id, jobs, results) -> None:
    while True:
        job = jobs.get()
        if job is None:
            return
        idx, items = job
        t0 = time.perf_counter()
        raws = sign_dynamic_fee_txs(chain_id, keys, items)
        results.put((idx, raws, time.perf_counter() - t0))


class SignerPool:
    """Chunks of tx items in, signed raw txs out, in submission order."""

    def __init__(self, keys, chain_id: int, workers: int):
        ctx = multiprocessing.get_context("spawn")
        self._jobs = ctx.Queue()
        self._results = ctx.Queue()
        self._procs = [ctx.Process(target=_worker, daemon=True,
                                   args=(keys, chain_id, self._jobs,
                                         self._results))
                       for _ in range(workers)]
        for p in self._procs:
            p.start()
        self._submitted = 0
        self._taken = 0
        self._ready: dict = {}
        self.signed = 0
        self.sign_s = 0.0  # worker seconds spent signing
        self.wait_s = 0.0  # parent seconds spent waiting for a chunk

    def submit(self, items) -> None:
        self._jobs.put((self._submitted, items))
        self._submitted += 1

    def _collect(self, timeout=None) -> bool:
        try:
            idx, raws, secs = self._results.get(timeout=timeout)
        except queue.Empty:
            return False
        self._ready[idx] = raws
        self.signed += len(raws)
        self.sign_s += secs
        return True

    def take(self) -> list:
        """The next chunk in submission order, waiting for it if need be."""
        if self._taken >= self._submitted:
            raise RuntimeError("no signing job outstanding")
        while self._collect(timeout=0):
            pass
        t0 = time.perf_counter()
        while self._taken not in self._ready:
            if not self._collect(timeout=1.0) and not all(
                    p.is_alive() for p in self._procs):
                raise RuntimeError("a signing worker died")
        self.wait_s += time.perf_counter() - t0
        raws = self._ready.pop(self._taken)
        self._taken += 1
        return raws

    def close(self) -> None:
        for _ in self._procs:
            self._jobs.put(None)
        deadline = time.monotonic() + 30
        while any(p.is_alive() for p in self._procs) \
                and time.monotonic() < deadline:
            self._collect(timeout=0.2)  # drain before join
        for p in self._procs:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()
                p.join(timeout=5)
        self._jobs.close()
        self._results.close()
