"""Keccak-256 (the Ethereum variant: pad byte 0x01, not SHA-3's 0x06).

`keccak256_batch` hashes many messages at once: the 25 lanes of every
state are NumPy uint64 vectors, so one keccak-f[1600] round is a few
dozen vector operations whatever the batch size. `keccak256` is the
one-message form. Nothing here imports the program under test.
"""

from __future__ import annotations

import numpy as np

RATE = 136
_RC = (
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
)
# rotation offset of lane (x, y), indexed x + 5 * y
_ROT = (
    0, 1, 62, 28, 27,
    36, 44, 6, 55, 20,
    3, 10, 43, 25, 39,
    41, 45, 15, 21, 8,
    18, 2, 61, 56, 14,
)
_RC_NP = [np.uint64(c) for c in _RC]
_SHIFT = [(np.uint64(r), np.uint64(64 - r)) for r in _ROT]


def _rotl(a, i):
    if _ROT[i] == 0:
        return a
    left, right = _SHIFT[i]
    return (a << left) | (a >> right)


def _permute(s: list) -> list:
    """keccak-f[1600] over a list of 25 uint64 vectors (lane x + 5y)."""
    for rc in _RC_NP:
        c = [s[x] ^ s[x + 5] ^ s[x + 10] ^ s[x + 15] ^ s[x + 20]
             for x in range(5)]
        d = [c[(x - 1) % 5] ^ ((c[(x + 1) % 5] << np.uint64(1))
                               | (c[(x + 1) % 5] >> np.uint64(63)))
             for x in range(5)]
        b = [None] * 25
        for x in range(5):
            for y in range(5):
                i = x + 5 * y
                b[y + 5 * ((2 * x + 3 * y) % 5)] = _rotl(s[i] ^ d[x], i)
        s = [b[i] ^ (~b[(i % 5 + 1) % 5 + 5 * (i // 5)]
                     & b[(i % 5 + 2) % 5 + 5 * (i // 5)])
             for i in range(25)]
        s[0] = s[0] ^ rc
    return s


def _pad(msg: bytes, blocks: int) -> bytes:
    out = bytearray(msg) + bytes(blocks * RATE - len(msg))
    out[len(msg)] ^= 0x01
    out[-1] ^= 0x80
    return bytes(out)


def keccak256_batch(msgs) -> list:
    """Digests of a sequence of byte strings, in order."""
    msgs = list(msgs)
    out = [b""] * len(msgs)
    groups: dict = {}
    for i, m in enumerate(msgs):
        groups.setdefault(len(m) // RATE + 1, []).append(i)
    for blocks, idx in groups.items():
        n = len(idx)
        buf = np.frombuffer(b"".join(_pad(msgs[i], blocks) for i in idx),
                            dtype="<u8").reshape(n, blocks, RATE // 8)
        s = [np.zeros(n, np.uint64) for _ in range(25)]
        for b in range(blocks):
            for w in range(RATE // 8):
                s[w] = s[w] ^ buf[:, b, w]
            s = _permute(s)
        dig = np.stack(s[:4], axis=1).astype("<u8").tobytes()
        for k, i in enumerate(idx):
            out[i] = dig[32 * k:32 * k + 32]
    return out


def keccak256(msg: bytes) -> bytes:
    return keccak256_batch([msg])[0]
