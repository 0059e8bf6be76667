"""Pallas TPU kernel for batched Keccak-f[1600] / Keccak-256.

Same math and host layout contract as coreth_tpu/ops/keccak_jax.py, but the
whole sponge runs inside one Pallas kernel so the 25-lane state lives in VMEM
(registers) across all 24 rounds and all rate blocks — no HBM traffic between
rounds. The batch is laid out with lanes on the last two axes as (8, 128)
tiles to match the TPU VPU shape.

Replaces the CPU hasher fan-out of the reference (/root/reference/trie/
hasher.go:124-139) with a data-parallel device kernel.

Layout (device side):
    words:   uint32[L, 34, R, 128]  -- R*128 lanes, R multiple of 8
    nblocks: int32[R, 128]
    out:     uint32[8, R, 128]
Grid: (R // 8,) over batch tiles; each program hashes 1024 lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .keccak_ref import _ROUND_CONSTANTS, _ROTC

WORDS_PER_BLOCK = 34
PALLAS_LANES = 1024  # lane multiple the segment kernel's grid tiles
_RC_LO = tuple(rc & 0xFFFFFFFF for rc in _ROUND_CONSTANTS)
_RC_HI = tuple(rc >> 32 for rc in _ROUND_CONSTANTS)

# Unroll the rate-block loop when small (trie nodes are 1-5 blocks); fall back
# to fori_loop with dynamic block indexing for large inputs (contract code).
_UNROLL_MAX_BLOCKS = 8


def _rotl_pair(lo, hi, n: int):
    n %= 64
    if n == 0:
        return lo, hi
    if n == 32:
        return hi, lo
    if n > 32:
        lo, hi = hi, lo
        n -= 32
    m = 32 - n
    return (lo << n) | (hi >> m), (hi << n) | (lo >> m)


def _permute(lo, hi):
    for r in range(24):
        c_lo = [lo[x] ^ lo[x + 5] ^ lo[x + 10] ^ lo[x + 15] ^ lo[x + 20] for x in range(5)]
        c_hi = [hi[x] ^ hi[x + 5] ^ hi[x + 10] ^ hi[x + 15] ^ hi[x + 20] for x in range(5)]
        d_lo, d_hi = [], []
        for x in range(5):
            rl, rh = _rotl_pair(c_lo[(x + 1) % 5], c_hi[(x + 1) % 5], 1)
            d_lo.append(c_lo[(x - 1) % 5] ^ rl)
            d_hi.append(c_hi[(x - 1) % 5] ^ rh)
        lo = [lo[i] ^ d_lo[i % 5] for i in range(25)]
        hi = [hi[i] ^ d_hi[i % 5] for i in range(25)]
        b_lo = [None] * 25
        b_hi = [None] * 25
        for x in range(5):
            for y in range(5):
                src = x + 5 * y
                dst = y + 5 * ((2 * x + 3 * y) % 5)
                b_lo[dst], b_hi[dst] = _rotl_pair(lo[src], hi[src], _ROTC[src])
        lo = [
            b_lo[i] ^ (~b_lo[(i % 5 + 1) % 5 + 5 * (i // 5)] & b_lo[(i % 5 + 2) % 5 + 5 * (i // 5)])
            for i in range(25)
        ]
        hi = [
            b_hi[i] ^ (~b_hi[(i % 5 + 1) % 5 + 5 * (i // 5)] & b_hi[(i % 5 + 2) % 5 + 5 * (i // 5)])
            for i in range(25)
        ]
        lo[0] = lo[0] ^ jnp.uint32(_RC_LO[r])
        hi[0] = hi[0] ^ jnp.uint32(_RC_HI[r])
    return lo, hi


def _absorb_permute_snapshot(lo, hi, out, block_words, j, nb):
    """Absorb one masked rate block, permute, snapshot finished lanes."""
    live = j < nb
    zero = jnp.zeros_like(lo[0])
    lo = list(lo)
    hi = list(hi)
    for i in range(17):
        lo[i] = lo[i] ^ jnp.where(live, block_words[2 * i], zero)
        hi[i] = hi[i] ^ jnp.where(live, block_words[2 * i + 1], zero)
    lo, hi = _permute(lo, hi)
    digest = [lo[0], hi[0], lo[1], hi[1], lo[2], hi[2], lo[3], hi[3]]
    is_last = j == nb - 1
    out = [jnp.where(is_last, digest[w], out[w]) for w in range(8)]
    return tuple(lo), tuple(hi), tuple(out)


def _make_kernel(num_blocks: int):
    def kernel(words_ref, nblocks_ref, out_ref):
        nb = nblocks_ref[:]
        zeros = jnp.zeros(nb.shape, jnp.uint32)
        lo = (zeros,) * 25
        hi = (zeros,) * 25
        out = (zeros,) * 8
        if num_blocks <= _UNROLL_MAX_BLOCKS:
            for j in range(num_blocks):
                block = [words_ref[j, w] for w in range(WORDS_PER_BLOCK)]
                lo, hi, out = _absorb_permute_snapshot(
                    lo, hi, out, block, jnp.int32(j), nb
                )
        else:
            def body(j, carry):
                lo, hi, out = carry
                block = [words_ref[j, w] for w in range(WORDS_PER_BLOCK)]
                return _absorb_permute_snapshot(lo, hi, out, block, j, nb)

            lo, hi, out = jax.lax.fori_loop(0, num_blocks, body, (lo, hi, out))
        for w in range(8):
            out_ref[w] = out[w]

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def keccak256_blocks_pallas(words: jax.Array, nblocks: jax.Array, interpret: bool = False):
    """Pallas drop-in for keccak_jax.keccak256_blocks.

    words: uint32[B, L, 34]; nblocks: int32[B]; B must be a multiple of 1024.
    Returns uint32[B, 8].
    """
    b, num_blocks, _ = words.shape
    assert b % 1024 == 0, "pallas keccak batch must be padded to 1024 lanes"
    rows = b // 128
    w = jnp.transpose(words, (1, 2, 0)).reshape(num_blocks, WORDS_PER_BLOCK, rows, 128)
    nb = nblocks.reshape(rows, 128)

    grid = (rows // 8,)
    out = pl.pallas_call(
        _make_kernel(num_blocks),
        out_shape=jax.ShapeDtypeStruct((8, rows, 128), jnp.uint32),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (num_blocks, WORDS_PER_BLOCK, 8, 128), lambda r: (0, 0, r, 0)
            ),
            pl.BlockSpec((8, 128), lambda r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((8, 8, 128), lambda r: (0, r, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(w, nb)
    return jnp.transpose(out.reshape(8, b), (1, 0))


def pallas_impl(interpret: bool = False):
    """Implementation callable for BatchedKeccak (batch_multiple=1024)."""

    def impl(words, nblocks):
        return keccak256_blocks_pallas(words, nblocks, interpret=interpret)

    return impl


# ---------------------------------------------------------------------------
# Segment kernel for the staged commit (ops/keccak_staged.py)
# ---------------------------------------------------------------------------


def _make_segment_kernel(num_blocks: int):
    """Mask-free variant: every lane has exactly num_blocks rate blocks
    (the native planner buckets segments by exact block count), so there is
    no nblocks input, no live-lane masking, and no digest snapshotting —
    the digest is simply the state after the final permutation."""

    def kernel(words_ref, out_ref):
        shape = words_ref.shape[-2:]  # (8, 128) lane tile
        zeros = jnp.zeros(shape, jnp.uint32)
        lo = [zeros] * 25
        hi = [zeros] * 25

        def absorb_permute(lo, hi, j):
            lo = list(lo)
            hi = list(hi)
            for i in range(17):
                lo[i] = lo[i] ^ words_ref[j, 2 * i]
                hi[i] = hi[i] ^ words_ref[j, 2 * i + 1]
            return _permute(lo, hi)

        if num_blocks <= _UNROLL_MAX_BLOCKS:
            for j in range(num_blocks):
                lo, hi = absorb_permute(lo, hi, j)
        else:
            def body(j, carry):
                lo, hi = carry
                lo, hi = absorb_permute(list(lo), list(hi), j)
                return tuple(lo), tuple(hi)

            lo, hi = jax.lax.fori_loop(
                0, num_blocks, body, (tuple(lo), tuple(hi))
            )
        digest = [lo[0], hi[0], lo[1], hi[1], lo[2], hi[2], lo[3], hi[3]]
        for w in range(8):
            out_ref[w] = digest[w]

    return kernel


def segment_keccak_pallas(words: jax.Array, interpret: bool = False) -> jax.Array:
    """uint32[P, L, 34] -> uint32[P, 8]; P must be a multiple of 1024.

    Drop-in for keccak_staged._segment_keccak on lane counts the TPU grid
    can tile (the staged runner falls back to the XLA scan for smaller
    segments). State lives in VMEM across every round and block — one HBM
    read of the message words, one HBM write of digests."""
    p, num_blocks, _ = words.shape
    assert p % PALLAS_LANES == 0, \
        "pallas segment batch must be a multiple of 1024 lanes"
    rows = p // 128
    w = jnp.transpose(words, (1, 2, 0)).reshape(
        num_blocks, WORDS_PER_BLOCK, rows, 128
    )
    grid = (rows // 8,)
    out = pl.pallas_call(
        _make_segment_kernel(num_blocks),
        out_shape=jax.ShapeDtypeStruct((8, rows, 128), jnp.uint32),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (num_blocks, WORDS_PER_BLOCK, 8, 128), lambda r: (0, 0, r, 0)
            ),
        ],
        out_specs=pl.BlockSpec((8, 8, 128), lambda r: (0, r, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(w)
    return jnp.transpose(out.reshape(8, p), (1, 0))


def staged_seg_impl(interpret: bool = False):
    """seg_impl for keccak_staged.StagedCommit: Pallas for big segments,
    XLA scan fallback below the 1024-lane grid minimum (shape decision is
    static at trace time)."""

    def impl(words):
        if words.shape[0] % PALLAS_LANES == 0:
            return segment_keccak_pallas(words, interpret=interpret)
        from .keccak_staged import _segment_keccak

        return _segment_keccak(words)

    impl.pallas_lanes = PALLAS_LANES
    return impl


def count_segments(layer: str, seg_impl, lanes) -> None:
    """Count one commit's segments by the kernel that hashes them:
    `<layer>/segments/pallas` for lane counts a staged_seg_impl sends to
    the Pallas kernel, `<layer>/segments/xla` for the rest."""
    from ..metrics import default_registry

    tile = getattr(seg_impl, "pallas_lanes", 0)
    n_pallas = sum(1 for n in lanes if tile and n % tile == 0)
    default_registry.counter(f"{layer}/segments/pallas").inc(n_pallas)
    default_registry.counter(f"{layer}/segments/xla").inc(
        len(lanes) - n_pallas)
