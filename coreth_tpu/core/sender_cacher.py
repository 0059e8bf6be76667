"""Background batched sender recovery (role of /root/reference/core/
sender_cacher.go).

The reference fans ecrecover across N goroutines with a strided split
(sender_cacher.go:88-115). Here the seam is batch-first: recover() takes
the whole tx slice and dispatches to a pluggable batch recoverer — the
C++ keccak path covers the hashing; the secp256k1 scalar work stays on
CPU (BASELINE.json config #3 keeps verification host-side). A thread pool
overlaps recovery with block execution.

recover() tags each dispatch with a batch token so wait(token) joins one
block's futures only: with the insert pipeline keeping two blocks in
flight, a global wait would serialize block k+1's recovery behind block
k's — exactly the stall the pipeline exists to remove.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

from ..metrics import default_registry as _metrics
from ..metrics.spans import span
from .types import Signer, Transaction

# txs below this per shard aren't worth a second dispatch wave: the
# shard's Python-side item building is cheaper than the bookkeeping
_SHARD_MIN = 64

# per-shard wall time; rolls up under the chain/phase/recover clock the
# insert path wraps around wait()
_shard_timer = _metrics.timer("chain/recover/shard")


class TxSenderCacher:
    def __init__(self, threads: int = 4, batch_recover=None):
        self.threads = max(threads, 1)
        self._pool = ThreadPoolExecutor(max_workers=self.threads)
        self._batch_recover = batch_recover
        self._lock = threading.Lock()
        # batch token -> outstanding futures for that recover() call
        self._batches: Dict[int, list] = {}  # guarded-by: _lock
        self._tokens = itertools.count(1)
        # fork guard (exec shards, core/exec_shards.py): fork copies only
        # the calling thread, so an inherited ThreadPoolExecutor is a
        # threadless shell — submit() would queue work nobody runs and
        # wait() would hang forever on it
        self._owner_pid = os.getpid()  # guarded-by: _lock

    def _ensure_pool(self) -> None:
        """Respawn-after-fork guard: if this cacher object crossed a
        fork, its pool's worker threads did not — submits would queue
        work nobody runs and waits would hang. Rebuild the pool (and
        drop the parent's futures — they can never complete here) before
        any dispatch or join. The unlocked pre-check is benign: the pid
        only changes across fork, and a forked child starts single-
        threaded."""
        if os.getpid() == self._owner_pid:
            return
        with self._lock:
            pid = os.getpid()
            if pid == self._owner_pid:
                return
            _metrics.counter("exec/shard/fork_guard_trips").inc()
            self._pool = ThreadPoolExecutor(max_workers=self.threads)
            self._batches.clear()
            self._owner_pid = pid

    def recover(self, signer: Signer, txs: List[Transaction]) -> Optional[int]:
        """Kick off sender recovery for txs; results land in each tx's
        _sender cache so later Sender() calls are free. Returns a batch
        token for wait(token) (None when there was nothing to do)."""
        if not txs:
            return None
        self._ensure_pool()
        # prune finished batches so the fire-and-forget path stays bounded
        with self._lock:
            for tok in [t for t, fs in self._batches.items()
                        if all(f.done() for f in fs)]:
                del self._batches[tok]
            token = next(self._tokens)
        if self._batch_recover is not None:
            fut = self._pool.submit(self._batch_recover, signer, txs)
            # under _lock: a concurrent wait() pops the batch, and an
            # unlocked store can land after the pop and be lost
            with self._lock:
                self._batches[token] = [fut]
            return token

        def work_batch(chunk, shard=0, of=1, native_threads=0):
            t0 = time.perf_counter()
            try:
                with span("chain/recover/shard", shard=shard, of=of,
                          txs=len(chunk)):
                    signer.sender_batch(chunk, native_threads=native_threads)
            except Exception:
                for tx in chunk:
                    try:
                        signer.sender(tx)
                    except Exception:
                        # recovery here is a prefetch — the insert path
                        # re-derives senders and surfaces the real error —
                        # but a malformed-signature flood must be visible
                        _metrics.counter(
                            "core/sender_cacher/recover_error").inc()
            _shard_timer.update(time.perf_counter() - t0)

        # strided shards across the CPU-thread pool, each pinned to ONE
        # native thread: the Python-side item building (RLP + sig-hash
        # keccak, GIL-bound) of shard k overlaps the GIL-released native
        # recovery of the other shards — one big native call would
        # serialise all the item building in front of it
        # (sender_cacher.go:88-115's strided split, batch-first)
        n = min(self.threads, max(1, len(txs) // _SHARD_MIN))
        if n <= 1:
            futs = [self._pool.submit(work_batch, txs)]
        else:
            futs = [self._pool.submit(work_batch, txs[i::n], i, n, 1)
                    for i in range(n)]
        with self._lock:
            self._batches[token] = futs
        return token

    def recover_from_block(self, signer: Signer, block) -> Optional[int]:
        return self.recover(signer, block.transactions)

    def wait(self, token: Optional[int] = None) -> None:
        """Join one recover() batch (by token), or every outstanding
        batch when token is None. A token that already completed (or was
        pruned, or is None from an empty recover) is a no-op — senders
        for those txs are cached either way."""
        self._ensure_pool()
        with self._lock:
            if token is None:
                futures = [f for fs in self._batches.values() for f in fs]
                self._batches.clear()
            else:
                futures = self._batches.pop(token, [])
        for f in futures:
            f.result()

    def shutdown(self) -> None:
        self._pool.shutdown(wait=False)


# module-level shared cacher (core/sender_cacher.go txSenderCacher
# singleton). Fan-out follows the shared CPU-thread policy — the
# CORETH_TPU_CPU_THREADS env override, else min(16, cores) — instead of a
# hardcoded width (the reference sizes it runtime.NumCPU()).
from ..native import default_cpu_threads

sender_cacher = TxSenderCacher(threads=default_cpu_threads())
