"""The one traffic generator: a mix file's parameters plus `--seed` in,
one block's worth of unsigned transactions at a time out.

The mix is coreth's own InsertChain ring benchmark (`genTxRing` in
core/bench_test.go): value transfers that walk a ring of accounts, each
account sending to the next. The ring is the configuration's senders in
the order their keys are derived; `--seed` picks where the walk starts
and draws the values. Every seed therefore sends the same number of
transactions from as many distinct senders, each to an existing account.
Each sender's nonces run in order.
"""

from __future__ import annotations

import random


class TxStream:
    def __init__(self, genesis, mix: dict, seed: int):
        self.g = genesis
        self.mix = mix
        self.nonces = [0] * len(genesis.senders)
        if mix["tx"] != "ring_transfer":
            raise ValueError(f"unknown tx kind {mix['tx']!r}")
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Draw what follows from another seed: the walk restarts at a
        place drawn from it; nonces carry on."""
        self.rng = random.Random(seed)
        self.at = self.rng.randrange(len(self.nonces))

    def block(self) -> list:
        """One block's worth: (sender, nonce, tip, max fee, gas, to,
        value, data) items."""
        mix, ring = self.mix, self.g.senders
        lo, hi = mix["value_wei"]
        out = []
        for _ in range(mix["txs_per_block"]):
            sender = self.at
            self.at = (sender + 1) % len(ring)
            out.append((sender, self.nonces[sender], mix["tip_wei"],
                        mix["max_fee_wei"], mix["gas"], ring[self.at],
                        self.rng.randrange(lo, hi), b""))
            self.nonces[sender] += 1
        return out
