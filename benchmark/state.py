"""A deployment's genesis state, built from its configuration and the
run's seed.

The same plain description feeds the program (through `Genesis`, in the
harness) and the reference, so neither takes anything from the other.
What fixes the trie's shape (the addresses and the sender keys) is drawn
from the configuration's `state_seed`; the balances in the leaves from
the run's `--seed`. Balances leave every node's size unchanged, so every
run of a cell boots a trie of one shape, and the programs that commit it
compile once per checkout.
"""

from __future__ import annotations

import numpy as np

from .signer import addresses, derive_keys


class Genesis:
    """alloc: address -> balance in wei; every account is an externally
    owned one, with no code and no storage."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        tag = f"{cfg['name']}/{cfg['state_seed']}".encode()
        self.keys = derive_keys(tag + b"/senders", cfg["senders"])
        self.senders = addresses(self.keys)
        rng = np.random.default_rng(cfg["state_seed"])
        values = np.random.default_rng(seed)
        self.alloc = {a: int(cfg["sender_balance_wei"]) for a in self.senders}
        n_other = cfg["accounts"] - cfg["senders"]
        raw = rng.integers(0, 256, size=(n_other, 20),
                           dtype=np.uint8).tobytes()
        bal = values.integers(1, int(cfg["account_balance_max_wei"]),
                              size=n_other, dtype=np.int64)
        for i, b in enumerate(bal.tolist()):
            self.alloc[raw[20 * i:20 * i + 20]] = b
        if len(self.alloc) != cfg["accounts"]:
            raise ValueError("duplicate genesis address drawn")
