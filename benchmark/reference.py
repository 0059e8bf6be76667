"""The plain reference that decides `correct`.

It re-executes every accepted block from the deployment's genesis with
its own state and its own Merkle-Patricia trie, and derives each block's
state root, receipts root and gas used. It reads only the block bytes
the program produced and the harness's record of who signed which
transaction; it imports nothing of the program.

Semantics followed (coreth v0.12.5 with every fork active at genesis):
value transfers between externally owned accounts, EIP-1559 fees with
the whole fee (base fee and tip) paid to the block's coinbase, Istanbul
calldata costs, and coreth's five-field account [nonce, balance, root,
codeHash, isMultiCoin]. No account holds code, and a transaction that
would create a contract is refused rather than guessed.
"""

from __future__ import annotations

from . import rlp
from .keccak import keccak256, keccak256_batch
from .mpt import EMPTY_ROOT, Trie

EMPTY_CODE_HASH = keccak256(b"")
TX_GAS = 21_000
DYNAMIC_FEE_TX = 2
ZERO_BLOOM = bytes(256)


class ReferenceError_(Exception):
    """The block does something the reference cannot reproduce."""


class Account:
    __slots__ = ("nonce", "balance")

    def __init__(self, balance=0):
        self.nonce, self.balance = 0, balance


def _intrinsic_gas(data: bytes) -> int:
    nz = sum(1 for b in data if b)
    return TX_GAS + 16 * nz + 4 * (len(data) - nz)


class Reference:
    def __init__(self, genesis):
        """genesis: benchmark.state.Genesis. The trie is built here."""
        self.accounts = {a: Account(b) for a, b in genesis.alloc.items()}
        addrs = list(self.accounts)
        self._hashed = dict(zip(addrs, keccak256_batch(addrs)))
        self.trie = Trie((self._hashed[a], self._leaf(self.accounts[a]))
                         for a in addrs)
        self.root = self.genesis_root = self.trie.root()

    @staticmethod
    def _leaf(acct: Account) -> bytes:
        return rlp.encode([acct.nonce, acct.balance, EMPTY_ROOT,
                           EMPTY_CODE_HASH, 0])

    def _account(self, addr: bytes) -> Account:
        acct = self.accounts.get(addr)
        if acct is None:
            acct = self.accounts[addr] = Account()
            self._hashed[addr] = keccak256(addr)
        return acct

    def apply_block(self, block_bytes: bytes, sender_of) -> dict:
        """Execute one block; returns what the header should say and the
        header's own values beside it."""
        header, txs = rlp.decode(block_bytes)[:2]
        coinbase, number = header[2], rlp.to_int(header[8])
        base_fee = rlp.to_int(header[16])
        touched: set = {coinbase}
        receipts, cumulative = [], 0
        for raw in txs:
            if not isinstance(raw, bytes) or raw[:1] != bytes([DYNAMIC_FEE_TX]):
                raise ReferenceError_("only type-2 transactions are planned")
            sender = sender_of(raw)
            if sender is None:
                raise ReferenceError_(f"block {number}: a transaction the "
                                      "harness never signed")
            cumulative += self._apply_tx(raw, sender, coinbase, base_fee,
                                         touched)
            receipts.append(bytes([DYNAMIC_FEE_TX]) + rlp.encode(
                [1, cumulative, ZERO_BLOOM, []]))
        self._commit_accounts(touched)
        self.root = self.trie.root()
        receipts_root = Trie((rlp.encode(i), r)
                             for i, r in enumerate(receipts)).root()
        return {"number": number, "txs": len(txs),
                "root": self.root, "header_root": header[3],
                "receipts_root": receipts_root, "header_receipts": header[5],
                "gas_used": cumulative,
                "header_gas_used": rlp.to_int(header[10]),
                "gas_limit": rlp.to_int(header[9])}

    def _commit_accounts(self, touched) -> None:
        """Write the changed accounts into the account trie."""
        for addr in touched:
            self.trie.put(self._hashed[addr], self._leaf(self.accounts[addr]))

    def _apply_tx(self, raw: bytes, sender: bytes, coinbase: bytes,
                  base_fee: int, touched: set) -> int:
        """Apply one transfer; returns the gas it used."""
        (_cid, nonce, tip, max_fee, gas, to, value, data, access_list,
         *_sig) = rlp.decode(raw[1:])
        nonce, tip, max_fee = rlp.to_int(nonce), rlp.to_int(tip), \
            rlp.to_int(max_fee)
        gas, value = rlp.to_int(gas), rlp.to_int(value)
        if access_list or not to:
            raise ReferenceError_("access lists and creations are not planned")
        src = self._account(sender)
        if nonce != src.nonce:
            raise ReferenceError_(f"nonce {nonce}, state has {src.nonce}")
        if max_fee < base_fee or tip > max_fee:
            raise ReferenceError_("fee cap below the base fee")
        if src.balance < gas * max_fee + value:
            raise ReferenceError_("sender cannot pay for the transaction")
        price = min(max_fee, base_fee + tip)
        used = _intrinsic_gas(data)
        if used > gas:
            raise ReferenceError_("gas below the intrinsic cost")
        src.nonce += 1
        src.balance -= used * price + value
        self._account(to).balance += value
        touched.update((sender, to))
        self._account(coinbase).balance += used * price
        return used


def compare(results: list) -> dict:
    """Counts of blocks whose header disagrees with the reference."""
    return {
        "state_root_diffs": sum(r["root"] != r["header_root"]
                                for r in results),
        "receipts_root_diffs": sum(r["receipts_root"] != r["header_receipts"]
                                   for r in results),
        "gas_used_diffs": sum(r["gas_used"] != r["header_gas_used"]
                              for r in results),
    }
