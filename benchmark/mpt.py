"""A plain Merkle-Patricia trie: the reference for every root compared.

Nodes are Python objects; an update clears the cached reference of each
node on its path, and `root()` re-encodes exactly those nodes, bottom
up, hashing each level in one `keccak256_batch` call. Keys are byte
strings no one of which is a prefix of another (32-byte hashed keys,
RLP-encoded receipt indices), so no branch carries a value. Deleting is
not needed by any workload and is refused.
"""

from __future__ import annotations

from . import rlp
from .keccak import keccak256, keccak256_batch

EMPTY_ROOT = keccak256(b"\x80")
_TO_NIB = bytes.maketrans(b"0123456789abcdef", bytes(range(16)))
_FROM_NIB = bytes.maketrans(bytes(range(16)), b"0123456789abcdef")


def nibbles(key: bytes) -> bytes:
    return key.hex().encode().translate(_TO_NIB)


def hex_prefix(nib: bytes, leaf: bool) -> bytes:
    flag = 2 if leaf else 0
    if len(nib) % 2:
        return bytes([(flag + 1) << 4 | nib[0]]) + bytes.fromhex(
            nib[1:].translate(_FROM_NIB).decode())
    return bytes([flag << 4]) + bytes.fromhex(nib.translate(_FROM_NIB).decode())


class Leaf:
    __slots__ = ("path", "value", "ref")

    def __init__(self, path: bytes, value: bytes):
        self.path, self.value, self.ref = path, value, None


class Ext:
    __slots__ = ("path", "child", "ref")

    def __init__(self, path: bytes, child):
        self.path, self.child, self.ref = path, child, None


class Branch:
    __slots__ = ("children", "ref")

    def __init__(self):
        self.children, self.ref = [None] * 16, None


def _common(a: bytes, b: bytes) -> int:
    n = min(len(a), len(b))
    i = 0
    while i < n and a[i] == b[i]:
        i += 1
    return i


def _put(node, path: bytes, value: bytes):
    if node is None:
        return Leaf(path, value)
    if isinstance(node, Branch):
        if not path:
            raise ValueError("a key is a prefix of another")
        node.children[path[0]] = _put(node.children[path[0]], path[1:], value)
        node.ref = None
        return node
    if isinstance(node, Leaf) and node.path == path:
        node.value, node.ref = value, None
        return node
    cp = _common(node.path, path)
    if isinstance(node, Ext) and cp == len(node.path):
        node.child = _put(node.child, path[cp:], value)
        node.ref = None
        return node
    if cp == len(path) or cp == len(node.path):
        raise ValueError("a key is a prefix of another")
    br = Branch()
    rest = node.path[cp + 1:]
    if isinstance(node, Leaf):
        br.children[node.path[cp]] = Leaf(rest, node.value)
    else:
        br.children[node.path[cp]] = Ext(rest, node.child) if rest \
            else node.child
    br.children[path[cp]] = Leaf(path[cp + 1:], value)
    return Ext(path[:cp], br) if cp else br


def _build(items, lo: int, hi: int, depth: int):
    """Subtrie over sorted (nibble key, value) items[lo:hi] below depth."""
    first, last = items[lo][0], items[hi - 1][0]
    if hi - lo == 1:
        return Leaf(first[depth:], items[lo][1])
    cp = depth + _common(first[depth:], last[depth:])
    if cp > depth:
        return Ext(first[depth:cp], _build(items, lo, hi, cp))
    br = Branch()
    i = lo
    while i < hi:
        nib = items[i][0][depth]
        j = i + 1
        while j < hi and items[j][0][depth] == nib:
            j += 1
        br.children[nib] = _build(items, i, j, depth + 1)
        i = j
    return br


class Trie:
    def __init__(self, items=()):
        """items: (key, value) pairs with distinct keys, bulk-loaded."""
        pairs = sorted((nibbles(k), v) for k, v in items)
        for a, b in zip(pairs, pairs[1:]):
            if a[0] == b[0]:
                raise ValueError("duplicate key")
        self._root = _build(pairs, 0, len(pairs), 0) if pairs else None

    def put(self, key: bytes, value: bytes) -> None:
        if not value:
            raise ValueError("deleting a key is not supported")
        self._root = _put(self._root, nibbles(key), value)

    def root(self) -> bytes:
        if self._root is None:
            return EMPTY_ROOT
        levels: list = []

        def height(node) -> int:
            if node is None or node.ref is not None:
                return -1
            if isinstance(node, Leaf):
                h = 0
            elif isinstance(node, Ext):
                h = height(node.child) + 1
            else:
                h = max(height(c) for c in node.children) + 1
            while len(levels) <= h:
                levels.append([])
            levels[h].append(node)
            return h

        height(self._root)
        for level in levels:
            encs = [_encode(n) for n in level]
            long_ = [i for i, e in enumerate(encs) if len(e) >= 32]
            for i, d in zip(long_, keccak256_batch(encs[i] for i in long_)):
                level[i].ref = d
            for n, e in zip(level, encs):
                if n.ref is None:
                    n.ref = e  # embedded in its parent
        # a reference is a 32-byte hash or an embedded encoding of fewer
        # than 32 bytes; the root is hashed either way
        ref = self._root.ref
        return ref if len(ref) == 32 else keccak256(ref)


def _child(node) -> bytes:
    if node is None:
        return b"\x80"
    return rlp.encode(node.ref) if len(node.ref) == 32 else node.ref


def _encode(node) -> bytes:
    if isinstance(node, Leaf):
        return rlp.encode([hex_prefix(node.path, True), node.value])
    if isinstance(node, Ext):
        return rlp.encode_list([rlp.encode(hex_prefix(node.path, False)),
                                _child(node.child)])
    return rlp.encode_list([_child(c) for c in node.children] + [b"\x80"])


def trie_root(items) -> bytes:
    return Trie(items).root()
