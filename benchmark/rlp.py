"""Recursive Length Prefix encoding (Ethereum yellow paper, appendix B).

Items are bytes, non-negative ints (big-endian, no leading zeros) and
lists of items. `decode` returns bytes and lists only.
"""

from __future__ import annotations


def _length(n: int, offset: int) -> bytes:
    if n < 56:
        return bytes([offset + n])
    b = n.to_bytes((n.bit_length() + 7) // 8, "big")
    return bytes([offset + 55 + len(b)]) + b


def int_bytes(v: int) -> bytes:
    if v < 0:
        raise ValueError("RLP encodes non-negative integers only")
    return v.to_bytes((v.bit_length() + 7) // 8, "big")


def encode(item) -> bytes:
    if isinstance(item, int):
        item = int_bytes(item)
    if isinstance(item, (bytes, bytearray)):
        if len(item) == 1 and item[0] < 0x80:
            return bytes(item)
        return _length(len(item), 0x80) + bytes(item)
    if isinstance(item, list):
        return encode_list([encode(x) for x in item])
    raise TypeError(f"cannot RLP-encode {type(item).__name__}")


def encode_list(encoded_parts) -> bytes:
    """A list whose items are already encoded (or are embedded nodes)."""
    payload = b"".join(encoded_parts)
    return _length(len(payload), 0xC0) + payload


def _decode_at(data: bytes, pos: int):
    b = data[pos]
    if b < 0x80:
        return data[pos:pos + 1], pos + 1
    if b < 0xB8:
        n = b - 0x80
        return data[pos + 1:pos + 1 + n], pos + 1 + n
    if b < 0xC0:
        ll = b - 0xB7
        n = int.from_bytes(data[pos + 1:pos + 1 + ll], "big")
        start = pos + 1 + ll
        return data[start:start + n], start + n
    if b < 0xF8:
        n, start = b - 0xC0, pos + 1
    else:
        ll = b - 0xF7
        n = int.from_bytes(data[pos + 1:pos + 1 + ll], "big")
        start = pos + 1 + ll
    end, items, p = start + n, [], start
    while p < end:
        item, p = _decode_at(data, p)
        items.append(item)
    if p != end:
        raise ValueError("RLP list length mismatch")
    return items, end


def decode(data: bytes):
    item, end = _decode_at(bytes(data), 0)
    if end != len(data):
        raise ValueError("trailing bytes after RLP item")
    return item


def to_int(b: bytes) -> int:
    return int.from_bytes(b, "big")
