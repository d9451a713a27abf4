"""Bencode, the bridge's wire codec — the benchmark's own copy, so the
program's codec is part of what is measured and not of the yardstick."""

from __future__ import annotations


def encode(x) -> bytes:
    if isinstance(x, bool):
        raise TypeError("bencode has no bool")
    if isinstance(x, int):
        return b"i%de" % x
    if isinstance(x, (bytes, bytearray, memoryview)):
        x = bytes(x)
        return b"%d:%s" % (len(x), x)
    if isinstance(x, str):
        return encode(x.encode())
    if isinstance(x, (list, tuple)):
        return b"l" + b"".join(encode(v) for v in x) + b"e"
    if isinstance(x, dict):
        items = sorted((k.encode() if isinstance(k, str) else k, v) for k, v in x.items())
        return b"d" + b"".join(encode(k) + encode(v) for k, v in items) + b"e"
    raise TypeError(f"cannot bencode {type(x).__name__}")


def decode(data: bytes):
    """The whole of ``data`` as one value; ValueError on anything else."""
    try:
        value, end = _decode(data, 0)
    except (IndexError, KeyError) as e:
        raise ValueError(f"truncated bencode: {e}") from None
    if end != len(data):
        raise ValueError("trailing bytes after bencode value")
    return value


def _decode(d: bytes, i: int):
    c = d[i : i + 1]
    if c == b"i":
        j = d.index(b"e", i)
        return int(d[i + 1 : j]), j + 1
    if c == b"l":
        out, i = [], i + 1
        while d[i : i + 1] != b"e":
            v, i = _decode(d, i)
            out.append(v)
        return out, i + 1
    if c == b"d":
        out, i = {}, i + 1
        while d[i : i + 1] != b"e":
            k, i = _decode(d, i)
            out[k], i = _decode(d, i)
        return out, i + 1
    if c.isdigit():
        j = d.index(b":", i)
        n = int(d[i:j])
        if j + 1 + n > len(d):
            raise ValueError("string runs past the end")
        return d[j + 1 : j + 1 + n], j + 1 + n
    raise ValueError(f"bad bencode at {i}")
