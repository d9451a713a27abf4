"""Seeded payloads. The same seed gives the same bytes, the same corrupted
pieces and the same torrent.

A payload is ``n_pieces`` pieces of ``piece_length`` bytes. Piece *i* is
row ``i % BASE_PIECES`` of one seeded random block with its first 8 bytes
overwritten by *i* (little endian), so every piece has a digest of its own
while the generator draws only ``BASE_PIECES`` pieces of randomness: SHA-1's
work does not depend on the bytes, nothing on the path compresses or
deduplicates, and the set-up of every run of every later check pays for
this. A corrupted piece has one byte flipped at a seeded offset.
"""

from __future__ import annotations

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BASE_PIECES = 64
THREADS = max(2, min(8, (os.cpu_count() or 2) - 2))


def base_block(seed: int, piece_length: int) -> np.ndarray:
    """``uint8[BASE_PIECES, piece_length]`` of seeded random bytes."""
    words = BASE_PIECES * piece_length // 8
    raw = np.random.Generator(np.random.Philox(seed)).integers(
        0, 2**64, words, dtype=np.uint64, endpoint=False
    )
    return raw.view(np.uint8).reshape(BASE_PIECES, piece_length)


def fill_piece(row: np.ndarray, base: np.ndarray, index: int) -> None:
    """Write piece ``index`` into ``row``: its base row, stamped."""
    row[:] = base[index % BASE_PIECES]
    row[:8] = np.frombuffer(int(index).to_bytes(8, "little"), dtype=np.uint8)


def piece(base: np.ndarray, index: int) -> np.ndarray:
    """A fresh copy of piece ``index``."""
    row = np.empty(base.shape[1], dtype=np.uint8)
    fill_piece(row, base, index)
    return row


def corruption_plan(seed: int, n_pieces: int, share: float, piece_length: int):
    """``{piece index: byte offset to flip}`` — ``round(share * n_pieces)``
    pieces whatever the seed (at least one), at seeded places."""
    rng = np.random.Generator(np.random.Philox([seed, 0xC0]))
    k = max(1, round(share * n_pieces))
    idx = rng.choice(n_pieces, size=k, replace=False)
    # keep off the first 8 bytes, which hold the stamp
    off = rng.integers(8, piece_length, size=k)
    return {int(i): int(o) for i, o in zip(idx, off)}


def flip(row: np.ndarray, offset: int) -> None:
    row[offset] ^= 0x5A


def write_payload(path: str, seed: int, n_pieces: int, piece_length: int, corrupt: dict):
    """Write the payload file with ``corrupt`` applied and return the
    digests of the *clean* pieces, which go into the torrent. Pieces are
    composed, hashed and written in slabs on a few threads (hashlib and
    pwrite release the interpreter lock)."""
    base = base_block(seed, piece_length)
    digests: list[bytes | None] = [None] * n_pieces
    slab = 128
    fd = os.open(path, os.O_CREAT | os.O_WRONLY | os.O_TRUNC, 0o644)
    try:
        os.ftruncate(fd, n_pieces * piece_length)

        def do(start: int) -> None:
            stop = min(start + slab, n_pieces)
            buf = np.empty((stop - start, piece_length), dtype=np.uint8)
            for r, i in enumerate(range(start, stop)):
                fill_piece(buf[r], base, i)
                digests[i] = hashlib.sha1(buf[r]).digest()
                if i in corrupt:
                    flip(buf[r], corrupt[i])
            os.pwrite(fd, buf, start * piece_length)

        with ThreadPoolExecutor(THREADS) as pool:
            list(pool.map(do, range(0, n_pieces, slab)))
    finally:
        os.close(fd)
    return digests


def write_torrent(path: str, name: str, n_pieces: int, piece_length: int, digests) -> None:
    """A single-file BEP 3 torrent for the payload."""
    from benchmark.harness import bencode

    info = {
        "length": n_pieces * piece_length,
        "name": name,
        "piece length": piece_length,
        "pieces": b"".join(digests),
    }
    with open(path, "wb") as f:
        f.write(bencode.encode({"announce": "http://127.0.0.1:1/announce", "info": info}))
