"""The plain reference for BEP 52 (BitTorrent v2): SHA-256 over 16 KiB
blocks and per-file merkle trees, with hashlib alone, over the bytes that
were really on disk.

Nothing here imports the program or takes anything it made. The same
functions make the torrent's roots and layers from the clean leaves
(``harness/payload_v2.py``) and, once the window has closed, the verdicts
from the bytes on disk.

By BEP 52 ("pieces root", "piece layers"): a file is cut into 16 KiB
blocks, the last one short; a leaf is the SHA-256 of a block; a node is the
SHA-256 of its two children's 64 bytes. A file longer than a piece: each
piece's root is the root over its ``piece_length / 16 KiB`` leaves, the
last piece's missing leaves being 32 zero bytes (the value, not a hash of
zeros); those roots are the file's piece layer, and the layer, padded to
a power of two with the root of an all-zero piece subtree, folds to the
``pieces root``. A file of one piece or less has no layer: its ``pieces
root`` is the root of its own leaves padded with 32 zero bytes to the next
power of two.

Departures from BEP 52, each because the specification leaves it open or
the payload never has the case:

* an empty file has no ``pieces root`` and gets no verdict; the payload
  writes none;
* the specification says what the hashes are, not what a recheck answers.
  The verdicts are the configuration's guarantees: one verdict a piece;
  a file of one piece or less has one verdict, its root against ``pieces
  root``; a longer file's piece is valid where its root equals the
  torrent's layer entry, and every piece of a file is invalid where the
  torrent's layer does not fold to ``pieces root`` (a layer that lies
  would otherwise place the damage in the wrong pieces), where the file
  is missing, or where its size is not the torrent's;
* hybrid torrents, pad files and the v1 view are out of scope: the
  payload is pure v2.
"""

from __future__ import annotations

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

from benchmark.harness.payload import THREADS
from benchmark.harness.reference import compare, control_verdicts  # noqa: F401  the same comparison, the same control

BLOCK = 16384
ZERO = bytes(32)
SLAB = 1024  # leaves a task


def leaf_hashes(path: str, length: int) -> list[bytes]:
    """SHA-256 of every 16 KiB block of the file's bytes on disk."""
    n = -(-length // BLOCK)
    out: list = [None] * n
    fd = os.open(path, os.O_RDONLY)
    try:
        def do(start: int) -> None:
            stop = min(start + SLAB, n)
            data = os.pread(fd, min(stop * BLOCK, length) - start * BLOCK, start * BLOCK)
            for i in range(start, stop):
                out[i] = hashlib.sha256(data[(i - start) * BLOCK : (i - start + 1) * BLOCK]).digest()

        with ThreadPoolExecutor(THREADS) as pool:
            list(pool.map(do, range(0, n, SLAB)))
    finally:
        os.close(fd)
    return out


def fold(nodes: list[bytes]) -> bytes:
    """The root over ``nodes``, whose count is a power of two."""
    if not nodes or len(nodes) & (len(nodes) - 1):
        raise ValueError("a merkle layer holds a power of two of nodes")
    while len(nodes) > 1:
        nodes = [hashlib.sha256(nodes[i] + nodes[i + 1]).digest() for i in range(0, len(nodes), 2)]
    return nodes[0]


def pow2_at_least(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def zero_root(leaves: int) -> bytes:
    """Root of a subtree of ``leaves`` all-zero leaves."""
    return fold([ZERO] * leaves)


def piece_roots(leaves: list[bytes], leaves_per_piece: int) -> list[bytes]:
    """Every piece's root, the last piece's missing leaves zero."""
    out = []
    for start in range(0, len(leaves), leaves_per_piece):
        mine = leaves[start : start + leaves_per_piece]
        out.append(fold(mine + [ZERO] * (leaves_per_piece - len(mine))))
    return out


def layer_root(layer: list[bytes], leaves_per_piece: int) -> bytes:
    """A piece layer folded to the file's root."""
    return fold(list(layer) + [zero_root(leaves_per_piece)] * (pow2_at_least(len(layer)) - len(layer)))


def file_root(leaves: list[bytes], length: int, piece_length: int) -> tuple[bytes, list[bytes]]:
    """``(pieces root, piece layer)`` of a file from its leaves; the layer
    is empty for a file of one piece or less."""
    if length <= piece_length:
        return fold(leaves + [ZERO] * (pow2_at_least(len(leaves)) - len(leaves))), []
    lpp = piece_length // BLOCK
    layer = piece_roots(leaves, lpp)
    return layer_root(layer, lpp), layer


def num_pieces(length: int, piece_length: int) -> int:
    return max(1, -(-length // piece_length))


def file_verdicts(path: str, length: int, pieces_root: bytes, layer, piece_length: int) -> list[bool]:
    """One verdict a piece of one file, from the bytes on disk against the
    torrent's ``pieces root`` and piece layer."""
    n = num_pieces(length, piece_length)
    if not os.path.isfile(path) or os.path.getsize(path) != length:
        return [False] * n
    leaves = leaf_hashes(path, length)
    if length <= piece_length:
        return [file_root(leaves, length, piece_length)[0] == pieces_root]
    lpp = piece_length // BLOCK
    if len(layer) != n or layer_root(list(layer), lpp) != pieces_root:
        return [False] * n
    return [mine == theirs for mine, theirs in zip(piece_roots(leaves, lpp), layer)]
