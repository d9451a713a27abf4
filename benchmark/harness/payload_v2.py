"""Seeded pure-v2 payloads: a directory of files, its BEP 52 torrent, and a
corruption plan. The same seed gives the same bytes, the same torrent and
the same corrupted pieces.

Leaf *j* of file *f* is row ``(j + 7 f) % BASE_LEAVES`` of one seeded random
block of ``BASE_LEAVES`` 16 KiB rows, its first 16 bytes overwritten by *f*
and *j* (two little-endian 64-bit words): every leaf has a digest of its
own while the generator draws 1 MiB of randomness. SHA-256's work does not
depend on the bytes and nothing on the path compresses or deduplicates. A
file's last leaf is cut to the file's length. The torrent's roots and
layers come from the reference's own fold (``harness/reference_v2.py``)
over the clean leaves; the corruption is applied to the bytes on disk
afterwards, one byte a planned piece.
"""

from __future__ import annotations

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.harness import bencode, reference_v2
from benchmark.harness.payload import THREADS
from benchmark.harness.reference_v2 import BLOCK

BASE_LEAVES = 64
SLAB = 2048  # leaves composed, hashed and written by one task: 32 MiB


def file_plan(classes) -> list[tuple[tuple[str, ...], int]]:
    """``[(path, length)]`` from a configuration's ``files``: class *c* has
    ``count`` files of ``bytes + (i + 1) * step_bytes`` bytes, *i* from 0,
    named ``<dir>/<i>.bin``. In the order a v2 file tree sorts them."""
    out = []
    for c in classes:
        for i in range(int(c["count"])):
            out.append(((c["dir"], f"{i:02d}.bin"), int(c["bytes"]) + (i + 1) * int(c["step_bytes"])))
    return sorted(out)


def base_block(seed: int) -> np.ndarray:
    """``uint8[BASE_LEAVES, BLOCK]`` of seeded random bytes."""
    raw = np.random.Generator(np.random.Philox(seed)).integers(
        0, 2**64, BASE_LEAVES * BLOCK // 8, dtype=np.uint64, endpoint=False
    )
    return raw.view(np.uint8).reshape(BASE_LEAVES, BLOCK)


def leaves_of(base: np.ndarray, file_index: int, start: int, stop: int) -> np.ndarray:
    """``uint8[stop - start, BLOCK]``: leaves ``start..stop`` of a file, whole."""
    j = np.arange(start, stop, dtype=np.uint64)
    buf = base[(j + np.uint64(7 * file_index)) % np.uint64(BASE_LEAVES)]
    stamp = buf.view(np.uint64)
    stamp[:, 0] = file_index
    stamp[:, 1] = j
    return buf


def write_file(path: str, base: np.ndarray, file_index: int, length: int) -> list[bytes]:
    """Write one clean file and return its leaf hashes."""
    n = -(-length // BLOCK)
    digests: list = [None] * n
    os.makedirs(os.path.dirname(path), exist_ok=True)
    fd = os.open(path, os.O_CREAT | os.O_WRONLY | os.O_TRUNC, 0o644)
    try:
        os.ftruncate(fd, length)

        def do(start: int) -> None:
            stop = min(start + SLAB, n)
            flat = leaves_of(base, file_index, start, stop).reshape(-1)[: min(stop * BLOCK, length) - start * BLOCK]
            data = memoryview(flat)
            for i in range(start, stop):
                digests[i] = hashlib.sha256(data[(i - start) * BLOCK : (i - start + 1) * BLOCK]).digest()
            os.pwrite(fd, data, start * BLOCK)

        with ThreadPoolExecutor(THREADS) as pool:
            list(pool.map(do, range(0, n, SLAB)))
    finally:
        os.close(fd)
    return digests


def write_payload(root: str, name: str, seed: int, files, piece_length: int) -> list[dict]:
    """Write every file clean under ``root/name`` and return, a file,
    ``{path, length, pieces_root, layer}`` as the torrent states them."""
    base = base_block(seed)
    out = []
    for index, (path, length) in enumerate(files):
        leaves = write_file(os.path.join(root, name, *path), base, index, length)
        pieces_root, layer = reference_v2.file_root(leaves, length, piece_length)
        out.append({"path": path, "length": length, "pieces_root": pieces_root, "layer": layer})
    return out


def write_torrent(path: str, name: str, piece_length: int, entries) -> None:
    """A pure-v2 multi-file torrent (BEP 52: ``meta version`` 2, ``file
    tree``, top-level ``piece layers`` for every file longer than a piece)."""
    tree: dict = {}
    for e in entries:
        node = tree
        for part in e["path"]:
            node = node.setdefault(part, {})
        node[""] = {"length": e["length"], "pieces root": e["pieces_root"]}
    info = {"file tree": tree, "meta version": 2, "name": name, "piece length": piece_length}
    layers = {e["pieces_root"]: b"".join(e["layer"]) for e in entries if e["layer"]}
    with open(path, "wb") as f:
        f.write(bencode.encode({"announce": "http://127.0.0.1:1/announce", "info": info, "piece layers": layers}))


def corruption_plan(seed: int, files, piece_length: int, share: float) -> dict:
    """``{(file index, piece index): byte offset in the file to flip}`` —
    ``round(share * pieces)`` pieces whatever the seed (at least two), at
    seeded places, always with one piece of a file of one piece or less and
    one short last piece of a file at least half as long as the longest."""
    rng = np.random.Generator(np.random.Philox([seed, 0xC2]))
    counts = [reference_v2.num_pieces(length, piece_length) for _, length in files]
    small = [f for f, (_, length) in enumerate(files) if length <= piece_length]
    longest = max(length for _, length in files)
    large = [f for f, (_, length) in enumerate(files)
             if counts[f] > 1 and length % piece_length and 2 * length >= longest]
    if not small or not large:
        raise ValueError("the file mix needs a file of one piece or less and a long file with a short last piece")
    f_small, f_large = int(rng.choice(small)), int(rng.choice(large))
    picked = [(f_small, 0), (f_large, counts[f_large] - 1)]
    rest = [(f, p) for f in range(len(files)) for p in range(counts[f]) if (f, p) not in picked]
    k = max(2, round(share * sum(counts)))
    picked += [rest[i] for i in rng.choice(len(rest), size=k - 2, replace=False)]
    plan = {}
    for f, p in picked:
        lo, hi = p * piece_length, min((p + 1) * piece_length, files[f][1])
        plan[(f, p)] = int(rng.integers(lo, hi))
    return plan


def apply_corruption(root: str, name: str, files, plan: dict) -> None:
    """Flip one byte on disk at every planned place."""
    for (f, _), offset in plan.items():
        fd = os.open(os.path.join(root, name, *files[f][0]), os.O_RDWR)
        try:
            os.pwrite(fd, bytes([os.pread(fd, 1, offset)[0] ^ 0x5A]), offset)
        finally:
            os.close(fd)
