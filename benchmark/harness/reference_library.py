"""The plain reference for a library of v1 torrents: for each ``.torrent``
as it was written, ``hashlib.sha1`` of every piece over the bytes that are
really on disk, against the torrent's digest.

Nothing here imports the program, nor the module that made the payload:
the torrent is decoded with the harness's bencode and its file list walked
as BEP 3 states it (files end to end in list order, a piece may span
several) with BEP 47's one addition: an entry whose ``attr`` holds ``p`` is
never on disk and reads as zeros. A file that is missing or shorter than
the torrent says reads as missing from there on, and a piece that touches
missing bytes is invalid. It runs once the window has closed.
"""

from __future__ import annotations

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

from benchmark.harness import bencode
from benchmark.harness.payload import THREADS
from benchmark.harness.reference import compare, control_verdicts  # noqa: F401  the same comparison, the same control

TASK_PIECES = 64


def read_torrent(path: str) -> dict:
    """``{name, piece_length, digests, entries}`` of a v1 ``.torrent``;
    ``entries`` is ``[(path parts or None for a pad, length)]`` in the
    torrent's order."""
    with open(path, "rb") as f:
        info = bencode.decode(f.read())[b"info"]
    name = info[b"name"].decode()
    if b"files" in info:
        entries = []
        for e in info[b"files"]:
            pad = b"p" in e.get(b"attr", b"")
            entries.append((None if pad else (name, *(p.decode() for p in e[b"path"])), int(e[b"length"])))
    else:
        entries = [((name,), int(info[b"length"]))]
    pieces = info[b"pieces"]
    return {
        "name": name,
        "piece_length": int(info[b"piece length"]),
        "digests": [pieces[i : i + 20] for i in range(0, len(pieces), 20)],
        "entries": entries,
    }


def torrent_verdicts(torrent_path: str, data_root: str) -> list[bool]:
    """One verdict a piece: the bytes on disk under ``data_root`` hash to
    the torrent's digest."""
    t = read_torrent(torrent_path)
    plen, digests = t["piece_length"], t["digests"]
    total = sum(length for _, length in t["entries"])
    if len(digests) != -(-total // plen):
        raise ValueError(f"{torrent_path}: {len(digests)} digests for {total} bytes of {plen}")
    spans, pos, fds = [], 0, {}
    for parts, length in t["entries"]:
        spans.append((pos, parts, length))
        pos += length
        if parts is not None and parts not in fds:
            try:
                fds[parts] = os.open(os.path.join(data_root, *parts), os.O_RDONLY)
            except OSError:
                fds[parts] = None
    out = [False] * len(digests)

    def piece(i: int) -> bool:
        lo, hi = i * plen, min((i + 1) * plen, total)
        h = hashlib.sha1()
        for start, parts, length in spans:
            a, b = max(start, lo), min(start + length, hi)
            if a >= b:
                continue
            if parts is None:
                h.update(bytes(b - a))
                continue
            fd = fds[parts]
            data = os.pread(fd, b - a, a - start) if fd is not None else b""
            if len(data) != b - a:
                return False
            h.update(data)
        return h.digest() == digests[i]

    def do(first: int) -> None:
        for i in range(first, min(first + TASK_PIECES, len(out))):
            out[i] = piece(i)

    try:
        with ThreadPoolExecutor(THREADS) as pool:
            list(pool.map(do, range(0, len(out), TASK_PIECES)))
    finally:
        for fd in fds.values():
            if fd is not None:
                os.close(fd)
    return out
