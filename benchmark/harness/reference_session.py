"""The plain reference for a download: ``hashlib.sha1`` over the bytes a
piece held, against the torrent's digest.

A leecher judges deliveries: a piece assembled from the blocks its peers
sent. What a delivery held is what the copy of the peers that sent it
holds at that piece, so the reference's verdict for a delivery of piece
*i* is the verdict of that copy's piece *i* (``copy_verdicts``), and the
leecher's verdict events are compared with it one by one
(``compare_deliveries``). The same function over the leecher's own file
says what reached the disk.

Nothing here imports the program or takes anything it made: the torrent
is decoded with the harness's bencode. It runs once the window has
closed.
"""

from __future__ import annotations

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

from benchmark.harness import bencode
from benchmark.harness.payload import THREADS
from benchmark.harness.reference import control_verdicts  # noqa: F401  the same control

TASK_PIECES = 64


def read_torrent(path: str) -> dict:
    """``{name, length, piece_length, digests}`` of a single-file v1
    ``.torrent``."""
    with open(path, "rb") as f:
        info = bencode.decode(f.read())[b"info"]
    pieces = info[b"pieces"]
    return {
        "name": info[b"name"].decode(),
        "length": int(info[b"length"]),
        "piece_length": int(info[b"piece length"]),
        "digests": [pieces[i : i + 20] for i in range(0, len(pieces), 20)],
    }


def piece_digests(path: str, length: int, piece_length: int) -> list[bytes | None]:
    """``hashlib.sha1`` of every piece of the file as it is on disk; a
    piece the file does not hold whole (it is missing, or short) is
    ``None``."""
    n = -(-length // piece_length)
    out: list[bytes | None] = [None] * n
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return out
    try:
        def do(first: int) -> None:
            for i in range(first, min(first + TASK_PIECES, n)):
                want = min(piece_length, length - i * piece_length)
                data = os.pread(fd, want, i * piece_length)
                if len(data) == want:
                    out[i] = hashlib.sha1(data).digest()

        with ThreadPoolExecutor(THREADS) as pool:
            list(pool.map(do, range(0, n, TASK_PIECES)))
    finally:
        os.close(fd)
    return out


def copy_verdicts(path: str, torrent: dict) -> list[bool]:
    """One verdict a piece: the bytes this copy holds hash to the
    torrent's digest."""
    got = piece_digests(path, torrent["length"], torrent["piece_length"])
    return [g == d for g, d in zip(got, torrent["digests"], strict=True)]


def compare_deliveries(events, held: list[bool], every: str) -> dict:
    """The leecher's verdict events ``(index, outcome)`` of one phase of a
    download against the reference's, ``held[index]``: what the copy of
    the phase's seeders holds there. ``outcome`` is ``"ok"`` where the
    leecher called the delivery valid, anything else where it refused it.

    ``every`` says which pieces the phase must have judged: ``"valid"``,
    a download that ends complete (a valid piece never called valid is a
    missing verdict); ``"invalid"``, the planted phase (an invalid piece
    never refused was never put to the leecher, and says nothing).
    """
    wrong = sum(1 for i, outcome in events if (outcome == "ok") != held[i])
    if every == "valid":
        judged = {i for i, outcome in events if outcome == "ok"}
        due = [i for i, ok in enumerate(held) if ok]
    else:
        judged = {i for i, outcome in events if outcome != "ok"}
        due = [i for i, ok in enumerate(held) if not ok]
    return {
        "compared": len(events),
        "reference_invalid": sum(1 for i, _ in events if not held[i]),
        "wrong_verdicts": wrong,
        "missing_verdicts": sum(1 for i in due if i not in judged),
    }
