"""The plain reference: hashlib over the bytes that were really on disk or
really sent, and the control that stands in the program's place.

Nothing here imports the program or takes anything it made. It runs once
the window has closed and the program's state is freed.
"""

from __future__ import annotations

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

from benchmark.harness.payload import THREADS


def file_verdicts(path: str, n_pieces: int, piece_length: int, digests) -> list[bool]:
    """For every piece of the file: does hashlib's SHA-1 of the bytes on
    disk equal the torrent's digest."""
    out = [False] * n_pieces
    fd = os.open(path, os.O_RDONLY)
    try:
        def do(start: int) -> None:
            for i in range(start, min(start + 128, n_pieces)):
                data = os.pread(fd, piece_length, i * piece_length)
                out[i] = hashlib.sha1(data).digest() == digests[i]

        with ThreadPoolExecutor(THREADS) as pool:
            list(pool.map(do, range(0, n_pieces, 128)))
    finally:
        os.close(fd)
    return out


def piece_verdict(data, expected: bytes) -> bool:
    return hashlib.sha1(data).digest() == expected


def control_verdicts(n: int) -> list[bool]:
    """The control: a verifier that trusts what it was told to expect and
    answers "valid" without hashing (what a resume file, a cache of earlier
    verdicts or a sampled check would do). It breaks the one guarantee the
    configurations state — every verdict equals hashlib's over the bytes —
    on exactly the corrupted pieces, so it has to come out as not correct."""
    return [True] * n


def compare(program, reference) -> dict:
    """Numbers compared, each beside its limit. ``program[i]`` is the
    program's verdict for answer *i* (``None`` where none came)."""
    missing = sum(1 for p in program if p is None)
    wrong = sum(1 for p, r in zip(program, reference) if p is not None and bool(p) != bool(r))
    return {
        "compared": len(reference),
        "reference_invalid": sum(1 for r in reference if not r),
        "wrong_verdicts": {"value": wrong, "limit": 0},
        "missing_verdicts": {"value": missing + abs(len(program) - len(reference)), "limit": 0},
    }
