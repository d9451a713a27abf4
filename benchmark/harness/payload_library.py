"""A seeded library of v1 torrents: sizes by a Zipf law, piece lengths by
the upstream authoring rule, three layouts. The same seed gives the same
bytes, the same torrents and the same corrupted pieces; the seed changes
bytes, never shapes.

Nothing here imports the program: the bencode is the harness's, the
digests are ``hashlib``'s, and the authoring rule is written out from the
upstream source (rclarey/torrent ``tools/make_torrent.ts:18-33``: a power of
two from 32 KiB to 1 MiB, doubled while it is under a thousandth of the
payload).

Torrent *k* (1 … ``torrents``) holds ``head_bytes // k + k * step_bytes``
payload bytes. Its layout goes by ``k % 3``:

* 1: a single file, ``data/<stem>/payload.bin``;
* 2: a directory ``data/<stem>/payload/`` of five files of 8/16, 4/16, 2/16,
  1/16 of the payload and the rest, each but the last ``+ 7 k`` bytes, so
  that every file boundary falls inside a piece (the upstream
  ``test_data/multifile.torrent``'s shape);
* 0: the same five files with a BEP 47 pad entry (``attr`` ``p``, path
  ``.pad/<length>``) after each file but the last, as ``torrent-tpu make
  --pad-files`` writes them: every file starts on a piece boundary, a
  file's last piece ends in zeros, and the pads are never on disk.

The payload stream of a torrent (its real files end to end) is cut into
32 KiB blocks; block *j* is row ``(j + 7 k) % BASE_BLOCKS`` of one seeded
block of random rows, its first 16 bytes overwritten by *k* and *j*: every
block, so every piece, has a digest of its own while the generator draws 2
MiB of randomness (as ``harness/payload.py`` does a piece). The torrents'
digests are taken over the clean piece space, pads as zeros; the corruption
is applied to the bytes on disk afterwards, one byte a planned piece.
"""

from __future__ import annotations

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from benchmark.harness import bencode
from benchmark.harness.payload import THREADS

BLOCK = 32768
BASE_BLOCKS = 64
STAMP = 16  # bytes of a block that hold its torrent and index
TASK_BYTES = 32 << 20  # piece space composed, hashed and written by one task
SHARES = (8, 4, 2, 1)  # sixteenths of the payload in the first four of five files


def choose_piece_length(total: int, rule: dict) -> int:
    """The upstream rule: a power of two in ``[min, max]`` aiming at
    ``target_pieces`` pieces."""
    target = max(1, total // int(rule["target_pieces"]))
    plen = int(rule["min"])
    while plen < target and plen < int(rule["max"]):
        plen *= 2
    return plen


@dataclass(frozen=True)
class Torrent:
    k: int
    stem: str  # <stem>.torrent, data/<stem>/
    name: str  # the info dict's name: the file, or the directory of files
    payload_bytes: int
    piece_length: int
    entries: tuple  # ((path or None for a pad, length), ...) in piece-space order; one entry, path (), for a single file

    @property
    def single(self) -> bool:
        return self.entries[0][0] == ()

    @property
    def space_bytes(self) -> int:
        return sum(length for _, length in self.entries)

    @property
    def n_pieces(self) -> int:
        return -(-self.space_bytes // self.piece_length)

    def spans(self):
        """``(offset in the piece space, path or None for a pad, offset in
        the payload stream, length)`` of every entry; a file's bytes begin
        at its offset 0."""
        out, pos, stream = [], 0, 0
        for path, length in self.entries:
            out.append((pos, path, stream, length))
            pos += length
            if path is not None:
                stream += length
        return out

    def file_path(self, root: str, path: tuple) -> str:
        return os.path.join(root, "data", self.stem, self.name, *path)


def library(config: dict) -> list[Torrent]:
    """The library a configuration states: shapes only, no bytes."""
    if int(config["piece_rule"]["min"]) % BLOCK:
        raise ValueError(f"a piece holds whole {BLOCK}-byte blocks, each with its stamp, or pieces would share digests")
    out = []
    for k in range(1, int(config["torrents"]) + 1):
        size = int(config["head_bytes"]) // k + k * int(config["step_bytes"])
        plen = choose_piece_length(size, config["piece_rule"])
        stem = f"lib{k:03d}"
        if k % 3 == 1:
            out.append(Torrent(k, stem, "payload.bin", size, plen, (((), size),)))
            continue
        lengths = [size * s // 16 + 7 * k for s in SHARES]
        lengths.append(size - sum(lengths))
        entries = []
        for i, length in enumerate(lengths):
            entries.append(((f"f{i}.bin",), length))
            if k % 3 == 0 and i < len(lengths) - 1 and length % plen:
                pad = plen - length % plen
                entries.append((None, pad))
        out.append(Torrent(k, stem, "payload", size, plen, tuple(entries)))
    return out


def base_block(seed: int) -> np.ndarray:
    """``uint8[BASE_BLOCKS, BLOCK]`` of seeded random bytes."""
    raw = np.random.Generator(np.random.Philox(seed)).integers(
        0, 2**64, BASE_BLOCKS * BLOCK // 8, dtype=np.uint64, endpoint=False
    )
    return raw.view(np.uint8).reshape(BASE_BLOCKS, BLOCK)


def stream_bytes(base: np.ndarray, k: int, start: int, stop: int) -> np.ndarray:
    """Bytes ``start..stop`` of torrent *k*'s payload stream."""
    j = np.arange(start // BLOCK, -(-stop // BLOCK), dtype=np.uint64)
    buf = base[(j + np.uint64(7 * k)) % np.uint64(BASE_BLOCKS)]
    stamp = buf.view(np.uint64)
    stamp[:, 0] = k
    stamp[:, 1] = j
    lo = start - int(j[0]) * BLOCK
    return buf.reshape(-1)[lo : lo + stop - start]


def _write_range(base: np.ndarray, t: Torrent, fds: dict, first: int, last: int) -> list[bytes]:
    """Compose pieces ``first..last`` of the clean piece space, write the
    parts that files hold, and return the pieces' digests."""
    lo, hi = first * t.piece_length, min(last * t.piece_length, t.space_bytes)
    space = np.zeros(hi - lo, dtype=np.uint8)  # pads stay zero
    for pos, path, stream, length in t.spans():
        a, b = max(pos, lo), min(pos + length, hi)
        if path is None or a >= b:
            continue
        part = stream_bytes(base, t.k, stream + a - pos, stream + b - pos)
        space[a - lo : b - lo] = part
        os.pwrite(fds[path], part, a - pos)
    view = memoryview(space)
    return [
        hashlib.sha1(view[p * t.piece_length - lo : min((p + 1) * t.piece_length, hi) - lo]).digest()
        for p in range(first, last)
    ]


def write_torrent_file(path: str, t: Torrent, digests) -> None:
    """A BEP 3 torrent as ``torrent-tpu make`` would write it for the layout."""
    info: dict = {"name": t.name, "piece length": t.piece_length, "pieces": b"".join(digests)}
    if t.single:
        info["length"] = t.payload_bytes
    else:
        files = []
        for fpath, length in t.entries:
            if fpath is None:
                files.append({"attr": "p", "length": length, "path": [".pad", str(length)]})
            else:
                files.append({"length": length, "path": list(fpath)})
        info["files"] = files
    with open(path, "wb") as f:
        f.write(bencode.encode({"announce": "http://127.0.0.1:1/announce", "info": info}))


def write_library(root: str, seed: int, torrents: list[Torrent]) -> None:
    """Write every torrent's files clean under ``root/data/<stem>/`` and its
    ``.torrent`` under ``root/torrents/``. Pieces are composed, hashed and
    written in ranges on a few threads (hashlib and pwrite release the
    interpreter lock)."""
    base = base_block(seed)
    os.makedirs(os.path.join(root, "torrents"), exist_ok=True)
    with ThreadPoolExecutor(THREADS) as pool:
        for t in torrents:
            fds = {}
            try:
                for path, length in t.entries:
                    if path is None:
                        continue
                    fp = t.file_path(root, path)
                    os.makedirs(os.path.dirname(fp), exist_ok=True)
                    fds[path] = os.open(fp, os.O_CREAT | os.O_WRONLY | os.O_TRUNC, 0o644)
                    os.ftruncate(fds[path], length)
                step = max(1, TASK_BYTES // t.piece_length)
                ranges = [(p, min(p + step, t.n_pieces)) for p in range(0, t.n_pieces, step)]
                parts = pool.map(lambda r: _write_range(base, t, fds, *r), ranges)
                digests = [d for part in parts for d in part]
            finally:
                for fd in fds.values():
                    os.close(fd)
            write_torrent_file(os.path.join(root, "torrents", t.stem + ".torrent"), t, digests)


def _flippable(t: Torrent, piece: int) -> list[tuple]:
    """``(path, lo, hi)`` file-offset ranges of the piece's bytes that a
    file holds and no stamp covers."""
    lo, hi = piece * t.piece_length, min((piece + 1) * t.piece_length, t.space_bytes)
    out = []
    for pos, path, stream, length in t.spans():
        a, b = max(pos, lo), min(pos + length, hi)
        if path is None or a >= b:
            continue
        s0, s1 = stream + a - pos, stream + b - pos  # the same bytes in the payload stream
        for j in range(s0 // BLOCK, -(-s1 // BLOCK)):
            first, stop = max(s0, j * BLOCK + STAMP), min(s1, (j + 1) * BLOCK)
            if first < stop:
                out.append((path, first - stream, stop - stream))
    return out


def corruption_plan(seed: int, torrents: list[Torrent], share: float) -> dict:
    """``{(torrent index, piece): (path, file offset)}``: one byte to flip in
    ``round(share * pieces)`` pieces of the library whatever the seed, at
    seeded places, always in bytes a file holds (never in a pad span, which
    is not on disk, nor in a stamp). Always among them, where the library has
    one: a piece that spans two files, a piece that ends in a pad span, the
    short last piece of the torrent with the longest pieces, and a piece of
    the smallest torrent."""
    rng = np.random.Generator(np.random.Philox([seed, 0xC1]))

    def spanning(ti: int, t: Torrent) -> list:
        # an entry that starts inside a piece shares that piece with the file before it
        return [(ti, pos // t.piece_length) for pos, *_ in t.spans()[1:] if pos % t.piece_length]

    def before_pad(ti: int, t: Torrent) -> list:
        return [(ti, (pos - 1) // t.piece_length) for pos, path, _, _ in t.spans() if path is None]

    longest = max(range(len(torrents)), key=lambda i: (torrents[i].piece_length, torrents[i].payload_bytes))
    smallest = min(range(len(torrents)), key=lambda i: torrents[i].payload_bytes)
    groups = [
        [x for ti, t in enumerate(torrents) if t.k % 3 == 2 for x in spanning(ti, t)],
        [x for ti, t in enumerate(torrents) if t.k % 3 == 0 for x in before_pad(ti, t)],
        [(longest, torrents[longest].n_pieces - 1)],
        [(smallest, p) for p in range(torrents[smallest].n_pieces)],
    ]
    picked: list = []
    for group in groups:
        group = [x for x in group if x not in picked]
        if group:
            picked.append(group[int(rng.integers(len(group)))])
    total = sum(t.n_pieces for t in torrents)
    want = max(len(picked), round(share * total))
    taken = set(picked)
    rest = [(ti, p) for ti, t in enumerate(torrents) for p in range(t.n_pieces) if (ti, p) not in taken]
    picked += [rest[i] for i in rng.choice(len(rest), size=want - len(picked), replace=False)]
    plan = {}
    for ti, p in picked:
        ranges = _flippable(torrents[ti], p)
        path, lo, hi = ranges[int(rng.integers(len(ranges)))]
        plan[(ti, p)] = (path, int(rng.integers(lo, hi)))
    return plan


def apply_corruption(root: str, torrents: list[Torrent], plan: dict) -> None:
    """Flip one byte on disk at every planned place."""
    for (ti, _), (path, offset) in plan.items():
        fd = os.open(torrents[ti].file_path(root, path), os.O_RDWR)
        try:
            os.pwrite(fd, bytes([os.pread(fd, 1, offset)[0] ^ 0x5A]), offset)
        finally:
            os.close(fd)
