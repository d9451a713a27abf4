"""The plain reference for a v2 download: BEP 52 by ``hashlib`` alone
(``harness/reference_v2.py``'s leaves and folds) over the bytes a piece
held, against the torrent's piece root.

A v2 torrent's pieces, as a session numbers them, are its files' pieces in
file-tree order, each file starting on a piece: a piece of a file longer
than one piece is judged against the file's ``piece layers`` entry, its
missing leaves zero (a full piece's 64 at 1 MiB); a file of one piece is
its own piece, judged against its ``pieces root``, its leaves padded to the
next power of two of its own count. A delivery of piece *i* held what the
copy of its peers holds there, so the reference's verdict for it is the
verdict of that copy's piece *i* (``copy_verdicts``), and the leecher's
verdict events are compared with it one by one
(``reference_session.compare_deliveries``).

The control (``--control 1``) judges with the BEP 52 padding mistake: a
file of one piece padded to a full piece's leaves and not to its own power
of two (``control=True``). It differs from the reference at every delivery
of a file of one piece, and nowhere else.

Nothing here imports the program or takes anything it made: the torrent
is decoded with the harness's bencode. It runs once the window has
closed.
"""

from __future__ import annotations

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

from benchmark.harness import bencode, reference_v2
from benchmark.harness.payload import THREADS
from benchmark.harness.reference_session import compare_deliveries  # noqa: F401  the same comparison
from benchmark.harness.reference_v2 import BLOCK, ZERO, fold


def _walk(tree: dict, prefix: tuple) -> list:
    """``[(path, length, pieces root)]`` of a BEP 52 ``file tree``, in
    its sorted order."""
    out = []
    for key in sorted(tree):
        node = tree[key]
        if key == b"":
            out.append((prefix, int(node[b"length"]), node.get(b"pieces root")))
        else:
            out += _walk(node, prefix + (key.decode(),))
    return out


def read_torrent(path: str) -> dict:
    """``{name, piece_length, files, pieces}`` of a pure-v2 ``.torrent``:
    ``files`` is ``[{path, length, pieces_root, layer}]`` in file-tree
    order, ``pieces`` ``[(file index, piece of the file, expected root)]``
    in the session's order."""
    with open(path, "rb") as f:
        meta = bencode.decode(f.read())
    info = meta[b"info"]
    plen = int(info[b"piece length"])
    layers = meta.get(b"piece layers", {})
    files, pieces = [], []
    for path_, length, root in _walk(info[b"file tree"], ()):
        if length == 0:
            continue
        blob = layers.get(root, b"") if length > plen else b""
        layer = [blob[i : i + 32] for i in range(0, len(blob), 32)]
        n = reference_v2.num_pieces(length, plen)
        f = len(files)
        files.append({"path": path_, "length": length, "pieces_root": root, "layer": layer})
        pieces += [(f, p, layer[p] if n > 1 else root) for p in range(n)]
    return {"name": info[b"name"].decode(), "piece_length": plen, "files": files, "pieces": pieces}


def _piece_root(fd: int, start: int, want: int, pad: int) -> bytes | None:
    """The root of ``want`` bytes of a file from ``start``, its leaves
    padded with zero leaves to ``pad``; ``None`` where the file does not
    hold them whole."""
    data = os.pread(fd, want, start)
    if len(data) != want:
        return None
    leaves = [hashlib.sha256(data[i : i + BLOCK]).digest() for i in range(0, want, BLOCK)]
    return fold(leaves + [ZERO] * (pad - len(leaves)))


def piece_roots(root: str, torrent: dict, control: bool = False) -> list:
    """The root of every piece of the copy under ``root/<name>``, in the
    session's order: a piece at a time, so that a file the leecher holds
    only some pieces of gives those; ``None`` where the copy does not
    hold a piece whole. A piece of a file of one piece is padded to its
    own power of two of leaves (``control``: to a full piece's)."""
    plen = torrent["piece_length"]
    lpp = plen // BLOCK
    fds = []
    for f in torrent["files"]:
        try:
            fds.append(os.open(os.path.join(root, torrent["name"], *f["path"]), os.O_RDONLY))
        except OSError:
            fds.append(None)

    def one(piece) -> bytes | None:
        f, p, _ = piece
        length, fd = torrent["files"][f]["length"], fds[f]
        if fd is None:
            return None
        want = min(plen, length - p * plen)
        pad = lpp if length > plen or control else reference_v2.pow2_at_least(-(-want // BLOCK))
        return _piece_root(fd, p * plen, want, pad)

    try:
        with ThreadPoolExecutor(THREADS) as pool:
            return list(pool.map(one, torrent["pieces"]))
    finally:
        for fd in fds:
            if fd is not None:
                os.close(fd)


def copy_verdicts(root: str, torrent: dict, control: bool = False) -> list[bool]:
    """One verdict a piece: the bytes this copy holds fold to the
    torrent's root for the piece. Every piece of a file is invalid where
    the torrent's layer does not fold to the file's ``pieces root``."""
    plen = torrent["piece_length"]
    lpp = plen // BLOCK
    sound = [not f["layer"] or reference_v2.layer_root(f["layer"], lpp) == f["pieces_root"] for f in torrent["files"]]
    got = piece_roots(root, torrent, control)
    return [sound[f] and r == e for r, (f, _, e) in zip(got, torrent["pieces"], strict=True)]
