"""The v2 download arm under ``hasher="tpu"``: every delivered piece, a
multi-piece file's short tail and a file of one piece too, has its leaves
hashed on the device leaf plane (``session/v2.ingest_verdicts``), and
every verdict equals the plain reference's — BEP 52 by ``hashlib`` alone:
SHA-256 leaves over 16 KiB blocks, zero leaves up to each piece's pad
target, pairs folded to the root.

Small and on the CPU: batches of a few pieces called directly, then a
five-file torrent at 64 KiB pieces downloaded from two ``hasher="cpu"``
seeders in this process, and from a poisoner.
"""

import asyncio
import hashlib
import os

import numpy as np
import pytest

from benchmark.harness.reference_v2 import ZERO, fold
from torrent_tpu.codec.metainfo_v2 import BLOCK
from torrent_tpu.models.v2 import build_v2
from torrent_tpu.obs.ledger import pipeline_ledger
from torrent_tpu.session import v2 as session_v2
from torrent_tpu.session.resume import FsResumeStore, ResumeData
from torrent_tpu.session.torrent import TorrentConfig
from torrent_tpu.utils.bitfield import Bitfield

from tests.test_session_ingest import a_leecher, fallbacks, join, run, seeder

SEED = 2147483777


def reference_root(data: bytes, pad: int) -> bytes:
    """BEP 52 by hashlib: the piece's leaves, zero leaves up to ``pad``,
    folded."""
    leaves = [hashlib.sha256(data[i : i + BLOCK]).digest() for i in range(0, len(data), BLOCK)]
    return fold(leaves + [ZERO] * (pad - len(leaves)))


def payload(n: int, seed: int) -> bytes:
    return np.random.Generator(np.random.Philox(seed)).integers(0, 256, n, dtype=np.uint8).tobytes()


def stats_delta(before: dict) -> dict:
    after = session_v2.v2_ingest_stats()
    return {k: after[k] - before[k] for k in after}


# a batch, called directly -----------------------------------------------------

PLEN = 256 * 1024  # 16 leaves a piece
LPP = PLEN // BLOCK

# (kind, length, pad target): full pieces, multi-piece files' short tails, files of one piece
MIX = [
    ("full", PLEN, LPP), ("full", PLEN, LPP), ("full", PLEN, LPP),
    ("tail", 1, LPP), ("tail", BLOCK + 1, LPP), ("tail", PLEN - 1, LPP),
    ("single", 9_000, 1), ("single", 2 * BLOCK + 77, 4), ("single", 4 * BLOCK, 4), ("single", 4 * BLOCK + 1, 8),
    ("single", 100, 1),  # shorter than one leaf
]


def mixed_batch(seed: int):
    """The mix in a seeded order, with each piece's reference root."""
    order = np.random.Generator(np.random.Philox([seed, 1])).permutation(len(MIX))
    kinds, pieces, pads = [], [], []
    for j, i in enumerate(order):
        kind, length, pad = MIX[i]
        kinds.append(kind)
        pieces.append(payload(length, seed + j))
        pads.append(pad)
    return kinds, pieces, pads, [reference_root(p, pad) for p, pad in zip(pieces, pads)]


@pytest.mark.parametrize("seed", [7, SEED])
@pytest.mark.parametrize("where", ["first", "last"])
def test_a_mixed_batch_is_judged_as_the_reference_judges_it(seed, where):
    kinds, pieces, pads, expected = mixed_batch(seed)
    before = session_v2.v2_ingest_stats()
    assert session_v2.ingest_verdicts(pieces, expected, pads, PLEN) == [True] * len(pieces)
    got = stats_delta(before)
    grid = sum(pad == LPP for pad in pads)
    assert got == {"batches": 1, "pieces": len(pieces), "short_pieces": len(pieces) - 3,
                   "pieces_host_hashed": 0, "fold_pieces_launched": session_v2.fold_bucket(grid)}
    # one byte flipped in one piece of each kind: exactly those verdicts are false
    flipped = list(pieces)
    spoiled = []
    for kind in ("full", "tail", "single"):
        i = kinds.index(kind) if where == "first" else len(kinds) - 1 - kinds[::-1].index(kind)
        data = bytearray(flipped[i])
        data[0 if where == "first" else -1] ^= 0x5A
        flipped[i] = bytes(data)
        spoiled.append(i)
    assert [reference_root(p, pad) == e for p, pad, e in zip(flipped, pads, expected)] == [
        i not in spoiled for i in range(len(pieces))]
    assert session_v2.ingest_verdicts(flipped, expected, pads, PLEN) == [i not in spoiled for i in range(len(pieces))]


def test_no_payload_byte_goes_through_hashlib(monkeypatch):
    """The host fold of a file of one piece hashes 64-byte node pairs,
    never a block of the payload."""
    import torrent_tpu.models.v2 as models_v2

    kinds, pieces, pads, expected = mixed_batch(11)
    seen = []
    real = hashlib.sha256

    def spy(data=b""):
        seen.append(bytes(data))
        return real(data)

    monkeypatch.setattr(models_v2.hashlib, "sha256", spy)
    assert session_v2.ingest_verdicts(pieces, expected, pads, PLEN) == [True] * len(pieces)
    assert seen and {len(x) for x in seen} == {64}
    assert not any(x in p for x in seen for p in pieces)


@pytest.mark.parametrize("k", [1, 2, 3, 5, 8, 9])
def test_the_fold_bucket_never_changes_a_root(k):
    """``k`` pieces fold in whole grids of eight rows; the padding pieces
    are all-zero and their roots dropped."""
    pieces = [payload(PLEN if i % 2 else PLEN - 5003 * i, 100 + i) for i in range(k)]
    expected = [reference_root(p, LPP) for p in pieces]
    before = session_v2.v2_ingest_stats()
    assert session_v2.ingest_verdicts(pieces, expected, [LPP] * k, PLEN) == [True] * k
    assert stats_delta(before)["fold_pieces_launched"] == session_v2.fold_bucket(k) == 8 * -(-k // 8)
    # a padding piece's root is the zero subtree's, which no piece delivers
    assert session_v2.ingest_verdicts(pieces, [fold([ZERO] * LPP)] * k, [LPP] * k, PLEN) == [False] * k


def test_a_batch_of_files_of_one_piece_launches_no_fold():
    pieces = [payload(n, 300 + n) for n in (1, 9_000, 3 * BLOCK, 4 * BLOCK + 1)]
    pads = [1, 1, 4, 8]
    before = session_v2.v2_ingest_stats()
    assert session_v2.ingest_verdicts(pieces, [reference_root(p, pad) for p, pad in zip(pieces, pads)], pads, PLEN) == [True] * 4
    assert stats_delta(before) == {"batches": 1, "pieces": 4, "short_pieces": 4, "pieces_host_hashed": 0,
                                   "fold_pieces_launched": 0}


def test_a_batch_is_one_ledger_wait_and_its_fold_a_merkle_stage():
    kinds, pieces, pads, expected = mixed_batch(13)
    before = pipeline_ledger().snapshot()
    session_v2.ingest_verdicts(pieces, expected, pads, PLEN)
    after = pipeline_ledger().snapshot()

    def moved(table, name, key):
        return after[table][name][key] - before[table].get(name, {}).get(key, 0)

    assert moved("waits", "ingest_v2_batch", "ops") == 1
    assert moved("waits", "ingest_v2_batch", "bytes") == sum(len(p) for p in pieces)
    assert moved("stages", "merkle", "ops") == 1
    # one leaf launch a piece: the geometry is as it was
    assert moved("stages", "launch", "ops") == len(pieces)
    assert "ingest_v2_batch" not in after["stages"]


# a download -----------------------------------------------------------------

SWARM_PLEN = 64 * 1024  # 4 leaves a piece
FILES = [  # (path, length): each file's last piece is short
    (("a", "big.bin"), 4 * SWARM_PLEN + 5_003),  # 4 full pieces and a tail
    (("a", "mid.bin"), SWARM_PLEN + BLOCK + 1),  # a full piece and a tail of two leaves
    (("b", "one.bin"), 2 * BLOCK + 77),  # one piece of 3 leaves, padded to 4: a full piece's target
    (("b", "two.bin"), 9_000),  # one piece of one leaf
    (("c.bin",), 100),  # one piece shorter than a leaf
]
NAME = "v2dl"


def write_tree(root, corrupt=()):
    """The five files under ``root/NAME`` (one byte flipped at each
    ``(file index, offset)`` of ``corrupt``) and their torrent."""
    blobs = [payload(n, SEED + i) for i, (_, n) in enumerate(FILES)]
    meta = build_v2([(p, b) for (p, _), b in zip(FILES, blobs)], name=NAME, piece_length=SWARM_PLEN, hasher="cpu")
    for i, ((path, _), blob) in enumerate(zip(FILES, blobs)):
        data = bytearray(blob)
        for f, off in corrupt:
            if f == i:
                data[off] ^= 0x5A
        dest = os.path.join(root, NAME, *path)
        os.makedirs(os.path.dirname(dest), exist_ok=True)
        with open(dest, "wb") as fh:
            fh.write(data)
    return meta, blobs


def piece_index(meta, file_index: int, offset: int) -> int:
    """The session's piece of a byte of a file: files start on a piece."""
    start = 0
    for f in meta.info.files[:file_index]:
        start += -(-f.length // SWARM_PLEN)
    return start + offset // SWARM_PLEN


def test_the_layout_is_the_one_the_counters_are_reckoned_against(tmp_path):
    meta, _ = write_tree(str(tmp_path))
    info = session_v2.v2_session_info(meta.info, meta.piece_layers)
    assert [f.path for f in meta.info.files] == [p for p, _ in FILES]
    assert info.num_pieces == 10
    assert sum(s < SWARM_PLEN for s in info.piece_sizes) == len(FILES)
    assert info.piece_pad_leaves == (4, 4, 4, 4, 4, 4, 4, 4, 1, 1)


def test_a_tpu_leecher_downloads_every_piece_through_the_device_batch(tmp_path):
    src, dest = str(tmp_path / "src"), str(tmp_path / "dest")
    meta, blobs = write_tree(src)
    info = session_v2.v2_session_info(meta.info, meta.piece_layers)
    n = info.num_pieces

    async def go():
        seeders = [await seeder(meta, src) for _ in range(2)]
        leech, sched = await a_leecher("tpu")
        events: list = []
        before, fell_back = session_v2.v2_ingest_stats(), fallbacks()
        try:
            t = await leech.add(meta, dest)
            t.on_piece_verdict = lambda index, outcome: events.append((index, outcome))
            join(t, *seeders)
            await asyncio.wait_for(t.on_complete.wait(), 90)
        finally:
            await leech.close()
            await sched.close()
            for s in seeders:
                await s.close()
        return events, stats_delta(before), fallbacks() - fell_back

    events, got, fell_back = run(go(), timeout=150)
    for (path, _), blob in zip(FILES, blobs):
        with open(os.path.join(dest, NAME, *path), "rb") as fh:
            assert fh.read() == blob
    assert sorted(events) == [(i, "ok") for i in range(n)]
    grid = sum(pad == SWARM_PLEN // BLOCK for pad in info.piece_pad_leaves)
    assert got["pieces_host_hashed"] == 0 and fell_back == 0
    assert got["pieces"] == n and got["short_pieces"] == len(FILES)
    assert 1 <= got["batches"] <= n
    # every batch that holds pieces of a full piece's target folds them in whole grids of eight
    assert grid == 8 and got["fold_pieces_launched"] % 8 == 0
    assert 8 <= got["fold_pieces_launched"] <= 8 * min(got["batches"], grid)


def test_a_poisoned_tail_and_file_of_one_piece_are_refused(tmp_path):
    """Alone with a seeder whose copy has one byte flipped in a
    multi-piece file's tail and in a file of one piece (and never banned
    for it), the leecher refuses those two pieces at every delivery and
    takes every other."""
    src, bad, dest = str(tmp_path / "src"), str(tmp_path / "bad"), str(tmp_path / "dest")
    meta, _ = write_tree(src)
    corrupt = [(0, 4 * SWARM_PLEN + 4_999), (3, 8_191)]
    write_tree(bad, corrupt)
    planted = {piece_index(meta, f, off) for f, off in corrupt}
    assert planted == {4, 8}
    info = session_v2.v2_session_info(meta.info, meta.piece_layers)
    n = info.num_pieces
    full = Bitfield(n)
    for i in range(n):
        full.set(i)
    FsResumeStore(bad).save(ResumeData(meta.truncated_info_hash, n, full.to_bytes(), completed_reported=True))

    async def go():
        poisoner = await seeder(meta, bad, resume=True)
        leech, sched = await a_leecher("tpu")
        leech.config.torrent = TorrentConfig(choke_interval=0.15, max_corrupt_pieces=10**6)
        events: list = []
        before = session_v2.v2_ingest_stats()
        try:
            t = await leech.add(meta, dest)
            t.on_piece_verdict = lambda index, outcome: events.append((index, outcome))
            join(t, poisoner)
            for _ in range(3000):
                if planted <= {i for i, o in events if o != "ok"} and t.bitfield.count() == n - len(planted):
                    break
                await asyncio.sleep(0.02)
            have = [t.bitfield.has(i) for i in range(n)]
        finally:
            await leech.close()
            await sched.close()
            await poisoner.close()
        return events, have, stats_delta(before)

    events, have, got = run(go(), timeout=150)
    assert {i for i, o in events if o != "ok"} == planted
    assert {o for _, o in events if o != "ok"} == {"corrupt"}
    assert not {i for i, o in events if o == "ok"} & planted
    assert have == [i not in planted for i in range(n)]
    assert got["pieces_host_hashed"] == 0
