"""The v2 recheck against the benchmark's plain reference (PR 30): seeded
files on disk at small sizes, whole and damaged, through ``verify_v2`` with
``hasher="tpu"`` (the scan backend on the CPU) and ``hasher="cpu"``; every
verdict of every file has to equal ``benchmark/harness/reference_v2.py``'s,
which is hashlib alone and imports nothing of the program."""

from __future__ import annotations

import os

import numpy as np
import pytest

from benchmark.harness import payload_v2, reference_v2
from torrent_tpu.codec.metainfo_v2 import BLOCK, parse_metainfo_v2
from torrent_tpu.models import v2

PLEN = 4 * BLOCK
SEED = 2147483777  # past 32 signed bits, as the driver's seeds are
# the benchmark's three classes, small: files of several leaf launches with a
# short last piece and a short last leaf, files of a few pieces, files of one
# piece or less; and a file of exactly one piece
CLASSES = [
    {"dir": "large", "count": 2, "bytes": 9 * PLEN, "step_bytes": 5003},
    {"dir": "mid", "count": 2, "bytes": 2 * PLEN, "step_bytes": 1031},
    {"dir": "one", "count": 1, "bytes": PLEN, "step_bytes": 0},
    {"dir": "small", "count": 3, "bytes": 3 * BLOCK, "step_bytes": 523},
]


@pytest.fixture(autouse=True)
def small_batches(monkeypatch):
    """Sixteen leaf rows a launch: the large files take three."""
    monkeypatch.setattr(v2, "LEAF_BATCH", 16)


@pytest.fixture
def payload(tmp_path):
    files = payload_v2.file_plan(CLASSES)
    entries = payload_v2.write_payload(str(tmp_path), "payload", SEED, files, PLEN)
    return tmp_path, files, entries


def _path(root, entry) -> str:
    return os.path.join(str(root), "payload", *entry["path"])


def _flip(path: str, offset: int) -> None:
    with open(path, "r+b") as f:
        f.seek(offset)
        byte = f.read(1)[0]
        f.seek(offset)
        f.write(bytes([byte ^ 0x5A]))


def _both(root, entries, hasher):
    """``(program, reference)``: one list of verdicts a file, each."""
    torrent = os.path.join(str(root), "payload.torrent")
    payload_v2.write_torrent(torrent, "payload", PLEN, entries)
    with open(torrent, "rb") as f:
        meta = parse_metainfo_v2(f.read())
    assert meta is not None

    def read_file(path):
        fp = os.path.join(str(root), "payload", *path)
        return fp if os.path.isfile(fp) else None

    res = v2.verify_v2(read_file, meta, hasher=hasher)
    program = [[bool(b) for b in res[e["path"]]] for e in entries]
    reference = [
        reference_v2.file_verdicts(_path(root, e), e["length"], e["pieces_root"], e["layer"], PLEN) for e in entries
    ]
    return program, reference


def _index(entries, directory: str, i: int = 0) -> int:
    return [n for n, e in enumerate(entries) if e["path"][0] == directory][i]


def damage_nothing(root, files, entries):
    return {}


def damage_small_file(root, files, entries):
    f = _index(entries, "small", 1)
    _flip(_path(root, entries[f]), 2 * BLOCK + 17)
    return {f: [0]}


def damage_last_piece(root, files, entries):
    f = _index(entries, "large", 1)
    _flip(_path(root, entries[f]), entries[f]["length"] - 1)  # in the short last leaf of the short last piece
    return {f: [9]}


def damage_first_leaf(root, files, entries):
    f = _index(entries, "mid")
    _flip(_path(root, entries[f]), 0)
    return {f: [0]}


def damage_exactly_one_piece(root, files, entries):
    f = _index(entries, "one")
    assert entries[f]["length"] == PLEN and entries[f]["layer"] == []
    _flip(_path(root, entries[f]), PLEN - 1)
    return {f: [0]}


def damage_by_the_plan(root, files, entries):
    plan = payload_v2.corruption_plan(SEED, files, PLEN, 0.125)
    payload_v2.apply_corruption(str(root), "payload", files, plan)
    bad: dict = {}
    for f, p in plan:
        bad.setdefault(f, []).append(p)
    return bad


def damage_missing_file(root, files, entries):
    f = _index(entries, "large")
    os.remove(_path(root, entries[f]))
    return {f: list(range(10))}


def damage_shorter_file(root, files, entries):
    f = _index(entries, "mid", 1)
    os.truncate(_path(root, entries[f]), entries[f]["length"] - 1)
    return {f: [0, 1, 2]}


def damage_longer_small_file(root, files, entries):
    f = _index(entries, "small")
    with open(_path(root, entries[f]), "ab") as fh:
        fh.write(b"\x00")
    return {f: [0]}


def damage_layer_that_lies(root, files, entries):
    """The bytes are whole; the torrent's piece layer does not fold to its
    root: every piece of that file is invalid, of no other."""
    f = _index(entries, "large")
    layer = entries[f]["layer"]
    entries[f] = dict(entries[f], layer=[layer[1], layer[0]] + layer[2:])
    return {f: list(range(10))}


DAMAGE = [
    damage_nothing, damage_small_file, damage_last_piece, damage_first_leaf, damage_exactly_one_piece,
    damage_by_the_plan, damage_missing_file, damage_shorter_file, damage_longer_small_file, damage_layer_that_lies,
]


@pytest.mark.parametrize("hasher", ["tpu", "cpu"])
@pytest.mark.parametrize("damage", DAMAGE, ids=lambda d: d.__name__.removeprefix("damage_"))
def test_every_verdict_equals_the_references(payload, damage, hasher):
    root, files, entries = payload
    bad = damage(root, files, entries)
    program, reference = _both(root, entries, hasher)
    assert program == reference
    # and the reference says what the damage was: those pieces, no others
    invalid = {f: [p for p, ok in enumerate(v) if not ok] for f, v in enumerate(reference) if not all(v)}
    assert invalid == {f: sorted(ps) for f, ps in bad.items()}


def test_the_file_mix_has_the_shapes_it_claims(payload):
    _, files, entries = payload
    leaves = [-(-length // BLOCK) for _, length in files]
    pieces = [reference_v2.num_pieces(length, PLEN) for _, length in files]
    assert sorted(zip(leaves, pieces)) == [(4, 1)] * 4 + [(9, 3)] * 2 + [(37, 10)] * 2
    assert all(length % BLOCK for (path, length) in files if path[0] != "one")  # short last leaves
    assert [bool(e["layer"]) for e in entries] == [p > 1 for p in pieces]


def test_leaves_and_roots_agree_word_for_word(payload):
    """Below the verdicts: the program's leaf words and roots against the
    reference's digests, for one file of each class."""
    root, files, entries = payload
    for directory in ("large", "mid", "one", "small"):
        e = entries[_index(entries, directory)]
        ref_leaves = reference_v2.leaf_hashes(_path(root, e), e["length"])
        for words in (v2._leaf_words_device(_path(root, e), "auto"), v2._leaf_words_cpu(_path(root, e))):
            assert v2.words32_to_digests(words) == ref_leaves
        for hasher in ("tpu", "cpu"):
            got_root, got_layer = v2.hash_file_v2(_path(root, e), PLEN, hasher=hasher)
            assert (got_root, list(got_layer)) == (e["pieces_root"], e["layer"])
