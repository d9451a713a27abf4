"""The cell ``session-v2-1m.download``: whole runs of the harness on the CPU
at rehearsal sizes (sound and traced, the control, a judge that answers
all-valid, one that answers short pieces valid, one that hashes tails
with hashlib), the payload's torrent against the program's parser, the
reference's verdicts by hand, and the cell's readers."""

import dataclasses
import hashlib
import os

import pytest

from benchmark import run as bench
from benchmark.harness import manifest, reference_session_v2
from benchmark.harness.reference_v2 import BLOCK, ZERO, fold
from benchmark.tests.test_run import _break_at_window

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "session-v2-1m.download"
NEW_READERS = ["ingest_verdict_ms.v2dl", "peer_verdict_stall_share.v2dl", "v2_ingest_pieces_per_batch",
               "v2_ingest_batch_ms", "v2_ingest_launches_per_batch"]


def _run(hook=None, control=0, trace=0, seed=2147483777):
    args = bench.parse_args(
        ["--workload", CELL, "--seed", str(seed), "--seconds", "2", "--trace", str(trace),
         "--rehearse", "1", "--control", str(control)]
    )
    line, code = bench.run(args, driver_hook=hook)
    assert code == 0
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    return line


# whole runs -----------------------------------------------------------------


def test_sound_traced_run_is_correct_and_feeds_the_new_readers():
    line = _run(trace=1)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    checks = line["checks"]
    zero = {"value": 0, "limit": 0}
    for key in ("wrong_verdicts", "missing_verdicts", "disk_mismatch", "planted_on_disk", "stalled_downloads",
                "hashlib_fallback_verdicts", "pieces_host_hashed", "forced_kinds_unrefused"):
        assert checks[key] == zero, key
    # each poisoner of one kind delivered its piece until it was dropped: three refusals make a ban
    assert checks["forced_unrefused"] == [] and checks["forced_deliveries"] >= 3 * 4
    # 21 pieces a download at the rehearsal's size: 14 full, 4 tails, 3 files of one piece
    assert checks["downloads"] >= 1 and checks["timed_deliveries"] >= 21 * checks["downloads"]
    assert checks["planted_pieces"] == 4 and checks["reference_invalid"] >= 3
    assert checks["planted_refused"] and checks["planted_adopted"] < 21
    # every timed delivery a piece of a device batch, each a leaf launch of its own
    assert checks["v2_ingest_pieces"] == checks["timed_deliveries"] == checks["launches"]
    assert checks["leaf_launches"] == [["scan", checks["launches"]]]
    assert checks["v2_ingest_short_pieces"] == 7 * checks["downloads"]
    assert 1 <= checks["v2_ingest_batches"] <= checks["v2_ingest_pieces"]
    assert checks["v2_ingest_fold_pieces_launched"] >= 18 * checks["downloads"]
    # a CPU trace holds no device plane, so no trace reader
    assert set(line["rehearsal"]["would_report"]) == set(NEW_READERS) | {
        "h2d_gib_s", "host_cpu_s_per_gib", "step_compiles_in_window", "leaf_fill_share", "leaf_scan_launch_share",
        "merkle_share", "stage_busy_share", "pass_setup_share",
    }


def test_control_is_not_correct_by_exactly_the_single_piece_files_deliveries():
    bad = _run(control=1)
    assert bad["correct"] is False
    checks = bad["checks"]
    assert checks["wrong_verdicts"]["value"] == checks["single_piece_deliveries_valid"] > 0


def test_a_judge_that_answers_all_valid_is_not_correct(monkeypatch):
    """The poisoner's deliveries are accepted and written, and it is
    never dropped: the phase is given up and the planted bytes are on
    disk."""
    from torrent_tpu.session import v2 as session_v2

    def shorten(d):
        d.stall_seconds = 3.0
        _break_at_window(d, lambda: monkeypatch.setattr(
            session_v2, "ingest_verdicts", lambda pieces, *a: [True] * len(pieces)))

    line = _run(hook=shorten)
    assert line["correct"] is False
    checks = line["checks"]
    assert checks["wrong_verdicts"]["value"] > 0 and checks["planted_on_disk"]["value"] > 0
    assert checks["planted_refused"] == []


def test_a_judge_that_answers_short_pieces_valid_is_not_correct(monkeypatch):
    """Full pieces judged as the program judges them, every tail and
    file of one piece called valid: the timed downloads hold only honest
    deliveries, so the poisoners of one kind are what find it."""
    from torrent_tpu.session import v2 as session_v2

    real = session_v2.ingest_verdicts

    def short_valid(pieces, expected, pads, piece_length):
        return [ok or len(p) < piece_length for ok, p in zip(real(pieces, expected, pads, piece_length), pieces)]

    def shorten(d):
        d.stall_seconds = 3.0
        _break_at_window(d, lambda: monkeypatch.setattr(session_v2, "ingest_verdicts", short_valid))

    line = _run(hook=shorten)
    assert line["correct"] is False
    checks = line["checks"]
    assert checks["forced_kinds_unrefused"]["value"] == 2
    assert checks["forced_unrefused"] == ["tail", "single_piece_file"]
    assert checks["wrong_verdicts"]["value"] > 0


def test_a_judge_that_hashes_tails_with_hashlib_is_not_correct(monkeypatch):
    """Every verdict right, and the short pieces judged on the program's
    ``hasher="cpu"`` road (as the parent of this cell's PR judged them)
    breaks the fourth guarantee where the program counts them."""
    from torrent_tpu.session.torrent import Torrent

    real = Torrent._verify_piece_data

    async def tails_by_hashlib(self, index, data, expected):
        if len(data) == self.info.piece_length:
            return await real(self, index, data, expected)
        self.config = dataclasses.replace(self.config, hasher="cpu")
        try:
            return await real(self, index, data, expected)
        finally:
            self.config = dataclasses.replace(self.config, hasher="tpu")

    line = _run(hook=lambda d: _break_at_window(
        d, lambda: monkeypatch.setattr(Torrent, "_verify_piece_data", tails_by_hashlib)))
    checks = line["checks"]
    assert checks["wrong_verdicts"]["value"] == 0 and checks["missing_verdicts"]["value"] == 0
    assert checks["pieces_host_hashed"]["value"] >= 7 * checks["downloads"]
    assert line["correct"] is False


# the payload ----------------------------------------------------------------


def _driver(rehearse: bool, seed=2147483777, work_dir=""):
    cell = manifest.load_cell(ROOT, CELL)
    if rehearse:
        bench.apply_rehearsal(cell)
    cell.seed, cell.work_dir, cell.log = seed, work_dir, lambda *_: None
    return manifest.load_driver(ROOT, cell.config["driver"]).Driver(cell)


@pytest.mark.parametrize("seed", [7, 2147483777, 4294967295])
def test_the_full_size_plan_holds_the_four_kinds(seed):
    d = _driver(False, seed)
    plen = 1 << 20
    assert (d.n_pieces, d.length, d.piece_length, d.n_peers) == (284, 269_380_162, plen, 8)
    sizes = [n for _, _, n in d.pieces]
    assert sizes.count(plen) == 256 and len(d.single) == 16
    assert sum(1 for i, n in enumerate(sizes) if n < plen and i not in d.single) == 12
    assert sum(-(-n // BLOCK) for n in sizes) == 16_461
    plan = d._plan()
    assert len(plan) == 36 and d._plan() == plan
    at = {i: off - d.pieces[i][1] for i, off in plan.items()}  # offset in the piece
    assert all(0 <= off < sizes[i] and off % BLOCK >= 16 for i, off in at.items())  # never in a stamp
    # the four kinds first, in FORCED's order: the pieces the poisoners of one kind hold
    first, last, tail, single = list(plan)[:4]
    assert sizes[first] == plen and at[first] < BLOCK  # a full piece's first leaf
    assert sizes[last] == plen and at[last] >= plen - BLOCK  # a full piece's last block
    assert sizes[tail] < plen and tail not in d.single  # a tail
    assert single in d.single  # a file of one piece


def test_the_torrent_is_one_the_program_accepts_and_the_reference_agrees(tmp_path):
    from torrent_tpu.codec.metainfo import parse_any_metainfo
    from torrent_tpu.session.v2 import v2_session_meta

    d = _driver(True, work_dir=str(tmp_path))
    d._write_copies()
    path = tmp_path / "payload.torrent"
    assert d.torrent_path == str(path)
    parsed = parse_any_metainfo(path.read_bytes())
    assert parsed is not None
    view = v2_session_meta(parsed[0]).info
    # the program's piece space is the driver's: the same pieces, sizes, pad targets and roots
    assert list(view.piece_sizes) == [n for _, _, n in d.pieces]
    torrent = reference_session_v2.read_torrent(str(path))
    assert list(view.pieces) == [e for _, _, e in torrent["pieces"]]
    assert [i for i, pad in enumerate(view.piece_pad_leaves) if pad != d.piece_length // BLOCK] == sorted(d.single)
    assert reference_session_v2.copy_verdicts(str(tmp_path / "source"), torrent) == [True] * d.n_pieces
    bad = reference_session_v2.copy_verdicts(str(tmp_path / "poisoned"), torrent)
    assert [i for i, ok in enumerate(bad) if not ok] == sorted(d.plan)


# the reference, by hand -------------------------------------------------------

PLEN = 4 * BLOCK


def _tree(tmp_path, files: dict, name="t"):
    """Files ``{path: bytes}`` under ``tmp_path/name`` and a hand-made
    torrent of them (hashlib leaves and folds) as ``read_torrent`` gives it."""
    out = {"name": name, "piece_length": PLEN, "files": [], "pieces": []}
    for f, (path, data) in enumerate(sorted(files.items())):
        full = tmp_path / name / path
        full.parent.mkdir(parents=True, exist_ok=True)
        full.write_bytes(data)
        leaves = [hashlib.sha256(data[i : i + BLOCK]).digest() for i in range(0, len(data), BLOCK)]
        if len(data) > PLEN:
            layer = [fold(leaves[i : i + 4] + [ZERO] * (4 - len(leaves[i : i + 4]))) for i in range(0, len(leaves), 4)]
            root = fold(layer + [fold([ZERO] * 4)] * (4 - len(layer)))
            out["pieces"] += [(f, p, r) for p, r in enumerate(layer)]
        else:
            pad = 1 << max(0, (len(leaves) - 1).bit_length())
            layer, root = [], fold(leaves + [ZERO] * (pad - len(leaves)))
            out["pieces"].append((f, 0, root))
        out["files"].append({"path": (path,), "length": len(data), "pieces_root": root, "layer": layer})
    return out


def test_reference_verdicts_by_hand(tmp_path):
    big = bytes(range(256)) * (2 * PLEN // 256) + b"tail" * 1000  # 2 full pieces and a 4,000 B tail
    small = b"s" * (BLOCK + 7)  # one piece of 2 leaves, padded to 2
    torrent = _tree(tmp_path, {"a.bin": big, "b.bin": small})
    assert len(torrent["pieces"]) == 4
    assert reference_session_v2.copy_verdicts(str(tmp_path), torrent) == [True] * 4
    # the control pads the file of one piece to a full piece's 4 leaves: wrong there alone
    assert reference_session_v2.copy_verdicts(str(tmp_path), torrent, control=True) == [True, True, True, False]
    for file, offset, piece in (("a.bin", 0, 0), ("a.bin", PLEN - 1, 0), ("a.bin", PLEN, 1), ("a.bin", len(big) - 1, 2),
                                ("b.bin", 0, 3), ("b.bin", BLOCK + 6, 3)):
        data = bytearray(big if file == "a.bin" else small)
        data[offset] ^= 0x5A
        (tmp_path / "t" / file).write_bytes(bytes(data))
        got = reference_session_v2.copy_verdicts(str(tmp_path), torrent)
        assert got == [i != piece for i in range(4)], (file, offset)
        (tmp_path / "t" / file).write_bytes(big if file == "a.bin" else small)
    # a file that holds only its first piece gives that one; a missing file gives none
    (tmp_path / "t" / "a.bin").write_bytes(big[:PLEN + 5])
    (tmp_path / "t" / "b.bin").unlink()
    assert reference_session_v2.copy_verdicts(str(tmp_path), torrent) == [True, False, False, False]
    assert reference_session_v2.piece_roots(str(tmp_path), torrent)[1:] == [None] * 3


def test_a_layer_that_does_not_fold_to_its_root_fails_its_file(tmp_path):
    big = os.urandom(2 * PLEN + 9)
    torrent = _tree(tmp_path, {"a.bin": big, "b.bin": b"x"})
    assert reference_session_v2.copy_verdicts(str(tmp_path), torrent) == [True] * 4
    torrent["files"][0]["pieces_root"] = bytes(32)  # the layer's own pieces still match the bytes
    assert reference_session_v2.copy_verdicts(str(tmp_path), torrent) == [False, False, False, True]


# the readers ----------------------------------------------------------------


def reader(name):
    return manifest.load_reader(ROOT, name)


def _wait(busy_s, ops):
    return {"busy_s": busy_s, "bytes": 0, "ops": ops, "active": 0, "max_active": 8}


def _counts(batches, pieces):
    return {"batches": batches, "pieces": pieces, "short_pieces": 0, "pieces_host_hashed": 0, "fold_pieces_launched": 0}


def test_the_readers_by_hand():
    ledger = ({"stages": {}, "waits": {"ingest_v2_batch": _wait(1.0, 10), "ingest_verdict_wait": _wait(0.0, 0)}},
              {"stages": {}, "waits": {"ingest_v2_batch": _wait(9.0, 410), "ingest_verdict_wait": _wait(4.0, 2000)}})
    obs = {"ledger": ledger, "window_s": 20.0, "peers": 8, "root": ROOT,
           "v2_ingest": (_counts(10, 50), _counts(410, 2890)),
           "leaf_rows": ({"scan": {"launches": 50, "rows_launched": 0, "rows_live": 0}},
                         {"scan": {"launches": 2850, "rows_launched": 0, "rows_live": 0},
                          "pallas": {"launches": 40, "rows_launched": 0, "rows_live": 0}})}
    assert reader("v2_ingest_batch_ms").read(obs) == pytest.approx(20.0)  # 8 s over 400 batches
    assert reader("v2_ingest_pieces_per_batch").read(obs) == pytest.approx(7.1)  # 2,840 pieces over 400 batches
    assert reader("v2_ingest_launches_per_batch").read(obs) == pytest.approx(7.1)  # 2,800 + 40 launches
    assert reader("ingest_verdict_ms.v2dl").read(obs) == pytest.approx(2.0)  # 4 s over 2,000 pieces
    assert reader("peer_verdict_stall_share.v2dl").read(obs) == pytest.approx(2.5)  # 4 s of 8 x 20 s
    for alias in ("ingest_verdict_ms", "peer_verdict_stall_share"):
        assert reader(alias + ".v2dl").__file__ == reader(alias).__file__


@pytest.mark.parametrize("name", NEW_READERS)
@pytest.mark.parametrize(
    "obs",
    [
        # the parent's: leaf counters and the ledger, no v2 ingest counters, no batch wait, no hand-off wait
        {"ledger": ({"stages": {}, "waits": {"lane_idle": _wait(1.0, 1)}}, {"stages": {}, "waits": {"lane_idle": _wait(9.0, 5)}}),
         "window_s": 20.0, "peers": 8, "v2_ingest": None,
         "leaf_rows": ({"scan": {"launches": 1, "rows_launched": 16, "rows_live": 4}},
                       {"scan": {"launches": 9, "rows_launched": 144, "rows_live": 36}})},
        # a window that took no batch
        {"ledger": ({"stages": {}, "waits": {"ingest_v2_batch": _wait(1.0, 3)}}, {"stages": {}, "waits": {"ingest_v2_batch": _wait(1.0, 3)}}),
         "window_s": 20.0, "v2_ingest": (_counts(3, 9), _counts(3, 9)), "leaf_rows": ({}, {})},
    ],
)
def test_a_reader_reads_nothing_without_its_source(name, obs):
    assert reader(name).read(dict(obs, root=ROOT)) is None


def test_the_manifest_lists_the_cell_where_its_readers_read():
    m = manifest.load_manifest(ROOT)
    mine = {p["name"] for p in manifest.metrics_for(m, "per_layer", CELL)}
    assert mine == set(NEW_READERS) | {
        "device_idle_share", "idle_unattributed_share", "host_cpu_s_per_gib", "h2d_gib_s", "step_compiles_in_window",
        "hash_step_gib_s", "hash_step_roofline", "leaf_fill_share", "leaf_scan_launch_share", "merkle_share",
        "stage_busy_share", "pass_setup_share",
    }
    assert {e["name"] for e in manifest.metrics_for(m, "end_to_end", CELL)} == {"verify_gib_s", "setup_s"}
    layers = {"v2_ingest_launches_per_batch": "step"}
    for p in m["per_layer"]:
        if p["name"] in NEW_READERS:
            assert p["workloads"] == [CELL] and p["moves"] == "verify_gib_s"
            assert p["layer"] == layers.get(p["name"], "session")
    assert [w["chips"] for w in m["workloads"] if w["name"] == CELL] == [1]
    cell = manifest.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.config["algo"] == "sha256" and cell.config["driver"] == "session_v2"
    assert cell.config["reduced"] == ["payload_bytes", "peers"] and len(cell.config["guarantees"]) == 5
    assert cell.traffic == manifest.load_cell(ROOT, "session-256k.download").traffic
    v2cell = manifest.load_cell(ROOT, "v2-16k.leaves").config
    assert set(cell.config["step_modules"]) == set(v2cell["step_modules"])
    assert cell.config["piece_length"] == v2cell["piece_length"] and cell.config["leaf_bytes"] == v2cell["leaf_bytes"]
    # v2-16k's classes at an eighth of the scale
    for mine_, theirs in zip(cell.config["files"], v2cell["files"]):
        assert (mine_["dir"], mine_["count"], mine_["step_bytes"]) == (theirs["dir"], theirs["count"], theirs["step_bytes"])
        assert mine_["bytes"] * (8 if mine_["dir"] != "small" else 1) == theirs["bytes"]
