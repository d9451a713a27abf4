"""The cell ``library-zipf.bulk`` (PR 32): whole runs of the harness on the
CPU at rehearsal sizes (sound and traced, the control, the scheduler
swapped for one that answers all-valid), the payload's torrents against the
program's parser, the reference's verdicts by hand, and the five readers
of the road's new counters and waits."""

import hashlib
import os

import pytest

from benchmark import run as bench
from benchmark.harness import bencode, manifest, payload_library, reference_library
from benchmark.tests.test_run import _break_at_window

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "library-zipf.bulk"
NEW_READERS = ["deadline_flush_share", "deadline_wait_share", "unit_drain_share", "staged_fill_share", "h2d_live_share"]


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
    assert checks["reference_invalid"] == checks["planted_invalid"] > 0
    for key in ("cpu_fallback_launches", "failed_pieces", "launch_failures", "hashlib_lanes", "staging_outstanding"):
        assert checks[key] == {"value": 0, "limit": 0}
    # every launch took the zero-copy road, on each of the rehearsal's three lanes
    assert checks["staged_launches"] == checks["launches"] > 0 and checks["lanes_with_launches"] == 3
    assert checks["lane_kernels"] == ["scan"]
    # what the program's ledger and counters give a CPU run too (a CPU trace
    # holds no device plane, so no trace reader)
    assert set(line["rehearsal"]["would_report"]) == set(NEW_READERS) | {
        "h2d_gib_s", "host_cpu_s_per_gib", "pass_setup_share", "stage_busy_share", "step_compiles_in_window",
        "sched_mean_fill", "sched_pieces_per_launch", "sched_wait_ms",
    }


def test_control_is_not_correct_by_exactly_the_planted_count():
    bad = _run(control=1)
    assert bad["correct"] is False
    checks = bad["checks"]
    assert checks["wrong_verdicts"]["value"] == checks["reference_invalid"] == checks["planted_invalid"] > 0


def test_a_scheduler_that_answers_all_valid_is_not_correct(monkeypatch):
    """The verdicts come from the scheduler's demux: one that calls every
    piece valid fails on exactly the planted pieces."""
    from torrent_tpu.sched import scheduler

    real = scheduler.HashPlaneScheduler.enqueue

    async def all_valid(self, tenant, pieces, expected=None, *a, **kw):
        fut = await real(self, tenant, pieces, expected, *a, **kw)
        answered = fut.get_loop().create_future()
        fut.add_done_callback(lambda f: answered.set_result(bytes([1]) * len(pieces)))
        return answered

    line = _run(hook=lambda d: _break_at_window(d, lambda: monkeypatch.setattr(scheduler.HashPlaneScheduler, "enqueue", all_valid)))
    assert line["correct"] is False
    assert line["checks"]["wrong_verdicts"]["value"] == line["checks"]["planted_invalid"] > 0


def test_a_plane_on_hashlib_is_not_correct(monkeypatch):
    """Every verdict right and a launch on the hashlib plane breaks the
    third guarantee."""
    from torrent_tpu.sched import scheduler

    trip = lambda: monkeypatch.setattr(scheduler._LaneBreaker, "acquire_primary", lambda self: False)
    line = _run(hook=lambda d: _break_at_window(d, trip))
    assert line["checks"]["wrong_verdicts"]["value"] == 0
    assert line["checks"]["cpu_fallback_launches"]["value"] > 0
    assert line["correct"] is False


# the payload ----------------------------------------------------------------


def _cell(rehearse: bool):
    cell = manifest.load_cell(ROOT, CELL)
    if rehearse:
        bench.apply_rehearsal(cell)
    return cell


def test_the_full_size_library_is_the_issues():
    cell = _cell(False)
    lib = payload_library.library(cell.config)
    assert len(lib) == 31 and sum(t.payload_bytes for t in lib) == cell.config["payload_bytes"] == 2_164_143_893
    assert [t.payload_bytes for t in lib][:2] == [536_875_011, 268_443_654]
    by_length: dict = {}
    for t in lib:
        by_length[t.piece_length] = by_length.get(t.piece_length, 0) + 1
    assert by_length == {1 << 20: 1, 1 << 19: 1, 1 << 18: 2, 1 << 17: 4, 1 << 16: 8, 1 << 15: 15}
    for t in lib:
        assert 513 <= -(-t.payload_bytes // t.piece_length) <= 1000
        assert t.space_bytes % t.piece_length  # every last piece short
        assert len({path for path, _ in t.entries if path is not None}) == (1 if t.k % 3 == 1 else 5)
        pads = [length for path, length in t.entries if path is None]
        assert len(pads) == (4 if t.k % 3 == 0 else 0)
        for pos, path, _, _ in t.spans()[1:]:
            if t.k % 3 == 2:  # every boundary inside a piece
                assert pos % t.piece_length
            elif path is not None:  # a file after a pad starts on a piece boundary
                assert pos % t.piece_length == 0
    # the pads add 19 pieces to the 20,909 the payloads alone would make
    assert sum(-(-t.payload_bytes // t.piece_length) for t in lib) == 20_909
    assert sum(t.n_pieces for t in lib) == 20_928 and sum(t.space_bytes for t in lib) == 2_165_526_447
    assert [t.stem for t in lib] == sorted(t.stem for t in lib)  # the command's sorted glob gives k ascending


def test_the_full_size_corruption_plan_holds_the_four_kinds():
    cell = _cell(False)
    lib = payload_library.library(cell.config)
    for seed in (7, 2147483777):
        plan = payload_library.corruption_plan(seed, lib, cell.traffic["corrupt_share"])
        assert len(plan) == 164
        kinds = set()
        for (ti, p), (path, offset) in plan.items():
            t = lib[ti]
            lengths = dict(t.entries)
            assert 0 <= offset < lengths[path]  # in bytes a file holds
            touched = [(q, length) for pos, q, _, length in t.spans() if pos < (p + 1) * t.piece_length and pos + length > p * t.piece_length]
            if len([q for q, _ in touched if q is not None]) > 1:
                kinds.add("spans two files")
            if touched[-1][0] is None:
                kinds.add("ends in a pad")
            if t.k == 1 and p == t.n_pieces - 1:
                kinds.add("short last piece of the 1 MiB torrent")
            if t.k == 31:
                kinds.add("smallest torrent")
        assert len(kinds) == 4, kinds


def test_the_torrents_are_ones_the_program_accepts_and_the_reference_agrees(tmp_path):
    from torrent_tpu.codec.metainfo import parse_metainfo

    cell = _cell(True)
    lib = payload_library.library(cell.config)
    assert len({t.piece_length for t in lib}) >= 2  # at least two lanes
    assert any(t.k % 3 == 0 for t in lib) and any(t.k % 3 == 2 for t in lib)  # multi-file with pads and without
    payload_library.write_library(str(tmp_path), 2147483777, lib)
    for t in lib:
        path = tmp_path / "torrents" / (t.stem + ".torrent")
        meta = parse_metainfo(path.read_bytes())
        ref = reference_library.read_torrent(str(path))
        assert meta is not None and meta.info.name == t.name == ref["name"]
        assert meta.info.piece_length == t.piece_length == ref["piece_length"]
        assert meta.info.num_pieces == t.n_pieces == len(ref["digests"]) and meta.info.length == t.space_bytes
        if t.single:
            assert meta.info.files is None and os.path.getsize(t.file_path(str(tmp_path), ())) == t.payload_bytes
        else:
            assert [(f.path if not f.pad else None, f.length) for f in meta.info.files] == [
                (p, n) for p, n in t.entries
            ]
            assert not (tmp_path / "data" / t.stem / t.name / ".pad").exists()  # pads never on disk
        verdicts = reference_library.torrent_verdicts(str(path), str(tmp_path / "data" / t.stem))
        assert len(verdicts) == t.n_pieces and all(verdicts)
    # the same seed, the same torrents; every piece a digest of its own
    again = tmp_path / "again"
    payload_library.write_library(str(again), 2147483777, lib)
    digests = []
    for t in lib:
        a = (tmp_path / "torrents" / (t.stem + ".torrent")).read_bytes()
        assert a == (again / "torrents" / (t.stem + ".torrent")).read_bytes()
        digests += reference_library.read_torrent(str(again / "torrents" / (t.stem + ".torrent")))["digests"]
    assert len(set(digests)) == len(digests)


def test_the_corruption_plan_flips_exactly_its_pieces(tmp_path):
    cell = _cell(True)
    lib = payload_library.library(cell.config)
    payload_library.write_library(str(tmp_path), 5, lib)
    plan = payload_library.corruption_plan(5, lib, 0.125)
    assert len(plan) == round(0.125 * sum(t.n_pieces for t in lib))
    payload_library.apply_corruption(str(tmp_path), lib, plan)
    bad = set()
    for ti, t in enumerate(lib):
        verdicts = reference_library.torrent_verdicts(
            str(tmp_path / "torrents" / (t.stem + ".torrent")), str(tmp_path / "data" / t.stem))
        bad |= {(ti, p) for p, ok in enumerate(verdicts) if not ok}
    assert bad == set(plan)


# the reference, by hand -------------------------------------------------------

PLEN = 64
FILES = [("a.bin", bytes(range(100))), ("b.bin", bytes(range(100, 170))), ("c.bin", bytes(range(170, 200)))]


def _three_files(tmp_path, pads: bool):
    """100 + 70 + 30 bytes in three files, 64-byte pieces, written and
    hashed here with nothing of the harness but its bencode."""
    space, files = b"", []
    for i, (name, data) in enumerate(FILES):
        (tmp_path / "data" / "t").mkdir(parents=True, exist_ok=True)
        (tmp_path / "data" / "t" / name).write_bytes(data)
        space += data
        files.append({"length": len(data), "path": [name]})
        if pads and i < 2 and len(data) % PLEN:
            n = PLEN - len(data) % PLEN
            space += bytes(n)
            files.append({"attr": "p", "length": n, "path": [".pad", str(n)]})
    pieces = b"".join(hashlib.sha1(space[i : i + PLEN]).digest() for i in range(0, len(space), PLEN))
    info = {"name": "t", "piece length": PLEN, "pieces": pieces, "files": files}
    (tmp_path / "t.torrent").write_bytes(bencode.encode({"announce": "http://x/", "info": info}))
    return lambda: reference_library.torrent_verdicts(str(tmp_path / "t.torrent"), str(tmp_path / "data"))


def _flip(path, offset):
    data = bytearray(path.read_bytes())
    data[offset] ^= 0x5A
    path.write_bytes(bytes(data))


@pytest.mark.parametrize(
    "pads, n_pieces, name, offset, piece, what",
    [
        (False, 4, "b.bin", 10, 1, "a piece that spans two files (a.bin's tail, b.bin's head)"),
        (False, 4, "a.bin", 99, 1, "the same piece from its other file"),
        (False, 4, "c.bin", 29, 3, "the short last piece (8 bytes)"),
        (False, 4, "b.bin", 69, 2, "a piece that spans b.bin and c.bin"),
        (True, 5, "a.bin", 70, 1, "a piece that ends in a pad span (36 bytes of a.bin, 28 zeros)"),
        (True, 5, "b.bin", 0, 2, "with pads a file starts its own piece"),
        (True, 5, "b.bin", 69, 3, "b.bin's 6-byte tail and 58 zeros"),
        (True, 5, "c.bin", 0, 4, "the short last piece (30 bytes)"),
    ],
)
def test_reference_verdicts_by_hand(tmp_path, pads, n_pieces, name, offset, piece, what):
    verdicts = _three_files(tmp_path, pads)
    assert verdicts() == [True] * n_pieces
    _flip(tmp_path / "data" / "t" / name, offset)
    assert verdicts() == [i != piece for i in range(n_pieces)], what


def test_reference_verdicts_of_a_missing_and_a_short_file(tmp_path):
    verdicts = _three_files(tmp_path, False)
    (tmp_path / "data" / "t" / "b.bin").write_bytes(FILES[1][1][:-1])  # one byte short: its last piece cannot be read
    assert verdicts() == [True, True, False, True]
    (tmp_path / "data" / "t" / "b.bin").unlink()
    assert verdicts() == [True, False, False, True]


# the readers ----------------------------------------------------------------


def reader(name):
    return manifest.load_reader(ROOT, name)


def _stage(busy_s=0.0, nbytes=0, moved=None):
    s = {"busy_s": busy_s, "bytes": nbytes, "ops": 1, "active": 0, "max_active": 1}
    if moved is not None:
        s["moved_bytes"] = moved
    return s


def _lane(launches, staged=None, rows=0, live=0):
    lane = {"target": 256, "launches": launches, "mean_fill": 0.5, "pad_rows_total": 0, "launched_rows_total": 0}
    if staged is not None:
        lane.update(staged_launches=staged, staged_rows_total=rows, staged_live_rows_total=live)
    return lane


def test_the_five_readers_by_hand():
    before = {"launches": 10, "flush_reasons": {"full": 8, "deadline": 2, "shutdown": 0},
              "lane_stats": {"sha1/1048576": _lane(4, 4, 508, 256)}}
    after = {"launches": 60, "flush_reasons": {"full": 38, "deadline": 22, "shutdown": 0},
             "lane_stats": {"sha1/1048576": _lane(13, 13, 1651, 769), "sha1/32768": _lane(41, 41, 10496, 9000)}}
    ledger = (
        {"stages": {"h2d": _stage(1.0, 100, moved=400)}, "waits": {"deadline_wait": _stage(1.0), "unit_drain": _stage(2.0)}},
        {"stages": {"h2d": _stage(3.0, 700, moved=1400)}, "waits": {"deadline_wait": _stage(5.0), "unit_drain": _stage(17.0)}},
    )
    obs = {"sched": (before, after), "ledger": ledger, "window_s": 20.0, "root": ROOT}
    assert reader("deadline_flush_share").read(obs) == pytest.approx(40.0)  # 20 of 50
    assert reader("deadline_wait_share").read(obs) == pytest.approx(20.0)
    assert reader("unit_drain_share").read(obs) == pytest.approx(75.0)
    assert reader("staged_fill_share").read(obs) == pytest.approx(100 * (513 + 9000) / (1143 + 10496))
    assert reader("h2d_live_share").read(obs) == pytest.approx(60.0)
    # a lane, a wait or a stage that first appears inside the window
    first = {"sched": ({"launches": 0}, after), "ledger": ({"stages": {}}, ledger[1]), "window_s": 20.0, "root": ROOT}
    assert reader("deadline_flush_share").read(first) == pytest.approx(100 * 22 / 60)
    assert reader("staged_fill_share").read(first) == pytest.approx(100 * 9769 / 12147)
    assert reader("unit_drain_share").read(first) == pytest.approx(85.0)
    assert reader("h2d_live_share").read(first) == pytest.approx(50.0)


@pytest.mark.parametrize("name", NEW_READERS)
@pytest.mark.parametrize(
    "obs",
    [
        # the parent's keys: lanes without staged rows, a ledger without the new wait and without moved bytes
        {"sched": ({"launches": 1, "lane_stats": {"sha1/64": _lane(1)}}, {"launches": 9, "lane_stats": {"sha1/64": _lane(9)}}),
         "ledger": ({"stages": {"h2d": _stage(1.0, 10)}}, {"stages": {"h2d": _stage(2.0, 90)}}), "window_s": 20.0},
        # a recheck: no scheduler, no waits table
        {"ledger": ({"stages": {}}, {"stages": {}}), "window_s": 20.0},
        # nothing launched, staged or uploaded in the window
        {"sched": ({"launches": 5, "flush_reasons": {"deadline": 1}, "lane_stats": {"sha1/64": _lane(5, 5, 40, 30)}},
                   {"launches": 5, "flush_reasons": {"deadline": 1}, "lane_stats": {"sha1/64": _lane(5, 5, 40, 30)}}),
         "ledger": ({"stages": {"h2d": _stage(1.0, 10, moved=20)}, "waits": {}}, {"stages": {"h2d": _stage(1.0, 10, moved=20)}, "waits": {}}),
         "window_s": 20.0},
    ],
)
def test_a_reader_reads_nothing_without_its_source(name, obs):
    assert reader(name).read(dict(obs, root=ROOT)) is None


def test_the_manifest_lists_the_cell_where_its_readers_read():
    m = manifest.load_manifest(ROOT)
    mine = {p["name"] for p in manifest.metrics_for(m, "per_layer", CELL)}
    assert mine == set(NEW_READERS) | {
        "device_idle_share", "idle_unattributed_share", "host_cpu_s_per_gib", "stage_busy_share", "h2d_gib_s",
        "step_compiles_in_window", "pass_setup_share", "sched_mean_fill", "sched_pieces_per_launch", "sched_wait_ms",
    }
    assert {e["name"] for e in manifest.metrics_for(m, "end_to_end", CELL)} == {"verify_gib_s", "setup_s"}
    for p in m["per_layer"]:
        if p["name"] in NEW_READERS + ["sched_mean_fill", "sched_pieces_per_launch", "sched_wait_ms"]:
            assert p["workloads"] == [CELL] and p["moves"] == "verify_gib_s"
    cell = manifest.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.config["algo"] == "sha1" and cell.config["driver"] == "library"
    assert cell.config["batch_target"] == 256 and cell.config["unit_mb"] == 0  # the command's defaults
