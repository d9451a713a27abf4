"""The cell ``session-256k.download`` (PR 36): whole runs of the harness on
the CPU at rehearsal sizes (sound and traced, the control, the ingest judge
swapped for one that answers all-valid, the lane on hashlib), the payload's
torrent against the program's parser, the reference's verdicts by hand, and
the two readers of the session's wait."""

import hashlib
import os

import pytest

from benchmark import run as bench
from benchmark.harness import bencode, manifest, reference_session
from benchmark.tests.test_run import _break_at_window

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "session-256k.download"
NEW_READERS = ["ingest_verdict_ms", "peer_verdict_stall_share"]


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
                "hashlib_fallback_verdicts", "cpu_fallback_launches", "launch_failures", "failed_pieces",
                "hashlib_lanes", "verdicts_pending", "staging_outstanding"):
        assert checks[key] == zero, key
    assert checks["downloads"] >= 1 and checks["timed_deliveries"] >= 65 * checks["downloads"]
    # the planted download: the leecher dropped the poisoner after three refusals in a row at the least
    assert checks["planted_pieces"] == 8 and checks["reference_invalid"] >= 3
    assert checks["planted_refused"] and checks["planted_adopted"] < 65
    # the scheduler was the road: every timed delivery a piece of the tenant `ingest`, on the one lane
    # (a duplicate delivery still at the judge when its download ends is a piece of the tenant and no event)
    assert checks["timed_deliveries"] <= checks["ingest_pieces"] <= checks["timed_deliveries"] + 8 * checks["downloads"]
    assert checks["lane_kernels"] == ["scan"]
    assert 0 < checks["launches"] <= checks["timed_deliveries"]
    # what the program's ledger and counters give a CPU run too (a CPU trace
    # holds no device plane, so no trace reader)
    assert set(line["rehearsal"]["would_report"]) == set(NEW_READERS) | {
        "h2d_gib_s", "host_cpu_s_per_gib", "step_compiles_in_window",
        "sched_mean_fill.session", "sched_pieces_per_launch.session", "sched_wait_ms.session",
    }


def test_control_is_not_correct_by_exactly_the_planted_deliveries():
    bad = _run(control=1)
    assert bad["correct"] is False
    checks = bad["checks"]
    assert checks["wrong_verdicts"]["value"] == checks["reference_invalid"] >= 3


def test_a_judge_that_answers_all_valid_is_not_correct(monkeypatch):
    """The verdicts come from the scheduler's demux: one that calls every
    piece valid accepts the poisoner's deliveries, writes them, and never
    drops it; the phase is given up and the planted bytes are on disk."""
    from torrent_tpu.sched import scheduler

    real = scheduler.HashPlaneScheduler.enqueue

    async def all_valid(self, tenant, pieces, expected=None, *a, **kw):
        fut = await real(self, tenant, pieces, expected, *a, **kw)
        answered = fut.get_loop().create_future()
        fut.add_done_callback(lambda f: answered.set_result(bytes([1]) * len(pieces)))
        return answered

    def shorten(d):
        d.stall_seconds = 3.0
        _break_at_window(d, lambda: monkeypatch.setattr(scheduler.HashPlaneScheduler, "enqueue", all_valid))

    line = _run(hook=shorten)
    assert line["correct"] is False
    checks = line["checks"]
    assert checks["wrong_verdicts"]["value"] > 0 and checks["planted_on_disk"]["value"] > 0
    assert checks["planted_refused"] == []


def test_a_lane_on_hashlib_is_not_correct(monkeypatch):
    """Every verdict right and a launch on the hashlib plane breaks the
    fourth guarantee."""
    from torrent_tpu.sched import scheduler

    trip = lambda: monkeypatch.setattr(scheduler._LaneBreaker, "acquire_primary", lambda self: False)
    line = _run(hook=lambda d: _break_at_window(d, trip))
    assert line["checks"]["wrong_verdicts"]["value"] == 0
    assert line["checks"]["cpu_fallback_launches"]["value"] > 0
    assert line["correct"] is False


# the payload ----------------------------------------------------------------


def _driver(rehearse: bool, seed=2147483777, work_dir=""):
    cell = manifest.load_cell(ROOT, CELL)
    if rehearse:
        bench.apply_rehearsal(cell)
    cell.seed, cell.work_dir, cell.log = seed, work_dir, lambda *_: None
    return manifest.load_driver(ROOT, cell.config["driver"]).Driver(cell)


@pytest.mark.parametrize("seed", [7, 2147483777, 4294967295])
def test_the_full_size_plan_holds_the_three_kinds(seed):
    d = _driver(False, seed)
    assert (d.n_pieces, d.length, d.piece_length, d.n_peers) == (1025, 268_435_456 + 5_003, 262_144, 8)
    plan = d._plan()
    assert len(plan) == 128 and all(0 <= i < 1025 for i in plan)
    assert 8 <= plan[0] < 16_384 and 8 <= plan[1024] < 5_003  # first block of the first piece; the short last piece
    assert sum(1 for i, off in plan.items() if i < 1024 and off >= 262_144 - 16_384) >= 1  # a last block
    assert all(off >= 8 for off in plan.values())  # never in the stamp
    assert d._plan() == plan


def test_the_torrent_is_one_the_program_accepts_and_the_reference_agrees(tmp_path):
    from torrent_tpu.codec.metainfo import parse_metainfo

    d = _driver(True, work_dir=str(tmp_path))
    d.seeders = None
    d.plan = d._plan()
    digests = d._write_copy(str(tmp_path / "source"), {})
    d._write_copy(str(tmp_path / "poisoned"), d.plan)
    src, bad = tmp_path / "source" / d.name, tmp_path / "poisoned" / d.name
    assert os.path.getsize(src) == os.path.getsize(bad) == d.length == 2 * 1024 * 1024 + 5003
    data = src.read_bytes()
    tail = d.length - (d.n_pieces - 1) * d.piece_length
    digests[-1] = hashlib.sha1(data[-tail:]).digest()
    info = {"length": d.length, "name": d.name, "piece length": d.piece_length, "pieces": b"".join(digests)}
    path = tmp_path / "payload.torrent"
    path.write_bytes(bencode.encode({"announce": "", "info": info}))
    meta = parse_metainfo(path.read_bytes())
    torrent = reference_session.read_torrent(str(path))
    assert meta is not None and meta.info.num_pieces == d.n_pieces == len(torrent["digests"]) == 65
    assert meta.info.length == torrent["length"] == d.length and meta.announce == ""
    assert len(set(torrent["digests"])) == d.n_pieces  # every piece a digest of its own
    assert reference_session.copy_verdicts(str(src), torrent) == [True] * d.n_pieces
    assert [i for i, ok in enumerate(reference_session.copy_verdicts(str(bad), torrent)) if not ok] == sorted(d.plan)
    diff = [i for i, (a, b) in enumerate(zip(data, bad.read_bytes())) if a != b]
    assert diff == sorted(i * d.piece_length + off for i, off in d.plan.items())  # one byte a planted piece


# the reference, by hand -------------------------------------------------------


def _copy(tmp_path, name, data):
    (tmp_path / name).write_bytes(data)
    return str(tmp_path / name)


def test_reference_verdicts_by_hand(tmp_path):
    data = bytes(range(200))  # 64-byte pieces: three whole and one of 8 bytes
    torrent = {"length": 200, "piece_length": 64,
               "digests": [hashlib.sha1(data[i : i + 64]).digest() for i in range(0, 200, 64)]}
    assert reference_session.copy_verdicts(_copy(tmp_path, "a", data), torrent) == [True] * 4
    for offset, piece in ((0, 0), (63, 0), (64, 1), (191, 2), (192, 3), (199, 3)):
        flipped = bytearray(data)
        flipped[offset] ^= 0x5A
        got = reference_session.copy_verdicts(_copy(tmp_path, "b", bytes(flipped)), torrent)
        assert got == [i != piece for i in range(4)], offset
    # a file that is short holds no whole last piece; one that is missing holds none
    assert reference_session.copy_verdicts(_copy(tmp_path, "c", data[:-1]), torrent) == [True, True, True, False]
    assert reference_session.copy_verdicts(str(tmp_path / "none"), torrent) == [False] * 4
    assert reference_session.piece_digests(_copy(tmp_path, "d", data[:100]), 200, 64)[1:] == [None] * 3


def test_deliveries_are_compared_one_by_one():
    held = [True, False, True, True]
    events = [(0, "ok"), (1, "corrupt"), (1, "corrupt"), (2, "ok"), (2, "ok"), (3, "ok")]
    assert reference_session.compare_deliveries(events, held, every="valid") == {
        "compared": 6, "reference_invalid": 2, "wrong_verdicts": 0, "missing_verdicts": 0}
    # an invalid delivery accepted, a valid one refused (an I/O error refuses too), a valid piece never judged
    got = reference_session.compare_deliveries([(1, "ok"), (0, "corrupt"), (2, "io_error")], held, every="valid")
    assert got == {"compared": 3, "reference_invalid": 1, "wrong_verdicts": 3, "missing_verdicts": 3}
    # the planted phase: every invalid piece has to have been refused
    assert reference_session.compare_deliveries([(0, "ok")], held, every="invalid")["missing_verdicts"] == 1
    assert reference_session.compare_deliveries([(1, "corrupt")], held, every="invalid")["missing_verdicts"] == 0
    # the control's verdicts in the program's place
    control = [(i, "ok") for i, _ in events]
    assert reference_session.compare_deliveries(control, held, every="valid")["wrong_verdicts"] == 2


# the readers ----------------------------------------------------------------


def reader(name):
    return manifest.load_reader(ROOT, name)


def _wait(busy_s, ops):
    return {"busy_s": busy_s, "bytes": 0, "ops": ops, "active": 0, "max_active": 8}


def test_the_two_readers_by_hand():
    ledger = ({"stages": {}, "waits": {"ingest_verdict_wait": _wait(1.0, 100)}},
              {"stages": {}, "waits": {"ingest_verdict_wait": _wait(33.0, 8100)}})
    obs = {"ledger": ledger, "window_s": 20.0, "peers": 8, "root": ROOT}
    assert reader("ingest_verdict_ms").read(obs) == pytest.approx(4.0)  # 32 s over 8,000 pieces
    assert reader("peer_verdict_stall_share").read(obs) == pytest.approx(20.0)  # 32 s of 8 x 20 s
    # a wait first entered inside the window; one declared and never entered
    first = dict(obs, ledger=({"stages": {}, "waits": {}}, ledger[1]))
    assert reader("ingest_verdict_ms").read(first) == pytest.approx(1000 * 33.0 / 8100)
    idle = dict(obs, ledger=({"stages": {}, "waits": {"ingest_verdict_wait": _wait(0.0, 0)}},) * 2)
    assert reader("ingest_verdict_ms").read(idle) is None and reader("peer_verdict_stall_share").read(idle) == 0.0


@pytest.mark.parametrize("name", NEW_READERS)
@pytest.mark.parametrize(
    "obs",
    [
        # the parent's ledger: waits of other layers, none of the session's
        {"ledger": ({"stages": {}, "waits": {"lane_idle": _wait(1.0, 1)}}, {"stages": {}, "waits": {"lane_idle": _wait(9.0, 5)}}),
         "window_s": 20.0, "peers": 8},
        # a recheck: no waits table
        {"ledger": ({"stages": {}}, {"stages": {}}), "window_s": 20.0},
    ],
)
def test_a_reader_reads_nothing_without_its_source(name, obs):
    assert reader(name).read(dict(obs, root=ROOT)) is None


def test_the_manifest_lists_the_cell_where_its_readers_read():
    m = manifest.load_manifest(ROOT)
    mine = {p["name"] for p in manifest.metrics_for(m, "per_layer", CELL)}
    assert mine == set(NEW_READERS) | {
        "device_idle_share", "idle_unattributed_share", "host_cpu_s_per_gib", "h2d_gib_s", "step_compiles_in_window",
        "sched_mean_fill.session", "sched_pieces_per_launch.session", "sched_wait_ms.session",
        "hash_step_gib_s", "hash_step_roofline",
    }
    assert {e["name"] for e in manifest.metrics_for(m, "end_to_end", CELL)} == {"verify_gib_s", "setup_s"}
    # the scheduler's three counters under names of this cell's: `tests/test_library.py` pins the plain
    # names' `workloads` to the library cell alone, and a benchmark file that is there is not edited
    for name in ("sched_mean_fill", "sched_pieces_per_launch", "sched_wait_ms"):
        assert manifest.load_reader(ROOT, name + ".session").__file__ == manifest.load_reader(ROOT, name).__file__
    for p in m["per_layer"]:
        if p["name"] in NEW_READERS:
            assert p["workloads"] == [CELL] and p["moves"] == "verify_gib_s" and p["layer"] == "session"
            assert p["source"] == "program_span" and reader(p["name"]).SOURCE == "ledger"
    cell = manifest.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.config["algo"] == "sha1" and cell.config["driver"] == "session"
    assert cell.config["reduced"] == ["payload_bytes", "peers"] and len(cell.config["guarantees"]) == 5
    assert cell.config["step_modules"] == manifest.load_cell(ROOT, "bridge-256k.live").config["step_modules"]
