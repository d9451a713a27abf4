"""The cell ``v2-16k.leaves`` (PR 30): whole runs of the harness on the CPU
at rehearsal sizes (sound, the control, the timed path broken underneath),
the payload's torrent against the program's parser, the reference's folds
by hand, and the three readers of the leaf counters and the fold's stage."""

import hashlib
import os

import pytest

from benchmark import run as bench
from benchmark.harness import manifest, payload_v2, reference_v2
from benchmark.tests.test_run import _break_at_window

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "v2-16k.leaves"
BLOCK = reference_v2.BLOCK


def h(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


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
    assert checks["passes_without_launch"]["value"] == 0 and checks["launch_ops_uncounted"]["value"] == 0
    # what the program's ledger and counters give a CPU run too (a CPU trace
    # holds no device plane, so no trace reader)
    assert set(line["rehearsal"]["would_report"]) == {
        "h2d_gib_s", "host_cpu_s_per_gib", "leaf_fill_share", "leaf_scan_launch_share", "merkle_share",
        "pass_setup_share", "stage_busy_share", "step_compiles_in_window",
    }


def test_control_is_not_correct():
    bad = _run(control=1)
    assert bad["correct"] is False
    assert bad["checks"]["wrong_verdicts"]["value"] == bad["checks"]["reference_invalid"] > 0


def _alter_leaves(monkeypatch, how):
    from torrent_tpu.models import v2

    real = v2._launch_leaves

    def broken(*a, **kw):
        words = real(*a, **kw).copy()
        if how == "altered":
            words[0, 0] ^= 1  # one leaf of every launch
        else:  # half of the batch left out
            words[len(words) // 2 :] = 0
        return words

    monkeypatch.setattr(v2, "_launch_leaves", broken)


@pytest.mark.parametrize("how", ["altered", "half_left_out"])
def test_broken_timed_path_is_not_correct(monkeypatch, how):
    line = _run(hook=lambda d: _break_at_window(d, lambda: _alter_leaves(monkeypatch, how)))
    assert line["correct"] is False
    assert line["failed"] > 0 and line["checks"]["wrong_verdicts"]["value"] > 0


def test_hashlib_leaves_on_the_timed_path_are_not_correct(monkeypatch):
    """Every verdict right and no launch counted breaks the second
    guarantee: the leaves were hashed off the device."""
    from torrent_tpu.models import v2

    def trip():
        monkeypatch.setattr(v2, "_leaf_words_device", lambda source, backend, *a, **kw: v2._leaf_words_cpu(source))

    line = _run(hook=lambda d: _break_at_window(d, trip))
    assert line["checks"]["wrong_verdicts"]["value"] == 0
    assert line["checks"]["passes_without_launch"]["value"] > 0
    assert line["correct"] is False


# the payload and the reference ----------------------------------------------


def _rehearsal_cell():
    cell = manifest.load_cell(ROOT, CELL)
    bench.apply_rehearsal(cell)
    return cell


def test_the_torrent_is_one_the_program_accepts(tmp_path):
    from torrent_tpu.codec.metainfo_v2 import parse_metainfo_v2

    cell = _rehearsal_cell()
    plen = cell.config["piece_length"]
    files = payload_v2.file_plan(cell.config["files"])
    assert sum(n for _, n in files) == cell.config["payload_bytes"]
    entries = payload_v2.write_payload(str(tmp_path), "payload", 2147483777, files, plen)
    payload_v2.write_torrent(str(tmp_path / "t.torrent"), "payload", plen, entries)
    meta = parse_metainfo_v2((tmp_path / "t.torrent").read_bytes())
    assert meta is not None and meta.info.piece_length == plen and meta.info.name == "payload"
    assert [(f.path, f.length, f.pieces_root) for f in meta.info.files] == [
        (e["path"], e["length"], e["pieces_root"]) for e in entries
    ]
    for e in entries:
        assert list(meta.piece_layers.get(e["pieces_root"], ())) == e["layer"]
        assert os.path.getsize(tmp_path / "payload" / os.path.join(*e["path"])) == e["length"]
    # the same seed, the same bytes; every leaf a digest of its own
    again = payload_v2.write_payload(str(tmp_path / "again"), "payload", 2147483777, files, plen)
    assert again == entries
    leaves = [d for e in entries for d in reference_v2.leaf_hashes(
        str(tmp_path / "payload" / os.path.join(*e["path"])), e["length"])]
    assert len(set(leaves)) == len(leaves)


def test_the_full_size_file_mix_is_the_issues():
    cell = manifest.load_cell(ROOT, CELL)
    files = payload_v2.file_plan(cell.config["files"])
    plen = cell.config["piece_length"]
    assert plen == 1 << 20 and len(files) == 28
    assert sum(n for _, n in files) == cell.config["payload_bytes"] == 2_148_428_354
    assert sum(reference_v2.num_pieces(n, plen) for _, n in files) == 2076
    assert sum(-(-n // BLOCK) for _, n in files) == 131_149
    plan = payload_v2.corruption_plan(2147483777, files, plen, cell.traffic["corrupt_share"])
    assert len(plan) == 16
    lengths = [n for _, n in files]
    assert any(lengths[f] <= plen for f, _ in plan)  # a small file's one piece
    assert any(lengths[f] > 400 << 20 and p == 448 for f, p in plan)  # a large file's short last piece
    for (f, p), offset in plan.items():
        assert p * plen <= offset < min((p + 1) * plen, lengths[f])


def test_the_corruption_plan_flips_exactly_its_pieces(tmp_path):
    cell = _rehearsal_cell()
    plen = cell.config["piece_length"]
    files = payload_v2.file_plan(cell.config["files"])
    entries = payload_v2.write_payload(str(tmp_path), "payload", 5, files, plen)

    def verdicts():
        return [reference_v2.file_verdicts(str(tmp_path / "payload" / os.path.join(*e["path"])), e["length"],
                                           e["pieces_root"], e["layer"], plen) for e in entries]

    assert all(all(v) for v in verdicts())
    plan = payload_v2.corruption_plan(5, files, plen, 0.125)
    payload_v2.apply_corruption(str(tmp_path), "payload", files, plan)
    got = verdicts()
    assert {(f, p) for f, v in enumerate(got) for p, ok in enumerate(v) if not ok} == set(plan)


def test_the_folds_by_hand():
    z = reference_v2.ZERO
    a, b, c = h(b"a"), h(b"b"), h(b"c")
    # a file of one piece or less: its leaves padded with zero hashes to a power of two
    assert reference_v2.file_root([a], 5, 4 * BLOCK) == (a, [])
    assert reference_v2.file_root([a, b, c], 3 * BLOCK, 4 * BLOCK) == (h(h(a + b) + h(c + z)), [])
    # a longer file, two leaves a piece: the last piece's missing leaf is zero, and
    # the layer is padded with the root of an all-zero piece, not with the zero hash
    root, layer = reference_v2.file_root([a, b, c], 2 * BLOCK + 1, 2 * BLOCK)
    assert layer == [h(a + b), h(c + z)] and root == h(layer[0] + layer[1])
    five = [a, b, c, a, b]
    root, layer = reference_v2.file_root(five, 4 * BLOCK + 9, 2 * BLOCK)
    assert layer == [h(a + b), h(c + a), h(b + z)]
    assert root == h(h(layer[0] + layer[1]) + h(layer[2] + h(z + z)))
    assert reference_v2.zero_root(2) == h(z + z)


def test_verdicts_of_a_missing_file_a_wrong_size_and_a_layer_that_lies(tmp_path):
    path = tmp_path / "f.bin"
    data = bytes(range(256)) * 200  # 51,200 B: four leaves, two pieces of two leaves
    path.write_bytes(data)
    leaves = reference_v2.leaf_hashes(str(path), len(data))
    assert leaves == [h(data[i : i + BLOCK]) for i in range(0, len(data), BLOCK)]
    root, layer = reference_v2.file_root(leaves, len(data), 2 * BLOCK)
    args = (len(data), root, layer, 2 * BLOCK)
    assert reference_v2.file_verdicts(str(path), *args) == [True, True]
    assert reference_v2.file_verdicts(str(tmp_path / "none.bin"), *args) == [False, False]
    assert reference_v2.file_verdicts(str(path), len(data) + 1, root, layer, 2 * BLOCK) == [False, False]
    assert reference_v2.file_verdicts(str(path), len(data), root, [layer[1], layer[0]], 2 * BLOCK) == [False, False]
    path.write_bytes(data[:-1] + b"\x00")
    assert reference_v2.file_verdicts(str(path), *args) == [True, False]


# the readers ----------------------------------------------------------------


def reader(name):
    return manifest.load_reader(ROOT, name)


def rows(launches, launched, live):
    return {"launches": launches, "rows_launched": launched, "rows_live": live}


def test_leaf_fill_and_scan_launch_shares():
    before = {"pallas": rows(12, 163_840, 131_085), "scan": rows(16, 256, 64)}
    after = {"pallas": rows(36, 491_520, 393_255), "scan": rows(48, 768, 192)}
    obs = {"leaf_rows": (before, after), "root": ROOT}
    assert reader("leaf_fill_share").read(obs) == pytest.approx(100 * 262_298 / 328_192)
    assert reader("leaf_scan_launch_share").read(obs) == pytest.approx(100 * 32 / 56)
    # a kernel that first launches inside the window
    obs = {"leaf_rows": ({}, {"scan": rows(4, 64, 16)}), "root": ROOT}
    assert reader("leaf_fill_share").read(obs) == pytest.approx(25.0)
    assert reader("leaf_scan_launch_share").read(obs) == pytest.approx(100.0)


@pytest.mark.parametrize("leaf_rows", [None, (None, None), ({}, {}), ({"scan": rows(4, 64, 16)}, {"scan": rows(4, 64, 16)})])
def test_the_leaf_readers_read_nothing_without_their_source(leaf_rows):
    obs = {"leaf_rows": leaf_rows, "root": ROOT}
    assert reader("leaf_fill_share").read(obs) is None
    assert reader("leaf_scan_launch_share").read(obs) is None
    assert reader("leaf_fill_share").read({"root": ROOT}) is None  # another driver's observations


def test_merkle_share():
    stage = lambda busy: {"busy_s": busy, "bytes": 0, "ops": 1, "active": 0, "max_active": 1}
    before, after = {"stages": {"merkle": stage(1.0)}}, {"stages": {"merkle": stage(1.5)}}
    assert reader("merkle_share").read({"ledger": (before, after), "window_s": 20.0}) == pytest.approx(2.5)
    assert reader("merkle_share").read({"ledger": ({"stages": {}}, after), "window_s": 20.0}) == pytest.approx(7.5)
    assert reader("merkle_share").read({"ledger": ({"stages": {}}, {"stages": {}}), "window_s": 20.0}) is None


def test_the_manifest_lists_the_cell_where_its_readers_read():
    m = manifest.load_manifest(ROOT)
    mine = {p["name"] for p in manifest.metrics_for(m, "per_layer", CELL)}
    assert mine == {
        "device_idle_share", "idle_unattributed_share", "hash_step_gib_s", "hash_step_roofline", "host_cpu_s_per_gib",
        "stage_busy_share", "h2d_gib_s", "step_compiles_in_window", "pass_setup_share", "merkle_share",
        "leaf_fill_share", "leaf_scan_launch_share",
    }
    assert {e["name"] for e in manifest.metrics_for(m, "end_to_end", CELL)} == {"verify_gib_s", "setup_s"}
    cell = manifest.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.config["algo"] == "sha256" and cell.config["driver"] == "recheck_v2"
