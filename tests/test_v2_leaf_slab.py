"""The v2 leaf road's one kept slab (PR 31), beside ``test_v2.py``: rows
that hold an earlier launch's bytes are made sound without a memset of the
slab, a path source is read by row into it, a resident one copied into it,
and its check-outs and launches are counted."""

from __future__ import annotations

import hashlib
import os
import threading

import numpy as np
import pytest

from torrent_tpu.codec.metainfo_v2 import BLOCK
from torrent_tpu.models import v2
from torrent_tpu.models.merkle import digests_to_words32
from torrent_tpu.native import io_engine
from torrent_tpu.obs.ledger import pipeline_ledger

from test_metrics import prom_lint
from test_v2_stage_spans import PLEN, _corpus, _launches, _on_disk, pallas_leaves, small_batches  # noqa: F401


@pytest.fixture
def engine():
    """The native pread pool, which the direct road needs."""
    if io_engine.get_engine() is None:
        pytest.skip("no native io engine (no toolchain)")


def _hashlib_words(data: bytes) -> np.ndarray:
    digs = [hashlib.sha256(data[i : i + BLOCK]).digest() for i in range(0, len(data), BLOCK)]
    return digests_to_words32(digs or [hashlib.sha256(b"").digest()])


@pytest.fixture
def slab(monkeypatch):
    """A kept slab of this test's own in the process's place: 32 rows, 0xFF in every byte."""
    monkeypatch.setattr(v2, "LEAF_BATCH", 32)
    fresh = v2._LeafSlab()
    padded, kept = fresh.checkout(32)
    padded[:] = 0xFF
    fresh.checkin()
    assert kept
    monkeypatch.setattr(v2, "_leaf_slab", fresh)
    return fresh


def _no_engine(monkeypatch):
    monkeypatch.setattr(io_engine, "get_engine", lambda *a, **kw: None)


def _as_path(tmp_path, data: bytes, name="f.bin") -> str:
    fp = tmp_path / name
    fp.write_bytes(data)
    return str(fp)


SOUND = {
    # (a) every row full
    "full_rows": [5 * BLOCK],
    # (b) a short last leaf: its padding inside the data columns, the last
    # length that keeps the bit-length field there, and the first two that
    # put it in the pad columns
    "short_leaf_padding_in_data_columns": [2 * BLOCK + 523],
    "short_leaf_one_byte": [3 * BLOCK + 1],
    "short_leaf_length_field_last_in_data": [BLOCK + 16375],
    "short_leaf_length_field_in_pad_columns": [BLOCK + 16376],
    "short_leaf_one_byte_short_of_full": [BLOCK + 16383],
    # (c) the empty source's one zero-length leaf
    "empty_source": [0],
    # a file of several launches, the last one short: its rows past the
    # fifth hold the launch before
    "several_launches_last_short": [37 * BLOCK + 5003 - BLOCK],
    # (d) buckets large, small, large on the one slab
    "buckets_large_small_large": [20 * BLOCK + 77, 3 * BLOCK + 523, 25 * BLOCK, 1, 32 * BLOCK],
}


class TestSoundRows:
    @pytest.mark.parametrize("sizes", SOUND.values(), ids=SOUND.keys())
    def test_a_dirty_slab_hashes_as_hashlib(self, slab, sizes):
        rng = np.random.default_rng(len(sizes) + sizes[0])
        for size in sizes:
            data = rng.bytes(size)
            got = v2._leaf_words_device(data, "auto")
            assert np.array_equal(got, _hashlib_words(data)), size
        st = slab.stats()
        assert st["leaf_slab_allocs"] == 1 and st["leaf_slab_transient"] == 0
        assert st["leaf_slab_reuses"] == len(sizes) and st["direct"] == 0

    # (e) a path source and the same bytes resident; (f) the path with no native engine
    @pytest.mark.parametrize("road", ["direct", "resident", "path_without_engine"])
    def test_a_path_and_its_bytes_agree(self, slab, road, tmp_path, monkeypatch):
        if road == "direct" and io_engine.get_engine() is None:
            pytest.skip("no native io engine (no toolchain)")
        if road == "path_without_engine":
            _no_engine(monkeypatch)
        rng = np.random.default_rng(5)
        for size in (70 * BLOCK + 16380, 2 * BLOCK + 9, 0):  # three launches of 32 rows, one of 16, the empty file
            data = rng.bytes(size)
            source = data if road == "resident" else _as_path(tmp_path, data, f"{size}.bin")
            launches, before = _launches(), slab.stats()
            got = v2._leaf_words_device(source, "auto")
            assert np.array_equal(got, _hashlib_words(data)), size
            after = slab.stats()
            staged = "direct" if road == "direct" and size else "copied"
            other = "copied" if staged == "direct" else "direct"
            assert after[staged] - before[staged] == _launches() - launches == max(1, -(-size // (32 * BLOCK)))
            assert after[other] == before[other]

    def test_the_cpu_hasher_takes_no_slab(self, slab, tmp_path):
        data = os.urandom(3 * BLOCK + 5)
        before = slab.stats()
        assert v2.hash_file_v2(_as_path(tmp_path, data), PLEN, hasher="cpu") == v2.hash_file_v2(data, PLEN, hasher="cpu")
        assert slab.stats() == before

    def test_the_slab_never_grows_past_the_largest_bucket(self, slab):
        for size in (32 * BLOCK * 3, 5):
            v2._leaf_words_device(os.urandom(size), "auto")
        assert slab._slab.shape == (32, v2.alloc_padded(1, BLOCK)[0].shape[1])
        assert slab.stats()["leaf_slab_allocs"] == 1


class TestCountersAndConcurrency:
    def test_a_second_pass_allocates_nothing_and_reads_direct(self, engine, small_batches, tmp_path, monkeypatch):
        monkeypatch.setattr(v2, "_leaf_slab", v2._LeafSlab())
        files = _corpus()
        meta = v2.build_v2(files, "t", PLEN, hasher="cpu")
        read_file = _on_disk(tmp_path, files)
        assert all(ok.all() for ok in v2.verify_v2(read_file, meta, hasher="tpu").values())
        first, launches = v2.leaf_slab_stats(), _launches()
        assert first == {"leaf_slab_allocs": 1, "leaf_slab_reuses": 2, "leaf_slab_transient": 0, "direct": 5, "copied": 0,
                         "leaf_launches_overlapped": 4, "leaf_uploads_chunked": 0}
        assert all(ok.all() for ok in v2.verify_v2(read_file, meta, hasher="tpu").values())
        second = v2.leaf_slab_stats()
        assert _launches() - launches == 5
        assert second == {"leaf_slab_allocs": 1, "leaf_slab_reuses": 5, "leaf_slab_transient": 0, "direct": 10, "copied": 0,
                          "leaf_launches_overlapped": 8, "leaf_uploads_chunked": 0}

    def test_two_threads_at_once_one_takes_a_transient_slab(self, slab):
        rng = np.random.default_rng(3)
        blobs = [rng.bytes(9 * BLOCK + 100), rng.bytes(20 * BLOCK + 16379)]
        both_hold_a_slab = threading.Barrier(2, timeout=120)
        got: dict[int, np.ndarray] = {}

        def chunks(data):
            both_hold_a_slab.wait()  # the check-out comes before the first chunk is asked for
            yield data

        def work(i):
            got[i] = v2._leaf_words_from_chunks(chunks(blobs[i]), len(blobs[i]), "auto")

        threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        for i, data in enumerate(blobs):
            assert np.array_equal(got[i], _hashlib_words(data)), i
        st = slab.stats()
        assert st["leaf_slab_transient"] == 1 and st["leaf_slab_reuses"] == 1 and st["copied"] == 2
        # the kept slab came back in
        v2._leaf_words_device(blobs[0], "auto")
        assert slab.stats()["leaf_slab_transient"] == 1 and slab.stats()["leaf_slab_reuses"] == 2

    def test_a_file_torn_between_the_phases_reads_every_piece_false(self, engine, small_batches, tmp_path, monkeypatch):
        monkeypatch.setattr(v2, "_leaf_slab", v2._LeafSlab())
        files = _corpus()
        meta = v2.build_v2(files, "t", PLEN, hasher="cpu")
        read_file = _on_disk(tmp_path, files)
        large = files[0][0]

        def tear(done, total):
            # after the large file's first launch of three: its later rows are gone
            os.truncate(read_file(large), 20 * BLOCK)

        res = v2.verify_v2(read_file, meta, hasher="tpu", progress_cb=tear)
        assert not res[large].any() and len(res[large]) == 10
        assert all(ok.all() for path, ok in res.items() if path != large)
        # the failed read gave the slab back
        st = v2.leaf_slab_stats()
        assert st["leaf_slab_transient"] == 0 and st["leaf_slab_allocs"] == 1


def _ledger_ops() -> dict[str, int]:
    stages = pipeline_ledger().snapshot()["stages"]
    return {name: stages.get(name, {}).get("ops", 0) for name in ("h2d", "launch", "digest")}


def _launch_rows() -> int:
    return sum(c["launches"] for c in v2.leaf_launch_stats().values())


def _flip(path: str, offset: int) -> None:
    with open(path, "r+b") as fh:
        fh.seek(offset)
        byte = fh.read(1)
        fh.seek(offset)
        fh.write(bytes([byte[0] ^ 0x01]))


# Files of every row bucket up to a LEAF_BATCH of 64 (16, 32 and 64 rows),
# with PLEN = 4 leaves a piece: (name, bytes, piece to flip a byte in or None).
WINDOW_FILES = [
    ("several_launches_full_piece_bad", 150 * BLOCK + 777, 5),  # 151 leaves: launches of 64, 64 and 23
    ("bucket_32_short_last_piece_bad", 25 * BLOCK + 100, 6),  # 26 leaves, the short seventh piece flipped
    ("bucket_16_first_piece_bad", 9 * BLOCK + 5, 0),
    ("one_piece_bad", 3 * BLOCK + 523, 0),
    ("several_launches_clean", 64 * BLOCK * 2 + 1, None),  # 129 leaves: 64, 64 and 1
    ("one_piece_clean", 2 * BLOCK, None),
]


class TestWindow:
    """``verify_v2``'s device road keeps one leaf launch in flight across a
    pass (:class:`v2._LeafWindow`); on the CPU backend a launch in flight
    uploads a copy of the slab, so the next one may be staged into it."""

    @pytest.fixture
    def window_slab(self, monkeypatch):
        """LEAF_BATCH 64 and a kept slab of 64 rows, 0xFF in every byte."""
        monkeypatch.setattr(v2, "LEAF_BATCH", 64)
        fresh = v2._LeafSlab()
        padded, _ = fresh.checkout(64)
        padded[:] = 0xFF
        fresh.checkin()
        monkeypatch.setattr(v2, "_leaf_slab", fresh)
        return fresh

    def _damaged(self, tmp_path):
        rng = np.random.default_rng(45)
        files = [((name, "f.bin"), rng.bytes(size)) for name, size, _ in WINDOW_FILES]
        meta = v2.build_v2(files, "t", PLEN, hasher="cpu")
        read_file = _on_disk(tmp_path, files)
        for (path, data), (_, _, bad) in zip(files, WINDOW_FILES):
            if bad is not None:
                _flip(read_file(path), min(len(data) - 1, bad * PLEN + 7))
        return meta, read_file

    @pytest.mark.parametrize("road", ["path", "resident"])
    def test_the_windowed_pass_gives_the_cpu_verdicts_twice(self, window_slab, road, tmp_path):
        meta, read_file = self._damaged(tmp_path)
        if road == "resident":
            on_disk = read_file
            read_file = lambda path: open(on_disk(path), "rb").read()  # noqa: E731
        want = v2.verify_v2(read_file, meta, hasher="cpu")
        assert sum(int((~ok).sum()) for ok in want.values()) == 4
        for _ in range(2):  # the second pass over the slab the first left dirty
            got = v2.verify_v2(read_file, meta, hasher="tpu")
            assert set(got) == set(want)
            for path in want:
                assert np.array_equal(got[path], want[path]), path
        st = window_slab.stats()
        assert st["leaf_slab_allocs"] == 1 and st["leaf_slab_transient"] == 0

    @pytest.mark.parametrize(
        "files, launches",
        [
            ([(("one", "f.bin"), 3 * BLOCK)], 1),
            ([(("a", "f.bin"), 40 * BLOCK + 9)], 3),  # 41 leaves: 16, 16 and 9 rows
            ([(("a", "f.bin"), 40 * BLOCK + 9), (("b", "f.bin"), 5 * BLOCK), (("c", "f.bin"), 17 * BLOCK)], 6),
        ],
        ids=["one_launch", "one_file_of_three", "three_files_of_six"],
    )
    def test_a_pass_of_n_launches_overlaps_n_minus_one(self, small_batches, files, launches, monkeypatch):
        monkeypatch.setattr(v2, "_leaf_slab", v2._LeafSlab())
        rng = np.random.default_rng(launches)
        files = [(path, rng.bytes(size)) for path, size in files]
        meta = v2.build_v2(files, "t", PLEN, hasher="cpu")
        data = dict(files)
        counted = _launches()
        for n_pass in (1, 2):
            assert all(ok.all() for ok in v2.verify_v2(lambda p: data[p], meta, hasher="tpu").values())
            assert _launches() - counted == n_pass * launches
            assert v2.leaf_slab_stats()["leaf_launches_overlapped"] == n_pass * (launches - 1)

    def test_the_ledger_the_histogram_and_the_counters_agree(self, small_batches, monkeypatch):
        monkeypatch.setattr(v2, "_leaf_slab", v2._LeafSlab())
        files = _corpus()
        meta = v2.build_v2(files, "t", PLEN, hasher="cpu")
        data = dict(files)
        ops, counted, rows = _ledger_ops(), _launches(), _launch_rows()
        v2.verify_v2(lambda p: data[p], meta, hasher="tpu")
        after = _ledger_ops()
        assert {k: after[k] - ops[k] for k in after} == {"h2d": 5, "launch": 5, "digest": 5}
        assert _launches() - counted == _launch_rows() - rows == 5

        # a pass that raises drains its window all the same
        def stop(done, total):
            if done >= 8:
                raise RuntimeError("stop")

        ops, counted = _ledger_ops(), _launches()
        with pytest.raises(RuntimeError):
            v2.verify_v2(lambda p: data[p], meta, hasher="tpu", progress_cb=stop)
        after = _ledger_ops()
        assert after["launch"] - ops["launch"] == after["digest"] - ops["digest"] == _launches() - counted == 3
        assert not v2._leaf_slab._out

    def test_a_file_torn_after_one_in_flight_leaves_that_file_right(self, small_batches, tmp_path, monkeypatch):
        monkeypatch.setattr(v2, "_leaf_slab", v2._LeafSlab())
        files = _corpus()
        meta = v2.build_v2(files, "t", PLEN, hasher="cpu")
        read_file = _on_disk(tmp_path, files)
        large, mid, small = (path for path, _ in files)
        ops = _ledger_ops()

        def tear(done, total):
            # the large file's second launch is fetched, its third in flight:
            # the next file goes before the first of its rows is read
            if done == 8:
                os.remove(read_file(mid))

        res = v2.verify_v2(read_file, meta, hasher="tpu", progress_cb=tear)
        assert res[large].all() and len(res[large]) == 10
        assert not res[mid].any() and len(res[mid]) == 3
        assert res[small].all()
        after = _ledger_ops()
        assert after["launch"] - ops["launch"] == after["digest"] - ops["digest"] == 4
        st = v2.leaf_slab_stats()
        assert not v2._leaf_slab._out and st["leaf_slab_transient"] == 0
        # the torn file drained the window: the small file's launch went out alone
        assert st["leaf_launches_overlapped"] == 2


class TestSlabMetrics:
    def test_the_counters_render_as_prometheus_text(self):
        from test_metrics import prom_lint
        from torrent_tpu.utils.metrics import render_leaf_slab_metrics

        text = render_leaf_slab_metrics(
            {"leaf_slab_allocs": 2, "leaf_slab_reuses": 82, "leaf_slab_transient": 1, "direct": 84, "copied": 3,
             "leaf_launches_overlapped": 83, "leaf_uploads_chunked": 12}
        )
        prom_lint(text)
        assert "torrent_tpu_v2_leaf_slab_allocs_total 2" in text
        assert "torrent_tpu_v2_leaf_slab_reuses_total 82" in text
        assert "torrent_tpu_v2_leaf_slab_transient_total 1" in text
        assert 'torrent_tpu_v2_leaf_launches_staged_total{staged="direct"} 84' in text
        assert 'torrent_tpu_v2_leaf_launches_staged_total{staged="copied"} 3' in text
        assert "torrent_tpu_v2_leaf_launches_overlapped_total 83" in text
        assert "torrent_tpu_v2_leaf_uploads_chunked_total 12" in text

    def test_a_recheck_pass_moves_the_overlap_counter_on_metrics(self, small_batches, monkeypatch):
        from torrent_tpu.obs import render_obs_metrics

        monkeypatch.setattr(v2, "_leaf_slab", v2._LeafSlab())
        files = _corpus()
        meta = v2.build_v2(files, "t", PLEN, hasher="cpu")
        data = dict(files)
        v2.verify_v2(lambda p: data[p], meta, hasher="tpu")
        text = render_obs_metrics()
        prom_lint(text)
        assert "torrent_tpu_v2_leaf_launches_overlapped_total 4" in text
        assert "torrent_tpu_v2_leaf_uploads_chunked_total 0" in text  # 16-row launches: scan, one put each

    def test_metrics_carry_them(self, slab):
        from torrent_tpu.obs import render_obs_metrics

        v2._leaf_words_device(os.urandom(2 * BLOCK), "auto")
        text = render_obs_metrics()
        assert "torrent_tpu_v2_leaf_slab_reuses_total 1" in text
        assert 'torrent_tpu_v2_leaf_launches_staged_total{staged="copied"} 1' in text


# A leaf bucket of every size up to LEAF_BATCH 4,096 (the 16-row floor to
# the 4,096-row Pallas launch of four row blocks), as a file whose leaves
# fill more than half of it and end short, and one piece of it bad.
BUCKETS = [16, 32, 64, 128, 256, 512, 1024, 2048, 4096]


class TestHostOrderUpload:
    """Every leaf launch goes up as host-order ``u32`` rows: a Pallas launch
    of 2,048 rows and more in concurrent row blocks, any other in one put.
    The chip's choice of kernel runs here in interpret mode
    (``pallas_leaves``)."""

    @pytest.fixture
    def dirty_slab(self, monkeypatch):
        """LEAF_BATCH 4,096 and a kept slab of 4,096 rows, 0xFF in every byte."""
        monkeypatch.setattr(v2, "LEAF_BATCH", 4096)
        fresh = v2._LeafSlab()
        padded, _ = fresh.checkout(4096)
        padded[:] = 0xFF
        fresh.checkin()
        monkeypatch.setattr(v2, "_leaf_slab", fresh)
        return fresh

    @pytest.mark.parametrize("road", ["path", "resident"])
    @pytest.mark.parametrize("rows", BUCKETS)
    def test_every_bucket_gives_hashlibs_verdicts_and_leaves(self, pallas_leaves, dirty_slab, rows, road, tmp_path):
        rng = np.random.default_rng(rows)
        leaves = rows * 3 // 4 + 1
        files = [(("bucket", "f.bin"), rng.bytes(leaves * BLOCK - 523)), (("after", "f.bin"), rng.bytes(2 * BLOCK + 9))]
        meta = v2.build_v2(files, "t", PLEN, hasher="cpu")
        read_file = _on_disk(tmp_path, files)
        bad = (leaves // 4) // 2
        _flip(read_file(files[0][0]), bad * PLEN + 11)
        if road == "resident":
            on_disk = read_file
            read_file = lambda path: open(on_disk(path), "rb").read()  # noqa: E731
        want = v2.verify_v2(read_file, meta, hasher="cpu")
        assert [int(i) for i in np.flatnonzero(~want[files[0][0]])] == [bad] and want[files[1][0]].all()
        before = dirty_slab.stats()
        # the bucket's launch is in flight while the next file's is staged
        # in the slab it went up from: on the CPU each of its blocks is a copy
        got = v2.verify_v2(read_file, meta, hasher="tpu")
        assert {p: list(ok) for p, ok in got.items()} == {p: list(ok) for p, ok in want.items()}
        after = dirty_slab.stats()
        assert after["leaf_launches_overlapped"] - before["leaf_launches_overlapped"] == 1
        assert after["leaf_uploads_chunked"] - before["leaf_uploads_chunked"] == (rows >= 2048)
        source = read_file(files[0][0])
        data = open(source, "rb").read() if road == "path" else source
        assert np.array_equal(v2._leaf_words_device(source, "auto"), _hashlib_words(data))

    @pytest.mark.parametrize("rows", [2048, 4096, 8192])
    def test_a_windowed_upload_copies_every_block(self, rows):
        """On the CPU backend ``device_put`` may alias a row block of the
        slab; the window's launch (``copy``) puts a copy of each, so the
        slab refilled at once leaves what went up as it was."""
        padded = v2.alloc_padded(rows, BLOCK)[0]
        padded[:, :BLOCK] = np.random.default_rng(rows).integers(0, 256, (rows, BLOCK), dtype=np.uint8)
        nblocks = v2._pad_rows(padded, rows * BLOCK)
        want = padded.view(np.uint32).copy()
        as_uploaded = (lambda blocks, nb: blocks), "pallas"  # a step that hands back what it was given
        flight = v2._enqueue_leaves(as_uploaded, padded, nblocks, copy=True)
        padded[:] = 0xEE  # the next launch staged into the same rows
        assert isinstance(flight.out, tuple) and len(flight.out) == min(4, rows // 1024)
        assert np.array_equal(np.concatenate([np.asarray(blk) for blk in flight.out]), want)

    def test_only_pallas_launches_go_up_in_blocks(self, pallas_leaves, dirty_slab):
        """``leaf_uploads_chunked`` counts the Pallas launches of 2,048 rows
        and more, never a scan launch nor a 1,024-row one (one block)."""
        kernels = {}
        for rows in BUCKETS:
            before, launched = dirty_slab.stats()["leaf_uploads_chunked"], v2.leaf_launch_stats()
            data = os.urandom((rows - 1) * BLOCK + 1)
            assert np.array_equal(v2._leaf_words_device(data, "auto"), _hashlib_words(data))
            now = v2.leaf_launch_stats()
            (kernel,) = [k for k in now if now[k]["launches"] != launched.get(k, {}).get("launches", 0)]
            kernels[rows] = (kernel, dirty_slab.stats()["leaf_uploads_chunked"] - before)
        assert kernels == {rows: ("pallas" if rows >= 1024 else "scan", int(rows >= 2048)) for rows in BUCKETS}

    def test_the_sessions_ingest_goes_up_in_one_put(self, pallas_leaves, dirty_slab):
        from torrent_tpu.session import v2 as session_v2

        pieces = [os.urandom(PLEN), os.urandom(PLEN), os.urandom(PLEN - 600)]
        roots = [v2.hash_file_v2(p, PLEN, hasher="cpu")[0] for p in pieces]
        assert list(session_v2.ingest_verdicts(pieces, roots, [4, 4, 4], PLEN)) == [True] * 3
        assert dirty_slab.stats()["leaf_uploads_chunked"] == 0
