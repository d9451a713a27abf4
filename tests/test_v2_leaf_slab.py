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

from test_v2_stage_spans import PLEN, _corpus, _launches, _on_disk, small_batches  # noqa: F401


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
        assert first == {"leaf_slab_allocs": 1, "leaf_slab_reuses": 2, "leaf_slab_transient": 0, "direct": 5, "copied": 0}
        assert all(ok.all() for ok in v2.verify_v2(read_file, meta, hasher="tpu").values())
        second = v2.leaf_slab_stats()
        assert _launches() - launches == 5
        assert second == {"leaf_slab_allocs": 1, "leaf_slab_reuses": 5, "leaf_slab_transient": 0, "direct": 10, "copied": 0}

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


class TestSlabMetrics:
    def test_the_counters_render_as_prometheus_text(self):
        from test_metrics import prom_lint
        from torrent_tpu.utils.metrics import render_leaf_slab_metrics

        text = render_leaf_slab_metrics(
            {"leaf_slab_allocs": 2, "leaf_slab_reuses": 82, "leaf_slab_transient": 1, "direct": 84, "copied": 3}
        )
        prom_lint(text)
        assert "torrent_tpu_v2_leaf_slab_allocs_total 2" in text
        assert "torrent_tpu_v2_leaf_slab_reuses_total 82" in text
        assert "torrent_tpu_v2_leaf_slab_transient_total 1" in text
        assert 'torrent_tpu_v2_leaf_launches_staged_total{staged="direct"} 84' in text
        assert 'torrent_tpu_v2_leaf_launches_staged_total{staged="copied"} 3' in text

    def test_metrics_carry_them(self, slab):
        from torrent_tpu.obs import render_obs_metrics

        v2._leaf_words_device(os.urandom(2 * BLOCK), "auto")
        text = render_obs_metrics()
        assert "torrent_tpu_v2_leaf_slab_reuses_total 1" in text
        assert 'torrent_tpu_v2_leaf_launches_staged_total{staged="copied"} 1' in text
