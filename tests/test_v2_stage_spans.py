"""The v2 recheck's stages (PR 30), beside ``test_stage_spans.py``'s: every
stage boundary of ``models/v2.verify_v2``'s device road is a ledger stage
and so a ``sched_<stage>`` span, the leaf launches are counted by kernel,
``hasher="cpu"`` charges no device stage, and the leaf steps keep the XLA
module names the benchmark finds their device time by."""

from __future__ import annotations

import os
import re
import threading

import numpy as np
import pytest

from torrent_tpu.codec.metainfo_v2 import BLOCK
from torrent_tpu.models import v2
from torrent_tpu.obs.hist import histograms
from torrent_tpu.obs.ledger import pipeline_ledger
from torrent_tpu.obs.profiler import TRACE_SPAN_PREFIX

from test_metrics import prom_lint
from test_stage_spans import _no_metadata, recorder  # noqa: F401  the recording TraceAnnotation

PLEN = 4 * BLOCK
DEVICE_STAGES = {"h2d", "launch", "digest"}


@pytest.fixture
def small_batches(monkeypatch):
    """Sixteen leaf rows a launch, so a file of a few pieces takes several."""
    monkeypatch.setattr(v2, "LEAF_BATCH", 16)


def _corpus(seed=11):
    rng = np.random.default_rng(seed)
    return [
        (("large", "00.bin"), rng.bytes(9 * PLEN + 5003)),  # 37 leaves: launches of 16, 16 and 5; a short last piece and leaf
        (("mid", "00.bin"), rng.bytes(2 * PLEN + 1031)),  # 9 leaves, three pieces
        (("small", "00.bin"), rng.bytes(3 * BLOCK + 523)),  # one piece or less: 4 leaves, no layer
    ]


def _launches() -> int:
    snap = histograms().family_snapshot(v2.LEAF_LAUNCH_HIST[0])
    return 0 if snap is None else snap[1]


def _on_disk(tmp_path, files):
    for path, data in files:
        fp = tmp_path.joinpath(*path)
        fp.parent.mkdir(parents=True, exist_ok=True)
        fp.write_bytes(data)
    return lambda path: str(tmp_path.joinpath(*path))


class TestDeviceRoad:
    def test_a_recheck_emits_every_stage_in_order(self, recorder, small_batches):
        files = _corpus()
        meta = v2.build_v2(files, "t", PLEN, hasher="cpu")
        pipeline_ledger().clear()
        recorder.spans.clear()
        data = dict(files)
        res = v2.verify_v2(lambda p: data[p], meta, hasher="tpu")
        assert all(ok.all() for ok in res.values())

        mine = [s.removeprefix(TRACE_SPAN_PREFIX) for s in recorder.names(threading.get_ident())]
        file_start = ["pass_setup", "make_leaf_fn", "alloc_padded"]
        launch = ["stage", "h2d", "launch", "digest"]
        # resident bytes are not read; the fold comes after the last file's
        # leaves, then one piece-layer check a file longer than a piece
        assert mine == (file_start + launch * 3) + (file_start + launch) * 2 + ["merkle"] * 3, mine
        _no_metadata(recorder.names())
        for child in recorder.of("make_leaf_fn") + recorder.of("alloc_padded"):
            assert any(recorder.inside(child, parent) for parent in recorder.of("pass_setup"))

    def test_stage_bytes_ops_and_the_launch_counters(self, recorder, small_batches, tmp_path):
        files = _corpus()
        meta = v2.build_v2(files, "t", PLEN, hasher="cpu")
        read_file = _on_disk(tmp_path, files)
        payload = sum(len(d) for _, d in files)
        pipeline_ledger().clear()
        counted, rows = _launches(), v2.leaf_launch_stats()
        res = v2.verify_v2(read_file, meta, hasher="tpu")
        assert all(ok.all() for ok in res.values())

        stages = pipeline_ledger().snapshot()["stages"]
        assert {"pass_setup", "read", "stage", "merkle"} | DEVICE_STAGES <= set(stages)
        for name in ("read", "stage", "h2d", "launch", "digest"):
            assert stages[name]["bytes"] == payload, name
        # the upload moves the whole padded slab and its block counts
        slab = 16 * (v2.alloc_padded(1, BLOCK)[0].shape[1] + 4)
        assert stages["h2d"]["moved_bytes"] == 5 * slab >= stages["h2d"]["bytes"]
        assert stages["pass_setup"]["ops"] == 3 and stages["pass_setup"]["bytes"] == 0
        # one flush of 37 + 9 + 4 leaves, then the two layers of 10 and 3 pieces
        assert stages["merkle"]["ops"] == 3 and stages["merkle"]["bytes"] == 32 * (50 + 10 + 3)
        # the ledger's launches are the histogram's and the counters'
        after = v2.leaf_launch_stats()
        delta = {k: after["scan"][k] - rows.get("scan", {}).get(k, 0) for k in after["scan"]}
        assert stages["launch"]["ops"] == stages["h2d"]["ops"] == stages["digest"]["ops"] == 5
        assert _launches() - counted == 5
        assert delta == {"launches": 5, "rows_launched": 80, "rows_live": 50}
        assert set(after) == {"scan"}  # off a TPU the Pallas kernel is never chosen

    def test_hasher_cpu_charges_no_device_stage(self, recorder, small_batches, tmp_path):
        files = _corpus()
        meta = v2.build_v2(files, "t", PLEN, hasher="cpu")
        read_file = _on_disk(tmp_path, files)
        pipeline_ledger().clear()
        counted, rows = _launches(), v2.leaf_launch_stats()
        res = v2.verify_v2(read_file, meta, hasher="cpu")
        assert all(ok.all() for ok in res.values())
        stages = pipeline_ledger().snapshot()["stages"]
        assert not DEVICE_STAGES & set(stages) and "pass_setup" not in stages
        assert stages["read"]["bytes"] == sum(len(d) for _, d in files)
        assert stages["merkle"]["ops"] == 3
        assert _launches() == counted and v2.leaf_launch_stats() == rows

    def test_progress_is_called_once_a_launch(self, small_batches):
        files = _corpus()
        meta = v2.build_v2(files, "t", PLEN, hasher="cpu")
        data = dict(files)
        marks = []
        v2.verify_v2(lambda p: data[p], meta, hasher="tpu", progress_cb=lambda d, t: marks.append((d, t)))
        # 10 + 3 + 1 pieces; the large file's launches end after 4, 8 and all 10 of its pieces
        assert marks == [(4, 14), (8, 14), (10, 14), (13, 14), (14, 14)]
        # a file without a source is counted from the start
        marks.clear()
        res = v2.verify_v2(lambda p: None if p[0] == "mid" else data[p], meta, hasher="tpu",
                           progress_cb=lambda d, t: marks.append((d, t)))
        assert marks == [(7, 14), (11, 14), (13, 14), (14, 14)] and not res[("mid", "00.bin")].any()
        marks.clear()
        v2.verify_v2(lambda p: data[p], meta, hasher="cpu", progress_cb=lambda d, t: marks.append((d, t)))
        assert marks == [(10, 14), (13, 14), (14, 14)]

    def test_the_cli_prints_the_v1_progress_line(self, tmp_path, capsys):
        from torrent_tpu.codec.metainfo_v2 import encode_metainfo_v2
        from torrent_tpu.tools.cli import main

        files = _corpus()
        meta = v2.build_v2(files, "t", PLEN, hasher="cpu")
        _on_disk(tmp_path / "t", files)
        torrent = tmp_path / "t.torrent"
        torrent.write_bytes(encode_metainfo_v2(meta.info, meta.piece_layers, "http://t/announce"))
        assert main(["verify", str(torrent), str(tmp_path), "--hasher", "cpu"]) == 0
        out = capsys.readouterr()
        assert "\rverified 14/14 pieces" in out.err and "14/14 pieces valid (v2)" in out.out


class TestLeafCounters:
    def test_the_counters_render_as_prometheus_text(self):
        from torrent_tpu.utils.metrics import render_leaf_metrics

        text = render_leaf_metrics(
            {"pallas": {"launches": 12, "rows_launched": 163840, "rows_live": 131085},
             "scan": {"launches": 16, "rows_launched": 256, "rows_live": 64}}
        )
        prom_lint(text)
        assert 'torrent_tpu_v2_leaf_rows_launched_total{kernel="pallas"} 163840' in text
        assert 'torrent_tpu_v2_leaf_rows_live_total{kernel="scan"} 64' in text
        assert 'torrent_tpu_v2_leaf_launches_total{kernel="scan"} 16' in text
        prom_lint(render_leaf_metrics({}))

    def test_no_update_is_lost_between_threads(self):
        import sys

        counters = v2._LeafCounters()
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            def work():
                for _ in range(2000):
                    counters.add("scan", 16, 4)

            threads = [threading.Thread(target=work) for _ in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(interval)
        assert counters.stats() == {"scan": {"launches": 32000, "rows_launched": 512000, "rows_live": 128000}}

    def test_metrics_carry_them_once_the_plane_is_in(self, small_batches):
        from torrent_tpu.obs import render_obs_metrics

        v2._leaf_words_device(os.urandom(3 * BLOCK), "auto")
        text = render_obs_metrics()
        assert re.search(r'torrent_tpu_v2_leaf_rows_live_total\{kernel="scan"\} \d+', text)


class TestLeafStepModuleNames:
    """``hash_step_*`` find the leaf step's device time by XLA module name."""

    def test_the_leaf_steps_lower_to_the_pinned_names(self):
        import jax

        from torrent_tpu.ops import sha256_pallas as sp
        from torrent_tpu.ops.sha256_jax import sha256_pieces_jax

        width = v2.alloc_padded(1, BLOCK)[0].shape[1]
        u8 = jax.ShapeDtypeStruct((1024, width), np.uint8)
        nb = jax.ShapeDtypeStruct((1024,), np.int32)
        lowered = [
            sha256_pieces_jax.lower(u8, nb),
            sp._sha256_pallas_aligned.lower(u8, nb, interpret=True, tile_sub=8, unroll=sp.UNROLL,
                                            full_unroll=False, interleave2=False),
        ]
        names = {re.search(r"module @(\S+)", lo.as_text()).group(1) for lo in lowered}
        assert names == v2.LEAF_STEP_MODULE_NAMES

    def test_the_merkle_reduce_has_names_of_its_own(self):
        import jax

        from torrent_tpu.models import merkle

        lowered = [
            merkle.sha256_pairs.lower(jax.ShapeDtypeStruct((8, 16), np.uint32)),
            merkle._merkle_reduce_fused.lower(jax.ShapeDtypeStruct((2, 8, 8), np.uint32), levels=3),
        ]
        names = {re.search(r"module @(\S+)", lo.as_text()).group(1) for lo in lowered}
        assert names == merkle.MERKLE_MODULE_NAMES
        assert not names & v2.LEAF_STEP_MODULE_NAMES
