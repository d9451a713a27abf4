"""One span helper on the verify path: a pipeline-ledger stage IS a host
span in the profiler's trace (``obs/ledger.track`` → ``obs/profiler.
open_span``), waits are kept apart from stages, moved bytes are counted
where they move, and the jitted steps keep the names the benchmark finds
their device time by.

A recording stand-in takes ``jax.profiler.TraceAnnotation``'s place: it
accepts a name and nothing else, so any keyword metadata on a span fails
the test that opened it.
"""

from __future__ import annotations

import asyncio
import glob
import hashlib
import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from torrent_tpu.obs.attrib import attribute
from torrent_tpu.obs.ledger import (
    MAX_STAGES,
    PIPELINE_STAGES,
    PipelineLedger,
    pipeline_ledger,
    render_pipeline_metrics,
)
from torrent_tpu.obs.profiler import TRACE_SPAN_PREFIX, annotate

from test_metrics import prom_lint

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PLEN = 16384
BATCH = 8

# the stages of the two roads, as the ledger's docstring names them
RECHECK_STAGES = {"pass_setup", "read", "pad", "h2d", "launch", "digest"}
SCHED_STAGES = {"assemble", "stage", "h2d", "launch", "digest", "verdict"}
SCHED_WAITS = {"lane_idle", "deadline_wait", "sem_wait", "verdict_wake"}
# the bridge's two stages and its four waits (a request's phases, PR 34)
BRIDGE_STAGES = {"decode", "reply"}
BRIDGE_WAITS = {"http_head", "http_body", "verdict_wake", "http_request"}


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: every span as
    ``[name, thread, t_enter, t_exit]``, in the order they were entered."""

    def __init__(self):
        self.spans: list[list] = []
        self._lock = threading.Lock()
        rec = self

        class Annotation:
            def __init__(self, name):  # a name and no keyword metadata
                self._row = [name, None, None, None]

            def __enter__(self):
                self._row[1] = threading.get_ident()
                self._row[2] = time.monotonic()
                with rec._lock:
                    rec.spans.append(self._row)
                return self

            def __exit__(self, *exc):
                self._row[3] = time.monotonic()

        self.Annotation = Annotation

    def names(self, thread=None) -> list[str]:
        return [s[0] for s in self.spans if thread is None or s[1] == thread]

    def of(self, name: str) -> list[list]:
        return [s for s in self.spans if s[0] == TRACE_SPAN_PREFIX + name]

    def inside(self, child: list, parent: list) -> bool:
        return parent[2] <= child[2] and child[3] <= parent[3]


@pytest.fixture
def recorder(monkeypatch):
    import jax

    rec = _Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec.Annotation)
    pipeline_ledger().clear()
    yield rec
    pipeline_ledger().clear()


@pytest.fixture
def one_device(monkeypatch):
    """The conftest gives JAX eight CPU devices; the flat upload path
    (the road a one-chip host takes) needs a mesh of one."""
    import jax

    from torrent_tpu.models import verifier
    from torrent_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(verifier, "make_mesh", lambda devices=None: make_mesh(jax.devices()[:1]))


def _torrent(tmp_path, n_pieces: int, tail: int = 100):
    from torrent_tpu.codec.metainfo import parse_metainfo
    from torrent_tpu.storage.storage import FsStorage, Storage
    from torrent_tpu.tools.make_torrent import make_torrent

    path = tmp_path / "payload.bin"
    path.write_bytes(np.random.default_rng(7).bytes(PLEN * (n_pieces - 1) + tail))
    info = parse_metainfo(make_torrent(str(path), "http://t/announce", piece_length=PLEN)).info
    return Storage(FsStorage(str(tmp_path)), info), info


def _no_metadata(names) -> None:
    """Low-cardinality names: the prefix, then letters and underscores;
    only the scheduler's launch span carries its lane's geometry."""
    for name in set(names):
        assert name.startswith(TRACE_SPAN_PREFIX), name
        if re.fullmatch(r"sched_sha(1|256)_launch_b\d+", name):
            continue
        assert re.fullmatch(r"[a-z_]+", name.replace("h2d", "htod")), name


class TestRecheckRoad:
    def test_a_pass_emits_every_stage_in_order(self, recorder, one_device, tmp_path):
        from torrent_tpu.ops.padding import padded_len_for
        from torrent_tpu.parallel.verify import verify_pieces

        n = 3 * BATCH + 2  # four launches, the last one ragged
        storage, info = _torrent(tmp_path, n)
        ok = verify_pieces(storage, info, hasher="tpu", batch_size=BATCH)
        assert ok.all() and len(ok) == n

        main = threading.get_ident()
        mine = [s.removeprefix(TRACE_SPAN_PREFIX) for s in recorder.names(main)]
        # window of one batch: launch i+1 is enqueued before batch i's fetch
        assert mine == (
            ["pass_setup", "build_verifier", "pass_setup", "alloc_staging", "first_load"]
            + ["h2d", "launch", "step_load"]
            + ["read_wait", "h2d", "launch", "digest"] * 3
            + ["digest"]
        ), mine
        _no_metadata(recorder.names())

        setup_a, setup_b = recorder.of("pass_setup")
        assert recorder.inside(recorder.of("build_verifier")[0], setup_a)
        assert recorder.inside(recorder.of("alloc_staging")[0], setup_b)
        assert recorder.inside(recorder.of("first_load")[0], setup_b)
        assert setup_b[3] <= recorder.of("h2d")[0][2]  # closed before the first upload
        assert recorder.inside(recorder.of("step_load")[0], recorder.of("launch")[0])
        # one span an upload: its chunks' pool workers open none
        assert len(recorder.of("h2d")) == 4
        assert set(recorder.names()) == {TRACE_SPAN_PREFIX + s for s in set(mine) | {"read", "pad"}}
        # the loader's stages, beside the reads, off the main thread
        assert len(recorder.of("pad")) == 4 and all(s[1] != main for s in recorder.of("pad"))
        assert recorder.of("read") and all(s[1] != main for s in recorder.of("read"))

        snap = pipeline_ledger().snapshot()
        assert set(snap["stages"]) == RECHECK_STAGES
        assert set(snap["waits"]) == {"read_wait"}
        assert snap["stages"]["pass_setup"]["ops"] == 2
        assert snap["waits"]["read_wait"]["ops"] == 3
        h2d = snap["stages"]["h2d"]
        assert h2d["ops"] == 4
        assert h2d["moved_bytes"] == 4 * BATCH * padded_len_for(PLEN)  # the padded footprint
        assert h2d["bytes"] == info.length  # the payload
        for stage in ("pad", "launch", "digest"):
            assert snap["stages"][stage]["bytes"] == info.length, stage
            assert snap["stages"][stage]["moved_bytes"] == 0

    def test_the_mesh_path_takes_the_same_stages(self, recorder, tmp_path):
        """Eight devices: the flat road's stages in the flat road's order
        with its window of one — launch *i+1* is enqueued before batch
        *i*'s fetch opens — the sharded upload is ``h2d`` (one op and
        the padded slab a batch), ``launch`` moves nothing, and no
        ``batch`` span opens in a pass (that is ``_run_batch``'s)."""
        from torrent_tpu.ops.padding import padded_len_for
        from torrent_tpu.parallel.verify import verify_pieces

        n = 3 * BATCH + 2  # four launches, the last one ragged
        storage, info = _torrent(tmp_path, n)
        ok = verify_pieces(storage, info, hasher="tpu", batch_size=BATCH)
        assert ok.all() and len(ok) == n
        main = threading.get_ident()
        mine = [s.removeprefix(TRACE_SPAN_PREFIX) for s in recorder.names(main)]
        assert mine == (
            ["pass_setup", "build_verifier", "pass_setup", "alloc_staging", "first_load"]
            + ["h2d", "launch", "step_load"]
            + ["read_wait", "h2d", "launch", "digest"] * 3
            + ["digest"]
        ), mine
        _no_metadata(recorder.names())
        assert not recorder.of("batch")
        h2ds, launches, digests = (recorder.of(s) for s in ("h2d", "launch", "digest"))
        assert len(h2ds) == len(launches) == len(digests) == 4
        assert all(s[1] == main for s in h2ds + launches + digests)
        for i in range(3):  # one thread, so the order above is the order in time: said once more on the clock
            assert launches[i + 1][3] <= digests[i][2]  # launch i+1 was enqueued before batch i's fetch opens
        assert recorder.inside(recorder.of("step_load")[0], launches[0])
        snap = pipeline_ledger().snapshot()
        assert set(snap["stages"]) == RECHECK_STAGES
        assert snap["waits"]["read_wait"]["ops"] == 3
        h2d = snap["stages"]["h2d"]
        assert h2d["ops"] == 4
        assert h2d["moved_bytes"] == 4 * BATCH * padded_len_for(PLEN)
        for stage in ("h2d", "launch", "digest"):
            assert snap["stages"][stage]["bytes"] == info.length, stage
        assert snap["stages"]["launch"]["moved_bytes"] == 0
        assert snap["stages"]["digest"]["ops"] == 4

    @pytest.mark.parametrize("entry", ["verify_batch", "digest_batch"])
    def test_batch_entry_points_on_a_mesh_take_the_same_stages(self, recorder, entry):
        """``verify_batch`` / ``digest_batch`` called directly on the
        eight-device mesh: the recheck's code, so the recheck's stages."""
        from torrent_tpu.models.verifier import TPUVerifier
        from torrent_tpu.ops.padding import digests_to_words, pad_pieces, words_to_digests

        v = TPUVerifier(piece_length=PLEN, batch_size=BATCH)
        assert v.mesh.size == 8 and not v._use_flat(np.zeros((BATCH, v.padded_len), np.uint8))
        pieces = [bytes([i + 1]) * PLEN for i in range(BATCH)]
        want = [hashlib.sha1(p).digest() for p in pieces]
        padded, nblocks = pad_pieces(pieces)
        if entry == "verify_batch":
            want[3] = bytes(20)
            ok = v.verify_batch(padded, nblocks, digests_to_words(want), nbytes=BATCH * PLEN)
            assert list(ok) == [i != 3 for i in range(BATCH)]
        else:
            assert words_to_digests(v.digest_batch(padded, nblocks, nbytes=BATCH * PLEN)) == want
        names = [s.removeprefix(TRACE_SPAN_PREFIX) for s in recorder.names(threading.get_ident())]
        assert names == ["batch", "h2d", "launch", "digest"]  # no step_load: no pass began
        stages = pipeline_ledger().snapshot()["stages"]
        assert set(stages) == {"h2d", "launch", "digest"}
        assert stages["h2d"]["moved_bytes"] == padded.nbytes and stages["h2d"]["bytes"] == BATCH * PLEN
        assert stages["launch"]["moved_bytes"] == 0 and stages["digest"]["moved_bytes"] == 0

    def test_the_multi_process_road_opens_no_h2d(self, recorder, monkeypatch):
        """A mesh spanning processes keeps its fused launch: the global
        arrays are assembled inside the dispatch, so ``launch`` carries
        the moved bytes and no ``h2d`` entry stands beside it. (One
        process plays the cluster: ``global_batch`` of all the rows is
        what a process holding every shard would build.)"""
        from torrent_tpu.models.verifier import TPUVerifier
        from torrent_tpu.ops.padding import digests_to_words, pad_pieces

        v = TPUVerifier(piece_length=PLEN, batch_size=BATCH)
        monkeypatch.setattr(v, "_mesh_processes", 2)
        assert not v.upload_supported(np.zeros((BATCH, v.padded_len), np.uint8))
        pieces = [bytes([i + 1]) * PLEN for i in range(BATCH)]
        padded, nblocks = pad_pieces(pieces)
        expected = digests_to_words([hashlib.sha1(p).digest() for p in pieces])
        assert v.verify_batch(padded, nblocks, expected, nbytes=BATCH * PLEN).all()
        names = [s.removeprefix(TRACE_SPAN_PREFIX) for s in recorder.names(threading.get_ident())]
        assert names == ["batch", "launch", "digest"]
        stages = pipeline_ledger().snapshot()["stages"]
        assert set(stages) == {"launch", "digest"}
        assert stages["launch"]["moved_bytes"] == padded.nbytes

    def test_batch_entry_points_open_each_interval_once(self, recorder, one_device):
        from torrent_tpu.models.verifier import TPUVerifier
        from torrent_tpu.ops.padding import pad_pieces

        v = TPUVerifier(piece_length=PLEN, batch_size=BATCH)
        pieces = [bytes([i]) * PLEN for i in range(BATCH)]
        padded, nblocks = pad_pieces(pieces)
        words = v.digest_batch(padded, nblocks, nbytes=BATCH * PLEN)
        from torrent_tpu.ops.padding import words_to_digests

        assert words_to_digests(words) == [hashlib.sha1(p).digest() for p in pieces]
        names = [s.removeprefix(TRACE_SPAN_PREFIX) for s in recorder.names(threading.get_ident())]
        assert names == ["batch", "h2d", "launch", "digest"]
        # upload_batch opens no stage of its own: the scheduler's planes do
        recorder.spans.clear()
        v.digest_uploaded(v.upload_batch(padded), nblocks).block_until_ready()
        assert recorder.names() == []

    @pytest.mark.parametrize("entry", ["verify_batch", "digest_batch"])
    def test_batch_entry_points_count_in_the_operators_capture(
        self, entry, one_device, monkeypatch, tmp_path
    ):
        """``TORRENT_TPU_PROFILE`` captures the first N batches of
        authoring, the library sweep and the mesh-path recheck too, not
        only the scheduler's launches."""
        import jax

        from torrent_tpu.models.verifier import TPUVerifier
        from torrent_tpu.obs import profiler
        from torrent_tpu.ops.padding import digests_to_words, pad_pieces

        calls = []
        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: calls.append(("start", d)))
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append(("stop",)))
        for name, value in (("_trace_started", False), ("_trace_done", False), ("_batches_seen", 0)):
            monkeypatch.setattr(profiler, name, value)
        monkeypatch.setenv("TORRENT_TPU_PROFILE", str(tmp_path))
        monkeypatch.setenv("TORRENT_TPU_PROFILE_BATCHES", "2")
        v = TPUVerifier(piece_length=PLEN, batch_size=BATCH)
        pieces = [bytes([i]) * PLEN for i in range(BATCH)]
        padded, nblocks = pad_pieces(pieces)
        rest = ()
        if entry == "verify_batch":
            rest = (digests_to_words([hashlib.sha1(p).digest() for p in pieces]),)
        getattr(v, entry)(padded, nblocks, *rest)
        assert calls == [("start", str(tmp_path))]
        for _ in range(2):
            getattr(v, entry)(padded, nblocks, *rest)
        assert calls == [("start", str(tmp_path)), ("stop",)]


class TestSchedulerRoad:
    def _flush(self, n_pieces: int, target: int = 8):
        from torrent_tpu.obs.hist import histograms
        from torrent_tpu.sched import HashPlaneScheduler, SchedulerConfig
        from torrent_tpu.sched.scheduler import _H_QUEUE_WAIT

        pieces = [bytes([i + 1]) * PLEN for i in range(n_pieces)]
        out: dict = {}

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=target, flush_deadline=0.05), hasher="tpu"
            )
            try:
                out["loop_thread"] = threading.get_ident()
                out["snap0"] = sched.metrics_snapshot()
                hist0 = histograms().family_snapshot(_H_QUEUE_WAIT[0]) or ([], 0, 0.0)
                # two requests a few ms apart, both under the target: one
                # deadline flush carries them
                first = asyncio.ensure_future(sched.submit("a", pieces[:1], piece_length=PLEN))
                await asyncio.sleep(0.01)
                rest = await sched.submit("b", pieces[1:], piece_length=PLEN)
                out["digests"] = (await first) + rest
                out["snap"] = sched.metrics_snapshot()
                hist1 = histograms().family_snapshot(_H_QUEUE_WAIT[0])
                out["hist"] = (hist1[1] - hist0[1], hist1[2] - hist0[2])
            finally:
                await sched.close()

        asyncio.run(go())
        assert out["digests"] == [hashlib.sha1(p).digest() for p in pieces]
        return out

    def test_a_deadline_flush_emits_every_stage_in_order(self, recorder, one_device):
        from torrent_tpu.ops.padding import padded_len_for

        out = self._flush(3)
        assert out["snap"]["flush_reasons"].get("deadline") == 1
        _no_metadata(recorder.names())
        loop = [s.removeprefix(TRACE_SPAN_PREFIX) for s in recorder.names(out["loop_thread"])]
        # the lane's coroutine: parked on its deadline (the second
        # request's enqueue wakes it once, and it parks again), then
        # assembly, the pipeline semaphore, and after the launch the demux
        flow = [s for s in loop if s != "lane_idle"]
        assert flow == ["deadline_wait", "deadline_wait", "assemble", "sem_wait", "verdict"], loop
        # held across its await, each wait comes out whole: one span of
        # the wait's own length, some 10 ms and then the deadline's rest
        first, second = recorder.of("deadline_wait")
        assert first[3] <= second[2]  # one lane, one coroutine: no overlap
        assert 0.005 < first[3] - first[2] < 0.05 and 0.02 < second[3] - second[2] < 0.2
        # the worker thread: the launch span is the parent of the stages
        (parent,) = [s for s in recorder.spans if re.fullmatch(r"sched_sha1_launch_b\d+", s[0])]
        worker = [s for s in recorder.spans if s[1] == parent[1] and s is not parent]
        assert [s[0].removeprefix(TRACE_SPAN_PREFIX) for s in worker] == ["stage", "h2d", "launch", "digest"]
        assert all(recorder.inside(s, parent) for s in worker)
        assert len(recorder.of("h2d")) == 1

        snap = pipeline_ledger().snapshot()
        assert set(snap["stages"]) == SCHED_STAGES
        assert set(snap["waits"]) <= SCHED_WAITS and {"deadline_wait", "sem_wait"} <= set(snap["waits"])
        h2d = snap["stages"]["h2d"]
        assert h2d["moved_bytes"] == 8 * padded_len_for(PLEN)  # the whole slab, whatever its fill
        assert h2d["bytes"] == 3 * PLEN
        assert snap["stages"]["assemble"]["bytes"] == 3 * PLEN
        # submit() says how late the loop woke it, after the fact: an op
        # a call in the wait table, and no span (many callers park here
        # at once: a span each would name every gap it covers)
        assert snap["waits"]["verdict_wake"]["ops"] == 2 and snap["waits"]["verdict_wake"]["active"] == 0
        assert 0 < snap["waits"]["verdict_wake"]["busy_s"] < 0.05
        assert not recorder.of("verdict_wake")
        # a parked lane is no busy stage: the attributor never sees it
        rep = attribute(snap)
        assert not set(rep["stages"]) & SCHED_WAITS
        assert rep["bottleneck"]["stage"] in SCHED_STAGES

    def test_queue_wait_sum_is_the_histograms_mean(self, one_device):
        out = self._flush(5)
        snap = out["snap"]
        count, total = out["hist"]
        # the snapshot surfaces the histograms' own sum and count (the
        # process's, so a reader takes deltas, as it does of the ledger)
        pieces = snap["queue_wait_pieces"] - out["snap0"]["queue_wait_pieces"]
        waited = snap["queue_wait_s_sum"] - out["snap0"]["queue_wait_s_sum"]
        assert pieces == count == 5
        assert waited == pytest.approx(total, rel=1e-9)
        mean = waited / pieces
        assert 0.03 < mean < 0.2  # four of five waited 40 ms of the deadline, one all 50
        # enqueue → verdict likewise, the e2e family's own sum and count:
        # the same five pieces, each at least its queue wait
        e2e_pieces = snap["e2e_pieces"] - out["snap0"]["e2e_pieces"]
        e2e = snap["e2e_s_sum"] - out["snap0"]["e2e_s_sum"]
        assert e2e_pieces == 5 and waited < e2e < waited + 5 * 0.5


class TestBridgeRoad:
    def test_a_request_writes_its_phases_at_its_reply_and_opens_no_span(self, recorder):
        """A buffered hash request: ``decode`` and ``reply`` are the loop
        thread's work, stages; head, body, wake and the whole are waits.
        All of them are recorded after the fact, so none opens a span for
        an idle gap of the device to take its name from (a ``track()``
        each for the two stages was measured and cost the live cell's
        median 3.5 %: PERF.md, PR 34)."""
        from torrent_tpu.bridge.service import BridgeServer
        from torrent_tpu.codec.bencode import bencode

        pieces = [bytes([i + 1]) * 1024 for i in range(2)]
        body = bencode({b"pieces": pieces, b"expected": [hashlib.sha1(p).digest() for p in pieces]})
        out: dict = {}

        async def go():
            server = await BridgeServer(port=0, hasher="cpu", flush_deadline_ms=5).start()
            try:
                reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
                writer.write(b"POST /v1/verify HTTP/1.1\r\nContent-Length: %d\r\n\r\n" % len(body) + body)
                await writer.drain()
                out["reply"] = await reader.read()
                writer.close()
                out["loop"] = threading.get_ident()
            finally:
                server.close()
                await server.wait_closed()

        asyncio.run(go())
        assert out["reply"].startswith(b"HTTP/1.1 200")
        _no_metadata(recorder.names())
        assert not any(recorder.of(name) for name in BRIDGE_WAITS | BRIDGE_STAGES)
        # the lane's own spans are there as before, on the loop's thread
        assert {s[1] for s in recorder.of("verdict")} == {out["loop"]}
        snap = pipeline_ledger().snapshot()
        assert BRIDGE_STAGES <= set(snap["stages"]) and BRIDGE_WAITS <= set(snap["waits"])
        assert all(snap["waits"][w]["ops"] == 1 and snap["waits"][w]["max_active"] == 0 for w in BRIDGE_WAITS)
        assert all(snap["stages"][s]["ops"] == 1 and snap["stages"][s]["max_active"] == 0 for s in BRIDGE_STAGES)
        # the two stages enter the attribution, the waits never do
        assert BRIDGE_STAGES <= set(attribute(snap)["stages"])
        assert not set(attribute(snap)["stages"]) & BRIDGE_WAITS


class TestFabricRoad:
    def test_a_solo_sweep_names_its_drain_and_its_ends(self, recorder, one_device, tmp_path):
        """``verify_library_fabric`` with no transport: the executor's
        park on its oldest launch is the wait ``unit_drain``, the plan
        and the bitfields' assembly the stage ``pass_setup``, each a host
        span too; the reads land in a staging slab and launch in place."""
        from torrent_tpu.parallel.bulk import verify_library_fabric
        from torrent_tpu.sched import HashPlaneScheduler, SchedulerConfig

        storage, info = _torrent(tmp_path, 11)
        out: dict = {}

        async def go():
            sched = await HashPlaneScheduler(
                SchedulerConfig(batch_target=BATCH, flush_deadline=0.01), hasher="tpu"
            ).start()
            try:
                out["res"] = await verify_library_fabric(
                    [(storage, info)], sched, nproc=1, pid=0, unit_bytes=BATCH * PLEN
                )
                out["snap"] = sched.metrics_snapshot()
            finally:
                await sched.close()

        asyncio.run(go())
        assert out["res"].bitfields[0].all() and out["res"].n_pieces == 11
        _no_metadata(recorder.names())
        snap = pipeline_ledger().snapshot()
        # two units (8 and 3 pieces), one chunk each: one drain a chunk
        assert snap["waits"]["unit_drain"]["ops"] == len(recorder.of("unit_drain")) == 2
        assert snap["waits"]["unit_drain"]["busy_s"] > 0 and "unit_drain" not in snap["stages"]
        assert snap["stages"]["pass_setup"]["ops"] == len(recorder.of("pass_setup")) == 2
        assert snap["stages"].get("stage", {"bytes": 0})["bytes"] == 0  # no copy into a slot
        # a full slab, then a ragged one launched whole: 16 rows for 11 live
        lane = out["snap"]["lane_stats"][f"sha1/{PLEN}"]
        assert (lane["staged_launches"], lane["staged_rows_total"], lane["staged_live_rows_total"]) == (2, 16, 11)
        assert lane["pad_rows_total"] == 0 and out["snap"]["staging"]["outstanding"] == 0
        # the ragged chunk is its unit's last and says so: no deadline is
        # sat out, and the wait nobody entered stands in the ledger at zero
        assert out["snap"]["flush_reasons"] == {"full": 1, "deadline": 0, "hint": 1, "shutdown": 0}
        assert snap["waits"]["deadline_wait"]["ops"] == 0 and snap["waits"]["deadline_wait"]["busy_s"] == 0.0
        assert not recorder.of("deadline_wait")
        # the drain is a wait: the attributor never names it
        assert "unit_drain" not in attribute(snap)["stages"]


class TestLedgerContract:
    def test_waits_stay_out_of_stages_overlap_and_wall(self):
        led = PipelineLedger()
        with led.track("lane_idle", wait=True):
            with led.track("read", 10):
                pass
            mid = led.snapshot()
        snap = led.snapshot()
        assert set(snap["stages"]) == {"read"} and set(snap["waits"]) == {"lane_idle"}
        assert mid["waits"]["lane_idle"]["active"] == 1
        assert snap["waits"]["lane_idle"]["ops"] == 1 and snap["waits"]["lane_idle"]["active"] == 0
        # a wait beside a stage is no overlap of two stages, and the
        # activity wall ends with the stage, not with the wait
        assert snap["overlap"]["max_concurrent_stages"] == 1 and snap["overlap"]["busy_s"] == 0.0
        assert snap["t_last"] == mid["t_last"]
        assert attribute(snap)["bottleneck"]["stage"] == "read"
        led.clear()
        assert led.snapshot()["waits"] == {}

    def test_a_wait_recorded_after_the_fact_touches_the_wait_table_alone(self):
        led = PipelineLedger()
        with led.track("read", 10):
            pass
        mid = led.snapshot()
        led.record("http_body", 4096, 0.25, wait=True)
        led.record("http_body", 4096, 0.5, wait=True)
        led.record("http_request", 0, -1.0, wait=True)  # a clock that stepped back counts an op and no time
        snap = led.snapshot()
        assert snap["waits"]["http_body"] == {
            "busy_s": 0.75, "bytes": 8192, "moved_bytes": 0, "ops": 2, "active": 0, "max_active": 0
        }
        assert snap["waits"]["http_request"]["busy_s"] == 0.0 and snap["waits"]["http_request"]["ops"] == 1
        # neither the stage table, nor the overlap, nor the activity wall
        assert snap["stages"] == mid["stages"] and snap["overlap"] == mid["overlap"]
        assert (snap["t_first"], snap["t_last"]) == (mid["t_first"], mid["t_last"])
        assert attribute(snap)["bottleneck"]["stage"] == "read"
        # without the keyword it is a stage, as before, and moves the wall
        led.record("decode", 7, 0.125)
        after = led.snapshot()
        assert after["stages"]["decode"]["ops"] == 1 and "decode" not in after["waits"]
        assert after["t_last"] > mid["t_last"]

    def test_many_entries_go_in_under_one_lock_and_read_like_single_ones(self):
        one, many = PipelineLedger(), PipelineLedger()
        entries = [
            ("http_head", 0, 0.5, True), ("http_body", 4096, 0.25, True),
            ("decode", 4100, 0.125, False), ("reply", 4096, 0.0625, False), ("http_request", 0, 1.0, True),
        ]
        for e in entries:
            one.record(*e)
        takes = []
        lock = many._lock

        class Counting:
            def __enter__(self):
                takes.append(1)
                return lock.__enter__()

            def __exit__(self, *exc):
                return lock.__exit__(*exc)

        many._lock = Counting()
        many.record_many(entries)
        many._lock = lock
        assert len(takes) == 1
        a, b = one.snapshot(), many.snapshot()
        assert a["stages"] == b["stages"] and a["waits"] == b["waits"]
        assert set(b["stages"]) == {"decode", "reply"} and b["stages"]["decode"]["max_active"] == 0
        assert b["t_first"] is not None and b["overlap"]["busy_s"] == 0.0

    def test_a_declared_wait_reads_zero_until_it_is_entered(self):
        led = PipelineLedger()
        led.declare_wait("deadline_wait")
        zero = led.snapshot()
        assert zero["waits"] == {"deadline_wait": {k: 0 for k in zero["waits"]["deadline_wait"]}}
        assert zero["stages"] == {} and zero["t_first"] is None
        with led.track("deadline_wait", wait=True):
            pass
        led.declare_wait("deadline_wait")  # again: what was counted stays
        assert led.snapshot()["waits"]["deadline_wait"]["ops"] == 1

    def test_new_stages_render_lintable_and_fold_nothing(self):
        led = PipelineLedger()
        # one process can hold them all: a bridge that also runs a
        # fabric job and a v2 stream (``merkle``, tests/test_v2_stage_spans.py)
        new = sorted((RECHECK_STAGES | SCHED_STAGES | BRIDGE_STAGES | {"merkle"}) - set(PIPELINE_STAGES))
        assert new == ["assemble", "decode", "merkle", "pad", "pass_setup", "reply"]
        assert len(PIPELINE_STAGES) + len(new) == 14 <= MAX_STAGES
        for stage in new + list(PIPELINE_STAGES):
            with led.track(stage, 64, moved=128):
                pass
        waits = sorted(SCHED_WAITS | BRIDGE_WAITS | {"read_wait", "unit_drain"})
        assert len(waits) <= MAX_STAGES  # the wait table folds past the same bound
        for wait in waits:
            with led.track(wait, wait=True):
                pass
        snap = led.snapshot()
        assert "other" not in snap["stages"] and "other" not in snap["waits"]
        assert set(snap["stages"]) == set(new) | set(PIPELINE_STAGES)
        text = render_pipeline_metrics(led)
        prom_lint(text)
        for stage in new:
            assert f'torrent_tpu_pipeline_stage_busy_seconds_total{{stage="{stage}"}}' in text
        assert "deadline_wait" not in text and "read_wait" not in text and "unit_drain" not in text
        assert not any(w in text for w in BRIDGE_WAITS)

    def test_track_without_jax_imports_nothing(self):
        """``import torrent_tpu`` itself pulls JAX in today (``parallel/
        mesh.py``), so the process drops it again before it tracks: the
        helper looks JAX up in ``sys.modules`` and never imports it."""
        code = (
            "import sys\n"
            "from torrent_tpu.obs.ledger import pipeline_ledger\n"
            "from torrent_tpu.obs.profiler import annotate\n"
            "for name in [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]:\n"
            "    del sys.modules[name]\n"
            "with pipeline_ledger().track('read', 5, moved=8):\n"
            "    with annotate('first_load'):\n"
            "        pass\n"
            "with pipeline_ledger().track('lane_idle', wait=True):\n"
            "    pass\n"
            "snap = pipeline_ledger().snapshot()\n"
            "assert snap['stages']['read']['moved_bytes'] == 8, snap\n"
            "assert snap['waits']['lane_idle']['ops'] == 1, snap\n"
            "assert 'jax' not in sys.modules, 'track() imported jax'\n"
        )
        done = subprocess.run(
            [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True, timeout=120
        )
        assert done.returncode == 0, done.stderr

    def test_annotate_is_a_span_and_no_stage(self, recorder):
        with annotate("first_load"):
            pass
        assert recorder.names() == [TRACE_SPAN_PREFIX + "first_load"]
        snap = pipeline_ledger().snapshot()
        assert snap["stages"] == {} and snap["waits"] == {}


class TestStepModuleNames:
    """``hash_step_*`` find the step's device time by XLA module name."""

    def test_the_jitted_steps_lower_to_the_pinned_names(self):
        import jax

        from torrent_tpu.models.verifier import STEP_MODULE_NAMES, TPUVerifier
        from torrent_tpu.parallel.mesh import make_mesh

        v = TPUVerifier(piece_length=PLEN, batch_size=BATCH, mesh=make_mesh(jax.devices()[:1]))
        u8 = jax.ShapeDtypeStruct((BATCH, v.padded_len), np.uint8)
        u32 = [jax.ShapeDtypeStruct((BATCH // 4, v.padded_len // 4), np.uint32)] * 4
        nb = jax.ShapeDtypeStruct((BATCH,), np.int32)
        exp = jax.ShapeDtypeStruct((BATCH, 5), np.uint32)
        lowered = {
            v._verify_step_flat.lower(u32, nb, exp),
            v._digest_step_flat.lower(u32, nb),
            v._verify_step.lower(u8, nb, exp),
            v._digest_step.lower(u8, nb),
            v._digest_step_donated.lower(u8, nb),
        }
        names = {re.search(r"module @(\S+)", lo.as_text()).group(1) for lo in lowered}
        assert names == STEP_MODULE_NAMES

    @pytest.mark.parametrize(
        "path", sorted(glob.glob(os.path.join(ROOT, "benchmark", "configs", "*.json"))), ids=os.path.basename
    )
    def test_every_configuration_names_a_step_the_program_has(self, path):
        """Each configuration is held to the steps of its ``algo``: the
        SHA-1 verifier's, or the v2 leaf plane's."""
        from torrent_tpu.models.v2 import LEAF_STEP_MODULE_NAMES
        from torrent_tpu.models.verifier import STEP_MODULE_NAMES

        with open(path) as f:
            config = json.load(f)
        pinned = {"sha1": STEP_MODULE_NAMES, "sha256": LEAF_STEP_MODULE_NAMES}[config["algo"]]
        modules = config["step_modules"]
        assert modules and set(modules) <= pinned, (path, modules)
