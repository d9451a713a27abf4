"""Pipeline ledger and bottleneck attribution.

Covers the PR-7 observability plane end to end:

* ``obs/ledger.py`` accounting (track/record, byte accumulation,
  occupancy, cardinality bound, snapshot/clear)
* ``obs/attrib.py`` attribution (idle, limiting stage, achieved vs
  demanded, interval deltas)
* scheduler instrumentation: a CPU-plane run records read/launch/verdict;
  a device-plane run records stage/h2d/launch/digest too
* the ISSUE acceptance scenarios: with ``sched/faults.py`` latency
  injection throttling the H2D stage, a ``verify_library_sched`` run's
  ledger attributes the majority of pipeline wall time to ``h2d`` and
  both ``doctor --bottleneck`` machinery and ``GET /v1/pipeline`` name
  it as the limiting stage (deterministic, CPU-only)
* ``torrent-tpu top`` frame rendering
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import os
import time

import numpy as np
import pytest

from torrent_tpu.obs.attrib import attribute, format_report
from torrent_tpu.obs.ledger import (
    PIPELINE_STAGES,
    PipelineLedger,
    pipeline_ledger,
    render_pipeline_metrics,
)

from test_metrics import prom_lint


def run(coro):
    return asyncio.run(coro)


def _mk_torrent(tmp_path, n_pieces=32, plen=16384, seed=11):
    """Synthetic single-file v1 torrent on disk + its FsStorage."""
    from torrent_tpu.codec.metainfo import parse_metainfo
    from torrent_tpu.storage.storage import FsStorage, Storage
    from torrent_tpu.tools.make_torrent import make_torrent

    payload = os.path.join(str(tmp_path), "data.bin")
    rng = np.random.default_rng(seed)
    with open(payload, "wb") as f:
        f.write(rng.integers(0, 256, n_pieces * plen, dtype=np.uint8).tobytes())
    meta = parse_metainfo(
        make_torrent(payload, "http://t.invalid/announce", piece_length=plen)
    )
    return Storage(FsStorage(str(tmp_path)), meta.info), meta.info


class TestLedger:
    def test_track_and_record_accounting(self):
        led = PipelineLedger()
        with led.track("read", 100):
            time.sleep(0.002)
        led.record("launch", 50, 0.5)
        snap = led.snapshot()
        assert snap["stages"]["read"]["bytes"] == 100
        assert snap["stages"]["read"]["ops"] == 1
        assert snap["stages"]["read"]["busy_s"] > 0.001
        assert snap["stages"]["read"]["active"] == 0
        assert snap["stages"]["read"]["max_active"] == 1
        assert snap["stages"]["launch"] == {
            "busy_s": 0.5, "bytes": 50, "moved_bytes": 0, "ops": 1,
            "active": 0, "max_active": 0,
        }
        assert snap["t_last"] >= snap["t_first"]

    def test_tracked_byte_accumulation(self):
        led = PipelineLedger()
        with led.track("read") as t:
            t.add(10)
            t.add(20)
        assert led.snapshot()["stages"]["read"]["bytes"] == 30

    def test_occupancy_counts_overlap(self):
        led = PipelineLedger()
        a = led.track("h2d", 1)
        b = led.track("h2d", 1)
        a.__enter__()
        b.__enter__()
        assert led.snapshot()["stages"]["h2d"]["active"] == 2
        b.__exit__(None, None, None)
        a.__exit__(None, None, None)
        s = led.snapshot()["stages"]["h2d"]
        assert s["active"] == 0 and s["max_active"] == 2 and s["ops"] == 2

    def test_unknown_stage_cardinality_bound(self):
        led = PipelineLedger()
        for i in range(40):
            led.record(f"weird{i}", 1, 0.0)
        snap = led.snapshot()
        # canonical stages always fit; extras fold into "other"
        assert len(snap["stages"]) <= 17
        assert "other" in snap["stages"]

    def test_exception_in_tracked_body_still_records(self):
        led = PipelineLedger()
        with pytest.raises(ValueError):
            with led.track("stage", 5):
                raise ValueError("boom")
        s = led.snapshot()["stages"]["stage"]
        assert s["ops"] == 1 and s["active"] == 0

    def test_clear(self):
        led = PipelineLedger()
        led.record("read", 1, 0.1)
        led.clear()
        snap = led.snapshot()
        assert snap["stages"] == {} and snap["t_first"] is None


class TestAttrib:
    def test_idle_snapshot(self):
        rep = attribute(PipelineLedger().snapshot())
        assert rep["bottleneck"] is None
        assert "idle" in format_report(rep)

    def test_limiting_stage_and_demanded_rate(self):
        led = PipelineLedger()
        # h2d: 0.8s busy for 8 MiB (10 MiB/s); read: 0.1s for 100 MiB
        led.record("read", 100 << 20, 0.1)
        led.record("h2d", 8 << 20, 0.8)
        led.record("verdict", 8 << 20, 0.01)
        rep = attribute(led.snapshot())
        bn = rep["bottleneck"]
        assert bn["stage"] == "h2d"
        assert bn["achieved_bps"] == pytest.approx(10 * (1 << 20), rel=0.01)
        # demanded = the fastest other stage (read at 1000 MiB/s)
        assert bn["demanded_bps"] == pytest.approx(1000 * (1 << 20), rel=0.01)
        assert bn["headroom"] == pytest.approx(100, rel=0.05)
        assert rep["pipeline_bytes"] == 8 << 20
        assert "h2d limits the pipeline" in format_report(rep)

    def test_interval_delta(self):
        led = PipelineLedger()
        led.record("read", 100, 1.0)
        prev = led.snapshot()
        led.record("h2d", 100, 2.0)
        rep = attribute(led.snapshot(), prev=prev)
        assert rep["stages"]["read"]["busy_s"] == 0.0
        assert rep["stages"]["h2d"]["busy_s"] == 2.0
        assert rep["bottleneck"]["stage"] == "h2d"

    def test_delta_anchors_at_snapshot_not_last_activity(self):
        """Idle time between a previous run and the prev snapshot must
        not dilute the next interval's utilization: the wall anchors at
        prev's t_snap (when it was taken), not its t_last (when the
        previous activity ended)."""
        prev = {
            "stages": {"read": {"busy_s": 0.1, "bytes": 10, "ops": 1}},
            "t_first": 90.0, "t_last": 100.0, "t_snap": 200.0,
        }
        cur = {
            "stages": {
                "read": {"busy_s": 0.1, "bytes": 10, "ops": 1},
                "h2d": {"busy_s": 0.9, "bytes": 10, "ops": 1},
            },
            "t_first": 90.0, "t_last": 201.0, "t_snap": 201.0,
        }
        rep = attribute(cur, prev=prev)
        # wall = 201 - 200 (snapshot anchor), NOT 201 - 100
        assert rep["wall_s"] == pytest.approx(1.0)
        assert rep["bottleneck"]["stage"] == "h2d"
        assert rep["bottleneck"]["utilization"] == pytest.approx(0.9)

    def test_stage_order_constant(self):
        assert PIPELINE_STAGES == ("recv", "read", "stage", "h2d", "launch",
                                   "digest", "verdict", "egress")


class TestRenderer:
    def test_fresh_ledger_renders_clean(self):
        text = render_pipeline_metrics(PipelineLedger())
        prom_lint(text)
        assert "torrent_tpu_pipeline_wall_seconds 0" in text

    def test_active_ledger_renders_and_lints(self):
        led = PipelineLedger()
        led.record("read", 1024, 0.1)
        led.record("h2d", 1024, 0.9)
        text = render_pipeline_metrics(led)
        prom_lint(text)
        assert 'torrent_tpu_pipeline_stage_bytes_total{stage="read"} 1024' in text
        assert 'torrent_tpu_pipeline_bottleneck{stage="h2d"} 1' in text
        assert 'torrent_tpu_pipeline_bottleneck{stage="read"} 0' in text


class TestSchedulerInstrumentation:
    def test_cpu_plane_records_read_launch_verdict(self, tmp_path):
        from torrent_tpu.parallel.verify import verify_pieces_sched
        from torrent_tpu.sched import HashPlaneScheduler, SchedulerConfig

        async def go():
            storage, info = _mk_torrent(tmp_path, n_pieces=8)
            led = pipeline_ledger()
            prev = led.snapshot()
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=8, flush_deadline=0.02),
                hasher="cpu",
            )
            await sched.start()
            try:
                bf = await verify_pieces_sched(storage, info, sched)
            finally:
                await sched.close()
            assert bf.all()
            rep = attribute(led.snapshot(), prev=prev)
            for stage in ("read", "launch", "verdict"):
                assert rep["stages"].get(stage, {}).get("ops", 0) >= 1, (
                    stage, rep["stages"])
            assert rep["stages"]["read"]["bytes"] == info.length
            assert rep["stages"]["verdict"]["bytes"] == info.length

        run(go())

    def test_device_plane_records_stage_h2d_launch_digest(self):
        """The sha256 scan plane (XLA on CPU) reports the full stage
        split: staging copy, explicit device put, dispatch, D2H."""
        from torrent_tpu.sched import HashPlaneScheduler, SchedulerConfig

        async def go():
            led = pipeline_ledger()
            prev = led.snapshot()
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=8, flush_deadline=0.05, sha256_backend="scan"
                ),
                hasher="tpu",
            )
            await sched.start()
            try:
                pieces = [bytes([i + 1]) * 2048 for i in range(8)]
                got = await sched.submit(
                    "t", pieces, algo="sha256", piece_length=2048
                )
                assert got == [hashlib.sha256(p).digest() for p in pieces]
            finally:
                await sched.close()
            rep = attribute(led.snapshot(), prev=prev)
            for stage in ("stage", "h2d", "launch", "digest", "verdict"):
                assert rep["stages"].get(stage, {}).get("ops", 0) >= 1, (
                    stage, rep["stages"])

        run(go())


class TestBottleneckAcceptance:
    """ISSUE acceptance: latency-injected H2D throttling must be named
    by the attributor, by doctor --bottleneck, and by GET /v1/pipeline.
    Deterministic and CPU-only throughout."""

    def test_throttled_library_sched_names_h2d_majority(self, tmp_path):
        from torrent_tpu.parallel.bulk import verify_library_sched
        from torrent_tpu.sched import (
            FaultPlan,
            HashPlaneScheduler,
            SchedulerConfig,
        )

        async def go():
            storage, info = _mk_torrent(tmp_path, n_pieces=48)
            led = pipeline_ledger()
            prev = led.snapshot()
            plan = FaultPlan(latency_s=0.03)
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=16,
                    flush_deadline=0.02,
                    plane_factory=plan.plane_factory(hasher="cpu"),
                ),
                hasher="cpu",
            )
            await sched.start()
            try:
                res = await verify_library_sched(
                    [(storage, info)], sched, tenant="t"
                )
            finally:
                await sched.close()
            assert int(res.bitfields[0].sum()) == info.num_pieces
            rep = attribute(led.snapshot(), prev=prev)
            bn = rep["bottleneck"]
            assert bn["stage"] == "h2d", rep
            # the throttled stage owns the MAJORITY of pipeline wall time
            assert bn["utilization"] > 0.5, bn
            assert bn["utilization"] > max(
                st["utilization"]
                for name, st in rep["stages"].items()
                if name != "h2d"
            )
            # achieved ≪ demanded: the gap is the headroom the zero-copy
            # ingest refactor would unlock
            assert bn["demanded_bps"] > bn["achieved_bps"]

        run(go())

    def test_doctor_bottleneck_smoke_names_h2d(self, tmp_path):
        from torrent_tpu.tools.doctor import _bottleneck_smoke

        detail = run(_bottleneck_smoke(True, str(tmp_path)))
        assert "h2d limits the pipeline" in detail

    def test_bridge_pipeline_route_names_h2d(self):
        from torrent_tpu.bridge.service import BridgeServer

        async def go():
            pipeline_ledger().clear()
            svc = await BridgeServer(
                "127.0.0.1", port=0, hasher="cpu",
                fault_plan="latency_ms=25", batch_target=8,
            ).start()
            try:
                from torrent_tpu.codec.bencode import bencode

                pieces = [bytes([i]) * 1024 for i in range(16)]
                body = bencode({b"pieces": pieces})
                status, _, _ = await _http(
                    svc.port, "POST", "/v1/digests", body
                )
                assert status == 200
                status, resp, ctype = await _http(
                    svc.port, "GET", "/v1/pipeline", b""
                )
                assert status == 200
                assert ctype.startswith("application/json")
                payload = json.loads(resp)
                bn = payload["attribution"]["bottleneck"]
                assert bn["stage"] == "h2d", payload["attribution"]
                assert payload["sched"]["launches"] >= 1
                assert "h2d" in payload["snapshot"]["stages"]
                # /metrics carries the same ledger as Prometheus series
                status, resp, ctype = await _http(
                    svc.port, "GET", "/metrics", b""
                )
                assert status == 200
                text = resp.decode()
                assert 'torrent_tpu_pipeline_bottleneck{stage="h2d"} 1' in text
                prom_lint(text)
            finally:
                svc.close()
                await svc.wait_closed()

        run(go())


async def _http(port: int, method: str, path: str, body: bytes):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(
        (
            f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
            f"Content-Length: {len(body)}\r\n\r\n"
        ).encode()
        + body
    )
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    clen, ctype = 0, ""
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b""):
            break
        if line.lower().startswith(b"content-length:"):
            clen = int(line.split(b":", 1)[1])
        if line.lower().startswith(b"content-type:"):
            ctype = line.split(b":", 1)[1].strip().decode()
    resp = await reader.readexactly(clen)
    writer.close()
    return status, resp, ctype


class TestTopRendering:
    def test_render_frame(self):
        payload = {
            "attribution": {
                "wall_s": 10.0,
                "pipeline_bps": 3 << 20,
                "pipeline_bytes": 30 << 20,
                "stages": {
                    "read": {"utilization": 0.2, "busy_s": 2.0,
                             "bytes": 30 << 20, "ops": 3,
                             "achieved_bps": 15 << 20, "active": 0,
                             "max_active": 1},
                    "h2d": {"utilization": 1.4, "busy_s": 14.0,
                            "bytes": 30 << 20, "ops": 3,
                            "achieved_bps": 2 << 20, "active": 1,
                            "max_active": 2},
                },
                "bottleneck": {"stage": "h2d", "utilization": 1.4,
                               "achieved_bps": 2 << 20,
                               "demanded_bps": 15 << 20, "headroom": 7.5},
            },
            "snapshot": {},
            "sched": {"queue_pieces": 5, "queue_bytes": 1 << 20,
                      "launches": 9, "mean_fill": 0.75, "lanes": 2},
        }
        from torrent_tpu.tools.top import render_top

        frame = render_top(payload, url="http://x:1")
        assert "bottleneck: h2d" in frame
        assert "2.0 MiB/s achieved vs 15.0 MiB/s demanded" in frame
        assert "read" in frame and "140%" in frame
        assert "5 queued pieces" in frame
        # bars never overflow their fixed width
        for line in frame.splitlines():
            if "|" in line:
                assert len(line.split("|")[1]) == 26

    def test_render_idle(self):
        from torrent_tpu.tools.top import render_top

        frame = render_top({"attribution": {"wall_s": 0.0, "stages": {}}})
        assert "idle" in frame
