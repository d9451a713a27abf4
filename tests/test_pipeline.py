"""Pipeline ledger, bottleneck attribution, and the bench harness.

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
  it as the limiting stage (deterministic, CPU-only); ``torrent-tpu
  bench --smoke`` emits banked-schema JSON with the ledger breakdown
  embedded; ``bench --compare`` exits non-zero on a synthetically
  injected regression vs a fixture record
* ``torrent-tpu top`` frame rendering and the trajectory aggregator
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import os
import subprocess
import sys
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
            "busy_s": 0.5, "bytes": 50, "ops": 1, "active": 0, "max_active": 0,
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


class TestBenchHarness:
    """torrent-tpu bench: banked-schema records with the ledger
    breakdown embedded, self-banking, and the trajectory comparator."""

    def _smoke_record(self, tmp_path, extra=()):
        from torrent_tpu.tools import bench_cli

        out = str(tmp_path / "record.json")
        rc = bench_cli.main(
            ["--smoke", "--mb", "1", "--piece-kb", "64", "--out", out,
             *extra]
        )
        with open(out) as f:
            return rc, json.load(f)

    def test_smoke_emits_banked_schema_with_ledger(self, tmp_path, capsys):
        rc, rec = self._smoke_record(tmp_path)
        assert rc == 0
        assert rec["schema"] == "torrent-tpu-bench/1"
        assert rec["rung"] == "smoke"
        assert rec["value"] is not None and rec["unit"] == "pieces/s"
        assert rec["valid"] == rec["pieces"]
        # the per-stage ledger breakdown is embedded in the record
        assert rec["ledger"]["bottleneck"] is not None
        for stage in ("read", "launch", "verdict"):
            assert stage in rec["ledger"]["stages"]
        # stdout carries exactly the record as one JSON line
        line = capsys.readouterr().out.strip().splitlines()[-1]
        assert json.loads(line)["metric"] == rec["metric"]

    def test_compare_regression_exits_nonzero(self, tmp_path):
        from torrent_tpu.tools import bench_cli

        banked = {
            "metric": "sha1_recheck_smoke_64KiB_pieces_per_sec",
            "value": 1000.0, "unit": "pieces/s", "platform": "cpu",
            "batch": 32,
        }
        traj = str(tmp_path / "traj.json")
        with open(traj, "w") as f:
            json.dump({"records": [banked]}, f)
        # synthetically injected regression: 40% below the banked best
        cand = dict(banked, value=600.0)
        cand_path = str(tmp_path / "cand.json")
        with open(cand_path, "w") as f:
            json.dump(cand, f)
        rc = bench_cli.main(
            ["--record", cand_path, "--compare", "--trajectory", traj]
        )
        assert rc == 1
        # within tolerance → ok
        with open(cand_path, "w") as f:
            json.dump(dict(banked, value=950.0), f)
        assert bench_cli.main(
            ["--record", cand_path, "--compare", "--trajectory", traj]
        ) == 0
        # report-only never fails
        with open(cand_path, "w") as f:
            json.dump(cand, f)
        assert bench_cli.main(
            ["--record", cand_path, "--compare", "--trajectory", traj,
             "--report-only"]
        ) == 0

    def test_compare_unarmed_without_like_for_like(self, tmp_path, capsys):
        from torrent_tpu.tools import bench_cli

        traj = str(tmp_path / "traj.json")
        with open(traj, "w") as f:
            # same metric but a different batch shape AND a caveated
            # record: neither arms the gate
            json.dump({"records": [
                {"metric": "m", "value": 100.0, "platform": "cpu",
                 "batch": 512},
                {"metric": "m", "value": 100.0, "platform": "cpu",
                 "batch": 32, "non_like_for_like": True},
            ]}, f)
        cand_path = str(tmp_path / "cand.json")
        with open(cand_path, "w") as f:
            json.dump({"metric": "m", "value": 1.0, "platform": "cpu",
                       "batch": 32}, f)
        rc = bench_cli.main(
            ["--record", cand_path, "--compare", "--trajectory", traj]
        )
        assert rc == 0
        assert "unarmed" in capsys.readouterr().err

    def test_bank_then_compare_gates(self, tmp_path):
        """The self-banking loop: a banked smoke record arms the gate
        for the next run of the same shape."""
        from torrent_tpu.tools import bench_cli

        traj = str(tmp_path / "traj.json")
        rc, rec = self._smoke_record(
            tmp_path, extra=["--bank", "--trajectory", traj]
        )
        assert rc == 0
        records = bench_cli.load_trajectory(traj)
        assert len(records) == 1 and records[0]["metric"] == rec["metric"]
        # a regressed candidate of the same shape now fails the gate
        cand = dict(records[0], value=records[0]["value"] * 0.1)
        code, msg = bench_cli.compare_record(cand, records)
        assert code == 1 and "REGRESSION" in msg
        # and the genuine record passes against itself
        code, msg = bench_cli.compare_record(records[0], records)
        assert code == 0

    def test_null_value_record_fails(self, tmp_path):
        from torrent_tpu.tools import bench_cli

        cand_path = str(tmp_path / "cand.json")
        with open(cand_path, "w") as f:
            json.dump({"metric": "m", "value": None}, f)
        assert bench_cli.main(["--record", cand_path]) == 1

    def test_usage_errors(self):
        from torrent_tpu.tools import bench_cli

        assert bench_cli.main([]) == 2  # no rung, no record


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


class TestTrajectoryAggregation:
    @staticmethod
    def _bank(tmp_path):
        """A record bank under tmp_path with the aggregator beside it
        (summarize.py's bank is the directory it sits in): two stable
        live records — one carrying a shape caveat —, a timestamped
        audit copy, a loose rung artifact and a null that must be
        filtered."""
        import shutil

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        bank = tmp_path / "bank"
        (bank / "live").mkdir(parents=True)
        script = bank / "summarize.py"
        shutil.copy(os.path.join(repo, ".bench", "summarize.py"), script)
        metric = "sha1_recheck_256KiB_pieces_per_sec"
        wide = {"metric": metric, "value": 137804.6, "unit": "pieces/s",
                "vs_baseline": 24.11, "platform": "tpu", "batch": 8192,
                "banked_at_utc": "2026-07-30T07:10:51Z"}
        narrow = {**wide, "value": 246511.0, "batch": 512,
                  "banked_at_utc": "2026-08-02T15:50:39Z",
                  "like_for_like": "B=512 x 24 dispatches; not the B=8192 shape"}
        (bank / "live" / f"{metric}.json").write_text(json.dumps(wide))
        (bank / "live" / f"{metric}.20260802T155039Z.json").write_text(
            json.dumps(narrow))
        (bank / "cfg_author.json").write_text(json.dumps(
            {"metric": "sha1_author_256KiB_pieces_per_sec", "value": 133480.8,
             "unit": "pieces/s", "platform": "tpu"}))
        (bank / "null.json").write_text(json.dumps(
            {"metric": metric, "value": None, "unit": "pieces/s"}))
        return str(script)

    def test_summarize_trajectory_marks_shape_caveats(self, tmp_path):
        """summarize.py --trajectory aggregates a bank into one
        machine-readable file, preserving like-for-like caveats."""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = str(tmp_path / "traj.json")
        proc = subprocess.run(
            [sys.executable, self._bank(tmp_path), "--trajectory", out],
            capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == 0, proc.stderr
        with open(out) as f:
            data = json.load(f)
        assert data["schema"] == "torrent-tpu-bench-trajectory/1"
        recs = data["records"]
        assert len(recs) == 3, recs  # the null record is filtered
        assert all(r["value"] is not None for r in recs)
        # the B=512 narrow-batch record carries its shape caveat
        caveated = [r for r in recs if r["non_like_for_like"]]
        assert [(r["metric"], r["batch"]) for r in caveated] == [
            ("sha1_recheck_256KiB_pieces_per_sec", 512)
        ], recs
        # the committed trajectory matches the aggregator's schema
        committed = os.path.join(repo, "BENCH_trajectory.json")
        with open(committed) as f:
            assert json.load(f)["schema"] == data["schema"]

    def test_regeneration_preserves_self_banked_records(self, tmp_path):
        """`bench --bank` records exist only in the trajectory file;
        regenerating it from a bank must merge them back or the CI
        comparator they armed is silently disarmed."""
        from torrent_tpu.tools import bench_cli

        out = str(tmp_path / "traj.json")
        banked = {"metric": "sha1_recheck_smoke_256KiB_pieces_per_sec",
                  "value": 3000.0, "unit": "pieces/s", "platform": "cpu",
                  "batch": 32, "rung": "smoke",
                  "schema": "torrent-tpu-bench/1"}
        bench_cli.bank_record(banked, out)
        proc = subprocess.run(
            [sys.executable, self._bank(tmp_path), "--trajectory", out],
            capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == 0, proc.stderr
        records = bench_cli.load_trajectory(out)
        kept = [r for r in records if r["metric"] == banked["metric"]]
        assert kept and kept[0]["value"] == 3000.0, records
        # and the bank's aggregated records are present alongside it
        assert any(r.get("artifact") for r in records)
