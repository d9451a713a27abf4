"""Timeline & SLO plane (obs/timeline + obs/slo): the history tier,
error-budget burn rates, health/readiness, and retrospective replay.

The flagship scenario is the ISSUE acceptance path: a fault-injected
scheduler burst drives /v1/slo into a fast-burn breach, fires exactly
one slo_breach flight-recorder dump, flips /v1/health readiness, and
recovers after the breaker half-open probe — while a run with no
objectives configured constructs none of it; `torrent-tpu replay` on a
dumped timeline names the same limiting stage the live attributor
reported.
"""

import asyncio
import hashlib
import json
import os

import pytest

from torrent_tpu.obs.slo import (
    FAST_BURN,
    SloEngine,
    SloObjective,
    build_health,
    default_objectives,
    digest_summary,
    evaluate_slo,
    parse_objectives,
)
from torrent_tpu.obs.timeline import (
    Timeline,
    TimelineSampler,
    build_sample,
    replay_report,
)


def run(coro, timeout=90):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def mk_sample(t, pieces=0, shed=0, failed=0, breaker_opens=0, races=0,
              h2d_busy=0.0, h2d_bytes=0, verdict_bytes=0, verdict_ops=0,
              hist=None):
    """Synthetic cumulative-counter sample, bypassing build_sample."""
    stages = {}
    if h2d_busy:
        stages["h2d"] = {"busy_s": h2d_busy, "bytes": h2d_bytes,
                         "ops": max(1, int(h2d_busy * 10))}
    if verdict_ops:
        stages["verdict"] = {"busy_s": 0.01 * verdict_ops,
                             "bytes": verdict_bytes, "ops": verdict_ops}
    return {
        "v": 1,
        "t": float(t),
        "stages": stages,
        "overlap_s": 0.0,
        "sched": {"pieces": pieces, "shed": shed, "failed_pieces": failed},
        "hist": hist or {},
        "integrity": {"breaker_opens": breaker_opens, "open_lanes": 0,
                      "races": races, "distrust": 0},
    }


# ------------------------------------------------------------------- ring


class TestTimelineRing:
    def test_push_bound_and_drop_counter(self):
        tl = Timeline(depth=4)
        for i in range(7):
            tl.push(mk_sample(i))
        snap = tl.snapshot()
        assert snap["seq"] == 7
        assert snap["drops"] == 3
        assert len(snap["samples"]) == 4
        # oldest fell off; seq stamps survive
        assert [s["seq"] for s in snap["samples"]] == [4, 5, 6, 7]
        assert snap["depth"] == 4

    def test_clear_resets(self):
        tl = Timeline(depth=4)
        tl.push(mk_sample(1))
        tl.clear()
        snap = tl.snapshot()
        assert snap["seq"] == 0 and not snap["samples"] and snap["drops"] == 0


class TestBuildSample:
    def test_deterministic_and_compact(self):
        led = {"stages": {"read": {"busy_s": 1.0, "bytes": 10, "ops": 2},
                          "idle": {"busy_s": 0.0, "bytes": 0, "ops": 0}},
               "overlap": {"busy_s": 0.5}}
        sched = {
            "tenants": {"b": {"served_pieces": 3}, "a": {"served_pieces": 7}},
            "shed_total": 2,
            "failed_pieces": 1,
            "admission_factor": 0.5,
            "breakers": {
                "sha1/1": {"state": "open",
                           "transitions": {"closed->open": 2,
                                           "open->half_open": 1}},
            },
        }
        s1 = build_sample(12.5, led, sched_snap=sched)
        s2 = build_sample(12.5, led, sched_snap=sched)
        assert s1 == s2
        assert json.dumps(s1, sort_keys=True) == json.dumps(s2, sort_keys=True)
        assert s1["t"] == 12.5
        assert s1["sched"]["pieces"] == 10
        assert s1["sched"]["shed"] == 2
        assert s1["sched"]["admission_factor"] == 0.5
        # zero-op stages dropped (digest cardinality discipline)
        assert "idle" not in s1["stages"]
        assert s1["integrity"] == {"breaker_opens": 2, "open_lanes": 1,
                                   "races": 0, "distrust": 0}

    def test_pieces_counter_survives_tenant_eviction(self):
        """The availability denominator stays CUMULATIVE across tenant
        eviction: the scheduler moves an evicted tenant's served_pieces
        into the `evicted` blob, and the sample must count them — a
        dropping counter would make a real burst invisible (events
        delta clamps to 0) or a benign eviction page falsely."""
        before = {"tenants": {"a": {"served_pieces": 900},
                              "b": {"served_pieces": 100}},
                  "evicted": {"served_pieces": 0}}
        after = {"tenants": {"b": {"served_pieces": 110}},
                 "evicted": {"served_pieces": 900}}
        s0 = build_sample(1.0, {}, sched_snap=before)
        s1 = build_sample(2.0, {}, sched_snap=after)
        assert s0["sched"]["pieces"] == 1000
        assert s1["sched"]["pieces"] == 1010  # monotone across eviction

    def test_optional_fields_absent_when_off(self):
        s = build_sample(1.0, {})
        assert "control" not in s and "fleet" not in s and "tracker" not in s
        s = build_sample(1.0, {}, control={"stage": "h2d", "confirmed": True},
                         tracker={"announces": 5, "peers": 2, "swarms": 1})
        assert s["control"] == {"stage": "h2d", "confirmed": True}
        assert s["tracker"]["announces"] == 5


class TestSampler:
    def test_sample_once_captures_scheduler(self):
        from torrent_tpu.sched import HashPlaneScheduler, SchedulerConfig

        async def go():
            sched = HashPlaneScheduler(SchedulerConfig(), hasher="cpu")
            await sched.start()
            try:
                await sched.submit("tl", [b"x" * 64])
                tl = Timeline(depth=8)
                sampler = TimelineSampler(tl, scheduler=sched)
                sample = sampler.sample_once()
                assert sample["sched"]["pieces"] >= 1
                assert tl.snapshot()["seq"] == 1
            finally:
                await sched.close()

        run(go())

    def test_thread_lifecycle_and_alive(self):
        tl = Timeline(depth=8)
        sampler = TimelineSampler(tl, interval_s=0.01)
        assert not sampler.alive
        sampler.start()
        assert sampler.alive
        sampler.stop()
        assert not sampler.alive

    def test_broken_source_never_kills_a_sample(self):
        tl = Timeline(depth=8)

        def boom():
            raise RuntimeError("source down")

        sampler = TimelineSampler(tl, sources={"tracker": boom})
        sample = sampler.sample_once()
        assert "tracker" not in sample  # dropped, not fatal
        assert tl.snapshot()["seq"] == 1

    def test_dump_writes_replayable_file(self, tmp_path):
        tl = Timeline(depth=8)
        sampler = TimelineSampler(tl, dump_dir=str(tmp_path))
        sampler.sample_once()
        sampler.sample_once()
        path = sampler.dump()
        assert path and os.path.exists(path)
        with open(path) as f:
            payload = json.load(f)
        assert len(payload["samples"]) == 2
        assert replay_report(payload)["samples"] == 2

    def test_on_sample_hook_failure_tolerated(self):
        tl = Timeline(depth=8)
        calls = []

        def hook(snap):
            calls.append(len(snap["samples"]))
            raise RuntimeError("engine down")

        sampler = TimelineSampler(tl, on_sample=hook)
        sampler.sample_once()
        assert calls == [1]


# ----------------------------------------------------------------- replay


class TestReplay:
    def test_interval_and_overall_attribution(self):
        # h2d monotonically busiest: every interval and the overall
        # verdict must name it — the same answer the live attributor
        # gives over the same deltas
        samples = [
            mk_sample(t, h2d_busy=0.9 * t, h2d_bytes=1000 * t,
                      verdict_bytes=100 * t, verdict_ops=t)
            for t in range(1, 6)
        ]
        rep = replay_report({"samples": samples, "drops": 2})
        assert rep["samples"] == 5 and rep["drops"] == 2
        assert len(rep["intervals"]) == 4
        assert all(i["limiting"] == "h2d" for i in rep["intervals"])
        assert rep["overall"]["bottleneck"]["stage"] == "h2d"
        # ages count back from the newest sample
        assert rep["intervals"][-1]["age_s"] == 0.0
        assert rep["intervals"][0]["age_s"] == 3.0

    def test_empty_and_hostile_payloads(self):
        assert replay_report({})["samples"] == 0
        assert replay_report({"samples": None})["intervals"] == []
        rep = replay_report({"samples": [{"t": "x"}, 3, {"stages": "nope"}]})
        assert rep["samples"] == 2  # non-dicts filtered
        assert rep["overall"] is None or rep["overall"]["bottleneck"] is None

    def test_slo_evaluation_rides_along(self):
        samples = [mk_sample(1, pieces=10), mk_sample(2, pieces=10, failed=30)]
        rep = replay_report(
            {"samples": samples}, objectives=parse_objectives("availability=0.99")
        )
        assert rep["slo"]["objectives"]["availability"]["breach"]


# ----------------------------------------------------------------- SLO


class TestEvaluateSlo:
    def _avail(self, samples, target=0.999, short=4, long=16):
        return evaluate_slo(
            samples, parse_objectives(f"availability={target}"),
            short_samples=short, long_samples=long,
        )["objectives"]["availability"]

    def test_clean_ring_is_ok(self):
        samples = [mk_sample(t, pieces=10 * t) for t in range(1, 8)]
        obj = self._avail(samples)
        assert obj["classification"] == "ok" and not obj["breach"]
        assert obj["budget_remaining"] == 1.0

    def test_burst_is_fast_burn_breach(self):
        samples = [mk_sample(1, pieces=10), mk_sample(2, pieces=10, failed=10)]
        obj = self._avail(samples)
        assert obj["classification"] == "fast_burn" and obj["breach"]
        assert obj["budget_remaining"] == 0.0
        assert obj["burn_rate"] >= FAST_BURN

    def test_breach_clears_when_short_window_runs_clean(self):
        burst = [mk_sample(1, pieces=10), mk_sample(2, pieces=10, failed=10)]
        assert self._avail(burst)["breach"]
        # healthy samples push the errors out of the 4-sample short
        # window; the long window still shows the burn (slow_burn /
        # budget spent) but the page-now condition clears
        healthy = burst + [
            mk_sample(2 + i, pieces=10 + 10 * i, failed=10) for i in range(1, 6)
        ]
        obj = self._avail(healthy)
        assert not obj["breach"]
        assert obj["classification"] in ("ok", "slow_burn")

    def test_burn_rate_monotone_in_error_count(self):
        def burn(failed):
            samples = [mk_sample(1, pieces=100),
                       mk_sample(2, pieces=200, failed=failed)]
            return self._avail(samples)["burn_rate"]

        rates = [burn(f) for f in (0, 1, 5, 20, 80)]
        assert rates == sorted(rates)
        assert rates[0] == 0.0 and rates[-1] > rates[1]

    def test_integrity_event_burns_instantly_then_clears(self):
        objs = parse_objectives("integrity=on")
        burst = [mk_sample(1), mk_sample(2, breaker_opens=1)]
        rep = evaluate_slo(burst, objs, short_samples=3, long_samples=16)
        obj = rep["objectives"]["integrity"]
        assert obj["breach"] and obj["classification"] == "fast_burn"
        assert obj["budget_remaining"] == 0.0
        # the event ages out of the short window -> breach clears
        healthy = burst + [mk_sample(2 + i, breaker_opens=1) for i in range(1, 5)]
        obj = evaluate_slo(healthy, objs, short_samples=3, long_samples=16)[
            "objectives"]["integrity"]
        assert not obj["breach"]

    def test_latency_objective_over_log2_buckets(self):
        objs = parse_objectives("p99_ms=8:queue_wait")  # 0.008 s target
        # bucket 10 covers (2^-8, 2^-7] ≈ (3.9ms, 7.8ms]: under target;
        # bucket 16 covers (2^-2, 2^-1]: way over target
        fast = {"queue_wait": {"count": 100, "sum": 0.1,
                               "buckets": {"10": 100}}}
        slow = {"queue_wait": {"count": 200, "sum": 30.0,
                               "buckets": {"10": 100, "16": 100}}}
        ok = evaluate_slo(
            [mk_sample(1), mk_sample(2, hist=fast)], objs,
            short_samples=4, long_samples=16,
        )["objectives"]["latency_queue_wait"]
        assert not ok["breach"] and ok["classification"] == "ok"
        bad = evaluate_slo(
            [mk_sample(1), mk_sample(2, hist=slow)], objs,
            short_samples=4, long_samples=16,
        )["objectives"]["latency_queue_wait"]
        assert bad["breach"]
        assert bad["p99_s"] and bad["p99_s"] > 0.008

    def test_throughput_floor_counts_only_active_intervals(self):
        objs = parse_objectives("floor_mibps=1")
        # idle ring: no verdict ops -> never burns
        idle = [mk_sample(t) for t in range(1, 6)]
        obj = evaluate_slo(idle, objs, short_samples=4, long_samples=16)[
            "objectives"]["throughput"]
        assert not obj["breach"] and obj["events"] == 0
        # active but slow: 100 B/s << 1 MiB/s floor on every interval
        slow = [mk_sample(t, verdict_bytes=100 * t, verdict_ops=t)
                for t in range(1, 6)]
        obj = evaluate_slo(slow, objs, short_samples=4, long_samples=16)[
            "objectives"]["throughput"]
        assert obj["breach"] and obj["events"] == 4

    def test_hostile_samples_never_crash(self):
        hostile = [
            {"t": float("nan"), "sched": "zap", "stages": 7},
            {"t": "later", "hist": {"queue_wait": {"buckets": {"x": "y"}}}},
            {},
            {"t": -5, "integrity": None},
        ]
        rep = evaluate_slo(hostile, default_objectives())
        assert set(rep["objectives"]) == {"availability", "integrity"}

    def test_latency_overflow_bucket_reports_no_infinity(self):
        """Observations past the top log2 bound land in the overflow
        bucket; the report must carry p99_s=None + p99_overflow=True,
        never float('inf') — json.dumps would emit the non-RFC token
        `Infinity` and break strict /v1/slo parsers exactly when
        latency is pathological."""
        objs = parse_objectives("p99_ms=8:queue_wait")
        from torrent_tpu.obs.hist import BUCKET_BOUNDS

        overflow_idx = str(len(BUCKET_BOUNDS))
        hist = {"queue_wait": {"count": 100, "sum": 9000.0,
                               "buckets": {overflow_idx: 100}}}
        rep = evaluate_slo(
            [mk_sample(1), mk_sample(2, hist=hist)], objs,
            short_samples=4, long_samples=16,
        )
        obj = rep["objectives"]["latency_queue_wait"]
        assert obj["p99_s"] is None and obj["p99_overflow"]
        assert obj["breach"]
        # the whole report round-trips through strict JSON
        assert "Infinity" not in json.dumps(rep)

    def test_latency_evaluation_total_on_hostile_bucket_keys(self):
        """Non-canonical bucket keys ('07', ' 7', negatives) in a
        hand-edited/corrupt dump must not crash the latency evaluator
        (the replay CLI feeds arbitrary JSON straight through it)."""
        objs = parse_objectives("p99_ms=50:queue_wait")
        hist = {"queue_wait": {"count": 10, "sum": 1.0,
                               "buckets": {"07": 4, " 7": 2, "-3": 1,
                                           "x": 1, "16": 2}}}
        rep = evaluate_slo(
            [mk_sample(1), mk_sample(2, hist=hist)], objs,
            short_samples=4, long_samples=16,
        )
        obj = rep["objectives"]["latency_queue_wait"]
        assert obj["classification"] in ("ok", "slow_burn", "fast_burn")
        assert obj["p99_s"] is None or obj["p99_s"] > 0

    def test_spec_parse_errors(self):
        with pytest.raises(ValueError):
            parse_objectives("availability=1.5")
        with pytest.raises(ValueError):
            parse_objectives("frobnicate=1")
        with pytest.raises(ValueError):
            parse_objectives("")
        # a typo'd latency family would arm an objective that can never
        # observe data (green forever); nonpositive targets likewise
        with pytest.raises(ValueError):
            parse_objectives("p99_ms=50:requests")
        with pytest.raises(ValueError):
            parse_objectives("p99_ms=0")
        with pytest.raises(ValueError):
            parse_objectives("floor_mibps=0")
        # a duplicate name would collapse last-wins in the report —
        # the earlier target declared but never checked
        with pytest.raises(ValueError):
            parse_objectives("availability=0.999;availability=0.99")
        with pytest.raises(ValueError):
            parse_objectives("p99_ms=50:launch;p99_ms=10:launch")
        objs = parse_objectives(
            "availability=0.99;p99_ms=50:launch;floor_mibps=2;integrity=on"
        )
        assert [o.kind for o in objs] == [
            "availability", "latency", "throughput", "integrity"
        ]

    def test_digest_summary_shape(self):
        rep = evaluate_slo(
            [mk_sample(1, pieces=10), mk_sample(2, pieces=10, failed=10)],
            default_objectives(), short_samples=4, long_samples=16,
        )
        d = digest_summary(rep)
        assert d["breach"] == 1 and d["objective"] == "availability"
        assert d["burn"] > 0
        assert digest_summary(None) is None
        assert digest_summary({"worst": None}) is None


class TestSloEngine:
    def _dumps(self):
        from torrent_tpu.obs.recorder import flight_recorder

        return flight_recorder().counts().get("slo_breach", 0)

    def test_exactly_one_dump_per_breach_transition(self):
        eng = SloEngine("availability=0.99", short_samples=4, long_samples=16)
        base = self._dumps()
        ring = [mk_sample(1, pieces=10)]
        eng.observe({"samples": list(ring)})
        assert self._dumps() == base  # no breach yet
        ring.append(mk_sample(2, pieces=10, failed=10))
        eng.observe({"samples": list(ring)})
        assert self._dumps() == base + 1
        # still breaching: no second dump
        ring.append(mk_sample(3, pieces=10, failed=10))
        eng.observe({"samples": list(ring)})
        assert self._dumps() == base + 1
        # recovery clears, then a NEW burst transitions again -> 2nd dump
        for i in range(4, 9):
            ring.append(mk_sample(i, pieces=10 * i, failed=10))
        eng.observe({"samples": list(ring)})
        assert self._dumps() == base + 1
        assert not eng.report()["objectives"]["availability"]["breach"]
        ring.append(mk_sample(9, pieces=90, failed=100))
        eng.observe({"samples": list(ring)})
        assert self._dumps() == base + 2

    def test_simultaneous_breaches_coalesce_into_one_dump(self):
        from torrent_tpu.obs.recorder import flight_recorder

        eng = SloEngine("availability=0.99;integrity=on",
                        short_samples=4, long_samples=16)
        base = self._dumps()
        eng.observe({"samples": [mk_sample(1, pieces=10)]})
        eng.observe({"samples": [mk_sample(1, pieces=10),
                                 mk_sample(2, pieces=10, failed=10,
                                           breaker_opens=1)]})
        assert self._dumps() == base + 1
        dump = flight_recorder().dumps()[-1]
        assert dump["reason"] == "slo_breach"
        assert sorted(dump["detail"]["objectives"]) == [
            "availability", "integrity"
        ]


class TestArmedSlot:
    def test_disarm_only_releases_its_own_engine(self):
        """Server A shutting down must not clear server B's armed
        engine: the slot survives unless the disarming engine still
        owns it (force-clear with no argument stays for tests)."""
        from torrent_tpu.obs import slo as _slo

        a = SloEngine("availability=0.99")
        b = SloEngine("availability=0.9")
        _slo.arm(a)
        _slo.arm(b)  # B took over the slot
        _slo.disarm(a)  # A's shutdown: must NOT clobber B
        assert _slo.armed() is b
        _slo.disarm(b)
        assert _slo.armed() is None
        _slo.arm(a)
        _slo.disarm()  # argless force-clear
        assert _slo.armed() is None


class TestTimelineStats:
    def test_tail_snapshot_bounds_the_copy_to_the_window(self):
        tl = Timeline(depth=16)
        for i in range(10):
            tl.push(mk_sample(i))
        tail = tl.tail_snapshot(4)
        assert len(tail["samples"]) == 4
        assert [s["seq"] for s in tail["samples"]] == [7, 8, 9, 10]
        assert tail["seq"] == 10 and tail["drops"] == 0
        # shorter rings come back whole
        assert len(tl.tail_snapshot(64)["samples"]) == 10
        # a sampler armed with a tail hands the hook the bounded view
        seen = []
        sampler = TimelineSampler(tl, on_sample=lambda s: seen.append(
            len(s["samples"])), on_sample_tail=4)
        sampler.sample_once()
        assert seen == [4]

    def test_stats_matches_snapshot_counters_without_samples(self):
        tl = Timeline(depth=4)
        for i in range(6):
            tl.push(mk_sample(i))
        stats = tl.stats()
        snap = tl.snapshot()
        assert stats == {"v": 1, "depth": 4, "seq": 6, "drops": 2, "fill": 4}
        assert "samples" not in stats
        assert stats["fill"] == len(snap["samples"])
        from torrent_tpu.utils.metrics import render_timeline_metrics

        text = render_timeline_metrics(stats)
        assert "torrent_tpu_timeline_ring_fill 4" in text
        assert "torrent_tpu_timeline_samples_total 6" in text


class TestHealth:
    def test_ready_when_everything_resolves(self):
        h = build_health(probe_ok=True, breakers={}, sampler_alive=True)
        assert h == {"live": True, "ready": True, "status": "ready",
                     "reasons": [], "slo_breaches": []}

    def test_unready_reasons(self):
        h = build_health(probe_ok=False)
        assert h["status"] == "unready" and "backend probe unresolved" in h["reasons"]
        h = build_health(sampler_alive=False)
        assert "timeline sampler dead" in h["reasons"]
        h = build_health(pump_age_s=100.0, pump_max_age_s=30.0)
        assert any("pump stalled" in r for r in h["reasons"])

    def test_breaker_stuck_open_vs_transiently_open(self):
        fresh = {"l": {"state": "open", "open_age_s": 5.0, "cooldown": 30.0}}
        stuck = {"l": {"state": "open", "open_age_s": 90.0, "cooldown": 30.0}}
        assert build_health(breakers=fresh)["ready"]  # within cooldown
        h = build_health(breakers=stuck)
        assert h["status"] == "unready"
        assert any("stuck open" in r for r in h["reasons"])
        closed = {"l": {"state": "closed", "cooldown": 30.0}}
        assert build_health(breakers=closed)["ready"]

    def test_slo_breach_degrades_but_stays_live(self):
        report = {"objectives": {"availability": {"breach": True},
                                 "integrity": {"breach": False}}}
        h = build_health(probe_ok=True, slo_report=report)
        assert h["live"] and not h["ready"]
        assert h["status"] == "degraded"
        assert h["slo_breaches"] == ["availability"]


# ----------------------------------------------------------------- bridge


async def _http(port: int, method: str, path: str, body: bytes = b""):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(
        f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
        f"Content-Length: {len(body)}\r\n\r\n".encode() + body
    )
    await writer.drain()
    status_line = await reader.readline()
    clen = 0
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b""):
            break
        if line.lower().startswith(b"content-length:"):
            clen = int(line.split(b":", 1)[1])
    payload = await reader.readexactly(clen)
    writer.close()
    return int(status_line.split()[1]), payload


class TestBridgeRoutes:
    def test_unarmed_bridge_serves_detached_routes_and_ready_health(self):
        from torrent_tpu.bridge.service import BridgeServer

        async def go():
            svc = await BridgeServer("127.0.0.1", port=0, hasher="cpu").start()
            try:
                await svc._probe_task
                # zero overhead when off: nothing constructed
                assert svc.timeline is None and svc.slo_engine is None
                assert svc.sampler is None
                status, body = await _http(svc.port, "GET", "/v1/timeline")
                assert status == 200 and not json.loads(body)["attached"]
                status, body = await _http(svc.port, "GET", "/v1/slo")
                assert status == 200 and not json.loads(body)["attached"]
                status, body = await _http(svc.port, "GET", "/v1/health")
                health = json.loads(body)
                assert status == 200 and health["status"] == "ready"
                # no timeline/slo series pollute the unarmed scrape
                status, body = await _http(svc.port, "GET", "/metrics")
                assert b"torrent_tpu_timeline_" not in body
                assert b"torrent_tpu_slo_" not in body
            finally:
                svc.close()
                await svc.wait_closed()

        run(go())

    def test_armed_bridge_serves_timeline_slo_health_and_metrics(self):
        from torrent_tpu.bridge.service import BridgeServer
        from torrent_tpu.codec.bencode import bencode

        async def go():
            svc = await BridgeServer(
                "127.0.0.1", port=0, hasher="cpu",
                slo="availability=0.999;integrity=on",
                timeline_interval_s=3600.0,
            ).start()
            try:
                await svc._probe_task
                body = bencode({b"pieces": [b"tl-piece"]})
                status, _ = await _http(svc.port, "POST", "/v1/digests", body)
                assert status == 200
                svc.sampler.sample_once()
                svc.sampler.sample_once()
                status, payload = await _http(svc.port, "GET", "/v1/timeline")
                tl = json.loads(payload)
                assert tl["attached"] and len(tl["samples"]) == 2
                assert tl["sampler_alive"]
                status, payload = await _http(svc.port, "GET", "/v1/slo")
                slo = json.loads(payload)
                assert slo["attached"]
                assert set(slo["report"]["objectives"]) == {
                    "availability", "integrity"
                }
                assert not slo["report"]["breach_any"]
                status, payload = await _http(svc.port, "GET", "/v1/health")
                assert status == 200 and json.loads(payload)["ready"]
                status, payload = await _http(svc.port, "GET", "/metrics")
                text = payload.decode()
                assert "torrent_tpu_timeline_samples_total 2" in text
                assert 'torrent_tpu_slo_breach{objective="availability"} 0' in text
                assert "torrent_tpu_timeline_sampler_alive 1" in text
            finally:
                svc.close()
                await svc.wait_closed()
            # disarmed on close: the global engine slot is free again
            from torrent_tpu.obs import slo as _slo

            assert _slo.armed() is None

        run(go())


# ----------------------------------------------- ISSUE acceptance scenario


class TestAcceptanceScenario:
    def test_fault_burst_breach_dump_health_and_breaker_recovery(self):
        """The end-to-end SLO scenario, deterministic on CPU: injected
        transient device failures trip the lane breaker (an integrity
        event + CPU degradation), the engine classifies a fast burn and
        breaches, /v1/health flips ready→degraded, exactly one
        slo_breach dump fires — and after the breaker's half-open probe
        restores the device plane, clean samples clear the breach and
        readiness returns."""
        from torrent_tpu.bridge.service import BridgeServer
        from torrent_tpu.codec.bencode import bencode
        from torrent_tpu.obs.recorder import flight_recorder
        from torrent_tpu.sched import FaultPlan

        async def go():
            svc = await BridgeServer(
                "127.0.0.1", port=0, hasher="cpu",
                # exactly enough consecutive transient failures to
                # cross the default breaker threshold (launch + retry +
                # first bisected half); launch 4 — the half-open probe —
                # lands past the window and succeeds
                fault_plan=FaultPlan(fail_first=3),
                slo="availability=0.999;integrity=on",
                timeline_interval_s=3600.0,
                slo_short_samples=3, slo_long_samples=64,
            ).start()
            try:
                await svc._probe_task
                base = flight_recorder().counts().get("slo_breach", 0)
                svc.sampler.sample_once()
                # fixed width: burst and recovery must land in the SAME
                # (algo, piece-bucket) lane — its fault plane and
                # breaker — not open a fresh lane per size
                pieces = [(b"acc-%d" % i).ljust(8, b"x") for i in range(4)]
                body = bencode({b"pieces": pieces})
                # consecutive transient failures trip the breaker; the
                # CPU fallback still serves correct digests (200)
                status, payload = await _http(
                    svc.port, "POST", "/v1/digests", body
                )
                assert status == 200
                from torrent_tpu.codec.bencode import bdecode

                got = bdecode(payload)[b"digests"]
                assert got == [hashlib.sha1(p).digest() for p in pieces]
                snap = svc.sched.metrics_snapshot()
                lane = next(iter(snap["breakers"].values()))
                assert lane["state"] == "open", lane
                svc.sampler.sample_once()

                # breach: the breaker-open transition is an integrity
                # event -> instant fast burn
                status, payload = await _http(svc.port, "GET", "/v1/slo")
                rep = json.loads(payload)["report"]
                integ = rep["objectives"]["integrity"]
                assert integ["breach"], integ
                assert integ["classification"] == "fast_burn"
                assert integ["budget_remaining"] == 0.0
                status, payload = await _http(svc.port, "GET", "/v1/health")
                health = json.loads(payload)
                assert status == 503 and health["status"] == "degraded"
                assert "integrity" in health["slo_breaches"]
                dumps = flight_recorder().counts().get("slo_breach", 0) - base
                assert dumps == 1, f"exactly one slo_breach dump, got {dumps}"

                # recovery: expire the cooldown -> the next launch is
                # the half-open probe (fault window over, it succeeds)
                for lane_obj in svc.sched._lanes.values():
                    with lane_obj.breaker.lock:
                        lane_obj.breaker.opened_at -= 1e6
                more = bencode(
                    {b"pieces": [(b"rec-%d" % i).ljust(8, b"x")
                                 for i in range(4)]}
                )
                status, _ = await _http(svc.port, "POST", "/v1/digests", more)
                assert status == 200
                snap = svc.sched.metrics_snapshot()
                lane = next(iter(snap["breakers"].values()))
                assert lane["state"] == "closed", lane
                # clean samples age the event out of the short window
                for _ in range(4):
                    svc.sampler.sample_once()
                status, payload = await _http(svc.port, "GET", "/v1/slo")
                rep = json.loads(payload)["report"]
                assert not rep["objectives"]["integrity"]["breach"]
                status, payload = await _http(svc.port, "GET", "/v1/health")
                assert status == 200 and json.loads(payload)["ready"]
                dumps = flight_recorder().counts().get("slo_breach", 0) - base
                assert dumps == 1, "recovery must not re-dump"
            finally:
                svc.close()
                await svc.wait_closed()

        run(go())

    def test_replay_names_same_limiting_stage_as_live_attributor(self, tmp_path):
        """An h2d-throttled scheduler run bracketed by timeline samples:
        the live attributor and the offline replay over the dumped file
        must name the same limiting stage."""
        from torrent_tpu.obs.attrib import attribute
        from torrent_tpu.obs.ledger import pipeline_ledger
        from torrent_tpu.sched import FaultPlan, HashPlaneScheduler, SchedulerConfig

        async def go():
            plan = FaultPlan.parse("latency_ms=25")
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=8, flush_deadline=0.02,
                    plane_factory=plan.plane_factory(hasher="cpu"),
                ),
                hasher="cpu",
            )
            await sched.start()
            tl = Timeline(depth=32)
            sampler = TimelineSampler(tl, scheduler=sched,
                                      dump_dir=str(tmp_path))
            led = pipeline_ledger()
            base = led.snapshot()
            try:
                sampler.sample_once()
                pieces = [bytes([i % 251]) * 1024 for i in range(64)]
                want = [hashlib.sha1(p).digest() for p in pieces]
                for _ in range(2):
                    assert await sched.submit("replay", pieces) == want
                    sampler.sample_once()
            finally:
                await sched.close()
            live = attribute(led.snapshot(), prev=base)
            assert live["bottleneck"]["stage"] == "h2d", live["bottleneck"]
            path = sampler.dump()
            with open(path) as f:
                payload = json.load(f)
            rep = replay_report(payload)
            assert rep["overall"]["bottleneck"]["stage"] == "h2d"
            assert any(i["limiting"] == "h2d" for i in rep["intervals"])

        run(go())


# ------------------------------------------------------- tracker + serve


class TestTrackerHealth:
    def test_sharded_tracker_serves_health(self):
        from torrent_tpu.server.shard import run_sharded_tracker
        from torrent_tpu.server.tracker import ServeOptions

        async def go():
            server, task = await run_sharded_tracker(
                ServeOptions(http_port=0, udp_port=None, host="127.0.0.1"),
                n_shards=2,
            )
            try:
                await asyncio.sleep(0.05)  # let the pump stamp a tick
                status, body = await _http(
                    server.http_port, "GET", "/v1/health"
                )
                health = json.loads(body)
                assert status == 200 and health["ready"]
                assert health["live"]
            finally:
                server.close()
                task.cancel()
                try:
                    await task
                except (asyncio.CancelledError, Exception):
                    pass

        run(go())

    def test_serve_recipe_wires_everything(self):
        """The deployment recipe: one call starts the sharded tracker +
        DHT indexer + health + metrics (+ timeline/SLO when armed);
        /v1/health answers ready, /metrics carries tracker AND slo
        series, and an announce round-trips through the plane."""
        import hashlib as _hashlib

        from torrent_tpu.tools.serve import start_service

        async def go():
            handle = await start_service(
                http_port=0, udp_port=None, host="127.0.0.1",
                shards=2, dht_port=0, crawl_interval=3600.0,
                slo=True, timeline_interval_s=3600.0,
            )
            try:
                assert handle.dht is not None and handle.indexer is not None
                assert handle.slo_engine is not None
                ih = _hashlib.sha1(b"serve-swarm").digest()
                handle.store.announce(ih, b"p" * 20, "10.0.0.1", 6881, left=0)
                handle.sampler.sample_once()
                await asyncio.sleep(0.05)
                status, body = await _http(handle.http_port, "GET", "/v1/health")
                health = json.loads(body)
                assert status == 200 and health["ready"], health
                status, body = await _http(handle.http_port, "GET", "/metrics")
                text = body.decode()
                assert "torrent_tpu_tracker_peers 1" in text
                assert "torrent_tpu_slo_budget_remaining" in text
                assert "torrent_tpu_timeline_samples_total 1" in text
                # the sample carried tracker facts
                assert handle.timeline.samples()[-1]["tracker"]["peers"] == 1
            finally:
                await handle.close()
            from torrent_tpu.obs import slo as _slo

            assert _slo.armed() is None

        run(go())


# ------------------------------------------------------------ tools


class TestReplayCli:
    def test_replay_command_renders_and_exits_zero(self, tmp_path, capsys):
        from torrent_tpu.tools.cli import main as cli_main

        samples = [
            mk_sample(t, h2d_busy=0.9 * t, h2d_bytes=10_000 * t,
                      verdict_bytes=1000 * t, verdict_ops=t, pieces=10 * t)
            for t in range(1, 5)
        ]
        path = tmp_path / "timeline.json"
        path.write_text(json.dumps({"samples": samples, "drops": 0}))
        rc = cli_main(["replay", str(path), "--slo", "availability=0.999"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "h2d" in out and "overall:" in out
        assert "slo availability" in out

    def test_replay_json_mode_and_missing_file(self, tmp_path, capsys):
        from torrent_tpu.tools.cli import main as cli_main

        path = tmp_path / "t.json"
        path.write_text(json.dumps({"samples": [mk_sample(1), mk_sample(2)]}))
        rc = cli_main(["replay", str(path), "--json"])
        out = capsys.readouterr().out
        assert rc == 0
        assert json.loads(out)["samples"] == 2
        assert cli_main(["replay", str(tmp_path / "missing.json")]) == 2

    def test_replay_bad_slo_spec(self, tmp_path):
        from torrent_tpu.tools.cli import main as cli_main

        path = tmp_path / "t.json"
        path.write_text("{}")
        assert cli_main(["replay", str(path), "--slo", "nope=1"]) == 2


class TestHistoryRender:
    def test_render_history_sparklines_and_slo_lines(self):
        from torrent_tpu.tools.top import render_history

        samples = [
            mk_sample(t, h2d_busy=0.9 * t, h2d_bytes=10_000 * t,
                      verdict_bytes=1000 * t, verdict_ops=t)
            for t in range(1, 6)
        ]
        slo_payload = {
            "report": {
                "objectives": {
                    "availability": {
                        "burn_rate": 20.0, "classification": "fast_burn",
                        "budget_remaining": 0.0, "breach": True,
                    }
                }
            }
        }
        frame = render_history(
            {"samples": samples, "drops": 0}, slo_payload, url="http://x"
        )
        assert "h2d" in frame and "|" in frame
        assert "overall: h2d" in frame
        assert "BREACH" in frame and "burn ×20.0" in frame

    def test_render_history_empty(self):
        from torrent_tpu.tools.top import render_history

        frame = render_history({"samples": []})
        assert "timeline empty" in frame


class TestFleetBudgetHealth:
    def test_digest_carries_slo_and_rollup_surfaces_worst(self):
        from torrent_tpu.obs import slo as _slo
        from torrent_tpu.obs.fleet import aggregate_fleet, obs_digest

        eng = SloEngine("availability=0.99", short_samples=4, long_samples=16)
        eng.observe({"samples": [mk_sample(1, pieces=10),
                                 mk_sample(2, pieces=10, failed=10)]})
        _slo.arm(eng)
        try:
            digest = obs_digest()
            assert digest["slo"]["breach"] == 1
            assert digest["slo"]["burn"] > 0
        finally:
            _slo.disarm()
        # an unarmed digest carries no slo key (byte-identical to before)
        assert "slo" not in obs_digest()
        roll = aggregate_fleet({
            0: {"wall_s": 1.0, "stages": {}, "unit": {},
                "slo": {"burn": 2.0, "objective": "availability", "breach": 0}},
            1: {"wall_s": 1.0, "stages": {}, "unit": {},
                "slo": {"burn": 30.0, "objective": "integrity", "breach": 1}},
        })
        assert roll["slo"]["pid"] == 1
        assert roll["slo"]["worst_burn"] == 30.0
        assert roll["slo"]["breaching"] == 1

    def test_top_fleet_renders_budget_line(self):
        from torrent_tpu.tools.top import render_fleet

        frame = render_fleet({
            "nproc": 2, "reporting": 2, "scoreboard": [], "totals": {},
            "slo": {"pid": 1, "objective": "integrity", "worst_burn": 30.0,
                    "breaching": 1},
        })
        assert "budget: worst burn ×30.0" in frame
        assert "BREACH" in frame

    def test_rollup_without_slo_has_none(self):
        from torrent_tpu.obs.fleet import aggregate_fleet

        roll = aggregate_fleet({0: {"wall_s": 1.0, "stages": {}, "unit": {}}})
        assert roll["slo"] is None
