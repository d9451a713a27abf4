"""Prometheus metrics endpoint (utils/metrics.py).

Format is validated structurally (every sample line parses, HELP/TYPE
precede their family) and the endpoint is scraped over real HTTP during
a live swarm, asserting the counters actually move.
"""

import asyncio
import re
import urllib.error
import urllib.request

import numpy as np

from torrent_tpu.codec.metainfo import parse_metainfo
from torrent_tpu.session.client import Client, ClientConfig
from torrent_tpu.storage.storage import MemoryStorage, Storage
from torrent_tpu.utils.metrics import MetricsServer, render_metrics

from test_session import build_torrent_bytes, fast_config, run, start_tracker

# a label value is quoted and may hold any character but a raw newline,
# with only \\, \" and \n escaped (so a `}` inside one ends nothing)
_LABEL = r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\[\\"n])*"'
_SAMPLE = re.compile(
    rf"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{{(?:{_LABEL}(?:,{_LABEL})*)?\}})? [0-9.eE+-]+$"
)


def _parse(text):
    families = {}
    samples = []
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            families[name] = kind
        elif not line.startswith("#"):
            assert _SAMPLE.match(line), f"malformed sample: {line!r}"
            samples.append(line)
    return families, samples


class TestRenderFormat:
    def test_empty_client_renders_valid_exposition(self):
        async def go():
            c = Client(ClientConfig(host="127.0.0.1"))
            families, samples = _parse(render_metrics(c))
            assert families["torrent_tpu_torrents"] == "gauge"
            assert "torrent_tpu_torrents 0" in samples

        run(go())

    def test_label_escaping(self):
        class _T:
            pass

        from torrent_tpu.utils.metrics import _esc

        assert _esc('na"me\\x\n') == 'na\\"me\\\\x\\n'


def prom_lint(text: str) -> None:
    """Prometheus text-format lint: every sample belongs to a family
    that declared # HELP and # TYPE before it, histogram suffixes map
    to a histogram-typed family, and no series (name + label set) is
    emitted twice."""
    helps: set[str] = set()
    types: dict[str, str] = {}
    seen_series: set[str] = set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            helps.add(line.split(" ", 3)[2])
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = kind
            continue
        assert not line.startswith("#"), f"unknown comment: {line!r}"
        assert _SAMPLE.match(line), f"malformed sample: {line!r}"
        series = line.rsplit(" ", 1)[0]
        assert series not in seen_series, f"duplicate series: {series!r}"
        seen_series.add(series)
        name = series.split("{", 1)[0]
        family = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in types:
                family = name[: -len(suffix)]
                assert types[family] == "histogram", (
                    f"{name} uses histogram suffixes but {family} is "
                    f"{types[family]}"
                )
                break
        assert family in types, f"sample {name!r} has no # TYPE"
        assert family in helps, f"sample {name!r} has no # HELP"


class TestRendererEdgeCases:
    """Renderers must survive fresh components and partial (degraded)
    snapshots — /metrics is often scraped exactly when things are
    half-initialized — and every output must pass the format lint."""

    def test_fresh_scheduler_renders_clean(self):
        from torrent_tpu.sched import HashPlaneScheduler, SchedulerConfig
        from torrent_tpu.utils.metrics import render_sched_metrics

        sched = HashPlaneScheduler(SchedulerConfig(), hasher="cpu")
        text = render_sched_metrics(sched)
        prom_lint(text)
        assert "torrent_tpu_sched_queue_pieces 0" in text
        assert "torrent_tpu_sched_launches_total 0" in text

    def test_sched_renderer_tolerates_missing_keys(self):
        from torrent_tpu.utils.metrics import render_sched_metrics

        class _Degraded:
            def metrics_snapshot(self):
                return {"queue_pieces": 3}  # everything else absent

        text = render_sched_metrics(_Degraded())
        prom_lint(text)
        assert "torrent_tpu_sched_queue_pieces 3" in text
        assert "torrent_tpu_sched_queue_bytes 0" in text

    def test_fabric_renderer_tolerates_empty_snapshot(self):
        from torrent_tpu.utils.metrics import render_fabric_metrics

        text = render_fabric_metrics({})
        prom_lint(text)
        assert 'torrent_tpu_fabric_state{pid="0"} 3' in text  # unknown = failed

    def test_fabric_renderer_partial_snapshot(self):
        from torrent_tpu.utils.metrics import render_fabric_metrics

        text = render_fabric_metrics({"pid": 2, "state": "running", "units_done": 4})
        prom_lint(text)
        assert 'torrent_tpu_fabric_state{pid="2"} 1' in text
        assert 'torrent_tpu_fabric_units{pid="2",kind="done"} 4' in text
        assert 'torrent_tpu_fabric_shard_bytes{pid="2"} 0' in text

    def test_fabric_renderer_audit_quorum_fresh_defaults(self):
        # an f=0 (or half-initialized) snapshot still renders the
        # Byzantine audit/quorum families, zeroed — scrapes must not
        # see series flap in and out when byzantine_f changes
        from torrent_tpu.utils.metrics import render_fabric_metrics

        text = render_fabric_metrics({})
        prom_lint(text)
        assert 'torrent_tpu_fabric_audit_checks_total{pid="0"} 0' in text
        assert 'torrent_tpu_fabric_audit_mismatches_total{pid="0"} 0' in text
        assert 'torrent_tpu_fabric_quorum_convictions_total{pid="0"} 0' in text
        assert 'torrent_tpu_fabric_quorum_verifies_total{pid="0"} 0' in text
        assert 'torrent_tpu_fabric_quorum_need{pid="0"} 1' in text

    def test_fabric_renderer_audit_quorum_partial_snapshot(self):
        from torrent_tpu.utils.metrics import render_fabric_metrics

        text = render_fabric_metrics({
            "pid": 1, "state": "running", "byzantine_f": 1,
            "quorum_need": 2, "audit_checks": 9, "audit_mismatches": 1,
            "convictions": 1, "quorum_verifies": 3,
        })
        prom_lint(text)
        assert 'torrent_tpu_fabric_audit_checks_total{pid="1"} 9' in text
        assert 'torrent_tpu_fabric_audit_mismatches_total{pid="1"} 1' in text
        assert 'torrent_tpu_fabric_quorum_convictions_total{pid="1"} 1' in text
        assert 'torrent_tpu_fabric_quorum_verifies_total{pid="1"} 3' in text
        assert 'torrent_tpu_fabric_quorum_need{pid="1"} 2' in text

    def test_tsan_renderer_empty_snapshot(self):
        from torrent_tpu.utils.metrics import render_tsan_metrics

        text = render_tsan_metrics({})
        prom_lint(text)
        assert "torrent_tpu_lock_order_cycles_total 0" in text
        assert "torrent_tpu_lockset_races_total 0" in text

    def test_tsan_renderer_lockset_series(self):
        """The Eraser's guarded-cell/race series render as valid
        Prometheus text with per-cell labels."""
        from torrent_tpu.analysis.sanitizer import TsanState, guard_attrs
        from torrent_tpu.utils.metrics import render_tsan_metrics

        st = TsanState()
        guard_attrs("m.breaker", "state", state=st)
        guard_attrs("m.slab", "refs", state=st)
        guard_attrs("m.slab", "refs", state=st)  # second instance
        text = render_tsan_metrics(st.snapshot())
        prom_lint(text)
        assert 'torrent_tpu_guarded_cells{cell="m.breaker.state"} 1' in text
        assert 'torrent_tpu_guarded_cells{cell="m.slab.refs"} 2' in text
        assert "torrent_tpu_lockset_races_total 0" in text

    def test_obs_render_lints(self):
        from torrent_tpu.obs import histograms, render_obs_metrics

        histograms().get(
            "torrent_tpu_sched_queue_wait_seconds", help="x", lane="sha1/64"
        ).observe(0.004)
        prom_lint(render_obs_metrics())

    def test_pipeline_renderer_fresh_ledger(self):
        """A fresh (never-touched) ledger must render complete headers
        with no samples — /metrics is often scraped at startup."""
        from torrent_tpu.obs.ledger import PipelineLedger, render_pipeline_metrics

        text = render_pipeline_metrics(PipelineLedger())
        prom_lint(text)
        assert "torrent_tpu_pipeline_wall_seconds 0" in text
        assert "torrent_tpu_pipeline_stage_busy_seconds_total" in text

    def test_pipeline_renderer_partial_and_overflow_stages(self):
        """Partial activity (one stage touched) and unknown stage names
        (a plane_factory plane inventing stages past the cardinality
        bound) both render clean."""
        from torrent_tpu.obs.ledger import PipelineLedger, render_pipeline_metrics

        led = PipelineLedger()
        led.record("h2d", 4096, 0.25)
        for i in range(32):
            led.record(f"rogue{i}", 1, 0.001)
        text = render_pipeline_metrics(led)
        prom_lint(text)
        assert 'torrent_tpu_pipeline_stage_bytes_total{stage="h2d"} 4096' in text
        assert 'stage="other"' in text
        assert 'torrent_tpu_pipeline_bottleneck{stage="h2d"}' in text

    def test_pipeline_renderer_overlap_and_occupancy_series(self):
        """The zero-copy ingest visibility series: per-stage max_active
        plus the cross-stage overlap counter/gauges (read while h2d
        while launch — the double-buffering proof) render and lint."""
        from torrent_tpu.obs.ledger import PipelineLedger, render_pipeline_metrics

        led = PipelineLedger()
        with led.track("read", 100):
            with led.track("h2d", 100):
                pass
        text = render_pipeline_metrics(led)
        prom_lint(text)
        assert 'torrent_tpu_pipeline_stage_max_active{stage="read"} 1' in text
        assert "torrent_tpu_pipeline_overlap_seconds_total" in text
        assert "torrent_tpu_pipeline_concurrent_stages 0" in text
        assert "torrent_tpu_pipeline_concurrent_stages_max 2" in text

    def test_sched_staging_series_render(self):
        """Zero-copy slab accounting on /metrics: outstanding gauge and
        checkout counter (leak visibility for the ingest pools)."""
        from torrent_tpu.utils.metrics import render_sched_metrics

        class _Stub:
            def metrics_snapshot(self):
                return {"staging": {"pools": 1, "outstanding": 2,
                                    "checkouts": 9}}

        text = render_sched_metrics(_Stub())
        prom_lint(text)
        assert "torrent_tpu_sched_staging_outstanding 2" in text
        assert "torrent_tpu_sched_staging_checkouts_total 9" in text

    def test_fleet_renderer_fresh_rollup(self):
        """A fresh/empty fleet rollup (no digests held yet, even an
        empty dict) must render complete headers and zero samples —
        /metrics is often scraped before the first heartbeat lands."""
        from torrent_tpu.utils.metrics import render_fleet_metrics

        for rollup in ({}, {"nproc": 0, "scoreboard": []}):
            text = render_fleet_metrics(rollup)
            prom_lint(text)
            assert "torrent_tpu_fleet_processes 0" in text
            assert "torrent_tpu_fleet_digest_dropped_total 0" in text

    def test_fleet_renderer_partial_peer_set(self):
        """Mid-run view: some peers reported digests, some are only
        known by status (unreported/lapsed) — partial rows with missing
        keys must render as zeros, never a crash."""
        from torrent_tpu.utils.metrics import render_fleet_metrics

        rollup = {
            "nproc": 3,
            "reporting": 2,
            "bottleneck": {"pid": 1, "stage": "h2d",
                           "fleet_median_bps": 1000.0},
            "scoreboard": [
                {"pid": 0, "status": "ok", "achieved_bps": 2000.0,
                 "vs_median": 2.0, "units_planned": 2, "units_done": 2},
                {"pid": 1, "status": "ok", "achieved_bps": 10.0},
                {"pid": 2, "status": "lapsed", "adoption_debt": 4},
            ],
            "digest_drops": 1,
        }
        text = render_fleet_metrics(rollup)
        prom_lint(text)
        assert 'torrent_tpu_fleet_status{status="lapsed"} 1' in text
        assert (
            'torrent_tpu_fleet_limiting_process{pid="1",stage="h2d"} 1'
            in text
        )
        assert 'torrent_tpu_fleet_pid_achieved_bps{pid="2"} 0' in text
        assert 'torrent_tpu_fleet_pid_adoption_debt{pid="2"} 4' in text
        assert 'torrent_tpu_fleet_pid_units{pid="0",kind="done"} 2' in text
        assert "torrent_tpu_fleet_digest_dropped_total 1" in text

    def test_fleet_renderer_pid_overflow(self):
        """Bounded pid cardinality: a fleet wider than MAX_FLEET_PIDS
        folds the tail rows into one pid="overflow" aggregate."""
        from torrent_tpu.utils.metrics import (
            MAX_FLEET_PIDS,
            render_fleet_metrics,
        )

        n = MAX_FLEET_PIDS + 4
        rollup = {
            "nproc": n,
            "reporting": n,
            "scoreboard": [
                {"pid": p, "status": "ok", "achieved_bps": 100.0,
                 "vs_median": 0.4 if p == n - 1 else 1.0,
                 "units_planned": 1, "units_done": 1}
                for p in range(n)
            ],
        }
        text = render_fleet_metrics(rollup)
        prom_lint(text)
        assert 'torrent_tpu_fleet_pid_achieved_bps{pid="overflow"} 400.0' in text
        assert f'pid="{MAX_FLEET_PIDS - 1}"' in text
        assert f'pid="{MAX_FLEET_PIDS}"' not in text
        assert (
            'torrent_tpu_fleet_pid_units{pid="overflow",kind="done"} 4' in text
        )
        # a ratio doesn't sum: the folded vs_median reports the WORST
        # member, so an alert on < 0.5 still catches a folded straggler
        assert 'torrent_tpu_fleet_pid_vs_median{pid="overflow"} 0.4' in text

    def test_tracker_renderer_fresh_store(self):
        """A fresh sharded store (no announces yet) must render complete
        headers and zeroed totals — the tracker's /metrics is scraped
        from the moment the listener binds."""
        from torrent_tpu.server.shard import ShardedSwarmStore
        from torrent_tpu.utils.metrics import render_tracker_metrics

        text = render_tracker_metrics(ShardedSwarmStore(n_shards=4).metrics_snapshot())
        prom_lint(text)
        assert "torrent_tpu_tracker_announces_total 0" in text
        assert "torrent_tpu_tracker_shards 4" in text
        assert 'torrent_tpu_tracker_shard_peers{shard="3"} 0' in text

    def test_tracker_renderer_partial_snapshot(self):
        """Missing keys (a degraded or hand-rolled snapshot) render as
        zeros, never a crash mid-scrape; an indexer sub-dict adds the
        indexer families."""
        from torrent_tpu.utils.metrics import render_tracker_metrics

        text = render_tracker_metrics({"announces": 7, "shards": [{"peers": 3}]})
        prom_lint(text)
        assert "torrent_tpu_tracker_announces_total 7" in text
        assert "torrent_tpu_tracker_scrapes_total 0" in text
        assert 'torrent_tpu_tracker_shard_peers{shard="0"} 3' in text
        assert 'torrent_tpu_tracker_shard_swarms{shard="0"} 0' in text
        text = render_tracker_metrics(
            {"indexer": {"hashes": 5, "harvested": {"announce_peer": 2}}}
        )
        prom_lint(text)
        assert "torrent_tpu_tracker_indexer_hashes 5" in text
        assert (
            'torrent_tpu_tracker_indexer_harvested_total{kind="announce_peer"} 2'
            in text
        )
        assert (
            'torrent_tpu_tracker_indexer_harvested_total{kind="get_peers"} 0'
            in text
        )
        prom_lint(render_tracker_metrics({}))
        prom_lint(render_tracker_metrics(None))

    def test_tracker_renderer_shard_overflow(self):
        """Bounded shard cardinality: a store misconfigured wider than
        MAX_TRACKER_SHARDS folds the tail into shard="overflow"."""
        from torrent_tpu.utils.metrics import (
            MAX_TRACKER_SHARDS,
            render_tracker_metrics,
        )

        n = MAX_TRACKER_SHARDS + 4
        snap = {
            "n_shards": n,
            "shards": [
                {"swarms": 1, "peers": 2, "announces": 3} for _ in range(n)
            ],
        }
        text = render_tracker_metrics(snap)
        prom_lint(text)
        assert f'shard="{MAX_TRACKER_SHARDS - 1}"' in text
        assert f'shard="{MAX_TRACKER_SHARDS}"' not in text
        assert 'torrent_tpu_tracker_shard_peers{shard="overflow"} 8' in text
        assert (
            'torrent_tpu_tracker_shard_announces_total{shard="overflow"} 12'
            in text
        )

    def test_timeline_renderer_fresh_partial_and_full(self):
        """The timeline series render from a fresh ring, a partial
        hand-rolled snapshot, and a live ring with a sampler flag —
        never a crash mid-scrape."""
        from torrent_tpu.obs.timeline import Timeline
        from torrent_tpu.utils.metrics import render_timeline_metrics

        prom_lint(render_timeline_metrics({}))
        prom_lint(render_timeline_metrics(None))
        text = render_timeline_metrics({"seq": 9, "drops": 2})
        prom_lint(text)
        assert "torrent_tpu_timeline_samples_total 9" in text
        assert "torrent_tpu_timeline_dropped_total 2" in text
        assert "torrent_tpu_timeline_sampler_alive" not in text  # no key
        tl = Timeline(depth=4)
        tl.push({"t": 1.0})
        snap = tl.snapshot()
        snap["sampler_alive"] = True
        text = render_timeline_metrics(snap)
        prom_lint(text)
        assert "torrent_tpu_timeline_ring_fill 1" in text
        assert "torrent_tpu_timeline_depth 4" in text
        assert "torrent_tpu_timeline_sampler_alive 1" in text

    def test_slo_renderer_none_partial_and_breaching(self):
        """The SLO series render from no report yet (engine armed but
        never observed), a partial objective dict, and a breaching
        report — per-objective budget/burn/breach families."""
        from torrent_tpu.utils.metrics import render_slo_metrics

        prom_lint(render_slo_metrics(None))
        prom_lint(render_slo_metrics({}))
        text = render_slo_metrics({"objectives": {"availability": {}}})
        prom_lint(text)
        assert (
            'torrent_tpu_slo_budget_remaining{objective="availability"} 1.0'
            in text
        )
        report = {
            "objectives": {
                "availability": {
                    "budget_remaining": 0.25, "burn_rate": 20.0,
                    "burn_rate_long": 4.0, "breach": True,
                },
                "integrity": {
                    "budget_remaining": 1.0, "burn_rate": 0.0,
                    "burn_rate_long": 0.0, "breach": False,
                },
            }
        }
        text = render_slo_metrics(report)
        prom_lint(text)
        assert (
            'torrent_tpu_slo_burn_rate{objective="availability",window="short"} 20.0'
            in text
        )
        assert (
            'torrent_tpu_slo_burn_rate{objective="availability",window="long"} 4.0'
            in text
        )
        assert 'torrent_tpu_slo_breach{objective="availability"} 1' in text
        assert 'torrent_tpu_slo_breach{objective="integrity"} 0' in text

    def test_fleet_renderer_slo_budget_series(self):
        """A rollup carrying the fleet SLO summary renders the worst
        burn-rate series; one without it renders no slo series."""
        from torrent_tpu.obs.fleet import local_fleet_snapshot
        from torrent_tpu.utils.metrics import render_fleet_metrics

        roll = local_fleet_snapshot()
        roll["slo"] = {"pid": 1, "objective": "integrity",
                       "worst_burn": 30.5, "breaching": 1}
        text = render_fleet_metrics(roll)
        prom_lint(text)
        assert (
            'torrent_tpu_fleet_slo_worst_burn_rate{pid="1",objective="integrity"} 30.5'
            in text
        )
        assert "torrent_tpu_fleet_slo_breaching 1" in text
        assert "slo_worst_burn" not in render_fleet_metrics(
            local_fleet_snapshot()
        )

    def test_full_exposition_concatenation_lints(self):
        """What the bridge actually serves: sched + fabric + fleet +
        control + obs (incl. the pipeline ledger) + tsan in one payload
        must still have unique series and complete headers."""
        from torrent_tpu.analysis import sanitizer
        from torrent_tpu.obs import render_obs_metrics
        from torrent_tpu.obs.fleet import local_fleet_snapshot
        from torrent_tpu.obs.ledger import pipeline_ledger
        from torrent_tpu.sched import (
            ControlConfig,
            HashPlaneScheduler,
            SchedulerAutopilot,
            SchedulerConfig,
        )
        from torrent_tpu.obs.slo import SloEngine
        from torrent_tpu.obs.timeline import Timeline, TimelineSampler
        from torrent_tpu.server.shard import ShardedSwarmStore
        from torrent_tpu.utils.metrics import (
            render_control_metrics,
            render_fabric_metrics,
            render_fleet_metrics,
            render_sched_metrics,
            render_slo_metrics,
            render_timeline_metrics,
            render_tracker_metrics,
            render_tsan_metrics,
        )

        from torrent_tpu.serve_plane.telemetry import serve_telemetry

        pipeline_ledger().record("read", 1024, 0.01)  # ledger series live
        # activate the serve plane so its families join the payload
        serve_telemetry().on_egress("concat@1.1.1.1:1", "sendfile", 16384)
        serve_telemetry().on_choke_round(
            0.002, unchoked=1, interested=1, optimistic=None, rotated=False
        )
        sched = HashPlaneScheduler(SchedulerConfig(), hasher="cpu")
        pilot = SchedulerAutopilot(sched, ControlConfig())
        store = ShardedSwarmStore(n_shards=2)
        store.announce(b"\x01" * 20, b"\x02" * 20, "1.1.1.1", 7001, left=0)
        timeline = Timeline(depth=4)
        engine = SloEngine("availability=0.999;integrity=on")
        sampler = TimelineSampler(timeline, scheduler=sched,
                                  on_sample=engine.observe)
        sampler.sample_once()
        tl_snap = timeline.snapshot()
        tl_snap["sampler_alive"] = False
        text = (
            render_sched_metrics(sched)
            + render_fabric_metrics({"pid": 0})
            + render_fleet_metrics(local_fleet_snapshot(sched))
            + render_control_metrics(pilot.metrics_snapshot())
            + render_tracker_metrics(store.metrics_snapshot())
            + render_timeline_metrics(tl_snap)
            + render_slo_metrics(engine.report())
            + render_obs_metrics()
            + render_tsan_metrics(sanitizer.TsanState().snapshot())
        )
        prom_lint(text)
        assert "torrent_tpu_pipeline_stage_busy_seconds_total" in text
        assert "torrent_tpu_fleet_reporting 1" in text
        assert "torrent_tpu_control_enabled 1" in text
        assert "torrent_tpu_tracker_announces_total 1" in text
        # the swarm wire-plane families ride render_obs_metrics, so the
        # full bridge/MetricsServer payload carries both new families
        assert "torrent_tpu_swarm_peers " in text
        assert "torrent_tpu_peer_bytes_down_total" in text
        # the Byzantine audit/quorum families ride render_fabric_metrics
        # unconditionally (zeroed at f=0), so the concatenated payload
        # always carries them
        assert "torrent_tpu_fabric_audit_checks_total" in text
        assert "torrent_tpu_fabric_audit_mismatches_total" in text
        assert "torrent_tpu_fabric_quorum_convictions_total" in text
        assert "torrent_tpu_fabric_quorum_verifies_total" in text
        assert 'torrent_tpu_fabric_quorum_need{pid="0"} 1' in text
        # the seeder-plane families ride render_obs_metrics only once
        # the process has served (tracker-only scrapes stay lean): the
        # activation above came from the global registry poke
        assert "torrent_tpu_serve_peers" in text
        assert 'torrent_tpu_serve_bytes_total{path="sendfile"}' in text
        assert "torrent_tpu_serve_choke_round_seconds_bucket" in text

    def test_concat_omits_serve_until_active(self):
        """A process that never served renders NO torrent_tpu_serve_*
        series (checked on a private registry — the global one may have
        been activated by other tests in this session)."""
        from torrent_tpu.serve_plane.telemetry import ServeTelemetry
        from torrent_tpu.utils.metrics import render_serve_metrics

        reg = ServeTelemetry()
        assert not reg.active()
        # the render_obs_metrics gate: active() False → contributes ""
        text = render_serve_metrics(reg.snapshot())
        prom_lint(text)  # rendering a fresh one is still well-formed


class TestSwarmRenderer:
    """The swarm wire-plane renderer (obs/swarm → render_swarm_metrics):
    fresh registries, hostile/partial snapshots, and the bounded
    per-peer family's top-K + overflow contract."""

    def test_fresh_registry_renders_clean(self):
        from torrent_tpu.obs.swarm import SwarmTelemetry
        from torrent_tpu.utils.metrics import render_swarm_metrics

        text = render_swarm_metrics(SwarmTelemetry().snapshot())
        prom_lint(text)
        assert "torrent_tpu_swarm_peers 0" in text
        assert "torrent_tpu_swarm_connections_total 0" in text
        assert 'torrent_tpu_swarm_flight_triggers_total{reason="snub_storm"} 0' in text

    def test_partial_snapshot_tolerated(self):
        from torrent_tpu.utils.metrics import render_swarm_metrics

        prom_lint(render_swarm_metrics({}))
        prom_lint(render_swarm_metrics(None))
        # hostile shapes: wrong-typed sub-dicts render as zeros
        text = render_swarm_metrics(
            {"counts": {"connected": 3}, "peers": {"x": {"bytes_down": 7}},
             "overflow": None, "totals": None, "msgs": {"Piece": "bogus"}}
        )
        prom_lint(text)
        assert "torrent_tpu_swarm_peers 3" in text
        assert 'torrent_tpu_peer_bytes_down_total{peer="x"} 7' in text

    def test_peer_overflow_fold(self):
        from torrent_tpu.obs.swarm import SwarmTelemetry, TOP_PEERS
        from torrent_tpu.utils.metrics import render_swarm_metrics

        reg = SwarmTelemetry()
        n = TOP_PEERS + 5
        for i in range(n):
            key = f"p{i:02d}@10.0.0.{i}:6881"
            reg.peer_connected(key)
            reg.on_block(key, (i + 1) * 1000, 0.002)
        snap = reg.snapshot()
        assert len(snap["peers"]) == TOP_PEERS
        assert snap["overflow"]["peers"] == n - TOP_PEERS
        # named peers are the TOP transferors; the fold keeps the rest's
        # bytes and RTT observations
        assert snap["overflow"]["bytes_down"] == sum(
            (i + 1) * 1000 for i in range(n - TOP_PEERS)
        )
        assert snap["overflow"]["block_rtt"]["count"] == n - TOP_PEERS
        text = render_swarm_metrics(snap)
        prom_lint(text)
        assert text.count("torrent_tpu_peer_bytes_down_total{") == TOP_PEERS + 1
        assert 'torrent_tpu_peer_bytes_down_total{peer="overflow"}' in text


class TestServeRenderer:
    """The seeder-plane renderer (serve_plane/telemetry →
    render_serve_metrics): fresh registries, hostile/partial snapshots,
    the fixed-label egress/reject families, the choke-round histogram,
    and the per-peer top-K + overflow contract."""

    def test_fresh_registry_renders_clean(self):
        from torrent_tpu.serve_plane.telemetry import ServeTelemetry
        from torrent_tpu.utils.metrics import render_serve_metrics

        text = render_serve_metrics(ServeTelemetry().snapshot())
        prom_lint(text)
        assert "torrent_tpu_serve_peers 0" in text
        # the fixed egress/reject label sets render even at zero, so
        # dashboards see the full fallback matrix from scrape one
        assert 'torrent_tpu_serve_bytes_total{path="sendfile"} 0' in text
        assert 'torrent_tpu_serve_blocks_total{path="preadv"} 0' in text
        assert 'torrent_tpu_serve_rejects_total{reason="per_ip"} 0' in text
        assert 'torrent_tpu_serve_rejects_total{reason="choked"} 0' in text
        assert "torrent_tpu_serve_choke_rounds_total 0" in text

    def test_partial_snapshot_tolerated(self):
        from torrent_tpu.utils.metrics import render_serve_metrics

        prom_lint(render_serve_metrics({}))
        prom_lint(render_serve_metrics(None))
        # hostile shapes: wrong-typed sub-dicts render as zeros
        text = render_serve_metrics(
            {"counts": {"serving": 2}, "peers": {"x": {"bytes_up": 9}},
             "overflow": None, "paths": "bogus", "choke": None,
             "totals": {"blocks": "NaNsense"}}
        )
        prom_lint(text)
        assert "torrent_tpu_serve_peers 2" in text
        assert 'torrent_tpu_serve_peer_bytes_total{peer="x"} 9' in text

    def test_choke_round_histogram_lints(self):
        from torrent_tpu.serve_plane.telemetry import ServeTelemetry
        from torrent_tpu.utils.metrics import render_serve_metrics

        reg = ServeTelemetry()
        for d in (0.0005, 0.002, 0.03):
            reg.on_choke_round(d, unchoked=2, interested=5,
                               optimistic="o@1:1", rotated=True)
        text = render_serve_metrics(reg.snapshot())
        # prom_lint pins the _bucket/_sum/_count suffixes to a
        # histogram-typed family and the unique-series rule catches a
        # repeated le= bound
        prom_lint(text)
        assert "torrent_tpu_serve_choke_round_seconds_count 3" in text
        assert 'le="+Inf"} 3' in text
        assert "torrent_tpu_serve_unchoked 2" in text
        assert "torrent_tpu_serve_interested 5" in text
        assert "torrent_tpu_serve_optimistic_rotations_total 3" in text

    def test_peer_overflow_fold(self):
        from torrent_tpu.serve_plane.telemetry import (
            TOP_PEERS,
            ServeTelemetry,
        )
        from torrent_tpu.utils.metrics import render_serve_metrics

        reg = ServeTelemetry()
        n = TOP_PEERS + 4
        for i in range(n):
            key = f"s{i:02d}@10.0.0.{i}:6881"
            reg.peer_serving(key)
            reg.on_egress(key, "sendfile", (i + 1) * 1000)
        snap = reg.snapshot()
        assert len(snap["peers"]) == TOP_PEERS
        text = render_serve_metrics(snap)
        prom_lint(text)
        assert text.count("torrent_tpu_serve_peer_bytes_total{") == TOP_PEERS + 1
        assert 'torrent_tpu_serve_peer_bytes_total{peer="overflow"}' in text
        # the fold keeps the un-named peers' bytes: smallest uploaders
        assert f'peer="overflow"}} {sum((i + 1) * 1000 for i in range(4))}' in text


class TestLiveScrape:
    def test_scrape_during_swarm(self):
        async def go():
            rng = np.random.default_rng(80)
            payload = rng.integers(0, 256, size=200_000, dtype=np.uint8).tobytes()
            server, pump, announce_url = await start_tracker()
            m = parse_metainfo(build_torrent_bytes(payload, 32768, announce_url.encode()))
            seed = Client(ClientConfig(host="127.0.0.1"))
            leech = Client(ClientConfig(host="127.0.0.1"))
            seed.config.torrent = fast_config()
            leech.config.torrent = fast_config()
            await seed.start()
            await leech.start()
            metrics = await MetricsServer(leech).start()
            try:
                ss = Storage(MemoryStorage(), m.info)
                for off in range(0, len(payload), 65536):
                    ss.set(off, payload[off : off + 65536])
                await seed.add(m, ss)
                t = await leech.add(m, Storage(MemoryStorage(), m.info))
                await asyncio.wait_for(t.on_complete.wait(), timeout=30)

                def scrape():
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{metrics.port}/metrics", timeout=10
                    ) as r:
                        assert r.headers["Content-Type"].startswith("text/plain")
                        return r.read().decode()

                text = await asyncio.to_thread(scrape)
                families, samples = _parse(text)
                assert families["torrent_tpu_downloaded_bytes_total"] == "counter"
                ih = m.info_hash.hex()
                assert f'torrent_tpu_torrent_pieces_total{{info_hash="{ih}",name="swarm-test"}} 7' in samples
                assert f"torrent_tpu_downloaded_bytes_total {len(payload)}" in samples
                assert (
                    f'torrent_tpu_torrent_state{{info_hash="{ih}",state="seeding"}} 1'
                    in samples
                )

                def not_found():
                    try:
                        with urllib.request.urlopen(
                            f"http://127.0.0.1:{metrics.port}/other", timeout=10
                        ) as r:
                            return r.status
                    except urllib.error.HTTPError as e:
                        return e.code

                assert await asyncio.to_thread(not_found) == 404
            finally:
                metrics.close()
                await seed.close()
                await leech.close()
                server.close()
                await asyncio.wait_for(pump, 5)

        run(go())
