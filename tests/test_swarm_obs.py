"""Swarm wire-plane observability (ISSUE 15, torrent_tpu/obs/swarm).

Covers the bounded per-peer telemetry registry (message/state/RTT/depth
accounting, top-K + overflow fold, cumulative totals across drops), the
exactly-once flight-recorder triggers (snub storm, all-peers-choked,
announce failure streak), the pure snapshot builder's determinism, the
new ``recv`` pipeline-ledger stage charged by a real loopback download,
the ``/v1/swarm`` surfaces (bridge + session MetricsServer), the
``top --swarm`` renderer, the swarm SLO objectives, and the
PeerConnection rate-window fix.
"""

import asyncio
import json
import urllib.request

import numpy as np
import pytest

from torrent_tpu.codec.metainfo import parse_metainfo
from torrent_tpu.obs.recorder import flight_recorder
from torrent_tpu.obs.swarm import (
    ANNOUNCE_STREAK,
    MAX_TRACKED_PEERS,
    TOP_PEERS,
    SwarmTelemetry,
    build_swarm_snapshot,
    swarm_telemetry,
)
from torrent_tpu.session.client import Client, ClientConfig
from torrent_tpu.storage.storage import MemoryStorage, Storage

from test_session import build_torrent_bytes, fast_config, run, start_tracker


class _Clock:
    """Injectable monotonic clock for duration-accounting tests."""

    def __init__(self, t0: float = 1000.0):
        self.t = t0

    def __call__(self) -> float:
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    import torrent_tpu.obs.swarm as swarm_mod

    monkeypatch.setattr(swarm_mod.time, "monotonic", c)
    return c


class TestRegistry:
    def test_message_and_byte_accounting(self):
        reg = SwarmTelemetry()
        reg.peer_connected("a@1.1.1.1:1")
        reg.on_message("a@1.1.1.1:1", "Piece", 16384)
        reg.on_message("a@1.1.1.1:1", "Piece", 16384)
        reg.on_message("a@1.1.1.1:1", "Have", 0)
        reg.on_message("a@1.1.1.1:1", "TotallyNewMessage", 7)
        snap = reg.snapshot()
        p = snap["peers"]["a@1.1.1.1:1"]
        assert p["msgs"]["Piece"] == {"count": 2, "bytes": 32768}
        assert p["msgs"]["Have"]["count"] == 1
        # unknown kinds fold — bounded cardinality no matter the wire
        assert "TotallyNewMessage" not in p["msgs"]
        assert p["msgs"]["other"] == {"count": 1, "bytes": 7}
        assert snap["msgs"]["Piece"]["bytes"] == 32768

    def test_choke_timeline_durations(self, clock):
        reg = SwarmTelemetry()
        reg.peer_connected("a@1.1.1.1:1")
        clock.t += 10.0  # choked (spec default) for 10 s
        reg.on_state("a@1.1.1.1:1", peer_choking=False)
        clock.t += 4.0  # unchoked for 4 s
        reg.on_state("a@1.1.1.1:1", peer_choking=True, am_interested=True)
        clock.t += 1.0
        p = reg.snapshot()["peers"]["a@1.1.1.1:1"]
        tl = p["choke_timeline"]
        # 10 s initial choke + the open 1 s interval; the 4 s unchoked
        # gap does not count toward peer_choking's True-time
        assert tl["peer_choking"] == pytest.approx(11.0)
        assert tl["am_interested"] == pytest.approx(1.0)
        assert tl["transitions"] == 3
        assert p["state"] == {
            "am_choking": True, "am_interested": True,
            "peer_choking": True, "peer_interested": False,
        }
        # no-op values are not transitions
        reg.on_state("a@1.1.1.1:1", peer_choking=True)
        assert (
            reg.snapshot()["peers"]["a@1.1.1.1:1"]["choke_timeline"][
                "transitions"
            ]
            == 3
        )

    def test_rtt_depth_and_snub_redemption(self):
        reg = SwarmTelemetry()
        reg.peer_connected("a@1.1.1.1:1")
        reg.on_depth("a@1.1.1.1:1", 16)
        reg.on_depth("a@1.1.1.1:1", 4)
        reg.on_snub("a@1.1.1.1:1")
        snap = reg.snapshot()["peers"]["a@1.1.1.1:1"]
        assert snap["pipeline"] == {"depth": 4, "depth_max": 16}
        assert snap["snubbed"] and snap["snubs"] == 1
        for rtt in (0.001, 0.002, 0.004, 1.0):
            reg.on_block("a@1.1.1.1:1", 16384, rtt)
        snap = reg.snapshot()["peers"]["a@1.1.1.1:1"]
        assert not snap["snubbed"]  # delivering redeems
        assert snap["block_rtt"]["count"] == 4
        assert snap["block_rtt"]["p50_s"] is not None
        assert snap["block_rtt"]["p99_s"] >= 1.0
        assert not snap["block_rtt"]["p99_overflow"]

    def test_totals_survive_peer_drop(self):
        reg = SwarmTelemetry()
        reg.peer_connected("a@1.1.1.1:1")
        reg.on_block("a@1.1.1.1:1", 1000, 0.01)
        reg.on_upload("a@1.1.1.1:1", 500)
        reg.peer_dropped("a@1.1.1.1:1")
        snap = reg.snapshot()
        assert snap["counts"]["connected"] == 0
        # cumulative process totals never drop when a peer leaves — the
        # SLO window deltas depend on it
        assert snap["totals"]["bytes_down"] == 1000
        assert snap["totals"]["bytes_up"] == 500
        assert snap["totals"]["blocks"] == 1
        assert snap["totals"]["connections"] == 1

    def test_tracked_peer_bound_overflow_record(self):
        from test_metrics import prom_lint
        from torrent_tpu.utils.metrics import render_swarm_metrics

        reg = SwarmTelemetry(max_peers=4)
        for i in range(7):
            reg.peer_connected(f"p{i}@1.1.1.{i}:1")
            # the FOLDED peers carry the most bytes: even then the
            # shared overflow record must never rank into the named
            # top-K (that would emit peer="overflow" twice on /metrics)
            reg.on_block(f"p{i}@1.1.1.{i}:1", 100 * (7 - i), 0.001)
        snap = reg.snapshot()
        # every connection counted: 4 tracked + 3 sharing the overflow
        assert snap["counts"]["connected"] == 7
        assert snap["totals"]["connections"] == 7
        assert snap["totals"]["bytes_down"] == 100 * (7 + 6 + 5 + 4 + 3 + 2 + 1)
        assert "overflow" not in snap["peers"]
        assert snap["overflow"]["peers"] == 3
        prom_lint(render_swarm_metrics(snap))  # no duplicate series
        # folded peers leaving drain the shared record; the last one
        # removes it — the connected gauge never inflates forever
        for i in range(7):
            reg.peer_dropped(f"p{i}@1.1.1.{i}:1")
        snap = reg.snapshot()
        assert snap["counts"]["connected"] == 0
        assert snap["overflow"] is None
        assert snap["totals"]["bytes_down"] == 2800  # totals stay cumulative
        assert MAX_TRACKED_PEERS >= 4  # the default bound exists

    def test_snapshot_deterministic_bytes(self):
        raws = {
            f"p{i}": {
                "bytes_down": i * 100, "blocks": i, "rtt_counts": [i, 0, 2],
                "rtt_count": i + 2, "rtt_sum": 0.5, "state": {"peer_choking": True},
                "flag_true_s": {"peer_choking": 1.5},
            }
            for i in range(TOP_PEERS + 3)
        }
        totals = {"blocks": 9, "connections": 11}
        a = json.dumps(build_swarm_snapshot(raws, totals), sort_keys=True)
        b = json.dumps(build_swarm_snapshot(dict(reversed(raws.items())), totals),
                       sort_keys=True)
        assert a == b  # input dict order never reaches the bytes


class TestTriggers:
    def test_snub_storm_exactly_once_and_rearm(self):
        reg = SwarmTelemetry()
        base = flight_recorder().counts().get("snub_storm", 0)
        for i in range(4):
            reg.peer_connected(f"p{i}@2.2.2.{i}:1")
        reg.on_snub("p0@2.2.2.0:1")
        assert flight_recorder().counts().get("snub_storm", 0) == base  # 1/4 < half
        reg.on_snub("p1@2.2.2.1:1")
        assert flight_recorder().counts().get("snub_storm", 0) == base + 1
        reg.on_snub("p2@2.2.2.2:1")  # storm holds: no re-fire
        assert flight_recorder().counts().get("snub_storm", 0) == base + 1
        # delivery clears two snub flags -> storm clears -> re-snub fires
        reg.on_block("p0@2.2.2.0:1", 1, 0.001)
        reg.on_block("p1@2.2.2.1:1", 1, 0.001)
        reg.on_block("p2@2.2.2.2:1", 1, 0.001)
        reg.on_snub("p0@2.2.2.0:1")
        reg.on_snub("p1@2.2.2.1:1")
        assert flight_recorder().counts().get("snub_storm", 0) == base + 2
        assert reg.snapshot()["triggers"]["snub_storm"] == 2

    def test_all_peers_choked_gated_on_transfer(self):
        reg = SwarmTelemetry()
        base = flight_recorder().counts().get("all_peers_choked", 0)
        reg.peer_connected("a@3.3.3.1:1")
        reg.peer_connected("b@3.3.3.2:1")
        # startup: everything choked by spec default + we get interested
        # — must NOT fire (no transfer was underway)
        reg.on_state("a@3.3.3.1:1", am_interested=True)
        assert flight_recorder().counts().get("all_peers_choked", 0) == base
        # blocks flow, then the swarm chokes us → fires once
        reg.on_state("a@3.3.3.1:1", peer_choking=False)
        reg.on_block("a@3.3.3.1:1", 1, 0.001)
        reg.on_state("a@3.3.3.1:1", peer_choking=True)
        assert flight_recorder().counts().get("all_peers_choked", 0) == base + 1
        reg.on_state("b@3.3.3.2:1", peer_interested=True)  # still all-choked
        assert flight_recorder().counts().get("all_peers_choked", 0) == base + 1

    def test_announce_streaks_are_per_origin(self):
        """One torrent's healthy tracker must never mask another's dead
        one: streaks key on the announcing torrent's origin."""
        reg = SwarmTelemetry()
        base = flight_recorder().counts().get("announce_failure_streak", 0)
        for i in range(ANNOUNCE_STREAK):
            reg.on_announce(False, origin="swarm-dead")
            # torrent B's interleaved successes must not reset A's streak
            reg.on_announce(True, origin="swarm-healthy")
        assert (
            flight_recorder().counts().get("announce_failure_streak", 0)
            == base + 1
        )
        assert reg.snapshot()["totals"]["announce_streak"] == ANNOUNCE_STREAK

    def test_announce_failure_streak_exactly_once(self):
        reg = SwarmTelemetry()
        base = flight_recorder().counts().get("announce_failure_streak", 0)
        for _ in range(ANNOUNCE_STREAK - 1):
            reg.on_announce(False)
        assert (
            flight_recorder().counts().get("announce_failure_streak", 0) == base
        )
        reg.on_announce(False)  # crosses the streak
        assert (
            flight_recorder().counts().get("announce_failure_streak", 0)
            == base + 1
        )
        reg.on_announce(False)  # deeper into the same streak: no re-fire
        assert (
            flight_recorder().counts().get("announce_failure_streak", 0)
            == base + 1
        )
        reg.on_announce(True)  # re-arms
        for _ in range(ANNOUNCE_STREAK):
            reg.on_announce(False)
        assert (
            flight_recorder().counts().get("announce_failure_streak", 0)
            == base + 2
        )
        totals = reg.snapshot()["totals"]
        assert totals["announce_ok"] == 1
        assert totals["announce_failed"] == 2 * ANNOUNCE_STREAK + 1


class TestRateWindow:
    """ISSUE 15 small-fix satellite: PeerConnection.snapshot_rate's
    window anchors — rates feed the choke policy AND the telemetry, so
    a wrong window poisons both."""

    def _peer(self):
        from torrent_tpu.session.peer import PeerConnection

        class _W:
            def close(self):
                pass

        return PeerConnection(
            peer_id=b"x" * 20, reader=None, writer=_W(), num_pieces=4
        )

    def test_initial_window_anchored_at_construction(self, monkeypatch):
        import torrent_tpu.session.peer as peer_mod

        t = _Clock(5000.0)
        monkeypatch.setattr(peer_mod.time, "monotonic", t)
        p = self._peer()
        # a peer that delivered 1 MiB in its first 2 seconds must report
        # ~512 KiB/s — NOT bytes/monotonic-uptime (the old (0.0, 0)
        # default made every fresh connection's rate read as ~zero)
        p.bytes_down += 1 << 20
        t.t += 2.0
        assert p.download_rate() == pytest.approx((1 << 20) / 2.0)

    def test_snapshot_resets_window(self, monkeypatch):
        import torrent_tpu.session.peer as peer_mod

        t = _Clock(5000.0)
        monkeypatch.setattr(peer_mod.time, "monotonic", t)
        p = self._peer()
        p.bytes_down += 1000
        p.bytes_up += 4000
        t.t += 1.0
        p.snapshot_rate()
        # the old window's bytes are gone; only post-snapshot deltas count
        t.t += 2.0
        assert p.download_rate() == 0.0
        p.bytes_down += 500
        p.bytes_up += 900
        t.t += 0.5
        # marks were taken at t=5001: window is 2.5s, not 0.5s
        assert p.download_rate() == pytest.approx(500 / 2.5)
        assert p.upload_rate() == pytest.approx(900 / 2.5)

    def test_zero_dt_guard(self, monkeypatch):
        import torrent_tpu.session.peer as peer_mod

        t = _Clock(5000.0)
        monkeypatch.setattr(peer_mod.time, "monotonic", t)
        p = self._peer()
        p.snapshot_rate()
        p.bytes_down += 100
        assert p.download_rate() == 0.0  # dt == 0 never divides


class TestSwarmSlo:
    def _samples(self, rows):
        return [
            {"t": float(t), "swarm": dict(sw)} for t, sw in rows
        ]

    def test_snub_ratio_burns_and_clears(self):
        from torrent_tpu.obs.slo import evaluate_slo, parse_objectives

        objs = parse_objectives("swarm_snub=0.99")
        # 8 snubs against 2 blocks: error ratio 0.8 >> the 0.01 budget
        bad = self._samples([
            (1.0, {"snubs": 0, "blocks": 0}),
            (2.0, {"snubs": 8, "blocks": 2}),
        ])
        rep = evaluate_slo(bad, objs, short_samples=4, long_samples=8)
        obj = rep["objectives"]["swarm_availability"]
        assert obj["breach"] and obj["classification"] == "fast_burn"
        # a clean swarm never burns
        good = self._samples([
            (1.0, {"snubs": 0, "blocks": 0}),
            (2.0, {"snubs": 0, "blocks": 500}),
        ])
        rep = evaluate_slo(good, objs, short_samples=4, long_samples=8)
        assert rep["objectives"]["swarm_availability"]["burn_rate"] == 0.0

    def test_download_floor_burns_only_active_intervals(self):
        from torrent_tpu.obs.slo import evaluate_slo, parse_objectives

        objs = parse_objectives("swarm_floor_mibps=1")
        samples = self._samples([
            (1.0, {"bytes_down": 0, "blocks": 0}),
            # active interval at 100 KiB/s — under the 1 MiB/s floor
            (2.0, {"bytes_down": 100 * 1024, "blocks": 10}),
            # idle interval (no blocks moved): never burns
            (3.0, {"bytes_down": 100 * 1024, "blocks": 10}),
        ])
        rep = evaluate_slo(samples, objs, short_samples=4, long_samples=8)
        obj = rep["objectives"]["swarm_throughput"]
        assert obj["errors"] == 1 and obj["events"] == 1
        assert obj["burn_rate"] > 1.0
        fast = self._samples([
            (1.0, {"bytes_down": 0, "blocks": 0}),
            (2.0, {"bytes_down": 8 << 20, "blocks": 100}),
        ])
        rep = evaluate_slo(fast, objs, short_samples=4, long_samples=8)
        assert rep["objectives"]["swarm_throughput"]["burn_rate"] == 0.0

    def test_sample_now_carries_swarm_once_active(self):
        from torrent_tpu.obs.timeline import sample_now

        reg = swarm_telemetry()
        if not reg.active():
            reg.peer_connected("slo@9.9.9.9:1")
            reg.on_block("slo@9.9.9.9:1", 64, 0.001)
            reg.peer_dropped("slo@9.9.9.9:1")
        sample = sample_now()
        assert "swarm" in sample
        assert sample["swarm"]["blocks"] >= 1
        assert set(sample["swarm"]) >= {
            "peers", "snubbed", "bytes_down", "blocks", "snubs", "all_choked",
        }


class TestTopRender:
    def _payload(self):
        return {
            "counts": {"connected": 2, "snubbed": 1},
            "totals": {"bytes_down": 5 << 20, "bytes_up": 1 << 20,
                       "announce_ok": 4, "announce_failed": 2,
                       "announce_streak": 2},
            "peers": {
                "aa@10.0.0.1:6881": {
                    "state": {"peer_choking": True, "am_choking": False,
                              "peer_interested": True, "am_interested": True},
                    "pipeline": {"depth": 16, "depth_max": 16},
                    "blocks": 320, "bytes_down": 5 << 20, "bytes_up": 0,
                    "block_rtt": {"p99_s": 0.0039, "count": 320,
                                  "p99_overflow": False},
                    "snubbed": True, "snubs": 1,
                },
            },
            "overflow": {"peers": 3, "bytes_down": 123456, "snubbed": 1},
            "triggers": {"snub_storm": 1},
        }

    def test_render_swarm_frame(self):
        from torrent_tpu.tools.top import render_swarm

        frame = render_swarm(self._payload(), url="http://x:1")
        assert "2 peers (1 snubbed)" in frame
        assert "aa@10.0.0.1:6881" in frame
        assert "C-Ii*" in frame  # flags: peer choking, interested both ways, snubbed
        assert "3.9 ms" in frame
        assert "(+3 more peers" in frame
        assert "announces: 4 ok / 2 failed (streak 2)" in frame
        assert "snub_storm×1" in frame

    def test_render_swarm_idle_and_hostile(self):
        from torrent_tpu.tools.top import render_swarm

        frame = render_swarm({})
        assert "swarm idle" in frame
        render_swarm({"peers": {"x": {}}, "overflow": None, "counts": None})


class TestLoopbackWire:
    """The tentpole end-to-end: a real loopback download charges the
    recv ledger stage, populates the per-peer registry, emits lifecycle
    spans, and serves /v1/swarm from the session MetricsServer."""

    def test_download_charges_recv_and_populates_registry(self):
        from torrent_tpu.obs.ledger import pipeline_ledger
        from torrent_tpu.obs.tracer import tracer
        from torrent_tpu.utils.metrics import MetricsServer

        async def go():
            rng = np.random.default_rng(41)
            payload = rng.integers(0, 256, size=180_000, dtype=np.uint8).tobytes()
            prev = pipeline_ledger().snapshot()
            base_totals = swarm_telemetry().snapshot()["totals"]
            server, pump, announce_url = await start_tracker()
            m = parse_metainfo(
                build_torrent_bytes(payload, 32768, announce_url.encode())
            )
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

                # (a) recv stage: the download's bytes reached the ledger
                snap = pipeline_ledger().snapshot()
                recv = snap["stages"].get("recv") or {}
                prev_recv = (prev.get("stages") or {}).get("recv") or {}
                assert recv.get("bytes", 0) - prev_recv.get("bytes", 0) >= len(
                    payload
                )

                # (b) the registry saw both ends of the loopback pair
                swarm = swarm_telemetry().snapshot()
                assert swarm["counts"]["connected"] >= 2
                heavy = [
                    p for p in swarm["peers"].values()
                    if p["bytes_down"] >= len(payload)
                ]
                assert heavy, "no peer accounts the downloaded payload"
                assert heavy[0]["block_rtt"]["count"] > 0
                assert heavy[0]["msgs"]["Piece"]["count"] > 0
                assert (
                    swarm["totals"]["bytes_down"]
                    - base_totals.get("bytes_down", 0)
                    >= len(payload)
                )
                # every piece arrived as at least one counted block
                assert (
                    swarm["totals"]["blocks"] - base_totals.get("blocks", 0)
                    >= m.info.num_pieces
                )

                # (c) connection lifecycle spans under the deterministic
                # per-torrent swarm trace
                trace_id = f"swarm-{m.info_hash.hex()[:12]}"
                tree = tracer().trace_tree(trace_id)
                assert tree is not None
                names = {s["name"] for s in tree["spans"]}
                assert "swarm.peer.connect" in names

                # (d) GET /v1/swarm on the session MetricsServer
                def fetch():
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{metrics.port}/v1/swarm", timeout=10
                    ) as r:
                        assert r.headers["Content-Type"] == "application/json"
                        return json.loads(r.read().decode())

                payload_json = await asyncio.to_thread(fetch)
                assert payload_json["counts"]["connected"] >= 2
                assert "overflow" in payload_json

                # (e) the swarm families ride the session /metrics scrape
                def scrape():
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{metrics.port}/metrics", timeout=10
                    ) as r:
                        return r.read().decode()

                text = await asyncio.to_thread(scrape)
                assert "torrent_tpu_swarm_peers " in text
                assert 'torrent_tpu_peer_bytes_down_total{peer="' in text
                assert "torrent_tpu_swarm_block_rtt_seconds_bucket" in text
            finally:
                metrics.close()
                await seed.close()
                await leech.close()
                server.close()
                await asyncio.wait_for(pump, 5)

        run(go())

    def test_bridge_serves_v1_swarm(self):
        from torrent_tpu.bridge.service import BridgeServer

        async def go():
            svc = await BridgeServer("127.0.0.1", port=0, hasher="cpu").start()
            try:
                def fetch():
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{svc.port}/v1/swarm", timeout=10
                    ) as r:
                        assert r.headers["Content-Type"] == "application/json"
                        return json.loads(r.read().decode())

                payload = await asyncio.to_thread(fetch)
                # shape contract, even on an idle hash-plane sidecar
                assert set(payload) >= {
                    "counts", "peers", "overflow", "totals", "msgs", "triggers",
                }
            finally:
                svc.close()
                await svc.wait_closed()

        run(go())
