"""Scheduler autopilot (torrent_tpu/sched/control.py).

Covers the PR-11 observe→act loop:

* the pure decision core: grow/shrink laws, hysteresis (a flapping
  attribution verdict must leave every actuator untouched — ISSUE
  acceptance), admission shrink/recovery, the backend trial protocol
  (switch once, evaluate, revert-and-pin — no oscillation),
  determinism (same snapshot sequence → same decision sequence)
* the scheduler's actuator seams: tile-snapped ``set_lane_target``,
  per-lane deadlines, the effective admission budget, backend steering
  rebuilding the plane (and the cpu steer bypassing ``plane_factory``
  exactly like the breaker's fallback)
* controller-off bit-identical static behavior (ISSUE acceptance)
* end to end: under ``sched/faults.py`` throttles (``latency_ms`` h2d,
  the new ``read_latency_ms``) the controller names the limiting stage
  and moves the named actuators toward it
* the fabric rebalance hook: the laggard's offer list, peers adopting
  offered units through the ordinary adoption/trust path
* surfaces: ``GET /v1/control``, ``torrent_tpu_control_*`` rendering,
  the ``torrent-tpu top`` decision line
"""

from __future__ import annotations

import asyncio
import hashlib
import json

import pytest

from torrent_tpu.sched import (
    ControlConfig,
    FaultPlan,
    HashPlaneScheduler,
    SchedRejected,
    SchedulerAutopilot,
    SchedulerConfig,
)
from torrent_tpu.sched.control import build_inputs, decide, initial_state

from test_metrics import prom_lint


def run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


# ------------------------------------------------------- input builders


def mk_inputs(
    stage=None,
    util=0.9,
    headroom=5.0,
    achieved=1_000_000.0,
    launch_bps=5_000_000.0,
    fill=1.0,
    launches=4,
    target=8,
    base_target=8,
    afford=4096,
    deadline=0.02,
    backend="device",
    qw=1.0,
    factor=1.0,
    maxq=1 << 28,
    lane="sha1/262144",
    granule=1,
):
    rep = {
        "wall_s": 1.0,
        "stages": {"launch": {"achieved_bps": launch_bps}},
        "bottleneck": None,
    }
    if stage is not None:
        rep["bottleneck"] = {
            "stage": stage,
            "utilization": util,
            "achieved_bps": achieved,
            "demanded_bps": achieved * headroom if headroom else None,
            "headroom": headroom,
        }
    return {
        "attribution": rep,
        "lanes": {
            lane: {
                "backend": backend,
                "bucket": 262144,
                "granule": granule,
                "target": target,
                "base_target": base_target,
                "afford": afford,
                "deadline": deadline,
                "base_deadline": 0.02,
                "pending": 0,
                "launches": launches,
                "fill": fill,
                # the per-lane rate the backend trial judges against
                "launch_bps": launch_bps,
            }
        },
        "queue_wait_mean_s": qw,
        "admission": {"factor": factor, "max_queue_bytes": maxq, "queue_bytes": 0},
    }


class TestDecideLaws:
    def test_grow_waits_for_hysteresis_then_fires(self):
        cfg = ControlConfig(hysteresis_ticks=2, cooldown_ticks=0)
        state = initial_state()
        d1, state = decide(mk_inputs(stage="h2d"), state, cfg)
        assert d1["actions"] == []  # streak 1 < hysteresis 2
        d2, state = decide(mk_inputs(stage="h2d"), state, cfg)
        kinds = {a["actuator"] for a in d2["actions"]}
        assert "batch_target" in kinds and "admission" in kinds
        bt = next(a for a in d2["actions"] if a["actuator"] == "batch_target")
        assert bt["from"] == 8 and bt["to"] == 16
        assert d2["bottleneck"]["confirmed"] is True

    def test_flapping_verdict_leaves_actuators_stable(self):
        """ISSUE acceptance: a synthetic snapshot sequence alternating
        the limiting stage between two stages must produce ZERO actuator
        moves under hysteresis."""
        cfg = ControlConfig(hysteresis_ticks=2, cooldown_ticks=0)
        state = initial_state()
        stages = ["h2d", "read", "h2d", "read", "h2d", "read"]
        for s in stages:
            d, state = decide(mk_inputs(stage=s), state, cfg)
            assert d["actions"] == [], f"flapping verdict moved actuators: {d}"
            assert not (d["bottleneck"] or {}).get("confirmed")

    def test_shrink_on_low_fill_returns_toward_plan(self):
        cfg = ControlConfig(hysteresis_ticks=2, cooldown_ticks=0)
        state = initial_state()
        d, state = decide(
            mk_inputs(stage=None, fill=0.1, target=64, base_target=8),
            state,
            cfg,
        )
        bt = next(a for a in d["actions"] if a["actuator"] == "batch_target")
        assert bt["from"] == 64 and bt["to"] == 32

    def test_grow_bounded_by_afford_and_max_factor(self):
        cfg = ControlConfig(hysteresis_ticks=1, cooldown_ticks=0)
        state = initial_state()
        # afford caps below target*2
        d, state = decide(
            mk_inputs(stage="h2d", target=8, base_target=8, afford=12),
            state,
            cfg,
        )
        bt = next(a for a in d["actions"] if a["actuator"] == "batch_target")
        assert bt["to"] == 12
        # at the max-factor ceiling nothing grows
        state = initial_state()
        d, state = decide(
            mk_inputs(stage="h2d", target=64, base_target=8), state, cfg
        )
        assert not [a for a in d["actions"] if a["actuator"] == "batch_target"]

    def test_grow_cap_snaps_to_granule_no_chatter(self):
        """A tiled lane whose target already sits at the largest
        granule multiple under the cap must not get endless grow
        proposals the scheduler's snap would round straight back."""
        cfg = ControlConfig(hysteresis_ticks=1, cooldown_ticks=0)
        state = initial_state()
        for _ in range(3):
            d, state = decide(
                mk_inputs(stage="h2d", target=2048, base_target=512,
                          afford=3000, granule=1024),
                state, cfg,
            )
            assert not [
                a for a in d["actions"] if a["actuator"] == "batch_target"
            ], d["actions"]

    def test_admission_floor_then_recovery(self):
        cfg = ControlConfig(hysteresis_ticks=1, cooldown_ticks=0)
        state = initial_state()
        # tiny achieved rate vs a big budget: factor goes to the floor
        d, state = decide(
            mk_inputs(stage="h2d", achieved=1000.0), state, cfg
        )
        adm = next(a for a in d["actions"] if a["actuator"] == "admission")
        assert adm["to"] == cfg.admission_floor
        # verdict clears: the budget recovers by doubling
        d, state = decide(mk_inputs(stage=None, factor=0.25), state, cfg)
        adm = next(a for a in d["actions"] if a["actuator"] == "admission")
        assert adm["from"] == 0.25 and adm["to"] == 0.5

    def test_admission_recovers_after_flap_not_just_on_idle(self):
        """A flapping (never-confirming) verdict must not strand the
        admission budget at the floor: recovery keys on the last
        CONFIRMED tick, so after a cooldown of unconfirmed ticks the
        factor climbs back to 1.0 and rests there."""
        cfg = ControlConfig(hysteresis_ticks=2, cooldown_ticks=1)
        state = initial_state()
        # confirm h2d and shrink to the floor
        for _ in range(2):
            d, state = decide(
                mk_inputs(stage="h2d", achieved=1000.0), state, cfg
            )
        assert [a for a in d["actions"] if a["actuator"] == "admission"]
        factor = cfg.admission_floor
        # verdict flaps; after the cooldown recovery fires each tick
        recovered = []
        for s in ("read", "h2d", "read", "h2d", "read"):
            d, state = decide(
                mk_inputs(stage=s, achieved=1000.0, factor=factor), state, cfg
            )
            for a in d["actions"]:
                assert a["actuator"] == "admission"
                factor = a["to"]
                recovered.append(factor)
        assert recovered and recovered[-1] == 1.0
        # at 1.0 the continuing flap produces no further movement
        # (stable endpoint; "h2d" keeps alternating so nothing confirms)
        d, state = decide(mk_inputs(stage="h2d", factor=1.0), state, cfg)
        assert not [a for a in d["actions"] if a["actuator"] == "admission"]

    def test_backend_trial_extends_over_idle_interval(self):
        """A trial evaluated during a zero-traffic interval must not
        phantom-revert: it extends until a with-traffic interval
        actually measures the new backend."""
        cfg = ControlConfig(hysteresis_ticks=1, cooldown_ticks=1)
        state = initial_state()
        d1, state = decide(
            mk_inputs(stage="launch", backend="scan", launch_bps=1000.0),
            state, cfg,
        )
        assert [a for a in d1["actions"] if a["actuator"] == "backend"]
        d2, state = decide(
            mk_inputs(stage="launch", backend="pallas"), state, cfg
        )
        # evaluation tick, but the lane saw NO traffic: trial persists
        d3, state = decide(
            mk_inputs(stage=None, backend="pallas", launches=0, fill=None,
                      launch_bps=None),
            state, cfg,
        )
        assert not [a for a in d3["actions"] if a["actuator"] == "backend"]
        assert state["lanes"]["sha1/262144"]["backend_trial"] is not None
        # traffic returns with a 2x better rate: kept and pinned
        d4, state = decide(
            mk_inputs(stage=None, backend="pallas", launch_bps=2000.0),
            state, cfg,
        )
        assert not [a for a in d4["actions"] if a["actuator"] == "backend"]
        assert state["lanes"]["sha1/262144"]["backend_trial"] is None
        assert state["lanes"]["sha1/262144"]["backend_pinned"] is True

    def test_unconfirmed_verdict_never_shrinks_admission(self):
        cfg = ControlConfig(hysteresis_ticks=3, cooldown_ticks=0)
        state = initial_state()
        for _ in range(2):  # streak stays under 3
            d, state = decide(
                mk_inputs(stage="h2d", achieved=1000.0), state, cfg
            )
            assert not [a for a in d["actions"] if a["actuator"] == "admission"]

    def test_backend_trial_revert_and_pin(self):
        """Launch-limited lane: switch once, evaluate after the
        cooldown, revert when nothing improved, then PIN — further
        launch-limited ticks must not oscillate the backend."""
        cfg = ControlConfig(hysteresis_ticks=1, cooldown_ticks=1)
        state = initial_state()
        inp = lambda backend: mk_inputs(  # noqa: E731
            stage="launch", backend=backend, fill=0.5, launch_bps=1000.0
        )
        d1, state = decide(inp("scan"), state, cfg)
        sw = [a for a in d1["actions"] if a["actuator"] == "backend"]
        assert sw and sw[0]["to"] == "pallas"
        # cooldown tick: trial still accumulating, no action
        d2, state = decide(inp("pallas"), state, cfg)
        assert not [a for a in d2["actions"] if a["actuator"] == "backend"]
        # evaluation tick: launch_bps did not improve -> revert
        d3, state = decide(inp("pallas"), state, cfg)
        rv = [a for a in d3["actions"] if a["actuator"] == "backend"]
        assert rv and rv[0]["to"] == "scan"
        # pinned: persistent launch verdicts change nothing further
        for _ in range(4):
            d, state = decide(inp("scan"), state, cfg)
            assert not [a for a in d["actions"] if a["actuator"] == "backend"]

    def test_backend_trial_kept_when_improved(self):
        cfg = ControlConfig(hysteresis_ticks=1, cooldown_ticks=1)
        state = initial_state()
        d1, state = decide(
            mk_inputs(stage="launch", backend="device", launch_bps=1000.0),
            state, cfg,
        )
        assert [a for a in d1["actions"] if a["actuator"] == "backend"]
        d2, state = decide(
            mk_inputs(stage="launch", backend="cpu", launch_bps=1000.0),
            state, cfg,
        )
        # evaluation with a 10x better achieved rate: keep (no revert)
        d3, state = decide(
            mk_inputs(stage="launch", backend="cpu", launch_bps=10_000.0),
            state, cfg,
        )
        assert not [a for a in d3["actions"] if a["actuator"] == "backend"]
        assert state["lanes"]["sha1/262144"]["backend_pinned"] is True

    def test_observe_only_runs_no_backend_trials(self):
        """A disabled (observe-only) controller must not record phantom
        backend trials: the trial protocol interprets the next interval
        as the new backend's performance, which is meaningless when the
        steer was never applied."""
        cfg = ControlConfig(enabled=False, hysteresis_ticks=1, cooldown_ticks=0)
        state = initial_state()
        for _ in range(4):
            d, state = decide(
                mk_inputs(stage="launch", backend="scan"), state, cfg
            )
            assert not [a for a in d["actions"] if a["actuator"] == "backend"]
            assert not state["lanes"].get("sha1/262144", {}).get("backend_trial")

    def test_cpu_backend_has_no_alternative(self):
        cfg = ControlConfig(hysteresis_ticks=1, cooldown_ticks=0)
        state = initial_state()
        d, state = decide(
            mk_inputs(stage="launch", backend="cpu"), state, cfg
        )
        assert not [a for a in d["actions"] if a["actuator"] == "backend"]

    def test_decide_is_deterministic(self):
        """Same snapshot sequence → bit-identical decision sequence
        (the property the analysis determinism pass guards)."""
        seq = [
            mk_inputs(stage="h2d"),
            mk_inputs(stage="h2d", target=16),
            mk_inputs(stage=None, fill=0.2, target=32),
            mk_inputs(stage="launch", backend="scan"),
        ]
        cfg = ControlConfig(hysteresis_ticks=2, cooldown_ticks=1)

        def fold():
            out, state = [], initial_state()
            for inp in seq:
                d, state = decide(inp, state, cfg)
                out.append(d)
            return json.dumps(out, sort_keys=True)

        assert fold() == fold()

    def test_low_utilization_is_not_a_bottleneck(self):
        cfg = ControlConfig(hysteresis_ticks=1, cooldown_ticks=0)
        state = initial_state()
        d, _ = decide(mk_inputs(stage="h2d", util=0.3), state, cfg)
        assert d["bottleneck"] is None and d["actions"] == []


class TestBuildInputs:
    def test_lane_deltas_and_queue_wait_mean(self):
        surface = {
            "lanes": {
                "sha1/1024": {
                    "backend": "cpu", "bucket": 1024, "target": 8,
                    "base_target": 8,
                    "afford": 512, "deadline": 0.02, "base_deadline": 0.02,
                    "pending": 0, "launches": 10, "fill_sum": 9.0,
                }
            },
            "admission": {"factor": 1.0, "max_queue_bytes": 100, "queue_bytes": 0},
        }
        prev = {
            "lanes": {
                "sha1/1024": {"launches": 6, "fill_sum": 6.0}
            },
            "admission": {},
        }
        led = {"stages": {}, "t_first": 0.0, "t_last": 1.0, "t_snap": 1.0}
        inp = build_inputs(
            led, None, surface, prev,
            qw_snap=([0] * 25, 10, 2.0), prev_qw=([0] * 25, 4, 0.8),
        )
        lane = inp["lanes"]["sha1/1024"]
        assert lane["launches"] == 4
        assert lane["fill"] == pytest.approx(0.75)
        # per-lane launch rate: d_fill × target × bucket / wall
        assert lane["launch_bps"] == pytest.approx(3.0 * 8 * 1024 / 1.0)
        assert inp["queue_wait_mean_s"] == pytest.approx(0.2)

    def test_no_traffic_means_no_fill(self):
        surface = {
            "lanes": {
                "sha1/1024": {
                    "backend": "cpu", "target": 8, "base_target": 8,
                    "afford": 512, "deadline": 0.02, "base_deadline": 0.02,
                    "pending": 0, "launches": 3, "fill_sum": 3.0,
                }
            },
            "admission": {},
        }
        inp = build_inputs({"stages": {}}, None, surface, surface)
        assert inp["lanes"]["sha1/1024"]["launches"] == 0
        assert inp["lanes"]["sha1/1024"]["fill"] is None


# --------------------------------------------------- scheduler actuators


class _GeomPlane:
    """Fake plane with a tile-snapping geometry hook (1024-row granule)."""

    def __init__(self, algo):
        self._h = hashlib.sha256 if algo == "sha256" else hashlib.sha1

    @staticmethod
    def launch_geometry(n_rows: int, bucket: int):
        rows = (n_rows + 1023) // 1024 * 1024
        return rows, rows * bucket

    def run(self, payloads):
        return [self._h(bytes(p)).digest() for p in payloads]


class TestActuatorSeams:
    def test_set_lane_target_snaps_via_geometry_hook(self):
        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=8, flush_deadline=0.01,
                    plane_factory=lambda algo, bucket, batch: _GeomPlane(algo),
                ),
                hasher="cpu",
            )
            await sched.start()
            try:
                pieces = [bytes([i]) * 64 for i in range(4)]
                want = [hashlib.sha1(p).digest() for p in pieces]
                assert await sched.submit("t", pieces) == want  # builds plane
                got = sched.set_lane_target("sha1/64", 100)
                assert got == 1024  # snapped up to the tile granule
                assert sched.set_lane_target("nope/1", 5) is None
            finally:
                await sched.close()

        run(go())

    def test_set_lane_target_snap_never_exceeds_staging_afford(self):
        """The geometry hook snaps UP; when that would overrun the
        staging afford the applied target rounds DOWN to the largest
        granule multiple (or the raw afford when not even one granule
        fits) — the lane plan's own round-down discipline."""
        async def go():
            # afford = 320000 / padded_len(64)=128 -> 2500 rows
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=8, flush_deadline=0.01,
                    staging_budget=320000,
                    plane_factory=lambda algo, bucket, batch: _GeomPlane(algo),
                ),
                hasher="tpu",
            )
            await sched.start()
            try:
                pieces = [bytes([i]) * 64 for i in range(4)]
                want = [hashlib.sha1(p).digest() for p in pieces]
                assert await sched.submit("t", pieces) == want
                # within afford: plain snap up
                assert sched.set_lane_target("sha1/64", 100) == 1024
                # 3000 clamps to afford 2500, snap-up 3072 overruns ->
                # round down to the 1024 granule
                assert sched.set_lane_target("sha1/64", 3000) == 2048
            finally:
                await sched.close()

            # afford (500) smaller than one granule: the budget beats
            # the tiling and the raw afford stands
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=8, flush_deadline=0.01,
                    staging_budget=128 * 500,
                    plane_factory=lambda algo, bucket, batch: _GeomPlane(algo),
                ),
                hasher="tpu",
            )
            await sched.start()
            try:
                pieces = [bytes([i]) * 64 for i in range(4)]
                want = [hashlib.sha1(p).digest() for p in pieces]
                assert await sched.submit("t", pieces) == want
                assert sched.set_lane_target("sha1/64", 2000) == 500
            finally:
                await sched.close()

        run(go())

    def test_set_lane_deadline_and_snapshot(self):
        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=4, flush_deadline=0.01),
                hasher="cpu",
            )
            await sched.start()
            try:
                await sched.submit("t", [b"x" * 64])
                assert sched.set_lane_deadline("sha1/64", 0.25) == 0.25
                snap = sched.metrics_snapshot()
                assert snap["lane_stats"]["sha1/64"]["deadline"] == 0.25
                surface = sched.control_surface()
                assert surface["lanes"]["sha1/64"]["deadline"] == 0.25
            finally:
                await sched.close()

        run(go())

    def test_admission_factor_scales_the_shed_threshold(self):
        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=4, flush_deadline=0.01,
                    max_queue_bytes=1 << 20, max_tenant_bytes=1 << 20,
                ),
                hasher="cpu",
            )
            await sched.start()
            try:
                big = [b"z" * (200 << 10)]  # 200 KiB
                # factor 0.1 -> ~105 KiB effective budget: shed
                assert sched.set_admission_factor(0.1) == 0.1
                with pytest.raises(SchedRejected):
                    await sched.enqueue("t", big)
                # restored: the same submission is admitted
                sched.set_admission_factor(1.0)
                fut = await sched.enqueue("t", big)
                assert await fut == [hashlib.sha1(big[0]).digest()]
            finally:
                await sched.close()

        run(go())

    def test_steer_backend_rebuilds_plane_and_cpu_bypasses_factory(self):
        calls: list[tuple] = []

        def factory(algo, bucket, batch, sha256_backend=None):
            calls.append((algo, sha256_backend))
            return _GeomPlane(algo)

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=4, flush_deadline=0.01,
                    plane_factory=factory, sha256_backend="scan",
                ),
                hasher="tpu",
            )
            await sched.start()
            try:
                pieces = [bytes([i + 1]) * 64 for i in range(2)]
                want = [hashlib.sha256(p).digest() for p in pieces]
                got = await sched.submit("t", pieces, algo="sha256",
                                         piece_length=64)
                assert got == want
                assert calls == [("sha256", "scan")]
                # steering to pallas rebuilds through the factory with
                # the new backend pin
                assert sched.steer_lane_backend("sha256/64", "pallas") == "pallas"
                assert sched.steer_lane_backend("sha256/64", "pallas") is None
                got = await sched.submit("t", pieces, algo="sha256",
                                         piece_length=64)
                assert got == want
                assert calls == [("sha256", "scan"), ("sha256", "pallas")]
                # the cpu steer bypasses the factory entirely (hashlib
                # floor, same contract as the breaker's fallback)
                assert sched.steer_lane_backend("sha256/64", "cpu") == "cpu"
                got = await sched.submit("t", pieces, algo="sha256",
                                         piece_length=64)
                assert got == want
                assert len(calls) == 2
                with pytest.raises(ValueError):
                    sched.steer_lane_backend("sha256/64", "warp")
            finally:
                await sched.close()

        run(go())


# ------------------------------------------------- controller off = static


class TestControllerOff:
    def test_disabled_pilot_applies_nothing(self):
        async def go():
            plan = FaultPlan.parse("latency_ms=30")
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=8, flush_deadline=0.02,
                    plane_factory=plan.plane_factory(hasher="cpu"),
                ),
                hasher="cpu",
            )
            await sched.start()
            pilot = SchedulerAutopilot(
                sched,
                ControlConfig(enabled=False, hysteresis_ticks=1,
                              cooldown_ticks=0),
            )
            try:
                pieces = [bytes([i]) * 512 for i in range(32)]
                want = [hashlib.sha1(p).digest() for p in pieces]
                pilot.tick()
                for _ in range(2):
                    assert await sched.submit("t", pieces) == want
                    last = pilot.tick()
                # decisions ARE computed (observe-only)…
                assert last["decision"]["tick"] >= 2
                # …but nothing is applied and every actuator is static
                assert last["applied"] == []
                snap = sched.metrics_snapshot()
                assert snap["admission_factor"] == 1.0
                lane = snap["lane_stats"]["sha1/512"]
                assert lane["target"] == 8
                assert lane["deadline"] == pytest.approx(0.02)
                for ln in sched._lanes.values():
                    assert ln.deadline is None
            finally:
                await sched.close()

        run(go())


# ------------------------------------------------------------ end to end


class TestEndToEnd:
    def test_h2d_throttle_grows_target_and_shrinks_admission(self):
        async def go():
            plan = FaultPlan.parse("latency_ms=40")
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=8, flush_deadline=0.02,
                    plane_factory=plan.plane_factory(hasher="cpu"),
                ),
                hasher="cpu",
            )
            await sched.start()
            pilot = SchedulerAutopilot(
                sched,
                ControlConfig(enabled=True, hysteresis_ticks=1,
                              cooldown_ticks=0),
            )
            try:
                pieces = [bytes([i % 251]) * 1024 for i in range(64)]
                want = [hashlib.sha1(p).digest() for p in pieces]
                pilot.tick()
                last = None
                for _ in range(3):
                    assert await sched.submit("t", pieces) == want
                    last = pilot.tick()
                bn = last["decision"]["bottleneck"]
                assert bn and bn["stage"] == "h2d" and bn["confirmed"]
                snap = sched.metrics_snapshot()
                assert snap["lane_stats"]["sha1/1024"]["target"] > 8
                assert snap["admission_factor"] < 1.0
                # the status surface names the same actuator values
                status = pilot.status()
                assert status["actuators"]["lanes"]["sha1/1024"]["target"] > 8
                assert status["actions_total"].get("batch_target", 0) >= 1
            finally:
                await sched.close()

        run(go())

    def test_read_latency_throttle_names_read(self):
        """Satellite: the new read_latency_ms fault deterministically
        makes `read` the limiting stage, and the controller follows it
        (read is a per-launch cost, so the batch actuator moves too)."""
        async def go():
            plan = FaultPlan.parse("read_latency_ms=40")
            assert plan.read_latency_s == pytest.approx(0.04)
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=8, flush_deadline=0.02,
                    plane_factory=plan.plane_factory(hasher="cpu"),
                ),
                hasher="cpu",
            )
            await sched.start()
            pilot = SchedulerAutopilot(
                sched,
                ControlConfig(enabled=True, hysteresis_ticks=1,
                              cooldown_ticks=0),
            )
            try:
                pieces = [bytes([i % 251]) * 1024 for i in range(64)]
                want = [hashlib.sha1(p).digest() for p in pieces]
                pilot.tick()
                last = None
                for _ in range(2):
                    assert await sched.submit("t", pieces) == want
                    last = pilot.tick()
                bn = last["decision"]["bottleneck"]
                assert bn and bn["stage"] == "read" and bn["confirmed"]
                assert sched.metrics_snapshot()["lane_stats"]["sha1/1024"][
                    "target"
                ] > 8
            finally:
                await sched.close()

        run(go())

    def test_bad_read_latency_rejected(self):
        with pytest.raises(ValueError):
            FaultPlan.parse("read_latency_ms=-5")
        with pytest.raises(ValueError):
            FaultPlan.parse("read_latency=5")


# ------------------------------------------------------- fabric rebalance


class TestRebalance:
    def _executors(self, tmp_path, rebalance_pids=(0,)):
        from test_fabric import make_library

        from torrent_tpu.fabric import FabricConfig, build_fabric_executor
        from torrent_tpu.storage.storage import FsStorage, Storage

        items1, _, _ = make_library(tmp_path, [12, 20, 7])
        items2 = [
            (Storage(FsStorage(s.method.root), info), info)
            for (s, info) in items1
        ]

        def mk_sched():
            return HashPlaneScheduler(
                SchedulerConfig(batch_target=16, flush_deadline=0.01),
                hasher="cpu",
            )

        def mk_exec(items, sched, pid):
            cfg = FabricConfig(
                heartbeat_interval=0.05, lapse_after=5.0,
                rebalance=pid in rebalance_pids, rebalance_after=1,
            )
            return build_fabric_executor(
                items, sched, nproc=2, pid=pid,
                heartbeat_dir=str(tmp_path / "hb"),
                config=cfg, unit_bytes=8 * 16384,
            )

        return items1, items2, mk_sched, mk_exec

    def test_rebalance_offers_pure(self, tmp_path):
        items1, _, mk_sched, mk_exec = self._executors(tmp_path)

        async def go():
            sched = await mk_sched().start()
            try:
                ex = mk_exec(items1, sched, 0)
                mine = sorted(ex._queue)

                def roll(me_straggler, helper_ok=True, helper_straggler=False):
                    return {
                        "scoreboard": [
                            {"pid": 0, "status": "ok",
                             "straggler": me_straggler},
                            {"pid": 1,
                             "status": "ok" if helper_ok else "lapsed",
                             "straggler": helper_straggler},
                        ]
                    }

                # straggler with a healthy helper: offer every pending unit
                assert ex._rebalance_offers(roll(True)) == mine
                # not a straggler: nothing offered
                assert ex._rebalance_offers(roll(False)) == []
                # no healthy helper: nothing offered
                assert ex._rebalance_offers(roll(True, helper_ok=False)) == []
                assert ex._rebalance_offers(
                    roll(True, helper_straggler=True)
                ) == []
            finally:
                await sched.close()

        run(go())

    def test_straggler_offers_and_peer_adopts(self, tmp_path):
        """End to end: worker 0's fleet view names itself a straggler
        (forced — in-process executors share one ledger, so real rate
        divergence can't show up); its unstarted units ride the
        heartbeat offer list and worker 1 adopts them through the
        ordinary adoption path. Coverage stays exact and both global
        bitfields identical."""
        items1, items2, mk_sched, mk_exec = self._executors(tmp_path)

        async def go():
            s0 = await mk_sched().start()
            s1 = await mk_sched().start()
            try:
                e0 = mk_exec(items1, s0, 0)
                e1 = mk_exec(items2, s1, 1)
                e0.fleet_snapshot = lambda: {  # force the verdict
                    "scoreboard": [
                        {"pid": 0, "status": "ok", "straggler": True},
                        {"pid": 1, "status": "ok", "straggler": False},
                    ]
                }
                await asyncio.gather(e0.run(), e1.run())
            finally:
                await s0.close()
                await s1.close()
            return e0, e1

        e0, e1 = run(go())
        snap0, snap1 = e0.metrics_snapshot(), e1.metrics_snapshot()
        assert snap0["units_offered"] >= 1
        assert snap1["units_rebalanced"] >= 1
        assert snap1["units_adopted"] >= snap1["units_rebalanced"]
        for a, b in zip(e0.bitfields(), e1.bitfields()):
            assert (a == b).all()
        total = sum(int(b.sum()) for b in e0.bitfields())
        assert total == e0.plan.total_pieces

    def test_rebalance_off_by_default(self, tmp_path):
        from torrent_tpu.fabric import FabricConfig

        assert FabricConfig().rebalance is False


# -------------------------------------------------------------- surfaces


class TestSurfaces:
    def test_render_control_metrics_lints(self):
        from torrent_tpu.utils.metrics import render_control_metrics

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=4, flush_deadline=0.01),
                hasher="cpu",
            )
            await sched.start()
            pilot = SchedulerAutopilot(sched, ControlConfig(enabled=True))
            try:
                await sched.submit("t", [b"q" * 64])
                pilot.tick()
                text = render_control_metrics(pilot.metrics_snapshot())
            finally:
                await sched.close()
            return text

        text = run(go())
        prom_lint(text)
        assert "torrent_tpu_control_enabled 1" in text
        assert 'torrent_tpu_control_lane_target{lane="sha1/64"' in text
        # defensive on partial/empty snapshots
        prom_lint(render_control_metrics({}))

    def test_metrics_server_carries_control_series(self):
        """The SESSION /metrics endpoint (MetricsServer) carries
        torrent_tpu_control_* when given a controller — the 'both
        /metrics endpoints' half the bridge test doesn't cover."""
        import urllib.request

        from torrent_tpu.session.client import Client, ClientConfig
        from torrent_tpu.utils.metrics import MetricsServer

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=4, flush_deadline=0.01),
                hasher="cpu",
            )
            await sched.start()
            pilot = SchedulerAutopilot(sched, ControlConfig(enabled=True))
            client = Client(ClientConfig(host="127.0.0.1"))
            server = await MetricsServer(
                client, scheduler=sched, controller=pilot
            ).start()
            try:
                await sched.submit("t", [b"m" * 64])
                pilot.tick()
                text = await asyncio.to_thread(
                    lambda: urllib.request.urlopen(
                        f"http://127.0.0.1:{server.port}/metrics", timeout=10
                    ).read().decode()
                )
            finally:
                server.close()
                await sched.close()
            return text

        text = run(go())
        prom_lint(text)
        assert "torrent_tpu_control_enabled 1" in text
        assert "torrent_tpu_sched_queue_pieces" in text

    def test_top_renders_decision_line(self):
        from torrent_tpu.tools.top import render_top

        payload = {
            "attribution": {"wall_s": 1.0, "stages": {}},
            "control": {
                "enabled": True,
                "decision": {
                    "tick": 4,
                    "bottleneck": {"stage": "h2d", "streak": 3,
                                   "confirmed": True},
                    "actions": [],
                },
                "applied": [
                    {"actuator": "batch_target", "lane": "sha1/262144",
                     "from": 8, "to": 16, "applied": 16}
                ],
                "actuators": {
                    "admission_factor": 0.5,
                    "lanes": {
                        "sha1/262144": {"target": 16, "deadline": 0.04,
                                        "backend": "device"}
                    },
                },
            },
            "sched": {},
        }
        frame = render_top(payload)
        assert "autopilot:" in frame
        assert "h2d limiting x3 [confirmed]" in frame
        assert "batch_target[sha1/262144] 8→16" in frame
        assert "admission ×0.50" in frame
        assert "lane sha1/262144: target 16" in frame
        # no control key -> no autopilot line
        assert "autopilot" not in render_top({"attribution": {}})

    def test_bridge_control_route_and_metrics(self):
        from torrent_tpu.bridge.service import BridgeServer

        async def _get(port, path):
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
            await writer.drain()
            status = int((await reader.readline()).split()[1])
            clen = 0
            while True:
                line = await reader.readline()
                if line in (b"\r\n", b""):
                    break
                if line.lower().startswith(b"content-length:"):
                    clen = int(line.split(b":", 1)[1])
            body = await reader.readexactly(clen)
            writer.close()
            return status, body

        async def go():
            svc = await BridgeServer(
                "127.0.0.1", port=0, hasher="cpu",
                autopilot=ControlConfig(enabled=True, interval_s=0.05),
            ).start()
            try:
                svc.autopilot.tick()
                status, body = await _get(svc.port, "/v1/control")
                assert status == 200
                payload = json.loads(body.decode())
                assert payload["attached"] is True
                assert payload["enabled"] is True
                assert "actuators" in payload
                status, body = await _get(svc.port, "/metrics")
                assert status == 200
                assert b"torrent_tpu_control_enabled 1" in body
                status, body = await _get(svc.port, "/v1/pipeline")
                assert json.loads(body.decode())["control"]["enabled"] is True
            finally:
                svc.close()
                await svc.wait_closed()

            # a bridge WITHOUT an autopilot still answers /v1/control
            svc = await BridgeServer("127.0.0.1", port=0, hasher="cpu").start()
            try:
                status, body = await _get(svc.port, "/v1/control")
                assert status == 200
                payload = json.loads(body.decode())
                assert payload["attached"] is False
                status, body = await _get(svc.port, "/metrics")
                assert b"torrent_tpu_control_enabled" not in body
            finally:
                svc.close()
                await svc.wait_closed()

        run(go())
