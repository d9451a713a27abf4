"""The trace reduction: on a hand-made trace whose numbers can be worked
out by eye, and on a small recording from the chip. Loads no TPU library."""

import json
import os

import pytest

from benchmark.harness import trace as tr

MS = 1_000_000


def _ir(ops0, modules0, host, modules1=None):
    planes = [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops0}, {"name": "XLA Modules", "events": modules0}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]},
    ]
    if modules1 is not None:
        planes.append({"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": []}, {"name": "XLA Modules", "events": modules1}]})
    return {"planes": planes}


def test_busy_idle_step_and_gaps_by_hand():
    # window 0..100 ms; step launches at 10-30 and 50-60, another module at
    # 70-71, one launch that straddles the window's end; the operations of
    # the first launch were sampled (two that overlap, covering it)
    ops = [["while.1", 10 * MS, 15 * MS], ["fusion.2", 20 * MS, 10 * MS]]
    modules = [["jit_step(1)", 10 * MS, 20 * MS], ["jit_step(1)", 50 * MS, 10 * MS], ["jit_other(2)", 70 * MS, 1 * MS],
               ["jit_step(1)", 98 * MS, 10 * MS]]
    host = [["bench_trace_window", 0, 100 * MS], ["bench_call", 0, 45 * MS], ["sched_sha1_launch_b262144", 31 * MS, 10 * MS]]
    r = tr.reduce(_ir(ops, modules, host), {"jit_step"})
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s_mean"] == pytest.approx(0.033)
    assert r["idle_share"] == pytest.approx(0.67)
    # only launches wholly inside the window count for the step's time
    assert r["step_s"] == pytest.approx(0.030) and r["step_launches"] == 2 and r["step_devices"] == 1
    assert r["modules_seen"] == ["jit_other", "jit_step"]
    assert r["device_ops"][0][0] == "while.1" and r["device_ops"][0][1] == pytest.approx(0.015)
    assert r["ops_cover_modules"] == pytest.approx(1.0)
    gaps = dict((n, s) for n, s in r["idle_gaps"])
    # 0-10 under bench_call; 30-50: its middle (40) lies in both spans, the
    # shorter (the launch) wins; 60-70 and 71-98 under none
    assert gaps["bench_call_x1"] == pytest.approx(0.010)
    assert gaps["sched_sha1_launch_b262144_x1"] == pytest.approx(0.020)
    assert gaps["no_host_span_x2"] == pytest.approx(0.037)
    assert sum(gaps.values()) + r["busy_s_mean"] == pytest.approx(r["window_s"])


def test_mesh_reads_the_least_idle_device():
    m0 = [["jit_step(1)", 0, 10 * MS]]
    m1 = [["jit_step(1)", 0, 40 * MS]]
    r = tr.reduce(_ir([], m0, [["bench_trace_window", 0, 100 * MS]], m1), {"jit_step"})
    assert r["lead_device"] == "/device:TPU:1" and r["idle_share"] == pytest.approx(0.6)
    assert r["busy_s_mean"] == pytest.approx(0.025)
    assert r["idle_share_by_device"]["/device:TPU:0"] == pytest.approx(0.9)
    assert r["step_devices"] == 2 and r["step_s"] == pytest.approx(0.040)


def test_a_trace_with_no_device_operation_reads_nothing():
    assert tr.reduce(_ir([], [], [["bench_trace_window", 0, MS]]), set()) is None
    assert tr.reduce({"planes": []}, set()) is None


def test_step_is_selected_by_module_not_by_op_name():
    # the same work under another implementation: other op names, same module
    scan = _ir([["while.19", 0, 8 * MS]], [["jit__digests_flat(7)", 0, 8 * MS]], [["bench_trace_window", 0, 10 * MS]])
    pallas = _ir([["tpu_custom_call.3", 0, 8 * MS]], [["jit__digests_flat(9)", 0, 8 * MS]], [["bench_trace_window", 0, 10 * MS]])
    assert tr.reduce(scan, {"jit__digests_flat"})["step_s"] == tr.reduce(pallas, {"jit__digests_flat"})["step_s"]


RECORDED = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recording kept")
def test_recorded_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    r = tr.reduce(rec["ir"], set(rec["step_modules"]))
    for key, want in rec["expect"].items():
        assert r[key] == pytest.approx(want, rel=1e-9), key
    assert r["device_ops"][0][0] == rec["top_op"]
    assert 0 < r["idle_share"] < 1 and r["step_s"] <= r["busy_s_mean"] * 1.0001
    assert r["ops_cover_modules"] is None or r["ops_cover_modules"] > 0.9
