"""The four-chip cell ``recheck-256k-x4.bulk`` (PR 26): whole runs of the
harness at rehearsal sizes over four virtual CPU devices, and its reader
``device_busy_skew`` by hand.

The runs are child processes: the rehearsal asks XLA for four CPU devices
through ``XLA_FLAGS``, which only a process that has not started JAX yet
obeys, and a test process has. With one device the run would take the flat
one-chip road and prove nothing about the mesh."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest
from benchmark.harness import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "recheck-256k-x4.bulk"
MS = 1_000_000


def _run(*extra: str) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload", CELL, "--seed", "2147483777",
         "--seconds", "2", "--rehearse", "1", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["metrics"] == {} and list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
    return line


def test_sound_traced_run_is_correct_and_feeds_the_upload_reader():
    line = _run("--trace", "1")
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["checks"]["reference_invalid"] > 0  # the traffic plants invalid pieces
    # what the program's ledger gives a CPU run too: the mesh road's own h2d
    # stage among them (a CPU trace holds no device plane, so no trace reader)
    assert set(line["rehearsal"]["would_report"]) == {
        "h2d_gib_s", "host_cpu_s_per_gib", "pass_setup_share", "read_wait_share", "stage_busy_share",
        "step_compiles_in_window",
    }


def test_control_is_not_correct():
    bad = _run("--trace", "0", "--control", "1")
    assert bad["correct"] is False
    assert bad["checks"]["wrong_verdicts"]["value"] == bad["checks"]["reference_invalid"] > 0


def test_the_cell_is_the_manifests_one_mesh_cell():
    m = manifest.load_manifest(ROOT)
    cell = manifest.load_cell(ROOT, CELL)
    assert cell.chips == 4 and cell.config["batch"] == 1024 and cell.traffic_name == "bulk"
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == [CELL]
    mine = {p["name"] for p in manifest.metrics_for(m, "per_layer", CELL)}
    assert {"device_busy_skew", "h2d_gib_s", "device_idle_share", "hash_step_roofline"} <= mine
    assert not any(name.endswith(".live") for name in mine)
    assert [p["workloads"] for p in m["per_layer"] if p["name"] == "device_busy_skew"] == [[CELL]]


def _planes(*busy_ms: float) -> dict:
    planes = [{"name": "/host:CPU", "lines": [{"name": "main", "events": [["bench_trace_window", 0, 100 * MS]]}]}]
    for i, ms in enumerate(busy_ms):
        modules = [["jit__verify(1)", 10 * MS, int(ms * MS)]] if ms else []
        planes.append({"name": f"/device:TPU:{i}", "lines": [{"name": "XLA Modules", "events": modules}]})
    return {"planes": planes}


@pytest.mark.parametrize(
    "busy_ms, want",
    [
        ((8.0, 8.0, 8.0, 8.0), 0.0),  # four chips as one
        ((8.0, 8.4, 8.2, 8.0), 100 * 0.4 / 8.4),
        ((40.0, 10.0), 75.0),
        ((20.0, 20.0, 20.0, 0), 100.0),  # a chip of the mesh that never ran the step
    ],
)
def test_device_busy_skew_by_hand(busy_ms, want):
    reduced = tr.reduce(_planes(*busy_ms), {"jit__verify"})
    assert manifest.load_reader(ROOT, "device_busy_skew").read({"trace": reduced}) == pytest.approx(want)


@pytest.mark.parametrize(
    "obs",
    [
        {"trace": None},  # a rehearsal: no device plane
        {"trace": tr.reduce(_planes(8.0), {"jit__verify"})},  # one chip
        {"trace": tr.reduce(_planes(8.0, 0), {"jit__verify"})},  # two planes, one of which ran the step
        {"trace": tr.reduce(_planes(8.0, 8.0), {"jit_other"})},  # no device ran the step
    ],
)
def test_device_busy_skew_reads_nothing_without_a_mesh(obs):
    assert manifest.load_reader(ROOT, "device_busy_skew").read(obs) is None
