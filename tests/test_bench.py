"""bench.py contract tests: one process, one JSON line, no silent CPU.

Every case runs bench.py as a subprocess pinned to the CPU platform with
``JAX_PLATFORMS=cpu`` — being told to is the one way a CPU measurement
is allowed.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")

_SMALL = {
    "JAX_PLATFORMS": "cpu",
    "BENCH_TOTAL_MB": "4",
    "BENCH_BATCH": "4",
}


def _run_bench(extra_env, timeout=300):
    env = {**os.environ, **_SMALL, **extra_env}
    proc = subprocess.run(
        [sys.executable, BENCH],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=REPO,
    )
    return proc


def test_inline_cpu_prints_one_json_line():
    proc = _run_bench({})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert rec["metric"] == "sha1_recheck_256KiB_pieces_per_sec"
    assert rec["unit"] == "pieces/s"
    assert rec["value"] > 0 and rec["vs_baseline"] > 0
    # the device as JAX names it, not as a flag says
    assert rec["platform"] == "cpu" and rec["backend"] == "jax"
    assert rec["device_kind"] == "cpu" and rec["device_count"] >= 1


def _stdout_records(proc) -> list[str]:
    return [l for l in proc.stdout.splitlines() if l.lstrip().startswith("{")]


def test_no_accelerator_without_being_told_fails_with_no_record():
    """JAX_PLATFORMS unset on a host with no chip: JAX falls back to the
    CPU silently. bench.py must not measure that — non-zero exit, no
    record on stdout (not a null record, not a CPU number)."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(BENCH_TOTAL_MB="4", BENCH_BATCH="4")
    proc = subprocess.run(
        [sys.executable, BENCH], env=env, capture_output=True, text=True,
        timeout=300, cwd=REPO,
    )
    assert proc.returncode != 0
    assert _stdout_records(proc) == [], proc.stdout
    assert "no accelerator" in proc.stderr


def test_kernel_failure_exits_nonzero_and_never_remeasures():
    """A failure inside the measured path ends the run: no retry on
    another backend, no record, a non-zero exit. BENCH_BACKEND is unset,
    which is the case the removed pallas->jax retry used to catch."""
    code = (
        "import bench\n"
        "from torrent_tpu.models import verifier\n"
        "calls = []\n"
        "def boom(self, *a, **k):\n"
        "    calls.append(1)\n"
        "    raise RuntimeError('forced kernel failure')\n"
        "verifier.TPUVerifier.verify_batch = boom\n"
        "try:\n"
        "    bench.main()\n"
        "finally:\n"
        "    import sys; print('launch attempts:', len(calls), file=sys.stderr)\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "BENCH_BACKEND"}
    env.update(_SMALL)
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=300, cwd=REPO,
    )
    assert proc.returncode != 0
    assert _stdout_records(proc) == [], proc.stdout
    assert "forced kernel failure" in proc.stderr
    assert "launch attempts: 1" in proc.stderr


def test_bench_is_one_process(tmp_path):
    """No child process, no probe subprocess: bench.py measures in the
    process that was started. Proven by denying it fork/exec."""
    code = (
        "import subprocess, os\n"
        "def deny(*a, **k):\n"
        "    raise AssertionError('bench.py started a process')\n"
        "subprocess.Popen = deny\n"
        "os.execve = os.execv = os.fork = deny\n"
        "import bench\n"
        "bench.main()\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], env={**os.environ, **_SMALL},
        capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert len(_stdout_records(proc)) == 1


def test_e2e_cap_marks_record():
    """BENCH_E2E_MB: the end-to-end pass runs over a sub-range and
    the record carries the honest marker; the plane/baseline fields stay
    full-scale (the RAM-blowup guard for huge configs)."""
    proc = _run_bench({"BENCH_TOTAL_MB": "8", "BENCH_E2E_MB": "2"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["e2e_measured_mb"] == 2
    assert rec["value"] > 0 and rec["end_to_end_pps"] > 0


def test_record_carries_median_of_n_fields():
    """Round-2 verdict #4: every hash-plane record must carry the batch
    knob, the run count, the per-run rates, and the spread so a reader
    can tell tuning progress from variance."""
    proc = _run_bench({"BENCH_RUNS": "3"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["batch"] == 4
    assert rec["n_runs"] == 3
    assert len(rec["runs_pps"]) == 3
    assert rec["spread"] >= 0
    # value is the MEDIAN of the runs
    import statistics

    assert abs(rec["value"] - statistics.median(rec["runs_pps"])) <= 0.15


def test_micro_rung_single_batch_and_dispatch_fields():
    """Round-4 micro-rung: BENCH_NBATCH=1 stages one resident batch and
    BENCH_DISPATCHES amortizes the fixed dispatch cost over it; the record
    must carry both knobs so a reader can compare rungs fairly."""
    proc = _run_bench({"BENCH_NBATCH": "1", "BENCH_DISPATCHES": "6"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["value"] > 0
    assert rec["n_batches"] == 1
    assert rec["n_dispatches"] == 6


def test_baseline_cache_roundtrip(tmp_path):
    """BENCH_BASELINE_CACHE: first run measures and saves the hashlib
    rate; a later capped run loads it and marks the record as cached with
    the measured geometry, so a later run skips the re-hash."""
    cache = tmp_path / "cpu_baseline.json"
    proc = _run_bench({"BENCH_BASELINE_CACHE": str(cache)})
    assert proc.returncode == 0, proc.stderr[-2000:]
    saved = json.loads(cache.read_text())
    entry = saved["sha1:262144"]
    assert entry["cpu_pps"] > 0 and entry["measured_total_mb"] == 4

    proc = _run_bench(
        {
            "BENCH_BASELINE_CACHE": str(cache),
            "BENCH_TOTAL_MB": "8",
            "BENCH_E2E_MB": "2",
        }
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["baseline_cached"] is True
    assert rec["baseline_measured_total_mb"] == 4
    assert rec["value"] > 0 and rec["vs_baseline"] > 0
    # the larger population must NOT be overwritten by a smaller one, and
    # the cached-capped run never re-measured (measured_total_mb stays 4)
    saved2 = json.loads(cache.read_text())
    assert saved2["sha1:262144"]["measured_total_mb"] == 4


def test_v2_record_carries_median_of_n_fields():
    proc = _run_bench(
        {
            "BENCH_CONFIG": "v2",
            "BENCH_TOTAL_MB": "8",
            "TORRENT_TPU_LEAF_BATCH": "1024",
            "BENCH_RUNS": "3",
        }
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["n_runs"] == 3 and len(rec["runs_pps"]) == 3
    assert rec["batch"] == 1024 and rec["n_batches"] >= 3
    assert rec["spread"] >= 0
    # the leaf kernel that ran, named by the function that ran it
    assert rec["platform"] == "cpu" and rec["backend"] == "scan"
