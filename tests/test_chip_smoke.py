"""chip_smoke.py and the device helpers: no silent CPU path, one cache.

The smoke itself only proves something on a chip; what a CPU host can
check is its contract — it refuses to run without a TPU, prints nothing
then, and the explicit ``--cpu-dry-run`` drives every phase (the
multi-device one included, on virtual devices) at a tiny size.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env, cwd=REPO, timeout=600):
    return subprocess.run(
        [sys.executable, *args], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=timeout,
    )


def _json_lines(proc) -> list[str]:
    return [l for l in proc.stdout.splitlines() if l.lstrip().startswith("{")]


def test_cpu_dry_run_passes_every_phase():
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_PLATFORMS", None)  # the argument alone must pin the CPU
    proc = _run([SMOKE, "--cpu-dry-run"], env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    # stdout is the report, then the verdict with exactly the contract's keys
    report_line, verdict_line = proc.stdout.strip().splitlines()
    assert json.loads(verdict_line) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    (result,) = json.loads(report_line).values()
    assert result["dry_run"] is True
    assert set(result["phases"]) == {
        "0_device", "1_kernels", "2_library", "3_bridge", "4_v2", "5_shards",
        "6_session",
    }
    assert all(p["ok"] for p in result["phases"].values())
    # interpret mode is named as such, never mistaken for Mosaic
    kernels = result["phases"]["1_kernels"]["launches"]
    assert not any(k.get("mosaic") for k in kernels)
    assert result["phases"]["2_library"]["multi"]["recheck_backends"] == ["jax", "pallas"]
    assert result["phases"]["5_shards"]["sharded_recheck_devices"] == 4
    assert result["fallbacks"]["sched_cpu_fallback_launches"] == 0
    # the session's hashlib fallback reads zero from a phase whose pieces
    # were launched on the ingest scheduler, not from a counter nothing touched
    flushes = result["phases"]["6_session"]["ingest_flushes"]
    assert flushes["device"] > 0 and flushes["hashlib_fallback"] == 0
    assert result["fallbacks"]["session_ingest_hashlib_fallbacks"] == 0
    assert result["reduced"], "a dry run must say what it cut"


@pytest.mark.parametrize("platforms", ["cpu", None])
def test_without_a_tpu_it_fails_and_prints_no_result(platforms):
    """Told to use the CPU, or fallen back to it with JAX_PLATFORMS
    unset: either way there is no chip, so no result line and rc != 0."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    if platforms:
        env["JAX_PLATFORMS"] = platforms
    proc = _run([SMOKE], env)
    assert proc.returncode != 0
    assert _json_lines(proc) == [], proc.stdout
    assert "no TPU" in proc.stderr


def test_alone_in_a_directory_it_fails_and_prints_nothing(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    for args in (["chip_smoke.py"], ["chip_smoke.py", "--cpu-dry-run"]):
        proc = _run(args, env, cwd=str(tmp_path))
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""


# ----------------------------------------------------- compile-cache helper

_CACHE_PROBE = (
    "import jax\n"
    "sets = []\n"
    "real = jax.config.update\n"
    "def spy(key, value):\n"
    "    sets.append(key)\n"
    "    real(key, value)\n"
    "jax.config.update = spy\n"
    "from torrent_tpu.utils.device import enable_compile_cache\n"
    "import json\n"
    "print(json.dumps({'returned': enable_compile_cache(), 'sets': sets}))\n"
)


def _cache_probe(**env_overrides):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")
    }
    env.update(env_overrides)
    proc = _run(["-c", _CACHE_PROBE], env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cache_dir_placed_from_outside_is_left_alone(tmp_path):
    got = _cache_probe(JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert got == {"returned": str(tmp_path), "sets": []}


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout():
    got = _cache_probe()
    assert got["returned"] == os.path.join(REPO, ".jax_cache")
    assert len(got["sets"]) == 1 and got["sets"][0].endswith("cache_dir")
    # fixed: no tempfile, pid or time in it, and git ignores it
    assert _cache_probe()["returned"] == got["returned"]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cpu_pinned_process_gets_no_cache():
    assert _cache_probe(JAX_PLATFORMS="cpu") == {"returned": None, "sets": []}


# ------------------------------------------------------------ native engine


def test_native_binary_is_keyed_on_its_source(tmp_path, monkeypatch):
    """A binary that was not built from the committed source — stale, or
    carried along by a copy of the tree — is never loaded: the library's
    name carries the source hash, and building removes the others."""
    from torrent_tpu.native import build

    if shutil.which(os.environ.get("CXX", "g++")) is None:
        pytest.skip("no C++ toolchain")
    src = tmp_path / "io_engine.cpp"
    shutil.copy(build._SRC, src)
    monkeypatch.setattr(build, "_SRC", src)
    foreign = tmp_path / "libtorrent_tpu_io.so"  # the old, unkeyed name
    foreign.write_bytes(b"not an ELF file")
    os.utime(foreign, (2**31, 2**31))  # newer than any source

    lib = build.build()
    assert lib is not None and lib == build.lib_path() and lib != foreign
    assert lib.read_bytes()[:4] == b"\x7fELF"
    assert not foreign.exists()
    first = lib.name
    src.write_bytes(src.read_bytes() + b"\n// edited\n")
    assert build.lib_path().name != first  # new source, new binary name
    assert build.load() is not None
    assert [p.name for p in tmp_path.glob("*.so")] == [build.lib_path().name]
