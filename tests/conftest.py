"""Test env: force JAX onto a virtual 8-device CPU platform.

Must run before any `import jax` anywhere. The multi-chip sharding tests
(tests/test_parallel.py) rely on these 8 virtual devices to exercise the
same `jax.sharding.Mesh` code paths the driver dry-runs.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# tests run on the virtual 8-device CPU platform whatever the caller's
# environment says; subprocesses inherit it
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import contextlib
import pathlib
import signal

import pytest

# Concurrency sanitizer (tsan-lite): with TORRENT_TPU_TSAN=1 every
# named_lock the package creates is instrumented, so the whole suite
# doubles as a concurrency test. Enable BEFORE any torrent_tpu module
# import — module-level locks (native/io_engine) are created at import
# time and only locks created after enabling are sanitized.
_TSAN = os.environ.get("TORRENT_TPU_TSAN", "") in ("1", "true")
if _TSAN:
    from torrent_tpu.analysis import sanitizer as _tsan

    _tsan.enable()


def pytest_sessionfinish(session, exitstatus):
    """Under TSAN, a lock-order cycle OR a shared-state lockset race
    observed anywhere in the run fails the session even if every
    individual test passed."""
    if not _TSAN:
        return
    snap = _tsan.snapshot()
    rep = (
        f"tsan: {len(snap['locks'])} locks, {snap['edges']} order edges, "
        f"{len(snap['cycles'])} cycles, {snap['loop_stalls']} loop stalls "
        f"(max {snap['loop_stall_max_s']:.3f}s), {snap['long_holds']} long holds, "
        f"{len(snap['cells'])} guarded cells, "
        f"{snap['lockset_race_count']} lockset races"
    )
    print(f"\n{rep}")
    if snap["cycles"]:
        for cyc in snap["cycles"]:
            print(f"tsan: LOCK-ORDER CYCLE: {' -> '.join(cyc + cyc[:1])}")
        session.exitstatus = 3
    if snap["lockset_race_count"]:
        for race in snap["lockset_races"]:
            print(f"tsan: LOCKSET RACE: {race}")
        session.exitstatus = 3

REFERENCE_FIXTURES = pathlib.Path("/root/reference/test_data")


@contextlib.contextmanager
def hard_deadline(seconds: int):
    """SIGALRM wall-clock bound for soak-style tests.

    ``asyncio.wait_for`` can only fire while the event loop is running; a
    SYNC-blocked loop (a hung pread, a native call that never returns)
    sails past it and hangs CI forever. pytest-timeout is not installed
    in this image, so this is the real guard: the alarm interrupts the
    main thread wherever it is and raises. Main-thread only (a POSIX
    signal constraint), which is where pytest runs tests.
    """

    def on_alarm(signum, frame):
        raise TimeoutError(f"hard deadline of {seconds}s exceeded")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def ref_fixtures() -> pathlib.Path:
    """Golden .torrent fixtures from the mounted reference snapshot."""
    if not REFERENCE_FIXTURES.is_dir():
        pytest.skip("reference fixtures not mounted")
    return REFERENCE_FIXTURES
