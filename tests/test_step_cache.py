"""The jitted SHA-1 steps belong to the process (models/verifier.py).

``jax.jit`` finds a traced and loaded program again by the function
object it wraps. A ``TPUVerifier`` used to wrap five closures of its own,
so the first call of every recheck pass traced the scan and loaded its
program again; now every verifier of one (backend, tile_sub, mesh) takes
the same four jitted objects from a small cache. These cases hold the
mechanism on the CPU: what is shared, what gets an entry of its own, that
sharing changes no verdict, the bound, the fill under threads, and the
two counters ``/metrics`` renders. What it is worth in seconds only the
chip says (PERF.md, PR 29).
"""

import hashlib
import json
import os
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from torrent_tpu.codec.metainfo import InfoDict
from torrent_tpu.models import verifier as verifier_mod
from torrent_tpu.models.verifier import TPUVerifier, step_cache_stats
from torrent_tpu.parallel.mesh import make_mesh
from torrent_tpu.parallel.verify import verify_pieces_tpu
from torrent_tpu.storage.storage import MemoryStorage, Storage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = ("_digest_step", "_verify_step", "_verify_step_flat", "_digest_step_flat")


@pytest.fixture
def cache(monkeypatch):
    """A cache of this test's own, so its counts start at nought."""
    fresh = verifier_mod._StepCache(verifier_mod.STEP_CACHE_CAPACITY)
    monkeypatch.setattr(verifier_mod, "_step_cache", fresh)
    return fresh


def _mesh(devices: int):
    return make_mesh(jax.devices()[:devices])


def _payload(plen: int, n_pieces: int, tail: int, corrupt: list[int], seed: int):
    """A seeded payload in memory, its torrent, and hashlib's verdict on
    what the storage holds after one byte of each piece in ``corrupt``
    was flipped."""
    length = plen * (n_pieces - 1) + tail
    data = bytearray(np.random.default_rng(seed).bytes(length))
    pieces = tuple(hashlib.sha1(data[i : i + plen]).digest() for i in range(0, length, plen))
    info = InfoDict(name="v", piece_length=plen, pieces=pieces, length=length, files=None)
    for i in corrupt:
        data[i * plen + 5] ^= 0x01
    storage = Storage(MemoryStorage(), info)
    storage.set(0, bytes(data))
    held = storage.get(0, length)
    reference = [hashlib.sha1(held[i : i + plen]).digest() == pieces[i // plen] for i in range(0, length, plen)]
    return info, storage, reference


def test_two_verifiers_of_one_key_hold_the_same_five_jitted_objects(cache):
    a = TPUVerifier(piece_length=4096, batch_size=8, mesh=_mesh(1))
    b = TPUVerifier(piece_length=16384, batch_size=16, mesh=_mesh(1))
    for name in STEPS:
        assert getattr(a, name) is getattr(b, name), name
    assert len({id(getattr(a, name)) for name in STEPS}) == 4
    assert step_cache_stats() == {"step_builds": 1, "step_reuses": 1}


@pytest.mark.parametrize("devices,step", [(1, "_verify_step_flat"), (4, "_verify_step")])
def test_a_second_pass_traces_nothing(cache, devices, step):
    """Two back-to-back rechecks through the public road, each building
    its verifier: the step the road takes holds one traced program after
    both, where a verifier's own ``jax.jit`` held one a pass."""
    info, storage, reference = _payload(4096, 19, 77, [3, 18], seed=29)
    mesh = _mesh(devices)
    for _ in range(2):
        got = verify_pieces_tpu(storage, info, batch_size=8, mesh=mesh)
        assert got.tolist() == reference
    assert getattr(TPUVerifier(4096, 8, mesh=mesh), step)._cache_size() == 1
    stats = step_cache_stats()
    assert stats["step_builds"] == 1 and stats["step_reuses"] >= 1


@pytest.mark.parametrize("other", ["backend", "mesh", "tile_sub"])
def test_what_the_closures_capture_gets_an_entry_of_its_own(cache, monkeypatch, other):
    monkeypatch.delenv("TORRENT_TPU_TILE_BYTES", raising=False)
    base = dict(piece_length=16384, batch_size=1, backend="pallas" if other == "tile_sub" else "jax", mesh=_mesh(1))
    a = TPUVerifier(**base)
    if other == "backend":
        b = TPUVerifier(**{**base, "backend": "pallas"})
    elif other == "mesh":
        b = TPUVerifier(**{**base, "mesh": _mesh(4)})
    else:
        monkeypatch.setenv("TORRENT_TPU_TILE_BYTES", str(600_000))
        b = TPUVerifier(**base)
        assert (a.tile_sub, b.tile_sub) == (32, 8)
    for name in STEPS:
        assert getattr(a, name) is not getattr(b, name), name
    assert step_cache_stats() == {"step_builds": 2, "step_reuses": 0}
    # and the same again is served from the two entries
    TPUVerifier(**base)
    assert step_cache_stats() == {"step_builds": 2, "step_reuses": 1}


def test_an_equal_mesh_built_anew_is_the_same_key(cache):
    TPUVerifier(4096, 8, mesh=make_mesh(jax.devices()[:2]))
    TPUVerifier(4096, 8, mesh=make_mesh(list(jax.devices()[:2])))
    TPUVerifier(4096, 8, devices=jax.devices()[:2])
    assert step_cache_stats() == {"step_builds": 1, "step_reuses": 2}


@pytest.mark.parametrize("devices", [1, 4])
def test_two_geometries_through_one_entry_equal_hashlib(cache, devices):
    """Piece length and batch are shapes of the one jitted step, not
    keys: two torrents that differ in both, interleaved, each bit of
    each bitfield against hashlib, flipped bytes included."""
    mesh = _mesh(devices)
    small = _payload(4096, 21, 100, [0, 7, 20], seed=1)
    large = _payload(16384, 9, 16384, [4, 8], seed=2)
    for _ in range(2):
        for (info, storage, reference), batch in ((small, 8), (large, 4)):
            got = verify_pieces_tpu(storage, info, batch_size=batch, mesh=mesh)
            assert got.tolist() == reference
    assert step_cache_stats() == {"step_builds": 1, "step_reuses": 3}


def test_past_the_capacity_the_oldest_entry_goes_and_a_rebuilt_one_verifies(monkeypatch):
    small = verifier_mod._StepCache(2)
    monkeypatch.setattr(verifier_mod, "_step_cache", small)
    info, storage, reference = _payload(4096, 11, 9, [2], seed=3)
    first = TPUVerifier(4096, 8, mesh=_mesh(1))
    assert first.verify_storage(storage, info).tolist() == reference
    TPUVerifier(4096, 8, mesh=_mesh(2))
    TPUVerifier(4096, 8, mesh=_mesh(1))  # asked for again: the two-device entry is now the oldest
    TPUVerifier(4096, 8, mesh=_mesh(4))
    assert [key[2].size for key in small._entries] == [1, 4]
    TPUVerifier(4096, 8, mesh=_mesh(8))  # and now the one-device entry goes
    assert [key[2].size for key in small._entries] == [4, 8]
    rebuilt = TPUVerifier(4096, 8, mesh=_mesh(1))
    assert rebuilt._verify_step_flat is not first._verify_step_flat
    assert rebuilt.verify_storage(storage, info).tolist() == reference
    # a verifier built on an entry that went keeps its steps
    assert first.verify_storage(storage, info).tolist() == reference
    assert small.stats() == {"step_builds": 5, "step_reuses": 1}
    assert len(small._entries) == 2


def test_an_unknown_backend_leaves_no_entry(cache):
    with pytest.raises(ValueError, match="unknown sha1 backend"):
        TPUVerifier(4096, 8, backend="md5")
    assert not cache._entries and step_cache_stats() == {"step_builds": 0, "step_reuses": 0}


def _construct_from_eight_threads() -> list[TPUVerifier]:
    """Eight threads, 50 constructors each on one key: a barrier so that
    all reach the first together, and a short switch interval."""
    mesh = _mesh(1)
    barrier = threading.Barrier(8)
    built: list[TPUVerifier] = []
    errors: list[BaseException] = []

    def construct():
        try:
            barrier.wait(timeout=30)
            for _ in range(50):
                built.append(TPUVerifier(4096, 8, mesh=mesh))
        except BaseException as e:  # noqa: BLE001 - handed to the asserting thread
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=construct) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    return built


def test_eight_threads_constructing_one_key_yield_one_build(cache):
    built = _construct_from_eight_threads()
    assert len(built) == 400
    assert len({id(v._verify_step_flat) for v in built}) == 1
    assert step_cache_stats() == {"step_builds": 1, "step_reuses": 399}


_UNDER_TSAN = """
import json
from torrent_tpu.analysis import sanitizer
sanitizer.enable()
from tests.test_step_cache import _construct_from_eight_threads, step_cache_stats
built = _construct_from_eight_threads()
snap = sanitizer.snapshot()
print(json.dumps({
    "objects": len({id(v._verify_step_flat) for v in built}),
    "stats": step_cache_stats(),
    "lock": "models.verifier._steps_lock" in snap["locks"],
    "cell": "models.verifier.steps.entries" in snap["cells"],
    "cycles": len(snap["cycles"]),
    "races": snap["lockset_race_count"],
}))
"""


def test_eight_threads_one_build_under_the_sanitizer():
    """The same in a process of its own with ``TORRENT_TPU_TSAN=1``,
    where the cache's lock is an instrumented one and its entries a
    guarded cell: one build, no lock-order cycle, no lockset race."""
    env = dict(os.environ, TORRENT_TPU_TSAN="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _UNDER_TSAN], cwd=REPO, env=env, capture_output=True, text=True, timeout=300
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert json.loads(proc.stdout.splitlines()[-1]) == {
        "objects": 1,
        "stats": {"step_builds": 1, "step_reuses": 399},
        "lock": True,
        "cell": True,
        "cycles": 0,
        "races": 0,
    }


def test_metrics_render_the_two_counters(cache):
    from torrent_tpu.obs import render_obs_metrics

    TPUVerifier(4096, 8, mesh=_mesh(1))
    TPUVerifier(8192, 8, mesh=_mesh(1))
    TPUVerifier(8192, 8, mesh=_mesh(2))
    lines = render_obs_metrics().splitlines()
    assert "torrent_tpu_verifier_step_builds_total 2" in lines
    assert "torrent_tpu_verifier_step_reuses_total 1" in lines
    assert "# TYPE torrent_tpu_verifier_step_builds_total counter" in lines
    assert "# TYPE torrent_tpu_verifier_step_reuses_total counter" in lines
