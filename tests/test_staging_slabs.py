"""A recheck's two staging slabs belong to the process (models/verifier.py).

``verify_storage`` used to allocate two padded slabs a pass and pay each
one's first fill in page faults; now it checks out the pair the process
keeps (``_StagingPair``) and checks it in once nothing fills or reads it.
A kept slab holds the last pass's bytes when the next pass begins, so
these cases hold on the CPU what must never follow from that: a stale
verdict. Also what is reused, replaced or left alone, what a second
caller gets, that a failed pass gives the pair back, and the counters
``/metrics`` renders. What the kept pages are worth in seconds only the
chip says (PERF.md, PR 35).
"""

import gc
import hashlib
import itertools
import json
import os
import subprocess
import sys
import threading
import weakref

import jax
import numpy as np
import pytest

from torrent_tpu.codec.metainfo import parse_metainfo
from torrent_tpu.models import verifier as verifier_mod
from torrent_tpu.models.verifier import TPUVerifier, staging_slab_stats
from torrent_tpu.ops.padding import padded_len_for
from torrent_tpu.parallel.mesh import make_mesh
from torrent_tpu.parallel.verify import verify_pieces_tpu
from torrent_tpu.storage.storage import FsStorage, Storage
from torrent_tpu.tools.make_torrent import make_torrent

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PLEN = 4096
ALL_COUNTERS = ("staging_slab_allocs", "staging_slab_reuses", "staging_slab_transient")


@pytest.fixture
def pair(monkeypatch):
    """A keeper of this test's own in the process's place, so its counts
    start at nought and it holds nothing."""
    fresh = verifier_mod._StagingPair()
    monkeypatch.setattr(verifier_mod, "_staging_pair", fresh)
    return fresh


def _counts(allocs=0, reuses=0, transient=0) -> dict[str, int]:
    return dict(zip(ALL_COUNTERS, (allocs, reuses, transient)))


def _mesh(devices: int):
    return make_mesh(jax.devices()[:devices])


class _Torrent:
    """A seeded payload on disk under ``root`` and its torrent: one file,
    or three whose every boundary falls inside a piece; the last piece is
    short. ``files`` is ``[(path on disk, offset in the piece space,
    length)]``."""

    def __init__(self, root, layout: str, n_pieces: int, tail: int, seed: int, plen: int = PLEN, name: str = "payload"):
        self.root, self.plen = str(root), plen
        length = plen * (n_pieces - 1) + tail
        data = np.random.default_rng(seed).bytes(length)
        if layout == "single":
            cuts, target = [0, length], os.path.join(self.root, name)
            paths = [target]
        else:
            cuts = [0, 5 * plen + 1234, 11 * plen + 17, length]
            target = os.path.join(self.root, name)
            os.makedirs(target)
            paths = [os.path.join(target, f"{i}.bin") for i in range(3)]
        self.files = []
        for path, lo, hi in zip(paths, cuts, cuts[1:]):
            with open(path, "wb") as f:
                f.write(data[lo:hi])
            self.files.append((path, lo, hi - lo))
        self.info = parse_metainfo(make_torrent(target, "http://t/announce", piece_length=plen)).info
        assert self.info.num_pieces == n_pieces and self.info.length == length

    def storage(self) -> Storage:
        """A fresh one a pass: ``FsStorage`` keeps its files open, and a
        deleted file stays readable through a kept handle."""
        return Storage(FsStorage(self.root), self.info)

    def on_disk(self) -> list[bool]:
        """hashlib's verdict on what the disk holds now; what is missing
        of a file reads as nothing, so its pieces come out short."""
        stream = bytearray()
        for path, _, size in self.files:
            have = open(path, "rb").read() if os.path.exists(path) else b""
            stream += have[:size] + b"\0" * (size - len(have))
        return [
            hashlib.sha1(stream[i * self.plen : (i + 1) * self.plen]).digest() == digest
            for i, digest in enumerate(self.info.pieces)
        ]

    def pieces_over(self, lo: int, hi: int) -> set[int]:
        return set(range(lo // self.plen, (hi - 1) // self.plen + 1))

    # the three ways a payload goes bad between two rechecks; each
    # returns the pieces that must read false afterwards

    def delete_a_file(self) -> set[int]:
        path, lo, size = self.files[len(self.files) // 2]
        os.remove(path)
        return self.pieces_over(lo, lo + size)

    def truncate_a_file_mid_piece(self) -> set[int]:
        path, lo, size = self.files[-1]
        cut = (lo + size // 2) // self.plen * self.plen + 1000
        os.truncate(path, cut - lo)
        return self.pieces_over(cut, lo + size)

    def flip_a_byte(self) -> set[int]:
        path, lo, size = self.files[0]
        at = min(3 * self.plen + 5, size - 1)
        with open(path, "r+b") as f:
            f.seek(at)
            byte = f.read(1)
            f.seek(at)
            f.write(bytes([byte[0] ^ 0x01]))
        return self.pieces_over(lo + at, lo + at + 1)


def _spy_on_checkouts(pair, monkeypatch) -> list[list[int]]:
    """The addresses of the two slabs each check-out hands over."""
    seen: list[list[int]] = []
    checkout = pair.checkout

    def spy(rows, piece_length):
        staging, kept = checkout(rows, piece_length)
        seen.append([padded.ctypes.data for padded, _ in staging])
        return staging, kept

    monkeypatch.setattr(pair, "checkout", spy)
    return seen


# (1) -----------------------------------------------------------------------


@pytest.mark.parametrize("devices", [1, 4])
def test_a_second_pass_of_one_geometry_fills_the_first_ones_memory(pair, monkeypatch, tmp_path, devices):
    t = _Torrent(tmp_path, "single", 19, 77, seed=35)
    wrong = t.flip_a_byte()
    reference = t.on_disk()
    assert {i for i, ok in enumerate(reference) if not ok} == wrong
    seen = _spy_on_checkouts(pair, monkeypatch)
    for _ in range(2):
        got = verify_pieces_tpu(t.storage(), t.info, batch_size=8, mesh=_mesh(devices))
        assert got.tolist() == reference
    assert staging_slab_stats() == _counts(allocs=1, reuses=1)
    assert seen[0] == seen[1] and len(set(seen[0])) == 2
    assert not pair._out


# (2) -----------------------------------------------------------------------


@pytest.mark.parametrize("devices", [1, 4], ids=["one_device", "mesh"])
@pytest.mark.parametrize("layout", ["single", "multi"])
@pytest.mark.parametrize("harm", ["delete_a_file", "truncate_a_file_mid_piece", "flip_a_byte"])
def test_no_verdict_of_the_pass_before_survives_in_a_kept_slab(pair, tmp_path, harm, layout, devices):
    """Pass 1 over an intact payload leaves every row of both slabs
    holding bytes that hash right. Pass 2, same process and geometry,
    reads into the same rows at the same offsets: whatever the disk no
    longer gives must read as zeros, not as what the slab still holds."""
    t = _Torrent(tmp_path, layout, 19, 77, seed=7)
    mesh = _mesh(devices)
    assert verify_pieces_tpu(t.storage(), t.info, batch_size=8, mesh=mesh).all()
    wrong = getattr(t, harm)()
    assert wrong
    got = verify_pieces_tpu(t.storage(), t.info, batch_size=8, mesh=mesh)
    assert {i for i, ok in enumerate(got) if not ok} == wrong
    assert got.tolist() == t.on_disk()
    assert staging_slab_stats() == _counts(allocs=1, reuses=1)


# (3) -----------------------------------------------------------------------


@pytest.mark.parametrize("devices", [1, 4], ids=["one_device", "mesh"])
def test_a_smaller_torrent_after_a_larger_one_takes_the_row_prefix(pair, monkeypatch, tmp_path, devices):
    """Sixteen rows a batch, then eight of the same piece length: the
    second pass fills the first eight rows of the same pages, its last
    batch holds three pieces, and the five rows past them, which still
    hold pieces that hashed right a pass ago, are launched as nothing."""
    mesh = _mesh(devices)
    seen = _spy_on_checkouts(pair, monkeypatch)
    large = _Torrent(tmp_path, "single", 40, PLEN, seed=1, name="large")
    assert TPUVerifier(PLEN, 16, mesh=mesh).verify_storage(large.storage(), large.info).all()
    # the same bytes again, so a row that leaked would hash right
    small = _Torrent(tmp_path, "single", 11, 900, seed=1, name="small")
    with open(large.files[0][0], "rb") as f:
        assert open(small.files[0][0], "rb").read() == f.read(small.info.length)
    wrong = small.flip_a_byte() | small.truncate_a_file_mid_piece()
    v = TPUVerifier(PLEN, 8, mesh=mesh)
    launched: list[np.ndarray] = []
    for name in ("_verify_step_flat", "_verify_step"):

        def spy(data, nblocks, expected, _step=getattr(v, name)):
            launched.append(np.asarray(nblocks).copy())
            return _step(data, nblocks, expected)

        monkeypatch.setattr(v, name, spy)
    got = v.verify_storage(small.storage(), small.info)
    assert len(got) == 11 and {i for i, ok in enumerate(got) if not ok} == wrong
    assert got.tolist() == small.on_disk()
    assert [len(n) for n in launched] == [8, 8]
    assert launched[0].all() and launched[1][:3].all() and not launched[1][3:].any()
    assert staging_slab_stats() == _counts(allocs=1, reuses=1)
    assert seen[0] == seen[1]
    assert pair._pair[0].shape == (16, padded_len_for(PLEN))


# (4) -----------------------------------------------------------------------


def test_two_rechecks_at_once_one_takes_a_transient_pair_and_neither_waits(pair, monkeypatch, tmp_path):
    seen = _spy_on_checkouts(pair, monkeypatch)
    torrents = [_Torrent(tmp_path, "multi", 19, 77, seed=i, name=f"t{i}") for i in (1, 2)]
    wrong = [torrents[0].flip_a_byte(), torrents[1].delete_a_file()]
    both_hold_a_pair = threading.Barrier(2, timeout=120)
    got: dict[int, np.ndarray] = {}
    errors: list[BaseException] = []

    def work(i):
        met = []

        def first_batch_done(done, total):
            # a pass reports progress only after its check-out: were one
            # of the two waiting for the other's pair, this would time out
            if not met:
                met.append(both_hold_a_pair.wait())

        try:
            v = TPUVerifier(PLEN, 8, mesh=_mesh(1))
            got[i] = v.verify_storage(torrents[i].storage(), torrents[i].info, progress_cb=first_batch_done)
        except BaseException as e:  # noqa: BLE001 - handed to the asserting thread
            errors.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors and not any(t.is_alive() for t in threads)
    for i, t in enumerate(torrents):
        assert {j for j, ok in enumerate(got[i]) if not ok} == wrong[i]
        assert got[i].tolist() == t.on_disk()
    assert staging_slab_stats() == _counts(allocs=1, transient=1)
    assert not set(seen[0]) & set(seen[1])
    # the kept pair came back in
    assert not pair._out
    assert verify_pieces_tpu(torrents[0].storage(), torrents[0].info, batch_size=8, mesh=_mesh(1)).tolist() == torrents[0].on_disk()
    assert staging_slab_stats() == _counts(allocs=1, reuses=1, transient=1)


# (5) -----------------------------------------------------------------------


@pytest.mark.parametrize("other", ["piece_length", "more_rows"])
def test_another_geometry_replaces_the_kept_pair_and_one_pair_lives(pair, tmp_path, other):
    first = _Torrent(tmp_path, "single", 11, 9, seed=3, name="first")
    assert TPUVerifier(PLEN, 8, mesh=_mesh(1)).verify_storage(first.storage(), first.info).all()
    old = [weakref.ref(slab) for slab in pair._pair]
    plen, rows = (4 * PLEN, 8) if other == "piece_length" else (PLEN, 16)
    second = _Torrent(tmp_path, "single", 21, 100, seed=4, plen=plen, name="second")
    wrong = second.flip_a_byte()
    got = TPUVerifier(plen, rows, mesh=_mesh(1)).verify_storage(second.storage(), second.info)
    assert {i for i, ok in enumerate(got) if not ok} == wrong
    assert staging_slab_stats() == _counts(allocs=2)
    assert [slab.shape for slab in pair._pair] == [(rows, padded_len_for(plen))] * 2
    gc.collect()
    assert [ref() for ref in old] == [None, None]
    # and the first geometry again is served from the new pair only where it fits
    assert TPUVerifier(PLEN, 8, mesh=_mesh(1)).verify_storage(first.storage(), first.info).all()
    assert staging_slab_stats() == (_counts(allocs=3) if other == "piece_length" else _counts(allocs=2, reuses=1))


def test_a_pair_over_the_cap_is_transient_and_leaves_the_keeper_as_it_was(pair, monkeypatch, tmp_path):
    monkeypatch.setattr(verifier_mod, "STAGING_KEEP_BYTES", 2 * 8 * padded_len_for(PLEN))
    t = _Torrent(tmp_path, "single", 40, 77, seed=5)
    wrong = t.flip_a_byte()
    seen = _spy_on_checkouts(pair, monkeypatch)
    for rows in (8, 16, 8):
        got = TPUVerifier(PLEN, rows, mesh=_mesh(1)).verify_storage(t.storage(), t.info)
        assert {i for i, ok in enumerate(got) if not ok} == wrong
        kept = [slab.ctypes.data for slab in pair._pair]
        assert kept == seen[0] and pair._pair[0].shape[0] == 8 and not pair._out
    assert seen[2] == seen[0] and not set(seen[1]) & set(seen[0])
    assert staging_slab_stats() == _counts(allocs=1, reuses=1, transient=1)


def test_the_cap_is_a_gibibyte_and_both_cells_pairs_fit():
    assert verifier_mod.STAGING_KEEP_BYTES == 1 << 30
    width = padded_len_for(262144)
    assert 2 * 256 * width < 2 * 1024 * width <= verifier_mod.STAGING_KEEP_BYTES
    assert 2 * 4096 * padded_len_for(1 << 20) > verifier_mod.STAGING_KEEP_BYTES


# (6) -----------------------------------------------------------------------


class _Boom(Exception):
    pass


@pytest.mark.parametrize("devices", [1, 4], ids=["one_device", "mesh"])
@pytest.mark.parametrize("where", ["progress_cb", "read_batch"])
def test_a_pass_that_raises_checks_the_pair_back_in(pair, monkeypatch, tmp_path, where, devices):
    t = _Torrent(tmp_path, "multi", 19, 77, seed=6)
    mesh = _mesh(devices)
    storage = t.storage()
    if where == "progress_cb":

        def progress(done, total):
            raise _Boom("the caller's callback")

        kwargs = {"progress_cb": progress}
    else:
        # a stripe of the second batch's load fails in the loader's pool
        real, calls = storage.read_batch, itertools.count(1)

        def read_batch(indices, **kw):
            if next(calls) == 6:
                raise _Boom("a read that raises")
            return real(indices, **kw)

        monkeypatch.setattr(storage, "read_batch", read_batch)
        kwargs = {}
    with pytest.raises(_Boom):
        TPUVerifier(PLEN, 8, mesh=mesh).verify_storage(storage, t.info, **kwargs)
    assert not pair._out and staging_slab_stats() == _counts(allocs=1)
    assert verify_pieces_tpu(t.storage(), t.info, batch_size=8, mesh=mesh).all()
    assert staging_slab_stats() == _counts(allocs=1, reuses=1)


# the keeper itself, under threads ----------------------------------------


def _check_out_from_sixteen_threads(keeper) -> dict:
    """Sixteen threads, 200 check-outs each of two geometries, a short
    switch interval: whoever holds the kept pair holds it alone, and
    every check-out is counted once."""
    barrier = threading.Barrier(16)
    holders: list[int] = []
    kept_total: list[int] = []
    errors: list[BaseException] = []

    def work(i):
        try:
            barrier.wait(timeout=30)
            mine = 0
            for j in range(200):
                staging, kept = keeper.checkout(4 if (i + j) % 3 else 8, 64)
                assert len(staging) == 2 and staging[0][0].shape[0] == staging[0][1].shape[0]
                if kept:
                    holders.append(i)
                    assert holders == [i], holders
                    mine += 1
                    holders.remove(i)
                    keeper.checkin()
            kept_total.append(mine)
        except BaseException as e:  # noqa: BLE001 - handed to the asserting thread
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    st = keeper.stats()
    return {
        "counted": sum(st.values()),
        "kept": st["staging_slab_allocs"] + st["staging_slab_reuses"] == sum(kept_total),
        "out": keeper._out,
        "rows": keeper._pair[0].shape[0],
    }


def test_sixteen_threads_never_share_the_kept_pair():
    assert _check_out_from_sixteen_threads(verifier_mod._StagingPair()) == {
        "counted": 3200, "kept": True, "out": False, "rows": 8,
    }


_UNDER_TSAN = """
import json
from torrent_tpu.analysis import sanitizer
sanitizer.enable()
from torrent_tpu.models import verifier
from tests.test_staging_slabs import _check_out_from_sixteen_threads
result = _check_out_from_sixteen_threads(verifier._StagingPair())
snap = sanitizer.snapshot()
print(json.dumps(dict(
    result,
    lock="models.verifier._staging_lock" in snap["locks"],
    cell="models.verifier.staging.pair" in snap["cells"],
    cycles=len(snap["cycles"]),
    races=snap["lockset_race_count"],
)))
"""


def test_sixteen_threads_under_the_sanitizer():
    """The same in a process of its own with ``TORRENT_TPU_TSAN=1``,
    where the keeper's lock is an instrumented one and its pair a
    guarded cell: no lock-order cycle, no lockset race."""
    env = dict(os.environ, TORRENT_TPU_TSAN="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _UNDER_TSAN], cwd=REPO, env=env, capture_output=True, text=True, timeout=300
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert json.loads(proc.stdout.splitlines()[-1]) == {
        "counted": 3200, "kept": True, "out": False, "rows": 8,
        "lock": True, "cell": True, "cycles": 0, "races": 0,
    }


# (7) -----------------------------------------------------------------------


def test_the_counters_render_as_prometheus_text():
    from test_metrics import prom_lint
    from torrent_tpu.utils.metrics import render_staging_slab_metrics

    text = render_staging_slab_metrics(_counts(allocs=2, reuses=82, transient=1))
    prom_lint(text)
    lines = text.splitlines()
    assert "torrent_tpu_verifier_staging_slab_allocs_total 2" in lines
    assert "torrent_tpu_verifier_staging_slab_reuses_total 82" in lines
    assert "torrent_tpu_verifier_staging_slab_transient_total 1" in lines
    assert sum(line.startswith("# TYPE ") and line.endswith(" counter") for line in lines) == 3


def test_metrics_carry_them(pair, tmp_path):
    from torrent_tpu.obs import render_obs_metrics

    t = _Torrent(tmp_path, "single", 11, 9, seed=8)
    for _ in range(3):
        assert verify_pieces_tpu(t.storage(), t.info, batch_size=8, mesh=_mesh(1)).all()
    lines = render_obs_metrics().splitlines()
    assert "torrent_tpu_verifier_staging_slab_allocs_total 1" in lines
    assert "torrent_tpu_verifier_staging_slab_reuses_total 2" in lines
    assert "torrent_tpu_verifier_staging_slab_transient_total 0" in lines
