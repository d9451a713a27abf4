"""Continuous-batching hash-plane scheduler tests (torrent_tpu/sched).

The multi-tenant verify queue is deterministic on CPU: every test here
runs with the hashlib plane (or the XLA-CPU device plane for parity)
and proves the ISSUE acceptance criteria without a TPU —
cross-request coalescing to ≥0.9 batch fill, deadline flush for lone
small requests, DRR fairness under a greedy + trickle tenant pair,
typed load-shed mapped to HTTP 429 at the bridge, and CPU-path parity.
"""

from __future__ import annotations

import asyncio
import hashlib
import time

import numpy as np
import pytest

from torrent_tpu.codec.bencode import bdecode, bencode
from torrent_tpu.sched import HashPlaneScheduler, SchedRejected, SchedulerConfig
from torrent_tpu.sched.scheduler import Plane


def run(coro):
    return asyncio.run(coro)


def _pieces(n: int, plen: int = 1024, salt: int = 0) -> list[bytes]:
    return [bytes([(i + salt) % 251]) * plen for i in range(n)]


class _StallPlane(Plane):
    """Test plane that blocks until released — pins queue bytes so
    admission-control behaviour is deterministic, no timing involved."""

    def __init__(self):
        import threading

        self.release = threading.Event()

    def run(self, payloads):
        self.release.wait(timeout=30)
        return [hashlib.sha1(p).digest() for p in payloads]


class TestTenantCardinality:
    def test_idle_auto_tenants_are_evicted(self):
        """Fresh tenant names per request (attacker-controlled X-Tenant)
        must not grow per-tenant state without bound: idle auto-registered
        tenants beyond max_idle_tenants are evicted, pinned ones kept."""

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=4, flush_deadline=0.01, max_idle_tenants=8
                ),
                hasher="cpu",
            )
            try:
                sched.register_tenant("pinned", weight=0.5)
                for j in range(50):
                    got = await sched.submit(f"rnd{j}", _pieces(1, 256, salt=j))
                    assert got == [hashlib.sha1(p).digest() for p in _pieces(1, 256, salt=j)]
                snap = sched.metrics_snapshot()
                assert len(snap["tenants"]) <= 8 + 1, len(snap["tenants"])
                assert "pinned" in snap["tenants"]
                evicted = snap["evicted"]
                assert evicted["tenants"] >= 40
                # served totals stay monotonic across eviction
                live_pieces = sum(
                    t["served_pieces"] for t in snap["tenants"].values()
                )
                assert live_pieces + evicted["served_pieces"] == 50
                # rotation/queues shrink with the tenants
                for lane in sched._lanes.values():
                    assert len(lane.rotation) == len(lane.queues) <= 9
            finally:
                await sched.close()

        run(go())


class TestStagingReuse:
    def test_reused_slots_zero_stale_tails(self):
        """The SHA-1 device plane reuses staging slots across launches;
        pad_in_place needs zeroed tails, so a long-piece launch followed
        by shorter pieces in the same slot must still hash correctly
        (stale-tail zeroing, the classic staging-reuse corruption)."""

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=4, flush_deadline=0.01),
                hasher="tpu",  # the device plane (XLA CPU here) w/ slots
            )
            try:
                # launch 1: full-bucket pieces dirty the whole slot rows
                long = [bytes([i]) * 4096 for i in range(4)]
                got = await sched.submit("t", long, piece_length=4096)
                assert got == [hashlib.sha1(p).digest() for p in long]
                # launch 2, same lane: much shorter pieces — stale bytes
                # beyond each message must not leak into the hash
                short = [bytes([0x55 + i]) * 100 for i in range(4)]
                got = await sched.submit("t", short, piece_length=4096)
                assert got == [hashlib.sha1(p).digest() for p in short]
                # launch 3: ragged mix, including empty-ish rows
                mix = [b"x", b"y" * 2000, b"", b"z" * 4096]
                got = await sched.submit("t", mix, piece_length=4096)
                assert got == [hashlib.sha1(p).digest() for p in mix]
            finally:
                await sched.close()

        run(go())

    def test_pipelined_launches_stay_correct(self):
        """pipeline_depth=2 runs launches concurrently in worker threads;
        many batches of distinct payloads through one lane must demux to
        the right submitters."""

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=8, flush_deadline=0.01, pipeline_depth=2
                ),
                hasher="tpu",
            )
            try:
                outs = await asyncio.gather(
                    *(
                        sched.submit("t", _pieces(8, 512, salt=j), piece_length=512)
                        for j in range(12)
                    )
                )
                for j, got in enumerate(outs):
                    assert got == [
                        hashlib.sha1(p).digest() for p in _pieces(8, 512, salt=j)
                    ], f"submission {j} demuxed wrong"
            finally:
                await sched.close()

        run(go())


def _ragged(n: int, plen: int, salt: int = 0) -> list[bytes]:
    """``n`` payloads of ragged lengths up to ``plen``: a zero-length
    piece, a full one, and lengths around the SHA-1 block edges."""
    edges = [0, plen, 55, 56, 63, 64, 65, 1]
    return [
        bytes([(i * 7 + salt) % 251]) * min(plen, edges[i % 8] if i < 8 else (i * 37 + salt) % (plen + 1))
        for i in range(n)
    ]


def _sha1s(payloads) -> list[bytes]:
    return [hashlib.sha1(p).digest() for p in payloads]


async def _enqueue_staged(sched, tenant, pieces, piece_length, **kw):
    """The zero-copy road by hand: a slab checked out, filled with
    ``pieces`` and handed over; the caller's reference is released."""
    slab = sched.checkout_staging(piece_length, len(pieces))
    slab.prepare([len(p) for p in pieces])
    for i, p in enumerate(pieces):
        slab.view[i, : len(p)] = np.frombuffer(p, dtype=np.uint8)
    slab.finalize([True] * len(pieces))
    try:
        return await sched.enqueue_staged(tenant, slab, list(range(len(pieces))), **kw)
    finally:
        slab.release()


@pytest.fixture(scope="module", params=["mesh", "one_device"])
def ladder_plane(request):
    """A built 256-row SHA-1 device plane at 192-byte pieces: over the
    tests' 8 virtual devices (the sharded upload road) and over one
    device (the flat road a one-chip host takes)."""
    import jax

    from torrent_tpu.models import verifier as verifier_mod
    from torrent_tpu.sched.scheduler import _Sha1DevicePlane

    mp = pytest.MonkeyPatch()
    # the jitted steps are the process's (one set a mesh): a set of the
    # plane's own, so that the programs counted below are the plane's
    mp.setattr(verifier_mod, "_step_cache", verifier_mod._StepCache(1))
    if request.param == "one_device":
        real = verifier_mod.make_mesh
        mp.setattr(verifier_mod, "make_mesh", lambda devices=None: real(jax.devices()[:1]))
    try:
        plane = _Sha1DevicePlane(192, 256)
    finally:
        mp.undo()
    assert plane._verifier.mesh.size == (1 if request.param == "one_device" else 8)
    return plane


def _step_cache_sizes(plane) -> tuple[int, int]:
    """Programs held by the plane's two jitted digest steps (flat road,
    sharded road)."""
    v = plane._verifier
    return v._digest_step_flat._cache_size(), v._digest_step._cache_size()


@pytest.fixture(scope="module", params=["cpu", "sha1", "scan", "pallas"])
def any_plane(request):
    """``(kind, algo, plane, batch)``: one built plane of each kind at
    64-byte pieces and a 64-piece batch — hashlib, the SHA-1 device
    plane (a (32, 64) ladder over the tests' 8 devices), the scan plane
    and the pallas plane (interpret mode off a TPU; its batch is one
    1024-row tile)."""
    from torrent_tpu.sched.scheduler import (
        _CpuPlane,
        _Sha1DevicePlane,
        _Sha256DevicePlane,
        _Sha256PallasPlane,
    )

    kind = request.param
    if kind == "cpu":
        return kind, "sha1", _CpuPlane("sha1"), 64
    if kind == "sha1":
        return kind, "sha1", _Sha1DevicePlane(64, 64), 64
    if kind == "scan":
        return kind, "sha256", _Sha256DevicePlane(64, 64), 64
    return kind, "sha256", _Sha256PallasPlane(64, 64), 1024


class TestPlaneInterface:
    """Every plane, and a FaultyPlane around each, offers the whole
    interface; both roads give hashlib's digests; ``launch_rows`` is
    what the copying road really stages."""

    @pytest.mark.parametrize("wrapped", [False, True], ids=["bare", "faulty"])
    @pytest.mark.parametrize("fill", ["one", "partial", "full"])
    def test_members_digests_and_launched_rows(self, any_plane, wrapped, fill, monkeypatch):
        from torrent_tpu.ops.sha256_pallas import SUB_TILE_ROWS
        from torrent_tpu.sched.faults import FaultPlan, FaultyPlane
        from torrent_tpu.sched.scheduler import StagedSlab, _StagingSlots

        kind, algo, inner, batch = any_plane
        plane = FaultyPlane(FaultPlan(), inner) if wrapped else inner
        for name in ("kernel", "granule", "launch_rows", "run", "run_staged"):
            assert hasattr(plane, name), name
        assert plane.kernel == inner.kernel and plane.kernel is not None
        assert plane.granule == (SUB_TILE_ROWS if kind == "pallas" else 1)

        n = {"one": 1, "partial": 5, "full": batch}[fill]
        h = hashlib.sha256 if algo == "sha256" else hashlib.sha1
        payloads = _ragged(n, 64, salt=n)
        want = [h(p).digest() for p in payloads]

        # the copying road: the rows of every slot the launch staged
        staged: list[int] = []
        if kind != "cpu":
            real = inner._slots.stage

            def stage(chunk, rows):
                out = real(chunk, rows)
                staged.append(len(out[2]))
                return out

            monkeypatch.setattr(inner._slots, "stage", stage)
        assert plane.run(payloads) == want
        assert plane.launch_rows(n) == (sum(staged) if kind != "cpu" else n)
        want_rows = {"cpu": n, "sha1": 32 if n <= 32 else 64, "scan": 64, "pallas": 1024}
        assert plane.launch_rows(n) == want_rows[kind]

        # the zero-copy road: a slab filled as a reader fills it, its
        # rows asked for in reverse, launched in place with no stage
        pool = _StagingSlots(batch, 64)
        slab = StagedSlab(pool, pool.checkout(), algo, 64, 64)
        slab.prepare([len(p) for p in payloads])
        for i, p in enumerate(payloads):
            slab.view[i, : len(p)] = np.frombuffer(p, dtype=np.uint8)
        slab.finalize([True] * n)
        staged.clear()
        try:
            assert plane.run_staged(slab, list(range(n))[::-1]) == want[::-1]
        finally:
            slab.release()
        assert staged == [] and pool.stats()[0] == 0


class TestSha1RowLadder:
    """The SHA-1 device plane launches at the smallest rung of a fixed,
    warmed row ladder that holds the chunk (PR 25)."""

    @pytest.mark.parametrize(
        "batch,granule,want",
        [
            (256, 1, (32, 64, 128, 256)),
            (256, 8, (32, 64, 128, 256)),
            (512, 1, (32, 64, 128, 256, 512)),
            (4096, 1, (32, 128, 512, 2048, 4096)),
            (100, 1, (32, 64, 100)),
            # the 1 MiB and 512 KiB lanes under the default staging budget (below)
            (127, 1, (32, 64, 127)),
            (255, 1, (32, 64, 128, 255)),
            (96, 6, (36, 66, 96)),
            (32, 1, (32,)),
            (8, 8, (8,)),
        ],
    )
    def test_ladder_is_fixed_by_the_batch(self, batch, granule, want):
        from torrent_tpu.sched.scheduler import _row_ladder

        got = _row_ladder(batch, granule)
        assert got == want
        assert len(got) <= 5 and got[-1] == batch
        assert all(r % granule == 0 for r in got)

    @pytest.mark.parametrize(
        "piece_length,target",
        [(32 << 10, 256), (64 << 10, 256), (128 << 10, 256), (256 << 10, 256), (512 << 10, 255), (1 << 20, 127)],
    )
    def test_lane_targets_of_the_authoring_rules_piece_lengths(self, piece_length, target):
        """What the default 128 MiB staging budget leaves a lane at each
        piece length `choose_piece_length` can give: a staged slab, and a
        64 MiB fabric unit's chunk, has this many rows."""
        sched = HashPlaneScheduler(SchedulerConfig(batch_target=256), hasher="tpu")
        assert sched.chunk_for(piece_length) == target

    @pytest.mark.parametrize(
        "n,rows",
        [(1, 32), (15, 32), (16, 32), (17, 32), (32, 32), (33, 64), (64, 64),
         (65, 128), (200, 256), (256, 256), (257, 256 + 32), (512 + 40, 512 + 64)],
    )
    def test_rung_chosen_for_a_chunk(self, ladder_plane, n, rows):
        assert ladder_plane._ladder == (32, 64, 128, 256)
        assert ladder_plane.launch_rows(n) == rows
        if n <= 256:
            assert ladder_plane._rows_for(n) == rows

    @pytest.mark.parametrize("n", [1, 15, 32, 33, 64, 100, 128, 200, 256, 257])
    def test_digests_match_hashlib_at_every_rung(self, ladder_plane, n):
        payloads = _ragged(n, 192, salt=n)
        assert b"" in payloads
        before = ladder_plane._slots.stats()
        assert ladder_plane.run(payloads) == _sha1s(payloads)
        outstanding, checkouts = ladder_plane._slots.stats()
        assert outstanding == 0
        assert checkouts - before[1] == -(-n // 256)  # one slot a chunk

    @pytest.mark.parametrize("order", [(256, 5, 256), (5, 256, 40), (256, 40, 5, 130, 256)])
    def test_slot_reuse_across_rungs_zeroes_stale_tails(self, ladder_plane, order):
        """A full 256-row launch dirties every row to full width; a
        32-row launch after it restages only its rung; the next wide
        launch still finds rows 32.. as the first one left them and
        must tail-zero them for its shorter pieces (and the reverse)."""
        for k, n in enumerate(order):
            plen = (192, 70, 9, 120, 33)[k]  # shrinking and growing tails
            payloads = [bytes([(i + k) % 251 + 1]) * (plen if i % 3 else plen // 2) for i in range(n)]
            assert ladder_plane.run(payloads) == _sha1s(payloads), (k, n)
            # sequential launches keep reusing the one slot of the pool
            assert len(ladder_plane._slots._slots) == 1

    def test_every_rung_is_warmed_at_the_build(self, ladder_plane):
        """Launches at every rung, after the build, add no program to
        the jitted steps: nothing is traced or compiled in a window."""
        sizes = _step_cache_sizes(ladder_plane)
        flat = ladder_plane._verifier.mesh.size == 1
        assert sizes == ((4, 0) if flat else (0, 4))  # one road, every rung
        for n in (1, 32, 33, 64, 65, 128, 129, 256, 300):
            payloads = _pieces(n, 192, salt=n)
            assert ladder_plane.run(payloads) == _sha1s(payloads)
        assert _step_cache_sizes(ladder_plane) == sizes

    def test_geometry_hook_leaves_targets_alone(self, ladder_plane):
        """The SHA-1 plane's granule is 1: any row count is a flush
        target (the ladder follows the chunk, not the target)."""
        assert ladder_plane.granule == 1

    @pytest.mark.parametrize("target", [1, 37, 100, 255, 300])
    def test_set_lane_target_applies_any_row_count_on_a_built_lane(self, target):
        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=64, flush_deadline=0.01), hasher="tpu"
            )
            try:
                pieces = _pieces(3, 64)
                assert await sched.submit("t", pieces, piece_length=64) == _sha1s(pieces)
                assert sched._lanes[("sha1", 64)].plane is not None
                assert sched.control_surface()["lanes"]["sha1/64"]["granule"] == 1
                assert sched.set_lane_target("sha1/64", target) == target
                more = _pieces(70, 64, salt=3)  # past the plane's batch: two chunks
                assert await sched.submit("t", more, piece_length=64) == _sha1s(more)
            finally:
                await sched.close()

        run(go())

    def test_deadline_flush_counts_its_pad_and_launched_rows(self):
        """14 pieces flushed by the deadline on a 256 target launch at
        the 32-row rung: pad = 32 - 14 (it read 0 before PR 25)."""

        async def go():
            from torrent_tpu.utils.metrics import render_sched_metrics

            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=256, flush_deadline=0.02), hasher="tpu"
            )
            try:
                pieces = _ragged(14, 64)
                assert await sched.submit("t", pieces, piece_length=64) == _sha1s(pieces)
                snap = sched.metrics_snapshot()
                lane = snap["lane_stats"]["sha1/64"]
                assert lane["kernel"] == "scan" and lane["target"] == 256
                assert snap["launches"] == 1 and snap["flush_reasons"]["deadline"] == 1
                assert lane["launched_rows_total"] == 32
                assert lane["pad_rows_total"] == 32 - 14
                full = _pieces(256, 64, salt=9)  # a full take: the top rung, no pad
                assert await sched.submit("t", full, piece_length=64) == _sha1s(full)
                lane = sched.metrics_snapshot()["lane_stats"]["sha1/64"]
                assert lane["launched_rows_total"] == 32 + 256
                assert lane["pad_rows_total"] == 32 - 14
                text = render_sched_metrics(sched)
                assert 'torrent_tpu_sched_launch_pad_rows_total{lane="sha1/64"} 18' in text
                assert 'torrent_tpu_sched_launch_rows_total{lane="sha1/64"} 288' in text
            finally:
                await sched.close()

        run(go())

    def test_bisection_halves_take_lower_rungs(self):
        """A poisoned piece fails its 40-piece launch (the 64-row rung);
        the halves relaunch with fewer payloads and so at the 32-row
        rung on their own. Every attempt is charged the rows it staged."""
        from torrent_tpu.sched import SchedLaunchError
        from torrent_tpu.sched.faults import FaultPlan

        async def go():
            plan = FaultPlan(payload_prefix=b"\xde\xad")
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=64, flush_deadline=0.01,
                    plane_factory=plan.plane_factory(hasher="tpu"),
                ),
                hasher="tpu",
            )
            attempts: list[int] = []
            try:
                warm = _pieces(2, 64)
                assert await sched.submit("t", warm, piece_length=64) == _sha1s(warm)
                lane = sched._lanes[("sha1", 64)]
                assert lane.plane.inner._ladder == (32, 64)
                real = lane.plane.inner.run
                lane.plane.inner.run = lambda payloads: (attempts.append(len(payloads)), real(payloads))[1]
                before = sched.metrics_snapshot()["lane_stats"]["sha1/64"]
                pieces = _pieces(40, 64, salt=5)
                pieces[7] = b"\xde\xad" + pieces[7][2:]
                got = await asyncio.gather(
                    *(sched.submit("t", [p], piece_length=64) for p in pieces),
                    return_exceptions=True,
                )
                for i, (g, p) in enumerate(zip(got, pieces)):
                    if i == 7:
                        assert isinstance(g, SchedLaunchError), g
                    else:
                        assert g == _sha1s([p]), i
                after = sched.metrics_snapshot()
                assert after["bisections"] >= 1
                # the poisoned halves never reach the inner plane; the
                # clean ones did, each with fewer rows than the take
                assert attempts and max(attempts) < 40
                stats = after["lane_stats"]["sha1/64"]
                launched = stats["launched_rows_total"] - before["launched_rows_total"]
                pad = stats["pad_rows_total"] - before["pad_rows_total"]
                assert launched % 32 == 0 and launched >= 64 + 32 + 32
                assert launched - pad >= 40 + 39  # live rows of every attempt
            finally:
                await sched.close()

        run(go())


class TestStagedRowCounters:
    """The zero-copy road launches its slab whole; its fill has counters
    of its own, and ``pad_rows_total`` keeps its row-exact meaning."""

    @pytest.mark.parametrize("road", ["staged", "copying"])
    def test_only_a_staged_launch_moves_the_staged_rows(self, road):
        async def go():
            from torrent_tpu.utils.metrics import render_sched_metrics

            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=256, flush_deadline=0.01), hasher="tpu"
            )
            await sched.start()
            try:
                warm = _pieces(2, 64)
                assert await sched.submit("t", warm, piece_length=64) == _sha1s(warm)
                before = sched.metrics_snapshot()["lane_stats"]["sha1/64"]
                pieces = _pieces(5, 64, salt=3)
                if road == "staged":
                    fut = await _enqueue_staged(sched, "t", pieces, 64)
                    assert await fut == _sha1s(pieces)
                else:
                    assert await sched.submit("t", pieces, piece_length=64) == _sha1s(pieces)
                snap = sched.metrics_snapshot()
                after = snap["lane_stats"]["sha1/64"]
                moved = {k: after[k] - before[k] for k in
                         ("staged_launches", "staged_rows_total", "staged_live_rows_total", "pad_rows_total")}
                if road == "staged":
                    # 256 rows uploaded for 5 live; charged row-exact as before
                    assert moved == {"staged_launches": 1, "staged_rows_total": 256,
                                     "staged_live_rows_total": 5, "pad_rows_total": 0}
                    assert snap["staging"]["outstanding"] == 0
                else:  # the 32-row rung of the ladder: pad rows, and no staged row
                    assert moved == {"staged_launches": 0, "staged_rows_total": 0,
                                     "staged_live_rows_total": 0, "pad_rows_total": 32 - 5}
                text = render_sched_metrics(sched)
                for name, key in (("staged_launches_total", "staged_launches"),
                                  ("staged_rows_total", "staged_rows_total"),
                                  ("staged_live_rows_total", "staged_live_rows_total")):
                    assert f"# TYPE torrent_tpu_sched_{name} counter" in text
                    assert f'torrent_tpu_sched_{name}{{lane="sha1/64"}} {after[key]}' in text
            finally:
                await sched.close()

        run(go())


class TestParity:
    @pytest.mark.parametrize("hasher", ["cpu", "tpu"])
    def test_digests_match_hashlib(self, hasher):
        """CPU-path fallback parity: same results from the hashlib plane
        and the device plane (XLA-CPU here), both vs hashlib."""

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=16, flush_deadline=0.01), hasher=hasher
            )
            try:
                pieces = _pieces(23, 700)  # ragged: crosses batch_target
                got = await sched.submit("t", pieces, algo="sha1")
                assert got == [hashlib.sha1(p).digest() for p in pieces]
            finally:
                await sched.close()

        run(go())

    @pytest.mark.parametrize("hasher", ["cpu", "tpu"])
    def test_verify_mode_flags(self, hasher):
        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=8, flush_deadline=0.01), hasher=hasher
            )
            try:
                pieces = _pieces(10, 300)
                expected = [hashlib.sha1(p).digest() for p in pieces]
                expected[4] = b"\x00" * 20
                ok = await sched.submit("t", pieces, expected=expected)
                assert isinstance(ok, bytes) and len(ok) == 10
                assert ok[4] == 0 and all(ok[i] == 1 for i in range(10) if i != 4)
            finally:
                await sched.close()

        run(go())

    def test_sha256_lane(self):
        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=8, flush_deadline=0.01), hasher="cpu"
            )
            try:
                pieces = _pieces(5, 200)
                got = await sched.submit("t", pieces, algo="sha256")
                assert got == [hashlib.sha256(p).digest() for p in pieces]
            finally:
                await sched.close()

        run(go())

    def test_empty_submission(self):
        async def go():
            sched = HashPlaneScheduler(hasher="cpu")
            try:
                assert await sched.submit("t", []) == []
                assert await sched.submit("t", [], expected=[]) == b""
            finally:
                await sched.close()

        run(go())


class TestAssembler:
    def test_deadline_flush_for_lone_small_request(self):
        """A lone 4-piece request must never be stranded behind a big
        batch target: the deadline timer flushes it."""

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=256, flush_deadline=0.05), hasher="cpu"
            )
            try:
                t0 = time.monotonic()
                pieces = _pieces(4)
                got = await asyncio.wait_for(sched.submit("lone", pieces), 5)
                elapsed = time.monotonic() - t0
                assert got == [hashlib.sha1(p).digest() for p in pieces]
                snap = sched.metrics_snapshot()
                assert snap["flush_reasons"]["deadline"] == 1
                assert snap["flush_reasons"]["full"] == 0
                assert elapsed < 3.0
            finally:
                await sched.close()

        run(go())

    def test_cross_request_coalescing_fills_batches(self):
        """≥8 concurrent submitters of small piece counts reach a mean
        batch-fill ratio ≥0.9 of the configured target."""

        async def go():
            target = 64
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=target, flush_deadline=0.5), hasher="cpu"
            )
            try:
                # 8 tenants × 32 pieces = 4 exactly-full launches
                outs = await asyncio.gather(
                    *(
                        sched.submit(f"client{j}", _pieces(32, salt=j))
                        for j in range(8)
                    )
                )
                for j, got in enumerate(outs):
                    want = [hashlib.sha1(p).digest() for p in _pieces(32, salt=j)]
                    assert got == want
                snap = sched.metrics_snapshot()
                assert snap["launches"] >= 1
                assert snap["mean_fill"] >= 0.9, snap
                assert snap["flush_reasons"]["full"] >= 1
            finally:
                await sched.close()

        run(go())

    def test_shutdown_flushes_pending(self):
        """close() launches what's queued (reason 'shutdown') instead of
        dropping it."""

        async def go():
            sched = HashPlaneScheduler(
                # deadline far beyond the test: only shutdown can flush
                SchedulerConfig(batch_target=1024, flush_deadline=60.0),
                hasher="cpu",
            )
            pieces = _pieces(3)
            fut = await sched.enqueue("t", pieces)
            await sched.close()
            got = await asyncio.wait_for(fut, 5)
            assert got == [hashlib.sha1(p).digest() for p in pieces]
            assert sched.metrics_snapshot()["flush_reasons"]["shutdown"] == 1

        run(go())

    def test_geometry_lanes_are_separate(self):
        """Different piece-length buckets get their own lanes (the
        geometry-grouped compile cache), same algo."""

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=4, flush_deadline=0.01), hasher="cpu"
            )
            try:
                a = await sched.submit("t", _pieces(4, 512))
                b = await sched.submit("t", _pieces(4, 100_000))
                assert a and b
                assert sched.metrics_snapshot()["lanes"] == 2
            finally:
                await sched.close()

        run(go())


class TestFlushHint:
    """``flush=True``: the submitter sends nothing more until this
    submission resolves, so its lane takes at once. The deadline here is
    5 s and every await is cut at 2 s: a lane that sat the hint out
    fails by time-out, not by luck."""

    TARGET, PLEN, DEADLINE, SOON = 8, 64, 5.0, 2.0

    async def _enqueue(self, sched, road, tenant, pieces, **kw):
        if road == "bytes":
            return await sched.enqueue(tenant, pieces, piece_length=self.PLEN, **kw)
        return await _enqueue_staged(sched, tenant, pieces, self.PLEN, **kw)

    @pytest.mark.parametrize("road", ["bytes", "staged"])
    @pytest.mark.parametrize(
        "case", ["under_target", "rides_along", "fills_target", "not_sticky", "shed", "shutdown"]
    )
    def test_a_hinted_submission_is_launched_at_once(self, case, road):
        tight = case == "shed"  # a queue that holds four pieces

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=self.TARGET, flush_deadline=self.DEADLINE,
                    max_queue_bytes=4 * self.PLEN if tight else 256 << 20,
                ),
                hasher="cpu",
            )
            reasons = lambda: sched.metrics_snapshot()["flush_reasons"]  # noqa: E731
            lane = lambda: sched._lanes[("sha1", self.PLEN)]  # noqa: E731
            idle = {"full": 0, "deadline": 0, "hint": 0, "shutdown": 0}
            hinted = _pieces(3, self.PLEN, salt=1)
            plain = _pieces(2, self.PLEN, salt=9)
            t0 = time.monotonic()
            closed = False

            async def stays_queued(seen):
                """Unhinted traffic after it waits for fill or its deadline."""
                later = await self._enqueue(sched, road, "other", plain)
                _, waiting = await asyncio.wait({later}, timeout=0.3)
                assert waiting == {later} and reasons() == seen
                assert lane().flush_pending == 0 and lane().pending_pieces == 2
                return later

            try:
                if case == "under_target":
                    fut = await self._enqueue(sched, road, "fabric", hinted, flush=True)
                    assert await asyncio.wait_for(fut, self.SOON) == _sha1s(hinted)
                    assert reasons() == {**idle, "hint": 1}
                    from torrent_tpu.utils.metrics import render_sched_metrics

                    assert 'torrent_tpu_sched_flush_total{reason="hint"} 1' in render_sched_metrics(sched)
                elif case == "rides_along":
                    # another tenant's unhinted tickets, queued in the lane
                    # before: they ride the launch the hint sends
                    other = await self._enqueue(sched, road, "other", plain)
                    fut = await self._enqueue(sched, road, "fabric", hinted, flush=True)
                    assert await asyncio.wait_for(fut, self.SOON) == _sha1s(hinted)
                    assert await asyncio.wait_for(other, self.SOON) == _sha1s(plain)
                    assert reasons() == {**idle, "hint": 1}
                    assert sched.metrics_snapshot()["launches"] == 1
                elif case == "fills_target":
                    full = _pieces(self.TARGET, self.PLEN, salt=4)
                    fut = await self._enqueue(sched, road, "fabric", full, flush=True)
                    assert await asyncio.wait_for(fut, self.SOON) == _sha1s(full)
                    assert reasons() == {**idle, "full": 1}
                elif case == "not_sticky":
                    fut = await self._enqueue(sched, road, "fabric", hinted, flush=True)
                    assert await asyncio.wait_for(fut, self.SOON) == _sha1s(hinted)
                    later = await stays_queued({**idle, "hint": 1})
                    await sched.close()
                    closed = True
                    assert await asyncio.wait_for(later, self.SOON) == _sha1s(plain)
                    assert reasons() == {**idle, "hint": 1, "shutdown": 1}
                elif case == "shed":
                    with pytest.raises(SchedRejected):
                        await self._enqueue(
                            sched, road, "fabric", _pieces(6, self.PLEN), flush=True
                        )
                    # nothing of the shed submission is queued or remembered
                    await stays_queued(idle)
                else:  # shutdown: closing before the lane's loop has run
                    fut = await self._enqueue(sched, road, "fabric", hinted, flush=True)
                    assert lane().flush_pending == 3
                    await sched.close()
                    closed = True
                    assert await asyncio.wait_for(fut, self.SOON) == _sha1s(hinted)
                    assert reasons() == {**idle, "shutdown": 1}
            finally:
                if not closed:
                    await sched.close()
            assert lane().flush_pending == 0 and lane().pending_pieces == 0
            assert sched.metrics_snapshot()["staging"]["outstanding"] == 0
            assert time.monotonic() - t0 < self.DEADLINE  # no take sat a deadline out

        run(go())


class TestFairnessAndBackpressure:
    def test_greedy_plus_trickle_tenant(self):
        """ISSUE acceptance: under a saturating tenant plus a trickle
        tenant, the trickle tenant completes without timeout and the
        greedy tenant observes backpressure (shed) — deterministic, no
        TPU, no sleeps in the assertion path."""

        async def go():
            stall = _StallPlane()
            cfg = SchedulerConfig(
                batch_target=8,
                flush_deadline=0.02,
                max_queue_bytes=64 << 10,
                max_tenant_bytes=16 << 10,
                drr_quantum=2048,  # small quantum → per-pass interleave
                plane_factory=lambda a, b, t, sha256_backend=None: stall,
            )
            sched = HashPlaneScheduler(cfg, hasher="cpu")
            try:
                # greedy saturates: keeps submitting until admission
                # control sheds it (its queue bound is 16 KiB)
                greedy_futs = []
                shed = 0
                for i in range(64):
                    try:
                        greedy_futs.append(
                            await sched.enqueue("greedy", _pieces(4, 1024, salt=i))
                        )
                    except SchedRejected as e:
                        shed += 1
                        assert e.tenant == "greedy"
                        assert e.reason == "queue full"
                assert shed > 0, "greedy tenant never saw backpressure"
                assert sched.metrics_snapshot()["shed_total"] == shed

                # trickle submits one small request AFTER the greedy
                # backlog exists; DRR must serve it from an early batch
                trickle_fut = await sched.enqueue("trickle", _pieces(2, 512))
                stall.release.set()  # let launches run
                got = await asyncio.wait_for(trickle_fut, 10)
                assert got == [hashlib.sha1(p).digest() for p in _pieces(2, 512)]
                # the greedy backlog still drains correctly afterwards
                for i, fut in enumerate(greedy_futs):
                    res = await asyncio.wait_for(fut, 10)
                    assert res == [
                        hashlib.sha1(p).digest() for p in _pieces(4, 1024, salt=i)
                    ]
                snap = sched.metrics_snapshot()
                assert snap["tenants"]["trickle"]["served_pieces"] == 2
                assert snap["tenants"]["greedy"]["shed"] == shed
            finally:
                stall.release.set()
                await sched.close()

        run(go())

    def test_drr_serves_trickle_before_greedy_tail(self):
        """Byte-fair DRR: with a deep greedy backlog queued first, a
        later trickle piece is still served in the FIRST post-backlog
        launch round, not after the whole backlog."""

        async def go():
            order: list[str] = []

            class _RecordingPlane(Plane):
                def run(self, payloads):
                    order.append(f"launch:{len(payloads)}")
                    return [hashlib.sha1(p).digest() for p in payloads]

            stall = _StallPlane()
            first = [True]

            class _GatePlane(Plane):
                # first launch stalls (pins the queue while we enqueue),
                # later launches record
                def run(self, payloads):
                    if first[0]:
                        first[0] = False
                        stall.release.wait(timeout=30)
                    return _RecordingPlane().run(payloads)

            cfg = SchedulerConfig(
                batch_target=8,
                flush_deadline=0.02,
                drr_quantum=1024,
                plane_factory=lambda a, b, t, sha256_backend=None: _GatePlane(),
            )
            sched = HashPlaneScheduler(cfg, hasher="cpu")
            try:
                # prime: one piece launches immediately and stalls the lane
                prime = await sched.enqueue("greedy", _pieces(1, 64))
                await asyncio.sleep(0.1)  # let the stalled launch start
                # deep greedy backlog + one trickle piece behind it
                greedy = [
                    await sched.enqueue("greedy", _pieces(8, 1024, salt=i))
                    for i in range(8)
                ]
                trickle = await sched.enqueue("trickle", _pieces(1, 1024))
                done_at = {}
                counter = [0]

                def mark(name):
                    def cb(_fut):
                        counter[0] += 1
                        done_at[name] = counter[0]

                    return cb

                trickle.add_done_callback(mark("trickle"))
                greedy[-1].add_done_callback(mark("greedy_tail"))
                stall.release.set()
                await asyncio.wait_for(
                    asyncio.gather(prime, trickle, *greedy), 15
                )
                # trickle resolved before the last greedy submission
                assert done_at["trickle"] < done_at["greedy_tail"], done_at
            finally:
                stall.release.set()
                await sched.close()

        run(go())

    def test_blocking_submit_waits_instead_of_shedding(self):
        """wait=True is the streaming-backpressure path: over-budget
        submits delay until a launch frees bytes, then succeed."""

        async def go():
            stall = _StallPlane()
            cfg = SchedulerConfig(
                batch_target=4,
                flush_deadline=0.01,
                max_queue_bytes=8 << 10,
                plane_factory=lambda a, b, t, sha256_backend=None: stall,
            )
            sched = HashPlaneScheduler(cfg, hasher="cpu")
            try:
                first = await sched.enqueue("s", _pieces(8, 1024))  # fills budget
                waited = asyncio.ensure_future(
                    sched.submit("s", _pieces(2, 1024), wait=True)
                )
                await asyncio.sleep(0.1)
                assert not waited.done(), "blocking submit did not block"
                stall.release.set()
                got = await asyncio.wait_for(waited, 10)
                assert got == [hashlib.sha1(p).digest() for p in _pieces(2, 1024)]
                await asyncio.wait_for(first, 10)
            finally:
                stall.release.set()
                await sched.close()

        run(go())

    def test_oversize_submission_sheds_on_idle_queue(self):
        """A single submission bigger than the budget must shed on the
        non-blocking path even when the queue is empty — the empty-queue
        escape exists only for wait=True (livelock avoidance), else one
        giant request blows past both bounds and 429s everyone behind it."""

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=4,
                    flush_deadline=0.01,
                    max_queue_bytes=4096,
                    max_tenant_bytes=4096,
                ),
                hasher="cpu",
            )
            try:
                with pytest.raises(SchedRejected) as ei:
                    await sched.enqueue("t", _pieces(8, 1024))  # 8 KiB > 4 KiB
                assert ei.value.reason == "queue full"
                # the blocking path still admits the oversize lone
                # submission once the queue is empty (can never fit, so
                # waiting would livelock)
                got = await sched.submit("t", _pieces(8, 1024), wait=True)
                assert got == [hashlib.sha1(p).digest() for p in _pieces(8, 1024)]
            finally:
                await sched.close()

        run(go())

    def test_typed_rejection_fields(self):
        async def go():
            stall = _StallPlane()
            cfg = SchedulerConfig(
                max_queue_bytes=2048, plane_factory=lambda a, b, t, sha256_backend=None: stall
            )
            sched = HashPlaneScheduler(cfg, hasher="cpu")
            try:
                await sched.enqueue("t", _pieces(2, 1024))  # fills the budget
                with pytest.raises(SchedRejected) as ei:
                    await sched.enqueue("t", _pieces(1, 1024))
                assert ei.value.reason == "queue full"
                assert ei.value.tenant == "t"
                assert ei.value.limit_bytes == 2048
                assert ei.value.queued_bytes == 2048
            finally:
                stall.release.set()
                await sched.close()

        run(go())


class TestSha256PallasLane:
    """The v2 fast path: scheduler sha256 lanes on the pallas plane
    (interpret mode on CPU — same dispatch path, deterministic)."""

    def test_pallas_lane_parity_and_sentinel_rows(self):
        """A partial-fill launch pads to the 1024-row sub-tile granule
        with nblocks=0 sentinels; ragged live rows (incl. an empty
        piece) hash bit-identically to hashlib, and the pad waste is
        observable per lane."""

        async def go():
            from torrent_tpu.utils.metrics import render_sched_metrics

            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=1024, flush_deadline=0.05, sha256_backend="pallas"
                ),
                hasher="tpu",
            )
            try:
                pieces = [b"", b"x" * 200, b"y" * 64, b"z" * 256, b"w" * 129]
                got = await sched.submit(
                    "t", pieces, algo="sha256", piece_length=256
                )
                assert got == [hashlib.sha256(p).digest() for p in pieces]
                snap = sched.metrics_snapshot()
                assert snap["launch_failures"] == 0
                assert snap["cpu_fallback_launches"] == 0, "fell back off pallas"
                lane = snap["lane_stats"]["sha256/256"]
                assert lane["backend"] == "pallas"
                assert lane["pad_rows_total"] == 1024 - len(pieces)
                # staging-slot reuse across launches: a second, shorter
                # ragged batch must not see the first launch's stale bytes
                short = [b"a", b"bb" * 100, b"", b"c" * 256]
                got = await sched.submit(
                    "t", short, algo="sha256", piece_length=256
                )
                assert got == [hashlib.sha256(p).digest() for p in short]
                text = render_sched_metrics(sched)
                assert 'torrent_tpu_sched_launch_pad_rows_total{lane="sha256/256"}' in text
                assert 'torrent_tpu_sched_lane_fill_ratio{lane="sha256/256"}' in text
                assert 'backend="pallas"' in text
            finally:
                await sched.close()

        run(go())

    def test_flush_target_snaps_to_tile_and_full_launch_wastes_zero(self):
        """ISSUE acceptance: the sha256 lane flush target snaps to a
        tile multiple (batch_target 300 → 1024) and a full-target launch
        stages zero pad rows."""

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=300, flush_deadline=0.5, sha256_backend="pallas"
                ),
                hasher="tpu",
            )
            try:
                assert sched.chunk_for(64, "sha256") == 1024
                assert sched.chunk_for(64) == 300  # sha1 lanes unchanged
                pieces = [bytes([i % 251]) * 64 for i in range(1024)]
                got = await sched.submit(
                    "t", pieces, algo="sha256", piece_length=64
                )
                assert got == [hashlib.sha256(p).digest() for p in pieces]
                snap = sched.metrics_snapshot()
                lane = snap["lane_stats"]["sha256/64"]
                assert lane["target"] == 1024
                assert lane["launches"] == 1
                assert lane["mean_fill"] == 1.0
                assert lane["pad_rows_total"] == 0, lane
                assert snap["flush_reasons"]["full"] == 1
            finally:
                await sched.close()

            # a budget-clamped target whose only legal tiling is the
            # slow tile_sub=8 (5120 rows) rounds down to a full
            # configured-tile multiple (4096 @ tile_sub 32) instead
            from torrent_tpu.ops.padding import padded_len_for

            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=8192,
                    staging_budget=5500 * padded_len_for(64),
                    sha256_backend="pallas",
                ),
                hasher="tpu",
            )
            assert sched._lane_plan("sha256", 64) == ("pallas", 4096)
            await sched.close()

            # but a configured target that tiles legally at 24 sublanes
            # stands — no silent shrink over a mild tiling preference
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=6144, sha256_backend="pallas"),
                hasher="tpu",
            )
            assert sched._lane_plan("sha256", 64) == ("pallas", 6144)
            await sched.close()

        run(go())

    def test_scan_fallback_selection(self):
        """Backend selection end to end: explicit scan pins the lax.scan
        plane, a bucket whose tile floor blows the staging budget falls
        back to scan even under pallas, and a cpu-hasher scheduler never
        consults the device backends at all."""
        from torrent_tpu.sched.scheduler import (
            _Sha256DevicePlane,
            _Sha256PallasPlane,
            build_builtin_plane,
        )

        plane = build_builtin_plane("tpu", "sha256", 256, 64, sha256_backend="scan")
        assert isinstance(plane, _Sha256DevicePlane)
        plane = build_builtin_plane("tpu", "sha256", 256, 64, sha256_backend="pallas")
        assert isinstance(plane, _Sha256PallasPlane)

        async def go():
            # explicit scan: parity through the scheduler
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=8, flush_deadline=0.05, sha256_backend="scan"
                ),
                hasher="tpu",
            )
            try:
                pieces = _pieces(5, 200)
                got = await sched.submit("t", pieces, algo="sha256")
                assert got == [hashlib.sha256(p).digest() for p in pieces]
                lane = sched.metrics_snapshot()["lane_stats"]["sha256/256"]
                assert lane["backend"] == "scan"
                # a scan launch is the whole batch: 5 live rows of 8
                assert lane["launched_rows_total"] == 8
                assert lane["pad_rows_total"] == 8 - 5
            finally:
                await sched.close()

            # staging budget fallback: a 1 MiB bucket's 1024-row tile
            # floor exceeds a 64 MiB budget → scan, target un-snapped
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=2048,
                    staging_budget=64 << 20,
                    sha256_backend="pallas",
                ),
                hasher="tpu",
            )
            backend, target = sched._lane_plan("sha256", 1 << 20)
            assert backend == "scan"
            assert target == (64 << 20) // 1048704  # afford, not snapped
            await sched.close()

        run(go())

        with pytest.raises(ValueError, match="auto|pallas|scan"):
            from torrent_tpu.sched import resolve_sha256_backend

            resolve_sha256_backend("mosaic")

    def test_plane_factory_honors_budget_scan_fallback(self):
        """A FaultPlan factory carrying an explicit 'pallas' pin (bridge
        --fault-plan + --sha256-backend pallas) must not override the
        lane's budget-forced scan fallback: _build_plane passes the
        lane's resolved backend through the factory seam, so the pinned
        kernel's ≥1024-row tile floor can't allocate staging far beyond
        the configured budget."""
        from torrent_tpu.sched.faults import FaultPlan, FaultyPlane
        from torrent_tpu.sched.scheduler import (
            _Sha256DevicePlane,
            _Sha256PallasPlane,
        )

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=2048,
                    staging_budget=64 << 20,  # < the 1 MiB bucket's tile floor
                    sha256_backend="pallas",
                    plane_factory=FaultPlan().plane_factory(  # no-op: wiring only
                        hasher="tpu", sha256_backend="pallas"
                    ),
                ),
                hasher="tpu",
            )
            try:
                lane = sched._lane("sha256", 1 << 20)
                assert lane.backend == "scan"
                plane = sched._build_plane(lane)
                assert isinstance(plane, FaultyPlane)
                assert isinstance(plane.inner, _Sha256DevicePlane), type(plane.inner)
                # where the budget affords the tile floor, the pin stands
                lane = sched._lane("sha256", 256)
                assert lane.backend == "pallas"
                plane = sched._build_plane(lane)
                assert isinstance(plane.inner, _Sha256PallasPlane), type(plane.inner)
            finally:
                await sched.close()

        run(go())

    def test_interleave2_suppressed_on_sub_tile_launches(self, monkeypatch):
        """The interleave2 knob needs >=16 sublanes with whole-vreg
        halves; a 1024-row sub-tile launch silently runs the straight
        kernel (and still matches hashlib) instead of erroring."""
        from torrent_tpu.ops import sha256_pallas as sp
        from torrent_tpu.sched.scheduler import _Sha256PallasPlane

        monkeypatch.setattr(sp, "INTERLEAVE2", True)
        plane = _Sha256PallasPlane(256, 2048)
        assert plane._plan(5) == (1024, 8, False)  # il2 off: ts < 16
        assert plane._plan(2048) == (2048, 16, True)  # il2 composes at ts 16
        got = plane.run([b"q" * 200, b"r" * 64])
        assert got == [hashlib.sha256(b"q" * 200).digest(),
                       hashlib.sha256(b"r" * 64).digest()]

    def test_padded_admission_charges_staging_footprint(self):
        """Admission accounting charges the padded staging row, not raw
        payload bytes: tiny pieces in a big bucket pin full rows, so the
        queue bound reflects what launches actually stage."""
        from torrent_tpu.ops.padding import padded_len_for

        async def go():
            row = padded_len_for(4096)  # 4224
            stall = _StallPlane()
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=64,
                    flush_deadline=0.02,
                    max_queue_bytes=4 * row,
                    plane_factory=lambda a, b, t, sha256_backend=None: stall,
                ),
                hasher="tpu",
            )
            try:
                # 4 ten-byte pieces: 40 raw bytes, but 4 staging rows —
                # exactly the budget
                futs = [
                    await sched.enqueue("t", [b"0123456789"], piece_length=4096)
                    for _ in range(4)
                ]
                assert sched.metrics_snapshot()["queue_bytes"] == 4 * row
                with pytest.raises(SchedRejected) as ei:
                    await sched.enqueue("t", [b"x"], piece_length=4096)
                assert ei.value.queued_bytes == 4 * row
                stall.release.set()
                for fut in futs:
                    await asyncio.wait_for(fut, 10)
                # release returns the charged (padded) bytes, not raw
                assert sched.metrics_snapshot()["queue_bytes"] == 0
            finally:
                stall.release.set()
                await sched.close()

        run(go())

    def test_breaker_and_fault_plan_through_pallas_plane(self):
        """Fault-plan / breaker compatibility through the plane_factory
        seam: a FaultPlan wrapping the pallas plane still trips the lane
        to the CPU plane (digests stay correct) and recovers via the
        half-open probe back onto pallas; FaultyPlane delegates the
        granule and launch_rows to the wrapped plane."""
        from torrent_tpu.sched import FaultPlan

        plan = FaultPlan(fail_first=2)
        factory = plan.plane_factory(hasher="tpu", sha256_backend="pallas")
        wrapped = factory("sha256", 256, 1024)
        assert wrapped.granule == 1024 and wrapped.kernel == "pallas"
        assert wrapped.launch_rows(5) == 1024

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=1024,
                    flush_deadline=0.05,
                    breaker_threshold=2,
                    breaker_cooldown=300.0,
                    sha256_backend="pallas",
                    plane_factory=plan.plane_factory(
                        hasher="tpu", sha256_backend="pallas"
                    ),
                ),
                hasher="tpu",
            )
            try:
                pieces = [bytes([i + 1]) * 64 for i in range(4)]
                want = [hashlib.sha256(p).digest() for p in pieces]
                got = await sched.submit("t", pieces, algo="sha256", piece_length=256)
                assert got == want, "CPU degradation digests wrong"
                snap = sched.metrics_snapshot()
                lane = next(iter(snap["breakers"].values()))
                assert lane["state"] == "open", lane
                assert snap["cpu_fallback_launches"] > 0
                # degraded launches run on hashlib, which stages nothing:
                # the tile-padding waste counter must not grow while open
                pads_open = snap["lane_stats"]["sha256/256"]["pad_rows_total"]
                got = await sched.submit("t", pieces, algo="sha256", piece_length=256)
                assert got == want
                stats = sched.metrics_snapshot()["lane_stats"]["sha256/256"]
                assert stats["pad_rows_total"] == pads_open, stats
                # rewind the cooldown: next launch is the half-open probe
                # through the real pallas plane, which re-closes the lane
                for ln in sched._lanes.values():
                    with ln.breaker.lock:
                        ln.breaker.opened_at -= 1e6
                got = await sched.submit("t", pieces, algo="sha256", piece_length=256)
                assert got == want
                lane = next(iter(sched.metrics_snapshot()["breakers"].values()))
                assert lane["state"] == "closed", lane
            finally:
                await sched.close()

        run(go())


class TestDoctorV2:
    def test_doctor_v2_smoke(self):
        """doctor --v2: leaf + merkle-pair digests vs hashlib through
        the scheduler's pallas lane, interpret-safe on CPU."""
        from torrent_tpu.tools import doctor

        detail = run(doctor._v2_smoke())
        assert "parity ok" in detail


# ----------------------------------------------------------- sessions


def _build_torrent(length, piece_len, seed=0, name="s"):
    from torrent_tpu.codec.metainfo import InfoDict
    from torrent_tpu.storage.storage import MemoryStorage, Storage

    rng = np.random.default_rng(seed)
    payload = rng.integers(0, 256, size=length, dtype=np.uint8).tobytes()
    pieces = tuple(
        hashlib.sha1(payload[i : i + piece_len]).digest()
        for i in range(0, length, piece_len)
    )
    info = InfoDict(
        name=name, piece_length=piece_len, pieces=pieces, length=length, files=None
    )
    storage = Storage(MemoryStorage(), info)
    for off in range(0, length, 1 << 20):
        storage.set(off, payload[off : off + (1 << 20)])
    return info, storage


class TestSchedulerSessions:
    def test_verify_pieces_sched_matches_cpu(self):
        from torrent_tpu.parallel.verify import verify_pieces, verify_pieces_sched

        async def go():
            info, storage = _build_torrent(300_000, 16384, seed=3)
            storage.method.set(("s",), 33_000, b"XX")  # corrupt piece 2
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=8, flush_deadline=0.05), hasher="cpu"
            )
            try:
                bf = await verify_pieces_sched(storage, info, sched, tenant="cli")
            finally:
                await sched.close()
            want = verify_pieces(storage, info, hasher="cpu")
            assert (bf == want).all()
            assert not bf[2] and bf[0]

        run(go())

    def test_verify_library_sched_coalesces_across_torrents(self):
        """Cross-torrent coalescing: 6 torrents × 24 pieces at one
        geometry = 144 pieces = 3 full launches of 48 — the per-torrent
        ragged tails ride shared launches instead of flushing alone."""
        from torrent_tpu.parallel.bulk import verify_library_sched

        async def go():
            items = [
                (storage, info)
                for info, storage in (
                    _build_torrent(24 * 4096, 4096, seed=i, name=f"t{i}")
                    for i in range(6)
                )
            ]
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=48, flush_deadline=0.5), hasher="cpu"
            )
            try:
                res = await verify_library_sched(items, sched, tenant="bulk")
                snap = sched.metrics_snapshot()
            finally:
                await sched.close()
            assert all(bf.all() for bf in res.bitfields)
            assert res.n_pieces == 144
            assert snap["mean_fill"] >= 0.9, snap
            # 144 pieces at target 48: exactly 3 launches, all full
            assert snap["launches"] == 3
            assert snap["flush_reasons"]["full"] == 3

        run(go())

    def test_session_recheck_rides_scheduler_as_selfheal(self):
        """session/torrent.py resume recheck uses the shared queue as the
        low-priority 'selfheal' tenant when a scheduler is configured."""

        async def go():
            import dataclasses

            from torrent_tpu.session.torrent import Torrent, TorrentConfig

            info, storage = _build_torrent(200_000, 16384, seed=7, name="heal")
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=8, flush_deadline=0.05), hasher="cpu"
            )

            from torrent_tpu.codec.metainfo import Metainfo

            meta = Metainfo(
                announce="",
                info=info,
                info_hash=hashlib.sha1(b"heal").digest(),
                raw={},
            )
            torrent = Torrent(
                metainfo=meta,
                storage=storage,
                peer_id=b"-TT0001-xxxxxxxxxxxx",
                port=0,
                config=dataclasses.replace(
                    TorrentConfig(), scheduler=sched, selfheal_weight=0.25
                ),
            )
            try:
                await torrent.recheck()
                assert torrent.bitfield.complete
                snap = sched.metrics_snapshot()
                assert snap["tenants"]["selfheal"]["served_pieces"] == info.num_pieces
                assert snap["tenants"]["selfheal"]["weight"] == 0.25
            finally:
                await sched.close()

        run(go())


# ------------------------------------------------------------- bridge


async def _post(port, path, headers, body):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    head = [f"POST {path} HTTP/1.1", "Host: x", f"Content-Length: {len(body)}"]
    for k, v in headers.items():
        head.append(f"{k}: {v}")
    writer.write(("\r\n".join(head) + "\r\n\r\n").encode() + body)
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    clen = 0
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b""):
            break
        if line.lower().startswith(b"content-length:"):
            clen = int(line.split(b":", 1)[1])
    resp = await reader.readexactly(clen)
    writer.close()
    return status, resp


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
    resp = await reader.readexactly(clen)
    writer.close()
    return status, resp


class TestBridgeScheduler:
    def test_concurrent_bridge_clients_coalesce(self):
        """ISSUE acceptance: ≥8 concurrent bridge clients each submitting
        small piece counts achieve mean batch fill ≥0.9 of the target,
        with flush-reason and batch-fill metrics visible in /metrics."""
        from torrent_tpu.bridge.service import BridgeServer

        async def go():
            server = await BridgeServer(
                port=0, hasher="cpu", batch_target=64, flush_deadline_ms=500
            ).start()
            try:
                async def client(j):
                    pieces = _pieces(16, 2048, salt=j)
                    status, resp = await _post(
                        server.port,
                        "/v1/digests",
                        {"X-Tenant": f"client{j}"},
                        bencode({b"pieces": pieces}),
                    )
                    assert status == 200
                    got = bdecode(resp)[b"digests"]
                    assert got == [hashlib.sha1(p).digest() for p in pieces]

                # 12 clients × 16 pieces = 192 = 3 full 64-piece launches
                await asyncio.gather(*(client(j) for j in range(12)))
                snap = server.sched.metrics_snapshot()
                assert snap["mean_fill"] >= 0.9, snap
                status, resp = await _get(server.port, "/metrics")
                assert status == 200
                text = resp.decode()
                assert "torrent_tpu_sched_batch_fill_ratio" in text
                assert 'torrent_tpu_sched_flush_total{reason="full"}' in text
                assert 'tenant="client0"' in text
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_queue_full_maps_to_429(self):
        """Typed SchedRejected surfaces as HTTP 429 through the bridge."""
        from torrent_tpu.bridge.service import BridgeServer

        async def go():
            server = await BridgeServer(
                port=0, hasher="cpu", max_queue_mb=1, tenant_max_mb=1
            ).start()
            try:
                stall = _StallPlane()
                server.sched.config.plane_factory = lambda a, b, t, sha256_backend=None: stall
                # first request fills the 1 MiB budget and stalls in-plane
                big = asyncio.ensure_future(
                    _post(
                        server.port,
                        "/v1/digests",
                        {},
                        bencode({b"pieces": [b"z" * (1 << 20)]}),
                    )
                )
                # wait until the scheduler holds the bytes
                for _ in range(200):
                    if server.sched.metrics_snapshot()["queue_bytes"] > 0:
                        break
                    await asyncio.sleep(0.01)
                status, resp = await _post(
                    server.port,
                    "/v1/digests",
                    {},
                    bencode({b"pieces": [b"y" * (512 << 10)]}),
                )
                assert status == 429, (status, resp)
                assert b"queue full" in resp
                assert server.sched.metrics_snapshot()["shed_total"] == 1
                stall.release.set()
                status, _ = await asyncio.wait_for(big, 10)
                assert status == 200
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_stream_flushes_on_byte_budget(self):
        """A streaming connection's pre-flush batch is per-connection
        memory the admission budget can't see: big-piece streams must
        hand bytes to the scheduler before the piece-count chunk fills."""
        from torrent_tpu.bridge.service import STREAM_FLUSH_BYTES, BridgeServer

        async def go():
            server = await BridgeServer(
                port=0, hasher="cpu", batch_target=4096, flush_deadline_ms=50
            ).start()
            try:
                calls: list[int] = []
                orig = server.sched.enqueue

                async def spy(tenant, pieces, **kw):
                    calls.append(sum(len(p) for p in pieces))
                    return await orig(tenant, pieces, **kw)

                server.sched.enqueue = spy
                plen = 1 << 20
                pieces = [bytes([i + 1]) * plen for i in range(6)]
                body = b"".join(len(p).to_bytes(4, "big") + p for p in pieces)
                status, resp = await _post(
                    server.port,
                    "/v1/stream/digests",
                    {"X-Piece-Length": str(plen)},
                    body,
                )
                assert status == 200
                assert bdecode(resp)[b"digests"] == [
                    hashlib.sha1(p).digest() for p in pieces
                ]
                # 6 MiB of 1 MiB pieces with a 4 MiB cap: must have
                # flushed mid-stream, never holding more than cap + one
                # piece locally
                assert len(calls) >= 2, calls
                assert max(calls) <= STREAM_FLUSH_BYTES + plen, calls
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_info_reports_batch_target(self):
        from torrent_tpu.bridge.service import BridgeServer

        async def go():
            server = await BridgeServer(port=0, hasher="cpu", batch_target=99).start()
            try:
                status, resp = await _get(server.port, "/v1/info")
                assert status == 200
                assert bdecode(resp)[b"batch"] == 99
            finally:
                server.close()
                await server.wait_closed()

        run(go())
