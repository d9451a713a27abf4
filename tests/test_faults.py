"""Fault-tolerant hash plane tests (sched/faults.py + scheduler layer).

Accelerator faults can't be provoked on demand, so every behavior of the
fault-tolerance layer — launch retry, bisection isolation of a poisoned
ticket, the per-lane circuit breaker with CPU degradation, the bridge's
503/Retry-After mapping and per-frame stream failures, and the
mark-and-continue recheck semantics — is driven deterministically on
CPU through a ``FaultPlan`` wired into the ``plane_factory`` seam.
Includes both ISSUE acceptance scenarios (poisoned 16-piece batch from
3 tenants; breaker trip → CPU parity → half-open recovery with
transitions visible in /metrics).
"""

from __future__ import annotations

import asyncio
import hashlib
import threading

import numpy as np
import pytest

from torrent_tpu.codec.bencode import bdecode, bencode
from torrent_tpu.sched import (
    DeviceFaultError,
    FaultPlan,
    HashPlaneScheduler,
    PoisonedPayloadError,
    SchedLaunchError,
    SchedRejected,
    SchedulerConfig,
    classify_error,
)
from torrent_tpu.sched.scheduler import Plane


def run(coro):
    return asyncio.run(coro)


def _pieces(n: int, plen: int = 1024, salt: int = 0) -> list[bytes]:
    return [bytes([(i + salt) % 251]) * plen for i in range(n)]


def _sha1(pieces: list[bytes]) -> list[bytes]:
    return [hashlib.sha1(p).digest() for p in pieces]


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


class _StallPlane(Plane):
    """Blocks until released — pins queue bytes deterministically."""

    def __init__(self):
        self.release = threading.Event()

    def run(self, payloads):
        self.release.wait(timeout=30)
        return _sha1(payloads)


# ------------------------------------------------------------ fault plan


class TestFaultPlan:
    def test_parse_round_trip(self):
        plan = FaultPlan.parse(
            "fail_first=3; latency_ms=5; payload=deadbeef; "
            "fail_launches=2,5; dead_after=9"
        )
        assert plan.fail_first == 3
        assert plan.latency_s == pytest.approx(0.005)
        assert plan.payload_prefix == b"\xde\xad\xbe\xef"
        assert plan.fail_launches == frozenset({2, 5})
        assert plan.dead_after == 9

    def test_parse_rejects_garbage(self):
        for bad in (
            "fail_first",  # not key=value
            "frobnicate=1",  # unknown key
            "fail_first=x",  # non-int
            "payload=zz",  # non-hex
            "fail_first=-1",  # negative ordinal
            "latency_ms=-2",  # negative latency
            "payload=",  # empty prefix would match every payload
        ):
            with pytest.raises(ValueError):
                FaultPlan.parse(bad)

    def test_injected_errors_self_classify(self):
        assert classify_error(DeviceFaultError("x")) == "transient"
        assert classify_error(PoisonedPayloadError("x")) == "deterministic"
        # uninjected errors: payload/shape bugs are deterministic,
        # everything else is presumed a device hiccup worth one retry
        assert classify_error(ValueError("bad shape")) == "deterministic"
        assert classify_error(RuntimeError("XLA launch failed")) == "transient"
        assert classify_error(OSError("device lost")) == "transient"

    def test_faulty_plane_counts_launches_per_plan(self):
        plan = FaultPlan(fail_launches=frozenset({2}))
        plane = plan.plane_factory(hasher="cpu")("sha1", 1024, 8)
        pieces = _pieces(4, 64)
        assert plane.run(pieces) == _sha1(pieces)  # launch 1 fine
        with pytest.raises(DeviceFaultError):
            plane.run(pieces)  # launch 2 injected
        assert plane.run(pieces) == _sha1(pieces)  # launch 3 fine


# ------------------------------------------------- retry and bisection


class TestRetryAndBisection:
    def test_transient_failure_is_retried_once(self):
        """A single injected device fault is absorbed by the retry: the
        submitter sees correct digests and only the retry counter moves."""

        async def go():
            plan = FaultPlan(fail_launches=frozenset({1}))
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=8,
                    flush_deadline=0.05,
                    plane_factory=plan.plane_factory(hasher="cpu"),
                ),
                hasher="cpu",
            )
            try:
                pieces = _pieces(8, 512)
                assert await sched.submit("t", pieces) == _sha1(pieces)
                snap = sched.metrics_snapshot()
                assert snap["launch_failures"] == 1
                assert snap["retries"] == 1
                assert snap["bisections"] == 0
                assert snap["failed_pieces"] == 0
            finally:
                await sched.close()

        run(go())

    def test_poisoned_batch_isolates_single_ticket(self):
        """ISSUE acceptance: 16 pieces from 3 tenants with exactly one
        poisoned payload — the poisoned submitter's future fails with a
        classified (deterministic) error, the other 15 tickets all get
        correct digests, and sched_bisections > 0."""

        async def go():
            poison = b"\xbd\xbd\xbd\xbd" + b"p" * 508
            plan = FaultPlan(payload_prefix=b"\xbd\xbd\xbd\xbd")
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=16,
                    flush_deadline=0.5,
                    plane_factory=plan.plane_factory(hasher="cpu"),
                ),
                hasher="cpu",
            )
            try:
                a, b, c = _pieces(6, 512, salt=1), _pieces(5, 512, salt=9), _pieces(4, 512, salt=17)
                # enqueue all four submissions without an intervening
                # yield so all 16 pieces deterministically coalesce into
                # ONE poisoned launch
                fa = await sched.enqueue("tenant-a", a)
                fb = await sched.enqueue("tenant-b", b)
                fc = await sched.enqueue("tenant-c", c)
                fbad = await sched.enqueue("tenant-c", [poison])
                got_a, got_b, got_c, got_bad = await asyncio.gather(
                    fa, fb, fc, fbad, return_exceptions=True
                )
                assert got_a == _sha1(a), "tenant-a lost to a co-batched poison"
                assert got_b == _sha1(b), "tenant-b lost to a co-batched poison"
                assert got_c == _sha1(c), "tenant-c lost to a co-batched poison"
                assert isinstance(got_bad, SchedLaunchError), got_bad
                assert got_bad.kind == "deterministic"
                assert isinstance(got_bad.cause, PoisonedPayloadError)
                snap = sched.metrics_snapshot()
                assert snap["bisections"] > 0, snap
                assert snap["failed_pieces"] == 1
                # deterministic errors never burn the retry budget
                assert snap["retries"] == 0
            finally:
                await sched.close()

        run(go())

    def test_deterministic_failure_skips_retry(self):
        """A lone poisoned piece (batch of 1: nothing to bisect) fails
        immediately — no retry, no bisection."""

        async def go():
            plan = FaultPlan(payload_prefix=b"\xbd")
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=4,
                    flush_deadline=0.02,
                    plane_factory=plan.plane_factory(hasher="cpu"),
                ),
                hasher="cpu",
            )
            try:
                with pytest.raises(SchedLaunchError) as ei:
                    await sched.submit("t", [b"\xbd" * 64])
                assert ei.value.kind == "deterministic"
                snap = sched.metrics_snapshot()
                assert snap["retries"] == 0
                assert snap["bisections"] == 0
                assert snap["failed_pieces"] == 1
            finally:
                await sched.close()

        run(go())

    def test_bisect_depth_bounds_the_split(self):
        """Past bisect_depth the surviving group fails together instead
        of splitting forever — the recursion is bounded."""

        async def go():
            plan = FaultPlan(payload_prefix=b"\xbd")
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=8,
                    flush_deadline=0.1,
                    bisect_depth=1,
                    plane_factory=plan.plane_factory(hasher="cpu"),
                ),
                hasher="cpu",
            )
            try:
                good = _pieces(7, 64, salt=3)
                fa = await sched.enqueue("ok", good)
                fbad = await sched.enqueue("bad", [b"\xbd" * 64])
                got_ok, got_bad = await asyncio.gather(
                    fa, fbad, return_exceptions=True
                )
                assert isinstance(got_bad, SchedLaunchError)
                snap = sched.metrics_snapshot()
                # depth 1: one split of 8 -> two 4s; the poisoned half
                # (4 tickets incl. 3 innocents) fails together
                assert snap["bisections"] == 1
                assert snap["failed_pieces"] == 4
                # the innocent half still verified
                assert isinstance(got_ok, SchedLaunchError) or got_ok == _sha1(good)
            finally:
                await sched.close()

        run(go())


def _rewind_breaker(sched, seconds: float = 1e6) -> None:
    """Expire every lane breaker's cooldown without sleeping: tests use
    a long real cooldown (so a slow CI box can't close the breaker
    early) and rewind the clock to trigger the half-open probe."""
    for lane in sched._lanes.values():
        with lane.breaker.lock:
            lane.breaker.opened_at -= seconds


# -------------------------------------------------------------- breaker


class TestCircuitBreaker:
    def test_breaker_trips_to_cpu_and_recovers(self):
        """ISSUE acceptance: consecutive injected device failures trip
        the lane breaker → submits succeed via the CPU plane (digests
        match hashlib), a half-open probe restores the device plane
        after recovery, and the transitions appear in /metrics."""
        from torrent_tpu.utils.metrics import render_sched_metrics

        async def go():
            plan = FaultPlan(fail_first=2)
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=4,
                    flush_deadline=0.02,
                    breaker_threshold=2,
                    breaker_cooldown=300.0,
                    plane_factory=plan.plane_factory(hasher="cpu"),
                ),
                hasher="cpu",
            )
            try:
                pieces = _pieces(4, 256)
                # launch fails, retry fails -> threshold 2 trips the
                # breaker; the bisected halves ride the CPU plane, so
                # the caller still gets correct digests
                assert await sched.submit("t", pieces) == _sha1(pieces)
                snap = sched.metrics_snapshot()
                lane = next(iter(snap["breakers"].values()))
                assert lane["state"] == "open", lane
                assert lane["transitions"].get("closed->open") == 1
                assert snap["cpu_fallback_launches"] > 0
                assert snap["failed_pieces"] == 0, "degradation must not fail pieces"
                # breaker-open launches keep serving via CPU
                more = _pieces(4, 256, salt=40)
                assert await sched.submit("t", more) == _sha1(more)
                # expire the cooldown: the next launch is the half-open
                # probe; the injected fault window (fail_first=2) is
                # over, so it succeeds and re-closes the breaker
                _rewind_breaker(sched)
                again = _pieces(4, 256, salt=80)
                assert await sched.submit("t", again) == _sha1(again)
                snap = sched.metrics_snapshot()
                lane = next(iter(snap["breakers"].values()))
                assert lane["state"] == "closed", lane
                assert lane["transitions"].get("open->half_open") == 1
                assert lane["transitions"].get("half_open->closed") == 1
                text = render_sched_metrics(sched)
                assert "torrent_tpu_sched_breaker_state{lane=" in text
                assert (
                    'transition="closed->open"} 1' in text
                    and 'transition="half_open->closed"} 1' in text
                ), text
            finally:
                await sched.close()

        run(go())

    def test_permanent_device_loss_pins_cpu_plane(self):
        """dead_after=0 (every launch raises): a failed half-open probe
        re-opens the breaker and the lane keeps answering via CPU."""

        async def go():
            plan = FaultPlan(dead_after=0)
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=4,
                    flush_deadline=0.02,
                    breaker_threshold=2,
                    breaker_cooldown=300.0,
                    plane_factory=plan.plane_factory(hasher="cpu"),
                ),
                hasher="cpu",
            )
            try:
                pieces = _pieces(4, 256)
                assert await sched.submit("t", pieces) == _sha1(pieces)
                _rewind_breaker(sched)  # expire cooldown: next launch probes
                more = _pieces(4, 256, salt=5)
                assert await sched.submit("t", more) == _sha1(more)
                lane = next(iter(sched.metrics_snapshot()["breakers"].values()))
                assert lane["state"] == "open", lane
                assert lane["transitions"].get("half_open->open", 0) >= 1, lane
            finally:
                await sched.close()

        run(go())

    def test_count_contract_violation_feeds_breaker(self):
        """A plane persistently returning the wrong digest count is a
        primary-plane failure: it must trip the breaker to the CPU plane
        (not reset it via record_success), and callers still get correct
        digests instead of an unbounded retry+bisection cascade."""

        class _ShortPlane(Plane):
            def run(self, payloads):
                return _sha1(payloads)[:-1]  # always one digest short

        async def go():
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=4,
                    flush_deadline=0.02,
                    breaker_threshold=2,
                    breaker_cooldown=30.0,
                    plane_factory=lambda a, b, t, sha256_backend=None: _ShortPlane(),
                ),
                hasher="cpu",
            )
            try:
                pieces = _pieces(4, 256)
                assert await sched.submit("t", pieces) == _sha1(pieces)
                snap = sched.metrics_snapshot()
                lane = next(iter(snap["breakers"].values()))
                assert lane["state"] == "open", lane
                assert snap["cpu_fallback_launches"] > 0
                assert snap["failed_pieces"] == 0
            finally:
                await sched.close()

        run(go())

    def test_latency_spike_plan_stays_correct(self):
        """latency_ms slows every launch but nothing fails — digests
        stay correct and the breaker never moves."""

        async def go():
            plan = FaultPlan.parse("latency_ms=5")
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=4,
                    flush_deadline=0.02,
                    plane_factory=plan.plane_factory(hasher="cpu"),
                ),
                hasher="cpu",
            )
            try:
                pieces = _pieces(8, 256)
                assert await sched.submit("t", pieces) == _sha1(pieces)
                lane = next(iter(sched.metrics_snapshot()["breakers"].values()))
                assert lane["state"] == "closed"
                assert lane["transitions"] == {}
            finally:
                await sched.close()

        run(go())


# -------------------------------------------- recheck failure semantics


class TestRecheckFailureSemantics:
    def test_torn_file_marks_piece_failed_sched(self):
        """A piece whose read raises mid-recheck (torn/truncated file,
        raw OSError) is marked failed; every other piece still verifies —
        device-path parity with verify_pieces_cpu's mark-and-continue."""
        from torrent_tpu.parallel.verify import verify_pieces_sched

        async def go():
            info, storage = _build_torrent(16 * 16384, 16384, seed=11)
            # tear at the BACKEND seam: both read paths (per-piece
            # read_piece bytes and the zero-copy read_batch-into-slab
            # form) route through method.get, so the torn range fails
            # whichever one the scheduler session picks
            orig = storage.method.get
            lo, hi = 5 * 16384, 6 * 16384

            def torn(path, offset, length):
                if offset < hi and offset + length > lo:
                    raise OSError(5, "input/output error")
                return orig(path, offset, length)

            storage.method.get = torn
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=8, flush_deadline=0.05), hasher="cpu"
            )
            try:
                bf = await verify_pieces_sched(storage, info, sched, tenant="cli")
            finally:
                await sched.close()
            assert not bf[5]
            assert bf.sum() == info.num_pieces - 1

        run(go())

    def test_torn_file_marks_piece_failed_cpu(self):
        from torrent_tpu.parallel.verify import verify_pieces_cpu

        info, storage = _build_torrent(16 * 16384, 16384, seed=11)
        orig = storage.read_piece

        def torn(i):
            if i == 5:
                raise OSError(5, "input/output error")
            return orig(i)

        storage.read_piece = torn
        bf = verify_pieces_cpu(storage, info)
        assert not bf[5]
        assert bf.sum() == info.num_pieces - 1

    def test_read_batch_zero_fills_on_oserror(self):
        """The bulk device-read path (Storage.read_batch) zero-fills a
        range whose backend leaks a raw OSError instead of raising — the
        hash mismatch flags the piece, co-batched pieces are unaffected."""
        info, storage = _build_torrent(8 * 16384, 16384, seed=4)
        orig = storage.method.get

        def flaky(path, off, size):
            if off == 3 * 16384:  # piece 3's range
                raise OSError(5, "input/output error")
            return orig(path, off, size)

        storage.method.get = flaky
        buf, lengths = storage.read_batch(range(8))
        assert not buf[3].any(), "torn range must zero-fill"
        assert bytes(buf[2][: lengths[2]]) == storage.read_piece(2)

    def test_launch_failure_leaves_pieces_unverified_not_fatal(self):
        """verify_pieces_sched: a retry-exhausted launch failure marks
        its pieces unverified (False) instead of aborting the pass."""
        from torrent_tpu.parallel.verify import verify_pieces_sched

        async def go():
            info, storage = _build_torrent(16 * 16384, 16384, seed=21)
            # poison exactly piece 5 by matching its content prefix
            prefix = storage.read_piece(5)[:8]
            plan = FaultPlan(payload_prefix=prefix)
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=4,
                    flush_deadline=0.05,
                    plane_factory=plan.plane_factory(hasher="cpu"),
                ),
                hasher="cpu",
            )
            try:
                bf = await verify_pieces_sched(storage, info, sched, tenant="cli")
            finally:
                await sched.close()
            # the poisoned piece's submission chunk stays False (chunked
            # enqueue: pieces 4..7 share piece 5's submission future);
            # every piece outside that chunk verified
            assert not bf[5]
            assert bf[:4].all() and bf[8:].all()

        run(go())

    def test_library_sweep_survives_poisoned_torrent(self):
        """verify_library_sched: a poisoned piece in one torrent leaves
        that chunk unverified but the other torrents' results intact."""
        from torrent_tpu.parallel.bulk import verify_library_sched

        async def go():
            items = [
                (storage, info)
                for info, storage in (
                    _build_torrent(24 * 4096, 4096, seed=i, name=f"t{i}")
                    for i in range(3)
                )
            ]
            prefix = items[1][0].read_piece(0)[:8]  # poison torrent 1
            plan = FaultPlan(payload_prefix=prefix)
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=8,
                    flush_deadline=0.1,
                    plane_factory=plan.plane_factory(hasher="cpu"),
                ),
                hasher="cpu",
            )
            try:
                res = await verify_library_sched(items, sched, tenant="bulk")
            finally:
                await sched.close()
            assert res.bitfields[0].all(), "torrent 0 lost to torrent 1's poison"
            assert res.bitfields[2].all(), "torrent 2 lost to torrent 1's poison"
            assert not res.bitfields[1].all()
            assert res.bitfields[1][8:].all(), "only the poisoned chunk may fail"

        run(go())

    def test_session_recheck_falls_back_locally_on_rejection(self):
        """A whole-queue rejection (scheduler shutting down) drops the
        session recheck to the local verify path — the torrent still
        rechecks complete."""

        async def go():
            import dataclasses

            from torrent_tpu.codec.metainfo import Metainfo
            from torrent_tpu.session.torrent import Torrent, TorrentConfig

            info, storage = _build_torrent(200_000, 16384, seed=7, name="heal")
            sched = HashPlaneScheduler(
                SchedulerConfig(batch_target=8, flush_deadline=0.05), hasher="cpu"
            )
            await sched.close()  # enqueue now raises SchedRejected
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
                config=dataclasses.replace(TorrentConfig(), scheduler=sched),
            )
            await torrent.recheck()
            assert torrent.bitfield.complete

        run(go())


# ----------------------------------------------- submission abandonment


class TestAbandonedSubmission:
    def test_disconnect_mid_submit_releases_bytes_and_waiters(self):
        """A submission future abandoned before demux (client gone) must
        not leak queued_bytes: accounting drains and a blocked admission
        waiter still gets through."""

        async def go():
            stall = _StallPlane()
            sched = HashPlaneScheduler(
                SchedulerConfig(
                    batch_target=1,
                    flush_deadline=0.01,
                    max_queue_bytes=64 << 10,
                    plane_factory=lambda a, b, t, sha256_backend=None: stall,
                ),
                hasher="cpu",
            )
            try:
                fut = await sched.enqueue("gone", [b"x" * (32 << 10)])
                for _ in range(200):  # wait until the launch holds the bytes
                    if sched.metrics_snapshot()["queue_bytes"] > 0:
                        break
                    await asyncio.sleep(0.01)
                fut.cancel()  # client disconnected; nobody will await it
                del fut
                # 48 KiB doesn't fit beside the abandoned 32 KiB: blocks
                waiter = asyncio.ensure_future(
                    sched.submit("next", [b"y" * (48 << 10)], wait=True)
                )
                await asyncio.sleep(0.05)
                assert not waiter.done(), "waiter admitted over budget"
                stall.release.set()
                got = await asyncio.wait_for(waiter, 10)
                assert got == [hashlib.sha1(b"y" * (48 << 10)).digest()]
                for _ in range(200):
                    if sched.metrics_snapshot()["queue_bytes"] == 0:
                        break
                    await asyncio.sleep(0.01)
                assert sched.metrics_snapshot()["queue_bytes"] == 0, "leaked bytes"
            finally:
                stall.release.set()
                await sched.close()

        run(go())

    def test_bridge_client_disconnect_recovers(self):
        """A bridge client that vanishes before its response: the
        handler's reply write fails quietly, queued-byte accounting fully
        drains, and the next client is served normally."""
        from torrent_tpu.bridge.service import BridgeServer

        async def go():
            server = await BridgeServer(port=0, hasher="cpu").start()
            try:
                stall = _StallPlane()
                server.sched.config.plane_factory = lambda a, b, t, sha256_backend=None: stall
                body = bencode({b"pieces": [b"q" * 4096]})
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", server.port
                )
                writer.write(
                    (
                        "POST /v1/digests HTTP/1.1\r\nHost: x\r\n"
                        f"Content-Length: {len(body)}\r\n\r\n"
                    ).encode()
                    + body
                )
                await writer.drain()
                for _ in range(200):
                    if server.sched.metrics_snapshot()["queue_bytes"] > 0:
                        break
                    await asyncio.sleep(0.01)
                writer.close()  # disconnect before the demux
                stall.release.set()
                for _ in range(200):
                    if server.sched.metrics_snapshot()["queue_bytes"] == 0:
                        break
                    await asyncio.sleep(0.01)
                assert server.sched.metrics_snapshot()["queue_bytes"] == 0
                # the plane seam back to normal: next client unaffected
                server.sched.config.plane_factory = None
                pieces = _pieces(4, 512)
                status, _, resp = await _post_h(
                    server.port, "/v1/digests", {}, bencode({b"pieces": pieces})
                )
                assert status == 200
                assert bdecode(resp)[b"digests"] == _sha1(pieces)
            finally:
                server.close()
                await server.wait_closed()

        run(go())


# --------------------------------------------------------------- bridge


async def _post_h(port, path, headers, body):
    """POST returning (status, headers, body)."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    head = [f"POST {path} HTTP/1.1", "Host: x", f"Content-Length: {len(body)}"]
    for k, v in headers.items():
        head.append(f"{k}: {v}")
    writer.write(("\r\n".join(head) + "\r\n\r\n").encode() + body)
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    resp_headers: dict[str, str] = {}
    clen = 0
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b""):
            break
        k, _, v = line.decode("latin-1").partition(":")
        resp_headers[k.strip().lower()] = v.strip()
        if k.strip().lower() == "content-length":
            clen = int(v)
    resp = await reader.readexactly(clen)
    writer.close()
    return status, resp_headers, resp


class TestBridgeFaultMapping:
    def test_deterministic_failure_maps_to_500_without_retry_after(self):
        """A poisoned (deterministic) payload → 500 with NO Retry-After:
        resubmitting the same payload can never help, so the bridge must
        not invite it (shed stays 429: a different remedy)."""
        from torrent_tpu.bridge.service import BridgeServer

        async def go():
            server = await BridgeServer(
                port=0, hasher="cpu", fault_plan="payload=bdbdbdbd"
            ).start()
            try:
                status, hdrs, resp = await _post_h(
                    server.port,
                    "/v1/digests",
                    {},
                    bencode({b"pieces": [b"\xbd\xbd\xbd\xbd" + b"x" * 60]}),
                )
                assert status == 500, (status, resp)
                assert "retry-after" not in hdrs, hdrs
                assert b"deterministic" in resp
                # a clean request on the same server still succeeds
                pieces = _pieces(4, 512)
                status, _, resp = await _post_h(
                    server.port, "/v1/digests", {}, bencode({b"pieces": pieces})
                )
                assert status == 200
                assert bdecode(resp)[b"digests"] == _sha1(pieces)
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_transient_exhausted_maps_to_503_with_retry_after(self):
        """A transient failure that outlives the retry budget (single
        piece: nothing to bisect) → 503 + Retry-After."""
        from torrent_tpu.bridge.service import BridgeServer

        async def go():
            server = BridgeServer(port=0, hasher="cpu", fault_plan="fail_first=2")
            # launch + its one retry both fail; keep the breaker out of
            # the picture so the CPU plane can't rescue the submission
            server._sched_config.breaker_threshold = 10
            await server.start()
            try:
                status, hdrs, resp = await _post_h(
                    server.port, "/v1/digests", {},
                    bencode({b"pieces": [b"q" * 64]}),
                )
                assert status == 503, (status, resp)
                assert hdrs.get("retry-after") == "1", hdrs
                assert b"transient" in resp
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_stream_reports_per_frame_failures(self):
        """A poisoned piece in a stream fails its frame (empty digest +
        failed count) without dropping the connection or the other
        frames' digests."""
        from torrent_tpu.bridge.service import BridgeServer

        async def go():
            # batch_target=1 -> one piece per frame/submission
            server = await BridgeServer(
                port=0,
                hasher="cpu",
                batch_target=1,
                flush_deadline_ms=20,
                fault_plan="payload=bdbdbdbd",
            ).start()
            try:
                plen = 1024
                pieces = _pieces(4, plen, salt=2)
                pieces[2] = b"\xbd\xbd\xbd\xbd" + b"z" * (plen - 4)
                body = b"".join(len(p).to_bytes(4, "big") + p for p in pieces)
                status, _, resp = await _post_h(
                    server.port,
                    "/v1/stream/digests",
                    {"X-Piece-Length": str(plen)},
                    body,
                )
                assert status == 200, (status, resp)
                out = bdecode(resp)
                assert out[b"failed"] == 1
                digests = out[b"digests"]
                assert digests[2] == b""
                for i in (0, 1, 3):
                    assert digests[i] == hashlib.sha1(pieces[i]).digest()
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_breaker_transitions_visible_in_bridge_metrics(self):
        """ISSUE acceptance, bridge flavor: injected device failures trip
        the breaker, digests keep matching hashlib via the CPU plane, and
        the transitions show up in GET /metrics."""
        from torrent_tpu.bridge.service import BridgeServer

        async def go():
            server = BridgeServer(
                port=0, hasher="cpu", fault_plan="fail_first=2"
            )
            server._sched_config.breaker_threshold = 2
            server._sched_config.breaker_cooldown = 300.0
            await server.start()
            try:
                pieces = _pieces(4, 512)
                status, _, resp = await _post_h(
                    server.port, "/v1/digests", {}, bencode({b"pieces": pieces})
                )
                assert status == 200, (status, resp)
                assert bdecode(resp)[b"digests"] == _sha1(pieces)
                status, _, resp = await _get_h(server.port, "/metrics")
                text = resp.decode()
                assert 'transition="closed->open"} 1' in text, text
                assert "torrent_tpu_sched_breaker_state{" in text
                assert "torrent_tpu_sched_cpu_fallback_launches_total" in text
                _rewind_breaker(server.sched)  # expire cooldown -> probe
                status, _, resp = await _post_h(
                    server.port, "/v1/digests", {}, bencode({b"pieces": pieces})
                )
                assert status == 200
                assert bdecode(resp)[b"digests"] == _sha1(pieces)
                status, _, resp = await _get_h(server.port, "/metrics")
                text = resp.decode()
                assert 'transition="half_open->closed"} 1' in text, text
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_fault_plan_knob_requires_dev_mode(self, monkeypatch, capsys):
        """CI/tooling satellite: --fault-plan is refused outside dev/test
        mode (no env, no --dev), and a bad spec is refused even in dev
        mode — chaos knobs can't leak into production invocations."""
        from torrent_tpu.bridge import service

        monkeypatch.delenv("TORRENT_TPU_DEV", raising=False)
        rc = service.main(["--port", "0", "--fault-plan", "fail_first=1"])
        assert rc == 2
        assert "dev/test" in capsys.readouterr().err
        rc = service.main(
            ["--port", "0", "--dev", "--fault-plan", "frobnicate=1"]
        )
        assert rc == 2
        assert "bad --fault-plan" in capsys.readouterr().err


async def _get_h(port, path):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    resp_headers: dict[str, str] = {}
    clen = 0
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b""):
            break
        k, _, v = line.decode("latin-1").partition(":")
        resp_headers[k.strip().lower()] = v.strip()
        if k.strip().lower() == "content-length":
            clen = int(v)
    resp = await reader.readexactly(clen)
    writer.close()
    return status, resp_headers, resp


# --------------------------------------------------------------- doctor


class TestDoctorFaults:
    def test_faults_smoke_passes(self):
        """doctor --faults: the injected fail-then-recover plan proves
        bisection isolation and breaker trip/recovery in-process."""
        from torrent_tpu.tools import doctor

        detail = run(doctor._faults_smoke())
        assert "bisected" in detail and "breaker" in detail
