"""Ingest verify on the normal path: a ``hasher="tpu"`` client sends every
v1 piece it downloads through its ``HashPlaneScheduler`` (tenant
``ingest``), and every verdict equals the plain reference's — ``hashlib``
over the bytes the piece held (``benchmark/harness/reference_session.py``).

Small and on the CPU: 32 KiB pieces, 2 MiB + 5,003 B (65 pieces, the last
one short), three seeders in this process, a seeded payload, no tracker
(the leecher is given the seeders' addresses, as an announce would).
"""

import asyncio
import errno
import hashlib
import os

import numpy as np
import pytest

from benchmark.harness import reference_session
from torrent_tpu.codec.bencode import bencode
from torrent_tpu.codec.metainfo import parse_metainfo
from torrent_tpu.net.types import AnnouncePeer
from torrent_tpu.obs.hist import histograms
from torrent_tpu.obs.ledger import pipeline_ledger
from torrent_tpu.sched import FaultPlan, HashPlaneScheduler, SchedulerConfig
from torrent_tpu.session.client import Client, ClientConfig
from torrent_tpu.session.resume import FsResumeStore, ResumeData
from torrent_tpu.session.torrent import _H_INGEST_VERIFY, TorrentConfig
from torrent_tpu.storage.storage import FsStorage, storage_write_stats
from torrent_tpu.utils.bitfield import Bitfield

from tests.test_session import judged

PLEN = 32768
LENGTH = 2 * 1024 * 1024 + 5003
SEED = 2147483777


def run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def write_source(root, plen=PLEN, length=LENGTH, name="payload.bin", seed=SEED):
    """The seeded payload as a file under ``root`` and its torrent beside
    it, authored with ``hashlib``; returns (metainfo, torrent path)."""
    os.makedirs(root, exist_ok=True)
    payload = np.random.Generator(np.random.Philox(seed)).integers(0, 256, length, dtype=np.uint8).tobytes()
    with open(os.path.join(root, name), "wb") as f:
        f.write(payload)
    pieces = b"".join(hashlib.sha1(payload[i : i + plen]).digest() for i in range(0, length, plen))
    info = {b"name": name.encode(), b"piece length": plen, b"pieces": pieces, b"length": length}
    path = os.path.join(root, name + ".torrent")
    with open(path, "wb") as f:
        f.write(bencode({b"announce": b"", b"info": info}))
    with open(path, "rb") as f:
        return parse_metainfo(f.read()), path


def plant(src_root, dst_root, meta, indices):
    """A copy of the source with one byte flipped in each of ``indices``
    (the piece's last byte: its last block), and a resume file that claims
    every piece, so that a seeder of it serves what it holds."""
    os.makedirs(dst_root)
    name = meta.info.name
    data = bytearray(open(os.path.join(src_root, name), "rb").read())
    for i in indices:
        data[min((i + 1) * meta.info.piece_length, len(data)) - 1] ^= 0x5A
    with open(os.path.join(dst_root, name), "wb") as f:
        f.write(data)
    n = meta.info.num_pieces
    full = Bitfield(n)
    for i in range(n):
        full.set(i)
    FsResumeStore(dst_root).save(ResumeData(meta.info_hash, n, full.to_bytes(), completed_reported=True))


async def seeder(meta, root, resume=False, host="127.0.0.1"):
    c = Client(ClientConfig(host=host, hasher="cpu", resume=resume, torrent=TorrentConfig(choke_interval=0.15)))
    await c.start()
    t = await c.add(meta, root)
    assert t.bitfield.complete
    return c


def join(torrent, *clients):
    torrent._connect_new_peers([AnnouncePeer(ip="127.0.0.1", port=c.port) for c in clients])


def small_sched(**kw):
    """A scheduler whose lanes hold 8 rows: one rung, one compile."""
    return HashPlaneScheduler(SchedulerConfig(batch_target=8, **kw), hasher="tpu")


def leecher(scheduler=None):
    return Client(
        ClientConfig(
            host="127.0.0.1", hasher="tpu", resume=False, scheduler=scheduler,
            torrent=TorrentConfig(choke_interval=0.15),
        )
    )


def fallbacks() -> int:
    return histograms().get(*_H_INGEST_VERIFY, plane="hashlib_fallback").snapshot()[1]


def wait_entries() -> int:
    return pipeline_ledger().snapshot()["waits"].get("ingest_verdict_wait", {}).get("ops", 0)


async def download(leech, meta, dest, seeders, events=None):
    t = await leech.add(meta, dest)
    if events is not None:
        t.on_piece_verdict = lambda index, outcome: events.append((index, outcome))
    join(t, *seeders)
    await asyncio.wait_for(t.on_complete.wait(), 60)
    return t


def test_download_is_byte_equal_and_every_verdict_is_the_references(tmp_path):
    """(a), and (c): the bare ``hasher="tpu"`` client, with no other
    setting, judged every piece on its own scheduler."""
    src, dest = str(tmp_path / "src"), str(tmp_path / "dest")
    meta, torrent_path = write_source(src)

    async def go():
        seeders = [await seeder(meta, src) for _ in range(3)]
        leech = Client(ClientConfig(host="127.0.0.1", hasher="tpu", resume=False))
        await leech.start()
        events: list = []
        fell_back, waited = fallbacks(), wait_entries()
        try:
            sched = leech.ingest_scheduler
            assert isinstance(sched, HashPlaneScheduler) and leech._owns_ingest_scheduler
            assert "ingest" in sched.metrics_snapshot()["tenants"]  # registered at start, before any piece
            t = await download(leech, meta, dest, seeders, events)
            assert t.ingest_scheduler is sched and t.config.scheduler is None
            snap = sched.metrics_snapshot()
        finally:
            await leech.close()
            for s in seeders:
                await s.close()
        return snap, events, fallbacks() - fell_back, wait_entries() - waited, sched

    snap, events, fell_back, waited, sched = run(go())
    n = meta.info.num_pieces
    torrent = reference_session.read_torrent(torrent_path)
    name = meta.info.name
    assert open(os.path.join(dest, name), "rb").read() == open(os.path.join(src, name), "rb").read()
    assert all(reference_session.copy_verdicts(os.path.join(dest, name), torrent))
    held = reference_session.copy_verdicts(os.path.join(src, name), torrent)
    assert held == [True] * n == [True] * 65
    got = reference_session.compare_deliveries(events, held, every="valid")
    # a piece at the judge has an owner (PR 38): no scan and no endgame hands it to a second peer, so each
    # piece is delivered, judged and written once, and each delivery is an event
    assert got == {"compared": len(events), "reference_invalid": 0, "wrong_verdicts": 0, "missing_verdicts": 0}
    assert len(events) == n
    # the scheduler was the road: the tenant's pieces, one lane, its launches
    assert snap["tenants"]["ingest"]["served_pieces"] == len(events) + 1  # and the lane's warm-up launch
    lane = snap["lane_stats"][f"sha1/{PLEN}"]
    assert lane["launches"] >= 2 and lane["target"] == 256
    # rows launched < 256 where fewer finished together: three peers, so never more than the lowest rung
    assert lane["launched_rows_total"] < 256 * lane["launches"]
    assert snap["flush_reasons"]["hint"] == snap["launches"] and snap["flush_reasons"]["deadline"] == 0
    assert snap["cpu_fallback_launches"] == 0 and snap["launch_failures"] == 0 and fell_back == 0
    # one ledger entry a piece put to the judge (one cut off by the torrent's end has its entry and no event)
    assert len(events) <= waited <= len(events) + 8
    assert sched._closing and sched.metrics_snapshot()["staging"]["outstanding"] == 0
    assert sched.metrics_snapshot()["queue_pieces"] == 0


async def until_dropped(torrent, limit=60.0):
    """The leecher alone with a poisoner, until it has banned it: a
    refused piece is asked for again at once, the third refusal in a row
    bans the address (``max_corrupt_pieces``) and drops the peer."""
    for _ in range(int(limit / 0.02)):
        if torrent._banned and not torrent.peers:
            return
        await asyncio.sleep(0.02)
    raise AssertionError(f"the poisoner was not banned: {torrent.status()}")


def test_exactly_the_planted_deliveries_are_refused(tmp_path):
    """(b): alone with a seeder whose copy has one byte flipped in a
    seeded set P, the leecher refuses exactly the deliveries at P and
    writes nothing of P; then honest seeders serve the same directory's
    torrent and it completes byte-equal."""
    src, bad, dest = str(tmp_path / "src"), str(tmp_path / "bad"), str(tmp_path / "dest")
    meta, torrent_path = write_source(src)
    n = meta.info.num_pieces
    rng = np.random.Generator(np.random.Philox([SEED, 0xC0]))
    planted = sorted({0, n - 1} | {int(i) for i in rng.choice(n, size=n // 8, replace=False)})
    plant(src, bad, meta, planted)
    torrent = reference_session.read_torrent(torrent_path)
    name = meta.info.name
    held_bad = reference_session.copy_verdicts(os.path.join(bad, name), torrent)
    assert [i for i, ok in enumerate(held_bad) if not ok] == planted

    async def go():
        sched = await small_sched().start()
        poisoner = await seeder(meta, bad, resume=True)
        honest = [await seeder(meta, src) for _ in range(2)]
        leech = leecher(sched)
        await leech.start()
        one: list = []
        two: list = []
        try:
            t = await leech.add(meta, dest)
            t.on_piece_verdict = lambda index, outcome: one.append((index, outcome))
            join(t, poisoner)
            await until_dropped(t)
            # the ban is the torrent's: the same directory added again knows the honest seeders' address
            await leech.remove(meta.info_hash)
            on_disk = reference_session.piece_digests(os.path.join(dest, name), torrent["length"], PLEN)
            t = await leech.add(meta, dest)
            adopted = t.bitfield.count()
            t.on_piece_verdict = lambda index, outcome: two.append((index, outcome))
            join(t, *honest)
            await asyncio.wait_for(t.on_complete.wait(), 60)
        finally:
            await leech.close()
            await sched.close()
            for s in honest + [poisoner]:
                await s.close()
        return one, two, on_disk, adopted

    one, two, on_disk, adopted = run(go())
    got = reference_session.compare_deliveries(one, held_bad, every="valid")
    assert got["wrong_verdicts"] == 0 and got["reference_invalid"] >= 3  # the ban's three strikes at the least
    refused = {i for i, o in one if o != "ok"}
    assert refused and refused <= set(planted) and {o for _, o in one if o != "ok"} == {"corrupt"}
    accepted = {i for i, o in one if o == "ok"}
    assert not accepted & set(planted)
    bad_digests = reference_session.piece_digests(os.path.join(bad, name), torrent["length"], PLEN)
    # nothing of P reached the disk: neither the planted bytes nor any that pass for the piece
    assert all(on_disk[i] not in (bad_digests[i], torrent["digests"][i]) for i in planted)
    assert all(on_disk[i] == torrent["digests"][i] for i in accepted)
    # the control at this comparison: a verifier that answers "valid" is wrong on every planted delivery
    control = [(i, "ok") for i, _ in one]
    assert reference_session.compare_deliveries(control, held_bad, every="valid")["wrong_verdicts"] == got["reference_invalid"]
    # the second add adopted what the first wrote, and honest deliveries are never refused
    assert adopted == len(accepted)
    held = reference_session.copy_verdicts(os.path.join(src, name), torrent)
    assert reference_session.compare_deliveries(two, held, every="valid")["wrong_verdicts"] == 0
    assert {i for i, o in two if o == "ok"} == set(range(n)) - accepted
    assert open(os.path.join(dest, name), "rb").read() == open(os.path.join(src, name), "rb").read()


def test_a_failing_lane_gives_fallback_verdicts_equal_to_the_references(tmp_path):
    """(d): every launch of the lane fails (``sched/faults.py``), the
    breaker is kept shut, so every submission comes back failed and the
    session judges the piece by hashlib, counted as ``hashlib_fallback``."""
    src, dest = str(tmp_path / "src"), str(tmp_path / "dest")
    meta, torrent_path = write_source(src, length=16 * PLEN + 5003)
    n = meta.info.num_pieces
    torrent = reference_session.read_torrent(torrent_path)
    name = meta.info.name

    async def go():
        plan = FaultPlan(dead_after=0)
        sched = await small_sched(
            plane_factory=plan.plane_factory(hasher="tpu"), breaker_threshold=1 << 30, launch_retries=0
        ).start()
        seed = await seeder(meta, src)
        leech = leecher(sched)
        await leech.start()
        events: list = []
        before = fallbacks()
        try:
            t = await download(leech, meta, dest, [seed], events)
            # a delivery with one byte flipped in its last block, put to the same judge
            flipped = bytearray(open(os.path.join(src, name), "rb").read()[:PLEN])
            flipped[-1] ^= 0x5A
            refused = await t._verify_piece_data(0, bytes(flipped), meta.info.pieces[0])
            snap = sched.metrics_snapshot()
        finally:
            await leech.close()
            await sched.close()
            await seed.close()
        return events, fallbacks() - before, snap, refused

    events, fell_back, snap, refused = run(go())
    held = reference_session.copy_verdicts(os.path.join(src, name), torrent)
    got = reference_session.compare_deliveries(events, held, every="valid")
    assert got["wrong_verdicts"] == 0 and got["missing_verdicts"] == 0 and len(events) == n
    assert refused is False
    assert fell_back == n + 1 and snap["launch_failures"] >= n + 1
    assert snap["tenants"]["ingest"]["served_pieces"] == 0  # the device judged nothing
    assert open(os.path.join(dest, name), "rb").read() == open(os.path.join(src, name), "rb").read()


def test_two_torrents_of_one_client_share_the_scheduler_on_two_lanes(tmp_path):
    """(e)"""
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    meta_a, _ = write_source(a, plen=32768, length=8 * 32768 + 5003, name="a.bin", seed=SEED)
    meta_b, _ = write_source(b, plen=65536, length=6 * 65536 + 5003, name="b.bin", seed=SEED + 1)

    async def go():
        sched = await small_sched().start()
        seed = Client(ClientConfig(host="127.0.0.1", hasher="cpu", resume=False))
        await seed.start()
        await seed.add(meta_a, a)
        await seed.add(meta_b, b)
        leech = leecher(sched)
        await leech.start()
        try:
            ta = await leech.add(meta_a, str(tmp_path / "da"))
            tb = await leech.add(meta_b, str(tmp_path / "db"))
            assert ta.ingest_scheduler is tb.ingest_scheduler is sched and not leech._owns_ingest_scheduler
            join(ta, seed)
            join(tb, seed)
            await asyncio.wait_for(asyncio.gather(ta.on_complete.wait(), tb.on_complete.wait()), 60)
            snap = sched.metrics_snapshot()
        finally:
            await leech.close()
            await seed.close()
        assert not sched._closing  # the caller's scheduler is the caller's to close
        await sched.close()
        return snap

    snap = run(go())
    lanes = snap["lane_stats"]
    assert set(lanes) == {"sha1/32768", "sha1/65536"}
    assert all(lane["launches"] >= 2 for lane in lanes.values())
    assert snap["tenants"]["ingest"]["served_pieces"] == meta_a.info.num_pieces + meta_b.info.num_pieces + 2
    for name, src in (("a.bin", a), ("b.bin", b)):
        got = open(os.path.join(str(tmp_path / ("d" + name[0])), name), "rb").read()
        assert got == open(os.path.join(src, name), "rb").read()


@pytest.mark.parametrize("hasher", ["cpu", "tpu"])
def test_the_resume_recheck_keeps_its_road(tmp_path, hasher, monkeypatch):
    """The client's own ingest scheduler is not a ``scheduler`` the caller
    set: a torrent added over data on disk rechecks it on the verifier's
    road (``verify_storage``), never through the scheduler."""
    from torrent_tpu.models.verifier import TPUVerifier

    src = str(tmp_path / "src")
    meta, _ = write_source(src, length=8 * PLEN + 5003)
    calls = []
    real = TPUVerifier.verify_storage
    monkeypatch.setattr(TPUVerifier, "verify_storage", lambda self, *a, **kw: calls.append(1) or real(self, *a, **kw))

    async def go():
        c = Client(ClientConfig(host="127.0.0.1", hasher=hasher, resume=False, torrent=TorrentConfig(verify_batch_size=8)))
        await c.start()
        try:
            t = await c.add(meta, src)
            assert t.bitfield.complete and t.config.scheduler is None
            return c.ingest_scheduler.metrics_snapshot() if c.ingest_scheduler else None
        finally:
            await c.close()

    snap = run(go())
    if hasher == "tpu":
        assert calls == [1]
        assert snap["tenants"]["ingest"]["served_pieces"] == 1  # the warm-up launch alone
        assert "selfheal" not in snap["tenants"]
    else:
        assert calls == [] and snap is None


def test_the_ingest_tenant_is_in_the_clients_metrics(tmp_path):
    """``/metrics`` of a ``hasher="tpu"`` client renders its own ingest
    scheduler without being handed one; a ``hasher="cpu"`` client has none."""
    from torrent_tpu.utils.metrics import MetricsServer

    async def scrape(port):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(b"GET /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
        body = await reader.read()
        writer.close()
        return body.decode()

    async def go(hasher):
        c = Client(ClientConfig(host="127.0.0.1", hasher=hasher, resume=False, scheduler=small_sched() if hasher == "tpu" else None))
        await c.start()
        server = await MetricsServer(c).start()
        try:
            return await scrape(server.port)
        finally:
            server.close()
            await c.close()

    text = run(go("tpu"))
    assert 'torrent_tpu_sched_tenant_served_pieces_total{tenant="ingest"} 0' in text
    assert "torrent_tpu_sched_tenant" not in run(go("cpu"))


# ---------------------------------------------------------------- a piece at the judge has an owner (PR 38)


def write_source_v2(root, plen=PLEN, length=LENGTH, name="payload.bin", seed=SEED):
    """The same seeded payload as a single-file BEP 52 torrent."""
    from torrent_tpu.models.v2 import build_v2

    os.makedirs(root, exist_ok=True)
    payload = np.random.Generator(np.random.Philox(seed)).integers(0, 256, length, dtype=np.uint8).tobytes()
    with open(os.path.join(root, name), "wb") as f:
        f.write(payload)
    return build_v2([((name,), payload)], name=name, piece_length=plen, hasher="cpu")


def slow_judge(torrent, judged, seconds=0.05, hold=None, broken=()):
    """The torrent's judge behind a stub that awaits: every delivery put
    to it is listed in ``judged`` and waits ``seconds`` first (at 32 KiB a
    ``hasher="cpu"`` verdict is inline and the window would not exist);
    a delivery of the piece ``hold[0]`` waits for the event ``hold[1]``,
    and raises after it while ``broken`` holds anything (popped)."""
    real = torrent._verify_piece_data

    async def judge(index, data, expected):
        judged.append(index)
        if hold is not None and index == hold[0]:
            await hold[1].wait()
            if broken:
                raise RuntimeError(broken.pop())
        else:
            await asyncio.sleep(seconds)
        return await real(index, data, expected)

    torrent._verify_piece_data = judge


async def a_leecher(hasher):
    """A started leecher and what to close after it."""
    if hasher == "tpu":
        sched = await small_sched().start()
        leech = leecher(sched)
    else:
        sched = None
        leech = Client(ClientConfig(host="127.0.0.1", hasher="cpu", resume=False, torrent=TorrentConfig(choke_interval=0.15)))
    await leech.start()
    return leech, sched


@pytest.mark.parametrize("version", ["v1", "v2"])
@pytest.mark.parametrize("hasher", ["cpu", "tpu"])
def test_every_piece_is_put_to_the_judge_once(tmp_path, hasher, version):
    """Three seeders, a verdict of 50 ms: while a piece is at the judge it
    is missing, has no partial and no block in flight, and the picker used
    to hand it to the next peer that refilled (three deliveries in ten on
    the chip, PERF.md §5). Now every scan passes it over and counts that."""
    src, dest = str(tmp_path / "src"), str(tmp_path / "dest")
    meta = write_source(src)[0] if version == "v1" else write_source_v2(src)

    async def go():
        seeders = [await seeder(meta, src) for _ in range(3)]
        leech, sched = await a_leecher(hasher)
        judged, events = [], []
        try:
            t = await leech.add(meta, dest)
            slow_judge(t, judged)
            t.on_piece_verdict = lambda index, outcome: events.append((index, outcome))
            join(t, *seeders)
            await asyncio.wait_for(t.on_complete.wait(), 60)
            return t.info.num_pieces, judged, events, t.status(), dict(t._partials), t._wanted_missing
        finally:
            await leech.close()
            if sched is not None:
                await sched.close()
            for s in seeders:
                await s.close()

    n, judged, events, status, partials, wanted = run(go())
    assert n == 65
    assert sorted(judged) == list(range(n))  # deliveries put to the judge == pieces
    assert sorted(events) == [(i, "ok") for i in range(n)]
    assert status["duplicate_judged"] == 0 and status["judging_skips"] > 0
    assert status["judging"] == 0 and status["partials"] == 0 and partials == {} and wanted == 0
    assert status["downloaded"] == LENGTH
    assert open(os.path.join(dest, "payload.bin"), "rb").read() == open(os.path.join(src, "payload.bin"), "rb").read()


X = 5  # the piece held at the judge below


async def seeder_at(host, meta, root, resume=False):
    """A seeder on a loopback address of its own: a ban is by address."""
    try:
        return await seeder(meta, root, resume, host=host)
    except OSError as e:  # pragma: no cover - a host without the whole of 127/8
        if e.errno != errno.EADDRNOTAVAIL:
            raise
        pytest.skip(f"cannot listen on {host}: {e}")


def peer_at(torrent, host):
    return next(p for p in torrent.peers.values() if p.address and p.address[0] == host)


async def eventually(what, said, limit=30.0):
    for _ in range(int(limit / 0.01)):
        if what():
            return
        await asyncio.sleep(0.01)
    raise AssertionError(f"never {said}")


class Held:
    """The leecher alone with one seeder (127.0.0.2) until piece X is at
    the judge, where the stub keeps it, and alone there (the peer loop
    went on past X and its other pieces' verdicts came); then a second
    seeder (127.0.0.3) is let in, and finds nothing left to ask once
    everything else is written. ``release()`` lets the verdict come."""

    def __init__(self, tmp_path, first_root=None, hasher="cpu", **torrent_kw):
        self.src, self.dest = str(tmp_path / "src"), str(tmp_path / "dest")
        self.meta, _ = write_source(self.src, length=16 * PLEN + 5003)
        self.first_root = first_root or self.src
        self.hasher = hasher
        self.torrent_kw = torrent_kw
        self.judged, self.events, self.broken = [], [], []

    async def __aenter__(self):
        self.gate = asyncio.Event()
        self.first = await seeder_at("127.0.0.2", self.meta, self.first_root, resume=self.first_root != self.src)
        self.second = await seeder_at("127.0.0.3", self.meta, self.src)
        self.leech, self.sched = await a_leecher(self.hasher)
        self.leech.config.torrent = TorrentConfig(choke_interval=0.15, **self.torrent_kw)
        t = self.t = await self.leech.add(self.meta, self.dest)
        slow_judge(t, self.judged, seconds=0.0, hold=(X, self.gate), broken=self.broken)
        t.on_piece_verdict = lambda index, outcome: self.events.append((index, outcome))
        t._connect_new_peers([AnnouncePeer(ip="127.0.0.2", port=self.first.port)])
        await eventually(lambda: X in t._judging, "piece X at the judge")
        await eventually(lambda: t._judging == {X}, "piece X alone at the judge")
        return self

    async def let_the_second_in(self):
        """Until everything but X is written and the second seeder's
        pipeline is empty and starved: the state before this PR's picker
        would have filled with a second copy of X."""
        t = self.t
        t._connect_new_peers([AnnouncePeer(ip="127.0.0.3", port=self.second.port)])
        await eventually(lambda: t.bitfield.count() == t.info.num_pieces - 1, "everything but X written")
        await eventually(lambda: any(p.address[0] == "127.0.0.3" for p in t.peers.values()), "the second seeder connected")
        peer = peer_at(t, "127.0.0.3")
        await eventually(lambda: peer.fill_starved and not peer.inflight, "the second seeder starved")
        return peer

    def release(self):
        self.gate.set()

    async def __aexit__(self, *exc):
        self.gate.set()
        await self.leech.close()
        if self.sched is not None:
            await self.sched.close()
        for s in (self.first, self.second):
            await s.close()

    def byte_equal(self):
        name = self.meta.info.name
        return open(os.path.join(self.dest, name), "rb").read() == open(os.path.join(self.src, name), "rb").read()


@pytest.mark.parametrize("strikes", [1, 3], ids=["the_refusal_bans_the_deliverer", "the_deliverer_stays"])
def test_a_refused_piece_leaves_the_judge_and_is_fetched_again(tmp_path, strikes):
    """(a) X is held at the judge while the only other peer runs dry and
    marks itself starved on an empty pipeline: no message of that peer is
    due, so the refusal itself has to reach it, also when it bans the
    peer that would have refilled itself."""
    bad = str(tmp_path / "bad")
    held = Held(tmp_path, first_root=bad, max_corrupt_pieces=strikes)
    plant(held.src, bad, held.meta, [X])

    async def go():
        async with held:
            t = held.t
            peer = await held.let_the_second_in()
            assert t._piece_inflight[X] == 0 and X not in t._partials and t.status()["judging"] == 1
            before = peer.bytes_down
            held.release()
            await asyncio.wait_for(t.on_complete.wait(), 30)
            return t.status(), set(t._banned), peer.bytes_down - before

    status, banned, second_bytes = run(go())
    assert held.events.count((X, "corrupt")) >= 1 and held.events[-1] == (X, "ok")
    assert [o for i, o in held.events if i != X] == ["ok"] * 16
    assert "127.0.0.3" not in banned and (strikes == 3 or banned == {"127.0.0.2"})
    assert status["judging"] == 0 and status["partials"] == 0 and status["duplicate_judged"] == 0
    assert second_bytes >= 16384  # X came from the other peer, at the least its last block, where the flip is
    assert held.byte_equal()


def test_the_endgame_asks_for_no_block_of_a_piece_at_the_judge(tmp_path):
    """(b) with X the only piece left, the second seeder's fill is the
    endgame's: it used to take all of X's blocks. A late block of X is
    dropped where a block of a piece we have is, and opens no partial."""
    held = Held(tmp_path)

    async def go():
        async with held:
            t = held.t
            peer = await held.let_the_second_in()
            assert t._wanted_remaining() == 1 <= t._tail_threshold()  # still counted as wanted: the tail's gate
            skips, downloaded = t.status()["judging_skips"], t.downloaded
            await t._fill_pipeline(peer)
            assert not peer.inflight and peer.fill_starved and t._piece_inflight[X] == 0
            assert t.status()["judging_skips"] > skips
            skips = t.status()["judging_skips"]
            await t._ingest_block(peer, X, 0, bytes(16384))
            assert X not in t._partials and t.downloaded == downloaded
            assert t.status()["judging_skips"] == skips + 1
            held.release()
            await asyncio.wait_for(t.on_complete.wait(), 30)
            return t.status()

    status = run(go())
    assert held.judged.count(X) == 1 and sorted(held.events) == [(i, "ok") for i in range(17)]
    assert status["judging"] == 0 and status["partials"] == 0 and status["duplicate_judged"] == 0
    assert held.byte_equal()


@pytest.mark.parametrize("way", ["stop", "close", "drop"])
def test_a_piece_at_the_judge_is_released_whatever_ends_the_wait(tmp_path, way):
    """(c) ``stop()`` and ``close()`` cancel the loop that awaits the
    verdict; a dropped peer's loop still gets its verdict and acts on it."""
    held = Held(tmp_path, hasher="tpu")

    async def go():
        async with held:
            t = held.t
            assert t.status()["judging"] == 1 and t._judging == {X}
            if way == "stop":
                await t.stop()
            elif way == "close":
                await held.leech.close()
            else:
                t._drop_peer(peer_at(t, "127.0.0.2"))
                assert t._judging == {X}  # the verdict is still owed
                held.release()
                await eventually(lambda: not t._judging, "the verdict acted on")
                assert t.bitfield.has(X) and (X, "ok") in held.events
            snap = held.sched.metrics_snapshot()
            return t.status(), set(t._judging), list(t._verify_pending), len(t._tasks) if way != "drop" else 0, snap

    status, judging, pending, tasks, snap = run(go())
    assert status["judging"] == 0 and judging == set() and pending == [] and tasks == 0
    assert snap["queue_pieces"] == 0 and snap["staging"]["outstanding"] == 0
    assert held.judged.count(X) == 1 and status["duplicate_judged"] == 0


def test_a_judge_that_raises_releases_the_piece_and_the_starved_peer_fetches_it(tmp_path):
    """(c) an exception that is not the scheduler's two ends the
    delivering peer's loop; the piece leaves the judge with it and the
    peer that sat starved beside it is offered the piece."""
    held = Held(tmp_path)

    async def go():
        async with held:
            t = held.t
            await held.let_the_second_in()
            held.broken.append("the judge fell over")
            held.release()
            await asyncio.wait_for(t.on_complete.wait(), 30)
            return t.status(), [p.address[0] for p in t.peers.values()]

    status, left = run(go())
    assert held.judged.count(X) == 2 and held.events.count((X, "ok")) == 1 and left == ["127.0.0.3"]
    assert status["judging"] == 0 and status["partials"] == 0 and status["duplicate_judged"] == 0
    assert held.byte_equal()


def test_a_webseed_loop_is_never_given_a_piece_at_the_judge(tmp_path):
    """(d)"""
    src = str(tmp_path / "src")
    meta, _ = write_source(src, length=16 * PLEN + 5003)

    async def go():
        leech, _ = await a_leecher("cpu")
        try:
            t = await leech.add(meta, str(tmp_path / "dest"))
            n = t.info.num_pieces
            assert sorted(t._pick_webseed_pieces(n)) == list(range(n))
            t._judging.add(X)
            skips = t._judging_skips
            t._stream_positions["reader"] = (X - 1, 3)  # a stream window over it too
            picked = t._pick_webseed_pieces(n)
            assert sorted(picked) == [i for i in range(n) if i != X] and picked[:2] == [X - 1, X + 1]
            assert t._judging_skips > skips
            t._judging.discard(X)
            assert sorted(t._pick_webseed_pieces(n)) == list(range(n))
        finally:
            await leech.close()

    run(go())


def test_the_three_counters_are_in_status_and_metrics(tmp_path):
    from torrent_tpu.utils.metrics import render_metrics

    src = str(tmp_path / "src")
    meta, _ = write_source(src, length=4 * PLEN)

    async def go():
        leech, _ = await a_leecher("cpu")
        try:
            t = await leech.add(meta, str(tmp_path / "dest"))
            t._judging.add(1)
            t._judging_skips, t._duplicate_judged = 7, 2
            return t.status(), leech.status()["torrents"][meta.info_hash.hex()], render_metrics(leech)
        finally:
            await leech.close()

    status, through_client, text = run(go())
    assert (status["judging"], status["judging_skips"], status["duplicate_judged"]) == (1, 7, 2)
    assert through_client["judging"] == 1
    label = f'{{info_hash="{meta.info_hash.hex()}",name="payload.bin"}}'
    assert f"torrent_tpu_torrent_judging{label} 1" in text
    assert f"torrent_tpu_torrent_judging_skips_total{label} 7" in text
    assert f"torrent_tpu_torrent_duplicate_judged_total{label} 2" in text


# ---------------------------------------------------------------- a verified piece is one write


class CountingFs(FsStorage):
    """The leecher's backend, listing every ``set``: (offset, length)."""

    def __init__(self, root):
        super().__init__(root)
        self.sets = []

    def set(self, path, offset, data):
        self.sets.append((offset, len(data)))
        super().set(path, offset, data)


def write_entries() -> tuple[int, int]:
    s = pipeline_ledger().snapshot()["stages"].get("write", {})
    return s.get("ops", 0), s.get("bytes", 0)


@pytest.mark.parametrize("version", ["v1", "v2"])
@pytest.mark.parametrize("hasher", ["cpu", "tpu"])
def test_a_downloaded_piece_reaches_the_disk_as_one_write(tmp_path, hasher, version):
    """Every valid piece is one ``method.set`` of the whole piece (one file:
    one segment, where block by block it was one a 16 KiB block), one
    ledger ``write`` entry, and one reuse of the file's kept handle; still
    one delivery a piece to the judge."""
    from torrent_tpu.utils.metrics import render_metrics

    src, dest = str(tmp_path / "src"), str(tmp_path / "dest")
    meta = write_source(src)[0] if version == "v1" else write_source_v2(src)
    backend = CountingFs(dest)

    async def go():
        seeders = [await seeder(meta, src) for _ in range(3)]
        leech, sched = await a_leecher(hasher)
        judged, events = [], []
        entries, handles = write_entries(), storage_write_stats()
        try:
            t = await leech.add(meta, backend)
            slow_judge(t, judged, seconds=0.01)
            t.on_piece_verdict = lambda index, outcome: events.append((index, outcome))
            join(t, *seeders)
            await asyncio.wait_for(t.on_complete.wait(), 60)
            wrote = [a - b for a, b in zip(write_entries(), entries)]
            opened = {k: storage_write_stats()[k] - handles[k] for k in handles}
            return t.info.num_pieces, t.info.piece_length, judged, events, wrote, opened, render_metrics(leech)
        finally:
            await leech.close()
            if sched is not None:
                await sched.close()
            for s in seeders:
                await s.close()

    n, plen, judged, events, wrote, opened, text = run(go())
    assert n == 65
    assert sorted(judged) == list(range(n))  # deliveries put to the judge == pieces
    assert sorted(events) == [(i, "ok") for i in range(n)]
    assert sorted(backend.sets) == [(i * plen, min(plen, LENGTH - i * plen)) for i in range(n)]
    assert wrote == [n, LENGTH]  # one ledger entry, and the piece's bytes, a valid piece
    assert opened == {"write_opens": 1, "write_reuses": n - 1}
    assert "torrent_tpu_storage_write_opens_total " in text and "torrent_tpu_storage_write_reuses_total " in text
    assert open(os.path.join(dest, "payload.bin"), "rb").read() == open(os.path.join(src, "payload.bin"), "rb").read()
    assert backend._writers == {} == backend._handles  # the torrent's stop let go of its handles


# ---------------------------------------------------------------- a peer goes on while its piece is judged


class Gate:
    """The torrent's judge behind a gate a piece: a delivery put to it is
    listed in ``asked`` and waits until its piece is let through (the
    first ``hold`` deliveries only, where given), then gets the real
    verdict."""

    def __init__(self, torrent, hold=None):
        self.real = torrent._verify_piece_data
        self.hold = hold
        self.asked: list[int] = []
        self.gates: dict[int, asyncio.Event] = {}
        self.opened = False
        torrent._verify_piece_data = self.judge

    def gate(self, index) -> asyncio.Event:
        if index not in self.gates:
            self.gates[index] = asyncio.Event()
            if self.opened:
                self.gates[index].set()
        return self.gates[index]

    def let(self, *indices):
        for i in indices:
            self.gate(i).set()

    def open(self):
        self.opened = True
        for g in self.gates.values():
            g.set()

    async def judge(self, index, data, expected):
        self.asked.append(index)
        if self.hold is None or len(self.asked) <= self.hold:
            await self.gate(index).wait()
        return await self.real(index, data, expected)


def lone(tmp_path, plen, pieces, **config):
    """A torrent (not started) over an empty memory store, with ``pieces``
    whole pieces of ``plen`` bytes, and its payload."""
    from torrent_tpu.session.client import generate_peer_id
    from torrent_tpu.session.torrent import Torrent
    from torrent_tpu.storage.storage import MemoryStorage, Storage

    src = str(tmp_path / "src")
    meta, _ = write_source(src, plen=plen, length=pieces * plen)
    payload = open(os.path.join(src, meta.info.name), "rb").read()
    t = Torrent(
        metainfo=meta, storage=Storage(MemoryStorage(), meta.info), peer_id=b"-TT0001-lonelonelone",
        port=1, config=TorrentConfig(**config),
    )
    return t, payload


def fake_seeder(t, host="10.0.0.2"):
    """A connected peer that holds every piece and has unchoked us; what
    we ask of it is on its fake writer."""
    from torrent_tpu.session.peer import PeerConnection
    from tests.test_session import _FakeWriter

    peer = PeerConnection(
        peer_id=b"S" * 20, reader=object(), writer=_FakeWriter(), num_pieces=t.info.num_pieces, address=(host, 6881)
    )
    for i in range(t.info.num_pieces):
        peer.bitfield.set(i)
    t.peers[peer.peer_id] = peer
    t._avail += peer.bitfield.as_numpy()
    peer.peer_choking, peer.am_interested = False, True
    return peer


async def deliver(t, peer, payload, index):
    """Every block of piece ``index`` of ``payload`` from ``peer``, as its
    loop ingests them."""
    from torrent_tpu.storage.piece import BLOCK_SIZE

    plen = t.info.piece_length
    piece = payload[index * plen : (index + 1) * plen]
    for begin in range(0, len(piece), BLOCK_SIZE):
        await t._ingest_block(peer, index, begin, piece[begin : begin + BLOCK_SIZE])


def verdict_wait() -> tuple[int, float]:
    w = pipeline_ledger().snapshot()["waits"].get("ingest_verdict_wait", {})
    return w.get("ops", 0), w.get("busy_s", 0.0)


@pytest.mark.parametrize("hasher", ["cpu", "tpu"])
def test_the_peer_downloads_on_while_its_piece_is_judged(tmp_path, hasher):
    """One seeder; the first piece put to the judge is held there. The
    peer loop that delivered it goes on: it ingests the next blocks and
    asks for more, and every other piece is delivered, judged and
    written before that verdict comes. Every hand-off after the first
    found the peer's held piece at the judge. The torrent is smaller than
    the peer's judge bound (2 x 16 x 16 KiB), so that holds however late
    the other verdicts come: one piece more and a loop whose pieces are
    all still at the judge waits for the held one (the bound's own test
    is below)."""
    src, dest = str(tmp_path / "src"), str(tmp_path / "dest")
    length = 15 * PLEN + 5003
    meta, _ = write_source(src, length=length)
    n = meta.info.num_pieces

    async def go():
        seed = await seeder(meta, src)
        leech, sched = await a_leecher(hasher)
        events: list = []
        try:
            t = await leech.add(meta, dest)
            gate = Gate(t, hold=1)
            t.on_piece_verdict = lambda index, outcome: events.append((index, outcome))
            join(t, seed)
            await eventually(lambda: t.bitfield.count() == n - 1, "every other piece written")
            first = gate.asked[0]
            (peer,) = t.peers.values()
            assert t._judging == {first} and not t.bitfield.has(first)
            assert peer.bytes_down >= length - PLEN  # asked for and ingested behind the held piece
            during = t.status()
            gate.let(first)
            await asyncio.wait_for(t.on_complete.wait(), 30)
            return during, t.status(), sorted(gate.asked), events
        finally:
            await leech.close()
            if sched is not None:
                await sched.close()
            await seed.close()

    during, status, asked, events = run(go())
    assert during["judge_overlapped"] == n - 1 and during["judging_peak"] >= 2
    assert asked == list(range(n)) and sorted(events) == [(i, "ok") for i in range(n)]
    assert status["judging"] == 0 and status["duplicate_judged"] == 0 and status["downloaded"] == length


@pytest.mark.parametrize(("plen", "allowed"), [(16384, 32), (262144, 2), (1 << 20, 1)], ids=["16k", "256k", "1m"])
def test_a_completion_over_the_bound_waits_for_the_peers_oldest_verdict(tmp_path, plen, allowed):
    """A peer's pieces at the judge hold at most twice the bytes its
    pipeline asks for (2 x 16 x 16 KiB), and at least one piece: 32 at
    16 KiB, two at 256 KiB, one at 1 MiB. The completion past that waits
    for the oldest verdict, and the ledger wait is that hold alone."""
    t, payload = lone(tmp_path, plen, allowed + 2)

    async def go():
        gate = Gate(t)
        peer = fake_seeder(t)
        ops0, busy0 = verdict_wait()
        for i in range(allowed):
            await deliver(t, peer, payload, i)
        await asyncio.sleep(0)
        assert t._judging == set(range(allowed)) and sorted(gate.asked) == list(range(allowed))
        assert verdict_wait() == (ops0 + allowed, busy0)  # went on at once: an entry each, 0 s
        held = asyncio.ensure_future(deliver(t, peer, payload, allowed))
        # the hold is timed from its start, which follows the delivery's
        # own blocks: sleep only once it has begun
        await eventually(lambda: t.status()["judge_bound_waits"] == 1, "held at the bound")
        await asyncio.sleep(0.05)
        assert not held.done() and t.status()["judge_bound_waits"] == 1
        assert allowed in t._partials and allowed not in t._judging  # complete, not handed over yet
        gate.let(0)
        await asyncio.wait_for(held, 10)
        ops, busy = verdict_wait()
        assert ops == ops0 + allowed + 1 and busy - busy0 >= 0.05
        assert t._judging == set(range(1, allowed + 1))
        gate.open()
        await judged(t)
        return t.status()

    status = run(go())
    assert status["judging"] == 0 and status["judge_bound_waits"] == 1 and status["judging_peak"] == allowed
    assert status["judge_overlapped"] == (allowed - 1) + (allowed > 1)
    assert [t.bitfield.has(i) for i in range(allowed + 2)] == [True] * (allowed + 1) + [False]


def test_a_refusal_by_a_judge_task_bans_the_poisoner_and_its_other_verdicts_still_come(tmp_path):
    """Six pieces of one peer at the judge, three of them poisoned: the
    third refusal in a row bans the address and drops the peer from a
    judge task, and the verdicts still owed are acted on all the same."""
    t, payload = lone(tmp_path, PLEN, 8, max_corrupt_pieces=3)
    bad = bytearray(payload)
    for i in (0, 2, 4):
        bad[(i + 1) * PLEN - 1] ^= 0x5A

    async def go():
        gate = Gate(t)
        peer = fake_seeder(t, host="10.0.0.9")
        events: list = []
        t.on_piece_verdict = lambda index, outcome: events.append((index, outcome))
        for i in range(6):
            await deliver(t, peer, bytes(bad), i)
        await asyncio.sleep(0)
        assert t._judging == set(range(6)) and t.peers
        gate.let(0, 2)
        await eventually(lambda: not t._judging & {0, 2}, "two refusals acted on")
        assert t.peers and not t._banned
        gate.let(4)
        await eventually(lambda: 4 not in t._judging, "the third refusal acted on")
        assert set(t._banned) == {"10.0.0.9"} and not t.peers and peer.writer.closed
        assert t._judging == {1, 3, 5}  # still owed
        gate.open()
        await judged(t)
        return events

    events = run(go())
    assert t._judging == set() and sorted(events) == [(i, "corrupt" if i % 2 == 0 else "ok") for i in range(6)]
    assert [t.bitfield.has(i) for i in range(8)] == [False, True, False, True, False, True, False, False]


@pytest.mark.parametrize("where", ["verdict", "write"])
def test_stop_with_pieces_at_the_judge_leaves_none_there_and_no_task(tmp_path, where):
    """``stop()`` cancels the judge tasks as it does every task. One whose
    write runs in its thread waits for the bytes: a write cannot be
    called back, so the piece ends in the bitfield with its verdict."""
    import threading

    plen = 262144  # over INLINE_IO_MAX: the write takes a thread
    t, payload = lone(tmp_path, plen, 3)

    async def go():
        gate = Gate(t)
        peer = fake_seeder(t)
        events: list = []
        t.on_piece_verdict = lambda index, outcome: events.append((index, outcome))
        writing, resume = threading.Event(), threading.Event()
        real = t._write_piece

        def slow_write(base, data):
            writing.set()
            resume.wait(10)
            return real(base, data)

        t._write_piece = slow_write
        for i in range(2):
            await deliver(t, peer, payload, i)
        await asyncio.sleep(0)
        tasks = [task for task, _ in peer.at_judge]
        assert len(tasks) == 2 and t._judging == {0, 1}
        if where == "write":
            gate.let(0)
            await eventually(writing.is_set, "piece 0 in its write")
            stopping = asyncio.ensure_future(t.stop())
            await asyncio.sleep(0.05)
            assert not stopping.done()  # the cancel waits for the bytes
            resume.set()
            await asyncio.wait_for(stopping, 10)
        else:
            resume.set()
            await t.stop()
        return tasks, events

    tasks, events = run(go())
    assert t._judging == set() and not t._tasks and all(task.done() for task in tasks)
    if where == "write":
        assert events == [(0, "ok")] and t.bitfield.has(0) and t.storage.get(0, plen) == payload[:plen]
    else:
        assert events == [] and t.bitfield.count() == 0


@pytest.mark.parametrize("hasher", ["cpu", "tpu"])
def test_overlapped_verdicts_judge_every_piece_once(tmp_path, hasher):
    """Three seeders and a verdict of 20 ms: each peer has several pieces
    at the judge at once, and still every piece is put to the judge once
    (no duplicate came back with the overlap)."""
    src, dest = str(tmp_path / "src"), str(tmp_path / "dest")
    meta, _ = write_source(src)

    async def go():
        seeders = [await seeder(meta, src) for _ in range(3)]
        leech, sched = await a_leecher(hasher)
        judged_, events = [], []
        try:
            t = await leech.add(meta, dest)
            slow_judge(t, judged_, seconds=0.02)
            t.on_piece_verdict = lambda index, outcome: events.append((index, outcome))
            join(t, *seeders)
            await asyncio.wait_for(t.on_complete.wait(), 60)
            return t.info.num_pieces, judged_, events, t.status()
        finally:
            await leech.close()
            if sched is not None:
                await sched.close()
            for s in seeders:
                await s.close()

    n, judged_, events, status = run(go())
    assert sorted(judged_) == list(range(n)) and sorted(events) == [(i, "ok") for i in range(n)]
    assert status["duplicate_judged"] == 0 and status["judging"] == 0
    assert status["judge_overlapped"] > 0 and status["judging_peak"] > 3  # more than one a peer


def test_the_judge_counters_are_in_status_and_metrics(tmp_path):
    from torrent_tpu.utils.metrics import render_metrics

    src = str(tmp_path / "src")
    meta, _ = write_source(src, length=4 * PLEN)

    async def go():
        leech, _ = await a_leecher("cpu")
        try:
            t = await leech.add(meta, str(tmp_path / "dest"))
            t._judge_overlapped, t._judge_bound_waits, t._judging_peak = 11, 3, 9
            return t.status(), leech.status()["torrents"][meta.info_hash.hex()], render_metrics(leech)
        finally:
            await leech.close()

    status, through_client, text = run(go())
    assert (status["judge_overlapped"], status["judge_bound_waits"], status["judging_peak"]) == (11, 3, 9)
    assert through_client["judging_peak"] == 9
    label = f'{{info_hash="{meta.info_hash.hex()}",name="payload.bin"}}'
    assert f"torrent_tpu_torrent_judge_overlapped_total{label} 11" in text
    assert f"torrent_tpu_torrent_judge_bound_waits_total{label} 3" in text
    assert f"torrent_tpu_torrent_judging_peak{label} 9" in text
