"""Session runtime tests: scheduler units + full two-client swarm e2e.

The end-to-end swarm test (tracker + seed client + leech client on
localhost, real wire protocol all the way down) is coverage the reference
never had (SURVEY §4: torrent.ts/client.ts untested).
"""

import asyncio
import hashlib

import numpy as np
import pytest

from torrent_tpu.codec.bencode import bencode
from torrent_tpu.codec.metainfo import parse_metainfo
from torrent_tpu.net.types import AnnounceEvent
from torrent_tpu.server.in_memory import run_tracker
from torrent_tpu.server.tracker import ServeOptions
from torrent_tpu.session.client import Client, ClientConfig, generate_peer_id
from torrent_tpu.session.peer import PeerConnection
from torrent_tpu.session.torrent import Torrent, TorrentConfig, TorrentState, _PartialPiece
from torrent_tpu.storage.piece import BLOCK_SIZE
from torrent_tpu.storage.storage import MemoryStorage, Storage


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def build_torrent_bytes(payload: bytes, piece_len: int, announce: bytes, name=b"swarm-test"):
    pieces = b"".join(
        hashlib.sha1(payload[i : i + piece_len]).digest() for i in range(0, len(payload), piece_len)
    )
    return bencode(
        {
            b"announce": announce,
            b"info": {
                b"name": name,
                b"piece length": piece_len,
                b"pieces": pieces,
                b"length": len(payload),
            },
        }
    )


def fast_config(**kw):
    cfg = TorrentConfig(choke_interval=0.15, announce_retry=1.0, **kw)
    return cfg


class TestSchedulerUnits:
    def make_torrent(self, payload_len=100_000, piece_len=32768):
        rng = np.random.default_rng(5)
        payload = rng.integers(0, 256, size=payload_len, dtype=np.uint8).tobytes()
        data = build_torrent_bytes(payload, piece_len, b"http://127.0.0.1:1/announce")
        m = parse_metainfo(data)
        storage = Storage(MemoryStorage(), m.info)
        t = Torrent(
            metainfo=m,
            storage=storage,
            peer_id=generate_peer_id(),
            port=1234,
            config=fast_config(),
        )
        return t, payload

    def test_blocks_of_last_piece(self):
        t, _ = self.make_torrent(payload_len=BLOCK_SIZE * 2 + 100, piece_len=BLOCK_SIZE * 2)
        blocks = list(t._blocks_of(1))
        assert blocks == [(1, 0, 100)]
        blocks0 = list(t._blocks_of(0))
        assert blocks0 == [(0, 0, BLOCK_SIZE), (0, BLOCK_SIZE, BLOCK_SIZE)]

    def test_left_accounting(self):
        t, _ = self.make_torrent()
        assert t.left == 100_000
        t.bitfield.set(0)
        assert t.left == 100_000 - 32768
        for i in range(t.info.num_pieces):
            t.bitfield.set(i)
        assert t.left == 0

    def test_announce_info_counters(self):
        t, _ = self.make_torrent()
        t.uploaded = 17
        t.downloaded = 23
        info = t._announce_info(AnnounceEvent.STARTED)
        assert info.uploaded == 17 and info.downloaded == 23 and info.left == 100_000
        assert len(info.key) == 4

    def test_status(self):
        t, _ = self.make_torrent()
        s = t.status()
        assert s["pieces"] == "0/4" and s["state"] == "stopped"


async def start_tracker():
    opts = ServeOptions(http_port=0, udp_port=None, host="127.0.0.1", interval=2)
    server, task = await run_tracker(opts)
    return server, task, f"http://127.0.0.1:{server.http_port}/announce"


class TestSwarmE2E:
    def test_seed_to_leech_transfer(self, tmp_path):
        """Full pipeline: author → seed → tracker → leech → verify."""

        async def go():
            rng = np.random.default_rng(42)
            payload = rng.integers(0, 256, size=200_000, dtype=np.uint8).tobytes()
            server, pump, announce_url = await start_tracker()
            torrent_bytes = build_torrent_bytes(payload, 32768, announce_url.encode())
            m = parse_metainfo(torrent_bytes)
            assert m is not None

            seed = Client(ClientConfig(host="127.0.0.1"))
            leech = Client(ClientConfig(host="127.0.0.1"))
            seed.config.torrent = fast_config()
            leech.config.torrent = fast_config()
            await seed.start()
            await leech.start()
            try:
                # seed side: payload already on "disk"
                seed_storage = Storage(MemoryStorage(), m.info)
                for off in range(0, len(payload), 65536):
                    seed_storage.set(off, payload[off : off + 65536])
                t_seed = await seed.add(m, seed_storage)
                assert t_seed.state == TorrentState.SEEDING  # recheck found all

                leech_storage = Storage(MemoryStorage(), m.info)
                t_leech = await leech.add(m, leech_storage)
                assert t_leech.state == TorrentState.DOWNLOADING

                await asyncio.wait_for(t_leech.on_complete.wait(), timeout=30)
                assert t_leech.bitfield.complete
                assert t_leech.state == TorrentState.SEEDING
                # data integrity end to end
                got = t_leech.storage.get(0, len(payload))
                assert got == payload
                # live counters moved (§8.3 fix)
                assert t_leech.downloaded == len(payload)
                assert t_seed.uploaded >= len(payload)
                assert t_leech.left == 0
            finally:
                await seed.close()
                await leech.close()
                server.close()
                await asyncio.wait_for(pump, 5)

        run(go())

    def test_unknown_infohash_dropped_pre_reply(self):
        async def go():
            client = Client(ClientConfig(host="127.0.0.1"))
            await client.start()
            try:
                reader, writer = await asyncio.open_connection("127.0.0.1", client.port)
                from torrent_tpu.net.protocol import send_handshake

                await send_handshake(writer, b"\x07" * 20, b"-XX0001-cccccccccccc")
                # server must close without ever replying
                data = await asyncio.wait_for(reader.read(100), timeout=5)
                assert data == b""
                writer.close()
            finally:
                await client.close()

        run(go())

    def test_duplicate_add_rejected(self):
        async def go():
            client = Client(ClientConfig(host="127.0.0.1"))
            await client.start()
            try:
                data = build_torrent_bytes(b"\x01" * 50_000, 16384, b"http://127.0.0.1:1/a")
                m = parse_metainfo(data)
                await client.add(m, Storage(MemoryStorage(), m.info))
                with pytest.raises(ValueError, match="already added"):
                    await client.add(m, Storage(MemoryStorage(), m.info))
            finally:
                await client.close()

        run(go())

    def test_resume_recheck_partial(self, tmp_path):
        """Partial data on disk → recheck marks only valid pieces."""

        async def go():
            rng = np.random.default_rng(9)
            payload = rng.integers(0, 256, size=131072, dtype=np.uint8).tobytes()
            data = build_torrent_bytes(payload, 32768, b"http://127.0.0.1:1/a")
            m = parse_metainfo(data)
            storage = Storage(MemoryStorage(), m.info)
            # only pieces 0 and 2 present and correct
            storage.set(0, payload[:32768])
            storage.set(65536, payload[65536:98304])
            t = Torrent(
                metainfo=m,
                storage=storage,
                peer_id=generate_peer_id(),
                port=1,
                config=fast_config(),
            )
            await t.recheck()
            assert [i for i in range(4) if t.bitfield.has(i)] == [0, 2]
            assert t.left == 65536
            # rechecked pieces are write-protected against duplicates
            assert storage.set(0, b"\x00" * 32768) is False

        run(go())

    def test_corrupt_piece_rejected_and_not_counted(self):
        """A peer sending garbage fails verification; stats roll back."""

        async def go():
            rng = np.random.default_rng(3)
            payload = rng.integers(0, 256, size=32768, dtype=np.uint8).tobytes()
            data = build_torrent_bytes(payload, 32768, b"http://127.0.0.1:1/a")
            m = parse_metainfo(data)
            t = Torrent(
                metainfo=m,
                storage=Storage(MemoryStorage(), m.info),
                peer_id=generate_peer_id(),
                port=1,
                config=fast_config(),
            )
            from torrent_tpu.session.torrent import _PartialPiece

            partial = _PartialPiece(index=0, length=32768, buffer=bytearray(32768))
            partial.buffer[:] = b"\x00" * 32768  # wrong content
            partial.received = set(range(0, 32768, BLOCK_SIZE))
            t._partials[0] = partial
            t.downloaded = 32768
            await t._finish_piece(partial)
            assert not t.bitfield.has(0)
            assert t.downloaded == 0  # poisoned bytes not counted
            assert 0 not in t._partials  # re-requestable

        run(go())


class TestReviewRegressions:
    """Regressions for the milestone-2 code-review findings."""

    def test_completed_event_sent_to_tracker(self):
        async def go():
            rng = np.random.default_rng(21)
            payload = rng.integers(0, 256, size=100_000, dtype=np.uint8).tobytes()
            server, pump, announce_url = await start_tracker()
            m = parse_metainfo(build_torrent_bytes(payload, 32768, announce_url.encode()))
            seed = Client(ClientConfig(host="127.0.0.1"))
            leech = Client(ClientConfig(host="127.0.0.1"))
            seed.config.torrent = fast_config()
            leech.config.torrent = fast_config()
            await seed.start()
            await leech.start()
            try:
                s_storage = Storage(MemoryStorage(), m.info)
                for off in range(0, len(payload), 65536):
                    s_storage.set(off, payload[off : off + 65536])
                await seed.add(m, s_storage)
                t_leech = await leech.add(m, Storage(MemoryStorage(), m.info))
                await asyncio.wait_for(t_leech.on_complete.wait(), timeout=30)
                # the tracker must record the snatch (lifetime downloaded)
                for _ in range(80):
                    f = pump.tracker.files.get(m.info_hash)
                    if f and f.downloaded >= 1:
                        break
                    await asyncio.sleep(0.1)
                assert pump.tracker.files[m.info_hash].downloaded >= 1
            finally:
                await seed.close()
                await leech.close()
                server.close()
                await asyncio.wait_for(pump, 5)

        run(go())

    def test_add_before_start_raises_cleanly(self):
        async def go():
            client = Client(ClientConfig())
            data = build_torrent_bytes(b"\x01" * 50_000, 16384, b"http://x/a")
            m = parse_metainfo(data)
            with pytest.raises(RuntimeError, match="start"):
                await client.add(m, Storage(MemoryStorage(), m.info))

        run(go())

    def test_task_set_self_prunes(self):
        async def go():
            t, _ = TestSchedulerUnits().make_torrent()

            async def noop():
                pass

            task = t._spawn(noop())
            await task
            await asyncio.sleep(0)
            assert task not in t._tasks

        run(go())

    def test_udp_negative_numwant_means_default(self):
        async def go():
            from torrent_tpu.server.in_memory import run_tracker as rt
            from torrent_tpu.server.tracker import ServeOptions as SO
            from torrent_tpu.utils.bytesio import write_int

            server, pump = await rt(SO(http_port=None, udp_port=0, host="127.0.0.1"))
            try:
                loop = asyncio.get_running_loop()
                fut = loop.create_future()

                class P(asyncio.DatagramProtocol):
                    def connection_made(self, tr):
                        self.tr = tr

                    def datagram_received(self, data, addr):
                        if not fut.done():
                            fut.set_result(data)

                tr, proto = await loop.create_datagram_endpoint(
                    P, remote_addr=("127.0.0.1", server.udp_port)
                )
                tr.sendto(write_int(0x41727101980, 8) + write_int(0, 4) + write_int(7, 4))
                conn = await asyncio.wait_for(fut, 5)
                cid = conn[8:16]
                fut2 = loop.create_future()
                proto.datagram_received = lambda d, a: (not fut2.done()) and fut2.set_result(d)
                ann = (
                    cid + write_int(1, 4) + write_int(8, 4) + b"\x05" * 20 + b"-TT0001-zzzzzzzzzzzz"
                    + write_int(0, 8) + write_int(10, 8) + write_int(0, 8)
                    + write_int(2, 4) + b"\x00" * 4 + b"\x00" * 4
                    + b"\xff\xff\xff\xff"  # numwant = -1
                    + write_int(7070, 2)
                )
                tr.sendto(ann)
                resp = await asyncio.wait_for(fut2, 5)
                assert resp[:4] == write_int(1, 4)  # announce reply, not error
                tr.close()
            finally:
                server.close()
                await asyncio.wait_for(pump, 5)

        run(go())


class TestTpuIngestVerify:
    """Completed pieces verified through the client's hash-plane scheduler
    during a live swarm transfer (hasher='tpu'), not just at resume-recheck."""

    def test_seed_to_leech_with_tpu_hasher(self, tmp_path):
        from torrent_tpu.models.verifier import TPUVerifier

        async def go():
            rng = np.random.default_rng(77)
            payload = rng.integers(0, 256, size=200_000, dtype=np.uint8).tobytes()
            server, pump, announce_url = await start_tracker()
            torrent_bytes = build_torrent_bytes(payload, 32768, announce_url.encode())
            m = parse_metainfo(torrent_bytes)

            seed = Client(ClientConfig(host="127.0.0.1"))
            leech = Client(ClientConfig(host="127.0.0.1", hasher="tpu"))
            seed.config.torrent = fast_config()
            leech.config.torrent = fast_config(hasher="tpu", verify_batch_size=4)
            # pre-seed the verifier cache with a small test-geometry one
            leech._verifier_cache[32768] = TPUVerifier(
                piece_length=32768, batch_size=4, backend="jax"
            )
            await seed.start()
            await leech.start()
            try:
                seed_storage = Storage(MemoryStorage(), m.info)
                for off in range(0, len(payload), 65536):
                    seed_storage.set(off, payload[off : off + 65536])
                t_seed = await seed.add(m, seed_storage)
                assert t_seed.state == TorrentState.SEEDING
                t_leech = await leech.add(m, Storage(MemoryStorage(), m.info))
                assert t_leech.verifier is not None
                assert t_leech.ingest_scheduler is leech.ingest_scheduler is not None
                await asyncio.wait_for(t_leech.on_complete.wait(), timeout=30)
                assert t_leech.storage.get(0, len(payload)) == payload
            finally:
                await seed.close()
                await leech.close()
                server.close()
                await asyncio.wait_for(pump, 5)

        run(go())

    def test_concurrent_finishers_share_one_launch_and_corrupt_is_flagged(self):
        """Direct check of the ingest road: good pieces pass, corrupt
        fails, and pieces that finish together ride one scheduler launch
        of the tenant ``ingest`` (each submission carries the flush hint,
        so nobody sits out the deadline)."""
        from torrent_tpu.sched import HashPlaneScheduler, SchedulerConfig

        async def go():
            t, payload = TestSchedulerUnits().make_torrent(payload_len=4 * 32768)
            sched = await HashPlaneScheduler(
                SchedulerConfig(batch_target=4, flush_deadline=5.0), hasher="tpu"
            ).start()
            t.ingest_scheduler = sched
            t.config.hasher = "tpu"
            datas = [payload[i * 32768 : (i + 1) * 32768] for i in range(3)]
            corrupt = bytearray(datas[1])
            corrupt[0] ^= 0xFF
            try:
                results = await asyncio.wait_for(
                    asyncio.gather(
                        t._verify_piece_data(0, datas[0], t.info.pieces[0]),
                        t._verify_piece_data(1, bytes(corrupt), t.info.pieces[1]),
                        t._verify_piece_data(2, datas[2], t.info.pieces[2]),
                    ),
                    30,
                )
                snap = sched.metrics_snapshot()
            finally:
                await sched.close()
            assert results == [True, False, True]
            assert snap["launches"] == 1 and snap["flush_reasons"]["hint"] == 1
            assert snap["tenants"]["ingest"]["served_pieces"] == 3
            assert t._verify_pending == [] and not t._verify_flushing  # the v2 micro-batch was not the road

        run(go())


class TestPoisonedPeerBan:
    def _mk_peer(self, t, pid=b"E" * 20, ip="10.9.9.9"):
        peer = PeerConnection(
            peer_id=pid,
            reader=object(),
            writer=_FakeWriter(),
            num_pieces=t.info.num_pieces,
            address=(ip, 6881),
        )
        t.peers[peer.peer_id] = peer
        return peer

    async def _fail_piece(self, t, peer, index):
        partial = _PartialPiece(index=index, length=32768, buffer=bytearray(b"\xff" * 32768))
        partial.contributors.add((peer.peer_id, peer.address[0]))
        partial.received.update(range(0, 32768, BLOCK_SIZE))
        t._partials[index] = partial
        await t._finish_piece(partial)

    def test_corrupt_contributors_banned(self):
        """Failure detection (SURVEY §5): an address feeding corrupt pieces
        is dropped and banned from redial/re-accept."""

        async def go():
            t, payload = TestSchedulerUnits().make_torrent(payload_len=6 * 32768)
            t.config.max_corrupt_pieces = 2
            peer = self._mk_peer(t)
            for i in range(2):
                await self._fail_piece(t, peer, i)
            assert peer.peer_id not in t.peers  # dropped
            assert "10.9.9.9" in t._banned
            # redial attempts skip the banned address
            from torrent_tpu.net.types import AnnouncePeer

            t._connect_new_peers([AnnouncePeer(ip="10.9.9.9", port=6881)])
            assert not t._dialing
            # inbound reconnect is refused
            await t.add_peer(b"F" * 20, object(), _FakeWriter(), address=("10.9.9.9", 9))
            assert b"F" * 20 not in t.peers

        run(go())

    def test_strikes_survive_reconnect(self):
        """Cycling connections must not reset the corruption count."""

        async def go():
            t, _ = TestSchedulerUnits().make_torrent(payload_len=6 * 32768)
            t.config.max_corrupt_pieces = 2
            p1 = self._mk_peer(t, pid=b"A" * 20)
            await self._fail_piece(t, p1, 0)
            t._drop_peer(p1)  # attacker disconnects with 1 strike
            p2 = self._mk_peer(t, pid=b"B" * 20)  # same IP, new identity
            await self._fail_piece(t, p2, 1)
            assert "10.9.9.9" in t._banned  # 1 + 1 strikes, same address

        run(go())

    def test_strike_and_ban_tables_capped(self, monkeypatch):
        """bounded-state hardening: strike/ban state is keyed by
        attacker-minted IPs, so both tables must churn at capacity
        instead of growing for the life of the session."""
        from torrent_tpu.session import torrent as torrent_mod

        monkeypatch.setattr(torrent_mod, "MAX_CORRUPTION_IPS", 3)
        monkeypatch.setattr(torrent_mod, "MAX_BANNED_IPS", 2)

        async def go():
            t, _ = TestSchedulerUnits().make_torrent()
            t.config.max_corrupt_pieces = 100  # strikes only, no bans yet
            # the repeat offender accumulates strikes...
            for _ in range(3):
                t._credit_corruption({(b"A" * 20, "9.0.0.1")})
            # ...then a burst of fresh one-strike IPs hits the cap: the
            # least-incriminated entry is evicted, never the offender
            for i in range(5):
                t._credit_corruption({(b"A" * 20, f"1.0.0.{i}")})
            assert len(t._corruption) == 3
            assert "9.0.0.1" in t._corruption
            # ban list: FIFO churn at capacity
            t.config.max_corrupt_pieces = 1
            for i in range(4):
                t._credit_corruption({(b"B" * 20, f"2.0.0.{i}")})
            assert len(t._banned) == 2
            assert "2.0.0.3" in t._banned  # newest ban live
            assert "2.0.0.0" not in t._banned  # oldest aged out

        run(go())

    def test_absolve_decays_strikes(self):
        """A verified piece sheds a strike — honest co-contributors of a
        poisoner are not collaterally banned."""

        async def go():
            t, payload = TestSchedulerUnits().make_torrent(payload_len=6 * 32768)
            t.config.max_corrupt_pieces = 3
            peer = self._mk_peer(t, ip="10.1.1.1")
            await self._fail_piece(t, peer, 0)
            assert t._corruption["10.1.1.1"] == 1
            # now a GOOD piece this peer contributed to verifies
            good = _PartialPiece(
                index=1, length=32768, buffer=bytearray(payload[32768:65536])
            )
            good.contributors.add((peer.peer_id, "10.1.1.1"))
            good.received.update(range(0, 32768, BLOCK_SIZE))
            t._partials[1] = good
            await t._finish_piece(good)
            assert t.bitfield.has(1)
            assert t._corruption["10.1.1.1"] == 0  # absolved

        run(go())

    def test_drop_peer_idempotent(self):
        async def go():
            t, _ = TestSchedulerUnits().make_torrent()
            peer = self._mk_peer(t)
            peer.bitfield.set(0)
            t._avail[0] += 1
            t._drop_peer(peer)
            t._drop_peer(peer)  # peer-loop finally calls again
            assert t._avail[0] == 0  # decremented exactly once

        run(go())


class _FakeWriter:
    def __init__(self):
        self.data = bytearray()
        self.closed = False

    def write(self, b):
        self.data += b

    async def drain(self):
        pass

    def close(self):
        self.closed = True


class TestAntiSnubbing:
    def test_snubbed_peer_releases_inflight(self):
        """A peer that stops delivering frees its requested blocks for
        other peers instead of holding them until the 240s peer timeout."""
        import time as _time

        async def go():
            t, _ = TestSchedulerUnits().make_torrent()
            t.config.snub_timeout = 5.0
            slow = PeerConnection(
                peer_id=b"S" * 20,
                reader=object(),
                writer=_FakeWriter(),
                num_pieces=t.info.num_pieces,
            )
            t.peers[slow.peer_id] = slow
            blk = (0, 0, BLOCK_SIZE)
            slow.inflight.add(blk)
            t._inflight_count[blk] += 1
            slow.last_block_rx = _time.monotonic() - 1  # recent: kept
            await t._release_snubbed()
            assert blk in slow.inflight
            slow.last_block_rx = _time.monotonic() - 60  # stalled: freed
            await t._release_snubbed()
            assert not slow.inflight and t._inflight_count[blk] == 0
            assert slow.peer_id in t.peers  # connection itself survives

        run(go())

    def test_snubbed_peer_skipped_until_redeemed(self):
        """Freed blocks must not bounce straight back to the snubber, and
        NATed co-contributors take one strike per corrupt piece, not one
        per connection."""
        import time as _time

        async def go():
            t, _ = TestSchedulerUnits().make_torrent()
            t.config.snub_timeout = 5.0
            slow = PeerConnection(
                peer_id=b"S" * 20, reader=object(), writer=_FakeWriter(),
                num_pieces=t.info.num_pieces,
            )
            for i in range(t.info.num_pieces):
                slow.bitfield.set(i)
            slow.peer_choking = False
            t.peers[slow.peer_id] = slow
            blk = (0, 0, BLOCK_SIZE)
            slow.inflight.add(blk)
            t._inflight_count[blk] += 1
            slow.last_block_rx = _time.monotonic() - 60
            await t._release_snubbed()
            assert slow.snubbed and not slow.inflight
            await t._fill_pipeline(slow)
            assert not slow.inflight  # no re-requests while snubbed
            # a delivered block redeems
            await t._handle_message(slow, __import__("torrent_tpu.net.protocol", fromlist=["Piece"]).Piece(0, 0, b"\x00" * BLOCK_SIZE))
            assert not slow.snubbed

            # NAT dedup: two peer ids, one IP, one corrupt piece = 1 strike
            t2, _ = TestSchedulerUnits().make_torrent()
            contributors = {(b"A" * 20, "9.9.9.9"), (b"B" * 20, "9.9.9.9")}
            t2._credit_corruption(contributors)
            assert t2._corruption["9.9.9.9"] == 1

        run(go())


class TestAdviceRegressions:
    """Round-1 advisor findings: webseed/peer race, BEP 27 private flag."""

    def test_finish_piece_idempotent(self):
        """Finishing the same partial twice (webseed + endgame peer both
        complete it) must be a no-op the second time, not a KeyError."""

        async def go():
            t, payload = TestSchedulerUnits().make_torrent(payload_len=4 * 32768)
            partial = _PartialPiece(
                index=0, length=32768, buffer=bytearray(payload[:32768]), webseed=True
            )
            partial.received.update(range(0, 32768, BLOCK_SIZE))
            t._partials[0] = partial
            await t._finish_piece(partial)
            assert t.bitfield.has(0)
            before = t.bitfield.count()
            await t._finish_piece(partial)  # stale second finish: no-op
            assert t.bitfield.count() == before

        run(go())

    def test_fill_pipeline_skips_webseed_reservations(self):
        """Peers must not race an in-flight HTTP fetch for a reserved
        piece — outside endgame the scheduler skips webseed partials."""

        async def go():
            t, _ = TestSchedulerUnits().make_torrent(payload_len=4 * 32768)
            reserved = _PartialPiece(
                index=0, length=32768, buffer=bytearray(32768), webseed=True
            )
            t._partials[0] = reserved
            peer = PeerConnection(
                peer_id=b"W" * 20,
                reader=object(),
                writer=_FakeWriter(),
                num_pieces=t.info.num_pieces,
            )
            peer.peer_choking = False
            t.peers[peer.peer_id] = peer
            for i in range(t.info.num_pieces):
                peer.bitfield.set(i)
                t._avail[i] += 1
            t._rarity_dirty = True
            await t._fill_pipeline(peer)
            assert peer.inflight  # it did pick work...
            assert all(blk[0] != 0 for blk in peer.inflight)  # ...but not piece 0

        run(go())

    def test_webseed_skips_piece_completed_by_peer(self):
        """If a peer (endgame) finishes a reserved piece first, the
        webseed's late finish must not double-count `downloaded`."""

        async def go():
            t, payload = TestSchedulerUnits().make_torrent(payload_len=4 * 32768)
            reserved = _PartialPiece(
                index=1,
                length=32768,
                buffer=bytearray(payload[32768:65536]),
                webseed=True,
            )
            t._partials[1] = reserved
            reserved.received.update(range(0, 32768, BLOCK_SIZE))
            await t._finish_piece(reserved)  # "peer" completed it
            downloaded_after_peer = t.downloaded
            # the webseed loop's guard: stale partial no longer registered
            assert t._partials.get(1) is not reserved
            # a second finish on the stale object is a no-op
            await t._finish_piece(reserved)
            assert t.downloaded == downloaded_after_peer

        run(go())

    def _private_metainfo(self, payload, piece_len=32768):
        pieces = b"".join(
            hashlib.sha1(payload[i : i + piece_len]).digest()
            for i in range(0, len(payload), piece_len)
        )
        return parse_metainfo(
            bencode(
                {
                    b"announce": b"http://127.0.0.1:1/announce",
                    b"info": {
                        b"name": b"priv",
                        b"piece length": piece_len,
                        b"pieces": pieces,
                        b"length": len(payload),
                        b"private": 1,
                    },
                }
            )
        )

    def test_private_torrent_skips_dht_and_pex(self):
        """BEP 27: a private torrent must not announce to the DHT, gossip
        PEX, or advertise ut_pex in its extended handshake."""

        async def go():
            rng = np.random.default_rng(6)
            payload = rng.integers(0, 256, size=4 * 32768, dtype=np.uint8).tobytes()
            m = self._private_metainfo(payload)
            storage = Storage(MemoryStorage(), m.info)
            t = Torrent(
                metainfo=m,
                storage=storage,
                peer_id=generate_peer_id(),
                port=1234,
                config=fast_config(),
                dht=object(),  # would crash if the dht loop ever ran
            )
            assert t.private
            await t.start()
            try:
                names = {task.get_name() for task in t._tasks}
                assert not any(n.startswith(("dht", "pex")) for n in names), names
                # incoming PEX gossip is dropped
                import torrent_tpu.net.extension as ext

                peer = PeerConnection(
                    peer_id=b"P" * 20,
                    reader=object(),
                    writer=_FakeWriter(),
                    num_pieces=t.info.num_pieces,
                )
                t.peers[peer.peer_id] = peer
                await t._handle_extended(
                    peer,
                    ext.LOCAL_EXT_IDS[ext.UT_PEX],
                    bencode({b"added": b"\x7f\x00\x00\x01\x1a\xe1"}),
                )
                assert not t._dialing
            finally:
                await t.stop()

        run(go())

    def test_public_torrent_advertises_pex(self):
        async def go():
            t, _ = TestSchedulerUnits().make_torrent()
            assert not t.private
            await t.start()
            try:
                names = {task.get_name() for task in t._tasks}
                assert any(n.startswith("pex") for n in names)
            finally:
                await t.stop()

        run(go())


class TestLargeGeometryScaling:
    """VERDICT weak #5: the session must stay responsive at 100k-piece
    geometry — per-message scheduler work is vectorized/O(changed), not a
    Python scan over every piece."""

    def test_100k_piece_session_hot_paths(self):
        import time as _t

        n = 100_000
        plen = 16384
        tb = bencode(
            {
                b"announce": b"http://127.0.0.1:1/announce",
                b"info": {
                    b"name": b"big",
                    b"piece length": plen,
                    # fake digests: nothing is verified in this test
                    b"pieces": b"\x00" * (20 * n),
                    b"length": n * plen - 5,  # short last piece
                },
            }
        )
        m = parse_metainfo(tb)
        assert m.info.num_pieces == n

        async def go():
            t = Torrent(
                metainfo=m,
                storage=Storage(MemoryStorage(), m.info),
                peer_id=generate_peer_id(),
                port=1234,
                config=fast_config(),
            )
            peer = PeerConnection(
                peer_id=b"B" * 20,
                reader=object(),
                writer=_FakeWriter(),
                num_pieces=n,
            )
            t.peers[peer.peer_id] = peer

            from torrent_tpu.net import protocol as proto
            from torrent_tpu.utils.bitfield import Bitfield as BF

            full = BF(n)
            full.from_numpy(np.ones(n, dtype=bool))

            t0 = _t.perf_counter()
            # full bitfield ingest: one vector op, not 100k Python ops
            await t._handle_message(peer, proto.BitfieldMsg(full.to_bytes()))
            assert int(t._avail.sum()) == n
            # 1000 haves at descending high indices: the old interest scan
            # walked ~99k pieces per message here
            peer2 = PeerConnection(
                peer_id=b"C" * 20, reader=object(), writer=_FakeWriter(), num_pieces=n
            )
            t.peers[peer2.peer_id] = peer2
            for i in range(n - 1, n - 1001, -1):
                await t._handle_message(peer2, proto.Have(i))
            # per-announce accounting is cheap (a vectorized numpy sum
            # over the bitfield — O(n) but microseconds at 100k pieces)
            for _ in range(1000):
                assert t.left == n * plen - 5
            t._rebuild_rarity()
            assert len(t._rarity_order) == n
            elapsed = _t.perf_counter() - t0
            # generous budget: the old O(n_pieces)-per-message paths took
            # tens of seconds here; the vectorized ones take well under 1s
            assert elapsed < 5.0, f"hot paths took {elapsed:.1f}s at 100k pieces"
            assert t._avail[n - 1] == 2 and t._avail[0] == 1

        run(go())


class TestClientContextManager:
    def test_async_with_starts_and_closes(self):
        async def go():
            async with Client(ClientConfig(host="127.0.0.1")) as c:
                assert c.port is not None
                port = c.port
                r, w = await asyncio.open_connection("127.0.0.1", port)
                w.close()
            # closed on exit: the listener is gone
            with pytest.raises(OSError):
                await asyncio.open_connection("127.0.0.1", port)

        run(go())


class TestIpv6Session:
    def test_v6_loopback_swarm_with_encryption(self):
        """The session layer end to end over IPv6 (::1): v6 tracker
        announce (peers6), v6 TCP accept/dial, MSE required — closing
        the gap between the tracker/DHT v6 e2es and the session."""

        async def go():
            rng = np.random.default_rng(66)
            payload = rng.integers(0, 256, size=120_000, dtype=np.uint8).tobytes()
            server, pump = await run_tracker(
                ServeOptions(http_port=0, udp_port=None, host="::1", interval=1)
            )
            url = f"http://[::1]:{server.http_port}/announce"
            m = parse_metainfo(build_torrent_bytes(payload, 32768, url.encode()))
            seed = Client(ClientConfig(host="::1"))
            leech = Client(ClientConfig(host="::1"))
            seed.config.torrent = fast_config(encryption="required")
            leech.config.torrent = fast_config(encryption="required")
            await seed.start()
            await leech.start()
            try:
                ss = Storage(MemoryStorage(), m.info)
                ss.set(0, payload)
                t_seed = await seed.add(m, ss)
                assert t_seed.state == TorrentState.SEEDING
                t = await leech.add(m, Storage(MemoryStorage(), m.info))
                await asyncio.wait_for(t.on_complete.wait(), timeout=30)
                assert t.storage.get(0, len(payload)) == payload
                assert (
                    t.status()["encrypted_peers"] >= 1
                    or t_seed.status()["encrypted_peers"] >= 1
                )
            finally:
                await seed.close()
                await leech.close()
                server.close()
                await asyncio.wait_for(pump, 5)

        run(go())


class TestBroadcastMutationSafety:
    def test_peer_registering_during_have_broadcast(self, monkeypatch):
        """The have-broadcast awaits per send; an inbound peer
        registering mid-iteration mutates self.peers — observed killing
        the ingesting peer's loop in an 8-leech fanout swarm."""

        async def go():
            rng = np.random.default_rng(5)
            payload = rng.integers(0, 256, size=65536, dtype=np.uint8).tobytes()
            data = build_torrent_bytes(payload, 32768, b"http://127.0.0.1:1/a")
            m = parse_metainfo(data)
            t = Torrent(
                metainfo=m,
                storage=Storage(MemoryStorage(), m.info),
                peer_id=generate_peer_id(),
                port=1234,
                config=TorrentConfig(),
            )
            for i in range(3):
                p = PeerConnection(
                    peer_id=bytes([i]) * 20,
                    reader=object(),
                    writer=_FakeWriter(),
                    num_pieces=m.info.num_pieces,
                )
                t.peers[p.peer_id] = p

            from torrent_tpu.net import protocol as proto_mod

            orig = proto_mod.send_message
            injected = {"done": False}

            async def racing_send(writer, msg):
                if not injected["done"]:
                    injected["done"] = True
                    late = PeerConnection(
                        peer_id=b"Z" * 20,
                        reader=object(),
                        writer=_FakeWriter(),
                        num_pieces=m.info.num_pieces,
                    )
                    t.peers[late.peer_id] = late  # mutate mid-broadcast
                await orig(writer, msg)

            monkeypatch.setattr(proto_mod, "send_message", racing_send)
            partial = _PartialPiece(index=0, length=32768, buffer=bytearray(payload[:32768]))
            partial.received.add(0)
            t._partials[0] = partial
            # must not raise "dictionary keys changed during iteration"
            assert await t._finish_piece(partial) == "ok"

        run(go())


class TestPickerCadence:
    def test_fill_pipeline_runs_per_half_pipeline_not_per_block(self):
        """The picker is an O(pieces) scan; running it once per ingested
        block made fast transfers O(n²) (measured ~40% of transfer CPU).
        With refill hysteresis it must run ~2/depth times per block."""

        async def go():
            rng = np.random.default_rng(7)
            payload = rng.integers(0, 256, size=8 * 1024 * 1024, dtype=np.uint8).tobytes()
            server, pump, announce_url = await start_tracker()
            m = parse_metainfo(build_torrent_bytes(payload, 65536, announce_url.encode()))
            seed = Client(ClientConfig(host="127.0.0.1"))
            leech = Client(ClientConfig(host="127.0.0.1"))
            seed.config.torrent = fast_config()
            leech.config.torrent = fast_config()
            await seed.start()
            await leech.start()
            try:
                ss = Storage(MemoryStorage(), m.info)
                for off in range(0, len(payload), 65536):
                    ss.set(off, payload[off : off + 65536])
                await seed.add(m, ss)
                leech_storage = Storage(MemoryStorage(), m.info)
                t_leech = await leech.add(m, leech_storage)
                calls = 0
                orig = t_leech._fill_pipeline

                async def counting(peer):
                    nonlocal calls
                    calls += 1
                    await orig(peer)

                t_leech._fill_pipeline = counting
                await asyncio.wait_for(t_leech.on_complete.wait(), timeout=30)
                n_blocks = len(payload) // 16384  # 512
                # per-block refill would be ~n_blocks calls; hysteresis
                # caps it near 2*n_blocks/depth (+ endgame/unchoke noise)
                assert calls < n_blocks // 2, (calls, n_blocks)
            finally:
                await seed.close()
                await leech.close()
                server.close()
                await asyncio.wait_for(pump, 5)

        run(go())


class TestConfigIsolationAndRaces:
    """VERDICT weak #6 + #8: caller-owned configs are never mutated, and
    concurrent delivery paths can't double-count or corrupt."""

    def test_client_does_not_mutate_callers_torrent_config(self):
        from torrent_tpu.session.client import Client, ClientConfig

        shared = TorrentConfig()
        cfg = ClientConfig(hasher="tpu", torrent=shared)
        Client(cfg)
        assert shared.hasher == "cpu"  # untouched by construction

        async def go():
            client = Client(ClientConfig(host="127.0.0.1", hasher="cpu", torrent=shared))
            await client.start()
            try:
                rng = np.random.default_rng(8)
                payload = rng.integers(0, 256, size=2 * 32768, dtype=np.uint8).tobytes()
                tb = build_torrent_bytes(payload, 32768, b"")
                m = parse_metainfo(tb)
                t = await client.add(m, Storage(MemoryStorage(), m.info))
                # the torrent got a derived copy, not the caller's object
                assert t.config is not shared
                assert shared.hasher == "cpu"
            finally:
                await client.close()

        run(go())

    def test_two_peers_same_block_counted_once(self):
        """Endgame duplicates: the same block arriving from two peers must
        be ingested once — no double count, no buffer corruption."""

        async def go():
            t, payload = TestSchedulerUnits().make_torrent(payload_len=2 * 32768)
            a = PeerConnection(
                peer_id=b"A" * 20, reader=object(), writer=_FakeWriter(),
                num_pieces=t.info.num_pieces,
            )
            b = PeerConnection(
                peer_id=b"B" * 20, reader=object(), writer=_FakeWriter(),
                num_pieces=t.info.num_pieces,
            )
            t.peers[a.peer_id] = a
            t.peers[b.peer_id] = b
            blocks = [
                (begin, payload[begin : begin + BLOCK_SIZE])
                for begin in range(0, 32768, BLOCK_SIZE)
            ]
            # interleave: A and B both deliver every block of piece 0
            for begin, data in blocks:
                await t._ingest_block(a, 0, begin, data)
                await t._ingest_block(b, 0, begin, data)
            assert t.bitfield.has(0)
            assert t.downloaded == 32768  # each block counted exactly once

        run(go())

    def test_verifier_staging_buffer_reuse_is_safe(self):
        """models/verifier.py contract: after _put_flat returns, the
        caller may immediately overwrite the staging buffer without
        corrupting the in-flight device batch."""
        import hashlib as _hl

        from torrent_tpu.models.verifier import TPUVerifier
        from torrent_tpu.ops.padding import digests_to_words, pad_in_place

        plen = 192
        v = TPUVerifier(piece_length=plen, batch_size=8)
        rng = np.random.default_rng(11)
        pieces = [rng.integers(0, 256, plen, np.uint8).tobytes() for _ in range(8)]
        padded = np.zeros((8, v.padded_len), dtype=np.uint8)
        for i, p in enumerate(pieces):
            padded[i, :plen] = np.frombuffer(p, dtype=np.uint8)
        nblocks = pad_in_place(padded, np.full(8, plen, dtype=np.int64))
        expected = digests_to_words([_hl.sha1(p).digest() for p in pieces])

        chunks = v._put_flat(padded)
        padded[:] = 0xFF  # hostile reuse: clobber the staging buffer NOW
        ok = np.asarray(v._verify_step_flat(chunks, nblocks, expected))
        assert ok.all(), "in-flight batch was corrupted by staging-buffer reuse"


class TestClientStatus:
    def test_aggregate_status(self):
        async def go():
            c = Client(ClientConfig(port=0, enable_upnp=False, max_upload_bps=1000))
            await c.start()
            try:
                s = c.status()
                assert s["port"] == c.port and s["peers"] == 0
                assert s["upload_cap_bps"] == 1000 and s["download_cap_bps"] == 0
                assert s["torrents"] == {} and not s["dht"] and not s["lsd"]
            finally:
                await c.close()

        run(go())


class TestChokePolicy:
    def test_seed_mode_unchokes_fastest_takers(self):
        """Seeding reciprocity: no downloads to rank by, so the slots go
        to the peers draining us fastest (max dissemination)."""
        import time as _time

        from torrent_tpu.net import protocol as proto
        from tests.test_fast import _messages

        async def go():
            t, _ = TestSchedulerUnits().make_torrent()
            t.state = TorrentState.SEEDING
            t.config.unchoke_slots = 1
            now = _time.monotonic()
            fast = PeerConnection(
                peer_id=b"U" * 20, reader=object(), writer=_FakeWriter(),
                num_pieces=t.info.num_pieces,
            )
            slow = PeerConnection(
                peer_id=b"V" * 20, reader=object(), writer=_FakeWriter(),
                num_pieces=t.info.num_pieces,
            )
            for p, up in ((fast, 10_000_000), (slow, 100)):
                p.peer_interested = True
                p.am_choking = True
                p.bytes_up = up
                p._up_mark = (now - 10.0, 0)
                t.peers[p.peer_id] = p
            # drive one real choke round (not a reimplementation of its
            # ranking): the fast taker must come out unchoked, the slow
            # one not (modulo the optimistic slot, pinned to fast here)
            t.config.choke_interval = 0.01
            task = t._spawn(t._choke_loop())
            for _ in range(100):
                if not fast.am_choking:
                    break
                await asyncio.sleep(0.01)
            t._stopping = True
            task.cancel()
            assert not fast.am_choking
            unchoked = [m for m in _messages(bytes(fast.writer.data))
                        if isinstance(m, proto.Unchoke)]
            assert unchoked

        run(go())


class TestServeCache:
    def test_piece_read_once_for_sequential_blocks(self):
        async def go():
            from tests.test_fast import _messages
            from torrent_tpu.net import protocol as proto

            t, payload = TestSchedulerUnits().make_torrent()
            await asyncio.to_thread(t.storage.set, 0, payload)
            for i in range(t.info.num_pieces):
                t.bitfield.set(i)
            reads = []
            orig = t.storage.read_piece
            t.storage.read_piece = lambda i: (reads.append(i), orig(i))[1]
            peer = PeerConnection(
                peer_id=b"C" * 20, reader=object(), writer=_FakeWriter(),
                num_pieces=t.info.num_pieces,
            )
            peer.am_choking = False
            t.peers[peer.peer_id] = peer
            for begin in range(0, 32768, BLOCK_SIZE):
                await t._serve_request(peer, 0, begin, BLOCK_SIZE)
            assert reads == [0]  # one disk read for both blocks
            blocks = [m for m in _messages(bytes(peer.writer.data))
                      if isinstance(m, proto.Piece)]
            assert b"".join(b.block for b in blocks) == payload[:32768]

        run(go())

    def test_cache_evicts_lru(self):
        async def go():
            t, payload = TestSchedulerUnits().make_torrent()
            t.config.serve_cache_pieces = 2
            await asyncio.to_thread(t.storage.set, 0, payload)
            for i in range(t.info.num_pieces):
                t.bitfield.set(i)
            peer = PeerConnection(
                peer_id=b"C" * 20, reader=object(), writer=_FakeWriter(),
                num_pieces=t.info.num_pieces,
            )
            peer.am_choking = False
            t.peers[peer.peer_id] = peer
            for idx in (0, 1, 2):
                await t._serve_request(peer, idx, 0, BLOCK_SIZE)
            assert set(t._serve_cache) == {1, 2}
            # touching 1 refreshes it; 2 is evicted next
            await t._serve_request(peer, 1, 0, BLOCK_SIZE)
            await t._serve_request(peer, 0, 0, BLOCK_SIZE)
            assert set(t._serve_cache) == {1, 0}

        run(go())

    def test_concurrent_misses_share_one_read(self):
        async def go():
            t, payload = TestSchedulerUnits().make_torrent()
            await asyncio.to_thread(t.storage.set, 0, payload)
            for i in range(t.info.num_pieces):
                t.bitfield.set(i)
            reads = []
            orig = t.storage.read_piece

            def slow_read(i):
                import time as _t

                reads.append(i)
                _t.sleep(0.05)
                return orig(i)

            t.storage.read_piece = slow_read
            peers = []
            for pid in (b"D" * 20, b"E" * 20):
                p = PeerConnection(
                    peer_id=pid, reader=object(), writer=_FakeWriter(),
                    num_pieces=t.info.num_pieces,
                )
                p.am_choking = False
                t.peers[pid] = p
                peers.append(p)
            await asyncio.gather(
                t._serve_request(peers[0], 0, 0, BLOCK_SIZE),
                t._serve_request(peers[1], 0, BLOCK_SIZE, BLOCK_SIZE),
            )
            assert reads == [0]  # one disk read shared by both misses
            assert not t._serve_pending

        run(go())

    def test_huge_pieces_bypass_cache(self):
        async def go():
            t, payload = TestSchedulerUnits().make_torrent()
            t.config.serve_cache_max_piece = 1024  # force bypass
            await asyncio.to_thread(t.storage.set, 0, payload)
            for i in range(t.info.num_pieces):
                t.bitfield.set(i)
            p = PeerConnection(
                peer_id=b"F" * 20, reader=object(), writer=_FakeWriter(),
                num_pieces=t.info.num_pieces,
            )
            p.am_choking = False
            t.peers[p.peer_id] = p
            await t._serve_request(p, 0, 0, BLOCK_SIZE)
            assert not t._serve_cache  # block path, no whole-piece read
            from tests.test_fast import _messages
            from torrent_tpu.net import protocol as proto

            blocks = [m for m in _messages(bytes(p.writer.data))
                      if isinstance(m, proto.Piece)]
            assert blocks[0].block == payload[:BLOCK_SIZE]

        run(go())


class TestSwarmResilience:
    async def _swarm(self, tmp_path, n_pieces=24):
        import os

        plen = 32768
        rng = np.random.default_rng(77)
        payload = rng.integers(0, 256, n_pieces * plen - 123, dtype=np.uint8).tobytes()
        data = None
        from torrent_tpu.server.in_memory import run_tracker
        from torrent_tpu.server.tracker import ServeOptions

        server, _ = await run_tracker(ServeOptions(http_port=0, udp_port=None, interval=1))
        data = build_torrent_bytes(
            payload, plen, b"http://127.0.0.1:%d/announce" % server.http_port,
            name=b"resil.bin",
        )
        m = parse_metainfo(data)
        seed_dir = str(tmp_path / "seed")
        os.makedirs(seed_dir, exist_ok=True)
        with open(os.path.join(seed_dir, "resil.bin"), "wb") as f:
            f.write(payload)
        return server, m, payload, seed_dir

    def test_leech_survives_seed_death(self, tmp_path):
        """A seed dying mid-transfer must not stall the leech: its
        in-flight blocks release and the survivor finishes the job."""
        import os

        async def go():
            server, m, payload, seed_dir = await self._swarm(tmp_path)
            c_seed1 = Client(ClientConfig(port=0, enable_upnp=False))
            c_seed2 = Client(ClientConfig(port=0, enable_upnp=False))
            c_leech = Client(ClientConfig(port=0, enable_upnp=False))
            for c in (c_seed1, c_seed2, c_leech):
                await c.start()
            try:
                await c_seed1.add(m, seed_dir)
                await c_seed2.add(m, seed_dir)
                leech_dir = str(tmp_path / "leech1")
                os.makedirs(leech_dir)
                t = await c_leech.add(m, leech_dir)
                # kill seed 1 as soon as the transfer is moving
                for _ in range(600):
                    if t.bitfield.count() >= 4:
                        break
                    await asyncio.sleep(0.02)
                await c_seed1.close()
                for _ in range(600):
                    if t.bitfield.complete:
                        break
                    await asyncio.sleep(0.05)
                assert t.bitfield.complete, f"stalled after seed death: {t.status()}"
                got = open(os.path.join(leech_dir, "resil.bin"), "rb").read()
                assert got == payload
            finally:
                await c_seed2.close()
                await c_leech.close()
                server.close()

        run(go(), timeout=90)

    def test_leeches_trade_pieces(self, tmp_path):
        """Two leeches on one seed end up serving each other (the
        have-broadcast + request path between non-seeds). The seed
        accepts only ONE peer, so the second leech can complete ONLY
        through the first — trading is structural, not a race."""
        import os

        async def go():
            server, m, payload, seed_dir = await self._swarm(tmp_path)
            c_seed = Client(
                ClientConfig(
                    port=0, enable_upnp=False,
                    torrent=TorrentConfig(max_peers=1, choke_interval=0.15),
                )
            )
            c_l1 = Client(ClientConfig(port=0, enable_upnp=False))
            c_l2 = Client(ClientConfig(port=0, enable_upnp=False))
            for c in (c_seed, c_l1, c_l2):
                await c.start()
            try:
                await c_seed.add(m, seed_dir)
                d1, d2 = str(tmp_path / "l1"), str(tmp_path / "l2")
                os.makedirs(d1)
                os.makedirs(d2)
                t1 = await c_l1.add(m, d1)
                t2 = await c_l2.add(m, d2)
                for _ in range(1600):
                    if t1.bitfield.complete and t2.bitfield.complete:
                        break
                    await asyncio.sleep(0.05)
                assert t1.bitfield.complete and t2.bitfield.complete, (
                    t1.status(), t2.status(),
                )
                for d in (d1, d2):
                    got = open(os.path.join(d, "resil.bin"), "rb").read()
                    assert got == payload
                # the seed served exactly one leech; the other's bytes
                # came peer-to-peer, so SOME leech upload must exist
                assert t1.uploaded + t2.uploaded > 0
            finally:
                await c_seed.close()
                await c_l1.close()
                await c_l2.close()
                server.close()

        run(go(), timeout=120)


class TestPauseResume:
    def test_pause_mid_transfer_then_resume_completes(self, tmp_path):
        """Pause stops all transfer (both directions, connections kept);
        resume finishes the download."""
        import os

        async def go():
            server, m, payload, seed_dir = await TestSwarmResilience()._swarm(
                tmp_path
            )
            c_seed = Client(ClientConfig(port=0, enable_upnp=False))
            c_leech = Client(ClientConfig(port=0, enable_upnp=False))
            await c_seed.start()
            await c_leech.start()
            try:
                await c_seed.add(m, seed_dir)
                d = str(tmp_path / "pl")
                os.makedirs(d)
                t = await c_leech.add(m, d)
                for _ in range(600):
                    if t.bitfield.count() >= 3:
                        break
                    await asyncio.sleep(0.02)
                await t.pause()
                assert t.status()["paused"]
                assert not any(p.inflight for p in t.peers.values())
                frozen = t.bitfield.count()
                await asyncio.sleep(0.8)  # several choke intervals
                assert t.bitfield.count() == frozen  # nothing moved
                assert t.peers  # connections survived the pause
                await t.resume()
                for _ in range(800):
                    if t.bitfield.complete:
                        break
                    await asyncio.sleep(0.05)
                assert t.bitfield.complete, t.status()
                got = open(os.path.join(d, "resil.bin"), "rb").read()
                assert got == payload
            finally:
                await c_seed.close()
                await c_leech.close()
                server.close()

        run(go(), timeout=90)

    def test_paused_serve_ignores_requests(self):
        async def go():
            t, payload = TestSchedulerUnits().make_torrent()
            await asyncio.to_thread(t.storage.set, 0, payload)
            for i in range(t.info.num_pieces):
                t.bitfield.set(i)
            p = PeerConnection(
                peer_id=b"G" * 20, reader=object(), writer=_FakeWriter(),
                num_pieces=t.info.num_pieces,
            )
            p.am_choking = False
            t.peers[p.peer_id] = p
            await t.pause()
            n = len(p.writer.data)
            await t._serve_request(p, 0, 0, BLOCK_SIZE)
            assert len(p.writer.data) == n  # no piece went out

        run(go())


class TestClientPauseAll:
    def test_pause_all_and_resume_all(self, tmp_path):
        async def go():
            import os

            server, m, payload, seed_dir = await TestSwarmResilience()._swarm(
                tmp_path
            )
            c = Client(ClientConfig(port=0, enable_upnp=False))
            await c.start()
            try:
                t = await c.add(m, seed_dir)
                await c.pause_all()
                assert t.paused
                await c.resume_all()
                assert not t.paused
            finally:
                await c.close()
                server.close()

        run(go())


class TestIdleSweep:
    def test_idle_peer_dropped_by_sweep_not_per_message_timer(self):
        """Dead-peer protection moved from a per-message wait_for (one
        timer handle per 16 KiB block — a measured top-5 event-loop cost
        at full rate) to one idle sweep per torrent: a connected peer
        whose last_rx goes stale is closed by the sweep and torn down by
        the ordinary drop path, while an active peer survives."""

        async def go():
            rng = np.random.default_rng(91)
            payload = rng.integers(0, 256, size=120_000, dtype=np.uint8).tobytes()
            server, pump = await run_tracker(
                ServeOptions(http_port=0, udp_port=None, interval=1)
            )
            url = f"http://127.0.0.1:{server.http_port}/announce"
            m = parse_metainfo(build_torrent_bytes(payload, 32768, url.encode()))
            seed = Client(ClientConfig())
            leech = Client(ClientConfig())
            # sweep interval floors at 1 s (peer_timeout/4 would be
            # 0.5 s) → worst-case drop ~3 s here; keepalives are far
            # apart so nothing refreshes last_rx once the swarm idles
            seed.config.torrent = fast_config(peer_timeout=2.0, keepalive_interval=300.0)
            leech.config.torrent = fast_config(peer_timeout=2.0, keepalive_interval=300.0)
            await seed.start()
            await leech.start()
            try:
                ss = Storage(MemoryStorage(), m.info)
                ss.set(0, payload)
                t_seed = await seed.add(m, ss)
                t = await leech.add(m, Storage(MemoryStorage(), m.info))
                await asyncio.wait_for(t.on_complete.wait(), timeout=30)
                assert len(t.peers) >= 1
                # freeze every peer's clock into the stale past; both
                # sides' sweeps must close + drop within ~1.25x timeout
                import time as _time

                for p in list(t.peers.values()):
                    p.last_rx = _time.monotonic() - 10.0
                for _ in range(100):
                    if not t.peers:
                        break
                    await asyncio.sleep(0.1)
                assert not t.peers, "idle peer not dropped by the sweep"
            finally:
                await seed.close()
                await leech.close()
                server.close()
                await asyncio.wait_for(pump, 5)

        run(go())


class TestPeerSlotRecycling:
    def test_full_peer_list_rotates_instead_of_starving(self, tmp_path):
        """A swarm larger than max_peers must rotate through the slots.

        Found by a 4x-scale soak (80 disjoint-selection leeches against
        one seed): with max_peers=50 the first 50 leeches finished their
        files, went NotInterested, and sat on their slots forever; the
        other 30 were refused on every redial and the swarm plateaued at
        exactly 50 leeches' worth of pieces. add_peer now recycles the
        slot of a mutually-uninterested idle peer (past evict_grace)
        for a fresh connection. Miniature here: max_peers=2, three
        leeches each selecting a disjoint file — the third can only
        ever complete through an eviction."""

        async def go():
            import os

            rng = np.random.default_rng(77)
            plen = 16384
            per_file = 4 * plen  # 4 pieces per file
            payload = rng.integers(
                0, 256, size=3 * per_file, dtype=np.uint8
            ).tobytes()
            digs = b"".join(
                hashlib.sha1(payload[i : i + plen]).digest()
                for i in range(0, len(payload), plen)
            )
            server, pump = await run_tracker(
                ServeOptions(http_port=0, udp_port=None, interval=1)
            )
            meta = bencode(
                {
                    b"announce": b"http://127.0.0.1:%d/announce"
                    % server.http_port,
                    b"info": {
                        b"name": b"rotate",
                        b"piece length": plen,
                        b"pieces": digs,
                        b"files": [
                            {b"length": per_file, b"path": [b"f%d.bin" % i]}
                            for i in range(3)
                        ],
                    },
                }
            )
            m = parse_metainfo(meta)
            sd = str(tmp_path / "seed")
            os.makedirs(os.path.join(sd, "rotate"))
            for i in range(3):
                open(os.path.join(sd, "rotate", "f%d.bin" % i), "wb").write(
                    payload[i * per_file : (i + 1) * per_file]
                )
            cfg = dict(max_peers=2, evict_grace=0.3, peer_timeout=60.0)
            seed = Client(ClientConfig(port=0, enable_upnp=False, resume=False))
            seed.config.torrent = fast_config(**cfg)
            leeches = [
                Client(ClientConfig(port=0, enable_upnp=False, resume=False))
                for _ in range(3)
            ]
            for c in leeches:
                c.config.torrent = fast_config(**cfg)
            await seed.start()
            for c in leeches:
                await c.start()
            try:
                t_seed = await seed.add(m, sd)
                tls = []
                for i, c in enumerate(leeches):
                    d = str(tmp_path / f"l{i}")
                    os.makedirs(d)
                    t = await c.add(m, d)
                    await t.select_files([i])
                    tls.append(t)
                for _ in range(600):  # 60 s budget
                    if all(t.status()["wanted_left"] == 0 for t in tls):
                        break
                    await asyncio.sleep(0.1)
                assert all(
                    t.status()["wanted_left"] == 0 for t in tls
                ), [t.status()["wanted_left"] for t in tls]
                # the cap itself held the whole time
                assert len(t_seed.peers) <= 2
                for i in range(3):
                    got = open(
                        str(tmp_path / f"l{i}" / "rotate" / f"f{i}.bin"), "rb"
                    ).read()
                    assert got == payload[i * per_file : (i + 1) * per_file]
            finally:
                await seed.close()
                for c in leeches:
                    await c.close()
                server.close()
                pump.cancel()

        run(go(), timeout=90)
