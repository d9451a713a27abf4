"""Storage + piece math tests.

Covers the reference's storage_test.ts territory — single-file,
within-one-file, and across-file-boundary reads/writes (storage_test.ts:
142-335) — plus the new read_batch path and last-piece geometry.
"""

import numpy as np
import pytest

from torrent_tpu.codec.metainfo import FileEntry, InfoDict
from torrent_tpu.storage.piece import (
    BLOCK_SIZE,
    block_length,
    num_blocks,
    piece_length,
    validate_received_block,
    validate_requested_block,
)
from torrent_tpu.storage.storage import (
    FsStorage,
    MemoryStorage,
    Storage,
    StorageError,
)


def make_info(length, piece_len, files=None, name="t"):
    n = (length + piece_len - 1) // piece_len
    return InfoDict(
        name=name,
        piece_length=piece_len,
        pieces=tuple(bytes([i % 256]) * 20 for i in range(n)),
        length=length,
        files=files,
    )


class TestPieceMath:
    def test_piece_length_even_division(self):
        # length % piece_length == 0 edge (piece.ts:16-19 || fallback)
        info = make_info(4 * BLOCK_SIZE, 2 * BLOCK_SIZE)
        assert piece_length(info, 0) == 2 * BLOCK_SIZE
        assert piece_length(info, 1) == 2 * BLOCK_SIZE

    def test_piece_length_short_last(self):
        info = make_info(5 * BLOCK_SIZE + 7, 2 * BLOCK_SIZE)
        assert info.num_pieces == 3
        assert piece_length(info, 2) == BLOCK_SIZE + 7

    def test_piece_length_out_of_range(self):
        info = make_info(100, 50)
        with pytest.raises(IndexError):
            piece_length(info, 2)
        with pytest.raises(IndexError):
            piece_length(info, -1)

    def test_num_blocks_and_block_length(self):
        info = make_info(3 * BLOCK_SIZE + 100, 2 * BLOCK_SIZE)
        assert num_blocks(info, 0) == 2
        assert num_blocks(info, 1) == 2  # BLOCK_SIZE + 100 → 2 blocks
        assert block_length(info, 1, BLOCK_SIZE) == 100

    def test_validate_requested_block(self):
        info = make_info(2 * BLOCK_SIZE + 100, 2 * BLOCK_SIZE)
        assert validate_requested_block(info, 0, 0, BLOCK_SIZE)
        assert validate_requested_block(info, 0, 100, 200)
        assert validate_requested_block(info, 1, 0, 100)
        assert not validate_requested_block(info, 1, 0, 101)  # past last piece
        assert not validate_requested_block(info, 0, 0, BLOCK_SIZE + 1)  # > cap
        assert not validate_requested_block(info, 0, 0, 0)
        assert not validate_requested_block(info, 2, 0, 10)  # bad index
        assert not validate_requested_block(info, 0, -1, 10)

    def test_validate_received_block(self):
        info = make_info(2 * BLOCK_SIZE + 100, 2 * BLOCK_SIZE)
        assert validate_received_block(info, 0, 0, BLOCK_SIZE)
        assert validate_received_block(info, 0, BLOCK_SIZE, BLOCK_SIZE)
        assert validate_received_block(info, 1, 0, 100)  # final short block
        assert not validate_received_block(info, 1, 0, BLOCK_SIZE)
        assert not validate_received_block(info, 0, 1, BLOCK_SIZE)  # unaligned
        assert not validate_received_block(info, 0, 2 * BLOCK_SIZE, 1)  # past end


def multi_info():
    # Three files; 100 KiB pieces deliberately span the file boundaries.
    files = (
        FileEntry(length=150_000, path=("a.bin",)),
        FileEntry(length=50_000, path=("sub", "b.bin")),
        FileEntry(length=123_456, path=("c.bin",)),
    )
    total = sum(f.length for f in files)
    return make_info(total, 102_400, files=files, name="multi")


class TestStorageMapping:
    def test_single_file_fanout(self):
        info = make_info(100_000, 16384)
        st = Storage(MemoryStorage(), info)
        segs = list(st.segments(5, 1000))
        assert segs == [(("t",), 5, 1000)]

    def test_boundary_spanning_read_write(self):
        info = multi_info()
        st = Storage(MemoryStorage(), info)
        # Piece 1 covers [102400, 204800): spans a.bin end(150000),
        # all of b.bin (150000-200000), into c.bin.
        segs = list(st.segments(102_400, 102_400))
        assert segs == [
            (("multi", "a.bin"), 102_400, 47_600),
            (("multi", "sub", "b.bin"), 0, 50_000),
            (("multi", "c.bin"), 0, 4_800),
        ]
        data = bytes(range(256)) * 400  # 102_400 bytes
        st.set(102_400, data)
        assert st.get(102_400, 102_400) == data

    def test_zero_length_file_skipped(self):
        files = (
            FileEntry(length=100, path=("a",)),
            FileEntry(length=0, path=("empty",)),
            FileEntry(length=100, path=("b",)),
        )
        info = make_info(200, 128, files=files)
        st = Storage(MemoryStorage(), info)
        segs = list(st.segments(50, 100))
        assert segs == [(("t", "a"), 50, 50), (("t", "b"), 0, 50)]

    def test_out_of_range_raises(self):
        info = make_info(1000, 512)
        st = Storage(MemoryStorage(), info)
        with pytest.raises(StorageError):
            list(st.segments(900, 200))
        with pytest.raises(StorageError):
            list(st.segments(-1, 10))

    def test_duplicate_block_suppressed(self):
        info = make_info(BLOCK_SIZE * 2, BLOCK_SIZE * 2)
        st = Storage(MemoryStorage(), info)
        assert st.set(0, b"x" * BLOCK_SIZE) is True
        assert st.set(0, b"y" * BLOCK_SIZE) is False
        assert st.get(0, 1) == b"x"

    def test_mark_pieces_written(self):
        info = make_info(BLOCK_SIZE * 4, BLOCK_SIZE * 2)
        st = Storage(MemoryStorage(), info)
        st.mark_pieces_written([1])
        assert st.set(2 * BLOCK_SIZE, b"z" * BLOCK_SIZE) is False
        assert st.set(0, b"z" * BLOCK_SIZE) is True

    def test_exists(self):
        info = multi_info()
        m = MemoryStorage()
        st = Storage(m, info)
        assert not st.exists()
        for f in info.files:
            m.set(("multi", *f.path), 0, b"\x01" * f.length)
        assert st.exists()


class TestReadBatch:
    def test_values_and_lengths(self):
        info = multi_info()
        st = Storage(MemoryStorage(), info)
        rng = np.random.default_rng(0)
        payload = rng.integers(0, 256, size=info.length, dtype=np.uint8).tobytes()
        # write via global offsets in big chunks
        for off in range(0, info.length, 65536):
            chunk = payload[off : off + 65536]
            for path, foff, clen in st.segments(off, len(chunk)):
                pass
            st.set(off, chunk)
        buf, lengths = st.read_batch(range(info.num_pieces))
        assert buf.shape == (info.num_pieces, info.piece_length)
        for i in range(info.num_pieces):
            plen = piece_length(info, i)
            assert lengths[i] == plen
            expect = payload[i * info.piece_length : i * info.piece_length + plen]
            assert buf[i, :plen].tobytes() == expect
            assert not buf[i, plen:].any()

    def test_missing_file_zero_fills(self):
        info = multi_info()
        st = Storage(MemoryStorage(), info)  # nothing written
        buf, lengths = st.read_batch([0, 1])
        assert not buf.any()
        assert lengths.tolist() == [102_400, 102_400]

    def test_out_buffer_reuse(self):
        info = make_info(1024, 256)
        st = Storage(MemoryStorage(), info)
        st.set(0, b"\xff" * 1024)
        out = np.ones((2, 256), dtype=np.uint8)
        buf, _ = st.read_batch([0, 3], out=out)
        assert buf is out
        assert (buf == 0xFF).all()
        with pytest.raises(StorageError):
            st.read_batch([0], out=np.zeros((2, 2), dtype=np.uint8))


class TestFsStorage:
    def test_roundtrip_and_dirs(self, tmp_path):
        fs = FsStorage(tmp_path)
        fs.set(("d", "sub", "f.bin"), 100, b"hello")
        assert (tmp_path / "d" / "sub" / "f.bin").exists()
        assert fs.get(("d", "sub", "f.bin"), 100, 5) == b"hello"
        # sparse region before offset reads as zeros
        assert fs.get(("d", "sub", "f.bin"), 0, 4) == b"\x00" * 4
        fs.close()

    def test_short_read_raises(self, tmp_path):
        fs = FsStorage(tmp_path)
        fs.set(("f",), 0, b"abc")
        with pytest.raises(StorageError):
            fs.get(("f",), 0, 10)
        fs.close()

    def test_missing_file(self, tmp_path):
        fs = FsStorage(tmp_path)
        with pytest.raises(StorageError):
            fs.get(("nope",), 0, 1)
        assert not fs.exists(("nope",))

    def test_exists_with_length(self, tmp_path):
        fs = FsStorage(tmp_path)
        fs.set(("f",), 0, b"abcd")
        assert fs.exists(("f",), 4)
        assert not fs.exists(("f",), 5)

    def test_unsafe_paths_rejected(self, tmp_path):
        fs = FsStorage(tmp_path)
        for bad in [("..", "evil"), ("a/b",), ("",), (".",)]:
            with pytest.raises(StorageError):
                fs.set(bad, 0, b"x")

    def test_overwrite_does_not_truncate(self, tmp_path):
        fs = FsStorage(tmp_path)
        fs.set(("f",), 0, b"A" * 100)
        fs.set(("f",), 10, b"B" * 5)
        assert fs.get(("f",), 0, 100) == b"A" * 10 + b"B" * 5 + b"A" * 85
        fs.close()

    def test_end_to_end_with_storage_facade(self, tmp_path):
        info = multi_info()
        st = Storage(FsStorage(tmp_path), info)
        data = bytes([i % 251 for i in range(info.length)])
        for off in range(0, info.length, 102_400):
            st.set(off, data[off : off + 102_400])
        buf, lengths = st.read_batch(range(info.num_pieces))
        flat = b"".join(
            buf[i, : lengths[i]].tobytes() for i in range(info.num_pieces)
        )
        assert flat == data


class TestReviewRegressions:
    """Regressions for the round-1 code-review findings."""

    def test_failed_write_does_not_poison_dedup(self):
        info = make_info(BLOCK_SIZE, BLOCK_SIZE)

        class FlakyMethod(MemoryStorage):
            fail = True

            def set(self, path, offset, data):
                if self.fail:
                    self.fail = False
                    raise StorageError("disk full")
                super().set(path, offset, data)

        st = Storage(FlakyMethod(), info)
        with pytest.raises(StorageError):
            st.set(0, b"x" * BLOCK_SIZE)
        # retry after failure must actually write
        assert st.set(0, b"x" * BLOCK_SIZE) is True
        assert st.get(0, 1) == b"x"

    def test_fsstorage_oserror_wrapped(self, tmp_path):
        fs = FsStorage(tmp_path)
        fs.set(("f",), 0, b"abc")
        f = fs._open_read(("f",))
        f.close()  # force ValueError/OSError on next pread via stale handle
        # cache notices closed handle and reopens — so instead check set():
        import os

        target = tmp_path / "dir"
        target.write_text("not a dir")
        with pytest.raises(StorageError):
            fs.set(("dir", "sub"), 0, b"x")  # makedirs over a file → OSError

    def test_zero_length_torrent_with_pieces_rejected(self):
        from torrent_tpu.codec.bencode import bencode
        from torrent_tpu.codec.metainfo import parse_metainfo

        info = {
            b"name": b"t",
            b"piece length": 16384,
            b"pieces": b"\x00" * 40,
            b"length": 0,
        }
        assert parse_metainfo(bencode({b"announce": b"http://t", b"info": info})) is None


class TestBep47PadFiles:
    """BEP 47 padding files: virtual zero spans that occupy piece space
    but never touch disk (hybrid torrents always carry them)."""

    def _meta(self, plen=32768):
        import hashlib

        from torrent_tpu.codec.bencode import bencode
        from torrent_tpu.codec.metainfo import parse_metainfo

        # file a (plen+100 bytes) + pad to the piece boundary + file b
        a = bytes(range(256)) * ((plen + 100) // 256 + 1)
        a = a[: plen + 100]
        pad = plen - 100
        b = b"B" * (plen // 2)
        payload = a + b"\x00" * pad + b
        digs = [
            hashlib.sha1(payload[i : i + plen]).digest()
            for i in range(0, len(payload), plen)
        ]
        meta = bencode(
            {
                b"announce": b"http://t/announce",
                b"info": {
                    b"name": b"padded",
                    b"piece length": plen,
                    b"pieces": b"".join(digs),
                    b"files": [
                        {b"length": len(a), b"path": [b"a.bin"]},
                        {
                            b"length": pad,
                            b"path": [b".pad", str(pad).encode()],
                            b"attr": b"p",
                        },
                        {b"length": len(b), b"path": [b"b.bin"]},
                    ],
                },
            }
        )
        return parse_metainfo(meta), a, b, payload

    def test_parser_marks_pad_entries(self):
        m, a, b, _ = self._meta()
        assert [f.pad for f in m.info.files] == [False, True, False]
        assert m.info.length == len(a) + (32768 - 100) + len(b)

    def test_reads_zero_fill_and_writes_skip_pads(self, tmp_path):
        import os

        m, a, b, payload = self._meta()
        st = Storage(FsStorage(str(tmp_path)), m.info)
        # write the whole payload through the piece-space API
        for off in range(0, len(payload), 16384):
            st.set(off, payload[off : off + 16384])
        # no pad file/dir was created
        assert not os.path.exists(os.path.join(str(tmp_path), "padded", ".pad"))
        assert os.path.exists(os.path.join(str(tmp_path), "padded", "a.bin"))
        assert os.path.exists(os.path.join(str(tmp_path), "padded", "b.bin"))
        # reading back crosses the pad span and yields its zeros
        assert st.get(0, len(payload)) == payload
        # files on disk hold exactly the real bytes
        assert open(os.path.join(str(tmp_path), "padded", "a.bin"), "rb").read() == a
        assert open(os.path.join(str(tmp_path), "padded", "b.bin"), "rb").read() == b

    def test_verify_passes_without_pad_files_on_disk(self, tmp_path):
        """Seeding a padded torrent from a directory that has only the
        real files (e.g. downloaded by a client that skips pads) must
        verify clean — pad ranges read as zeros."""
        import os

        from torrent_tpu.parallel.verify import verify_pieces

        m, a, b, _ = self._meta()
        os.makedirs(os.path.join(str(tmp_path), "padded"))
        open(os.path.join(str(tmp_path), "padded", "a.bin"), "wb").write(a)
        open(os.path.join(str(tmp_path), "padded", "b.bin"), "wb").write(b)
        st = Storage(FsStorage(str(tmp_path)), m.info)
        bf = verify_pieces(st, m.info, hasher="cpu")
        assert bf.all(), bf
        assert st.exists()  # pads don't block the resume precondition

    def test_read_batch_zero_fills_pads(self, tmp_path):
        import os

        import numpy as np

        m, a, b, payload = self._meta()
        os.makedirs(os.path.join(str(tmp_path), "padded"))
        open(os.path.join(str(tmp_path), "padded", "a.bin"), "wb").write(a)
        open(os.path.join(str(tmp_path), "padded", "b.bin"), "wb").write(b)
        st = Storage(FsStorage(str(tmp_path)), m.info)
        buf, lengths = st.read_batch(range(m.info.num_pieces))
        for i in range(m.info.num_pieces):
            want = payload[i * 32768 : i * 32768 + int(lengths[i])]
            assert buf[i, : int(lengths[i])].tobytes() == want, f"piece {i}"

    def test_padded_torrent_swarm_e2e(self, tmp_path):
        """Two clients transfer a BEP 47 padded torrent: the leech
        completes, real files round-trip, and no .pad artifacts appear."""
        import asyncio
        import os

        from tests.test_session import run
        from torrent_tpu.server.in_memory import run_tracker
        from torrent_tpu.server.tracker import ServeOptions
        from torrent_tpu.session.client import Client, ClientConfig

        async def go():
            server, _ = await run_tracker(
                ServeOptions(http_port=0, udp_port=None, interval=1)
            )
            m, a, b, _ = self._meta()
            # rewrite announce to the live tracker
            import hashlib

            from torrent_tpu.codec.bencode import bencode, bdecode
            from torrent_tpu.codec.metainfo import parse_metainfo

            raw = dict(m.raw)
            raw[b"announce"] = (
                b"http://127.0.0.1:%d/announce" % server.http_port
            )
            m = parse_metainfo(bencode(raw))
            sd, ld = str(tmp_path / "es"), str(tmp_path / "el")
            os.makedirs(os.path.join(sd, "padded"))
            os.makedirs(ld)
            open(os.path.join(sd, "padded", "a.bin"), "wb").write(a)
            open(os.path.join(sd, "padded", "b.bin"), "wb").write(b)
            c1 = Client(ClientConfig(port=0, enable_upnp=False))
            c2 = Client(ClientConfig(port=0, enable_upnp=False))
            await c1.start()
            await c2.start()
            try:
                t1 = await c1.add(m, sd)
                assert t1.bitfield.complete, "seed recheck failed without pads"
                t2 = await c2.add(m, ld)
                for _ in range(600):
                    if t2.bitfield.complete:
                        break
                    await asyncio.sleep(0.05)
                assert t2.bitfield.complete, t2.status()
                assert open(os.path.join(ld, "padded", "a.bin"), "rb").read() == a
                assert open(os.path.join(ld, "padded", "b.bin"), "rb").read() == b
                assert not os.path.exists(os.path.join(ld, "padded", ".pad"))
            finally:
                await c1.close()
                await c2.close()
                server.close()

        run(go(), timeout=60)

    def test_pad_entries_never_drive_wanting(self):
        """Deselecting every real file leaves nothing wanted — the pad
        entry must not hold its boundary piece at default priority."""
        import asyncio

        from tests.test_session import fast_config, run
        from torrent_tpu.session.client import generate_peer_id
        from torrent_tpu.session.torrent import Torrent
        from torrent_tpu.storage.storage import MemoryStorage

        async def go():
            m, a, b, _ = self._meta()
            t = Torrent(
                metainfo=m,
                storage=Storage(MemoryStorage(), m.info),
                peer_id=generate_peer_id(),
                port=1234,
                config=fast_config(),
            )
            await t.select_files([])  # nothing wanted
            assert t.status()["wanted_left"] == 0, t._piece_priority
            # selecting only b.bin wants exactly its piece
            await t.select_files([2])
            assert t.status()["wanted_left"] == 1

        run(go())


class TestLeafWindowing:
    def test_windowed_reduction_matches_unwindowed(self):
        """roots_batched_windowed with a tiny window (forcing many
        flushes) matches the single-pass result bit-exactly."""
        import numpy as np

        from torrent_tpu.models.v2 import (
            _leaf_words_cpu,
            roots_batched,
            roots_batched_windowed,
        )

        rng = np.random.default_rng(44)
        plen = 32768
        blobs = [
            rng.integers(0, 256, s, dtype=np.uint8).tobytes()
            for s in (5000, 3 * plen, plen, 2 * plen + 9, 100)
        ]
        entries = [(len(x), _leaf_words_cpu(x)) for x in blobs]
        whole = roots_batched(entries, plen)
        windowed = roots_batched_windowed(iter(entries), plen, window=2)
        assert windowed == whole


# ---------------------------------------------------------------- a verified piece is one write

PIECE = 16 * BLOCK_SIZE  # a 256 KiB piece: sixteen blocks

# piece 1 of each layout: inside one file; across two files; its own file's
# last 100 bytes, then a BEP 47 pad span to the piece's end. With it the
# method.set calls it takes whole, and block by block
LAYOUTS = {
    "one_file": (None, 1, 16),
    "two_files": ((FileEntry(length=PIECE + 7_000, path=("a.bin",)), FileEntry(length=2 * PIECE, path=("b.bin",))), 2, 17),
    "pad_tail": (
        (
            FileEntry(length=PIECE + 100, path=("a.bin",)),
            FileEntry(length=PIECE - 100, path=(".pad", str(PIECE - 100)), pad=True),
            FileEntry(length=PIECE, path=("b.bin",)),
        ),
        1,
        1,
    ),
}


class CountingFs(FsStorage):
    """An ``FsStorage`` that lists every ``set``: (path, offset, length)."""

    def __init__(self, root):
        super().__init__(root)
        self.sets = []

    def set(self, path, offset, data):
        self.sets.append((path, offset, len(data)))
        super().set(path, offset, data)


def layout(name):
    files, segments, block_sets = LAYOUTS[name]
    length = 3 * PIECE if files is None else sum(f.length for f in files)
    return make_info(length, PIECE, files=files), segments, block_sets


def tree(root):
    """Every file under ``root``: its path relative to it → its bytes."""
    import pathlib

    root = pathlib.Path(root)
    return {str(p.relative_to(root)): p.read_bytes() for p in root.rglob("*") if p.is_file()}


def piece_bytes(seed=41, n=PIECE):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8).tobytes()


class TestWritePiece:
    @pytest.mark.parametrize("name", sorted(LAYOUTS))
    def test_one_write_a_segment_lays_down_the_sixteen_blocks_bytes(self, tmp_path, name):
        info, segments, block_sets = layout(name)
        data = piece_bytes()
        whole, blocks = CountingFs(tmp_path / "whole"), CountingFs(tmp_path / "blocks")
        assert Storage(whole, info).write_piece(PIECE, data) is True
        st = Storage(blocks, info)
        for off in range(0, PIECE, BLOCK_SIZE):
            st.set(PIECE + off, data[off : off + BLOCK_SIZE])
        assert (len(whole.sets), len(blocks.sets)) == (segments, block_sets)
        assert sum(n for _, _, n in whole.sets) == sum(n for _, _, n in blocks.sets)
        assert tree(tmp_path / "whole") == tree(tmp_path / "blocks") != {}
        assert not any(".pad" in p for p in tree(tmp_path / "whole"))  # the pad span never reaches disk
        assert Storage(whole, info).get(PIECE, PIECE) == (
            data if name != "pad_tail" else data[:100] + bytes(PIECE - 100)
        )
        whole.close()
        blocks.close()

    def test_a_written_piece_is_written_once_until_it_is_unmarked(self):
        info, *_ = layout("one_file")
        m = MemoryStorage()
        st = Storage(m, info)
        first, second = piece_bytes(1), piece_bytes(2)
        assert st.write_piece(PIECE, first) is True
        assert st._written == set(range(PIECE, 2 * PIECE, BLOCK_SIZE))  # as after sixteen set() calls
        assert st.write_piece(PIECE, second) is False
        assert st.set(PIECE + BLOCK_SIZE, second[:BLOCK_SIZE]) is False  # set() honours the marks too
        assert st.get(PIECE, PIECE) == first
        st.unmark_piece_written(1)
        assert st.write_piece(PIECE, second) is True
        assert st.get(PIECE, PIECE) == second

    def test_a_partly_written_piece_writes_its_unmarked_blocks_only(self):
        info, *_ = layout("one_file")
        st = Storage(MemoryStorage(), info)
        old, new = piece_bytes(1), piece_bytes(2)
        assert st.set(PIECE + 3 * BLOCK_SIZE, old[3 * BLOCK_SIZE : 4 * BLOCK_SIZE]) is True
        assert st.write_piece(PIECE, new) is True
        got = st.get(PIECE, PIECE)
        # block for block what sixteen set() calls leave: the marked block keeps its bytes
        assert got == new[: 3 * BLOCK_SIZE] + old[3 * BLOCK_SIZE : 4 * BLOCK_SIZE] + new[4 * BLOCK_SIZE :]
        assert st._written == set(range(PIECE, 2 * PIECE, BLOCK_SIZE))

    def test_a_failing_write_leaves_no_block_marked(self):
        info, *_ = layout("two_files")

        class FailsOnB(MemoryStorage):
            fail = True

            def set(self, path, offset, data):
                if self.fail and path[-1] == "b.bin":
                    self.fail = False
                    raise StorageError("disk full")
                super().set(path, offset, data)

        st = Storage(FailsOnB(), info)
        data = piece_bytes()
        with pytest.raises(StorageError):
            st.write_piece(PIECE, data)  # a.bin's segment went down, b.bin's failed
        assert st._written == set()
        assert st.write_piece(PIECE, data) is True  # the retry reaches the disk
        assert st.get(PIECE, PIECE) == data


class TestFsWriteHandles:
    def test_a_file_is_opened_once_for_every_write(self, tmp_path):
        from torrent_tpu.storage.storage import storage_write_stats

        info, *_ = layout("one_file")
        fs = FsStorage(tmp_path)
        st = Storage(fs, info)
        before = storage_write_stats()
        for i in range(3):
            assert st.write_piece(i * PIECE, piece_bytes(i)) is True
        fs.set(("t",), 5, b"x")
        after = storage_write_stats()
        assert after["write_opens"] - before["write_opens"] == 1
        assert after["write_reuses"] - before["write_reuses"] == 3
        assert len(fs._writers) == 1
        fs.close()

    def test_routing_to_the_parts_mirror_and_back_drops_the_kept_handles(self, tmp_path):
        import os
        import pathlib

        fs = FsStorage(tmp_path)
        path = ("t", "a.bin")
        fs.set_unwanted({path}, [path])
        fs.set(path, 0, b"A" * 10)
        mirror = fs._parts_abspath(path)
        real = os.path.join(tmp_path, *path)
        assert pathlib.Path(mirror).read_bytes() == b"A" * 10 and not os.path.exists(real)
        held = fs._writers[path]  # a writer in flight keeps its own reference
        fs.set_unwanted(set(), [path])  # wanted again: the mirror is promoted
        assert fs._writers == {} and not held.closed  # dropped, not closed
        os.pwrite(held.fileno(), b"B", 10)  # the in-flight write follows the renamed inode
        fs.set(path, 11, b"C" * 4)
        assert pathlib.Path(real).read_bytes() == b"A" * 10 + b"B" + b"C" * 4
        assert not os.path.exists(mirror)
        del held
        fs.close()

    def test_close_leaves_no_write_descriptor_open(self, tmp_path):
        import os

        if not os.path.isdir("/proc/self/fd"):
            pytest.skip("needs /proc/self/fd")
        before = len(os.listdir("/proc/self/fd"))
        fs = FsStorage(tmp_path)
        for name in ("a", "b", "c"):
            fs.set((name,), 0, b"x" * 100)
            fs.set((name,), 100, b"y" * 100)
        assert len(os.listdir("/proc/self/fd")) == before + 3
        fs.close()
        assert len(os.listdir("/proc/self/fd")) == before
        fs.set(("a",), 200, b"z")  # a closed backend opens again on demand
        assert (tmp_path / "a").read_bytes() == b"x" * 100 + b"y" * 100 + b"z"
        fs.close()

    def test_a_lost_piece_is_written_again_where_its_file_was_deleted(self, tmp_path):
        import os

        info, *_ = layout("one_file")
        fs = FsStorage(tmp_path)
        st = Storage(fs, info)
        data = piece_bytes()
        assert st.write_piece(0, data) is True
        os.remove(tmp_path / "t")  # gone from under the kept handle
        st.unmark_piece_written(0)  # the piece-loss path: the handles go with the marks
        assert fs._writers == {}
        assert st.write_piece(0, data) is True
        assert st.get(0, PIECE) == data
        fs.close()

    def test_threads_racing_on_every_piece_write_each_once_and_count_every_write(self, tmp_path):
        import sys
        import threading

        from torrent_tpu.storage.storage import storage_write_stats

        files = tuple(FileEntry(length=4 * PIECE + 1000 * i, path=(f"f{i}.bin",)) for i in range(3))
        info = make_info(sum(f.length for f in files), PIECE, files=files)  # pieces span files
        fs = FsStorage(tmp_path)
        st = Storage(fs, info)
        payload = piece_bytes(7, info.length)
        n = info.num_pieces
        wins = [0] * n
        lock = threading.Lock()

        def writer():
            for i in range(n):
                if st.write_piece(i * PIECE, payload[i * PIECE : (i + 1) * PIECE]):
                    with lock:
                        wins[i] += 1

        before = storage_write_stats()
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=writer) for _ in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(interval)
        after = storage_write_stats()
        assert wins == [1] * n  # no piece marked twice
        segments = sum(len(list(st.segments(i * PIECE, piece_length(info, i)))) for i in range(n))
        opens, reuses = (after[k] - before[k] for k in ("write_opens", "write_reuses"))
        assert (opens, opens + reuses) == (3, segments)  # one open a file; no write left uncounted
        assert st.get(0, info.length) == payload
        fs.close()

    def test_past_its_bound_the_oldest_write_handle_is_dropped(self, tmp_path, monkeypatch):
        monkeypatch.setattr(FsStorage, "WRITE_HANDLES_MAX", 2)
        fs = FsStorage(tmp_path)
        for name in ("a", "b", "c"):
            fs.set((name,), 0, name.encode() * 3)
        assert list(fs._writers) == [("b",), ("c",)]
        fs.set(("a",), 3, b"A")
        assert list(fs._writers) == [("c",), ("a",)]
        assert (tmp_path / "a").read_bytes() == b"aaaA"
        fs.close()
