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
import json

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


# ---------------------------------------------------------------- phases

_PHASE_WAITS = ("http_head", "http_body", "verdict_wake", "http_request")
_PHASE_STAGES = ("decode", "reply")


def _phase_counts() -> dict:
    """``(busy_s, ops, active, bytes)`` of every entry a request's phases
    touch in the process's ledger."""
    from torrent_tpu.obs import pipeline_ledger

    snap = pipeline_ledger().snapshot()
    out = {}
    for table, names in (("waits", _PHASE_WAITS), ("stages", _PHASE_STAGES)):
        for name in names:
            e = snap[table].get(name, {})
            out[name] = (e.get("busy_s", 0.0), e.get("ops", 0), e.get("active", 0), e.get("bytes", 0))
    return out


def bencode_req(pieces, expected=None) -> bytes:
    from torrent_tpu.codec.bencode import bencode

    req = {b"pieces": pieces}
    if expected is not None:
        req[b"expected"] = expected
    return bencode(req)


async def _raw(port: int, data: bytes) -> bytes:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(data)
    await writer.drain()
    out = await reader.read()
    writer.close()
    return out


class TestRequestPhases:
    """A buffered hash request's milliseconds by phase, from inside the
    bridge: head, body and wake are ledger waits recorded after the fact,
    decode and reply are stages (work on the loop thread), the whole is
    the wait ``http_request``; the scheduler exports the e2e family's
    sum and count; the request's trace tree shows the same intervals."""

    def test_one_verify_moves_every_phase_by_one(self):
        async def go():
            server = await _start("cpu")
            try:
                pieces = _mk_pieces(3, 4096)
                body = bencode_req(pieces, [hashlib.sha1(p).digest() for p in pieces])
                c0, s0 = _phase_counts(), server.sched.metrics_snapshot()
                status, resp = await _post_raw(server.port, "/v1/verify", {}, body)
                assert status == 200 and bdecode(resp)[b"ok"] == b"\x01\x01\x01"
                c1, s1 = _phase_counts(), server.sched.metrics_snapshot()
                return c0, c1, s0, s1, len(body), sum(len(p) for p in pieces)
            finally:
                server.close()
                await server.wait_closed()

        c0, c1, s0, s1, body_len, payload_len = run(go())
        d = {k: (c1[k][0] - c0[k][0], c1[k][1] - c0[k][1]) for k in c0}
        assert {k: ops for k, (_, ops) in d.items()} == dict.fromkeys(_PHASE_WAITS + _PHASE_STAGES, 1)
        assert all(c1[k][2] == 0 for k in c1)  # nothing left active
        assert s1["e2e_pieces"] - s0["e2e_pieces"] == 3
        # a piece's enqueue → verdict, a mean of three here; the request's is the same interval
        e2e = (s1["e2e_s_sum"] - s0["e2e_s_sum"]) / 3
        assert e2e > 0
        parts = sum(d[k][0] for k in ("http_head", "http_body", "decode", "verdict_wake", "reply")) + e2e
        assert parts <= d["http_request"][0]
        # bytes: the body's on the way in, the payload's on the way out
        assert c1["http_body"][3] - c0["http_body"][3] == body_len
        assert c1["decode"][3] - c0["decode"][3] == body_len
        assert c1["reply"][3] - c0["reply"][3] == payload_len

    @pytest.mark.parametrize(
        "case,status,moved",
        [
            ("refused", 429, ("http_head", "http_body", "decode", "reply", "http_request")),
            ("malformed", 400, ("http_head", "http_body", "decode", "reply", "http_request")),
            ("launch_failed", 500, _PHASE_WAITS + _PHASE_STAGES),
            # the two replies before the root span opens: counted too
            ("bad_request_line", 400, ("http_head", "reply", "http_request")),
            ("headers_too_large", 431, ("http_head", "reply", "http_request")),
            # a route without a body or a submit
            ("get", 200, ("http_head", "reply", "http_request")),
        ],
    )
    def test_every_reply_records_the_whole_and_leaves_nothing_active(self, case, status, moved):
        from torrent_tpu.bridge.service import BridgeServer

        async def go():
            kwargs = {}
            if case == "refused":
                kwargs["tenant_max_mb"] = 0  # every submission is over its tenant's budget
            if case == "launch_failed":
                kwargs["fault_plan"] = "payload=bdbdbdbd"
            server = await BridgeServer(port=0, hasher="cpu", **kwargs).start()
            try:
                c0 = _phase_counts()
                if case == "bad_request_line":
                    raw = await _raw(server.port, b"nonsense\r\n\r\n")
                elif case == "headers_too_large":
                    raw = await _raw(
                        server.port,
                        b"GET /v1/info HTTP/1.1\r\n" + b"".join(b"X-Pad-%d: %s\r\n" % (i, b"p" * 1000) for i in range(20)) + b"\r\n",
                    )
                elif case == "get":
                    raw = await _raw(server.port, b"GET /v1/info HTTP/1.1\r\nHost: x\r\n\r\n")
                else:
                    piece = (b"\xbd\xbd\xbd\xbd" if case == "launch_failed" else b"good") + b"x" * 60
                    body = b"not bencode" if case == "malformed" else bencode_req([piece])
                    got, _ = await _post_raw(server.port, "/v1/digests", {}, body)
                    raw = b"HTTP/1.1 %d X" % got
                return int(raw.split()[1]), c0, _phase_counts()
            finally:
                server.close()
                await server.wait_closed()

        got, c0, c1 = run(go())
        assert got == status
        assert {k for k in c0 if c1[k][1] - c0[k][1] == 1} == set(moved)
        assert all(c1[k][1] - c0[k][1] in (0, 1) for k in c0)
        assert all(c1[k][2] == 0 for k in c1)
        # only a verdict answers for payload bytes
        assert c1["reply"][3] == c0["reply"][3]

    def test_the_trace_tree_shows_the_phases_inside_their_parents(self):
        async def go():
            server = await _start("cpu")
            try:
                pieces = _mk_pieces(2, 4096)
                body = bencode_req(pieces, [hashlib.sha1(p).digest() for p in pieces])
                status, _ = await _post_raw(server.port, "/v1/verify", {"X-Trace-Id": "phases-0001"}, body)
                assert status == 200
                raw = await _raw(server.port, b"GET /v1/trace?id=phases-0001 HTTP/1.1\r\nHost: x\r\n\r\n")
                return json.loads(raw.split(b"\r\n\r\n", 1)[1]), len(body)
            finally:
                server.close()
                await server.wait_closed()

        tree, body_len = run(go())
        (root,) = tree["spans"]
        assert root["name"] == "bridge.request" and root["attrs"]["head_ms"] >= 0
        kids = {c["name"]: c for c in root["children"]}
        assert {"bridge.body", "bridge.decode", "sched.enqueue", "bridge.reply"} <= set(kids)
        order = ["bridge.body", "bridge.decode", "sched.enqueue", "bridge.reply"]
        starts = [kids[n]["start_ms"] for n in order]
        assert starts == sorted(starts)
        for name in ("bridge.body", "bridge.decode", "bridge.reply"):
            c = kids[name]
            assert c["start_ms"] >= root["start_ms"]
            # the tree rounds to the microsecond
            assert c["start_ms"] + c["duration_ms"] <= root["start_ms"] + root["duration_ms"] + 0.002, name
        assert kids["bridge.body"]["attrs"]["bytes"] == kids["bridge.decode"]["attrs"]["bytes"] == body_len
        assert kids["bridge.reply"]["attrs"]["status_code"] == 200
        sched = {c["name"]: c for c in kids["sched.enqueue"]["children"]}
        assert {"sched.lane_wait", "sched.launch", "sched.digest", "sched.wake"} <= set(sched)
        # the wake begins where the demux resolved the submission and
        # ends before the reply does
        assert sched["sched.wake"]["start_ms"] >= sched["sched.digest"]["start_ms"]
        assert sched["sched.wake"]["start_ms"] + sched["sched.wake"]["duration_ms"] <= kids["bridge.reply"]["start_ms"] + 0.002
