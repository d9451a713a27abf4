"""The failure classifier against a fake server that refuses, resets,
answers late and answers wrongly: each lands in its class, and a late
answer in none. Loads no TPU library."""

import asyncio
import sys

import pytest

from benchmark.harness import bencode, loadgen


async def _fake(reader, writer):
    line = await reader.readline()
    path = line.split()[1].decode()
    length = 0
    while True:
        h = await reader.readline()
        if h in (b"\r\n", b""):
            break
        if h.lower().startswith(b"content-length:"):
            length = int(h.split(b":")[1])
    body = await reader.readexactly(length)
    n = len(bencode.decode(body)[b"pieces"])
    if path == "/reset":
        writer.transport.abort()
        return
    if path == "/silent":
        await asyncio.sleep(5)
        writer.close()
        return
    if path == "/late":
        await asyncio.sleep(0.4)
    status = {"/429": 429, "/500": 500, "/503": 503}.get(path, 200)
    ok = bytes([1] * n)
    if path == "/wrong":
        ok = bytes([0] + [1] * (n - 1))
    if path == "/short":
        ok = ok[:-1]
    payload = bencode.encode({"ok": ok}) if status == 200 and path != "/garbage" else b"shed"
    writer.write(f"HTTP/1.1 {status} X\r\nContent-Length: {len(payload)}\r\nConnection: close\r\n\r\n".encode() + payload)
    await writer.drain()
    writer.close()


async def _ask(path, limit_s=2.0, n=3):
    server = await asyncio.start_server(_fake, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    body = bencode.encode({"pieces": [b"x" * 64] * n, "expected": [b"d" * 20] * n})
    try:
        status, reply, error = await loadgen.post("127.0.0.1", port, path, {"X-Tenant": "t"}, body, limit_s)
    finally:
        server.close()
        await server.wait_closed()
    verdicts, frame_failed, error = loadgen.parse_verify(status, reply, error)
    return loadgen.classify(status, error, verdicts, [True] * n, frame_failed)


@pytest.mark.parametrize(
    "path, classes",
    [
        ("/ok", [None, None, None]),
        ("/late", [None, None, None]),  # late is a sample of the tail, not a failure
        ("/429", [loadgen.REFUSED] * 3),
        ("/500", [loadgen.REFUSED] * 3),
        ("/503", [loadgen.REFUSED] * 3),
        ("/reset", [loadgen.TRANSPORT] * 3),
        ("/silent", [loadgen.TRANSPORT] * 3),  # the client's time limit
        ("/garbage", [loadgen.TRANSPORT] * 3),  # a 200 that cannot be read
        ("/wrong", [loadgen.WRONG, None, None]),
        ("/short", [None, None, loadgen.WRONG]),  # a 200 that leaves a verdict out
    ],
)
def test_each_failure_lands_in_its_class(path, classes):
    limit = 0.5 if path == "/silent" else 2.0
    assert asyncio.run(_ask(path, limit)) == classes


def test_connection_refused_is_transport():
    async def go():
        status, reply, error = await loadgen.post("127.0.0.1", 1, "/x", {}, b"", 1.0)
        return loadgen.classify(status, error, None, [True, False])

    assert asyncio.run(go()) == [loadgen.TRANSPORT] * 2


def test_stream_frame_failures_are_refused_and_counted_once():
    # the stream route reports failed frames as ok=0 plus a count
    got = loadgen.classify(200, None, [1, 0, 0, 1], [True, True, False, True], frame_failed=1)
    assert got == [None, loadgen.REFUSED, None, None]
    got = loadgen.classify(200, None, [1, 0, 0, 1], [True, True, False, True], frame_failed=0)
    assert got == [None, loadgen.WRONG, None, None]


def test_schedule_is_the_same_work_for_every_seed():
    a = loadgen.open_schedule(1, 100.0, 5.0)
    b = loadgen.open_schedule(2**31 + 5, 100.0, 5.0)
    assert len(a) == len(b) == 500
    assert a[-1] < 5.0 and b[-1] < 5.0 and a[0] == b[0] == 0.0
    gaps = lambda s: sorted(round(float(x), 9) for x in (s[1:] - s[:-1]))
    # the same multiset of gaps but for the one that wraps to the start
    assert sum(abs(x - y) for x, y in zip(gaps(a), gaps(b))) < 0.2
    assert list(a) != list(b)


def test_percentile_interpolates():
    assert loadgen.percentile([1, 2, 3, 4, 5], 50) == 3
    assert loadgen.percentile([10, 20], 95) == pytest.approx(19.5)


def test_traced_tails_read_only_requests_due_before_the_slice():
    from benchmark.harness import manifest
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    # completion order is not due order: a slow early request finishes after a quick later one
    report = {"latency_ms": [40.0, 50.0, 900.0, 45.0, 300.0], "latency_due_s": [0.1, 1.0, 0.2, 5.4, 5.6]}
    assert loadgen.latencies_due_before(report, 5.5) == [40.0, 50.0, 900.0, 45.0]
    obs = {"loadgen": report, "undisturbed_s": 5.5}
    assert manifest.load_reader(root, "verdict_p95_ms.obs").read(obs) == loadgen.percentile([40.0, 50.0, 900.0, 45.0], 95)
    assert manifest.load_reader(root, "verdict_p99_ms.obs").read(obs) == loadgen.percentile([40.0, 50.0, 900.0, 45.0], 99)
    assert manifest.load_reader(root, "verdict_p95_ms.obs").read(dict(obs, undisturbed_s=0.05)) is None


def test_imports_no_tpu_library():
    import subprocess

    code = "import sys; sys.path.insert(0, '.'); import benchmark.harness.loadgen, benchmark.harness.trace; print('jax' in sys.modules)"
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    out = subprocess.run([sys.executable, "-c", code], cwd=root, capture_output=True, text=True, timeout=60)
    assert out.stdout.strip() == "False", out.stderr
