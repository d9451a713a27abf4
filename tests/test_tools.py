"""make_torrent authoring + UPnP helpers + bridge service tests."""

import asyncio
import hashlib
import os

import numpy as np
import pytest

from torrent_tpu.codec.metainfo import parse_metainfo
from torrent_tpu.tools.make_torrent import (
    choose_piece_length,
    collect_files,
    make_torrent,
)


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def test_doctor_names_the_device_in_process(monkeypatch):
    """doctor holds the chip itself: the device check reads JAX in this
    process (platform, kind, count) and starts no probe subprocess."""
    import subprocess

    from torrent_tpu.tools import doctor

    def deny(*a, **k):
        raise AssertionError("device check spawned a process")

    monkeypatch.setattr(subprocess, "Popen", deny)
    doctor._RESULTS.clear()
    doctor._check_device()
    ((status, name, detail),) = doctor._RESULTS
    doctor._RESULTS.clear()
    assert name == "device"
    # CPU-only test host: degraded-but-working, named as JAX names it
    assert status == "WARN"
    assert "platform=cpu" in detail and "kind=cpu" in detail and "devices=8" in detail


def test_worker_env_gives_each_device_worker_its_own_chip():
    """Launchers of hashing workers never touch JAX; each child's
    environment, set before it imports JAX, decides what it may take:
    hashlib workers are pinned to the CPU platform, device workers see
    exactly one chip — their own — on the TPU platform alone (the chip
    host's ``tpu,cpu`` would let a worker without a chip hash on XLA:CPU
    under a record that says tpu), and the caller's env is not mutated."""
    from torrent_tpu.utils.device import worker_env

    base = {"PATH": "/bin", "JAX_PLATFORMS": "tpu,cpu"}
    cpu = worker_env(base, "cpu", 3)
    assert cpu["JAX_PLATFORMS"] == "cpu" and "TPU_VISIBLE_CHIPS" not in cpu
    envs = [worker_env(base, "tpu", i) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert e["JAX_PLATFORMS"] == "tpu" and e["PATH"] == "/bin"
    assert base == {"PATH": "/bin", "JAX_PLATFORMS": "tpu,cpu"}


def _fabric_launcher_with_fake_workers(monkeypatch, devices, returncodes):
    """.bench/measure_fabric.py with Popen replaced: worker p 'runs' at
    once, writes a result naming devices[p], exits returncodes[p]."""
    import importlib.util
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "measure_fabric", os.path.join(repo, ".bench", "measure_fabric.py")
    )
    mf = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mf)

    class FakeWorker:
        def __init__(self, argv, env, **_kw):
            p = int(argv[argv.index("--process-id") + 1])
            # handed its own chip, on the TPU platform alone
            assert env["JAX_PLATFORMS"] == "tpu"
            assert env["TPU_VISIBLE_CHIPS"] == str(p)
            with open(argv[argv.index("--result-file") + 1], "w") as f:
                json.dump(
                    {"n_valid": 4, "n_pieces": 4, "pid": p, "device": devices[p]}, f
                )
            self.returncode = returncodes[p]

        def poll(self):
            return self.returncode

    monkeypatch.setattr(mf.subprocess, "Popen", FakeWorker)
    return mf


def test_fabric_launcher_refuses_a_worker_that_was_not_on_a_chip(tmp_path, monkeypatch):
    """A device run is only a device run if EVERY worker's own JAX named
    one TPU chip: a worker that landed on XLA:CPU (its chip missing or
    busy) fails the run instead of hiding under worker 0's platform."""
    chip = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    host = {"platform": "cpu", "kind": "cpu", "count": 1}
    mf = _fabric_launcher_with_fake_workers(monkeypatch, [chip, host], [0, 0])
    with pytest.raises(RuntimeError, match="worker 1 did not hash on its own chip"):
        mf.run_once("t", "d", str(tmp_path), 2, "tpu", 8)
    # a worker that saw every chip of the host did not get "its own" either
    mf = _fabric_launcher_with_fake_workers(
        monkeypatch, [dict(chip, count=4), chip], [0, 0]
    )
    with pytest.raises(RuntimeError, match="worker 0 did not hash on its own chip"):
        mf.run_once("t", "d", str(tmp_path), 2, "tpu", 8)
    # surplus worker: its pinned backend init is fatal, and that is the run's
    mf = _fabric_launcher_with_fake_workers(monkeypatch, [chip, chip], [0, 1])
    with pytest.raises(RuntimeError, match="worker 1 rc=1"):
        mf.run_once("t", "d", str(tmp_path), 2, "tpu", 8)
    mf = _fabric_launcher_with_fake_workers(monkeypatch, [chip, chip], [0, 0])
    _seconds, rec = mf.run_once("t", "d", str(tmp_path), 2, "tpu", 8)
    assert [w["device"] for w in rec["per_process"]] == [chip, chip]


def test_doctor_cli_emits_one_json_object(tmp_path):
    """`python -m torrent_tpu.tools.doctor --json` runs the checks in the
    process that was started (no re-exec) and stdout is EXACTLY one JSON
    object (pipe to jq) that names the device check."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "torrent_tpu.tools.doctor", "--json", "--skip-swarm"],
        env=env,
        capture_output=True,
        text=True,
        timeout=180,
        cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary = json.loads(proc.stdout)
    assert summary["ok"] is True
    device = [c for c in summary["checks"] if c["name"] == "device"]
    assert len(device) == 1 and "platform=cpu" in device[0]["detail"]


def test_doctor_passes_on_this_host(capsys):
    """`torrent-tpu doctor --skip-swarm`: deps, kernels, native engine,
    and bridge all healthy in the test environment (the swarm smoke is
    the sibling e2e suites' job; the device check WARNs on CPU)."""
    from torrent_tpu.tools.doctor import main

    rc = main(["--skip-swarm"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "[PASS]  sha1 plane" in out
    assert "[PASS]  sha256 plane" in out
    assert "[PASS]  bridge" in out
    assert "0 FAIL" in out


def test_netbench_runs_from_any_cwd(tmp_path):
    """netbench resolves its test-harness imports relative to its own
    file, so the documented `python -m torrent_tpu.tools.netbench` works
    from any working directory (advisor r3)."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import asyncio, json; "
            "from torrent_tpu.tools.netbench import _swarm; "
            "print(json.dumps(asyncio.run(_swarm(65536, 16384, 1, False))))",
        ],
        cwd=str(tmp_path),
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "PYTHONPATH": repo},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec


class TestPieceLengthHeuristic:
    def test_bounds_and_target(self):
        # power of 2, 32 KiB ≤ len ≤ 1 MiB, ~size/1000
        assert choose_piece_length(0) == 32 * 1024
        assert choose_piece_length(1 << 20) == 32 * 1024
        assert choose_piece_length(100 << 20) == 128 * 1024
        assert choose_piece_length(1 << 40) == 1024 * 1024  # capped
        for size in (5 << 20, 300 << 20, 7 << 30):
            plen = choose_piece_length(size)
            assert plen & (plen - 1) == 0
            assert 32 * 1024 <= plen <= 1024 * 1024


class TestMakeTorrent:
    def _write_tree(self, root):
        rng = np.random.default_rng(8)
        (root / "sub").mkdir(parents=True)
        files = {
            "a.bin": rng.integers(0, 256, size=70_000, dtype=np.uint8).tobytes(),
            os.path.join("sub", "b.bin"): rng.integers(0, 256, size=40_001, dtype=np.uint8).tobytes(),
            "z.bin": rng.integers(0, 256, size=5, dtype=np.uint8).tobytes(),
        }
        for rel, data in files.items():
            (root / rel).write_bytes(data)
        return files

    def test_single_file_roundtrip(self, tmp_path):
        payload = np.random.default_rng(1).integers(0, 256, size=150_000, dtype=np.uint8).tobytes()
        target = tmp_path / "data.bin"
        target.write_bytes(payload)
        data = make_torrent(str(target), "http://t.local/announce", piece_length=32768)
        m = parse_metainfo(data)
        assert m is not None
        assert m.info.name == "data.bin" and m.info.length == 150_000
        assert m.announce == "http://t.local/announce"
        # digests must match ground truth
        for i, d in enumerate(m.info.pieces):
            assert d == hashlib.sha1(payload[i * 32768 : (i + 1) * 32768]).digest()

    def test_multi_file_boundary_spanning(self, tmp_path):
        files = self._write_tree(tmp_path)
        data = make_torrent(str(tmp_path), "http://t.local/announce", piece_length=65536)
        m = parse_metainfo(data)
        assert m is not None and m.info.is_multi_file
        # deterministic sorted walk
        assert [f.path for f in m.info.files] == [("a.bin",), ("z.bin",), ("sub", "b.bin")]
        concat = files["a.bin"] + files["z.bin"] + files[os.path.join("sub", "b.bin")]
        assert m.info.length == len(concat)
        for i, d in enumerate(m.info.pieces):
            assert d == hashlib.sha1(concat[i * 65536 : (i + 1) * 65536]).digest()

    def test_tpu_hasher_identical_output(self, tmp_path):
        payload = np.random.default_rng(2).integers(0, 256, size=100_000, dtype=np.uint8).tobytes()
        target = tmp_path / "x.bin"
        target.write_bytes(payload)
        cpu = make_torrent(str(target), "http://t/announce", piece_length=32768, hasher="cpu")
        tpu = make_torrent(str(target), "http://t/announce", piece_length=32768, hasher="tpu")
        # identical except creation date (strip both)
        m1, m2 = parse_metainfo(cpu), parse_metainfo(tpu)
        assert m1.info_hash == m2.info_hash

    def test_empty_dir_rejected(self, tmp_path):
        (tmp_path / "empty").mkdir()
        with pytest.raises(ValueError, match="no files"):
            make_torrent(str(tmp_path / "empty"), "http://t/a")

    def test_missing_path(self):
        with pytest.raises(FileNotFoundError):
            make_torrent("/nonexistent/nope", "http://t/a")

    def test_collect_files_deterministic(self, tmp_path):
        self._write_tree(tmp_path)
        assert collect_files(str(tmp_path)) == collect_files(str(tmp_path))

    def test_pad_files_piece_aligns_every_file(self, tmp_path):
        """BEP 47 authoring: pad entries align every non-first file to a
        piece boundary, pieces hash with the zeros, and a seed of the
        original (pad-less) directory verifies clean."""
        from torrent_tpu.storage.storage import FsStorage, Storage
        from torrent_tpu.parallel.verify import verify_pieces

        files = self._write_tree(tmp_path)
        plen = 65536
        data = make_torrent(
            str(tmp_path), "http://t.local/announce", piece_length=plen, pad_files=True
        )
        m = parse_metainfo(data)
        assert m is not None
        real = [f for f in m.info.files if not f.pad]
        pads = [f for f in m.info.files if f.pad]
        assert [f.path for f in real] == [("a.bin",), ("z.bin",), ("sub", "b.bin")]
        assert pads and all(f.path[0] == ".pad" for f in pads)
        # every real file starts on a piece boundary
        offset = 0
        for f in m.info.files:
            if not f.pad:
                assert offset % plen == 0, f.path
            offset += f.length
        # the hashed stream = files with zero fill between them
        concat = bytearray()
        for f in m.info.files:
            concat += (
                bytes(f.length)
                if f.pad
                else files[os.path.join(*f.path)]
            )
        for i, d in enumerate(m.info.pieces):
            assert d == hashlib.sha1(bytes(concat[i * plen : (i + 1) * plen])).digest()
        # the authored directory verifies complete without pad files on
        # disk (multi-file paths live under the torrent-name dir, so the
        # storage root is tmp_path's PARENT)
        ok = verify_pieces(
            Storage(FsStorage(str(tmp_path.parent)), m.info), m.info, hasher="cpu"
        )
        assert all(bool(x) for x in ok), "padded torrent must verify from the bare tree"

    def test_pad_files_noop_for_single_file(self, tmp_path):
        payload = np.random.default_rng(4).integers(0, 256, size=50_000, dtype=np.uint8).tobytes()
        (tmp_path / "one.bin").write_bytes(payload)
        a = make_torrent(str(tmp_path / "one.bin"), "http://t/a", piece_length=32768)
        b = make_torrent(
            str(tmp_path / "one.bin"), "http://t/a", piece_length=32768, pad_files=True
        )
        assert parse_metainfo(a).info_hash == parse_metainfo(b).info_hash


class TestUpnpHelpers:
    def test_soap_envelope(self):
        from torrent_tpu.net.upnp import WAN_SERVICE, soap_envelope

        env = soap_envelope("AddPortMapping", {"ExternalPort": "6881", "Protocol": "TCP"})
        assert b"<u:AddPortMapping" in env
        assert WAN_SERVICE.encode() in env
        assert b"<NewExternalPort>6881</NewExternalPort>" in env
        assert b"<NewProtocol>TCP</NewProtocol>" in env

    def test_extract_control_url_relative_and_absolute(self):
        from torrent_tpu.net.upnp import UpnpError, extract_control_url

        xml = (
            b"<device><serviceList><service>"
            b"<serviceType>urn:schemas-upnp-org:service:WANIPConnection:1</serviceType>"
            b"<controlURL>/ctl/IPConn</controlURL>"
            b"</service></serviceList></device>"
        )
        url = extract_control_url(xml, "http://192.168.1.1:5000/desc.xml")
        assert url == "http://192.168.1.1:5000/ctl/IPConn"
        xml_abs = xml.replace(b"/ctl/IPConn", b"http://10.0.0.1:80/c")
        assert extract_control_url(xml_abs, "http://x/") == "http://10.0.0.1:80/c"
        with pytest.raises(UpnpError, match="no WANIPConnection"):
            extract_control_url(b"<device/>", "http://x/")

    def test_ssdp_search_shape(self):
        from torrent_tpu.net.upnp import SSDP_SEARCH

        assert SSDP_SEARCH.startswith("M-SEARCH * HTTP/1.1")
        assert "239.255.255.250:1900" in SSDP_SEARCH
        assert "InternetGatewayDevice" in SSDP_SEARCH


class TestBridge:
    def test_digests_and_verify(self):
        async def go():
            from torrent_tpu.bridge.service import serve_bridge
            from torrent_tpu.codec.bencode import bdecode, bencode

            server = await serve_bridge(port=0, hasher="cpu")
            try:
                pieces = [b"alpha", b"beta" * 1000, b""]
                body = bencode({b"pieces": pieces})

                async def post(path, payload):
                    reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
                    writer.write(
                        f"POST {path} HTTP/1.1\r\nHost: x\r\nContent-Length: {len(payload)}\r\n\r\n".encode()
                        + payload
                    )
                    await writer.drain()
                    status = await reader.readline()
                    clen = 0
                    while True:
                        line = await reader.readline()
                        if line in (b"\r\n", b""):
                            break
                        if line.lower().startswith(b"content-length:"):
                            clen = int(line.split(b":", 1)[1])
                    resp = await reader.readexactly(clen)
                    writer.close()
                    return int(status.split()[1]), resp

                status, resp = await post("/v1/digests", body)
                assert status == 200
                digests = bdecode(resp)[b"digests"]
                assert digests == [hashlib.sha1(p).digest() for p in pieces]

                expected = list(digests)
                expected[1] = b"\x00" * 20  # corrupt one
                status, resp = await post(
                    "/v1/verify", bencode({b"pieces": pieces, b"expected": expected})
                )
                assert status == 200
                assert bdecode(resp)[b"ok"] == b"\x01\x00\x01"

                status, resp = await post("/v1/digests", b"garbage")
                assert status == 400
            finally:
                server.close()
                await server.wait_closed()

        run(go())

    def test_info_route(self):
        async def go():
            from torrent_tpu.bridge.service import serve_bridge
            from torrent_tpu.codec.bencode import bdecode

            server = await serve_bridge(port=0, hasher="cpu")
            try:
                reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
                writer.write(b"GET /v1/info HTTP/1.1\r\nHost: x\r\n\r\n")
                await writer.drain()
                data = await reader.read()
                body = data.split(b"\r\n\r\n", 1)[1]
                info = bdecode(body)
                # a hashlib bridge never imports jax: it claims no device
                # (and so takes no chip from the process that holds one)
                assert info[b"backend"] == b"cpu" and info[b"devices"] == 0
                assert {b"platform", b"device_kind"} <= set(info)
                writer.close()
            finally:
                server.close()
                await server.wait_closed()

        run(go())
