"""`torrent-tpu doctor` — one-command environment triage.

Checks, in dependency order, each with a PASS/WARN/FAIL line and a
one-line remedy on failure:

1. python deps (numpy, jax) and versions
2. JAX platform + device visibility, read in-process: doctor is the one
   process that holds the chip for the run (every worker it spawns is
   pinned to the CPU platform and the hashlib plane)
3. hash kernels: SHA-1/SHA-256 planes vs hashlib on this host's default
   backend (interpret/scan on CPU)
4. native io_engine availability (falls back to Python preads)
5. loopback swarm smoke: author → seed → download 256 KiB through a
   real tracker + two Clients
6. verify-scheduler smoke: four tenants coalesce into one shared
   hash-plane launch with correct digests (torrent_tpu/sched)
7. bridge smoke: /v1/digests round-trip on an ephemeral port

Exit codes (stable — CI consumes every mode, not just ``--lint``):

* **0** — every check PASS or WARN (WARN = degraded-but-working, e.g.
  no accelerator visible; it never fails the run)
* **1** — at least one check FAILed (including the core-deps short
  circuit)
* **2** — usage error (argparse: unknown flag/bad value)

With ``--json``, stdout carries exactly one JSON object (``doctor
--json | jq .`` works) with ``ok``/``fails``/``warns``/``exit_code``
and the per-check ``{status, name, detail}`` list covering whichever
modes ran; human check lines move to stderr. The
reference ships no equivalent; this exists because a TPU-backed stack
has strictly more environment to go wrong (device runtime, kernels,
native engine).
"""

from __future__ import annotations

import asyncio
import hashlib
import os
import subprocess
import sys
import tempfile
import time

from torrent_tpu.utils.device import worker_env

_RESULTS: list[tuple[str, str, str]] = []  # (status, name, detail)

# With --json, stdout must carry exactly one JSON object so
# `doctor --json | jq .` works; all human lines move to stderr.
_JSON_MODE = False


def _say(line: str) -> None:
    print(line, flush=True, file=sys.stderr if _JSON_MODE else sys.stdout)


def _report(status: str, name: str, detail: str = "") -> None:
    _RESULTS.append((status, name, detail))
    pad = {"PASS": "  ", "WARN": "  ", "FAIL": "  "}[status]
    line = f"[{status}]{pad}{name}"
    if detail:
        line += f" — {detail}"
    _say(line)


def _check_deps() -> bool:
    try:
        import numpy

        _report("PASS", "numpy", numpy.__version__)
    except Exception as e:  # pragma: no cover - image always has numpy
        _report("FAIL", "numpy", f"{e!r}; install numpy")
        return False
    try:
        import jax

        _report("PASS", "jax", jax.__version__)
    except Exception as e:
        _report("FAIL", "jax", f"{e!r}; install jax (CPU wheels suffice)")
        return False
    return True


def _check_device() -> None:
    """Name the device JAX resolved. In-process: this process holds the
    chip from here on, and nothing doctor spawns later needs one."""
    from torrent_tpu.utils.device import device_info

    try:
        dev = device_info()
    except Exception as e:
        _report("FAIL", "device", f"jax backend init failed: {e!r}")
        return
    detail = f"platform={dev['platform']} kind={dev['kind']} devices={dev['count']}"
    if dev["platform"] == "cpu":
        _report(
            "WARN", "device",
            detail + " (no accelerator; kernels run in interpret/scan mode)",
        )
    else:
        _report("PASS", "device", detail)


def _check_kernels() -> bool:
    def run_sha1():
        from torrent_tpu.models.verifier import TPUVerifier

        v = TPUVerifier(piece_length=16384, batch_size=4)
        pieces = [bytes([i]) * 16384 for i in range(4)]
        got = list(v.hash_pieces(pieces))
        want = [hashlib.sha1(p).digest() for p in pieces]
        return got == want, f"backend={v.backend}"

    def run_sha256():
        from torrent_tpu.models.merkle import words32_to_digests
        from torrent_tpu.models.v2 import _leaf_words_device

        data = b"\xa5" * 16384
        got = words32_to_digests(_leaf_words_device(data, "auto"))[0]
        return got == hashlib.sha256(data).digest(), ""

    ok = True
    for name, fn in (("sha1 plane", run_sha1), ("sha256 plane", run_sha256)):
        try:
            good, detail = fn()
        except Exception as e:
            _report("FAIL", name, repr(e))
            ok = False
            continue
        if good:
            _report("PASS", name, detail)
        else:
            _report("FAIL", name, "digests diverge from hashlib")
            ok = False
    return ok


def _check_native_io() -> None:
    try:
        from torrent_tpu.native.io_engine import native_available

        if native_available():
            _report("PASS", "native io_engine", "C++ pread pool loaded")
        else:
            _report(
                "WARN",
                "native io_engine",
                "not built; Python pread fallback active "
                "(python -m torrent_tpu.native.build to build)",
            )
    except Exception:
        _report("WARN", "native io_engine", "module unavailable; Python fallback")


class _LoopbackSwarm:
    """Shared two-client loopback scaffold for the swarm smokes: tmp
    payload file → in-memory tracker → seed + leech clients → download
    to completion. One copy of the port-0/teardown plumbing serves both
    doctor smokes."""

    def __init__(self, tmp: str, payload: bytes, name: str,
                 piece_length: int = 16384, seed_bps: int = 0):
        self.tmp = tmp
        self.payload = payload
        self.name = name
        self.piece_length = piece_length
        self.seed_bps = seed_bps  # client-global seed upload cap (0 = off)
        self.seed = self.leech = self.server = None
        self.seed_dir = self.leech_dir = None
        self.torrent = None  # the leech's Torrent once downloaded

    async def __aenter__(self) -> "_LoopbackSwarm":
        from torrent_tpu.codec.metainfo import parse_metainfo
        from torrent_tpu.server.in_memory import run_tracker
        from torrent_tpu.server.tracker import ServeOptions
        from torrent_tpu.session.client import Client, ClientConfig
        from torrent_tpu.tools.make_torrent import make_torrent

        self.seed_dir = os.path.join(self.tmp, "seed")
        os.makedirs(self.seed_dir)
        with open(os.path.join(self.seed_dir, self.name), "wb") as f:
            f.write(self.payload)
        self.server, _ = await run_tracker(
            ServeOptions(http_port=0, udp_port=None, interval=1)
        )
        ann = f"http://127.0.0.1:{self.server.http_port}/announce"
        self.meta = parse_metainfo(
            make_torrent(
                os.path.join(self.seed_dir, self.name), ann,
                piece_length=self.piece_length,
            )
        )
        self.leech_dir = os.path.join(self.tmp, "leech")
        os.makedirs(self.leech_dir)
        self.seed = Client(ClientConfig(
            port=0, enable_upnp=False, resume=False,
            max_upload_bps=self.seed_bps,
        ))
        self.leech = Client(ClientConfig(port=0, enable_upnp=False, resume=False))
        await self.seed.start()
        await self.leech.start()
        return self

    async def download(self, deadline_polls: int = 1200) -> None:
        t1 = await self.seed.add(self.meta, self.seed_dir)
        assert t1.bitfield.complete, "seed recheck failed"
        self.torrent = await self.leech.add(self.meta, self.leech_dir)
        for _ in range(deadline_polls):
            if self.torrent.bitfield.complete:
                return
            await asyncio.sleep(0.05)
        assert self.torrent.bitfield.complete, "download did not complete"

    async def __aexit__(self, *exc) -> None:
        if self.seed is not None:
            await self.seed.close()
        if self.leech is not None:
            await self.leech.close()
        if self.server is not None:
            self.server.close()


async def _swarm_smoke(tmp: str) -> None:
    import numpy as np

    payload = np.random.default_rng(1).integers(
        0, 256, 256 * 1024, dtype=np.uint8
    ).tobytes()
    async with _LoopbackSwarm(tmp, payload, "smoke.bin") as swarm:
        await swarm.download(deadline_polls=600)
        with open(os.path.join(swarm.leech_dir, "smoke.bin"), "rb") as f:
            assert f.read() == payload, "payload mismatch"


async def _swarm_wire_smoke(tmp: str) -> str:
    """Swarm wire-plane smoke (``--swarm``): a two-peer loopback
    seed→leech download over a THROTTLED link (the seed's client-global
    upload token bucket models a slow network), checked against the
    whole observe→attribute→alert stack one layer down:

    - the ledger's ``recv`` stage charged the downloaded bytes, and the
      bridge's ``/v1/pipeline`` attribution names ``recv`` as the
      limiting stage — the network, not disk;
    - ``/v1/swarm`` reports bounded per-peer telemetry: per-peer
      byte/block accounting, a choke timeline with durations, a
      block-RTT p99, pipeline depth, and the top-K + overflow contract;
    - ``/metrics`` carries the ``torrent_tpu_swarm_*`` and
      ``torrent_tpu_peer_*`` families;
    - a snub storm driven through the SAME registry API the session
      uses fires exactly ONE ``snub_storm`` flight dump per transition
      (further snubs while the storm holds must not re-fire).
    """
    import json as _json

    import numpy as np

    from torrent_tpu.bridge.service import BridgeServer
    from torrent_tpu.obs.ledger import pipeline_ledger
    from torrent_tpu.obs.recorder import flight_recorder
    from torrent_tpu.obs.swarm import swarm_telemetry

    # 384 KiB at a 128 KiB/s seed cap: the token bucket's one-second
    # burst passes the first 128 KiB, the remaining 256 KiB pace at the
    # cap — ~2 s of wall that only the wire (recv) can own
    payload = np.random.default_rng(3).integers(
        0, 256, 384 * 1024, dtype=np.uint8
    ).tobytes()
    prev = pipeline_ledger().snapshot()
    svc = await BridgeServer("127.0.0.1", port=0, hasher="cpu").start()
    _http = _http_request
    try:
        async with _LoopbackSwarm(
            tmp, payload, "wire.bin", seed_bps=128 * 1024
        ) as loop_swarm:
            await loop_swarm.download()

            # (a) recv owns the delta: the download was wire-limited,
            # so the recv stage must have charged the payload's bytes
            # and more busy time than any other stage of this interval
            snap = pipeline_ledger().snapshot()
            recv = snap["stages"].get("recv") or {}
            prev_recv = (prev.get("stages") or {}).get("recv") or {}
            recv_bytes = recv.get("bytes", 0) - prev_recv.get("bytes", 0)
            assert recv_bytes >= len(payload), (
                f"recv charged {recv_bytes} B, payload was {len(payload)} B"
            )
            status, body = await _http(svc.port, "GET", "/v1/pipeline")
            assert status == 200, status
            pipe = _json.loads(body)
            # attribute the ROUTE's served snapshot against this
            # smoke's start (the ledger is process-global and
            # cumulative: another doctor flag's scheduler traffic must
            # not make a healthy system fail this check)
            from torrent_tpu.obs.attrib import attribute

            bn = (attribute(pipe["snapshot"], prev=prev) or {}).get(
                "bottleneck"
            ) or {}
            assert bn.get("stage") == "recv", (
                f"attribution blamed {bn.get('stage')!r}, expected recv"
            )
            assert (pipe.get("attribution") or {}).get("bottleneck"), (
                "route served no attribution"
            )

            # (b) /v1/swarm: bounded per-peer telemetry (both ends of
            # the loopback pair live in this process's registry)
            status, body = await _http(svc.port, "GET", "/v1/swarm")
            assert status == 200, status
            swarm_json = _json.loads(body)
            assert swarm_json["counts"]["connected"] >= 2, swarm_json["counts"]
            assert "overflow" in swarm_json and "peers" in swarm_json
            downloaded = [
                p for p in swarm_json["peers"].values()
                if p.get("bytes_down", 0) >= len(payload)
            ]
            assert downloaded, "no peer shows the downloaded bytes"
            p = downloaded[0]
            assert p["block_rtt"]["count"] > 0
            assert p["block_rtt"]["p99_s"] is not None
            assert "choke_timeline" in p and "peer_choking" in p["choke_timeline"]
            assert p["pipeline"]["depth_max"] > 0

            # (c) the Prometheus families ride both /metrics endpoints
            status, body = await _http(svc.port, "GET", "/metrics")
            text = body.decode()
            assert "torrent_tpu_swarm_peers " in text
            assert 'torrent_tpu_peer_bytes_down_total{peer="' in text

            # (d) snub-storm trigger: drive the registry with the same
            # API the session uses — exactly one dump per False→True
            # transition
            reg = swarm_telemetry()
            base = flight_recorder().counts().get("snub_storm", 0)
            for i in range(2):
                reg.peer_connected(f"doc{i}@127.0.0.1:{7000 + i}")
            reg.on_snub("doc0@127.0.0.1:7000")
            reg.on_snub("doc1@127.0.0.1:7001")
            storm1 = flight_recorder().counts().get("snub_storm", 0) - base
            reg.on_snub("doc0@127.0.0.1:7000")  # storm already active
            storm2 = flight_recorder().counts().get("snub_storm", 0) - base
            for i in range(2):
                reg.peer_dropped(f"doc{i}@127.0.0.1:{7000 + i}")  # clears
            assert storm1 == 1 and storm2 == 1, (
                f"expected exactly one snub_storm dump, got {storm1}/{storm2}"
            )
            rtt_ms = (p["block_rtt"]["p99_s"] or 0.0) * 1e3
    finally:
        svc.close()
        await svc.wait_closed()
    return (
        f"recv limiting ({recv_bytes >> 10} KiB wire-charged), "
        f"block-RTT p99 {rtt_ms:.1f} ms, one snub_storm dump"
    )


async def _sched_smoke() -> str:
    """Verify-scheduler smoke: four tenants submit small piece lists
    concurrently and must come back with correct digests out of a
    COALESCED launch (cross-request batch fill is the scheduler's whole
    point). Returns the observed mean batch-fill ratio for the check
    detail line."""
    from torrent_tpu.sched import HashPlaneScheduler, SchedulerConfig

    sched = HashPlaneScheduler(
        SchedulerConfig(batch_target=32, flush_deadline=0.25), hasher="cpu"
    )
    await sched.start()
    try:
        pieces = [bytes([i]) * 1024 for i in range(8)]
        want = [hashlib.sha1(p).digest() for p in pieces]
        outs = await asyncio.gather(
            *(sched.submit(f"smoke{j}", pieces, algo="sha1") for j in range(4))
        )
        assert all(o == want for o in outs), "scheduler digests diverge from hashlib"
        snap = sched.metrics_snapshot()
        assert snap["launches"] >= 1, "no launch recorded"
        return f"4 tenants coalesced, mean fill {snap['mean_fill']:.2f}"
    finally:
        await sched.close()


async def _faults_smoke() -> str:
    """Fault-tolerance smoke (``--faults``): an in-process scheduler with
    an injected fail-then-recover plan must (a) bisect a poisoned batch
    so only the poisoned piece fails while co-batched pieces get correct
    digests, and (b) trip the lane breaker to the CPU plane under
    consecutive device faults, then restore the device plane with a
    half-open probe. Deterministic and CPU-only: the faults come from
    sched/faults.py through the plane_factory seam."""
    from torrent_tpu.sched import (
        FaultPlan,
        HashPlaneScheduler,
        SchedLaunchError,
        SchedulerConfig,
    )

    # (a) poisoned-payload isolation via bisection
    poison = b"\xbd" * 64
    plan = FaultPlan(payload_prefix=b"\xbd\xbd\xbd\xbd")
    sched = HashPlaneScheduler(
        SchedulerConfig(
            batch_target=16,
            flush_deadline=0.2,
            plane_factory=plan.plane_factory(hasher="cpu"),
        ),
        hasher="cpu",
    )
    await sched.start()
    try:
        good = [bytes([i + 1]) * 64 for i in range(15)]
        # enqueue both before awaiting (no intervening yield), so the 16
        # pieces deterministically ride ONE coalesced poisoned launch
        fut_ok = await sched.enqueue("ok", good)
        fut_bad = await sched.enqueue("poisoned", [poison])
        results = await asyncio.gather(fut_ok, fut_bad, return_exceptions=True)
        assert results[0] == [hashlib.sha1(p).digest() for p in good], (
            "co-batched pieces lost to a poisoned ticket"
        )
        assert isinstance(results[1], SchedLaunchError), results[1]
        snap = sched.metrics_snapshot()
        assert snap["bisections"] > 0, "poisoned batch was not bisected"
        bisections = snap["bisections"]
    finally:
        await sched.close()

    # (b) breaker trip -> CPU degradation -> half-open recovery: the
    # first two plane launches fail (launch + its retry -> threshold 2
    # trips the breaker, bisected halves ride the CPU plane), and the
    # third — the half-open probe after the cooldown — succeeds
    plan = FaultPlan(fail_first=2)
    sched = HashPlaneScheduler(
        SchedulerConfig(
            batch_target=4,
            flush_deadline=0.05,
            breaker_threshold=2,
            breaker_cooldown=300.0,
            plane_factory=plan.plane_factory(hasher="cpu"),
        ),
        hasher="cpu",
    )
    await sched.start()
    try:
        pieces = [bytes([i]) * 128 for i in range(4)]
        want = [hashlib.sha1(p).digest() for p in pieces]
        assert await sched.submit("t", pieces) == want, "CPU degradation wrong"
        snap = sched.metrics_snapshot()
        lane = next(iter(snap["breakers"].values()))
        assert lane["state"] == "open", f"breaker did not trip: {lane}"
        assert snap["cpu_fallback_launches"] > 0
        # expire the cooldown without sleeping (wall-clock-stall-proof):
        # the next launch becomes the half-open probe
        for ln in sched._lanes.values():
            with ln.breaker.lock:
                ln.breaker.opened_at -= 1e6
        assert await sched.submit("t", pieces) == want
        lane = next(iter(sched.metrics_snapshot()["breakers"].values()))
        assert lane["state"] == "closed", f"probe did not recover: {lane}"
        assert lane["transitions"].get("half_open->closed", 0) >= 1
    finally:
        await sched.close()
    return f"bisected poisoned piece ({bisections} splits), breaker tripped+recovered"


async def _v2_smoke() -> str:
    """BEP 52 plane smoke (``--v2``): 16 KiB leaf digests AND 64-byte
    merkle-pair digests vs hashlib, through the scheduler's pallas
    sha256 lane. Interpret-safe: on a CPU host the backend pin runs the
    kernel in interpret mode, so this validates the exact dispatch path
    the v2 fast path uses without needing a device. Also asserts the
    tile-snapped lane wastes zero pad rows at full fill."""
    from torrent_tpu.sched import HashPlaneScheduler, SchedulerConfig

    sched = HashPlaneScheduler(
        SchedulerConfig(
            batch_target=1024, flush_deadline=0.2, sha256_backend="pallas"
        ),
        hasher="tpu",
    )
    await sched.start()
    try:
        # leaf leg: a couple of 16 KiB BEP 52 leaf blocks (ragged tail)
        leaves = [bytes([i + 1]) * 16384 for i in range(2)] + [b"\x42" * 5000]
        got = await sched.submit("doctor", leaves, algo="sha256", piece_length=16384)
        assert got == [hashlib.sha256(p).digest() for p in leaves], (
            "leaf digests diverge from hashlib"
        )
        # merkle-pair leg: 64-byte child concatenations (the interior-
        # node message shape), a full 1024-piece launch — the snapped
        # lane target — which must waste zero pad rows
        pairs = [bytes([i % 251]) * 64 for i in range(1024)]
        got = await sched.submit("doctor", pairs, algo="sha256", piece_length=64)
        assert got == [hashlib.sha256(p).digest() for p in pairs], (
            "merkle-pair digests diverge from hashlib"
        )
        snap = sched.metrics_snapshot()
        pair_lane = snap["lane_stats"]["sha256/64"]
        assert pair_lane["backend"] == "pallas", pair_lane
        assert pair_lane["pad_rows_total"] == 0, (
            f"full-tile launch wasted pad rows: {pair_lane}"
        )
        assert snap["cpu_fallback_launches"] == 0, "pallas lane fell back to CPU"
        leaf_lane = snap["lane_stats"]["sha256/16384"]
        return (
            f"leaf+pair parity ok (pallas, pair fill "
            f"{pair_lane['mean_fill']:.2f}, leaf pad rows "
            f"{leaf_lane['pad_rows_total']})"
        )
    finally:
        await sched.close()


def _fabric_smoke(tmp: str) -> str:
    """Verify-fabric self-test (``--fabric``): a tiny two-torrent
    library, TWO real fabric-verify worker subprocesses over the
    shared-directory heartbeat transport (explicit process ids — no
    jax.distributed), worker 1 fault-injected to die after its first
    unit. Worker 0 must watch the heartbeat lapse, adopt the orphaned
    units, sentinel-cross-check the dead worker's published verdicts,
    and finish with every piece verified — plan → execute → heartbeat →
    adopt, end to end. Returns the per-process shard stats line."""
    import json

    import numpy as np

    from torrent_tpu.tools.make_torrent import make_torrent

    plen = 16384
    rng = np.random.default_rng(3)
    tdir = os.path.join(tmp, "torrents")
    ddir = os.path.join(tmp, "data")
    os.makedirs(tdir)
    # 96 + 160 pieces at 16 KiB = 5 one-MiB work units across 2 workers
    for t, npieces in enumerate((96, 160)):
        root = os.path.join(ddir, f"fab{t}")
        os.makedirs(root)
        payload = os.path.join(root, "payload.bin")
        with open(payload, "wb") as f:
            f.write(
                rng.integers(
                    0, 256, (npieces - 1) * plen + plen // 3, dtype=np.uint8
                ).tobytes()
            )
        with open(os.path.join(tdir, f"fab{t}.torrent"), "wb") as f:
            f.write(
                make_torrent(payload, "http://t.invalid/announce", piece_length=plen)
            )
    hb = os.path.join(tmp, "hb")
    # doctor holds the chip; its workers hash on the host
    env = worker_env(os.environ, "cpu", 0)
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    workers = []
    for p in range(2):
        cmd = [
            sys.executable, "-m", "torrent_tpu", "fabric-verify", tdir, ddir,
            "--hasher", "cpu", "--num-processes", "2", "--process-id", str(p),
            "--heartbeat-dir", hb, "--heartbeat-interval", "0.1",
            "--lapse-after", "1.0", "--unit-mb", "1", "--batch-target", "64",
            "--result-file", os.path.join(tmp, f"result_{p}.json"),
        ]
        if p == 1:
            cmd += ["--die-after-units", "1"]
        workers.append(
            subprocess.Popen(
                cmd, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
            )
        )
    try:
        for p, w in enumerate(workers):
            _, err = w.communicate(timeout=180)
            if p == 0:
                assert w.returncode == 0, f"worker 0 failed:\n{err[-2000:]}"
            else:
                from torrent_tpu.fabric import FAULT_EXIT_CODE

                assert w.returncode == FAULT_EXIT_CODE, (
                    f"worker 1 should die with the fault code, got "
                    f"{w.returncode}:\n{err[-2000:]}"
                )
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
                w.communicate()
    with open(os.path.join(tmp, "result_0.json")) as f:
        rec = json.load(f)
    assert rec["n_valid"] == rec["n_pieces"], (
        f"survivor left pieces unverified: {rec['n_valid']}/{rec['n_pieces']}"
    )
    assert rec["units_adopted"] >= 1, f"no units adopted: {rec}"
    assert rec["sentinel_checks"] >= 1, f"no sentinel cross-check ran: {rec}"
    assert rec["sentinel_mismatches"] == 0, rec
    return (
        f"worker1 died after 1 unit; survivor shard {rec['shard_units']}u/"
        f"{rec['shard_bytes'] >> 20}MiB + {rec['units_adopted']} adopted, "
        f"{rec['sentinel_checks']} sentinel checks, "
        f"{rec['n_valid']}/{rec['n_pieces']} pieces valid (plan {rec['plan']})"
    )


def _byzantine_smoke(tmp: str) -> str:
    """Byzantine-fabric self-test (``--byzantine``): one 96-piece
    torrent with ONE genuinely corrupt piece, TWO real fabric-verify
    workers at ``byzantine_f=1`` / ``audit_rate=1.0``, worker 1 lying
    via ``--fault-plan forge_receipts=1`` (every piece claimed ok under
    a consistent Merkle root, so only audit re-hashing can catch it).
    Worker 0's audit must convict the liar with portable evidence;
    worker 1 must re-verify that evidence against its own storage and
    convict ITSELF — symmetric termination: identical exit codes,
    bit-identical global bitfields rejecting exactly the corrupt piece,
    the liar in both distrusted sets, and exactly one
    ``fabric_distrust`` flight dump per process."""
    import json

    import numpy as np

    from torrent_tpu.tools.make_torrent import make_torrent

    plen = 16384
    npieces = 96
    bad_piece = 70
    rng = np.random.default_rng(3)
    tdir = os.path.join(tmp, "torrents")
    ddir = os.path.join(tmp, "data")
    os.makedirs(tdir)
    root_dir = os.path.join(ddir, "byz0")
    os.makedirs(root_dir)
    payload = os.path.join(root_dir, "payload.bin")
    with open(payload, "wb") as f:
        f.write(
            rng.integers(
                0, 256, (npieces - 1) * plen + plen // 3, dtype=np.uint8
            ).tobytes()
        )
    with open(os.path.join(tdir, "byz0.torrent"), "wb") as f:
        f.write(
            make_torrent(payload, "http://t.invalid/announce", piece_length=plen)
        )
    # corrupt one piece AFTER hashing: every honest verdict must reject
    # exactly this piece, and the forger's all-ok claim about it is the
    # lie the audit plane has to catch
    with open(payload, "r+b") as f:
        f.seek(bad_piece * plen)
        chunk = f.read(64)
        f.seek(bad_piece * plen)
        f.write(bytes(b ^ 0xFF for b in chunk))
    hb = os.path.join(tmp, "hb")
    # doctor holds the chip; its workers hash on the host
    env = worker_env(os.environ, "cpu", 0)
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    workers = []
    for p in range(2):
        flight = os.path.join(tmp, f"flight_{p}")
        os.makedirs(flight)
        cmd = [
            sys.executable, "-m", "torrent_tpu", "fabric-verify", tdir, ddir,
            "--hasher", "cpu", "--num-processes", "2", "--process-id", str(p),
            "--heartbeat-dir", hb, "--heartbeat-interval", "0.1",
            "--lapse-after", "2.0", "--unit-mb", "1", "--batch-target", "64",
            "--byzantine-f", "1", "--audit-rate", "1.0",
            "--result-file", os.path.join(tmp, f"result_{p}.json"),
        ]
        if p == 1:
            cmd += ["--fault-plan", "forge_receipts=1"]
        wenv = dict(env)
        wenv["TORRENT_TPU_FLIGHT_DIR"] = flight
        workers.append(
            subprocess.Popen(
                cmd, env=wenv, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
            )
        )
    codes = []
    try:
        for p, w in enumerate(workers):
            _, err = w.communicate(timeout=180)
            # one genuinely corrupt piece -> n_valid != n_pieces -> rc 2
            assert w.returncode == 2, (
                f"worker {p} should exit 2 (one corrupt piece), got "
                f"{w.returncode}:\n{err[-2000:]}"
            )
            codes.append(w.returncode)
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
                w.communicate()
    assert codes[0] == codes[1], f"exit-code parity broken: {codes}"
    recs = []
    for p in range(2):
        with open(os.path.join(tmp, f"result_{p}.json")) as f:
            recs.append(json.load(f))
    assert recs[0]["bitfields"] == recs[1]["bitfields"], (
        "global bitfields diverge between the honest worker and the liar"
    )
    bits = recs[0]["bitfields"][0]  # "0"/"1" chars, one per piece
    assert recs[0]["n_valid"] == npieces - 1 and bits[bad_piece] == "0", (
        f"corrupt piece survived the quorum: {recs[0]['n_valid']}/{npieces}, "
        f"bit {bits[bad_piece]!r}"
    )
    for p, rec in enumerate(recs):
        assert rec["byzantine_f"] == 1 and rec["quorum_need"] == 2, rec
        assert 1 in rec["distrusted"], (
            f"worker {p} never convicted the liar: {rec['distrusted']}"
        )
        assert rec["convictions"] >= 1, f"worker {p}: no conviction recorded"
        dumps = [
            n for n in os.listdir(os.path.join(tmp, f"flight_{p}"))
            if n.startswith("blackbox_")
        ]
        assert len(dumps) == 1, (
            f"worker {p}: expected exactly one fabric_distrust flight "
            f"dump, found {dumps}"
        )
        with open(os.path.join(tmp, f"flight_{p}", dumps[0])) as f:
            dump = json.load(f)
        assert dump.get("reason") == "fabric_distrust", dump.get("reason")
    assert recs[0]["audit_checks"] >= 1, "honest worker ran no audits"
    assert recs[0]["audit_mismatches"] >= 1, (
        "honest worker audits never caught the forged claim"
    )
    return (
        f"liar convicted on both processes ({recs[0]['audit_checks']}+"
        f"{recs[1]['audit_checks']} audits, "
        f"{recs[0]['audit_mismatches']} mismatch); bitfields identical, "
        f"{recs[0]['n_valid']}/{npieces} pieces valid, 1 flight dump each"
    )


def _fleet_smoke(tmp: str) -> str:
    """Fleet-observability self-test (``--fleet``): two real
    fabric-verify worker subprocesses over the shared-directory
    heartbeat, worker 0 fault-throttled with a ``latency_ms`` plan (the
    slow-interconnect model, accounted to its h2d ledger stage) and
    worker 1 serving its live obs surface (``--obs-port``). Worker 1's
    ``/v1/fleet`` — the heartbeat-carried digests merged by
    obs/fleet — must name worker 0 as the fleet's limiting process and
    ``h2d`` as its limiting stage: cross-process bottleneck
    attribution proven deterministically on CPU, from the PEER's point
    of view. Also exercises the ``top --fleet`` renderer on the live
    payload."""
    import json
    import urllib.request

    import numpy as np

    from torrent_tpu.tools.make_torrent import make_torrent
    from torrent_tpu.tools.top import render_fleet

    plen = 16384
    rng = np.random.default_rng(17)
    tdir = os.path.join(tmp, "torrents")
    ddir = os.path.join(tmp, "data")
    os.makedirs(tdir)
    # 96 + 160 pieces at 16 KiB = 5 one-MiB work units across 2 workers
    for t, npieces in enumerate((96, 160)):
        root = os.path.join(ddir, f"fleet{t}")
        os.makedirs(root)
        payload = os.path.join(root, "payload.bin")
        with open(payload, "wb") as f:
            f.write(
                rng.integers(
                    0, 256, (npieces - 1) * plen + plen // 3, dtype=np.uint8
                ).tobytes()
            )
        with open(os.path.join(tdir, f"fleet{t}.torrent"), "wb") as f:
            f.write(
                make_torrent(payload, "http://t.invalid/announce", piece_length=plen)
            )
    hb = os.path.join(tmp, "hb")
    port_file = os.path.join(tmp, "obs_port")
    # doctor holds the chip; its workers hash on the host
    env = worker_env(os.environ, "cpu", 0)
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    workers = []
    for p in range(2):
        cmd = [
            sys.executable, "-m", "torrent_tpu", "fabric-verify", tdir, ddir,
            "--hasher", "cpu", "--num-processes", "2", "--process-id", str(p),
            "--heartbeat-dir", hb, "--heartbeat-interval", "0.1",
            "--lapse-after", "30", "--unit-mb", "1", "--batch-target", "16",
            "--result-file", os.path.join(tmp, f"result_{p}.json"),
        ]
        if p == 0:
            # worker 0 is the designated straggler: every launch's h2d
            # sleeps 250 ms, so its shard dominates the sweep's wall
            cmd += ["--fault-plan", "latency_ms=250"]
        else:
            cmd += ["--obs-port", "0", "--obs-port-file", port_file]
        workers.append(
            subprocess.Popen(
                cmd, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
            )
        )
    live_fleet = None
    live_frames = 0
    try:
        deadline = time.monotonic() + 180
        port = None
        while time.monotonic() < deadline:
            if all(w.poll() is not None for w in workers):
                break
            if port is None:
                try:
                    with open(port_file) as f:
                        port = int(f.read().strip())
                except (OSError, ValueError):
                    time.sleep(0.1)
                    continue
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/v1/fleet", timeout=5
                ) as r:
                    live_fleet = json.loads(r.read().decode())
                    live_frames += 1
            except (OSError, ValueError):
                pass
            time.sleep(0.1)
        for p, w in enumerate(workers):
            _, err = w.communicate(timeout=60)
            assert w.returncode == 0, f"worker {p} failed:\n{err[-2000:]}"
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
                w.communicate()
    # the deterministic check: worker 1's FINAL fleet view (the result
    # record embeds it) must name worker 0 / h2d — the two-level verdict
    with open(os.path.join(tmp, "result_1.json")) as f:
        rec = json.load(f)
    assert rec["n_valid"] == rec["n_pieces"], (
        f"sweep left pieces unverified: {rec['n_valid']}/{rec['n_pieces']}"
    )
    fleet = rec.get("fleet") or {}
    bn = fleet.get("bottleneck") or {}
    assert bn.get("pid") == 0, (
        f"peer view did not name the throttled worker 0 as limiting: {bn}"
    )
    assert bn.get("stage") == "h2d", (
        f"peer view did not name h2d as worker 0's limiting stage: {bn}"
    )
    assert fleet.get("reporting", 0) == 2, f"peer digest missing: {fleet}"
    assert fleet.get("digest_drops", 0) == 0, fleet
    row0 = next(r for r in fleet["scoreboard"] if r["pid"] == 0)
    assert row0.get("limiting_stage") == "h2d", row0
    # the live surface answered while the sweep ran, and the top --fleet
    # renderer names the same verdict from the same payload
    assert live_frames > 0, "worker 1's /v1/fleet never answered"
    frame = render_fleet(live_fleet)
    assert "fleet bottleneck: process 0 (h2d)" in render_fleet(fleet), (
        f"top --fleet rendering lost the verdict:\n{render_fleet(fleet)}"
    )
    return (
        f"worker0 h2d-throttled; peer's /v1/fleet named pid 0/h2d "
        f"({bn.get('utilization', 0) * 100:.0f}% util, "
        f"{fleet['reporting']}/2 digests, {live_frames} live frames, "
        f"{len(frame.splitlines())}-line top frame)"
    )


async def _trace_smoke() -> str:
    """Observability smoke (``--trace``): a traced, fault-injected run
    must produce (a) an ordered span tree covering the ticket lifecycle
    (enqueue → admission → lane wait → launch → digest), (b) latency-
    histogram series for the queue-wait and launch stages, and (c)
    exactly one flight-recorder dump for a retry-exhausted launch and
    one for a breaker-open transition. Deterministic and CPU-only —
    the same machinery ``GET /v1/trace`` and ``torrent-tpu trace
    dump`` expose on a live bridge."""
    from torrent_tpu.obs import flight_recorder, histograms, tracer
    from torrent_tpu.sched import (
        FaultPlan,
        HashPlaneScheduler,
        SchedLaunchError,
        SchedulerConfig,
    )

    t = tracer()
    base = flight_recorder().counts()

    # (a)+(b): a healthy traced submission
    sched = HashPlaneScheduler(
        SchedulerConfig(batch_target=8, flush_deadline=0.05), hasher="cpu"
    )
    await sched.start()
    try:
        pieces = [bytes([i]) * 256 for i in range(4)]
        want = [hashlib.sha1(p).digest() for p in pieces]
        tid = t.mint()
        with t.span("doctor.trace", trace_id=tid):
            assert await sched.submit("doctor", pieces) == want
    finally:
        await sched.close()
    tree = t.trace_tree(tid)
    assert tree is not None, "trace not recorded"

    def names(node):
        yield node["name"]
        for c in node["children"]:
            yield from names(c)

    got = [n for root in tree["spans"] for n in names(root)]
    for stage in ("sched.enqueue", "sched.admission", "sched.lane_wait",
                  "sched.launch", "sched.digest", "sched.wake"):
        assert stage in got, f"span tree missing {stage}: {got}"
    rendered = histograms().render()
    for family in ("torrent_tpu_sched_queue_wait_seconds",
                   "torrent_tpu_sched_launch_seconds"):
        assert f"{family}_bucket" in rendered, f"no {family} histogram"

    # (c) retry-exhausted: a poisoned single-piece launch fails alone
    plan = FaultPlan(payload_prefix=b"\xbd\xbd")
    sched = HashPlaneScheduler(
        SchedulerConfig(
            batch_target=4, flush_deadline=0.05,
            plane_factory=plan.plane_factory(hasher="cpu"),
        ),
        hasher="cpu",
    )
    await sched.start()
    try:
        try:
            await sched.submit("doctor", [b"\xbd\xbd" + b"x" * 64])
            raise AssertionError("poisoned launch unexpectedly succeeded")
        except SchedLaunchError:
            pass
    finally:
        await sched.close()

    # (c) breaker-open: enough consecutive transient faults to trip the
    # breaker; the CPU fallback still answers, so the ticket succeeds
    plan = FaultPlan(fail_first=2)
    sched = HashPlaneScheduler(
        SchedulerConfig(
            batch_target=4, flush_deadline=0.05, breaker_threshold=2,
            launch_retries=2, breaker_cooldown=300.0,
            plane_factory=plan.plane_factory(hasher="cpu"),
        ),
        hasher="cpu",
    )
    await sched.start()
    try:
        pieces = [bytes([i]) * 128 for i in range(2)]
        want = [hashlib.sha1(p).digest() for p in pieces]
        assert await sched.submit("doctor", pieces) == want
    finally:
        await sched.close()

    counts = flight_recorder().counts()
    retry = counts.get("retry_exhausted", 0) - base.get("retry_exhausted", 0)
    brk = counts.get("breaker_open", 0) - base.get("breaker_open", 0)
    assert retry == 1, f"expected exactly 1 retry_exhausted dump, got {retry}"
    assert brk == 1, f"expected exactly 1 breaker_open dump, got {brk}"
    return (
        f"{tree['span_count']}-span tree, queue-wait/launch histograms, "
        f"1 retry-exhausted + 1 breaker-open dump"
    )


async def _bottleneck_smoke(throttled: bool, tmp: str) -> str:
    """Pipeline-ledger smoke (``--bottleneck``): a scheduler-fed library
    recheck with the ledger attributing every stage boundary
    (read → stage → h2d → launch → digest → verdict). Plain mode
    reports the attribution; with ``--faults`` the H2D stage is
    latency-throttled through ``sched/faults.py``'s ``latency_ms`` hook
    (the slow-interconnect model) and the attributor MUST name ``h2d``
    as the limiting stage with the majority of pipeline wall time —
    the deterministic, CPU-only proof that bottleneck attribution
    works. The same verdict is served by ``GET /v1/pipeline`` and
    rendered by ``torrent-tpu top``."""
    import numpy as np

    from torrent_tpu.codec.metainfo import parse_metainfo
    from torrent_tpu.obs.attrib import attribute, format_report
    from torrent_tpu.obs.ledger import pipeline_ledger
    from torrent_tpu.parallel.bulk import verify_library_sched
    from torrent_tpu.sched import FaultPlan, HashPlaneScheduler, SchedulerConfig
    from torrent_tpu.storage.storage import FsStorage, Storage
    from torrent_tpu.tools.make_torrent import make_torrent

    payload = os.path.join(tmp, "bottleneck.bin")
    with open(payload, "wb") as f:
        f.write(
            np.random.default_rng(5)
            .integers(0, 256, 64 * 16384, dtype=np.uint8)
            .tobytes()
        )
    meta = parse_metainfo(
        make_torrent(payload, "http://t.invalid/announce", piece_length=16384)
    )
    storage = Storage(FsStorage(tmp), meta.info)

    factory = None
    if throttled:
        factory = FaultPlan(latency_s=0.03).plane_factory(hasher="cpu")
    led = pipeline_ledger()
    prev = led.snapshot()
    sched = HashPlaneScheduler(
        SchedulerConfig(
            batch_target=16, flush_deadline=0.02, plane_factory=factory
        ),
        hasher="cpu",
    )
    await sched.start()
    try:
        res = await verify_library_sched(
            [(storage, meta.info)], sched, tenant="doctor"
        )
    finally:
        await sched.close()
    assert int(res.bitfields[0].sum()) == meta.info.num_pieces, (
        "recheck left pieces unverified"
    )
    rep = attribute(led.snapshot(), prev=prev)
    assert rep["bottleneck"] is not None, "ledger recorded no activity"
    # zero-copy ingest proof: the scheduler-fed recheck reads straight
    # into staging slabs, so the `stage` copy stage must account ~zero
    # bytes — and every slab must have come back to its pool
    stage_bytes = rep["stages"].get("stage", {}).get("bytes", 0)
    assert stage_bytes == 0, (
        f"zero-copy path still staged {stage_bytes} bytes"
    )
    staging = sched.metrics_snapshot().get("staging", {})
    assert staging.get("outstanding", 0) == 0, (
        f"staging slabs leaked: {staging}"
    )
    if throttled:
        bn = rep["bottleneck"]
        assert bn["stage"] == "h2d", (
            f"throttled H2D not named as limiting stage: {bn}"
        )
        assert bn["utilization"] > 0.5, (
            f"throttled H2D should own the majority of wall time: {bn}"
        )
    return format_report(rep) + "; zero-copy: stage 0 B, slabs all returned"


async def _control_smoke() -> str:
    """Scheduler-autopilot smoke (``--control``): an in-process
    scheduler whose plane is h2d-throttled through ``sched/faults.py``
    (``latency_ms`` — the slow-interconnect model) runs waves of
    submissions while the autopilot ticks between them. The controller
    must (a) name ``h2d`` as the confirmed bottleneck, (b) move the
    batch actuator TOWARD it — grow the lane's flush target so fewer,
    bigger launches amortize the fixed per-launch transfer cost — and
    (c) pull the admission budget down to what the limiting stage
    drains. A disabled controller ticking over the same scheduler must
    move nothing (controller-off = bit-identical static config).
    Deterministic and CPU-only; the decisions are pure functions of
    ledger/lane snapshot deltas."""
    import hashlib as _hashlib

    from torrent_tpu.sched import (
        ControlConfig,
        FaultPlan,
        HashPlaneScheduler,
        SchedulerAutopilot,
        SchedulerConfig,
    )

    base_target = 8
    plan = FaultPlan.parse("latency_ms=40")
    sched = HashPlaneScheduler(
        SchedulerConfig(
            batch_target=base_target,
            flush_deadline=0.02,
            plane_factory=plan.plane_factory(hasher="cpu"),
        ),
        hasher="cpu",
    )
    await sched.start()
    pilot = SchedulerAutopilot(
        sched, ControlConfig(enabled=True, hysteresis_ticks=1, cooldown_ticks=0)
    )
    try:
        pieces = [bytes([i % 251]) * 1024 for i in range(64)]
        want = [_hashlib.sha1(p).digest() for p in pieces]
        pilot.tick()  # baseline snapshots
        last = None
        for _ in range(3):
            assert await sched.submit("doctor", pieces) == want, (
                "digests diverged under autopilot control"
            )
            last = pilot.tick()
        decision = last["decision"]
        bn = decision.get("bottleneck") or {}
        assert bn.get("stage") == "h2d", (
            f"controller did not name the throttled h2d stage: {decision}"
        )
        snap = sched.metrics_snapshot()
        lane = next(iter(snap["lane_stats"].values()))
        assert lane["target"] > base_target, (
            f"batch actuator did not move toward the bottleneck: {lane}"
        )
        assert snap["admission_factor"] < 1.0, (
            f"admission budget did not follow the limiting stage: "
            f"{snap['admission_factor']}"
        )
        grown = lane["target"]
        factor = snap["admission_factor"]

        # controller-off parity: a DISABLED pilot over a fresh scheduler
        # must leave every actuator at its static value
        plan2 = FaultPlan.parse("latency_ms=40")
        sched2 = HashPlaneScheduler(
            SchedulerConfig(
                batch_target=base_target,
                flush_deadline=0.02,
                plane_factory=plan2.plane_factory(hasher="cpu"),
            ),
            hasher="cpu",
        )
        await sched2.start()
        try:
            pilot2 = SchedulerAutopilot(sched2, ControlConfig(enabled=False))
            pilot2.tick()
            assert await sched2.submit("doctor", pieces) == want
            off = pilot2.tick()
            assert not off.get("applied"), f"disabled pilot applied {off}"
            snap2 = sched2.metrics_snapshot()
            lane2 = next(iter(snap2["lane_stats"].values()))
            assert lane2["target"] == base_target, lane2
            assert snap2["admission_factor"] == 1.0, snap2
        finally:
            await sched2.close()
    finally:
        await sched.close()
    return (
        f"h2d confirmed limiting; lane target {base_target}→{grown}, "
        f"admission ×{factor:.2f}; disabled controller moved nothing"
    )


async def _slo_smoke() -> str:
    """SLO-engine smoke (``--slo``): a ``--slo``-armed bridge with a
    deterministic ``FaultPlan`` payload-poison plan. Healthy traffic
    keeps ``/v1/health`` ready; a burst of poisoned pieces (every piece
    fails deterministically → ``failed_pieces`` burns the availability
    budget) must drive ``/v1/slo`` into a fast-burn breach, flip
    ``/v1/health`` ready→degraded (503), and fire exactly ONE
    ``slo_breach`` flight-recorder dump; healthy traffic afterwards must
    clear the breach and restore readiness. Timeline samples are driven
    manually (``sampler.sample_once()``) so the whole scenario is
    deterministic on CPU — no cadence races."""
    import json as _json

    from torrent_tpu.bridge.service import BridgeServer
    from torrent_tpu.codec.bencode import bencode
    from torrent_tpu.obs.recorder import flight_recorder
    from torrent_tpu.sched import FaultPlan

    poison = b"DOCTORPOISON"
    _http = _http_request

    svc = await BridgeServer(
        "127.0.0.1", port=0, hasher="cpu",
        fault_plan=FaultPlan.parse(f"payload={poison.hex()}"),
        slo="availability=0.99", timeline_interval_s=3600.0,
        slo_short_samples=4, slo_long_samples=64,
    ).start()
    try:
        await svc._probe_task  # readiness gates on the resolved probe
        base_dumps = flight_recorder().counts().get("slo_breach", 0)
        good = bencode({b"pieces": [b"healthy-piece-%d" % i for i in range(8)]})
        svc.sampler.sample_once()
        status, _ = await _http(svc.port, "POST", "/v1/digests", good)
        assert status == 200, f"healthy wave failed: {status}"
        svc.sampler.sample_once()
        status, body = await _http(svc.port, "GET", "/v1/health")
        health = _json.loads(body)
        assert status == 200 and health["status"] == "ready", health

        # the burst: every piece carries the poison prefix → the whole
        # launch fails deterministically → failed_pieces burns budget
        bad = bencode({b"pieces": [poison + b"-%d" % i for i in range(8)]})
        status, _ = await _http(svc.port, "POST", "/v1/digests", bad)
        assert status == 500, f"poisoned wave should 500: {status}"
        svc.sampler.sample_once()
        status, body = await _http(svc.port, "GET", "/v1/slo")
        slo = _json.loads(body)
        avail = slo["report"]["objectives"]["availability"]
        assert avail["breach"] and avail["classification"] == "fast_burn", avail
        assert avail["budget_remaining"] < 1.0, avail
        status, body = await _http(svc.port, "GET", "/v1/health")
        health = _json.loads(body)
        assert status == 503 and health["status"] == "degraded", health
        dumps = flight_recorder().counts().get("slo_breach", 0) - base_dumps
        assert dumps == 1, f"expected exactly one slo_breach dump, got {dumps}"

        # recovery: healthy waves push the errors out of the short
        # window; the breach clears and readiness returns
        for _ in range(5):
            status, _ = await _http(svc.port, "POST", "/v1/digests", good)
            assert status == 200
            svc.sampler.sample_once()
        status, body = await _http(svc.port, "GET", "/v1/health")
        health = _json.loads(body)
        assert status == 200 and health["status"] == "ready", health
        dumps = flight_recorder().counts().get("slo_breach", 0) - base_dumps
        assert dumps == 1, f"recovery must not re-dump: {dumps}"
        burned = avail["budget_remaining"]
    finally:
        svc.close()
        await svc.wait_closed()
    return (
        f"availability fast-burn breach (budget {burned * 100:.0f}% left), "
        "health ready→degraded→ready, exactly one slo_breach dump"
    )


async def _announce_smoke() -> str:
    """Announce-plane smoke (``--announce``): concurrent announce storms
    from multiple simulated swarms against the sharded store, then
    three contracts checked:

    - sampled replies are well-formed (≤ numwant peers, valid ports,
      never the requester itself);
    - shard counts reconcile (per-shard peer sums == store totals ==
      scrape sums — no peer lost or double-counted across shard locks);
    - the batch path (the UDP drain's shape) returns one outcome per
      announce in order.
    """
    import hashlib

    from torrent_tpu.net.types import AnnounceEvent
    from torrent_tpu.server.shard import ShardedSwarmStore

    n_workers, per_worker = 4, 50
    store = ShardedSwarmStore(n_shards=4)
    swarm_hashes = [
        hashlib.sha1(b"doctor-swarm-%d" % i).digest() for i in range(4)
    ]

    def worker(wi: int) -> None:
        for k in range(per_worker):
            ih = swarm_hashes[(wi + k) % len(swarm_hashes)]
            pid = (b"W%dK%03d" % (wi, k)).ljust(20, b"w")
            store.announce(
                ih, pid, f"10.1.{wi}.{k}", 7000 + wi,
                left=k % 2, event=AnnounceEvent.EMPTY, numwant=20,
            )

    await asyncio.gather(
        *(asyncio.to_thread(worker, wi) for wi in range(n_workers))
    )

    probe_id = b"probe".ljust(20, b"q")
    out = store.announce(
        swarm_hashes[0], probe_id, "10.9.9.9", 9999, left=1, numwant=10
    )
    assert len(out.peers) <= 10, f"reply overflows numwant: {len(out.peers)}"
    assert all(0 < p.port < 65536 for p in out.peers), "invalid sampled port"
    assert all(p.peer_id != probe_id for p in out.peers), "sampled self"
    assert out.complete + out.incomplete >= len(out.peers)

    snap = store.metrics_snapshot()
    expected = n_workers * per_worker + 1  # unique announcers + the probe
    assert snap["peers"] == expected, (snap["peers"], expected)
    assert snap["peers"] == sum(s["peers"] for s in snap["shards"])
    sc = store.scrape(swarm_hashes)
    assert sum(c + i for _, c, _, i in sc) == expected, "scrape diverges"
    shards_hit = sum(1 for s in snap["shards"] if s["peers"])
    assert shards_hit >= 2, f"swarms all landed on one shard: {snap}"

    batch = [
        (swarm_hashes[i % 4], (b"B%02d" % i).ljust(20, b"b"),
         "10.2.0.1", 8000 + i, 1, AnnounceEvent.EMPTY, 5)
        for i in range(8)
    ]
    outs = store.announce_batch(batch)
    assert len(outs) == len(batch) and all(o.interval > 0 for o in outs)
    return (
        f"{snap['peers']} peers / {snap['swarms']} swarms reconcile across "
        f"{shards_hit}/4 shards; sampled replies ≤ numwant, batch path ok"
    )


def _lint_smoke() -> str:
    """Analysis-plane smoke (``--lint``): run all eight static passes
    over the installed package and require a clean gate — zero findings
    beyond the committed baseline (= what `torrent-tpu lint` enforces)."""
    from torrent_tpu.analysis.findings import diff_baseline, load_baseline
    from torrent_tpu.analysis.lint import default_baseline, default_root
    from torrent_tpu.analysis.passes import ALL_PASS_NAMES, run_passes

    root = default_root()
    findings, _index = run_passes(root)
    baseline = load_baseline(default_baseline(root))
    diff = diff_baseline(findings, baseline)
    if diff.new:
        lines = "; ".join(f.format() for f in diff.new[:5])
        raise AssertionError(
            f"{len(diff.new)} finding(s) beyond baseline: {lines}"
        )
    return (
        f"{len(ALL_PASS_NAMES)} passes, {len(findings)} findings, "
        f"all baselined ({len(baseline)} baseline entries)"
    )


def _scenario_smoke(name: str) -> str:
    """Scenario-plane smoke (``--scenario``): run one bundled
    hostile-internet scenario TWICE against the real serve stack. The
    verdict must pass (all behavior invariants held, no SLO objective
    breached), the wall-plane announce latency must hold its budget,
    and the two same-seed runs must produce bit-identical canonical
    verdict + timeline bytes — the determinism contract the replay
    surface depends on."""
    from torrent_tpu.scenario import canonical_bytes, run_scenario
    from torrent_tpu.scenario.library import get

    spec = get(name)
    first = run_scenario(spec)
    second = run_scenario(spec)
    b1 = canonical_bytes(first["verdict"], first["timeline"])
    b2 = canonical_bytes(second["verdict"], second["timeline"])
    if b1 != b2:
        raise AssertionError(
            "same-seed replay diverged: canonical verdict/timeline "
            f"bytes differ ({len(b1)} vs {len(b2)} bytes)"
        )
    verdict = first["verdict"]
    if not verdict["pass"]:
        raise AssertionError(
            "scenario failed: " + "; ".join(verdict["reasons"][:4])
        )
    wall = verdict["wall"]
    if not wall["ok"]:
        raise AssertionError(
            f"wall plane over budget: announce p99 {wall['p99_us']}us "
            f"vs {wall['budget_ms']}ms budget"
        )
    return (
        f"{verdict['population']} actors x {spec.ticks} ticks; "
        f"{verdict['budget']}; announce p99 {wall['p99_us']}us "
        f"({wall['announces_per_s']}/s) within {wall['budget_ms']}ms; "
        "replay bit-identical"
    )


async def _seed_smoke(tmp: str) -> str:
    """Seeder-plane smoke (``--seed``): ONE seeding client against a
    small crowd of raw-wire leechers dialing the listen port directly
    (no tracker — the serve side is the exam, not discovery):

    - every leecher downloads one full piece and the bytes must match
      the authored payload (the reactor + egress path serves correct
      frames under concurrency);
    - the serve telemetry's egress fallback matrix must show zero-copy
      traffic (``sendfile`` where the platform allows, ``preadv``
      staging otherwise) — a single-file FsStorage layout maps every
      block contiguously, so a smoke that served only via the ``copy``
      path means the zero-copy plane silently disengaged;
    - the choke economics must have run rounds AND rotated the
      optimistic slot (more interested leechers than slots);
    - ``/v1/swarm`` on the session MetricsServer must carry the
      serving-side ``serve`` entries, and ``/metrics`` the
      ``torrent_tpu_serve_*`` families.
    """
    import json as _json

    import numpy as np

    from torrent_tpu.codec.metainfo import parse_metainfo
    from torrent_tpu.net import protocol as proto
    from torrent_tpu.serve_plane.telemetry import serve_telemetry
    from torrent_tpu.session.client import Client, ClientConfig
    from torrent_tpu.session.torrent import TorrentConfig
    from torrent_tpu.tools.make_torrent import make_torrent
    from torrent_tpu.utils.metrics import MetricsServer

    piece_len = 65536
    block = 16384
    n_leechers = 6
    payload = np.random.default_rng(23).integers(
        0, 256, 8 * piece_len, dtype=np.uint8
    ).tobytes()
    seed_dir = os.path.join(tmp, "seedplane")
    os.makedirs(seed_dir)
    with open(os.path.join(seed_dir, "seed.bin"), "wb") as f:
        f.write(payload)
    meta = parse_metainfo(
        make_torrent(
            os.path.join(seed_dir, "seed.bin"),
            "http://127.0.0.1:1/announce",
            piece_length=piece_len,
        )
    )
    n_pieces = len(payload) // piece_len
    # fast rounds + fewer slots than leechers: rotations must happen in
    # smoke time, and the crowd must contend for the unchoke slots
    seed = Client(ClientConfig(
        port=0, enable_upnp=False, resume=False,
        torrent=TorrentConfig(choke_interval=0.1, unchoke_slots=2),
    ))
    base = serve_telemetry().snapshot()
    base_paths = {
        k: v.get("blocks", 0) for k, v in (base.get("paths") or {}).items()
    }
    await seed.start()
    metrics = await MetricsServer(seed).start()
    writers: list = []
    try:
        t = await seed.add(meta, seed_dir)
        assert t.bitfield.complete, "seed recheck failed"

        async def leech(i: int) -> None:
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", seed.port
            )
            writers.append(writer)
            pid = (b"-DC0001-" + f"{i:012d}".encode())[:20]
            await proto.send_handshake(writer, meta.info_hash, pid)
            await proto.read_handshake_head(reader)
            await proto.read_handshake_peer_id(reader)
            await proto.send_message(writer, proto.Interested())
            piece = i % n_pieces
            offsets = list(range(0, piece_len, block))
            got: dict[int, bytes] = {}
            while len(got) < len(offsets):
                msg = await proto.read_message(reader)
                if isinstance(msg, proto.Unchoke):
                    # (re-)request everything still missing — a choke
                    # tick may have silently dropped queued requests
                    for off in offsets:
                        if off not in got:
                            await proto.send_message(
                                writer, proto.Request(piece, off, block)
                            )
                elif isinstance(msg, proto.Piece) and msg.index == piece:
                    got[msg.begin] = msg.block
            data = b"".join(got[off] for off in offsets)
            want = payload[piece * piece_len:(piece + 1) * piece_len]
            assert data == want, f"leecher {i}: piece {piece} bytes diverge"

        await asyncio.wait_for(
            asyncio.gather(*(leech(i) for i in range(n_leechers))), 60
        )

        # the serving-side entries ride /v1/swarm while peers are live
        status, body = await _http_request(metrics.port, "GET", "/v1/swarm")
        assert status == 200, status
        swarm_json = _json.loads(body)
        serve_view = swarm_json.get("serve")
        assert serve_view, "/v1/swarm carries no serve entries"
        assert serve_view["counts"]["serving"] >= 1, serve_view["counts"]
        assert serve_view["totals"]["blocks"] >= n_leechers * (
            piece_len // block
        ), serve_view["totals"]

        status, body = await _http_request(metrics.port, "GET", "/metrics")
        assert status == 200, status
        text = body.decode()
        assert 'torrent_tpu_serve_bytes_total{path="sendfile"}' in text
        assert "torrent_tpu_serve_choke_rounds_total" in text

        snap = serve_telemetry().snapshot()
        paths = {
            k: v.get("blocks", 0) - base_paths.get(k, 0)
            for k, v in (snap.get("paths") or {}).items()
        }
        zero_copy = paths.get("sendfile", 0) + paths.get("preadv", 0)
        assert zero_copy > 0, (
            f"no zero-copy egress on a contiguous single-file layout "
            f"(fallback matrix: {paths})"
        )
        econ = t._serve_econ
        assert econ.rounds > 0, "choke economics never ran a round"
        assert econ.rotations > 0, "optimistic slot never rotated"
        served = dict(t._egress.served)
    finally:
        for w in writers:
            w.close()
        metrics.close()
        await seed.close()
    return (
        f"{n_leechers} leechers fed ({n_leechers} pieces bit-exact); "
        f"egress sendfile/preadv/copy = {served.get('sendfile', 0)}/"
        f"{served.get('preadv', 0)}/{served.get('copy', 0)} blocks; "
        f"{econ.rounds} choke rounds, {econ.rotations} optimistic rotations"
    )


async def _http_request(port: int, method: str, path: str, body: bytes = b""):
    """Minimal loopback HTTP round-trip (status, payload) — the bridge
    and SLO smokes share it; doctor must not depend on a client lib."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(
        f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
        f"Content-Length: {len(body)}\r\n\r\n".encode() + body
    )
    await writer.drain()
    status_line = await reader.readline()
    clen = 0
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b""):
            break
        if line.lower().startswith(b"content-length:"):
            clen = int(line.split(b":", 1)[1])
    payload = await reader.readexactly(clen)
    writer.close()
    return int(status_line.split()[1]), payload


async def _bridge_smoke() -> None:
    from torrent_tpu.bridge.service import BridgeServer
    from torrent_tpu.codec.bencode import bdecode, bencode

    svc = await BridgeServer("127.0.0.1", port=0, hasher="cpu").start()
    try:
        status, resp = await _http_request(
            svc.port, "POST", "/v1/digests",
            bencode({b"pieces": [b"doctor"]}),
        )
        assert status == 200, status
        got = bdecode(resp)[b"digests"][0]
        assert got == hashlib.sha1(b"doctor").digest(), "bridge digest wrong"
    finally:
        svc.close()
        await svc.wait_closed()


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="torrent-tpu doctor", description=__doc__, allow_abbrev=False
    )
    ap.add_argument(
        "--skip-swarm", action="store_true", help="skip the loopback swarm smoke"
    )
    ap.add_argument(
        "--faults",
        action="store_true",
        help="also run the fault-tolerance smoke: injected fail-then-recover "
        "plan proving bisection isolation and breaker trip/recovery",
    )
    ap.add_argument(
        "--v2",
        action="store_true",
        help="also run the BEP 52 plane smoke: leaf + merkle-pair digests vs "
        "hashlib through the scheduler's pallas sha256 lane (interpret-safe)",
    )
    ap.add_argument(
        "--fabric",
        action="store_true",
        help="also run the verify-fabric self-test: two local worker "
        "processes plan/execute/heartbeat over a shared directory, one "
        "dies mid-run, the survivor adopts and sentinel-checks its shard",
    )
    ap.add_argument(
        "--byzantine",
        action="store_true",
        help="also run the Byzantine-fabric self-test: two worker "
        "processes at byzantine_f=1, one publishing forged Merkle "
        "receipts over a genuinely corrupt piece; the audit plane must "
        "convict the liar with portable evidence on BOTH processes, "
        "bitfields must stay identical, and each process must dump "
        "exactly one fabric_distrust flight recording",
    )
    ap.add_argument(
        "--fleet",
        action="store_true",
        help="also run the fleet-observability smoke: two worker "
        "processes, one h2d-throttled via latency_ms faults; the healthy "
        "peer's /v1/fleet must name the throttled process (and its h2d "
        "stage) as the fleet bottleneck",
    )
    ap.add_argument(
        "--lint",
        action="store_true",
        help="also run the analysis-plane smoke: all eight static passes "
        "over the installed package, clean against the committed baseline",
    )
    ap.add_argument(
        "--trace",
        action="store_true",
        help="also run the observability smoke: traced fault-injected run "
        "producing a span tree, latency histograms, and flight-recorder "
        "dumps (retry-exhausted + breaker-open)",
    )
    ap.add_argument(
        "--bottleneck",
        action="store_true",
        help="also run the pipeline-ledger smoke: a scheduler-fed recheck "
        "attributed stage by stage (read/stage/h2d/launch/digest/verdict); "
        "combined with --faults the H2D stage is latency-throttled and the "
        "attributor must name it as the limiting stage",
    )
    ap.add_argument(
        "--control",
        action="store_true",
        help="also run the scheduler-autopilot smoke: an h2d-throttled "
        "scheduler under the controller must get its lane target grown "
        "and its admission budget pulled toward the limiting stage, while "
        "a disabled controller moves nothing",
    )
    ap.add_argument(
        "--slo",
        action="store_true",
        help="also run the SLO-engine smoke: a FaultPlan fail burst "
        "through a --slo bridge burns the availability budget, flips "
        "/v1/health ready→degraded, fires exactly one slo_breach "
        "flight dump, and recovers",
    )
    ap.add_argument(
        "--announce",
        action="store_true",
        help="also run the announce-plane smoke: concurrent announces "
        "from multiple simulated swarms against the sharded store; "
        "sampled replies must be well-formed and shard counts must "
        "reconcile with the store totals and scrape sums",
    )
    ap.add_argument(
        "--scenario",
        metavar="NAMES",
        help="run bundled hostile-internet scenarios (comma-separated "
        "names from scenario/library, e.g. sybil-stampede,churn-storm): "
        "each runs TWICE against the real serve stack on a virtual "
        "timeline — the SLO verdict must pass, the wall-plane announce "
        "latency must hold its budget, and the same-seed replay must be "
        "bit-identical",
    )
    ap.add_argument(
        "--swarm",
        action="store_true",
        help="also run the swarm wire-plane smoke: a throttled two-peer "
        "loopback download whose /v1/pipeline attribution must name the "
        "new recv stage limiting, /v1/swarm must report bounded "
        "per-peer telemetry (choke timeline, block-RTT p99, top-K + "
        "overflow), and a driven snub storm must fire exactly one "
        "flight dump",
    )
    ap.add_argument(
        "--seed",
        action="store_true",
        help="also run the seeder-plane smoke: one seeding client vs a "
        "crowd of raw-wire leechers dialing the port directly — every "
        "piece served bit-exact, the zero-copy egress counters "
        "(sendfile/preadv) non-zero on a contiguous layout, choke "
        "rounds rotating the optimistic slot, and /v1/swarm carrying "
        "the serving-side entries",
    )
    ap.add_argument(
        "--json",
        action="store_true",
        help="emit one JSON object after the checks (machine-readable)",
    )
    args = ap.parse_args(argv)
    global _JSON_MODE
    _JSON_MODE = args.json  # direct main() callers (tests, embedding)

    def emit_json() -> None:
        if not args.json:
            return
        import json

        fails = sum(1 for s, _, _ in _RESULTS if s == "FAIL")
        warns = sum(1 for s, _, _ in _RESULTS if s == "WARN")
        print(
            json.dumps(
                {
                    "ok": fails == 0,
                    "fails": fails,
                    "warns": warns,
                    # the documented contract (module docstring): 0 all
                    # PASS/WARN, 1 any FAIL — mirrored here so CI can
                    # read one field instead of re-deriving it
                    "exit_code": 1 if fails else 0,
                    "checks": [
                        {"status": s, "name": n, "detail": d}
                        for s, n, d in _RESULTS
                    ],
                }
            )
        )

    _RESULTS.clear()  # main() may run more than once per process (tests)
    _say("doctor: checking deps…")
    if not _check_deps():
        _say("\n1 FAIL — core dependencies missing")
        emit_json()  # the broken-environment case is where JSON matters most
        return 1
    from torrent_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    _check_device()
    _check_kernels()
    _check_native_io()
    if not args.skip_swarm:
        with tempfile.TemporaryDirectory(prefix="doctor_") as tmp:
            try:
                asyncio.run(asyncio.wait_for(_swarm_smoke(tmp), 90))
                _report("PASS", "loopback swarm", "256 KiB author→seed→download")
            except Exception as e:
                _report("FAIL", "loopback swarm", repr(e))
    try:
        detail = asyncio.run(asyncio.wait_for(_sched_smoke(), 30))
        _report("PASS", "verify scheduler", detail)
    except Exception as e:
        _report("FAIL", "verify scheduler", repr(e))
    if args.faults:
        try:
            detail = asyncio.run(asyncio.wait_for(_faults_smoke(), 30))
            _report("PASS", "fault tolerance", detail)
        except Exception as e:
            _report("FAIL", "fault tolerance", repr(e))
    if args.v2:
        try:
            # generous bound: interpret-mode compiles of two lane
            # geometries dominate (the kernel itself is milliseconds)
            detail = asyncio.run(asyncio.wait_for(_v2_smoke(), 120))
            _report("PASS", "v2 hash plane", detail)
        except Exception as e:
            _report("FAIL", "v2 hash plane", repr(e))
    if args.lint:
        try:
            detail = _lint_smoke()
            _report("PASS", "analysis plane", detail)
        except Exception as e:
            _report("FAIL", "analysis plane", repr(e))
    if args.trace:
        try:
            detail = asyncio.run(asyncio.wait_for(_trace_smoke(), 30))
            _report("PASS", "observability plane", detail)
        except Exception as e:
            _report("FAIL", "observability plane", repr(e))
    if args.bottleneck:
        with tempfile.TemporaryDirectory(prefix="doctor_bn_") as tmp:
            try:
                detail = asyncio.run(
                    asyncio.wait_for(_bottleneck_smoke(args.faults, tmp), 60)
                )
                _report("PASS", "pipeline ledger", detail)
            except Exception as e:
                _report("FAIL", "pipeline ledger", repr(e))
    if args.control:
        try:
            detail = asyncio.run(asyncio.wait_for(_control_smoke(), 60))
            _report("PASS", "scheduler autopilot", detail)
        except Exception as e:
            _report("FAIL", "scheduler autopilot", repr(e))
    if args.announce:
        try:
            detail = asyncio.run(asyncio.wait_for(_announce_smoke(), 30))
            _report("PASS", "announce plane", detail)
        except Exception as e:
            _report("FAIL", "announce plane", repr(e))
    if args.scenario:
        for scenario_name in [
            n.strip() for n in args.scenario.split(",") if n.strip()
        ]:
            try:
                detail = _scenario_smoke(scenario_name)
                _report("PASS", f"scenario {scenario_name}", detail)
            except Exception as e:
                _report("FAIL", f"scenario {scenario_name}", repr(e))
    if args.swarm:
        with tempfile.TemporaryDirectory(prefix="doctor_wire_") as tmp:
            try:
                detail = asyncio.run(asyncio.wait_for(_swarm_wire_smoke(tmp), 90))
                _report("PASS", "swarm wire plane", detail)
            except Exception as e:
                _report("FAIL", "swarm wire plane", repr(e))
    if args.seed:
        with tempfile.TemporaryDirectory(prefix="doctor_seed_") as tmp:
            try:
                detail = asyncio.run(asyncio.wait_for(_seed_smoke(tmp), 90))
                _report("PASS", "seeder plane", detail)
            except Exception as e:
                _report("FAIL", "seeder plane", repr(e))
    if args.slo:
        try:
            detail = asyncio.run(asyncio.wait_for(_slo_smoke(), 60))
            _report("PASS", "slo engine", detail)
        except Exception as e:
            _report("FAIL", "slo engine", repr(e))
    if args.fabric:
        with tempfile.TemporaryDirectory(prefix="doctor_fabric_") as tmp:
            try:
                # bounded by the workers' communicate(timeout) inside
                detail = _fabric_smoke(tmp)
                _report("PASS", "verify fabric", detail)
            except Exception as e:
                _report("FAIL", "verify fabric", repr(e))
    if args.byzantine:
        with tempfile.TemporaryDirectory(prefix="doctor_byz_") as tmp:
            try:
                # bounded by the workers' communicate(timeout) inside
                detail = _byzantine_smoke(tmp)
                _report("PASS", "byzantine fabric", detail)
            except Exception as e:
                _report("FAIL", "byzantine fabric", repr(e))
    if args.fleet:
        with tempfile.TemporaryDirectory(prefix="doctor_fleet_") as tmp:
            try:
                # bounded by the poll deadline + communicate(timeout)
                detail = _fleet_smoke(tmp)
                _report("PASS", "fleet observability", detail)
            except Exception as e:
                _report("FAIL", "fleet observability", repr(e))
    try:
        asyncio.run(asyncio.wait_for(_bridge_smoke(), 30))
        _report("PASS", "bridge", "/v1/digests round-trip")
    except Exception as e:
        _report("FAIL", "bridge", repr(e))

    fails = sum(1 for s, _, _ in _RESULTS if s == "FAIL")
    warns = sum(1 for s, _, _ in _RESULTS if s == "WARN")
    _say(f"\n{len(_RESULTS)} checks: {fails} FAIL, {warns} WARN")
    emit_json()
    return 1 if fails else 0


if __name__ == "__main__":  # pragma: no cover - manual entrypoint
    raise SystemExit(main())
