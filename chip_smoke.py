#!/usr/bin/env python3
"""Chip smoke: the quickest proof that torrent-tpu still starts on the chip.

    python chip_smoke.py                # on a machine with a TPU
    python chip_smoke.py --cpu-dry-run  # same phases, tiny, CPU, interpret

One process, no arguments, no network, data generated from a seed into a
temporary directory. It drives the verify path once through the entry
points a user calls — the two Pallas kernels at their published
geometries, the library flow (author → parse → recheck → corrupt →
recheck) at the two reference fixture geometries, the HTTP bridge, BEP 52
authoring and recheck, on a multi-chip host the sharded recheck, and a
seed-to-leech swarm transfer over localhost whose pieces are judged on the
leecher's ingest scheduler — and checks every answer against ``hashlib``.

Stdout is two lines, each one JSON object. The first is the report: the
versions, the compile-cache directory, and per phase ``ok`` / wall seconds
/ compile seconds / bytes plus the kernel each entry point actually ran.
The last is the verdict, these keys and no others, the device as JAX
reports it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Exit code 0 only if every phase passed. It exits non-zero at once,
printing no result, when the platform is not ``tpu``: nothing here may
pass on a silent CPU path. The timings are smoke timings, not benchmark
metrics.

``--cpu-dry-run`` (an argument, never an environment variable) exists for
the tier-1 test and for debugging before chip time is spent; its report
says ``"dry_run": true``.
"""

from __future__ import annotations

import argparse
import asyncio
import functools
import hashlib
import importlib.metadata
import inspect
import json
import os
import sys
import tempfile
import time

import numpy as np

SEED = 20260926
MOSAIC_CALL = "tpu_custom_call"  # what a Pallas kernel compiles to via Mosaic

# The sizes a run uses. REAL is the published shapes; DRY keeps every code
# path and cuts every size so interpret-mode kernels finish in seconds.
REAL = {
    # SHA-1 Pallas geometries: BEP 3 block, the two reference fixtures,
    # BASELINE config 4 (tile_sub 32 / 32 / 16 / 8)
    "sha1_piece_lengths": (16 << 10, 256 << 10, 512 << 10, 1 << 20),
    "sha256_leaf": 16 << 10,
    "merkle_trees": (512, 64),  # 512 pieces of 64 leaves (1 MiB pieces)
    # reference fixtures: singlefile.torrent / multifile.torrent
    "single": (447_135_744, 256 << 10, 1706),
    "multi": (972_283_904, 512 << 10, 1855),
    "bridge": (512, 256 << 10, 4),  # pieces, piece length, requests
    "stream_leaves": 2048,
    "v2": (1 << 30, 1 << 20),  # file bytes, piece length: 65,536 leaves
    "shard_piece_length": 32 << 10,
    # swarm transfer: payload bytes, piece length, ingest verify batch
    # (None = TorrentConfig's default, what a user's client runs)
    "session": (32 << 20, 256 << 10, None),
}
DRY = {
    "sha1_piece_lengths": (1 << 10, 2 << 10),
    "sha256_leaf": 1 << 10,
    "merkle_trees": (8, 4),
    "single": (13 * (4 << 10) + 1000, 4 << 10, 14),
    "multi": (21 * (8 << 10) + 4096, 8 << 10, 22),
    "bridge": (24, 2 << 10, 3),
    "stream_leaves": 40,
    "v2": (40 * (16 << 10), 64 << 10),
    "shard_piece_length": 1 << 10,
    "session": (11 * (32 << 10) + 999, 32 << 10, 4),
}
DRY_REDUCED = [
    "every size cut to KiB scale (see chip_smoke.DRY)",
    "sha1 geometries 1/2 KiB instead of 16 KiB..1 MiB: tile_sub stays 32",
    "kernels run in Pallas interpret mode on the CPU, not through Mosaic",
]
REAL_REDUCED = [
    "phase 1 launches one batch per geometry",
    "library flow at the two reference fixture sizes (0.45 and 0.97 GB), "
    "not BASELINE.json config 4's 100 GiB",
    "live session: one seed, one leech, 32 MiB over localhost",
]


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


class CompileClock:
    """Backend-compile seconds and persistent-cache traffic, from JAX's own
    monitoring events (a cache hit costs its retrieval time only)."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.hits = 0
        self.writes = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


# ------------------------------------------------------------ data helpers


def write_random(path: str, nbytes: int, rng: np.random.Generator) -> None:
    with open(path, "wb") as f:
        left = nbytes
        while left > 0:
            n = min(left, 64 << 20)
            f.write(rng.bytes(n))
            left -= n


def flip_byte(path: str, offset: int) -> None:
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0xFF]))


def stamped_batch(rows: int, row_len: int, rng: np.random.Generator):
    """A padded batch of distinct rows: one random base row, the row id in
    the first 8 bytes, the last row cut short so a ragged mask runs."""
    from torrent_tpu.ops.padding import alloc_padded, pad_in_place

    padded, view = alloc_padded(rows, row_len)
    view[:] = rng.integers(0, 256, row_len, dtype=np.uint8)[None, :]
    ids = np.arange(rows, dtype=">u8")
    view[:, :8] = ids.view(np.uint8).reshape(rows, 8)
    lengths = np.full(rows, row_len, dtype=np.int64)
    lengths[-1] = row_len - min(row_len // 3, 12345)
    view[-1, lengths[-1]:] = 0
    nblocks = pad_in_place(padded, lengths)
    return padded, view, lengths, nblocks


def check_rows(words, view, lengths, rows, algo) -> None:
    from torrent_tpu.ops.padding import words_to_digests

    got = words_to_digests(np.asarray(words)[list(rows)])
    for r, d in zip(rows, got):
        want = algo(view[r, : lengths[r]].tobytes()).digest()
        assert d == want, f"row {r}: device digest != hashlib"


# ----------------------------------------------------------------- phases


def phase_device(ctx) -> dict:
    import jax

    from torrent_tpu.models.verifier import DEFAULT_TILE_BYTES

    stats = jax.devices()[0].memory_stats() or {}
    return {
        "bytes": 0,
        "memory_limit_bytes": stats.get("bytes_limit"),
        # what the code assumes of HBM, for comparison with the limit
        "assumed": {
            "TORRENT_TPU_TILE_BYTES": DEFAULT_TILE_BYTES,
        },
    }


def phase_kernels(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from torrent_tpu.models.merkle import merkle_root, words32_to_digests
    from torrent_tpu.models.verifier import TPUVerifier
    from torrent_tpu.ops.sha256_pallas import (
        SUB_TILE_ROWS,
        sha256_pieces_pallas,
        tile_sub_for_rows,
    )

    sizes, rng, ndev = ctx["sizes"], ctx["rng"], ctx["device"]["count"]
    want_mosaic = not ctx["dry"]
    launches, nbytes = [], 0

    def note(name, mosaic, **kw):
        assert mosaic == want_mosaic, (
            f"{name}: compiled step {'has' if mosaic else 'lacks'} a Mosaic call"
        )
        launches.append({"launch": name, "mosaic": mosaic, **kw})
        log(f"  kernel {name}: ok {kw}")

    for plen in sizes["sha1_piece_lengths"]:
        v = TPUVerifier(piece_length=plen, batch_size=1, backend="pallas")
        b = v.batch_size  # one tile row per device
        padded, view, lengths, nblocks = stamped_batch(b, plen, rng)
        words = v.digest_batch(padded, nblocks)
        assert words.shape == (b, 5)
        check_rows(
            words, view, lengths,
            sorted({0, b - 1, *(k * b // ndev for k in range(ndev))}),
            hashlib.sha1,
        )
        # the step digest_batch just ran, lowered on arguments uploaded
        # the way it uploads them: the program inspected is the one that ran
        if v._use_flat(padded):
            lowered = v._digest_step_flat.lower(v._put_flat(padded), nblocks)
        else:
            lowered = v._digest_step.lower(padded, nblocks)
        text = lowered.compile().as_text()
        note(
            f"sha1/{plen}", MOSAIC_CALL in text,
            tile_sub=v.tile_sub, rows=b, mesh=v.mesh.size,
        )
        nbytes += int(lengths.sum())
        del padded, view

    def sha256_launch(name, rows, row_len, ts):
        nonlocal nbytes
        padded, view, lengths, nblocks = stamped_batch(rows, row_len, rng)
        fn = jax.jit(functools.partial(sha256_pieces_pallas, tile_sub=ts))
        d, nb = jnp.asarray(padded.view(np.uint32)), jnp.asarray(nblocks)
        compiled = fn.lower(d, nb).compile()
        words = np.asarray(compiled(d, nb))
        assert words.shape == (rows, 8)
        check_rows(words, view, lengths, (0, rows // 2, rows - 1), hashlib.sha256)
        note(name, MOSAIC_CALL in compiled.as_text(), tile_sub=ts, rows=rows)
        nbytes += int(lengths.sum())

    leaf = sizes["sha256_leaf"]
    sha256_launch(f"sha256/{leaf}", 32 * 128, leaf, 32)
    for k in (1, 2, 3):  # the scheduler's sub-tile buckets: 8 / 16 / 24
        rows = k * SUB_TILE_ROWS
        sha256_launch(f"sha256/{leaf}/rows{rows}", rows, leaf, tile_sub_for_rows(rows))
    sha256_launch("sha256/pair64", 32 * 128, 64, 32)

    # merkle_root: fused all-levels program off-CPU, per-level loop on CPU
    n_trees, n_leaves = sizes["merkle_trees"]
    grid = rng.integers(0, 2**32, (n_trees, n_leaves, 8), dtype=np.uint32)
    roots = words32_to_digests(merkle_root(grid))
    for t in (0, n_trees - 1):
        level = words32_to_digests(grid[t])
        while len(level) > 1:
            level = [
                hashlib.sha256(level[i] + level[i + 1]).digest()
                for i in range(0, len(level), 2)
            ]
        assert roots[t] == level[0], f"merkle_root tree {t} != hashlib fold"
    branch = "per_level" if jax.default_backend() == "cpu" else "fused"
    assert (branch == "fused") == want_mosaic
    launches.append({"launch": "merkle_root", "branch": branch, "trees": n_trees})
    return {"bytes": nbytes, "launches": launches}


def _piece_span(start: int, length: int, plen: int) -> set[int]:
    return set(range(start // plen, (start + length - 1) // plen + 1))


def _library_flow(ctx, tmp, name, total, plen, n_pieces, file_sizes) -> dict:
    """author → parse → recheck (CLI default backend, then pallas) →
    corrupt one byte → delete one file; every bitfield equals hasher=cpu."""
    from torrent_tpu.codec.metainfo import parse_metainfo
    from torrent_tpu.models.verifier import TPUVerifier
    from torrent_tpu.ops.sha1_pallas import _auto_interpret
    from torrent_tpu.parallel.verify import verify_pieces, verify_pieces_tpu
    from torrent_tpu.storage.storage import FsStorage, Storage
    from torrent_tpu.tools.make_torrent import make_torrent

    rng = ctx["rng"]
    root = os.path.join(tmp, f"lib_{name}")
    if file_sizes is None:  # single file: the payload IS the torrent
        os.makedirs(root)
        target = os.path.join(root, f"{name}.bin")
        write_random(target, total, rng)
        files = [(target, 0, total)]
    else:
        target = os.path.join(root, name)
        os.makedirs(target)
        files, pos = [], 0
        for i, size in enumerate(file_sizes):
            path = os.path.join(target, f"f{i}.bin")
            write_random(path, size, rng)
            files.append((path, pos, size))
            pos += size
        assert pos == total
    meta = parse_metainfo(
        make_torrent(
            target, "http://smoke.invalid/announce", piece_length=plen, hasher="tpu"
        )
    )
    assert meta is not None, "authored torrent failed to parse"
    info = meta.info
    assert (info.length, info.piece_length, info.num_pieces) == (total, plen, n_pieces)

    # neither make_torrent nor the CLI's verify names a backend: what
    # they run is the default of the function they call
    def default_of(fn):
        return inspect.signature(fn).parameters["backend"].default

    default_backend = default_of(verify_pieces_tpu)
    calls = {  # how the CLI calls it, then the same function's keyword
        default_backend: {},
        "pallas": {"backend": "pallas"},
    }

    def recheck(expect_bad: set[int], what: str) -> None:
        # fresh FsStorage per pass: it caches fds, and a deleted file
        # stays readable through a cached handle
        want = np.ones(n_pieces, dtype=bool)
        want[sorted(expect_bad)] = False
        cpu = verify_pieces(Storage(FsStorage(root), info), info, hasher="cpu")
        assert np.array_equal(cpu, want), f"{name} {what}: hasher=cpu bitfield wrong"
        for backend, kw in calls.items():
            got = verify_pieces(
                Storage(FsStorage(root), info), info, hasher="tpu", **kw
            )
            assert got.shape == (n_pieces,) and got.dtype == bool
            bad = sorted(np.flatnonzero(got != want).tolist())
            assert not bad, f"{name} {what} backend={backend}: pieces {bad[:8]} differ"

    recheck(set(), "clean")
    path, start, size = files[len(files) // 2]
    off = size // 2
    flip_byte(path, off)
    corrupt = {(start + off) // plen}
    recheck(corrupt, "one byte corrupted")
    deleted: set[int] = set()
    if len(files) > 1:
        path, start, size = files[1]
        os.remove(path)
        deleted = _piece_span(start, size, plen)
        recheck(corrupt | deleted, "one file deleted")
    return {
        "pieces": n_pieces,
        "piece_length": plen,
        "files": len(files),
        "last_piece_bytes": total - (n_pieces - 1) * plen,
        "flipped_by_corruption": sorted(corrupt),
        "flipped_by_deletion": len(deleted),
        # the backend each recheck call used, and whether "pallas" meant
        # Mosaic (False = interpret mode, dry run only)
        "recheck_backends": list(calls),
        "author_backend": default_of(TPUVerifier.__init__),
        "pallas_is_mosaic": not _auto_interpret(),
        # device passes over the payload: author + 3 (or 2) x 2 rechecks
        "device_bytes": total * (1 + 2 * (3 if len(files) > 1 else 2)),
    }


def phase_library(ctx) -> dict:
    sizes = ctx["sizes"]
    out = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_lib_") as tmp:
        total, plen, n = sizes["single"]
        out["single"] = _library_flow(ctx, tmp, "single", total, plen, n, None)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_lib_") as tmp:
        total, plen, n = sizes["multi"]
        # six uneven files, odd byte counts so pieces span every boundary
        cuts = [int(total * f) | 1 for f in (0.05, 0.21, 0.30, 0.11, 0.17)]
        out["multi"] = _library_flow(
            ctx, tmp, "multi", total, plen, n, cuts + [total - sum(cuts)]
        )
    assert out["multi"]["last_piece_bytes"] < out["multi"]["piece_length"]
    assert out["multi"]["pallas_is_mosaic"] == (not ctx["dry"])
    out["bytes"] = out["single"].pop("device_bytes") + out["multi"].pop("device_bytes")
    return out


async def _http(port: int, method: str, path: str, headers=None, body: bytes = b""):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    head = [f"{method} {path} HTTP/1.1", "Host: smoke", f"Content-Length: {len(body)}"]
    head += [f"{k}: {v}" for k, v in (headers or {}).items()]
    writer.write(("\r\n".join(head) + "\r\n\r\n").encode() + body)
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    clen = 0
    while (line := await reader.readline()) not in (b"\r\n", b""):
        if line.lower().startswith(b"content-length:"):
            clen = int(line.split(b":", 1)[1])
    resp = await reader.readexactly(clen)
    writer.close()
    return status, resp


async def _bridge(ctx) -> dict:
    from torrent_tpu.bridge.service import BridgeServer
    from torrent_tpu.codec.bencode import bdecode, bencode

    n_pieces, plen, n_req = ctx["sizes"]["bridge"]
    n_leaves, leaf = ctx["sizes"]["stream_leaves"], ctx["sizes"]["sha256_leaf"]
    rng = ctx["rng"]
    svc = await BridgeServer("127.0.0.1", port=0, hasher="tpu").start()
    try:
        # /v1/info: the device probe runs off-loop; wait for it to land
        deadline = time.monotonic() + 120
        while True:
            status, resp = await _http(svc.port, "GET", "/v1/info")
            info = bdecode(resp)
            if info[b"devices"] or time.monotonic() > deadline:
                break
            await asyncio.sleep(0.2)
        assert status == 200
        named = {
            "platform": info[b"platform"].decode(),
            "kind": info[b"device_kind"].decode(),
            "count": info[b"devices"],
        }
        assert named == ctx["device"], f"/v1/info names {named}"

        per = n_pieces // n_req
        pieces = [rng.bytes(plen) for _ in range(per * n_req)]
        pieces[-1] = pieces[-1][: plen // 3 + 1]  # ragged tail
        want = [hashlib.sha1(p).digest() for p in pieces]
        for r in range(n_req):
            chunk = pieces[r * per : (r + 1) * per]
            status, resp = await _http(
                svc.port, "POST", "/v1/digests", body=bencode({b"pieces": chunk})
            )
            assert status == 200, f"/v1/digests -> {status} {resp[:200]!r}"
            assert bdecode(resp)[b"digests"] == want[r * per : (r + 1) * per]
        expected = list(want[:per])
        expected[per // 2] = b"\x00" * 20
        status, resp = await _http(
            svc.port, "POST", "/v1/verify",
            body=bencode({b"pieces": pieces[:per], b"expected": expected}),
        )
        assert status == 200, f"/v1/verify -> {status}"
        ok = bdecode(resp)[b"ok"]
        assert [i for i in range(per) if not ok[i]] == [per // 2]

        # /v1/stream/*: 16 KiB leaves through the scheduler's sha256 lane
        leaves = [rng.bytes(leaf) for _ in range(n_leaves)]
        frames = b"".join(len(p).to_bytes(4, "big") + p for p in leaves)
        status, resp = await _http(
            svc.port, "POST", "/v1/stream/digests",
            {"X-Piece-Length": str(leaf), "X-Hash-Algo": "sha256"}, frames,
        )
        assert status == 200, f"/v1/stream/digests -> {status}"
        body = bdecode(resp)
        assert body[b"digests"] == [hashlib.sha256(p).digest() for p in leaves]
        assert not body.get(b"failed", 0)

        snap = svc.sched.metrics_snapshot()
    finally:
        svc.close()
        await svc.wait_closed()  # closes the scheduler too
    lanes = {
        key: {k: st[k] for k in ("backend", "kernel", "target", "launches")}
        for key, st in snap["lane_stats"].items()
    }
    breakers = {key: b["state"] for key, b in snap["breakers"].items()}
    assert snap["cpu_fallback_launches"] == 0, snap["cpu_fallback_launches"]
    assert set(breakers.values()) == {"closed"}, breakers
    assert snap["staging"]["outstanding"] == 0, snap["staging"]
    assert snap["launch_failures"] == 0 and snap["failed_pieces"] == 0
    sha256_kernel = lanes[f"sha256/{leaf}"]["kernel"]
    assert sha256_kernel == ("scan" if ctx["dry"] else "pallas"), lanes
    return {
        "bytes": sum(map(len, pieces)) + sum(map(len, pieces[:per])) + n_leaves * leaf,
        "info": named,
        "lanes": lanes,
        "breakers": breakers,
        "cpu_fallback_launches": snap["cpu_fallback_launches"],
        "staging_outstanding": snap["staging"]["outstanding"],
    }


def phase_bridge(ctx) -> dict:
    return asyncio.run(asyncio.wait_for(_bridge(ctx), 900))


def _leaf_launches() -> dict:
    from torrent_tpu.models.v2 import LEAF_LAUNCH_HIST
    from torrent_tpu.obs.hist import histograms

    return {
        k: histograms().get(*LEAF_LAUNCH_HIST, kernel=k).snapshot()[1]
        for k in ("pallas", "scan")
    }


def phase_v2(ctx) -> dict:
    from torrent_tpu.models.v2 import build_v2, verify_v2

    total, plen = ctx["sizes"]["v2"]
    before = _leaf_launches()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_v2_") as tmp:
        path = os.path.join(tmp, "v2.bin")
        write_random(path, total, ctx["rng"])
        files = [(("v2.bin",), path)]
        dev = build_v2(files, name="v2.bin", piece_length=plen, hasher="tpu")
        cpu = build_v2(files, name="v2.bin", piece_length=plen, hasher="cpu")
        assert dev.info.files[0].pieces_root == cpu.info.files[0].pieces_root
        assert dev.piece_layers == cpu.piece_layers, "piece layers differ from hasher=cpu"
        n_pieces = dev.info.files[0].num_pieces(plen)

        def read_file(p):
            return path

        ok = verify_v2(read_file, dev, hasher="tpu")[("v2.bin",)]
        assert ok.shape == (n_pieces,) and ok.all(), "clean v2 recheck failed"
        bad_piece = n_pieces // 2
        flip_byte(path, bad_piece * plen + 7)
        ok = verify_v2(read_file, dev, hasher="tpu")[("v2.bin",)]
        assert np.flatnonzero(~ok).tolist() == [bad_piece], "v2 corruption not isolated"
        ref = verify_v2(read_file, cpu, hasher="cpu")[("v2.bin",)]
        assert np.array_equal(ok, ref)
    after = _leaf_launches()
    launched = {k: after[k] - before[k] for k in after}
    # every leaf launch of this phase ran the same kernel, and on the chip
    # that kernel is Mosaic: a drop to the scan backend must not pass
    assert launched["scan" if not ctx["dry"] else "pallas"] == 0, launched
    assert sum(launched.values()) > 0
    return {
        "bytes": 3 * total,
        "pieces": n_pieces,
        "leaves": -(-total // 16384),
        "leaf_launches": launched,
    }


def phase_shards(ctx) -> dict:
    """More than one device: the sharded recheck really uses every chip."""
    import jax
    import jax.numpy as jnp

    from torrent_tpu.codec.metainfo import parse_metainfo
    from torrent_tpu.models.v2 import _make_leaf_fn
    from torrent_tpu.models.verifier import TPUVerifier
    from torrent_tpu.ops.sha256_pallas import sha256_pieces_pallas
    from torrent_tpu.parallel.verify import verify_pieces
    from torrent_tpu.storage.storage import FsStorage, Storage
    from torrent_tpu.tools.make_torrent import make_torrent

    ndev = ctx["device"]["count"]
    local = set(jax.local_devices())
    plen = ctx["sizes"]["shard_piece_length"]
    v = TPUVerifier(piece_length=plen, batch_size=1, backend="pallas")
    b = v.batch_size  # one full tile of live rows per device
    assert v.mesh.size == ndev and b % ndev == 0
    padded, view, lengths, nblocks = stamped_batch(b, plen, ctx["rng"])
    handle = v.upload_batch(padded)
    words = v.digest_uploaded(handle, nblocks)
    for arr, what in ((handle[1], "input"), (words, "digests")):
        shards = arr.addressable_shards
        assert {s.device for s in shards} == local, f"{what} not on every device"
        assert all(s.data.shape[0] == b // ndev for s in shards), (
            f"{what}: a device holds more than its share of the batch"
        )
    check_rows(
        words, view, lengths,
        sorted({b - 1, *(k * b // ndev for k in range(ndev))}), hashlib.sha1,
    )

    total = b * plen - plen // 2  # every device's tile live, short last piece
    with tempfile.TemporaryDirectory(prefix="chip_smoke_shard_") as tmp:
        path = os.path.join(tmp, "shard.bin")
        write_random(path, total, ctx["rng"])
        info = parse_metainfo(
            make_torrent(path, "http://smoke.invalid/announce", piece_length=plen)
        ).info
        flip_byte(path, (b // ndev + 3) * plen)  # a piece in the second shard
        got = verify_pieces(
            Storage(FsStorage(tmp), info), info, hasher="tpu",
            backend="pallas", batch_size=b,
        )
        cpu = verify_pieces(Storage(FsStorage(tmp), info), info, hasher="cpu")
    assert np.array_equal(got, cpu) and np.flatnonzero(~got).tolist() == [b // ndev + 3]

    # which paths use one chip only, read off the arrays they produce:
    # the v2 leaf path and the scheduler's sha256 plane both put their
    # batch with jnp.asarray and no sharding, then call these functions
    leaf_rows = 1024
    lp, _, _, lnb = stamped_batch(leaf_rows, ctx["sizes"]["sha256_leaf"], ctx["rng"])
    leaf_fn, _kernel = _make_leaf_fn(leaf_rows, "auto")
    leaf_out = leaf_fn(jnp.asarray(lp), jnp.asarray(lnb))
    plane_out = sha256_pieces_pallas(
        jnp.asarray(lp.view(np.uint32)), jnp.asarray(lnb), tile_sub=8
    )
    return {
        "bytes": int(lengths.sum()) + total,
        "rows_per_device": b // ndev,
        "sharded_recheck_devices": len(local),
        "single_chip_paths": {
            "v2_leaf_path_devices": len(leaf_out.devices()),
            "sched_sha256_plane_devices": len(plane_out.devices()),
        },
    }


def _ingest_flushes(s: dict) -> dict:
    """How the leecher's downloaded pieces were judged so far, from its
    ingest scheduler's snapshot: launches of the device planes, and every
    road to hashlib (a failed submission the session judged itself, a
    launch of a lane degraded to the hashlib plane)."""
    from torrent_tpu.obs.hist import histograms
    from torrent_tpu.session.torrent import _H_INGEST_VERIFY

    fell_back = histograms().get(*_H_INGEST_VERIFY, plane="hashlib_fallback").snapshot()[1]
    return {
        "device": s["launches"] - s["cpu_fallback_launches"],
        "hashlib_fallback": fell_back + s["cpu_fallback_launches"],
    }


async def _session(ctx, tmp) -> dict:
    from torrent_tpu.codec.metainfo import parse_metainfo
    from torrent_tpu.parallel.verify import verify_pieces
    from torrent_tpu.server.in_memory import run_tracker
    from torrent_tpu.server.tracker import ServeOptions
    from torrent_tpu.session.client import Client, ClientConfig
    from torrent_tpu.session.torrent import TorrentConfig, TorrentState
    from torrent_tpu.storage.storage import FsStorage, Storage
    from torrent_tpu.tools.make_torrent import make_torrent

    total, plen, verify_batch = ctx["sizes"]["session"]
    seed_dir, leech_dir = os.path.join(tmp, "seed"), os.path.join(tmp, "leech")
    os.makedirs(seed_dir)
    os.makedirs(leech_dir)
    write_random(os.path.join(seed_dir, "swarm.bin"), total, ctx["rng"])
    server, pump = await run_tracker(
        ServeOptions(http_port=0, udp_port=None, host="127.0.0.1", interval=1)
    )
    meta = parse_metainfo(
        make_torrent(
            os.path.join(seed_dir, "swarm.bin"),
            f"http://127.0.0.1:{server.http_port}/announce",
            piece_length=plen,
        )
    )
    def client():
        kw = {} if verify_batch is None else {"verify_batch_size": verify_batch}
        return Client(
            ClientConfig(
                host="127.0.0.1", hasher="tpu",
                torrent=TorrentConfig(hasher="tpu", announce_retry=1.0, **kw),
            )
        )

    seed, leech = client(), client()
    await seed.start()
    await leech.start()
    before = _ingest_flushes(leech.ingest_scheduler.metrics_snapshot())
    try:
        # the seed's add() rechecks its directory on the device (no resume
        # file yet) and must come up seeding every piece
        t_seed = await seed.add(meta, seed_dir)
        assert t_seed.state == TorrentState.SEEDING, t_seed.status()
        t_leech = await leech.add(meta, leech_dir)
        assert t_leech.verifier is not None and t_leech.bitfield.count() == 0
        await asyncio.wait_for(t_leech.on_complete.wait(), 600)
        verifier = t_leech.verifier
        ingest = leech.ingest_scheduler.metrics_snapshot()
        flushes = {k: v - before[k] for k, v in _ingest_flushes(ingest).items()}
    finally:
        await seed.close()
        await leech.close()
        server.close()
        await asyncio.wait_for(pump, 10)
    # what the leech wrote is the payload, piece for piece, by hashlib
    on_disk = verify_pieces(Storage(FsStorage(leech_dir), meta.info), meta.info, hasher="cpu")
    assert on_disk.all(), f"leech wrote {int((~on_disk).sum())} bad pieces"
    # every downloaded piece went through the leecher's scheduler (tenant
    # `ingest`, one more for the lane's warm-up), on device launches alone
    judged = ingest["tenants"]["ingest"]["served_pieces"] - 1
    assert judged >= meta.info.num_pieces, f"{judged} of {meta.info.num_pieces} pieces judged on the scheduler"
    assert flushes["device"] > 1, "no ingest launch reached the device"
    assert flushes["hashlib_fallback"] == 0, flushes
    kernels = {lane["kernel"] for lane in ingest["lane_stats"].values()}
    assert "hashlib" not in kernels, kernels
    return {
        "bytes": 2 * total,  # the seed's recheck + the leech's ingest
        "pieces": meta.info.num_pieces,
        "piece_length": plen,
        "verify_batch_size": verifier.batch_size,
        "backend": verifier.backend,
        "ingest_flushes": flushes,
        "ingest_pieces_per_launch": round(judged / (flushes["device"] - 1), 2),
    }


def phase_session(ctx) -> dict:
    """One seed, one leech, a tracker, all on localhost in this process:
    every piece the leech completes is judged on its ingest scheduler's
    device lane, none by hashlib."""
    with tempfile.TemporaryDirectory(prefix="chip_smoke_swarm_") as tmp:
        return asyncio.run(asyncio.wait_for(_session(ctx, tmp), 900))


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-dry-run", action="store_true",
        help="same phases at a tiny size on the CPU, kernels in interpret mode",
    )
    args = ap.parse_args(argv)
    t_start = time.monotonic()

    import jax

    if args.cpu_dry_run:
        jax.config.update("jax_platforms", "cpu")
    from torrent_tpu.native.io_engine import native_available
    from torrent_tpu.utils.device import device_info, enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    device = device_info()
    if device["platform"] != "tpu" and not args.cpu_dry_run:
        print(
            f"chip_smoke: JAX found no TPU (platform={device['platform']!r}); "
            "this run proves nothing about the chip. --cpu-dry-run runs the "
            "phases on the CPU on purpose.",
            file=sys.stderr,
        )
        return 3

    ctx = {
        "dry": args.cpu_dry_run,
        "sizes": DRY if args.cpu_dry_run else REAL,
        "rng": np.random.default_rng(SEED),
        "device": device,
    }
    plan = [
        ("0_device", phase_device),
        ("1_kernels", phase_kernels),
        ("2_library", phase_library),
        ("3_bridge", phase_bridge),
        ("4_v2", phase_v2),
    ]
    if device["count"] > 1:
        plan.append(("5_shards", phase_shards))
    plan.append(("6_session", phase_session))
    phases = {}
    for name, fn in plan:
        log(f"phase {name} ...")
        t0, c0 = time.monotonic(), clock.seconds
        try:
            res = {"ok": True, **fn(ctx)}
        except Exception as e:  # a failed phase is reported; the rest still run
            import traceback

            traceback.print_exc(file=sys.stderr)
            res = {"ok": False, "error": f"{type(e).__name__}: {e}"[:400]}
        res["wall_s"] = round(time.monotonic() - t0, 2)
        res["compile_s"] = round(clock.seconds - c0, 2)
        phases[name] = res
        log(f"phase {name}: ok={res['ok']} wall={res['wall_s']}s compile={res['compile_s']}s")

    fallbacks = {
        # the three ways the service carries on without the device, each
        # counted where it happens and required to be zero here
        "sched_cpu_fallback_launches": phases["3_bridge"].get("cpu_fallback_launches"),
        "session_ingest_hashlib_fallbacks": (
            phases["6_session"].get("ingest_flushes") or {}
        ).get("hashlib_fallback"),
        "v2_leaf_scan_launches": (phases["4_v2"].get("leaf_launches") or {}).get("scan"),
    }
    ok = all(p["ok"] for p in phases.values())
    if not args.cpu_dry_run:
        ok = ok and all(v == 0 for v in fallbacks.values())

    def version(pkg):
        try:
            return importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            return None

    report = {
        "dry_run": args.cpu_dry_run,
        "versions": {
            "python": sys.version.split()[0],
            "jax": jax.__version__,
            "jaxlib": version("jaxlib"),
            "libtpu": version("libtpu"),
            "numpy": np.__version__,
        },
        "compile_cache": {
            "dir": cache_dir,
            "hits": clock.hits,
            "writes": clock.writes,
            "compile_s": round(clock.seconds, 2),
        },
        # False = Storage.read_batch fell to Python preads (no g++?)
        "native_io": native_available(),
        "seed": SEED,
        "reduced": DRY_REDUCED if args.cpu_dry_run else REAL_REDUCED,
        "fallbacks": fallbacks,
        "phases": phases,
        "wall_s": round(time.monotonic() - t_start, 2),
    }
    print(json.dumps({"report": report}))
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
