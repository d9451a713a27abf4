"""Headline benchmark: SHA1 full-recheck throughput, TPU vs CPU baseline.

Workload = BASELINE.md primary metric: pieces/sec on a full re-verify of a
synthetic torrent with 256 KiB pieces (the reference's singlefile.torrent
geometry, metainfo_test.ts:26-29). The CPU baseline is streaming hashlib
(OpenSSL — strictly faster than the reference's Deno WebCrypto path, so
speedups reported here are conservative), measured over the FULL piece
population (pure hash time, excluding synthetic-payload assembly — again
conservative: the TPU side's timing includes its IO).

Two numbers are reported for the recheck configs:

- ``value`` / ``vs_baseline`` — the **hash plane**: masked SHA1 chain +
  on-device digest compare over device-resident batches (distinct inputs,
  serially executed, final result fetched). This is the framework's
  subsystem throughput and what transfers to any TPU host.
- ``end_to_end_pps`` / ``end_to_end_vs_baseline`` — the full pipeline
  including host→device transfer, whose measured bandwidth is reported
  beside it (``h2d_mib_s``, probed each run).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...},
naming the device as JAX reports it (``platform``, ``device_kind``,
``device_count``).

One process: it imports JAX, holds the chip and measures. It fails — a
non-zero exit and no record — when JAX lands on the CPU without
``JAX_PLATFORMS=cpu`` having asked for it, and on any error in the
measured path; it never re-measures on another backend.

Env knobs: BENCH_TOTAL_MB (default 1024), BENCH_BATCH (default:
auto-sized to ~2 GiB of staging per dispatch — 8192 rows at 256 KiB
pieces, halving as pieces grow), BENCH_BACKEND (jax|pallas, default
pallas on a TPU and jax on the CPU), BENCH_PIECE_KB (default 256),
BENCH_E2E_MB (cap the end-to-end pass of huge configs; plane + baseline
stay full-scale), BENCH_RUNS (timed passes, default 3).

Micro-rung knobs: BENCH_NBATCH=1 stages a single resident batch;
BENCH_DISPATCHES=N times N dispatches over the resident batch(es), each
with a distinct salted expected-digest operand; BENCH_H2D_MB shrinks
the bandwidth probe; BENCH_BASELINE_CACHE=path (opt-in) loads/saves the
CPU baseline rate so a run need not re-hash a 100 GiB population the
host already measured.

BENCH_CONFIG selects the measured workload (BASELINE.md configs; every
mode prints one JSON line):
- ``headline`` (default) — config 1/4 shape: synthetic single-file full
  recheck, 256 KiB pieces (BENCH_PIECE_KB=1024 BENCH_TOTAL_MB=102400
  BENCH_BATCH=4096 for the 100 GiB config at documented scale)
- ``multifile``  — config 2: recheck with pieces spanning file boundaries
- ``author``     — config 3: make_torrent-style authoring digests
  (BENCH_TOTAL_MB=10240 for the documented 10 GiB scale)
- ``bulk``       — config 5 at single-host scale: N torrents validated
  concurrently through one shared verifier (BENCH_BULK_N, default 8)
- ``v2``         — bonus BEP 52 metric: SHA-256 leaf hashing + merkle
  piece roots vs a full hashlib leaf+merkle baseline
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import time

import numpy as np


def _env_geometry():
    total_mb = int(os.environ.get("BENCH_TOTAL_MB", "1024"))
    # Default batch 8192 at 256 KiB: chosen on a retired setup, not
    # measured on this one. It keeps 2 distinct timed dispatches resident
    # within the device-plane budget below.
    config = os.environ.get("BENCH_CONFIG", "headline")
    plen = int(os.environ.get("BENCH_PIECE_KB", "256")) * 1024
    batch_env = os.environ.get("BENCH_BATCH")
    if batch_env:
        batch = int(batch_env)
    else:
        # auto-size to ~2 GiB of staging per dispatch (the measured-best
        # dispatch size at 256 KiB; bigger pieces scale the batch down so
        # an author batch of 1 MiB pieces doesn't allocate 8.6 GB rows).
        from torrent_tpu.ops.padding import padded_len_for

        padded = padded_len_for(plen)
        batch = 1024
        while batch < 8192 and 2 * batch * padded <= (2 << 30) + (1 << 28):
            batch *= 2
    return total_mb, batch, config, plen


def _metric_name(config: str, plen: int, total_mb: int) -> str:
    kib = plen // 1024
    if config == "multifile":
        return f"sha1_recheck_multifile_{kib}KiB_pieces_per_sec"
    if config == "author":
        return f"sha1_author_{kib}KiB_pieces_per_sec"
    if config == "bulk":
        n = int(os.environ.get("BENCH_BULK_N", "8"))
        return f"sha1_bulk_{n}x{total_mb}MB_pieces_per_sec"
    if config == "v2":
        return f"sha256_v2_author_{kib}KiB_pieces_per_sec"
    return f"sha1_recheck_{kib}KiB_pieces_per_sec"


# --------------------------------------------------------------- payload


class _VirtualPayload:
    """Deterministic synthetic torrent payload without materializing it.

    Piece ``i`` = one shared random base tile with the first 8 bytes
    replaced by ``i`` big-endian — every piece distinct (no digest-cache
    shortcuts possible), assembly is a memcpy, and the 100 GiB config
    needs only ``piece_length`` resident bytes.
    """

    def __init__(self, n_pieces: int, plen: int, seed: int = 0):
        self.n_pieces = n_pieces
        self.plen = plen
        self.total = n_pieces * plen
        rng = np.random.default_rng(seed)
        self.base = rng.integers(0, 256, size=plen, dtype=np.uint8).tobytes()

    def piece(self, i: int) -> bytes:
        return i.to_bytes(8, "big") + self.base[8:]

    def read(self, offset: int, length: int) -> bytes:
        out = bytearray(length)
        pos = 0
        while pos < length:
            o = offset + pos
            p, r = divmod(o, self.plen)
            n = min(self.plen - r, length - pos)
            out[pos : pos + n] = self.base[r : r + n]
            if r < 8:
                hdr = p.to_bytes(8, "big")
                k = min(8 - r, n)
                out[pos : pos + k] = hdr[r : r + k]
            pos += n
        return bytes(out)


class _PayloadMethod:
    """Zero-disk storage backend over the virtual payload.

    ``starts`` maps each file path to its global byte offset so the
    multifile config's file-relative reads land correctly.
    """

    def __init__(self, vp: _VirtualPayload, starts=None):
        self.vp = vp
        self.starts = starts or {}

    def get(self, path, offset, length):
        base = self.starts.get(path, 0)
        return self.vp.read(base + offset, length)

    def set(self, path, offset, data):
        raise NotImplementedError

    def exists(self, path, length=None):
        return True


# ------------------------------------------------------------- the bench


def _execute_v2(total_mb: int, plen: int):
    """BEP 52 authoring plane: SHA-256 leaves + merkle piece roots.

    Baseline = hashlib leaves + hashlib merkle on the same payload; the
    device side runs the batched sha256 plane + sha256_pairs levels.
    Both sides measured over the full population.
    """
    import jax

    from torrent_tpu.models.v2 import LEAF_BATCH, _leaf_words_device
    from torrent_tpu.models.merkle import piece_roots_from_leaves, words32_to_digests

    BLOCK = 16384
    if plen < BLOCK or plen % BLOCK or (plen // BLOCK) & (plen // BLOCK - 1):
        raise SystemExit(
            f"BENCH_CONFIG=v2 needs a piece length that is a power-of-two "
            f"multiple of 16 KiB (got {plen})"
        )
    n_pieces = total_mb * (1 << 20) // plen
    if n_pieces < 1:
        raise SystemExit("BENCH_CONFIG=v2 needs BENCH_TOTAL_MB >= one piece")
    lpp = plen // BLOCK
    vp = _VirtualPayload(n_pieces, plen)

    # CPU baseline: hashlib leaves + merkle, full population
    t0 = time.perf_counter()
    cpu_roots = []
    for i in range(n_pieces):
        data = vp.piece(i)
        level = [
            hashlib.sha256(data[j * BLOCK : (j + 1) * BLOCK]).digest() for j in range(lpp)
        ]
        while len(level) > 1:
            level = [
                hashlib.sha256(level[j] + level[j + 1]).digest()
                for j in range(0, len(level), 2)
            ]
        cpu_roots.append(level[0])
    cpu_secs = time.perf_counter() - t0
    cpu_pps = n_pieces / cpu_secs

    # device plane: stream the same payload through the batched plane in
    # LEAF_BATCH-block chunks (each chunk is block-aligned, so leaves
    # across chunk boundaries line up with piece geometry)
    total = n_pieces * plen
    chunk_bytes = LEAF_BATCH * BLOCK

    def chunks():
        off = 0
        while off < total:
            n = min(chunk_bytes, total - off)
            yield vp.read(off, n)
            off += n

    # warm every executable the timed loop will hit: the full-chunk
    # bucket, (if the total isn't chunk-aligned) the tail bucket, and the
    # merkle pair executables for every level shape of this geometry
    _ = _leaf_words_device(b"\0" * chunk_bytes, "auto")
    rem = total % chunk_bytes
    if rem:
        _ = _leaf_words_device(b"\0" * rem, "auto")
    _ = piece_roots_from_leaves(
        np.zeros((n_pieces * lpp, 8), dtype=np.uint32), lpp
    )
    t0 = time.perf_counter()
    leaf_words = np.concatenate(
        [_leaf_words_device(c, "auto") for c in chunks()], axis=0
    )
    roots = piece_roots_from_leaves(leaf_words, lpp)
    dev_secs = time.perf_counter() - t0
    got = words32_to_digests(roots)
    assert got == cpu_roots, "v2 device plane diverged from hashlib"
    dev_pps = n_pieces / dev_secs
    platform = jax.devices()[0].platform

    # Device-resident leaf plane (same dual-plane split as the sha1
    # configs): distinct resident leaf batches through the sha256 kernel,
    # completion forced by fetching an on-device reduction of the final
    # dispatch. The merkle reduction is <1% of the bytes (15 pair-hashes
    # of 64 B per 16 leaf hashes of 16 KiB) and is already validated in
    # the e2e pass above.
    import jax.numpy as jnp

    from torrent_tpu.models.v2 import _make_leaf_fn
    from torrent_tpu.ops.padding import alloc_padded, pad_in_place

    raw_fn, kernel = _make_leaf_fn(LEAF_BATCH, "auto")
    if kernel == "scan":
        # the scan backend wants u8 rows; the bitcast back is a real
        # reinterpret on the CPU
        def raw_fn(d32, nb, _raw=raw_fn):
            u8 = jax.lax.bitcast_convert_type(d32, jnp.uint8).reshape(
                d32.shape[0], -1
            )
            return _raw(u8, nb)

    fn = jax.jit(raw_fn)
    reduce_sum = jax.jit(lambda s: jnp.sum(s, dtype=jnp.uint32))
    # Queue several resident batches so a fixed per-dispatch cost
    # amortizes (BENCH_V2_NRES default 13: chosen on a retired setup, not
    # measured on this one). LEAF_BATCH x 16 KiB is 512 MiB per dispatch
    # at the default. The salted per-run copies (below) hold a SECOND
    # copy of every timed batch, so the resident cap is ~3 GiB to keep
    # resident+salted+swizzle temporaries inside a 16 GiB-HBM chip.
    batch_bytes = LEAF_BATCH * BLOCK
    n_res = max(
        3,
        min(
            int(os.environ.get("BENCH_V2_NRES", "13")),
            (3 << 30) // max(1, batch_bytes) + 1,
        ),
    )
    if platform == "cpu":
        n_res = 3
    rng = np.random.default_rng(7)
    resident = []
    for i in range(n_res):
        padded, view = alloc_padded(LEAF_BATCH, BLOCK)
        view[:] = rng.integers(0, 256, view.shape, dtype=np.uint8)
        nb = pad_in_place(padded, np.full(LEAF_BATCH, BLOCK, dtype=np.int64))
        resident.append(
            (jax.device_put(padded.view(np.uint32)), jax.device_put(nb))
        )
    w0 = fn(*resident[0])  # compile
    g0 = np.asarray(w0[0])
    want = np.frombuffer(
        hashlib.sha256(np.asarray(resident[0][0][0]).tobytes()[:BLOCK]).digest(),
        dtype=">u4",
    ).astype(np.uint32)
    assert np.array_equal(g0, want), "v2 leaf plane golden check failed"
    _ = int(reduce_sum(w0))
    lpp_piece = plen // BLOCK
    # median-of-N distinct-input runs: each run re-salts word 0 of row 0
    # ON DEVICE (an HBM copy, paid outside the timed window) so no
    # dispatch repeats an operand tuple (chosen on a retired setup, not
    # measured on this one). Row 0's digest changes; goldens were
    # checked above.
    n_runs = max(1, int(os.environ.get("BENCH_RUNS", "3")))
    salt_word = jax.jit(lambda d, s: d.at[0, 0].set(s))
    rates = []
    for run in range(n_runs):
        salted = [
            (salt_word(d, jnp.uint32(0xBEEF0000 + run)), nb)
            for d, nb in resident[1:]
        ]
        jax.block_until_ready([d for d, _ in salted])
        t0 = time.perf_counter()
        outs = [fn(d, nb) for d, nb in salted]
        _ = int(reduce_sum(outs[-1]))
        leaf_secs = time.perf_counter() - t0
        rates.append((n_res - 1) * LEAF_BATCH / lpp_piece / leaf_secs)
    plane_pps = float(np.median(rates))

    print(
        f"# detail: v2 leaf plane {plane_pps:.0f} p/s "
        f"({plane_pps * plen / 2**30:.2f} GiB/s) "
        f"end_to_end {dev_pps:.0f} p/s ({dev_pps * plen / 2**30:.2f} GiB/s) "
        f"cpu {cpu_pps:.0f} p/s ({cpu_pps * plen / 2**30:.2f} GiB/s)",
        file=sys.stderr,
    )
    return {
        "metric": _metric_name("v2", plen, total_mb),
        "value": round(plane_pps, 1),
        "unit": "pieces/s",
        "vs_baseline": round(plane_pps / cpu_pps, 2),
        "end_to_end_pps": round(dev_pps, 1),
        "end_to_end_vs_baseline": round(dev_pps / cpu_pps, 2),
        "platform": platform,
        "backend": kernel,
        "batch": LEAF_BATCH,
        "n_batches": n_res,
        **_runs_fields(plane_pps, rates),
    }


def _e2e_pieces_for(total_mb: int, plen: int, n_pieces: int) -> int:
    """Single source of truth for the BENCH_E2E_MB cap: the cached-
    baseline path computes real digests only for the prefix the e2e pass
    verifies, so _prepare and _execute MUST derive the same count."""
    e2e_mb = int(os.environ.get("BENCH_E2E_MB", "0")) or total_mb
    return min(n_pieces, max(1, e2e_mb * (1 << 20) // plen))


def _baseline_cache_load(plen: int):
    """Opt-in CPU-baseline cache (BENCH_BASELINE_CACHE=path): the sha1
    hashlib rate at a piece length is a property of this host, not of the
    run — no need to re-hash 100 GiB of it on every run. Keyed by piece
    length; entries carry their measured geometry + date for the
    record's honesty fields."""
    path = os.environ.get("BENCH_BASELINE_CACHE", "")
    if not path or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            entry = json.load(f).get(f"sha1:{plen}")
    except Exception:
        return None
    # validate: a malformed entry (hand edit, schema drift) must fall
    # through to the measured path, not crash the run
    if not isinstance(entry, dict):
        return None
    pps = entry.get("cpu_pps")
    if not isinstance(pps, (int, float)) or not pps > 0:
        return None
    return entry


def _baseline_cache_save(plen: int, cpu_pps: float, total_mb: int) -> None:
    path = os.environ.get("BENCH_BASELINE_CACHE", "")
    if not path:
        return
    try:
        data = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    data = json.load(f)
            except Exception:
                data = {}
        key = f"sha1:{plen}"
        prev = data.get(key)
        # keep the largest-population measurement (most representative)
        if prev and prev.get("measured_total_mb", 0) >= total_mb:
            return
        data[key] = {
            "cpu_pps": round(cpu_pps, 1),
            "measured_total_mb": total_mb,
            "measured_at_utc": _utcnow(),
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f)
        os.replace(tmp, path)
    except Exception as e:  # pragma: no cover - diagnostics only
        print(f"# baseline cache save failed: {e!r}", file=sys.stderr)


def _prepare(total_mb: int, config: str, plen: int, batch: int):
    """Build the virtual payload, measure the FULL CPU baseline while
    producing the expected digests (one pass, pure-hash time).

    With a cached baseline (headline/multifile only — author/bulk compare
    every digest so they always hash the full population), digests are
    computed just for the prefix the run actually checks (warmup batch +
    capped e2e range); the rest are placeholders never read."""
    n_pieces = total_mb * (1 << 20) // plen
    total = n_pieces * plen
    vp = _VirtualPayload(n_pieces, plen)

    baseline_meta = {}
    cached = (
        _baseline_cache_load(plen) if config in ("headline", "multifile") else None
    )
    e2e_pieces = _e2e_pieces_for(total_mb, plen, n_pieces)
    needed = min(n_pieces, max(batch, e2e_pieces))
    if cached and needed < n_pieces:
        cpu_pps = float(cached["cpu_pps"])
        digests = [hashlib.sha1(vp.piece(i)).digest() for i in range(needed)]
        digests += [b"\0" * 20] * (n_pieces - needed)
        baseline_meta = {
            "baseline_cached": True,
            "baseline_measured_total_mb": cached.get("measured_total_mb"),
            "baseline_measured_at_utc": cached.get("measured_at_utc"),
        }
    else:
        digests = []
        hash_secs = 0.0
        for i in range(n_pieces):
            data = vp.piece(i)
            t0 = time.perf_counter()
            d = hashlib.sha1(data).digest()
            hash_secs += time.perf_counter() - t0
            digests.append(d)
        cpu_pps = n_pieces / hash_secs
        _baseline_cache_save(plen, cpu_pps, total_mb)

    from torrent_tpu.codec.metainfo import InfoDict

    if config == "multifile":
        # config 2: ~5 uneven files so pieces span boundaries
        from torrent_tpu.codec.metainfo import FileEntry

        cuts = sorted({1, total // 3 - 1234, total // 2 + 77, total * 5 // 7, total})
        files, prev = [], 0
        for i, c in enumerate(cuts):
            files.append(FileEntry(length=c - prev, path=(f"f{i}.bin",)))
            prev = c
        info = InfoDict(
            name="bench",
            piece_length=plen,
            pieces=tuple(digests),
            length=total,
            files=tuple(files),
        )
    else:
        info = InfoDict(
            name="bench", piece_length=plen, pieces=tuple(digests), length=total, files=None
        )
    storage = _build_storage(vp, info)
    return vp, storage, info, digests, cpu_pps, baseline_meta


def _build_storage(vp: _VirtualPayload, info):
    """Storage over the virtual payload, with per-file global offsets."""
    from torrent_tpu.storage.storage import Storage

    starts = {}
    if info.files is not None:
        pos = 0
        for fe in info.files:
            starts[(info.name, *fe.path)] = pos
            pos += fe.length
    return Storage(_PayloadMethod(vp, starts), info)


def _probe_h2d() -> float:
    """Measured host→device bandwidth (MiB/s), completion forced by
    fetching an on-device reduction of the uploaded array."""
    import jax
    import jax.numpy as jnp

    # BENCH_H2D_MB: the micro-rung shrinks this probe (2×64 MiB staged by
    # default)
    mb = max(1, int(os.environ.get("BENCH_H2D_MB", "64")))
    rng = np.random.default_rng(0)
    warm = rng.integers(0, 256, mb << 20, dtype=np.uint8)
    arr = rng.integers(0, 256, mb << 20, dtype=np.uint8)  # distinct content
    fn = jax.jit(lambda x: jnp.sum(x.astype(jnp.uint32)))
    # warm with the SAME shape (jit caches per shape — a smaller warm array
    # would leave trace+compile inside the timed region) but different
    # bytes
    _ = int(fn(jax.device_put(warm)))
    t0 = time.perf_counter()
    _ = int(fn(jax.device_put(arr)))
    return mb / (time.perf_counter() - t0)


def _runs_fields(pps_median: float, runs: list) -> dict:
    """Reproducibility fields shared by every hash-plane record:
    median-of-N run rates and their spread."""
    return {
        "n_runs": len(runs),
        "runs_pps": [round(r, 1) for r in runs],
        "spread": round((max(runs) - min(runs)) / max(pps_median, 1e-9), 3),
    }


# device-resident working set of the plane measurement; assumes a 16 GB chip
RESIDENT_BUDGET_BYTES = 10 << 30


def _device_plane_pps(verifier, plen):
    """Hash-plane throughput: distinct resident batches, queued launches,
    completion forced by fetching the final result (the device executes
    in-order, so the last result landing implies all executed).

    Rows within a batch share a random base with the row id stamped into
    the first 8 bytes — every piece distinct, digests computed by hashlib
    for golden rows so a wrong kernel fails loudly.

    Returns ``(median_pps, run_rates)`` over BENCH_RUNS (default 3) timed
    passes. Every pass re-stamps the run id into a spare expected-digest
    row so no dispatch in any run repeats an earlier operand tuple (the
    salting was chosen on a retired setup, not measured on this one).
    """
    import hashlib

    import jax

    from torrent_tpu.ops.padding import digests_to_words, pad_in_place

    import jax.numpy as jnp

    b = verifier.batch_size
    # All batches stay device-resident during the timed queue; cap the
    # working set so big geometries (4096 × 1 MiB pieces ≈ 4.3 GB/batch)
    # leave HBM room for the kernel's per-tile swizzle temporaries
    # (~2 GiB with adaptive tiling — 10 GiB resident + temps fits the
    # 15.75 GiB chip). On CPU the "device" is host RAM and the plane/e2e
    # distinction is moot — keep it small. BENCH_NBATCH caps the count
    # explicitly.
    batch_bytes = b * verifier.padded_len
    n_batches = max(2, min(4, RESIDENT_BUDGET_BYTES // max(1, batch_bytes)))
    nb_env = os.environ.get("BENCH_NBATCH", "").strip()
    if nb_env.isdigit():
        # BENCH_NBATCH=1 is the micro-rung: ONE staged batch (the warmup
        # batch doubles as the timed batch), distinctness carried entirely
        # by the salted expected-digest operands below.
        n_batches = max(1, min(n_batches, int(nb_env)))
    elif nb_env:
        print(f"# ignoring non-numeric BENCH_NBATCH={nb_env!r}", file=sys.stderr)
    if jax.devices()[0].platform == "cpu":
        n_batches = min(n_batches, 2)
    rng = np.random.default_rng(1234)
    base = np.zeros(verifier.padded_len, dtype=np.uint8)
    base[:plen] = rng.integers(0, 256, plen, dtype=np.uint8)
    lengths = np.full(b, plen, dtype=np.int64)

    # resident row-block u32 chunks, dispatched through the verifier's
    # flat step (the same executable verify_storage uses)
    datas, nbs, exps = [], [], []
    for i in range(n_batches):
        padded = np.tile(base, (b, 1))
        ids = np.arange(i * b, (i + 1) * b, dtype=">u8")
        padded[:, :8] = ids.view(np.uint8).reshape(b, 8)
        nblocks = pad_in_place(padded, lengths)
        expected = np.zeros((b, 5), dtype=np.uint32)
        for row in (0, b - 1):
            d = hashlib.sha1(padded[row, :plen].tobytes()).digest()
            expected[row] = digests_to_words([d])[0]
        datas.append(verifier._put_flat(padded))
        nbs.append(jax.device_put(nblocks))
        exps.append(jax.device_put(expected))
    ok0 = np.asarray(verifier._verify_step_flat(datas[0], nbs[0], exps[0]))  # compile
    assert ok0[0] and ok0[b - 1], "device-plane golden check failed"
    host_exps = [np.asarray(e) for e in exps]
    n_runs = max(1, int(os.environ.get("BENCH_RUNS", "3")))
    # BENCH_DISPATCHES: how many timed dispatches per run. Default keeps
    # the historical shape (each non-warmup batch once). More dispatches
    # amortize a fixed per-dispatch cost over data already resident:
    # every dispatch gets a DISTINCT salted expected-digest operand (a
    # tiny b×5 u32 put), so no (data, nblocks, expected) tuple ever
    # repeats.
    nd_env = os.environ.get("BENCH_DISPATCHES", "").strip()
    n_disp = int(nd_env) if nd_env.isdigit() and int(nd_env) > 0 else max(
        1, n_batches - 1
    )
    # the distinctness guarantee rides the salt stamped into expected
    # row 1, which only exists when b > 2 (rows 0 and b-1 are golden) —
    # refuse a dispatch-cycling shape that would submit identical tuples
    if b <= 2 and (n_batches == 1 or n_disp > n_batches - 1):
        raise SystemExit(
            "BENCH_NBATCH=1/BENCH_DISPATCHES need BENCH_BATCH > 2: batches"
            " of <=2 rows have no salt row, so cycled dispatches would"
            " repeat identical operand tuples"
        )
    # timed dispatches cycle over the non-warmup batches; with a single
    # staged batch (micro-rung) they reuse batch 0 — already warmed.
    timed_idx = (
        [0] * n_disp
        if n_batches == 1
        else [1 + k % (n_batches - 1) for k in range(n_disp)]
    )
    rates = []
    salt = 0
    for run in range(n_runs):
        # distinct operands per dispatch: stamp a never-repeating salt into
        # expected row 1 (rows other than 0 / b-1 are never golden-checked)
        run_exps = []
        for i in timed_idx:
            salt += 1
            e2 = host_exps[i].copy()
            if b > 2:
                e2[1] = salt
            run_exps.append(jax.device_put(e2))
        jax.block_until_ready(run_exps)
        t0 = time.perf_counter()
        outs = [
            verifier._verify_step_flat(datas[i], nbs[i], e)
            for i, e in zip(timed_idx, run_exps)
        ]
        last = np.asarray(outs[-1])
        secs = time.perf_counter() - t0
        assert last[0] and last[b - 1], "device-plane golden check failed"
        rates.append(n_disp * b / secs)
    return float(np.median(rates)), rates, {"n_batches": n_batches, "n_dispatches": n_disp}


def _execute(
    backend, vp, storage, info, digests, cpu_pps, baseline_meta, batch, config, plen, total_mb
):
    import jax

    from torrent_tpu.models.verifier import TPUVerifier

    n_pieces = info.num_pieces
    verifier = TPUVerifier(piece_length=plen, batch_size=batch, backend=backend)
    metric = _metric_name(config, plen, total_mb)
    platform = jax.devices()[0].platform

    def result_line(pps, runs=None):
        line = {
            "metric": metric,
            "value": round(pps, 1),
            "unit": "pieces/s",
            "vs_baseline": round(pps / cpu_pps, 2),
            "platform": platform,
            "backend": backend,
            "batch": batch,
            **baseline_meta,
        }
        if runs:
            line.update(_runs_fields(pps, runs))
        return line

    if config == "author":
        # config 3: authoring-side digests (make_torrent hot loop) via the
        # batched hash plane; baseline = the full-population hashlib rate.
        # Pieces are materialized one batch at a time — a full list copy
        # would blow resident memory at the 10 GiB documented scale.
        b = verifier.batch_size

        def batch_pieces(start):
            stop = min(start + b, n_pieces)
            return [vp.piece(i) for i in range(start, stop)]

        verifier.hash_pieces(batch_pieces(0))  # warmup/compile
        t0 = time.perf_counter()
        ok = 0
        for start in range(0, n_pieces, b):
            out = verifier.hash_pieces(batch_pieces(start))
            ok += sum(d == digests[start + i] for i, d in enumerate(out))
        secs = time.perf_counter() - t0
        assert ok == n_pieces, f"authoring digests wrong: {ok}/{n_pieces}"
        # same dual-plane report as the recheck configs: value = the
        # device-resident hash plane, end_to_end = the full pipeline
        # (host assembly + transfer + digests)
        plane_pps, plane_runs, plane_meta = _device_plane_pps(verifier, plen)
        line = result_line(plane_pps, plane_runs)
        line.update(plane_meta)
        line["end_to_end_pps"] = round(n_pieces / secs, 1)
        line["end_to_end_vs_baseline"] = round(n_pieces / secs / cpu_pps, 2)
        return line

    if config == "bulk":
        # config 5 at single-host scale: a library of torrents validated
        # through one shared verifier.
        from torrent_tpu.parallel.bulk import verify_library

        n_torrents = int(os.environ.get("BENCH_BULK_N", "8"))
        jobs = [(storage, info) for _ in range(n_torrents)]
        # share one compiled verifier so the warmup's compile actually
        # warms the timed run
        verify_library(jobs[:1], verifier=verifier)
        t0 = time.perf_counter()
        result = verify_library(jobs, verifier=verifier)
        secs = time.perf_counter() - t0
        assert all(bf.all() for bf in result.bitfields)
        plane_pps, plane_runs, plane_meta = _device_plane_pps(verifier, plen)
        line = result_line(plane_pps, plane_runs)
        line.update(plane_meta)
        line["end_to_end_pps"] = round(n_torrents * n_pieces / secs, 1)
        line["end_to_end_vs_baseline"] = round(
            n_torrents * n_pieces / secs / cpu_pps, 2
        )
        return line

    # headline / multifile: full recheck through verify_storage.
    from torrent_tpu.ops.padding import digests_to_words, pad_in_place

    b = verifier.batch_size
    warm_n = min(b, n_pieces)
    padded = np.zeros((b, verifier.padded_len), dtype=np.uint8)
    storage.read_batch(range(warm_n), out=padded[:warm_n, :plen])
    lengths = np.full(b, plen, dtype=np.int64)
    nblocks = pad_in_place(padded, lengths)
    expected = np.zeros((b, 5), dtype=np.uint32)
    expected[:warm_n] = digests_to_words(digests[:warm_n])
    verifier.verify_batch(padded, nblocks, expected)  # warmup/compile

    # The e2e pass can be capped below the full geometry (BENCH_E2E_MB;
    # chosen on a retired setup, not measured on this one). The hash
    # plane and the CPU baseline are always full-scale.
    e2e_pieces = _e2e_pieces_for(total_mb, plen, n_pieces)
    if e2e_pieces < n_pieces:
        from torrent_tpu.codec.metainfo import FileEntry, InfoDict

        e2e_len = e2e_pieces * plen
        sub_files = None
        if info.files is not None:  # multifile: trim the file list
            sub_files, pos = [], 0
            for fe in info.files:
                if pos >= e2e_len:
                    break
                sub_files.append(
                    FileEntry(length=min(fe.length, e2e_len - pos), path=fe.path)
                )
                pos += fe.length
            sub_files = tuple(sub_files)
        sub_info = InfoDict(
            name=info.name,
            piece_length=plen,
            pieces=info.pieces[:e2e_pieces],
            length=e2e_len,
            files=sub_files,
        )
        e2e_storage = _build_storage(vp, sub_info)
    else:
        e2e_pieces = n_pieces
        sub_info, e2e_storage = info, storage

    t0 = time.perf_counter()
    bitfield = verifier.verify_storage(e2e_storage, sub_info)
    e2e_secs = time.perf_counter() - t0
    assert bitfield.all(), f"verify failed: {int(bitfield.sum())}/{e2e_pieces}"
    e2e_pps = e2e_pieces / e2e_secs

    # Hash-plane measurement (the headline: device-resident batches).
    # On CPU the "device" is the host, so the two coincide.
    plane_pps, plane_runs, plane_meta = _device_plane_pps(verifier, plen)
    h2d = _probe_h2d() if platform != "cpu" else None
    print(
        f"# detail: devices={jax.devices()} backend={backend} n_pieces={n_pieces} "
        f"hash_plane={plane_pps:.0f} p/s ({plane_pps * plen / 2**30:.2f} GiB/s) "
        f"end_to_end={e2e_pps:.0f} p/s ({e2e_pps * plen / 2**30:.2f} GiB/s) "
        f"h2d={h2d and round(h2d)} MiB/s "
        f"cpu={cpu_pps:.0f} p/s ({cpu_pps * plen / 2**30:.2f} GiB/s)",
        file=sys.stderr,
    )
    line = result_line(plane_pps, plane_runs)
    line.update(plane_meta)
    line["end_to_end_pps"] = round(e2e_pps, 1)
    line["end_to_end_vs_baseline"] = round(e2e_pps / cpu_pps, 2)
    if e2e_pieces < n_pieces:
        # the end-to-end pass was measured over a sub-range
        line["e2e_measured_mb"] = e2e_pieces * plen >> 20
    if h2d is not None:
        line["h2d_mib_s"] = round(h2d, 1)
    return line


def _utcnow() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


def main() -> None:
    total_mb, batch, config, plen = _env_geometry()

    from torrent_tpu.utils.device import device_info, enable_compile_cache

    enable_compile_cache()
    dev = device_info()
    # with JAX_PLATFORMS unset (or "tpu,cpu") a failed accelerator init
    # falls back to the host silently; only "cpu" asks for the CPU
    if dev["platform"] == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit(
            "bench: JAX found no accelerator and fell back to the CPU; "
            "set JAX_PLATFORMS=cpu to measure the host on purpose"
        )

    if config == "v2":
        result = _execute_v2(total_mb, plen)
    else:
        # pallas is the kernel on a TPU; interpret-mode pallas on the CPU
        # would be pathological, so the scan backend runs there
        backend = os.environ.get("BENCH_BACKEND") or (
            "pallas" if dev["platform"] == "tpu" else "jax"
        )
        state = _prepare(total_mb, config, plen, batch)
        result = _execute(backend, *state, batch, config, plen, total_mb)
    result.update(device_kind=dev["kind"], device_count=dev["count"])
    print(json.dumps(result))


if __name__ == "__main__":
    main()
