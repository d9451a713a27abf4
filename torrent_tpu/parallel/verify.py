"""Batched piece verification — the resume-recheck / authoring hash plane.

This is the subsystem the reference *lacks* (SURVEY §8.3: downloaded
pieces are never SHA1-checked; resume-recheck is an unchecked roadmap
item, README.md:34) and the BASELINE north star: ``verify_pieces(storage,
info)`` reads pieces in large batches (``Storage.read_batch``), pads them
on host, and hashes them on device — pieces sharded ``(hosts, dp)`` over
the mesh, digests compared on device, one bool per piece returned.

Pipeline shape (per batch of B pieces):

    disk → read_batch → pad_in_place → device put (sharded) ┐
                                    sha1 chain (scan)       │ overlapped:
                                    compare vs expected     │ next batch's
                                    psum-free bool[B] ──────┘ disk read runs
                                                              on a host thread

The CPU path (``hasher="cpu"``) is streaming hashlib — the measured
baseline the TPU path is benchmarked against (BASELINE.md configs 1-2).
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Callable

import numpy as np

from torrent_tpu.codec.metainfo import InfoDict
from torrent_tpu.storage.piece import piece_length
from torrent_tpu.storage.storage import Storage, StorageError


@dataclass
class VerifyResult:
    """Outcome of a full verify pass."""

    bitfield: np.ndarray  # bool[n_pieces]
    n_pieces: int
    n_valid: int
    bytes_hashed: int
    seconds: float

    @property
    def complete(self) -> bool:
        return self.n_valid == self.n_pieces

    @property
    def pieces_per_sec(self) -> float:
        return self.n_pieces / self.seconds if self.seconds > 0 else float("inf")

    @property
    def gib_per_sec(self) -> float:
        return self.bytes_hashed / self.seconds / 2**30 if self.seconds > 0 else float("inf")


ProgressCb = Callable[[int, int], None]  # (pieces_done, pieces_total)


def verify_pieces_cpu(
    storage: Storage, info: InfoDict, progress_cb: ProgressCb | None = None
) -> np.ndarray:
    """Streaming hashlib recheck — the measured CPU baseline."""
    n = info.num_pieces
    bitfield = np.zeros(n, dtype=bool)
    for idx in range(n):
        try:
            data = storage.read_piece(idx)
        except (StorageError, OSError):
            continue  # unreadable = failed piece, keep checking the rest
        if len(data) == piece_length(info, idx) and hashlib.sha1(data).digest() == info.pieces[idx]:
            bitfield[idx] = True
        if progress_cb and (idx + 1) % 256 == 0:
            progress_cb(idx + 1, n)
    if progress_cb:
        progress_cb(n, n)
    return bitfield


def verify_pieces_tpu(
    storage: Storage,
    info: InfoDict,
    batch_size: int = 1024,
    backend: str = "jax",
    mesh=None,
    progress_cb: ProgressCb | None = None,
    io_threads: int = 4,
) -> np.ndarray:
    """Batched device recheck; overlaps disk reads with device hashing.

    On a multi-process (``jax.distributed``) cluster this routes to the
    DCN path automatically: every process verifies its shard of each
    global batch and all return the identical global bitfield
    (parallel/distributed.py; proven by tests/test_distributed.py).
    """
    import jax

    # Route on the MESH's process span, not bare process_count(): a
    # caller on a multi-process cluster may pass a local-only mesh
    # (make_mesh(jax.local_devices(), n_hosts=1)) for a per-host
    # recheck, which must take the ordinary single-controller path.
    if jax.process_count() > 1:
        span_mesh = mesh
        if span_mesh is None:
            from torrent_tpu.parallel.mesh import make_mesh

            span_mesh = make_mesh()
        if len({d.process_index for d in span_mesh.devices.flat}) > 1:
            from torrent_tpu.parallel.distributed import (
                verify_storage_distributed,
            )

            bitfield, _ = verify_storage_distributed(
                storage,
                info,
                batch_size=batch_size,
                backend=backend,
                mesh=span_mesh,
                progress_cb=progress_cb,
                io_threads=io_threads,
            )
            return bitfield
        mesh = span_mesh

    from torrent_tpu.obs.ledger import pipeline_ledger
    from torrent_tpu.obs.profiler import annotate

    # the launch-free start of a pass, first entry: a pass builds its
    # verifier, which takes the process's jitted steps (models/verifier:
    # only a process's first pass of a shape traces and loads them), so
    # the build is about a millisecond (verify_storage opens the second
    # entry, up to its first upload)
    with pipeline_ledger().track("pass_setup"), annotate("build_verifier"):
        from torrent_tpu.models.verifier import TPUVerifier

        verifier = TPUVerifier(
            piece_length=info.piece_length,
            batch_size=batch_size,
            backend=backend,
            mesh=mesh,
        )
    return verifier.verify_storage(
        storage, info, progress_cb=progress_cb, io_threads=io_threads
    )


def verify_pieces_v2_cpu(
    storage: Storage, info, progress_cb: ProgressCb | None = None
) -> np.ndarray:
    """Streaming per-piece merkle recheck (session/v2.py geometry)."""
    from torrent_tpu.models.merkle import piece_root_cpu

    n = info.num_pieces
    bitfield = np.zeros(n, dtype=bool)
    for idx in range(n):
        try:
            data = storage.read_piece(idx)
        except (StorageError, OSError):
            continue  # unreadable = failed piece, keep checking the rest
        if (
            len(data) == info.piece_sizes[idx]
            and piece_root_cpu(data, info.piece_pad_leaves[idx]) == info.pieces[idx]
        ):
            bitfield[idx] = True
        if progress_cb and (idx + 1) % 256 == 0:
            progress_cb(idx + 1, n)
    if progress_cb:
        progress_cb(n, n)
    return bitfield


def verify_pieces_v2_tpu(
    storage: Storage,
    info,
    batch_size: int = 256,
    progress_cb: ProgressCb | None = None,
    indices=None,
    **_ignored,
) -> np.ndarray:
    """Batched device merkle recheck: SHA-256 16 KiB leaves on the hash
    plane, then one batched pair-reduction per tree level across the
    whole piece batch (models/merkle.py).

    ``indices``: optional subset of piece indices to recheck (the
    multi-host path gives each process its stride); the returned
    bitfield is always full length, False outside the subset.
    """
    from torrent_tpu.codec.metainfo_v2 import BLOCK
    from torrent_tpu.models.merkle import merkle_root, words32_to_digests
    from torrent_tpu.models.v2 import _launch_leaves, _make_leaf_fn
    from torrent_tpu.ops.padding import alloc_padded, pad_in_place

    n = info.num_pieces
    bitfield = np.zeros(n, dtype=bool)
    if n == 0:
        return bitfield
    todo = range(n) if indices is None else indices
    # group pieces by leaf-pad target: multi-piece files all share
    # blocks-per-piece, single-piece files use their own pow2 count
    by_pad: dict[int, list[int]] = {}
    for idx in todo:
        by_pad.setdefault(info.piece_pad_leaves[idx], []).append(idx)
    n_todo = sum(len(v) for v in by_pad.values())
    leaf_rows = 1024  # device rows per leaf dispatch (pow2-bucketed fn)
    leaf_fn = _make_leaf_fn(leaf_rows, "auto")
    padded, view = alloc_padded(leaf_rows, BLOCK)
    done = 0
    for pad, group in by_pad.items():
        for bstart in range(0, len(group), batch_size):
            batch = group[bstart : bstart + batch_size]
            buf, lengths = storage.read_batch(batch)
            ok_len = np.array(
                [lengths[i] == info.piece_sizes[p] for i, p in enumerate(batch)]
            )
            m = len(batch)
            grid = np.zeros((m, pad, 8), dtype=np.uint32)
            # flatten every real block of the batch into leaf-plane rows
            blocks: list[tuple[int, int, int]] = []  # (piece_i, block_i, blen)
            for i in range(m):
                ln = int(lengths[i])
                for bi in range(-(-ln // BLOCK) if ln else 0):
                    blocks.append((i, bi, min(BLOCK, ln - bi * BLOCK)))
                if ln == 0 and info.piece_sizes[batch[i]] == 0:
                    blocks.append((i, 0, 0))
            for rstart in range(0, len(blocks), leaf_rows):
                chunk = blocks[rstart : rstart + leaf_rows]
                padded[:] = 0
                row_len = np.zeros(leaf_rows, dtype=np.int64)
                for r, (i, bi, blen) in enumerate(chunk):
                    view[r, :blen] = buf[i, bi * BLOCK : bi * BLOCK + blen]
                    row_len[r] = blen
                nblocks = pad_in_place(padded, row_len)
                nblocks[len(chunk) :] = 0
                words = _launch_leaves(leaf_fn, padded, nblocks)
                for r, (i, bi, _blen) in enumerate(chunk):
                    grid[i, bi] = words[r]
            roots = words32_to_digests(merkle_root(grid))
            for i, p in enumerate(batch):
                bitfield[p] = bool(ok_len[i]) and roots[i] == info.pieces[p]
            done += m
            if progress_cb:
                progress_cb(done, n_todo)
    return bitfield


def read_pieces_chunk(storage: Storage, info: InfoDict, idxs):
    """Read a chunk of pieces with mark-and-continue semantics.

    Returns ``(payloads, expected, keep)`` — a torn/unreadable/short
    piece is skipped (stays False in the caller's bitfield) instead of
    aborting, the same contract as ``verify_pieces_cpu``; OSError too,
    because a backend that leaks a raw errno (file truncated between
    open and pread) must not kill the pass. The ONE implementation of
    the read/filter/keep contract, shared by the scheduler sessions
    here and the fabric executor (``torrent_tpu/fabric``) — which also
    makes it the pipeline ledger's ``read`` stage boundary for every
    scheduler-fed path."""
    from torrent_tpu.obs.ledger import pipeline_ledger

    payloads, exps, keep = [], [], []
    with pipeline_ledger().track("read") as tracked:
        for i in idxs:
            try:
                data = storage.read_piece(i)
            except (StorageError, OSError):
                continue
            tracked.add(len(data))
            if len(data) != piece_length(info, i):
                continue
            payloads.append(data)
            exps.append(info.pieces[i])
            keep.append(i)
    return payloads, exps, keep


def read_pieces_into(storage: Storage, info: InfoDict, idxs, scheduler):
    """Zero-copy sibling of :func:`read_pieces_chunk`.

    Checks a staging slab out of the scheduler's ingest pool
    (``sched._StagingSlots`` via ``checkout_staging``) FIRST, then has
    ``Storage.read_batch`` — the native ``io_engine.read_into`` pread
    pool when available, the pure-Python backend walk otherwise — land
    the reads directly in the slab's row-strided view and pads the rows
    in place. No intermediate per-piece ``bytes``, no ``np.frombuffer``
    row copy, no ``_StagingSlots.stage`` pass later: the slab IS the
    launch buffer.

    Mark-and-continue semantics are preserved: a torn/short/unreadable
    piece becomes an ``nblocks=0`` sentinel row, is dropped from the
    returned ticket rows, and stays False in the caller's bitfield —
    the same contract as ``read_pieces_chunk`` (differential-tested in
    tests/test_ingest.py, native engine present and absent).

    Returns ``(slab, rows, expected, keep)`` — the caller holds one
    slab reference and must ``slab.release()`` after hand-off (or on
    abort) — or ``None`` when this scheduler/geometry can't take
    pre-staged submissions (callers fall back to the byte path). Any
    read-path failure checks the slab back in before returning, so a
    mid-batch ``NativeIOError`` can never leak a slot.
    """
    checkout = getattr(scheduler, "checkout_staging", None)
    if checkout is None:
        return None
    idxs = list(idxs)
    slab = checkout(info.piece_length, len(idxs), algo="sha1")
    if slab is None:
        return None
    try:
        n = len(idxs)
        slab.prepare([piece_length(info, i) for i in idxs])
        ok = np.zeros(n, dtype=bool)
        storage.read_batch(
            idxs,
            out=slab.padded[:n, : info.piece_length],
            row_status=ok,
            zero_fill=False,
        )
        slab.finalize(ok)
    except Exception:
        # whatever broke (engine fault, backend bug): return the slot —
        # callers retry through the byte path, which re-reads cleanly
        slab.release()
        return None
    rows = [i for i in range(n) if ok[i]]
    expected = [info.pieces[idxs[i]] for i in rows]
    keep = [idxs[i] for i in rows]
    return slab, rows, expected, keep


class _SchedChunk:
    """One read chunk ready for scheduler submission — staged (slab)
    or byte form, behind one enqueue/discard surface so every
    scheduler-fed read loop (torrent rechecks, library sweeps, the
    fabric executor) shares the zero-copy-with-fallback contract."""

    __slots__ = ("slab", "rows", "payloads", "expected", "keep", "piece_length")

    def __init__(self, slab, rows, payloads, expected, keep, piece_length):
        self.slab = slab
        self.rows = rows
        self.payloads = payloads
        self.expected = expected
        self.keep = keep
        self.piece_length = piece_length

    @property
    def empty(self) -> bool:
        return not self.keep

    @property
    def nbytes(self) -> int:
        if self.slab is not None:
            return int(self.slab.lengths[list(self.rows)].sum())
        return sum(len(p) for p in self.payloads)

    async def enqueue(
        self, scheduler, tenant: str, wait: bool = True, flush: bool = False
    ):
        """Submit and hand ownership over: the creator's slab reference
        is released on EVERY path (tickets keep the slab alive through
        demux; a shed releases everything). ``flush=True`` is the
        scheduler's: the caller sends nothing more until this chunk
        resolves, so its lane launches at once. It is passed on only
        where it is true: a scheduler stand-in need not know the word."""
        hint = {"flush": True} if flush else {}
        if self.slab is not None:
            slab, self.slab = self.slab, None
            try:
                return await scheduler.enqueue_staged(
                    tenant, slab, self.rows, expected=self.expected, wait=wait,
                    **hint,
                )
            finally:
                slab.release()
        return await scheduler.enqueue(
            tenant,
            self.payloads,
            expected=self.expected,
            algo="sha1",
            piece_length=self.piece_length,
            wait=wait,
            **hint,
        )

    def discard(self) -> None:
        """Abandon without submitting (empty chunk, caller abort)."""
        if self.slab is not None:
            self.slab.release()
            self.slab = None


def read_chunk_for_sched(
    storage: Storage, info: InfoDict, idxs, scheduler
) -> _SchedChunk:
    """Read one chunk for scheduler submission, zero-copy when the
    scheduler's ingest pool can take it, ``read_pieces_chunk`` bytes
    otherwise. Runs in a worker thread (both read paths block)."""
    staged = read_pieces_into(storage, info, idxs, scheduler)
    if staged is not None:
        slab, rows, expected, keep = staged
        if not keep:  # nothing readable: give the slot straight back
            slab.release()
            return _SchedChunk(None, None, [], [], [], info.piece_length)
        return _SchedChunk(slab, rows, None, expected, keep, info.piece_length)
    payloads, exps, keep = read_pieces_chunk(storage, info, idxs)
    return _SchedChunk(None, None, payloads, exps, keep, info.piece_length)


async def enqueue_torrent_sched(
    storage: Storage,
    info: InfoDict,
    scheduler,
    tenant: str,
    chunk_pieces: int | None = None,
) -> list[tuple]:
    """Read a torrent's pieces off-thread and enqueue them on the shared
    hash-plane scheduler WITHOUT awaiting results.

    Returns ``[(future, keep_indices), ...]`` — each future resolves to
    ok-bytes for the pieces in ``keep_indices`` (rows that failed to read
    or were short are skipped and stay False in the caller's bitfield).
    Submissions use blocking admission (``wait=True``): a full queue
    pauses the disk read loop instead of buffering without bound. Shared
    by ``verify_pieces_sched`` and ``verify_library_sched`` so the read /
    filter / keep-demux contract lives in one place.

    Chunks go zero-copy whenever the scheduler's ingest pool covers the
    geometry (:func:`read_pieces_into` → ``enqueue_staged``): reads for
    chunk *k+1* land in a second slab while chunk *k*'s H2D/launch runs
    — the read→h2d→launch overlap the pipeline ledger's occupancy
    series makes visible.
    """
    import asyncio

    chunk = chunk_pieces or scheduler.chunk_for(info.piece_length)

    futs: list[tuple] = []
    for start in range(0, info.num_pieces, chunk):
        idxs = list(range(start, min(start + chunk, info.num_pieces)))
        ck = await asyncio.to_thread(
            read_chunk_for_sched, storage, info, idxs, scheduler
        )
        if ck.empty:
            ck.discard()
            continue
        fut = await ck.enqueue(scheduler, tenant, wait=True)
        futs.append((fut, ck.keep))
    return futs


async def verify_pieces_sched(
    storage: Storage,
    info: InfoDict,
    scheduler,
    tenant: str = "verify",
    chunk_pieces: int | None = None,
    progress_cb: ProgressCb | None = None,
) -> np.ndarray:
    """Recheck through the shared hash-plane scheduler (v1/sha1 infos).

    Instead of owning a private ``TPUVerifier`` batch loop, pieces are
    read off-thread and submitted to ``scheduler``
    (``torrent_tpu.sched.HashPlaneScheduler``): the scheduler coalesces
    them with every other caller's traffic into full device launches and
    keeps the geometry-grouped compile cache across sessions. Reads
    pipeline against launches — submissions are enqueued with blocking
    admission (``wait=True``), so a full queue pauses the disk read
    loop instead of buffering without bound.

    A launch failure that outlives the scheduler's retry/bisection
    (``SchedLaunchError``) marks its pieces unverified (False — retried
    on the next recheck or re-downloaded) instead of aborting the whole
    pass: one poisoned piece must not discard every verified one.

    v2 (merkle) infos don't map onto the flat digest plane; use
    ``verify_pieces`` for those.
    """
    from torrent_tpu.sched import SchedLaunchError
    from torrent_tpu.utils.log import get_logger

    if getattr(info, "v2", False):
        raise ValueError("scheduler sessions are sha1/v1-only; use verify_pieces")
    n = info.num_pieces
    bitfield = np.zeros(n, dtype=bool)
    if n == 0:
        return bitfield
    futs = await enqueue_torrent_sched(storage, info, scheduler, tenant, chunk_pieces)
    done = 0
    for fut, keep in futs:
        try:
            ok = await fut
        except SchedLaunchError as e:
            get_logger("parallel.verify").warning(
                "recheck: %d pieces unverified (hash launch failed: %s)",
                len(keep), e,
            )
            done += len(keep)  # stay False in the bitfield: retry later
            if progress_cb:
                progress_cb(min(done, n), n)
            continue
        for j, i in enumerate(keep):
            bitfield[i] = bool(ok[j])
        done += len(keep)
        if progress_cb:
            progress_cb(min(done, n), n)
    return bitfield


def verify_pieces(
    storage: Storage,
    info: InfoDict,
    hasher: str = "cpu",
    progress_cb: ProgressCb | None = None,
    **tpu_kwargs,
) -> np.ndarray:
    """Recheck every piece; returns ``bool[n_pieces]``.

    ``hasher`` mirrors the BASELINE API contract: ``"cpu"`` (default,
    streaming hashlib — the reference's std/crypto analogue) or ``"tpu"``
    (batched device path; on CPU-only hosts XLA still runs it, so the flag
    selects *strategy*, not hardware — ``utils.device.device_info()``
    names what it ran on). v2 session infos (session/v2.py) route to the
    merkle recheck automatically.
    """
    if info.num_pieces == 0:
        return np.zeros(0, dtype=bool)
    v2 = getattr(info, "v2", False)
    if hasher == "cpu":
        fn = verify_pieces_v2_cpu if v2 else verify_pieces_cpu
        return fn(storage, info, progress_cb)
    if hasher == "tpu":
        if v2:
            import jax

            # v2 batches are pad-grouped per host (no global mesh), so
            # the DCN route keys on process_count alone: on a cluster
            # every process calls collectively and gets the identical
            # bitfield; for host-local-only semantics call
            # verify_pieces_v2_tpu directly. An explicit caller subset
            # (indices=...) is host-local by definition — the
            # distributed stride would silently override it, so it
            # always takes the local path.
            if jax.process_count() > 1 and "indices" not in tpu_kwargs:
                from torrent_tpu.parallel.distributed import (
                    verify_pieces_v2_distributed,
                )

                return verify_pieces_v2_distributed(
                    storage,
                    info,
                    batch_size=tpu_kwargs.get("batch_size", 256),
                    progress_cb=progress_cb,
                )
            return verify_pieces_v2_tpu(
                storage, info, progress_cb=progress_cb, **tpu_kwargs
            )
        return verify_pieces_tpu(
            storage, info, progress_cb=progress_cb, **tpu_kwargs
        )
    raise ValueError(f"unknown hasher {hasher!r}")
