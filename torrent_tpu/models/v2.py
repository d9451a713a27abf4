"""BitTorrent v2 hashing/verify pipeline — batched SHA-256 + merkle.

Authoring and resume-recheck for BEP 52 torrents on the TPU hash plane:

- ``hash_file_v2``    — one file's bytes → (pieces_root, piece layer)
- ``build_v2``        — author a pure-v2 torrent from (path, reader)s
- ``verify_v2``       — recheck files against piece layers; returns a
                        per-piece bool array for every file (the v2
                        analogue of the v1 bitfield)

Leaves are uniform 16 KiB blocks → one padded batch through the SHA-256
plane; the merkle levels above them reduce one ``sha256_pairs`` dispatch
per level per shape group across ALL files (``roots_batched``).
``hasher='cpu'`` is device-free END TO END — hashlib leaves AND hashlib
merkle folds (``_root_cpu``) — so an explicitly-CPU author/verify never
touches the jax backend (and so never takes the chip from the process
that holds it). The independent spec oracle lives in tests/test_v2.py.
"""

from __future__ import annotations

import functools
import hashlib
import time
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np

from torrent_tpu.codec.metainfo_v2 import BLOCK, InfoDictV2, MetainfoV2, V2File
from torrent_tpu.models.merkle import (
    digests_to_words32,
    file_root_from_piece_roots,
    merkle_root,
    pad_leaves,
    piece_roots_from_leaves,
    small_file_root,
    words32_to_digests,
    zero_chain,
)
from torrent_tpu.analysis.sanitizer import guard_attrs, named_lock
from torrent_tpu.obs.ledger import pipeline_ledger
from torrent_tpu.obs.profiler import annotate
from torrent_tpu.ops.padding import alloc_padded, pad_in_place
from torrent_tpu.ops.sha256_jax import make_sha256_fn
from torrent_tpu.utils.env import env_int

# Leaf blocks hashed per device launch: 32768 × 16 KiB = 512 MiB
# staging (chosen on a retired setup, not measured on this one);
# memory-constrained hosts can dial it back via the env knob.
LEAF_BATCH = env_int("TORRENT_TPU_LEAF_BATCH", 32768)

# A "source" is either resident bytes or a filesystem path (str) that is
# streamed in LEAF_BATCH-block chunks — a 60 GiB file never holds more
# than one chunk (LEAF_BATCH x 16 KiB) in memory.
_RESIDENT = (bytes, bytearray, memoryview)


def source_len(source) -> int:
    if isinstance(source, _RESIDENT):
        return len(source)
    import os

    return os.path.getsize(source)


def _iter_source(source, chunk_bytes: int):
    """Yield ``chunk_bytes``-sized bytes-like slices of the source (last
    may be short), for the callers that hash them on the CPU or tee them
    (``_leaf_words_cpu``, ``_hybrid_hash_file``); the device road reads a
    path by row into the leaf slab (:class:`_PathChunk`) and comes
    here only without the native engine.

    A resident source is sliced, not copied. Path sources go through the
    native C++ pread pool when it's built (striped parallel reads per
    chunk, straight into the buffer whose ``memoryview`` is yielded — the
    same engine behind ``Storage.read_batch``); plain buffered reads
    otherwise.
    """
    ledger = pipeline_ledger()
    if isinstance(source, _RESIDENT):
        mv = memoryview(source)
        for off in range(0, len(mv), chunk_bytes):
            yield mv[off : off + chunk_bytes]
        return
    from torrent_tpu.native.io_engine import get_engine

    engine = get_engine()
    total = source_len(source)
    if engine is not None and total > 0:
        path = str(source)
        stripes = 4
        for off in range(0, total, chunk_bytes):
            n = min(chunk_bytes, total - off)
            step = -(-n // stripes)
            segs = [
                (0, off + s, s, min(step, n - s)) for s in range(0, n, step)
            ]
            chunk = np.empty(n, dtype=np.uint8)
            # the engine charges the ledger's ``read`` stage itself
            engine.read_segments([path], segs, chunk)
            yield chunk.data
        return
    with open(source, "rb") as f:
        while True:
            with ledger.track("read") as t:
                chunk = f.read(chunk_bytes)
                t.add(len(chunk))
            if not chunk:
                return
            yield chunk


class _PathChunk(NamedTuple):
    """``nbytes`` of the file at ``path`` from ``off``: a chunk that the
    native engine reads by row straight into the leaf slab."""

    engine: object
    path: str
    off: int
    nbytes: int

    def read_rows(self, padded: np.ndarray) -> None:
        """Row ``i`` of the launch is the file's 16 KiB block at ``off +
        i * BLOCK`` (the last one may be short), so a segment a row lands
        each where the kernel reads it: no read buffer, no copy. The
        engine serves rows that follow one another sixteen a ``preadv``,
        charges the ledger's ``read`` stage itself, and raises
        ``NativeIOError`` (an ``OSError``) when the file ends early."""
        full, rem = divmod(self.nbytes, BLOCK)
        k = full + (1 if rem else 0)
        rows = np.arange(k, dtype=np.int64)
        quads = np.zeros((k, 4), dtype=np.int64)
        quads[:, 1] = self.off + rows * BLOCK
        quads[:, 2] = rows * padded.strides[0]
        quads[:, 3] = BLOCK
        if rem:
            quads[-1, 3] = rem
        self.engine.read_into(
            [self.path], quads, padded.ctypes.data, padded.nbytes, keepalive=padded
        )


def _make_leaf_fn(b: int, backend: str):
    """SHA-256 fn for a ``b``-row leaf batch; ``auto`` prefers Pallas.

    The pallas kernel pads launches to a ``tile_sub*128``-row multiple and
    only compiles for real (non-interpret) on the TPU platform — anywhere
    else the scan backend wins. Any 1024-row-multiple batch qualifies:
    pick the largest sublane count that divides ``b`` (pow-2 bucketed
    batches of 1024/2048 rows keep the fast path at tile_sub 8/16); a
    smaller batch drops to the scan backend. Returns ``(fn, kernel)``:
    ``kernel`` names which it is (``"pallas"`` or ``"scan"``), and every
    launch is counted under that name (:data:`LEAF_LAUNCH_HIST`).
    """
    if backend == "auto":
        from torrent_tpu.ops.sha1_pallas import _auto_interpret

        backend = "jax"
        if not _auto_interpret():
            from torrent_tpu.ops import sha256_pallas as sp256

            # try the tuned TORRENT_TPU_SHA256_TILE_SUB first — the
            # knob must actually reach this hot path or the sweep
            # tool's winner would be a no-op here
            for ts in dict.fromkeys((sp256.TILE_SUB, 32, 16, 8)):
                if b % (ts * 128) == 0:
                    fn = functools.partial(sp256.sha256_pieces_pallas, tile_sub=ts)
                    return fn, "pallas"
    return make_sha256_fn(backend), "pallas" if backend == "pallas" else "scan"


# The XLA module names of the leaf steps (``jit_`` + the traced
# function's name): what ``sha256_pieces_pallas`` and ``sha256_pieces_jax``
# lower to. The benchmark finds the leaf step's device time by these
# names (``step_modules`` of a ``sha256`` configuration), as it finds the
# SHA-1 steps by ``models.verifier.STEP_MODULE_NAMES``; the merkle
# reduce's modules are apart (``models.merkle.MERKLE_MODULE_NAMES``).
# tests/test_v2_stage_spans.py holds the steps to this set.
LEAF_STEP_MODULE_NAMES = frozenset({"jit__sha256_pallas_aligned", "jit_sha256_pieces_jax"})

# Per-launch wall time of the v2 leaf path by kernel, from the upload's
# start to the fetch's end (in verify_v2's window of one that span holds
# the next launch's staging and upload too). Its per-kernel counts are
# how a drop from Mosaic to the scan backend (a leaf batch that is not a
# 1024-row multiple, a non-TPU platform) stays visible.
LEAF_LAUNCH_HIST = (
    "torrent_tpu_v2_leaf_launch_seconds",
    "v2 leaf-plane launch wall time (h2d + kernel + d2h) by kernel",
)


class _LeafCounters:
    """Leaf launches by kernel, and the rows each staged and had live.
    Batch rows are pow-2 bucketed, so a launch stages more rows than it
    hashes (28,673 leaves go out as 32,768 rows, 4 as 16): launched less
    live is that padding, and the ``scan`` entries are the small files'
    drop off the Pallas kernel."""

    def __init__(self):
        self._lock = named_lock("models.v2._leaf_lock")
        self._cells = guard_attrs("models.v2.leaf_counters", "by_kernel")
        self._by_kernel: dict[str, list[int]] = {}  # one entry a kernel name: pallas, scan

    def add(self, kernel: str, launched: int, live: int) -> None:
        with self._lock:
            self._cells.write("by_kernel")
            c = self._by_kernel.setdefault(kernel, [0, 0, 0])
            c[0] += 1
            c[1] += launched
            c[2] += live

    def stats(self) -> dict[str, dict[str, int]]:
        with self._lock:
            self._cells.read("by_kernel")
            return {
                k: {"launches": c[0], "rows_launched": c[1], "rows_live": c[2]}
                for k, c in self._by_kernel.items()
            }


_leaf_counters = _LeafCounters()


def leaf_launch_stats() -> dict[str, dict[str, int]]:
    """``{kernel: {launches, rows_launched, rows_live}}`` of this
    process's leaf launches, ``kernel`` being ``pallas`` or ``scan``.
    Rendered by ``/metrics`` (utils/metrics.py)."""
    return _leaf_counters.stats()


class _LeafSlab:
    """The process's padded leaf slab, and the counters of its use.

    One kept ``uint8[rows, padded_len_for(BLOCK)]`` buffer, grown to the
    largest row bucket a caller has asked for and never beyond
    ``LEAF_BATCH`` rows (541 MB), as ``models.verifier._step_cache`` keeps
    the jitted steps: a slab allocated anew a file is paid in page faults
    when it is filled and again when it is freed (1.9 s and 0.5 s of a
    9 s recheck pass on the chip's machine; PERF.md §6, PR 30). A smaller
    bucket takes the prefix ``slab[:b]``, which is C-contiguous, so every
    launch shape uses the same pages. Nothing in it is ever zeroed whole:
    :func:`_pad_rows` makes the live rows of a launch sound, and the rows
    past them keep whatever they held.

    Checked out for one :func:`_leaf_words_from_chunks` call and back in
    at its end, under one lock. A caller that finds it out (two threads
    of a session) gets a transient slab and never waits."""

    def __init__(self):
        self._lock = named_lock("models.v2._slab_lock")
        self._cells = guard_attrs("models.v2.leaf_slab", "slab")
        self._slab: np.ndarray | None = None  # bounded-by: LEAF_BATCH rows
        self._out = False
        self._counts = {
            "leaf_slab_allocs": 0, "leaf_slab_reuses": 0, "leaf_slab_transient": 0,
            "direct": 0, "copied": 0, "leaf_launches_overlapped": 0, "leaf_uploads_chunked": 0,
        }
        # the leaf road's one pool of upload workers, built on the first
        # launch that goes up in row blocks (_enqueue_leaves)
        self._pool_lock = named_lock("models.v2._upload_pool_lock")
        self._pool: ThreadPoolExecutor | None = None

    def checkout(self, b: int) -> tuple[np.ndarray, bool]:
        """``(uint8[b, padded_len], kept)``: rows for the caller alone,
        holding whatever the last launch left. ``kept`` says they are the
        kept slab's, and the caller then owes a :meth:`checkin`."""
        with self._lock:
            self._cells.write("slab")
            if self._out:
                self._counts["leaf_slab_transient"] += 1
                return alloc_padded(b, BLOCK)[0], False
            if self._slab is not None and self._slab.shape[0] >= b:
                self._counts["leaf_slab_reuses"] += 1
            else:
                # calloc'd: a page is first touched by the read that fills it
                self._slab = alloc_padded(b, BLOCK)[0]
                self._counts["leaf_slab_allocs"] += 1
            self._out = True
            return self._slab[:b], True

    def checkin(self) -> None:
        with self._lock:
            self._cells.write("slab")
            self._out = False

    def count_launch(self, direct: bool) -> None:
        with self._lock:
            self._cells.write("slab")
            self._counts["direct" if direct else "copied"] += 1

    def count_overlap(self) -> None:
        with self._lock:
            self._cells.write("slab")
            self._counts["leaf_launches_overlapped"] += 1

    def count_chunked(self) -> None:
        with self._lock:
            self._cells.write("slab")
            self._counts["leaf_uploads_chunked"] += 1

    def upload_pool(self) -> ThreadPoolExecutor:
        """The pool a chunked leaf upload puts its blocks on, as many
        workers as the SHA-1 flat road's."""
        with self._pool_lock:
            if self._pool is None:
                from torrent_tpu.models.verifier import upload_chunks

                self._pool = ThreadPoolExecutor(max_workers=upload_chunks())
            return self._pool

    def stats(self) -> dict[str, int]:
        with self._lock:
            self._cells.read("slab")
            return dict(self._counts)


_leaf_slab = _LeafSlab()


def leaf_slab_stats() -> dict[str, int]:
    """``leaf_slab_allocs`` / ``_reuses`` / ``_transient``: check-outs
    of the process's leaf slab that allocated or grew it, found it large
    enough, or found it out and took a transient one; ``direct`` /
    ``copied``: leaf launches whose rows the native engine read into the
    slab, or that were copied in from a resident chunk;
    ``leaf_launches_overlapped``: leaf launches dispatched while an
    earlier one was still unfetched (:class:`_LeafWindow`, n - 1 a
    ``verify_v2`` pass of n launches); ``leaf_uploads_chunked``: leaf
    launches whose slab went up in row blocks (:func:`_leaf_block_rows`:
    Pallas launches of 2,048 rows and more). Rendered by ``/metrics``
    (utils/metrics.py)."""
    return _leaf_slab.stats()


def _pad_rows(padded: np.ndarray, nbytes: int) -> np.ndarray:
    """SHA-256 padding for a launch whose first ``nbytes`` of data
    columns are live, on rows that may hold an earlier launch's bytes →
    ``int32[b]`` block counts. ``pad_in_place`` needs zeros after each
    live message: the pad columns of the live rows (128 B a row) and the
    tail of the one short last row are zeroed, never the slab. Rows past
    the live ones carry ``nblocks = 0`` and never run. ``nbytes == 0`` is
    the empty source's single zero-length leaf."""
    full, rem = divmod(nbytes, BLOCK)
    k = max(1, full + (1 if rem else 0))
    lengths = np.full(k, BLOCK, dtype=np.int64)
    padded[:k, BLOCK:] = 0
    if full < k:
        padded[full, rem:BLOCK] = 0
        lengths[full] = rem
    nblocks = np.zeros(padded.shape[0], dtype=np.int32)
    nblocks[:k] = pad_in_place(padded[:k], lengths)
    return nblocks


class _LeafFlight(NamedTuple):
    """A leaf launch dispatched by :func:`_enqueue_leaves` and not yet
    fetched: the device's ``u32[rows, 8]`` and what its fetch counts."""

    out: object
    kernel: str
    nbytes: int
    rows: int
    live: int
    t0: float


def _leaf_block_rows(kernel: str, rows: int) -> int:
    """Rows a block of a leaf launch's upload: a launch for the Pallas
    kernel (a ``SUB_TILE_ROWS`` multiple) goes up in as many blocks as
    the SHA-1 flat road's upload (``models.verifier.upload_chunks``), each
    a whole number of 1,024-row sub-tiles, so a tile reads its rows from
    one block or joins a few sub-tile slices; a scan launch (16–512 rows:
    the small files, the session's ingest) goes up in one put, where the
    pool's hops would cost more than they save."""
    if kernel != "pallas":
        return rows
    from torrent_tpu.models.verifier import upload_chunks
    from torrent_tpu.ops.sha256_pallas import SUB_TILE_ROWS

    n = max(1, min(upload_chunks(), rows // SUB_TILE_ROWS))
    return -(-rows // (n * SUB_TILE_ROWS)) * SUB_TILE_ROWS


def _enqueue_leaves(leaf_fn, padded, nblocks, nbytes: int = 0, copy: bool = False) -> _LeafFlight:
    """The first half of a counted launch of a :func:`_make_leaf_fn`
    pair, two ledger stages: ``h2d`` blocks until the batch is on the
    device, ``launch`` enqueues the leaf function. ``nbytes`` is the live
    payload of the batch; the upload moves the whole padded slab, as
    host-order ``u32`` rows (a free view, which the steps read without
    a byte-to-word conversion): a Pallas launch in concurrent row blocks
    on the leaf road's pool (:func:`_leaf_block_rows`, counted
    ``leaf_uploads_chunked``), which the kernel takes as they are, any
    other in one put. Once it returns ``padded`` may be refilled, except
    where the upload aliases it: ``copy`` uploads a copy of every block
    (:class:`_LeafWindow` on the CPU)."""
    import jax
    import jax.numpy as jnp

    fn, kernel = leaf_fn
    ledger = pipeline_ledger()
    words = padded.view(np.uint32)
    step = _leaf_block_rows(kernel, padded.shape[0])
    t0 = time.monotonic()
    with ledger.track("h2d", nbytes, moved=padded.nbytes + nblocks.nbytes):
        if step < padded.shape[0]:
            from torrent_tpu.models.verifier import put_row_blocks

            rows = tuple(put_row_blocks(_leaf_slab.upload_pool(), words, step, copy))
            _leaf_slab.count_chunked()
        else:
            rows = jax.device_put(words.copy() if copy else words)
        on_device = jax.block_until_ready((rows, jnp.asarray(nblocks)))
    with ledger.track("launch", nbytes):
        out = fn(*on_device)
    return _LeafFlight(out, kernel, nbytes, padded.shape[0], int(np.count_nonzero(nblocks)), t0)


def _launch_leaves(flight: _LeafFlight) -> np.ndarray:
    """The second half of a counted leaf launch → host ``u32[rows, 8]``:
    the ledger stage ``digest`` waits out the step and fetches, then the
    launch is counted (:data:`LEAF_LAUNCH_HIST`, :func:`leaf_launch_stats`).
    Every leaf launch's words come back here: the synchronous roads call
    it right after :func:`_enqueue_leaves`, verify_v2's window one launch
    later."""
    from torrent_tpu.obs.hist import histograms

    with pipeline_ledger().track("digest", flight.nbytes):
        words = np.asarray(flight.out)
    histograms().get(*LEAF_LAUNCH_HIST, kernel=flight.kernel).observe(
        time.monotonic() - flight.t0
    )
    _leaf_counters.add(flight.kernel, flight.rows, flight.live)
    return words


class _LeafWindow:
    """``verify_v2``'s window of one leaf launch across a pass: a launch
    is fetched only once the next one, of its file or a later one, is
    staged, uploaded and dispatched, so the chip hashes one while the host
    reads, pads and uploads the next. Never wider, as ``verify_storage``'s.

    The launches share the process's one slab (:class:`_LeafSlab`). On a
    chip the blocking upload has copied it before the next launch is
    staged; on the CPU backend ``device_put`` may alias an aligned slab,
    or a row block of it (``models/verifier._upload_must_copy``), so there
    a launch that goes in flight uploads a copy of every block. The
    synchronous roads fetch each launch before they stage the next and
    need none."""

    def __init__(self):
        import jax

        self.copy = jax.default_backend() == "cpu"
        self._held = None  # (flight, land): the launch in flight

    def put(self, flight: _LeafFlight, land) -> None:
        """Hold ``flight``, just dispatched, and fetch the one before it;
        ``land(words)`` takes a launch's words once it is fetched."""
        before, self._held = self._held, (flight, land)
        if before is not None:
            _leaf_slab.count_overlap()
            before_flight, before_land = before
            before_land(_launch_leaves(before_flight))

    def drain(self) -> None:
        """Fetch the launch in flight, if any, and land its words."""
        held, self._held = self._held, None
        if held is not None:
            held_flight, held_land = held
            held_land(_launch_leaves(held_flight))

    def drop(self) -> None:
        """Fetch the launch in flight, if any, and drop its words: what a
        pass that raises does, so that no launch outlives it uncounted."""
        held, self._held = self._held, None
        if held is not None:
            _launch_leaves(held[0])


def _leaf_bucket(total: int) -> int:
    """Rows of a leaf launch for a source of ``total`` bytes: pow-2
    bucketed (floor 16, cap LEAF_BATCH), so arbitrary file sizes share a
    handful of compiled executables instead of one per block count."""
    n = max(1, -(-total // BLOCK))
    return min(LEAF_BATCH, max(16, 1 << (n - 1).bit_length()))


def _leaf_words_from_chunks(
    chunks, total: int, backend: str, on_launch=None, window: _LeafWindow | None = None
) -> np.ndarray:
    """SHA-256 leaf hashes from an iterator of chunks → ``u32[n, 8]``,
    ``n = ceil(total / BLOCK)``.

    Every chunk is a launch of its own over the bucket of ``total``, its
    leaves starting at row 0 of the slab, so a chunk that ends in a short
    leaf pads that leaf where the chunk ends (``_pad_rows``). The output
    is the chunks' rows end to end: where a chunk that is not
    block-aligned stands before another, ``total`` must count whole rows
    (the sum of each chunk's ``ceil(bytes / BLOCK)`` times ``BLOCK``), as
    ``session/v2.ingest_verdicts`` gives it. A launch of several chunks
    at once would break that placement.

    A chunk is bytes-like (copied into the slab's rows) or a
    :class:`_PathChunk` (read into them); either way the rows are those
    of the process's one kept slab (:class:`_LeafSlab`). Sentinel rows
    carry ``nblocks=0`` and never run. ``on_launch(leaves_done)`` is
    called after every launch is fetched. Without ``window`` each launch
    is fetched before the next is staged, and the output is whole when
    this returns; with one, every launch goes through it, and the output
    is whole once ``window`` has fetched the last.
    """
    ledger = pipeline_ledger()
    n = max(1, -(-total // BLOCK))
    b = _leaf_bucket(total)
    with ledger.track("pass_setup"):
        with annotate("make_leaf_fn"):
            leaf_fn = _make_leaf_fn(b, backend)
        out = np.zeros((n, 8), dtype=np.uint32)
        with annotate("alloc_padded"):
            # Every launch is staged in these rows. Without a window each
            # is fetched before the next is staged, so they are free to
            # refill on any backend. In a window the next launch is staged
            # while this one runs: a chip's blocking upload has copied the
            # rows by then, but the CPU backend's jnp.asarray may alias a
            # 64-byte-aligned host buffer (models/verifier.py,
            # _upload_must_copy), so there the window uploads a copy.
            padded, kept = _leaf_slab.checkout(b)
    filled = 0

    def launch(nblocks: np.ndarray, nbytes: int, k: int) -> None:
        def land(words: np.ndarray) -> None:
            nonlocal filled
            out[filled : filled + k] = words[:k]
            filled += k
            if on_launch is not None:
                on_launch(filled)

        if window is None:
            land(_launch_leaves(_enqueue_leaves(leaf_fn, padded, nblocks, nbytes)))
        else:
            window.put(_enqueue_leaves(leaf_fn, padded, nblocks, nbytes, window.copy), land)

    try:
        view = padded[:, :BLOCK]
        for chunk in chunks:
            direct = isinstance(chunk, _PathChunk)
            nbytes = chunk.nbytes if direct else len(chunk)
            if direct:
                chunk.read_rows(padded)
            with ledger.track("stage", nbytes):
                if not direct:
                    flat = np.frombuffer(chunk, dtype=np.uint8)
                    full, rem = divmod(nbytes, BLOCK)
                    view[:full] = flat[: full * BLOCK].reshape(full, BLOCK)
                    if rem:
                        view[full, :rem] = flat[full * BLOCK :]
                nblocks = _pad_rows(padded, nbytes)
            _leaf_slab.count_launch(direct)
            launch(nblocks, nbytes, -(-nbytes // BLOCK))
        if total == 0:  # empty source: single zero-length leaf
            with ledger.track("stage"):
                nblocks = _pad_rows(padded, 0)
            _leaf_slab.count_launch(False)
            launch(nblocks, 0, 1)
    finally:
        # a launch still in the window has its rows on the device, or a
        # copy of them: the slab is free for the next check-out
        if kept:
            _leaf_slab.checkin()
    return out


def _leaf_words_device(
    source, backend: str, on_launch=None, window: _LeafWindow | None = None
) -> np.ndarray:
    total = source_len(source)
    chunk_bytes = _leaf_bucket(total) * BLOCK
    engine = None
    if total > 0 and not isinstance(source, _RESIDENT):
        from torrent_tpu.native.io_engine import get_engine

        engine = get_engine()
    if engine is not None:
        chunks = (
            _PathChunk(engine, str(source), off, min(chunk_bytes, total - off))
            for off in range(0, total, chunk_bytes)
        )
    else:
        chunks = _iter_source(source, chunk_bytes)
    return _leaf_words_from_chunks(chunks, total, backend, on_launch, window)


def _leaf_words_cpu_from_chunks(chunks) -> np.ndarray:
    digs = []
    for chunk in chunks:
        for i in range(0, len(chunk), BLOCK):
            digs.append(hashlib.sha256(chunk[i : i + BLOCK]).digest())
    if not digs:
        digs.append(hashlib.sha256(b"").digest())
    return digests_to_words32(digs)


def _leaf_words_cpu(source) -> np.ndarray:
    return _leaf_words_cpu_from_chunks(_iter_source(source, LEAF_BATCH * BLOCK))


def _root_cpu(words: np.ndarray, pad_to: int, pad_digest: bytes = b"\x00" * 32) -> bytes:
    """hashlib pair-fold of ``u32[n, 8]`` leaf/node words padded to
    ``pad_to`` with ``pad_digest`` — the device-free merkle reduction the
    ``hasher='cpu'`` paths use (a pure-CPU run must never touch the jax
    backend)."""
    nodes = list(words32_to_digests(words)) + [pad_digest] * (pad_to - words.shape[0])
    while len(nodes) > 1:
        nodes = [
            hashlib.sha256(nodes[i] + nodes[i + 1]).digest()
            for i in range(0, len(nodes), 2)
        ]
    return nodes[0]


def roots_batched(
    entries: "list[tuple[int, np.ndarray]]", piece_length: int, device: bool = True
) -> list[tuple[bytes, tuple[bytes, ...]]]:
    """(pieces_root, layer) for MANY files from precomputed leaf words,
    with ONE pair-reduction dispatch per tree level per shape group
    instead of one reduction chain per file (round-2 verdict #3: the
    per-file merkle levels were many small dispatches).

    ``entries`` is ``[(length, leaf_words u32[n,8]), ...]``. Three
    batched stages, numerically identical to hash_file_v2:

    1. small files (≤1 piece) group by their pow2 leaf-pad target; each
       group stacks to ``[k, target, 8]`` and reduces together (the
       leading axis of ``merkle_root`` flattens into the pair batch);
    2. big files' leaf grids concatenate to ``[total_pieces, lpp, 8]``
       — every piece root of every file in log2(lpp) dispatches;
    3. per-file piece-root layers pad with the zero-piece-subtree root,
       group by padded length, and reduce stacked the same way.
    """
    lpp = piece_length // BLOCK
    out: list = [None] * len(entries)

    # stage 1: single-piece files, grouped by pad target
    small_groups: dict[int, list[int]] = {}
    for i, (length, leaves) in enumerate(entries):
        if length == 0:
            out[i] = (b"\x00" * 32, ())
        elif length <= piece_length:
            n = leaves.shape[0]
            target = max(1, 1 << max(0, (n - 1).bit_length()))
            small_groups.setdefault(target, []).append(i)
    for target, idxs in small_groups.items():
        if device:
            stacked = np.stack(
                [pad_leaves(entries[i][1], target) for i in idxs]
            )  # [k, target, 8]
            roots = words32_to_digests(merkle_root(stacked))
        else:
            roots = [_root_cpu(entries[i][1], target) for i in idxs]
        for i, r in zip(idxs, roots):
            out[i] = (r, ())

    # stage 2: all big files' piece roots in one reduction chain
    big = [i for i, (length, _) in enumerate(entries) if length > piece_length]
    if big:
        counts = [-(-entries[i][0] // piece_length) for i in big]
        if device:
            grid = np.zeros((sum(counts), lpp, 8), dtype=np.uint32)
            pos = 0
            for i, n_pieces in zip(big, counts):
                leaves = entries[i][1]
                grid.reshape(-1, 8)[pos * lpp : pos * lpp + leaves.shape[0]] = leaves
                pos += n_pieces
            all_roots = merkle_root(grid)  # [sum_pieces, 8]
        else:
            rows = []
            for i, n_pieces in zip(big, counts):
                leaves = entries[i][1]
                for p in range(n_pieces):
                    rows.append(
                        digests_to_words32(
                            [_root_cpu(leaves[p * lpp : (p + 1) * lpp], lpp)]
                        )[0]
                    )
            all_roots = np.stack(rows)

        # stage 3: file roots from the piece-root layers, grouped by
        # padded layer length (zero-piece-subtree padding, BEP 52)
        height = lpp.bit_length() - 1
        zero_root = zero_chain(height)[height]
        zero_root_words = digests_to_words32([zero_root])[0]
        layer_groups: dict[int, list[tuple[int, np.ndarray]]] = {}
        pos = 0
        for i, n_pieces in zip(big, counts):
            roots_i = all_roots[pos : pos + n_pieces]
            pos += n_pieces
            padded_n = 1 << max(0, (n_pieces - 1).bit_length())
            layer_groups.setdefault(padded_n, []).append((i, roots_i))
        for padded_n, group in layer_groups.items():
            if device:
                stacked = np.tile(zero_root_words, (len(group), padded_n, 1))
                for g, (_, roots_i) in enumerate(group):
                    stacked[g, : roots_i.shape[0]] = roots_i
                file_roots = words32_to_digests(merkle_root(stacked))
            else:
                file_roots = [
                    _root_cpu(roots_i, padded_n, pad_digest=zero_root)
                    for _, roots_i in group
                ]
            for (i, roots_i), fr in zip(group, file_roots):
                out[i] = (fr, tuple(words32_to_digests(roots_i)))
    return out


# Leaf-word window for the batched reduction passes: flush once this
# many leaves (32 B each) are resident. The default bounds leaf RAM at
# ~64 MB (covering ~32 GiB of payload per window) — batching still
# collapses reductions to one dispatch per level per shape group WITHIN
# a window, without the corpus-proportional residency of an unbounded
# pass.
LEAF_WINDOW = env_int("TORRENT_TPU_LEAF_WINDOW", 1 << 21)


def roots_batched_windowed(
    entry_iter, piece_length: int, window: int | None = None, device: bool = True
) -> list[tuple[bytes, tuple[bytes, ...]]]:
    """Windowed driver for :func:`roots_batched`: consumes an iterator of
    ``(length, leaf_words)`` and flushes whenever the resident leaf count
    reaches ``window`` (default ``LEAF_WINDOW``), so memory stays bounded
    no matter how large the corpus is. Results keep input order."""
    window = window or LEAF_WINDOW
    out: list[tuple[bytes, tuple[bytes, ...]]] = []
    buf: list[tuple[int, np.ndarray]] = []
    acc = 0

    def flush():
        # the fold is a ledger stage of its own, off the canonical
        # chain: bytes are the 32-byte leaf hashes it folds
        with pipeline_ledger().track("merkle", 32 * acc):
            out.extend(roots_batched(buf, piece_length, device=device))

    for entry in entry_iter:
        buf.append(entry)
        acc += entry[1].shape[0]
        if acc >= window:
            flush()
            buf, acc = [], 0
    if buf:
        flush()
    return out


def hash_file_v2(
    source, piece_length: int, hasher: str = "tpu"
) -> tuple[bytes, tuple[bytes, ...]]:
    """One file source (bytes or filesystem path) → (pieces_root, layer).

    The layer is empty for files of at most one piece (BEP 52 publishes
    piece layers only for multi-piece files). Path sources stream in
    bounded chunks — memory is independent of file size.
    """
    total = source_len(source)
    if total == 0:
        return b"\x00" * 32, ()
    if hasher == "cpu":
        leaves = _leaf_words_cpu(source)
        # device=False keeps a 'cpu' run off the jax backend entirely
        return roots_batched([(total, leaves)], piece_length, device=False)[0]
    leaves = _leaf_words_device(source, "auto")
    if total <= piece_length:
        return small_file_root(leaves), ()
    lpp = piece_length // BLOCK
    roots = piece_roots_from_leaves(leaves, lpp)
    layer = tuple(words32_to_digests(roots))
    return file_root_from_piece_roots(roots, lpp), layer


def build_v2(
    files: list[tuple[tuple[str, ...], "bytes | str"]],
    name: str,
    piece_length: int,
    hasher: str = "tpu",
    announce: str | None = None,
    private: bool = False,
    comment: str | None = None,
    announce_list: list[list[str]] | None = None,
    web_seeds: list[str] | None = None,
) -> MetainfoV2:
    """Author a pure-v2 torrent from (path, source) entries.

    Sources are bytes or filesystem paths (streamed — a 60 GiB corpus
    never holds more than one leaf chunk resident).
    """
    if piece_length < BLOCK or piece_length & (piece_length - 1):
        raise ValueError("piece_length must be a power of two >= 16 KiB")
    from torrent_tpu.codec.metainfo_v2 import valid_path_component

    for path, _ in files:
        for part in path:
            if not valid_path_component(part):
                raise ValueError(
                    f"path component {part!r} cannot appear in a v2 file tree "
                    "(separator/traversal/non-UTF-8 names are not encodable)"
                )
    # phase 1: leaf words per file (streaming — bounded by the chunk
    # size, not file size); phase 2: batched reduction passes across
    # files (roots_batched_windowed: one dispatch per level per shape
    # group within each bounded-residency window, not a chain per file)
    ordered = sorted(files, key=lambda e: e[0])
    lengths = [source_len(source) for _, source in ordered]

    def leaf_entries():
        for (_, source), total in zip(ordered, lengths):
            if total == 0:
                yield 0, np.zeros((0, 8), dtype=np.uint32)
            elif hasher == "cpu":
                yield total, _leaf_words_cpu(source)
            else:
                yield total, _leaf_words_device(source, "auto")

    reduced = roots_batched_windowed(
        leaf_entries(), piece_length, device=hasher != "cpu"
    )
    v2files: list[V2File] = []
    layers: dict[bytes, tuple[bytes, ...]] = {}
    for (path, _), total, (root, layer) in zip(ordered, lengths, reduced):
        v2files.append(V2File(path=path, length=total, pieces_root=root))
        if layer:
            layers[root] = layer
    info = InfoDictV2(
        name=name, piece_length=piece_length, files=tuple(v2files), private=private
    )
    from torrent_tpu.codec.metainfo_v2 import encode_metainfo_v2, parse_metainfo_v2

    encoded = encode_metainfo_v2(
        info, layers, announce,
        comment=comment, announce_list=announce_list, web_seeds=web_seeds,
    )
    parsed = parse_metainfo_v2(encoded)
    assert parsed is not None, "authored v2 metainfo failed its own parse"
    return parsed


@functools.lru_cache(maxsize=4)
def _piece_verifier(plen: int):
    """One SHA-1 hash-plane verifier per piece geometry (a fresh one per
    file would recompile the same executable over and over)."""
    from torrent_tpu.models.verifier import TPUVerifier

    return TPUVerifier(piece_length=plen, batch_size=256)


def _hybrid_hash_file(
    source, plen: int, hasher: str, pad_tail: bool
) -> tuple[bytes, tuple[bytes, ...], list[bytes]]:
    """One streaming pass → (v2 pieces_root, v2 layer, v1 piece digests).

    Both hash families consume the same chunk iterator, so hybrid
    authoring reads each file from disk exactly once. ``pad_tail`` zero-
    extends the final v1 piece to full length (BEP 47 — the pad bytes are
    part of the hashed piece). Chunk size is the leaf bucket (a power-of-
    two multiple of BLOCK, hence of ``plen`` whenever plen ≤ chunk), so
    the v1 carry is only ever the file's final partial piece.
    """
    total = source_len(source)
    if total == 0:
        return b"\x00" * 32, (), []
    chunk_bytes = _leaf_bucket(total) * BLOCK

    if hasher == "cpu":
        import hashlib as _hl

        hash_batch = lambda ps: [_hl.sha1(p).digest() for p in ps]
    else:
        hash_batch = _piece_verifier(plen).hash_pieces

    v1_digs: list[bytes] = []
    state = {"carry": b""}
    ledger = pipeline_ledger()

    # The SHA-1 arm's turns (the concatenation, the slices, hash_pieces'
    # launches) are the ledger wait ``author_v1`` and its span: a wait
    # because its launches' ``h2d`` / ``launch`` / ``digest`` stages run
    # inside it, and a stage around them would count as overlap. A turn
    # outlasts a traced slice, and the profiler drops a span it did not
    # see begin, so the copies (and their release) have a span of their
    # own, ``author_split``, as each launch's staging has in hash_pieces.
    def feed_sha1(chunk: bytes) -> None:
        with ledger.track("author_v1", len(chunk), wait=True):
            with annotate("author_split"):
                buf = state["carry"] + chunk
                full = len(buf) // plen
                pieces = [buf[i * plen : (i + 1) * plen] for i in range(full)]
                state["carry"] = buf[full * plen :]
                del buf
            if pieces:
                v1_digs.extend(hash_batch(pieces))
            with annotate("author_split"):
                del pieces

    def tee():
        for chunk in _iter_source(source, chunk_bytes):
            feed_sha1(chunk)
            yield chunk

    if hasher == "cpu":
        leaves = _leaf_words_cpu_from_chunks(tee())
    else:
        leaves = _leaf_words_from_chunks(tee(), total, "auto")
    tail = state["carry"]
    if tail:
        with ledger.track("author_v1", wait=True):
            v1_digs.extend(hash_batch([tail.ljust(plen, b"\x00") if pad_tail else tail]))

    # device=False for 'cpu' keeps explicitly-CPU hybrid authoring off
    # the jax backend (as in hash_file_v2)
    with ledger.track("merkle", 32 * leaves.shape[0]):
        root, layer = roots_batched([(total, leaves)], plen, device=hasher != "cpu")[0]
    return root, layer, v1_digs


def build_hybrid(
    files: list[tuple[tuple[str, ...], "bytes | str"]],
    name: str,
    piece_length: int,
    hasher: str = "tpu",
    announce: str | None = None,
    private: bool = False,
    comment: str | None = None,
    announce_list: list[list[str]] | None = None,
    web_seeds: list[str] | None = None,
) -> tuple[bytes, MetainfoV2]:
    """Author a hybrid v1+v2 torrent (BEP 52 upgrade path).

    Every file except the last is padded to a piece boundary with a
    BEP 47 pad file (``.pad/N``, attr ``p``) so v1 pieces never span
    files — which is exactly what lets the v1 piece hashes and the v2
    per-file merkle trees describe the same bytes. Returns the bencoded
    torrent and its parsed v2 view (``parse_metainfo`` reads the same
    blob for the v1 view).
    """
    if piece_length < BLOCK or piece_length & (piece_length - 1):
        raise ValueError("piece_length must be a power of two >= 16 KiB")
    from torrent_tpu.codec.metainfo_v2 import (
        encode_metainfo_v2,
        parse_metainfo_v2,
        valid_path_component,
    )

    for path, _ in files:
        for part in path:
            if not valid_path_component(part):
                raise ValueError(f"path component {part!r} not encodable in a file tree")

    entries = sorted(files, key=lambda e: e[0])
    v2files: list[V2File] = []
    layers: dict[bytes, tuple[bytes, ...]] = {}
    v1_pieces: list[bytes] = []
    v1_files: list[dict] = []
    single = len(entries) == 1 and entries[0][0] == (name,)
    for idx, (path, source) in enumerate(entries):
        last = idx == len(entries) - 1
        root, layer, digs = _hybrid_hash_file(
            source, piece_length, hasher, pad_tail=not last
        )
        length = source_len(source)
        v2files.append(V2File(path=path, length=length, pieces_root=root))
        if layer:
            layers[root] = layer
        v1_pieces.extend(digs)
        v1_files.append({b"length": length, b"path": [p.encode() for p in path]})
        pad = (-length) % piece_length
        if not last and pad:
            v1_files.append(
                {b"length": pad, b"path": [b".pad", str(pad).encode()], b"attr": b"p"}
            )
    info = InfoDictV2(
        name=name, piece_length=piece_length, files=tuple(v2files), private=private
    )
    with annotate("author_encode"):
        encoded = encode_metainfo_v2(
            info,
            layers,
            announce=announce,
            comment=comment,
            announce_list=announce_list,
            web_seeds=web_seeds,
            v1_pieces=v1_pieces,
            v1_files=None if single else v1_files,
            v1_length=source_len(entries[0][1]) if single else None,
        )
        parsed = parse_metainfo_v2(encoded)
    assert parsed is not None, "authored hybrid failed its own v2 parse"
    return encoded, parsed


def verify_v2(
    read_file,
    meta: MetainfoV2,
    hasher: str = "tpu",
    progress_cb=None,
) -> dict[tuple[str, ...], np.ndarray]:
    """Recheck every file against its pieces_root / piece layer.

    ``read_file(path_tuple) -> bytes | path-str | None`` supplies each
    file's source (None = missing; a path source streams in bounded
    chunks). Returns ``{path: bool[n_pieces]}`` — the v2 analogue of the
    v1 resume-recheck bitfield, per file. ``progress_cb(done_pieces,
    total_pieces)`` is called once a leaf launch on the device road
    (once a file with ``hasher="cpu"``): the pieces whose leaves are
    hashed, files without a source counted from the start.
    """
    plen = meta.info.piece_length
    lpp = plen // BLOCK
    results: dict[tuple[str, ...], np.ndarray] = {}
    # phase 1: select present, size-matching files (stashing the source —
    # calling read_file again later could observe a concurrently deleted
    # or resized file and crash instead of marking it missing); phase 2:
    # windowed batched reduction passes (one dispatch per level per shape
    # group within each bounded-residency window, not a chain per file)
    todo: list[tuple[V2File, object]] = []  # (file, source)
    for f in meta.info.files:
        n_pieces = f.num_pieces(plen)
        source = read_file(f.path)
        if source is None or (source_len(source) != f.length):
            results[f.path] = (
                np.zeros(max(1, n_pieces), dtype=bool)
                if f.length
                else np.ones(0, dtype=bool)
            )
            continue
        if f.length == 0:
            results[f.path] = np.ones(0, dtype=bool)
            continue
        todo.append((f, source))

    total_pieces = sum(f.num_pieces(plen) for f in meta.info.files)
    done = total_pieces - sum(f.num_pieces(plen) for f, _ in todo)

    # the device road keeps one leaf launch in flight across the pass, so
    # a file's leaves are whole once the next file's first launch (or the
    # drain at the end) has fetched its last: each entry goes to the fold
    # one file behind the launches
    window = None if hasher == "cpu" else _LeafWindow()

    def leaf_entries():
        nonlocal done
        held = None  # the file before, whose last launch may be in flight
        for f, source in todo:
            base = done
            done += f.num_pieces(plen)

            on_launch = None
            if progress_cb is not None:

                def on_launch(leaves_done, f=f, base=base, end=done):
                    # a file's last launch ends inside its (short) last piece
                    at_end = leaves_done * BLOCK >= f.length
                    progress_cb(end if at_end else base + leaves_done // lpp, total_pieces)

            try:
                if window is None:
                    leaves = _leaf_words_cpu(source)
                    if on_launch is not None:
                        on_launch(len(leaves))
                else:
                    leaves = _leaf_words_device(source, "auto", on_launch, window)
            except OSError:
                # a path source deleted between phases: zero leaf words
                # can't match any real root, so every piece of this file
                # lands False — same verdict as a missing file. The launch
                # in flight (the file before's last, or one of this file's)
                # is fetched first.
                if window is not None:
                    window.drain()
                leaves = np.zeros((max(1, -(-f.length // BLOCK)), 8), dtype=np.uint32)
            if held is not None:
                yield held
            held = f.length, leaves
        if window is not None:
            window.drain()
        if held is not None:
            yield held

    try:
        reduced = roots_batched_windowed(leaf_entries(), plen, device=hasher != "cpu")
    finally:
        # leaf_entries drained the window; a pass that raised left one
        # launch in flight at most
        if window is not None:
            window.drop()
    for ei, (f, _) in enumerate(todo):
        n_pieces = f.num_pieces(plen)
        ok = np.zeros(max(1, n_pieces), dtype=bool)
        got_root, got_layer = reduced[ei]
        if f.length <= plen:
            ok[0] = got_root == f.pieces_root
            results[f.path] = ok
            continue
        layer = meta.piece_layers.get(f.pieces_root, ())
        # metadata self-consistency: the published layer must merkle up to
        # the published root (a hostile layer otherwise localizes damage
        # to the wrong pieces). Data corruption must NOT trip this — the
        # per-piece comparison below is what localizes it. The cpu hasher
        # folds with hashlib (device-free guarantee).
        if len(layer) != n_pieces:
            results[f.path] = ok
            continue
        with pipeline_ledger().track("merkle", 32 * n_pieces):
            if hasher == "cpu":
                height = lpp.bit_length() - 1
                padded_n = 1 << max(0, (n_pieces - 1).bit_length())
                layer_root = _root_cpu(
                    digests_to_words32(layer), padded_n,
                    pad_digest=zero_chain(height)[height],
                )
            else:
                layer_root = file_root_from_piece_roots(digests_to_words32(layer), lpp)
        if layer_root != f.pieces_root:
            results[f.path] = ok
            continue
        for i in range(n_pieces):
            ok[i] = got_layer[i] == layer[i]
        results[f.path] = ok
    return results
