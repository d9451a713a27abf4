"""TPUVerifier — the flagship pipeline of the framework.

One object owning the compiled hash plane for a given piece geometry:

- ``verify_storage``  — full resume-recheck of a torrent (BASELINE
  configs 1, 2, 4): disk → ``Storage.read_batch`` → pad → device →
  masked SHA1 chain → on-device digest compare → ``bool`` bitfield.
  Disk IO for batch *i+1* overlaps device compute for batch *i*.
- ``hash_pieces`` / ``hash_bytes`` — authoring-side digests (BASELINE
  config 3; replaces tools/make_torrent.ts:28-32's per-piece WebCrypto).
- ``verify_batch`` — the raw jitted step, used by the HTTP bridge and by
  ``__graft_entry__`` for compile checks.

Shapes are static per (piece_length, batch_size): ragged batches are
padded to ``batch_size`` rows with ``nblocks=0`` sentinel rows, so the
whole session reuses one XLA executable. The batch axis is sharded
``(hosts, dp)`` over the mesh (parallel/mesh.py); everything up to the
final per-piece bool is embarrassingly parallel, so the only cross-chip
traffic is output gathering.
"""

from __future__ import annotations


from torrent_tpu.analysis.sanitizer import guard_attrs, named_lock
import time
from collections import OrderedDict, deque
from collections.abc import Callable
from concurrent.futures import ThreadPoolExecutor
from contextlib import nullcontext
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from torrent_tpu.codec.metainfo import InfoDict
from torrent_tpu.obs.ledger import pipeline_ledger
from torrent_tpu.obs.profiler import TRACE_SPAN_PREFIX, annotate, maybe_profile_batch
from torrent_tpu.ops.padding import (
    alloc_padded,
    digests_to_words,
    pad_in_place,
    pad_pieces,
    padded_len_for,
    words_to_digests,
)
from torrent_tpu.ops.sha1_jax import make_sha1_fn
from torrent_tpu.parallel.mesh import (
    batch_sharding,
    make_mesh,
    round_up_to_multiple,
)
from torrent_tpu.parallel.verify import VerifyResult
from torrent_tpu.utils.env import env_int
from torrent_tpu.storage.storage import Storage


# The XLA module names of the jitted steps below (``jit_`` + the traced
# function's name). The benchmark finds the hash step's device time by
# these names (``step_modules`` in benchmark/configs/*.json): a rename
# silences ``hash_step_*`` on the chip and fails nothing on a CPU, so
# tests/test_stage_spans.py holds the steps and the configurations to
# this set.
STEP_MODULE_NAMES = frozenset(
    {"jit__verify_flat", "jit__verify", "jit__digests_flat", "jit__digests"}
)

# Default bound on one Pallas tile row's input slab (its swizzle
# temporaries are ~2x that): 1.25 GiB, sized for a 16 GB chip.
DEFAULT_TILE_BYTES = 1_342_177_280


# How many step sets a process keeps. A key is a backend, its Pallas
# tile and a mesh: a deployment has one or two, the tests a handful.
STEP_CACHE_CAPACITY = 8


class _Steps(NamedTuple):
    """The five jitted steps of one (backend, tile_sub, mesh)."""

    digest_step: Callable
    verify_step: Callable
    verify_step_flat: Callable
    digest_step_flat: Callable
    digest_step_donated: Callable


def _on_cpu(mesh) -> bool:
    return next(iter(mesh.devices.flat)).platform == "cpu"


def _pallas_tile_sub(padded_len: int) -> int:
    """Adaptive tiling: one tile row (tile_sub*128 pieces) is the
    kernel's swizzle/launch granularity, and its temporaries are ~2x
    the tile slab. Big pieces shrink the sublane count so a tile stays
    ~1 GiB regardless of piece size (the sweep's measured-best regime;
    at 4096x1 MiB a whole-batch slab OOMs a 16 GB chip outright)."""
    from torrent_tpu.ops.sha1_pallas import TILE_SUB

    budget = env_int("TORRENT_TPU_TILE_BYTES", DEFAULT_TILE_BYTES)
    ts = TILE_SUB
    # step by 8s, not halving: the env default may be any multiple
    # of 8 (halving 24 would land on 12 and crash _check_tiling)
    while ts > 8 and ts * 128 * padded_len > budget:
        ts -= 8
    return ts


def _build_steps(backend: str, tile_sub: int | None, mesh) -> _Steps:
    """Wrap the steps of one key in ``jax.jit``. Nothing is traced or
    compiled here: that happens at a jitted object's first call with a
    shape, once a process, since every verifier of the key calls the
    same objects. Piece length and batch size are shapes, and
    ``jax.jit`` keys on shapes itself."""
    sha1_fn = make_sha1_fn(backend)
    pallas = backend == "pallas"
    if pallas:
        # A pallas_call has no SPMD partitioning rule, so on a >1-device
        # mesh we shard it explicitly: each device runs the kernel on its
        # local piece sub-batch (embarrassingly parallel, no collectives).
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from torrent_tpu.ops.sha1_pallas import sha1_pieces_pallas

        def sha1_fn(data, nblocks, _ts=tile_sub):
            return sha1_pieces_pallas(data, nblocks, tile_sub=_ts)

        if mesh.size > 1:
            spec = P(tuple(mesh.axis_names))
            sha1_fn = shard_map(
                sha1_fn,
                mesh=mesh,
                in_specs=(spec, spec),
                out_specs=spec,
                check_vma=False,
            )
    shard = batch_sharding(mesh)

    def _digests(data_u8, nblocks):
        return sha1_fn(data_u8, nblocks)

    def _verify(data_u8, nblocks, expected):
        words = sha1_fn(data_u8, nblocks)
        return jnp.all(words == expected, axis=1)

    # Fast single-device upload path: row-block 2-D chunks put in
    # parallel, joined with one axis-0 concat on device. padded_len is
    # 128-byte aligned (ops/padding.py), so a 2-D put is a straight
    # memcpy. The earlier flatten→concat→reshape design is
    # gone for a reason: XLA's AOT lowering of the big 1-D→2-D
    # reshape materializes a (4,1)-subtiled intermediate padded 32x —
    # a 16 GiB allocation at 512 KiB pieces. Multi-device meshes take
    # one batch-sharded 2-D put instead (_put_sharded: the road a
    # four-chip host's recheck runs, timed as its own h2d stage).
    # Chunks arrive as host-order u32 (ndarray.view is free and a
    # u8→u32 bitcast on TPU lowers through a 4x-widened convert
    # fusion — the pallas kernel consumes u32 directly). The scan
    # backend still wants u8 rows; the bitcast back is cheap there
    # (CPU/GPU lower it as a real reinterpret).
    def _join(chunks):
        data = jnp.concatenate(chunks, axis=0)
        if not pallas:
            data = jax.lax.bitcast_convert_type(data, jnp.uint8).reshape(
                data.shape[0], -1
            )
        return data

    def _verify_flat(chunks, nblocks, expected):
        words = sha1_fn(_join(chunks), nblocks)
        return jnp.all(words == expected, axis=1)

    def _digests_flat(chunks, nblocks):
        return sha1_fn(_join(chunks), nblocks)

    # Donate the uploaded chunks on real accelerators: the launch
    # consumes them exactly once, so freeing the device input buffer
    # as the kernel runs lets the NEXT batch's H2D reuse that memory
    # — the double-buffered ingest contract the scheduler's sha1
    # plane relies on. XLA-CPU refuses donation (it would only emit
    # a warning per launch), so it stays off there.
    _donate = () if _on_cpu(mesh) else (0,)
    return _Steps(
        digest_step=jax.jit(_digests, in_shardings=(shard, shard), out_shardings=shard),
        verify_step=jax.jit(
            _verify, in_shardings=(shard, shard, shard), out_shardings=shard
        ),
        verify_step_flat=jax.jit(_verify_flat, donate_argnums=_donate),
        digest_step_flat=jax.jit(_digests_flat, donate_argnums=_donate),
        # the sharded twin of the donated digest step, for upload_batch
        # on a >1-device mesh (compiled only if that path runs)
        digest_step_donated=jax.jit(
            _digests, in_shardings=(shard, shard), out_shardings=shard,
            donate_argnums=_donate,
        ),
    )


class _StepCache:
    """The process's jitted steps, one set a (backend, tile_sub, mesh).

    ``jax.jit`` finds a traced and loaded program again by the function
    object it wraps, so steps wrapped once a verifier would trace the
    scan and load its program at every verifier's first call, which is
    every recheck pass's (``step_load``: 0.2 s of a 0.63 s pass on one
    chip, 0.3 s of 0.74 s on four; PERF.md §6, PR 29). Filled under one
    lock, so two threads that ask for one key get one set; the jitted
    objects themselves are safe to call from any thread. Past the
    capacity the set that was asked for longest ago goes, with the
    executables it holds."""

    def __init__(self, capacity: int):
        self._capacity = capacity
        self._steps_lock = named_lock("models.verifier._steps_lock")
        self._cells = guard_attrs("models.verifier.steps", "entries")
        self._entries: OrderedDict = OrderedDict()  # bounded-by: _capacity
        self._builds = 0
        self._reuses = 0

    def get(self, backend: str, tile_sub: int | None, mesh) -> _Steps:
        key = (backend, tile_sub, mesh)
        with self._steps_lock:
            self._cells.write("entries")
            steps = self._entries.get(key)
            if steps is not None:
                self._entries.move_to_end(key)
                self._reuses += 1
                return steps
            steps = self._entries[key] = _build_steps(backend, tile_sub, mesh)
            self._builds += 1
            if len(self._entries) > self._capacity:
                self._entries.popitem(last=False)
            return steps

    def stats(self) -> dict[str, int]:
        with self._steps_lock:
            self._cells.read("entries")
            return {"step_builds": self._builds, "step_reuses": self._reuses}


_step_cache = _StepCache(STEP_CACHE_CAPACITY)


def step_cache_stats() -> dict[str, int]:
    """``step_builds``: step sets this process has wrapped in
    ``jax.jit``; ``step_reuses``: verifiers built on a set that was
    there. Rendered by ``/metrics`` (utils/metrics.py)."""
    return _step_cache.stats()


# The most host memory a process keeps as a recheck's staging pair, both
# slabs together. The CLI's ``--batch 256`` at 256 KiB pieces asks for
# 2 x 67 MB and ``--batch 1024`` for 2 x 269 MB; ``--batch 4096`` at
# 1 MiB would pin 8.6 GB, so a pair over this is allocated a pass.
STAGING_KEEP_BYTES = 1 << 30


class _StagingPair:
    """The process's two staging slabs of a recheck, and the counters of
    their use.

    ``verify_storage`` fills one padded slab while the device consumes
    the other. Allocated anew a pass, each slab's first fill is paid in
    page faults (on four chips 0.2 s of ``first_load`` and 0.16 s of the
    first ``read_wait`` in a 0.60 s pass; PERF.md §6, PR 35), so one pair
    is kept, as ``_step_cache`` keeps the jitted steps and
    ``models.v2._LeafSlab`` the leaf slab. A pass of the same row width
    and no more rows takes the C-contiguous row prefix of the same
    pages; another width or more rows replaces the pair, the old one
    freed first, so a process holds one pair at most, and none over
    ``STAGING_KEEP_BYTES``. Nothing is zeroed here: ``load()`` makes
    every row of every batch sound before it is launched (``read_batch``
    zero-fills the rows it reads into; the pad columns and the rows past
    the last piece are cleared), so by then a kept slab holds nothing of
    an earlier pass.

    Checked out for one ``verify_storage`` call and back in at its end,
    under one lock. A caller that finds the pair out (two rechecks at
    once in a session) gets a transient pair and never waits."""

    def __init__(self):
        self._lock = named_lock("models.verifier._staging_lock")
        self._cells = guard_attrs("models.verifier.staging", "pair")
        self._pair: list[np.ndarray] | None = None  # bounded-by: STAGING_KEEP_BYTES
        self._out = False
        self._counts = {
            "staging_slab_allocs": 0, "staging_slab_reuses": 0, "staging_slab_transient": 0,
        }

    def checkout(
        self, rows: int, piece_length: int
    ) -> tuple[list[tuple[np.ndarray, np.ndarray]], bool]:
        """``([(padded, view), (padded, view)], kept)``: two slabs of
        :func:`alloc_padded`'s shape for the caller alone, holding
        whatever the last pass left. ``kept`` says they are the kept
        pair's, and the caller then owes a :meth:`checkin` once nothing
        reads or writes them any more."""
        width = padded_len_for(piece_length)
        with self._lock:
            self._cells.write("pair")
            if self._out or 2 * rows * width > STAGING_KEEP_BYTES:
                self._counts["staging_slab_transient"] += 1
                return [alloc_padded(rows, piece_length) for _ in range(2)], False
            pair = self._pair
            if pair is not None and pair[0].shape[1] == width and pair[0].shape[0] >= rows:
                self._counts["staging_slab_reuses"] += 1
            else:
                self._pair = pair = None  # the old pair goes before the new one comes
                # calloc'd: a page is first touched by the read that fills it
                self._pair = pair = [alloc_padded(rows, piece_length)[0] for _ in range(2)]
                self._counts["staging_slab_allocs"] += 1
            self._out = True
            return [(p[:rows], p[:rows, :piece_length]) for p in pair], True

    def checkin(self) -> None:
        with self._lock:
            self._cells.write("pair")
            self._out = False

    def stats(self) -> dict[str, int]:
        with self._lock:
            self._cells.read("pair")
            return dict(self._counts)


_staging_pair = _StagingPair()


def staging_slab_stats() -> dict[str, int]:
    """``staging_slab_allocs`` / ``_reuses`` / ``_transient``: recheck
    passes whose check-out allocated or replaced the process's kept
    staging pair, found it large enough, or took a transient pair (the
    kept one was out, or the pair asked for is over
    ``STAGING_KEEP_BYTES``). Rendered by ``/metrics``
    (utils/metrics.py)."""
    return _staging_pair.stats()


class TPUVerifier:
    def __init__(
        self,
        piece_length: int,
        batch_size: int = 1024,
        backend: str = "jax",
        mesh=None,
        devices=None,
    ):
        if piece_length <= 0:
            raise ValueError("piece_length must be positive")
        self.piece_length = piece_length
        self.mesh = mesh if mesh is not None else make_mesh(devices)
        self.batch_size = round_up_to_multiple(max(batch_size, self.mesh.size), self.mesh.size)
        self.padded_len = padded_len_for(piece_length)
        self.backend = backend
        self.tile_sub = None
        if backend == "pallas":
            self.tile_sub = _pallas_tile_sub(self.padded_len)
            # Per-device sub-batches must be tile-aligned or every
            # launch pads with wasted sentinel rows.
            self.batch_size = round_up_to_multiple(
                self.batch_size, self.tile_sub * 128 * self.mesh.size
            )
        steps = _step_cache.get(backend, self.tile_sub, self.mesh)
        self._digest_step = steps.digest_step
        self._verify_step = steps.verify_step
        self._verify_step_flat = steps.verify_step_flat
        self._digest_step_flat = steps.digest_step_flat
        self._digest_step_donated = steps.digest_step_donated
        # 4 concurrent upload streams: chosen on a retired setup, not
        # measured on this one.
        self._upload_chunks = env_int("TORRENT_TPU_UPLOAD_CHUNKS", 4)
        # Row counts the flat road takes on a one-device mesh: the
        # verifier's own batch, and what a caller warms besides (the
        # scheduler's SHA-1 plane adds its row ladder). Any other shape
        # takes the sharded step. Read on the chip (PERF.md, PR 25): at
        # 32 rows of 256 KiB the flat road uploads in 1.7 ms and steps
        # in 5.8, the sharded one in 2.2 and 6.7.
        self.flat_rows = {self.batch_size}
        self._upload_pool: ThreadPoolExecutor | None = None
        # verify_batch/digest_batch may be called from several threads on a
        # shared verifier (the bridge does); first-use pool init must not race
        self._upload_pool_lock = named_lock("models.verifier._upload_pool_lock")
        # On the CPU backend device_put can zero-copy an aligned numpy
        # view — the "device" array then aliases the staging buffer, and
        # reusing the buffer while a batch is still in flight would
        # corrupt it. Force a real copy there (still done in the upload
        # worker threads, so it's parallel).
        self._upload_must_copy = _on_cpu(self.mesh)
        self._shard = batch_sharding(self.mesh)
        # A mesh spanning >1 process (parallel/distributed.py) cannot be
        # fed global numpy arrays — each process only holds its
        # addressable shard. verify/digest then take this process's
        # LOCAL rows (batch_size / process_count of them) and convert
        # via make_array_from_process_local_data.
        self._mesh_processes = len(
            {d.process_index for d in self.mesh.devices.flat}
        )

    def _use_flat(self, padded: np.ndarray) -> bool:
        return (
            self.mesh.size == 1
            and isinstance(padded, np.ndarray)
            and padded.ndim == 2
            and padded.shape[1] == self.padded_len
            and padded.shape[0] in self.flat_rows
        )

    def _put_flat(self, padded: np.ndarray) -> list[jax.Array]:
        """Upload ``uint8[B, padded_len]`` as concurrent row-block chunks.

        Blocks until every chunk is resident so the caller may reuse the
        staging buffer immediately. The caller opens the ledger's ``h2d``
        stage around this call.
        """
        with self._upload_pool_lock:
            if self._upload_pool is None:
                self._upload_pool = ThreadPoolExecutor(max_workers=self._upload_chunks)
            pool = self._upload_pool
        rows = padded.shape[0]
        step = -(-rows // self._upload_chunks)
        views = [
            padded[i : i + step].view(np.uint32) for i in range(0, rows, step)
        ]
        if self._upload_must_copy:
            put = lambda v: jax.device_put(v.copy())
        else:
            put = jax.device_put
        chunks = list(pool.map(put, views))
        for c in chunks:
            c.block_until_ready()
        return chunks

    # ------------------------------------------------------------ raw steps

    def _put_global(self, padded, nblocks, expected_words=None):
        """Multi-process input path: build global batch-sharded Arrays
        from this process's local rows (parallel/distributed.py)."""
        from torrent_tpu.parallel.distributed import global_batch

        args = [global_batch(self._shard, np.asarray(padded)),
                global_batch(self._shard, np.asarray(nblocks))]
        if expected_words is not None:
            args.append(global_batch(self._shard, np.asarray(expected_words)))
        return args

    def _put_sharded(self, *arrays):
        """Single-process mesh input path: one explicit ``device_put`` of
        the rows (and their ``nblocks`` / ``expected``) with the batch
        sharding, awaited, so the staging buffer may be reused and the
        caller's ``h2d`` stage times the transfer itself. Explicit also
        because on a multi-process CLUSTER even a fully-addressable
        local mesh can't take numpy args through a jit with non-trivial
        in_shardings (each pod host bulk-validating its library shard
        on its own devices, verify_library_distributed)."""
        return jax.block_until_ready(jax.device_put(arrays, self._shard))

    def verify_batch_global(
        self, padded: np.ndarray, nblocks: np.ndarray, expected_words: np.ndarray
    ):
        """Multi-process verify: inputs are this process's LOCAL rows
        (``batch_size / process_count`` of them); returns
        ``(ok_local, ok_global)`` — the local bool rows plus the global
        sharded device array for collective stats (psum_valid_count)."""
        from torrent_tpu.parallel.distributed import local_values

        ok_global = self._verify_step(
            *self._put_global(padded, nblocks, expected_words)
        )
        return local_values(ok_global), ok_global

    def verify_batch(
        self,
        padded: np.ndarray,
        nblocks: np.ndarray,
        expected_words: np.ndarray,
        nbytes: int = 0,
    ) -> np.ndarray:
        """bool[B]: does each padded row hash to its expected digest words.

        On a multi-process mesh the inputs are this process's local rows
        and the returned bools are for those rows only. ``nbytes`` is the
        batch's payload bytes, for the pipeline ledger's stages."""
        return self._run_batch(
            self._verify_step_flat, self._verify_step, nbytes, padded, nblocks, expected_words
        )

    def digest_batch(
        self, padded: np.ndarray, nblocks: np.ndarray, nbytes: int = 0
    ) -> np.ndarray:
        """uint32[B, 5] big-endian digest words for each row (local rows
        on a multi-process mesh, as in verify_batch)."""
        return self._run_batch(
            self._digest_step_flat, self._digest_step, nbytes, padded, nblocks
        )

    def _enqueue(self, step_flat, step, nbytes: int, padded, *rest, first: bool = False):
        """Upload one batch and dispatch its step: the ledger's stages
        ``h2d`` (the blocking upload, with the padded slab as
        ``moved_bytes``) and ``launch`` (the jitted call, an enqueue;
        ``first`` marks a pass's first call, the ``step_load`` span: in
        a process's first pass of a shape it traces the step and loads
        its program, in every later one it finds both in the process's
        jitted steps). Returns the device result and the function that
        fetches it.

        Where the transfer is counted, by road: one device takes the
        flat road's chunked concurrent puts, a mesh of several local
        devices one batch-sharded ``device_put`` (``_put_sharded``) —
        both under ``h2d``, so ``launch`` moves nothing. Only a mesh
        spanning processes keeps the fused call: its global arrays are
        assembled from local rows inside the dispatch, so ``launch``
        carries the moved bytes there and no ``h2d`` entry of near-zero
        length is opened beside it."""
        led = pipeline_ledger()
        step_load = annotate("step_load") if first else nullcontext()
        if self._mesh_processes > 1:
            from torrent_tpu.parallel.distributed import local_values

            with led.track("launch", nbytes, moved=padded.nbytes), step_load:
                return step(*self._put_global(padded, *rest)), local_values
        with led.track("h2d", nbytes, moved=padded.nbytes):
            if self._use_flat(padded):
                step, args = step_flat, (self._put_flat(padded), *rest)
            else:
                args = self._put_sharded(padded, *rest)
        with led.track("launch", nbytes), step_load:
            return step(*args), np.asarray

    def _run_batch(
        self, step_flat, step, nbytes: int, padded, *rest, first: bool = False
    ) -> np.ndarray:
        """One synchronous batch: the ``batch`` span (and, under
        ``TORRENT_TPU_PROFILE``, one batch of the capture) around
        :meth:`_enqueue`'s ``h2d`` and ``launch`` and the ledger stage
        ``digest`` (the blocking fetch). For :meth:`verify_batch` and
        :meth:`digest_batch` (the bridge, the scheduler's fused
        fallback, authoring, the library sweep); a recheck pass
        (:meth:`verify_storage`) calls :meth:`_enqueue` itself and
        fetches a batch later."""
        with maybe_profile_batch(TRACE_SPAN_PREFIX + "batch"):
            out_dev, fetch = self._enqueue(step_flat, step, nbytes, padded, *rest, first=first)
            with pipeline_ledger().track("digest", nbytes):
                return fetch(out_dev)

    def upload_supported(self, padded) -> bool:
        """Whether :meth:`upload_batch` can take this batch — checked
        BEFORE opening an ``h2d`` ledger span, so a fused fallback never
        charges transfer bytes to a near-zero-duration span."""
        if self._mesh_processes > 1:
            return False
        if self._use_flat(padded):
            return True
        return (
            isinstance(padded, np.ndarray)
            and padded.ndim == 2
            and padded.shape[0] % self.mesh.size == 0
        )

    def upload_batch(self, padded: np.ndarray):
        """Explicit H2D for the scheduler's split-stage accounting.

        Single-device meshes take the chunked concurrent upload of
        ``digest_batch``'s flat path; >1-device single-process meshes an
        explicit batch-sharded ``device_put``. Returns an opaque handle
        for :meth:`digest_uploaded`, or ``None`` when neither form can
        take this batch (multi-process mesh, odd geometry) — callers
        then fall back to the fused :meth:`digest_batch`. Blocks until
        the batch is device-resident, so the staging buffer may be
        reused immediately.
        """
        if not self.upload_supported(padded):
            return None
        if self._use_flat(padded):
            return ("flat", self._put_flat(padded))
        return ("sharded", self._put_sharded(padded)[0])

    def digest_uploaded(self, handle, nblocks: np.ndarray):
        """Async digest dispatch on an :meth:`upload_batch` handle.

        Returns the device words array WITHOUT fetching — the caller's
        ``np.asarray`` is the D2H boundary (the scheduler accounts it as
        the ledger's ``digest`` stage). The handle is donated to the
        launch on real accelerators; it must not be reused.
        """
        kind, data = handle
        if kind == "flat":
            return self._digest_step_flat(data, nblocks)
        dev_n = jax.device_put(np.asarray(nblocks), self._shard)
        return self._digest_step_donated(data, dev_n)

    # ------------------------------------------------------------ authoring

    def hash_pieces(self, pieces: list[bytes]) -> list[bytes]:
        """SHA1 digests for a ragged list of pieces: the authoring path
        (``tools/make_torrent.py``, the v2 hybrid's v1 pieces, ``doctor``)
        and nothing else — a download's pieces are judged on the client's
        hash-plane scheduler, whose launches follow what finished together.

        Chunks into fixed ``batch_size`` launches so one executable serves
        any piece count; rows are padded with ``nblocks=0`` sentinels, and
        every launch allocates, uploads and hashes a whole batch.
        """
        if not pieces:
            return []
        if any(len(p) > self.piece_length for p in pieces):
            raise ValueError("piece longer than verifier piece_length")
        out: list[bytes] = []
        b = self.batch_size
        for start in range(0, len(pieces), b):
            chunk = pieces[start : start + b]
            padded, view = alloc_padded(b, self.piece_length)
            lengths = np.zeros(b, dtype=np.int64)
            for i, p in enumerate(chunk):
                view[i, : len(p)] = np.frombuffer(p, dtype=np.uint8)
                lengths[i] = len(p)
            nblocks = pad_in_place(padded, lengths)
            nblocks[len(chunk) :] = 0  # sentinel rows: skip entirely
            words = self.digest_batch(padded, nblocks)
            out.extend(words_to_digests(words[: len(chunk)]))
        return out

    # ------------------------------------------------------------ recheck

    def verify_storage(
        self,
        storage: Storage,
        info: InfoDict,
        progress_cb=None,
        io_threads: int = 4,
    ) -> np.ndarray:
        """Full recheck → bool[n_pieces]. Disk reads overlap device compute.

        Every boundary is a ledger stage and, through it, a host span in
        the profiler's trace: ``pass_setup`` (staging and the first
        load; ``verify_pieces_tpu`` opens the entry before it, around
        the verifier's build), in the loader thread ``read`` (storage's
        own) and ``pad``, in this thread the wait ``read_wait``, ``h2d``,
        ``launch`` (an enqueue; the first one of a pass is the
        ``step_load`` span, which traces the step and loads its program
        in a process's first pass of a shape and in no later one) and
        ``digest`` (the blocking fetch). The transfer is counted under ``h2d`` on
        both roads, and both keep ONE batch in flight: batch *i+1* is
        uploaded and dispatched while batch *i*'s result is unfetched,
        then batch *i* is fetched. One device uploads by its own
        chunked puts (the first branch below); a mesh of several local
        devices by :meth:`_enqueue`'s one batch-sharded ``device_put``,
        so the chips hash batch *i* while the host uploads batch *i+1*.
        The loader's reads run beside both. No ``batch`` span opens in
        a pass: that is :meth:`_run_batch`'s, for its direct callers."""
        if info.piece_length != self.piece_length:
            raise ValueError(
                f"verifier compiled for piece_length={self.piece_length}, "
                f"torrent has {info.piece_length}"
            )
        n = info.num_pieces
        bitfield = np.zeros(n, dtype=bool)
        if n == 0:
            return bitfield
        b = self.batch_size
        plen = self.piece_length
        led = pipeline_ledger()
        stripes = max(1, io_threads)

        def load(slot: int, start: int):
            padded, view = staging[slot]
            idxs = range(start, min(start + b, n))
            k = len(idxs)
            if io_pool is not None and k > stripes:
                step = (k + stripes - 1) // stripes
                futs = [
                    io_pool.submit(
                        storage.read_batch,
                        idxs[s : s + step],
                        out=view[s : min(s + step, k)],
                    )
                    for s in range(0, k, step)
                ]
                for f in futs:
                    f.result()
            else:
                storage.read_batch(idxs, out=view[:k])
            with led.track("pad") as padding:
                padded[:, plen:] = 0  # clear pad tail (stale 0x80/bitlen bytes)
                if k < b:
                    padded[k:] = 0
                lengths = np.zeros(b, dtype=np.int64)
                for i, idx in enumerate(idxs):
                    lengths[i] = min(plen, info.length - idx * plen)
                nblocks = pad_in_place(padded, lengths)
                if k < b:
                    nblocks[k:] = 0
                expected = np.zeros((b, 5), dtype=np.uint32)
                expected[:k] = expected_all[start : start + k]
                nbytes = int(lengths.sum())
                padding.add(nbytes)
            return padded, nblocks, expected, k, nbytes

        # Three overlapped stages: disk reads (loader thread) ahead of
        # uploads (chunked concurrent puts, or one sharded put on a
        # mesh) ahead of device compute (async dispatch). The async
        # window is ONE batch on both roads — see the drain loop below
        # for why it must not be widened.
        flat_path = self.mesh.size == 1
        inflight: deque = deque()
        # what fetches a result: the mesh branch takes the function
        # _enqueue hands back, which follows the mesh and not the batch
        fetch = np.asarray

        def drain_one():
            start_i, k_i, nbytes_i, ok_dev = inflight.popleft()
            with led.track("digest", nbytes_i):
                ok = fetch(ok_dev)
            bitfield[start_i : start_i + k_i] = ok[:k_i]
            if progress_cb:
                progress_cb(min(start_i + b, n), n)

        loader = ThreadPoolExecutor(max_workers=1)
        io_pool = ThreadPoolExecutor(max_workers=stripes) if stripes > 1 else None
        kept = False
        try:
            with led.track("pass_setup"):
                expected_all = digests_to_words(info.pieces)
                # Two staging buffers: the IO threads fill one while the
                # device consumes the other (the TPU analogue of the
                # reference's Promise.all hashing pipeline,
                # tools/make_torrent.ts:96-111). ``io_threads`` stripes
                # each batch's disk reads in parallel. They are the
                # process's kept pair where it is in and fits
                # (_StagingPair), so their pages are warm from the
                # second pass on.
                with annotate("alloc_staging"):
                    staging, kept = _staging_pair.checkout(b, plen)
                t0 = time.perf_counter()
                fut = loader.submit(load, 0, 0)
                with annotate("first_load"):
                    loaded = fut.result()
            start = 0
            slot = 0
            while start < n:
                if loaded is None:
                    with led.track("read_wait", wait=True):
                        loaded = fut.result()
                padded, nblocks, expected, k, nbytes = loaded
                loaded = None
                next_start = start + b
                if next_start < n:
                    slot = 1 - slot
                    fut = loader.submit(load, slot, next_start)
                if flat_path:
                    with led.track("h2d", nbytes, moved=padded.nbytes):
                        chunks = self._put_flat(padded)
                    step_load = annotate("step_load") if start == 0 else nullcontext()
                    with led.track("launch", nbytes), step_load:
                        ok_dev = self._verify_step_flat(chunks, nblocks, expected)
                    inflight.append((start, k, nbytes, ok_dev))
                    # Window of 1: upload/compute of batch i+1 overlap
                    # the result fetch of batch i, nothing more. The
                    # fetch is the backpressure that bounds how many
                    # uploaded batches are alive at once (the width
                    # was chosen on a retired setup, not measured on
                    # this one).
                    while len(inflight) > 1:
                        drain_one()
                else:
                    # a mesh: the same window of one. _enqueue awaits
                    # the sharded upload under h2d and dispatches; the
                    # chips hash this batch while the next is uploaded,
                    # two shards a device alive at most. The loader
                    # refills this slab while the batch is in flight,
                    # and the CPU backend's put aliases an aligned slab
                    # where a chip's copies it: a copy there, as in
                    # _put_flat.
                    if self._upload_must_copy:
                        padded = padded.copy()
                    ok_dev, fetch = self._enqueue(
                        self._verify_step_flat, self._verify_step, nbytes,
                        padded, nblocks, expected, first=start == 0,
                    )
                    inflight.append((start, k, nbytes, ok_dev))
                    while len(inflight) > 1:
                        drain_one()
                start = next_start
            while inflight:
                drain_one()
        finally:
            loader.shutdown(wait=True)
            if io_pool is not None:
                # waited for, since the slabs outlive the pass: a load
                # that failed on one stripe leaves its others reading
                io_pool.shutdown(wait=True)
            # the kept pair goes back only now: no thread fills a slab,
            # and no device array aliases one (a put has copied it by
            # the time it returns, on the CPU backend too)
            if kept:
                _staging_pair.checkin()
        self.last_result = VerifyResult(
            bitfield=bitfield,
            n_pieces=n,
            n_valid=int(bitfield.sum()),
            bytes_hashed=info.length,
            seconds=time.perf_counter() - t0,
        )
        return bitfield

    # ------------------------------------------------------------ misc

    def hash_bytes(self, data: bytes) -> bytes:
        """Single-message SHA1 on device (bridge convenience)."""
        padded, nblocks = pad_pieces([data])
        fn = make_sha1_fn(self.backend)
        words = np.asarray(fn(padded, nblocks))
        return words_to_digests(words)[0]
