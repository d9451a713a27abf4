"""Multi-host (DCN) support for the verify/bulk hash planes.

SURVEY §5/§7 names the split: XLA ICI collectives (``shard_map`` +
``psum``) within a host, and **DCN via ``jax.distributed`` only for
pod-scale bulk verification** (BASELINE config 5). Until round 5 the
``hosts`` mesh axis was a single-process fiction: ``verify_storage`` /
``verify_library`` fed whole *global* numpy arrays into ``jax.jit`` —
single-controller style that a real multi-process mesh rejects, because
each process only holds its addressable shard of a global array.

This module is the process-boundary glue, testable on CPU with two real
processes (tests/test_distributed.py spawns them; no TPU pod needed):

- :func:`initialize` — ``jax.distributed.initialize`` wrapper.
- :func:`global_batch` / :func:`local_values` — per-process local rows
  ↔ global sharded ``jax.Array`` (``make_array_from_process_local_data``
  on the way in, addressable-shard reassembly on the way out).
- :func:`psum_valid_count` — the bulk-validate stats reduction (psum
  over ``(hosts, dp)``) on a live multi-process mesh.
- :func:`verify_storage_distributed` — the pod-scale recheck: each
  process reads its own slice of every global batch, all processes
  enter the same jitted verify step, and the per-piece bitfield is
  assembled with a process allgather. Every process returns the same
  global bitfield.

Mesh layout contract: row ``p`` of the ``(hosts, dp)`` mesh is exactly
process ``p``'s local devices (``make_mesh`` groups by
``process_index`` when ``jax.process_count() > 1``), so the batch rows
a process feeds are the rows its devices own — data never crosses DCN;
only the few-byte stats/bitfield reductions do.
"""

from __future__ import annotations

import functools as _functools
import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from torrent_tpu.parallel.mesh import DP_AXIS, HOST_AXIS


def initialize(
    coordinator_address: str, num_processes: int, process_id: int
) -> None:
    """``jax.distributed.initialize`` with an idempotence guard.

    Call before the first use of ``jax.devices()``. On CPU test rigs set
    ``jax.config.update("jax_platforms", "cpu")`` and
    ``jax.config.update("jax_num_cpu_devices", k)`` first so each
    process contributes ``k`` virtual devices to the global mesh.
    """
    import jax

    try:  # private in some jax versions; fall back to is_initialized
        from jax._src.distributed import global_state as _state

        if getattr(_state, "client", None) is not None:
            return
    except ImportError:
        if getattr(jax.distributed, "is_initialized", lambda: False)():
            return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def global_batch(sharding, local: np.ndarray):
    """Build the global batch-sharded ``jax.Array`` from this process's
    local rows.

    ``local`` is this process's contiguous row-slice; the global leading
    dim is ``local.shape[0] * process_count`` (every process must pass
    the same local row count — pad ragged tails before calling).
    """
    import jax

    global_shape = (
        local.shape[0] * jax.process_count(),
        *local.shape[1:],
    )
    return jax.make_array_from_process_local_data(
        sharding, np.ascontiguousarray(local), global_shape
    )


def local_values(arr) -> np.ndarray:
    """This process's rows of a batch-sharded global array, in global
    row order (the inverse of :func:`global_batch`)."""
    shards = sorted(
        arr.addressable_shards, key=lambda s: s.index[0].start or 0
    )
    return np.concatenate([np.asarray(s.data) for s in shards])


@_functools.lru_cache(maxsize=8)
def _count_fn(mesh):
    """One compiled psum-count program per mesh (Mesh is hashable);
    rebuilding the jit closure per call would recompile the collective
    on every batch of the recheck hot loop."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P((HOST_AXIS, DP_AXIS))

    def _count(ok_local):
        return jax.lax.psum(
            jnp.sum(ok_local.astype(jnp.int32)), (HOST_AXIS, DP_AXIS)
        )

    return jax.jit(
        shard_map(_count, mesh=mesh, in_specs=(spec,), out_specs=P(), check_vma=False)
    )


def psum_valid_count(mesh, ok_global) -> int:
    """Total True count of a batch-sharded bool array, reduced on-device
    with ``psum`` over both mesh axes — the bulk-validate stats
    reduction (BASELINE config 5) riding ICI within a host and DCN
    across hosts. Every process returns the same total."""
    return int(_count_fn(mesh)(ok_global))


def allgather_bitfield(local_contrib: np.ndarray) -> np.ndarray:
    """OR-assemble per-process disjoint bitfield contributions into the
    global bitfield (identical on every process). A few bytes per piece
    — the only payload that crosses DCN in the whole recheck."""
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(
        local_contrib.astype(np.uint8), tiled=False
    )
    return np.asarray(gathered).any(axis=0)


def verify_storage_distributed(
    storage,
    info,
    batch_size: int = 1024,
    backend: str = "jax",
    mesh=None,
    progress_cb=None,
    io_threads: int = 4,
):
    """Pod-scale resume-recheck: every process verifies its slice of
    each global batch through one shared jitted step, then the bitfield
    is assembled over DCN. Returns ``(bitfield, n_valid)`` — identical
    on every process; ``n_valid`` comes from the on-device psum stats
    reduction, not a host-side sum, so the collective path is exercised
    on every call.

    Row layout per global batch ``g`` of size ``B`` over ``P``
    processes: process ``p`` loads pieces
    ``[g*B + p*(B/P), g*B + (p+1)*(B/P))`` — matching the mesh's
    process-aligned host rows, so piece bytes never cross a process
    boundary.
    """
    import jax

    from torrent_tpu.models.verifier import TPUVerifier
    from torrent_tpu.ops.padding import (
        alloc_padded,
        digests_to_words,
        pad_in_place,
    )

    nproc = jax.process_count()
    pid = jax.process_index()
    verifier = TPUVerifier(
        piece_length=info.piece_length,
        batch_size=batch_size,
        backend=backend,
        mesh=mesh,
    )
    B = verifier.batch_size
    if B % nproc:
        raise ValueError(f"batch_size {B} not divisible by {nproc} processes")
    L = B // nproc
    n = info.num_pieces
    plen = info.piece_length
    expected_all = digests_to_words(info.pieces)
    local_contrib = np.zeros(n, dtype=bool)
    n_valid = 0
    n_batches = math.ceil(n / B)

    # Same shape as TPUVerifier.verify_storage: two staging buffers, a
    # loader thread reading global batch g+1 (this process's contiguous
    # slice, striped over io_threads) while the device verifies batch g.
    staging = [alloc_padded(L, plen) for _ in range(2)]
    stripes = max(1, io_threads)
    io_pool = ThreadPoolExecutor(max_workers=stripes) if stripes > 1 else None

    def load(slot: int, g: int):
        padded, view = staging[slot]
        base = g * B + pid * L
        idxs = range(base, min(base + L, n))
        k = len(idxs)
        if k:
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
        padded[:, plen:] = 0  # clear pad tail (stale 0x80/bitlen bytes)
        if k < L:
            padded[k:] = 0
        lengths = np.zeros(L, dtype=np.int64)
        expected = np.zeros((L, 5), dtype=np.uint32)
        for r, idx in enumerate(idxs):
            lengths[r] = min(plen, info.length - idx * plen)
            expected[r] = expected_all[idx]
        nblocks = pad_in_place(padded, lengths)
        nblocks[k:] = 0
        return padded, nblocks, expected, list(idxs)

    try:
        with ThreadPoolExecutor(max_workers=1) as pool:
            fut = pool.submit(load, 0, 0)
            slot = 0
            for g in range(n_batches):
                padded, nblocks, expected, idxs = fut.result()
                if g + 1 < n_batches:
                    slot = 1 - slot
                    fut = pool.submit(load, slot, g + 1)
                # verify_batch_global copies rows into device shards
                # before returning, so reusing the staging buffer for
                # the next load cannot race the in-flight batch
                ok_local, ok_global = verifier.verify_batch_global(
                    padded, nblocks, expected
                )
                for r, idx in enumerate(idxs):
                    local_contrib[idx] = bool(ok_local[r])
                # on-device DCN+ICI stats reduction. Sentinel /
                # out-of-range rows carry expected=0, which no SHA1
                # digest ever equals, so they can never inflate the
                # count — n_valid == popcount(bitfield).
                n_valid += psum_valid_count(verifier.mesh, ok_global)
                if progress_cb:
                    progress_cb(min((g + 1) * B, n), n)
    finally:
        if io_pool is not None:
            io_pool.shutdown(wait=False)
    bitfield = allgather_bitfield(local_contrib)
    return bitfield, n_valid


def verify_pieces_v2_distributed(
    storage,
    info,
    batch_size: int = 256,
    progress_cb=None,
) -> np.ndarray:
    """Pod-scale BEP 52 (merkle) recheck: pieces are verified
    independently, so each process takes its round-robin stride of the
    piece index space through the ordinary per-host v2 device plane
    (leaf hashing + fused pair reduction on LOCAL devices — v2 batches
    are pad-grouped and never need a global mesh), and the disjoint
    bitfield contributions are OR-assembled over one DCN allgather.
    Returns the identical full bitfield on every process.

    SPMD contract: every process must call this collectively on the
    same torrent (the allgather blocks until all arrive). For a
    host-local-only recheck on a cluster call
    ``verify_pieces_v2_tpu`` directly.
    """
    import jax

    from torrent_tpu.parallel.verify import verify_pieces_v2_tpu

    nproc = jax.process_count()
    pid = jax.process_index()
    local = verify_pieces_v2_tpu(
        storage,
        info,
        batch_size=batch_size,
        progress_cb=progress_cb,
        indices=range(pid, info.num_pieces, nproc),
    )
    return allgather_bitfield(local)


def verify_library_distributed(
    items,
    batch_size: int = 1024,
    backend: str = "jax",
    io_threads: int = 4,
    progress_cb=None,
):
    """Pod-scale bulk library validation (BASELINE config 5): the
    torrent-level DCN parallelism `parallel/bulk.py` documents — each
    host runs :func:`verify_library` over its round-robin shard of the
    library on its LOCAL device mesh (no cross-host piece movement),
    then the per-torrent bitfields are assembled over one packed DCN
    allgather. Returns ``(bitfields, n_valid)``, identical on every
    process; ``n_valid`` counts valid pieces library-wide.

    ``items``: ``list[(Storage, InfoDict)]`` — the SAME list, in the
    same order, on every process (each host opens its own storage
    handles; only the round-robin slice is actually read).

    ``progress_cb`` reports THIS process's shard progress —
    ``(pieces_done_local, shard_pieces_total)`` — not library-wide
    progress: hosts advance independently and cross-host progress
    would cost a collective per batch. Only the RETURN values are
    identical on every process.
    """
    import jax

    from torrent_tpu.parallel.bulk import verify_library
    from torrent_tpu.parallel.mesh import make_mesh

    nproc = jax.process_count()
    pid = jax.process_index()
    # round-robin, not contiguous: libraries are often sorted by size,
    # and striding spreads the big torrents evenly across hosts
    mine = list(range(pid, len(items), nproc))
    local_mesh = make_mesh(jax.local_devices(), n_hosts=1)
    result = verify_library(
        [items[i] for i in mine],
        hasher="tpu",
        batch_size=batch_size,
        backend=backend,
        mesh=local_mesh,
        io_threads=io_threads,
        progress_cb=progress_cb,
    )
    # pack every torrent's bitfield into one flat disjoint-contribution
    # vector: this process fills only its torrents' spans, the OR-
    # allgather assembles the global view on every host
    offsets = np.zeros(len(items) + 1, dtype=np.int64)
    for i, (_, info) in enumerate(items):
        offsets[i + 1] = offsets[i] + info.num_pieces
    flat = np.zeros(int(offsets[-1]), dtype=bool)
    for j, i in enumerate(mine):
        flat[offsets[i] : offsets[i + 1]] = result.bitfields[j]
    flat = allgather_bitfield(flat)
    bitfields = [
        flat[offsets[i] : offsets[i + 1]].copy() for i in range(len(items))
    ]
    return bitfields, int(flat.sum())
