"""The recheck over a mesh of several local devices — the road a four-chip
host takes without being asked (``verify_pieces(hasher="tpu")`` →
``make_mesh()`` over every local device; benchmark configuration
``recheck-256k-x4``) — against the plain reference: ``hashlib`` over the
bytes the storage really holds, every bit of the bitfield.

The conftest's eight virtual CPU devices give meshes of 2, 4 and 8. Each
case is a shape the batch-sharded road has to get right: a ragged last
batch, a short last piece, a batch the constructor rounds up to the mesh,
fewer pieces than devices, and corruption at the first and last row of a
device's shard; five batches, so the window of one batch in flight
crosses its steady state and its trailing drain.

Then the window itself: never more than two device results alive,
progress reported once a batch and in order, and an upload that fails
with a batch in flight leaves no thread behind.
"""

import gc
import hashlib
import weakref

import numpy as np
import pytest

from torrent_tpu.codec.metainfo import InfoDict
from torrent_tpu.parallel.mesh import make_mesh
from torrent_tpu.parallel.verify import verify_pieces
from torrent_tpu.storage.storage import MemoryStorage, Storage

PLEN = 4096
ROWS = 4  # rows a device where a case fixes the batch: batch_size = ROWS * devices


def _shard_edges(devices: int) -> list[int]:
    """First and last row of the first, a middle and the last device's
    shard, in the first batch and again in the second."""
    batch = ROWS * devices
    first_rows = [d * ROWS for d in {0, devices // 2, devices - 1}]
    rows = sorted(first_rows + [r + ROWS - 1 for r in first_rows])
    return rows + [batch + r for r in rows]


# name → (pieces, bytes in the last piece, batch_size, pieces to corrupt), each of the mesh's size
CASES = {
    "ragged_last_batch": lambda d: (2 * ROWS * d + 3, PLEN, ROWS * d, [1, 2 * ROWS * d + 2]),
    "short_last_piece": lambda d: (ROWS * d, 77, ROWS * d, [ROWS * d - 1]),
    "batch_rounded_up_to_the_mesh": lambda d: (2 * d + 1, 1000, d + 1, [0, d, 2 * d]),
    "fewer_pieces_than_devices": lambda d: (d - 1, 500, d, [d - 2]),
    "corrupt_at_shard_edges": lambda d: (2 * ROWS * d, PLEN, ROWS * d, _shard_edges(d)),
    "five_batches_ragged_last": lambda d: (
        4 * ROWS * d + 3, 901, ROWS * d, [0, *(ROWS * d * i + i for i in range(1, 4)), 4 * ROWS * d, 4 * ROWS * d + 2]
    ),
}


def _payload(n_pieces: int, tail: int, corrupt: list[int], seed: int):
    """A seeded payload in memory, its torrent, and — after one byte of
    each piece in ``corrupt`` was flipped in the storage — hashlib's
    verdict on what the storage holds."""
    length = PLEN * (n_pieces - 1) + tail
    data = bytearray(np.random.default_rng(seed).bytes(length))
    pieces = tuple(hashlib.sha1(data[i : i + PLEN]).digest() for i in range(0, length, PLEN))
    info = InfoDict(name="v", piece_length=PLEN, pieces=pieces, length=length, files=None)
    for i in corrupt:
        data[i * PLEN + (min(PLEN, length - i * PLEN) - 1) // 2] ^= 0x01  # the piece's middle byte
    storage = Storage(MemoryStorage(), info)
    storage.set(0, bytes(data))
    held = storage.get(0, length)
    reference = [hashlib.sha1(held[i : i + PLEN]).digest() == pieces[i // PLEN] for i in range(0, length, PLEN)]
    return info, storage, reference


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("devices", [2, 4, 8])
def test_mesh_recheck_equals_hashlib_bit_for_bit(devices, case):
    import jax

    n_pieces, tail, batch_size, corrupt = CASES[case](devices)
    info, storage, reference = _payload(n_pieces, tail, corrupt, seed=devices * 100 + len(case))
    assert info.num_pieces == n_pieces
    assert [i for i, ok in enumerate(reference) if not ok] == sorted(corrupt)
    mesh = make_mesh(jax.devices()[:devices])
    assert mesh.devices.shape == (1, devices)
    bits = verify_pieces(storage, info, hasher="tpu", batch_size=batch_size, mesh=mesh)
    assert bits.dtype == bool and bits.shape == (n_pieces,)
    assert bits.tolist() == reference


@pytest.mark.parametrize("devices", [2, 4, 8])
def test_the_window_holds_one_batch_and_progress_is_in_order(devices, monkeypatch):
    """Batch *i+1* is dispatched before batch *i* is fetched and nothing
    further ahead: two device results alive at most (counted where they
    are made and fetched, and by weak references when progress is
    reported), every batch reported once, in order, ending at (n, n)."""
    import jax

    from torrent_tpu.models.verifier import TPUVerifier

    batch = ROWS * devices
    n_pieces = 4 * batch + 3
    info, storage, reference = _payload(n_pieces, 77, [batch, n_pieces - 1], seed=devices)
    real = TPUVerifier._enqueue
    unfetched, results, events = [], [], []

    def enqueue(self, *args, **kwargs):
        out_dev, fetch = real(self, *args, **kwargs)
        results.append(weakref.ref(out_dev))
        unfetched.append(id(out_dev))
        events.append("launch")
        return out_dev, lambda dev: counted(fetch, dev)

    def counted(fetch, dev):
        assert id(dev) == unfetched.pop(0)  # the oldest first
        events.append("fetch")
        return fetch(dev)

    def progress(done, total):
        gc.collect()
        alive.append(sum(r() is not None for r in results))
        reported.append((done, total))

    alive, reported = [], []
    monkeypatch.setattr(TPUVerifier, "_enqueue", enqueue)
    mesh = make_mesh(jax.devices()[:devices])
    bits = verify_pieces(storage, info, hasher="tpu", batch_size=batch, mesh=mesh, progress_cb=progress)
    assert bits.tolist() == reference
    assert events == ["launch"] + ["launch", "fetch"] * 4 + ["fetch"] and not unfetched  # two unfetched at most
    assert max(alive) <= 2 and alive[-1] == 1  # the last fetch: its own result and nothing in flight
    assert reported == [(min((i + 1) * batch, n_pieces), n_pieces) for i in range(5)]


def test_a_failed_upload_with_a_batch_in_flight_leaves_no_thread(monkeypatch):
    """Batch 1's upload raises while batch 0's result is unfetched: the
    exception reaches the caller, and the loader and the IO pool are
    shut down (the loader's thread joined) as after a whole pass."""
    import jax

    from torrent_tpu.models import verifier

    devices = 4
    batch = ROWS * devices
    info, storage, _ = _payload(3 * batch + 1, PLEN, [], seed=5)
    pools = []

    class Pool(verifier.ThreadPoolExecutor):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            pools.append(self)

    real = verifier.TPUVerifier._put_sharded
    uploads = []

    def put_sharded(self, *arrays):
        uploads.append(len(arrays))
        if len(uploads) == 2:
            raise RuntimeError("the second upload fails")
        return real(self, *arrays)

    monkeypatch.setattr(verifier, "ThreadPoolExecutor", Pool)
    monkeypatch.setattr(verifier.TPUVerifier, "_put_sharded", put_sharded)
    launched = []
    v = verifier.TPUVerifier(piece_length=PLEN, batch_size=batch, mesh=make_mesh(jax.devices()[:devices]))
    step = v._verify_step
    monkeypatch.setattr(v, "_verify_step", lambda *a: launched.append(1) or step(*a))
    reported = []
    with pytest.raises(RuntimeError, match="the second upload fails"):
        v.verify_storage(storage, info, progress_cb=lambda *a: reported.append(a), io_threads=2)
    assert uploads == [3, 3] and launched == [1]  # batch 0 was in flight, and never fetched
    assert reported == []
    assert len(pools) == 2  # the loader and the IO pool
    for pool in pools:
        assert pool._shutdown
    loader = next(p for p in pools if p._max_workers == 1)
    assert loader._threads and not any(t.is_alive() for t in loader._threads)


@pytest.mark.parametrize("devices", [2, 8])
def test_no_shard_in_flight_aliases_a_staging_slab(devices, monkeypatch):
    """The CPU backend's ``device_put`` takes a 64-byte-aligned host
    buffer without copying it. The loader refills a slab while the batch
    read from it is still in flight, so no device shard may live inside
    a staging slab (on a chip the transfer is always a copy)."""
    import jax

    from torrent_tpu.models import verifier
    from torrent_tpu.ops.padding import padded_len_for

    batch = ROWS * devices
    info, storage, reference = _payload(3 * batch + 1, PLEN, [0, 2 * batch], seed=11)
    slabs = []

    def aligned(n, piece_len):
        width = padded_len_for(piece_len)
        raw = np.zeros(n * width + 64, dtype=np.uint8)
        skip = -raw.ctypes.data % 64
        padded = raw[skip : skip + n * width].reshape(n, width)
        slabs.append(padded)
        return padded, padded[:, :piece_len]

    real = verifier.TPUVerifier._put_sharded
    inside = []

    def put_sharded(self, *arrays):
        out = real(self, *arrays)
        for shard in out[0].addressable_shards:
            at = shard.data.unsafe_buffer_pointer()
            inside.append(any(s.ctypes.data <= at < s.ctypes.data + s.nbytes for s in slabs))
        return out

    monkeypatch.setattr(verifier, "alloc_padded", aligned)
    # a keeper that holds nothing yet, so the pass allocates its pair here
    monkeypatch.setattr(verifier, "_staging_pair", verifier._StagingPair())
    monkeypatch.setattr(verifier.TPUVerifier, "_put_sharded", put_sharded)
    mesh = make_mesh(jax.devices()[:devices])
    bits = verify_pieces(storage, info, hasher="tpu", batch_size=batch, mesh=mesh)
    assert len(slabs) == 2 and all(s.ctypes.data % 64 == 0 for s in slabs)
    assert len(inside) == 4 * devices and not any(inside)
    assert bits.tolist() == reference
