"""The recheck over a mesh of several local devices — the road a four-chip
host takes without being asked (``verify_pieces(hasher="tpu")`` →
``make_mesh()`` over every local device; benchmark configuration
``recheck-256k-x4``) — against the plain reference: ``hashlib`` over the
bytes the storage really holds, every bit of the bitfield.

The conftest's eight virtual CPU devices give meshes of 2, 4 and 8. Each
case is a shape the batch-sharded road has to get right: a ragged last
batch, a short last piece, a batch the constructor rounds up to the mesh,
fewer pieces than devices, and corruption at the first and last row of a
device's shard.
"""

import hashlib

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
