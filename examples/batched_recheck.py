"""Batched piece verification — the TPU hash plane as a library call.

Authors a torrent for a generated directory, corrupts one byte, then
rechecks every piece with ``verify_pieces`` and reports exactly which
piece went bad. ``hasher="tpu"`` routes the same call through the
batched device hash plane (on whatever JAX resolved; ``backend="pallas"``
selects the Pallas SHA-1 kernel); ``hasher="cpu"`` keeps everything
host-side, which is what this demo uses so it runs anywhere.

Run:  python examples/batched_recheck.py            (CPU)
      python examples/batched_recheck.py tpu        (with an accelerator)
"""

import os
import sys
import tempfile

try:
    import torrent_tpu  # noqa: F401  (installed)
except ModuleNotFoundError:  # running from a checkout
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from torrent_tpu import FsStorage, Storage, parse_metainfo, verify_pieces
from torrent_tpu.tools.make_torrent import make_torrent


def main() -> None:
    hasher = sys.argv[1] if len(sys.argv) > 1 else "cpu"
    with tempfile.TemporaryDirectory() as work:
        src = os.path.join(work, "dataset")
        os.makedirs(src)
        rng = np.random.default_rng(7)
        for name, size in (("shard0.bin", 800_000), ("shard1.bin", 450_000)):
            with open(os.path.join(src, name), "wb") as f:
                f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())

        meta = parse_metainfo(
            make_torrent(src, "http://tracker.invalid/announce", piece_length=65536)
        )
        storage = Storage(FsStorage(work), meta.info)

        ok = verify_pieces(storage, meta.info, hasher=hasher)
        print(f"clean recheck ({hasher}): {int(ok.sum())}/{len(ok)} pieces valid")

        # flip one byte in the middle of shard1 and recheck
        victim = os.path.join(src, "shard1.bin")
        with open(victim, "r+b") as f:
            f.seek(123_456)
            b = f.read(1)
            f.seek(123_456)
            f.write(bytes([b[0] ^ 0xFF]))

        ok = verify_pieces(storage, meta.info, hasher=hasher)
        bad = np.flatnonzero(~ok)
        print(f"after corruption: {int(ok.sum())}/{len(ok)} valid; bad pieces {bad}")
        assert len(bad) == 1, "exactly one 64 KiB piece spans the flipped byte"


if __name__ == "__main__":
    main()
