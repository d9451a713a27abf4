"""Entry driver: the operator's recheck, ``torrent-tpu verify <torrent>
<dir> --hasher tpu --batch N``.

The window calls what ``tools/cli.py:_cmd_verify`` calls, with its
arguments, in its order: read and parse the torrent, open a fresh
``Storage(FsStorage(dir))``, ``verify_pieces(..., hasher="tpu",
batch_size=N, progress_cb=...)``. The command itself prints only a count
and the first ten invalid pieces; the comparison needs every bit, so the
driver takes the bitfield ``verify_pieces`` returns. Rechecks run back to
back in one process, so the interpreter's start and JAX's import are
set-up here and not part of a pass; each pass still builds its verifier
anew and finds its program in the compile cache, as each command does.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark.harness import payload, reference


class Driver:
    def __init__(self, cell):
        self.cell = cell
        c, t = cell.config, cell.traffic
        self.plen = int(c["piece_length"])
        self.n_pieces = int(c["payload_bytes"]) // self.plen
        self.batch = int(c["batch"])
        self.corrupt_share = float(t["corrupt_share"])
        self.passes: list[dict] = []  # per finished pass: t_end, bitfield
        self.launches = 0  # verify_storage reports progress once a batch, which is a launch

    # set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from torrent_tpu.utils.device import enable_compile_cache

        enable_compile_cache()  # as tools/cli.py:main does for --hasher tpu
        root = self.cell.work_dir
        self.name = "payload.bin"
        self.data_path = os.path.join(root, self.name)
        self.torrent_path = os.path.join(root, "payload.torrent")
        self.corrupt = payload.corruption_plan(self.cell.seed, self.n_pieces, self.corrupt_share, self.plen)
        self.digests = payload.write_payload(
            self.data_path, self.cell.seed, self.n_pieces, self.plen, self.corrupt
        )
        payload.write_torrent(self.torrent_path, self.name, self.n_pieces, self.plen, self.digests)
        self.cell.log("payload and torrent written")
        for _ in range(int(self.cell.traffic["warm_passes"])):
            self._recheck()
        self.passes.clear()

    def launch_count(self) -> int:
        return self.launches

    def _recheck(self) -> None:
        """One ``_cmd_verify``."""
        import jax

        from torrent_tpu.codec.metainfo import parse_metainfo
        from torrent_tpu.codec.metainfo_v2 import parse_metainfo_v2
        from torrent_tpu.parallel.verify import verify_pieces
        from torrent_tpu.storage.storage import FsStorage, Storage

        with jax.profiler.TraceAnnotation("bench_parse_torrent"):
            with open(self.torrent_path, "rb") as f:
                data = f.read()
            if parse_metainfo_v2(data) is not None:
                raise RuntimeError("the v1 payload parsed as v2")
            m = parse_metainfo(data)
            if m is None:
                raise RuntimeError("the program refused the benchmark's torrent")
        marks = []

        def progress(done, total):
            marks.append((time.monotonic(), done))
            self.launches += 1

        with jax.profiler.TraceAnnotation("bench_verify_pieces"):
            ok = verify_pieces(
                Storage(FsStorage(self.cell.work_dir), m.info),
                m.info,
                hasher="tpu",
                progress_cb=progress,
                batch_size=self.batch,
            )
        self.passes.append({"t_end": time.monotonic(), "bits": np.asarray(ok, dtype=bool), "marks": marks})

    # window ---------------------------------------------------------------

    def window(self, seconds: float) -> float:
        self.t_open = time.monotonic()
        while time.monotonic() - self.t_open < seconds:
            self._recheck()
        return self.t_open

    # after the window -----------------------------------------------------

    def release(self) -> None:
        """Nothing of the program outlives a pass."""

    def check(self, control: bool = False) -> dict:
        ref = reference.file_verdicts(self.data_path, self.n_pieces, self.plen, self.digests)
        program: list = []
        for p in self.passes:
            bits = list(p["bits"])
            bits += [None] * (self.n_pieces - len(bits))
            program += reference.control_verdicts(self.n_pieces) if control else bits[: self.n_pieces]
        numbers = reference.compare(program, ref * len(self.passes))
        numbers["planted_invalid"] = len(self.corrupt) * len(self.passes)
        return numbers

    def counts(self, numbers: dict) -> dict:
        attempted = self.n_pieces * len(self.passes)
        failed = numbers["wrong_verdicts"]["value"] + numbers["missing_verdicts"]["value"]
        return {
            "attempted": attempted, "failed": failed, "bytes": attempted * self.plen,
            # to the last verdict counted: the pass under way at the close is finished
            "window_s": self.passes[-1]["t_end"] - self.t_open,
            "classes": None, "failures": [],
        }

    def end_to_end(self, counts: dict) -> dict:
        return {"verify_gib_s": counts["bytes"] / 2**30 / counts["window_s"]}

    def observations(self) -> dict:
        # verify_storage reports progress once a batch, which is a launch
        return {"passes": len(self.passes), "launches": sum(len(p["marks"]) for p in self.passes)}
