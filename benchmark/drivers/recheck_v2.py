"""Entry driver: the operator's recheck of a pure-v2 multi-file torrent,
``torrent-tpu verify <torrent> <dir> --hasher tpu``.

The window calls what ``tools/cli.py:_cmd_verify`` calls for a v2 torrent,
in its order: read the torrent, ``parse_metainfo_v2``, then
``_verify_v2``'s ``read_file`` closure (a path a file, which ``verify_v2``
streams) and ``verify_v2(read_file, v2, hasher="tpu")``. The command
prints a count and the bad pieces of each file; the comparison needs every
verdict, so the driver takes the per-file arrays ``verify_v2`` returns.
Rechecks run back to back in one process, so the interpreter's start and
JAX's import are set-up here and not part of a pass.

A launch is counted where a parent of the PR that added this cell counts
it too: the histogram ``torrent_tpu_v2_leaf_launch_seconds`` (one
observation a leaf launch, over both kernels). ``progress_cb`` is passed
only where ``verify_v2`` takes one, and the leaf rows' counters are read
only where the program has them.
"""

from __future__ import annotations

import inspect
import os
import time

import numpy as np

from benchmark.harness import payload_v2, reference_v2

NAME = "payload"


class Driver:
    def __init__(self, cell):
        self.cell = cell
        c, t = cell.config, cell.traffic
        self.plen = int(c["piece_length"])
        self.files = payload_v2.file_plan(c["files"])
        self.payload_bytes = sum(length for _, length in self.files)
        if self.payload_bytes != int(c["payload_bytes"]):
            raise ValueError(f"the file mix holds {self.payload_bytes} bytes, payload_bytes says {c['payload_bytes']}")
        self.pieces_of = [reference_v2.num_pieces(length, self.plen) for _, length in self.files]
        self.n_pieces = sum(self.pieces_of)
        self.corrupt_share = float(t["corrupt_share"])
        self.passes: list[dict] = []  # per finished pass: t_end, the per-file arrays
        self.at_open: dict = {}

    # what the program counts ------------------------------------------------

    def launch_count(self) -> int:
        """Leaf launches of this process so far, over both kernels."""
        from torrent_tpu.models.v2 import LEAF_LAUNCH_HIST
        from torrent_tpu.obs.hist import histograms

        snap = histograms().family_snapshot(LEAF_LAUNCH_HIST[0])
        return 0 if snap is None else int(snap[1])

    def _counters(self) -> dict:
        from torrent_tpu.models import v2
        from torrent_tpu.obs.ledger import pipeline_ledger

        stats = getattr(v2, "leaf_launch_stats", None)
        launch = pipeline_ledger().snapshot()["stages"].get("launch", {})
        return {
            "launches": self.launch_count(),
            "leaf_rows": stats() if stats else None,
            "launch_ops": launch.get("ops", 0),
        }

    # set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from torrent_tpu.models.v2 import verify_v2
        from torrent_tpu.utils.device import enable_compile_cache

        enable_compile_cache()  # as tools/cli.py:main does for --hasher tpu
        self.takes_progress = "progress_cb" in inspect.signature(verify_v2).parameters
        root = self.cell.work_dir
        self.torrent_path = os.path.join(root, NAME + ".torrent")
        self.entries = payload_v2.write_payload(root, NAME, self.cell.seed, self.files, self.plen)
        payload_v2.write_torrent(self.torrent_path, NAME, self.plen, self.entries)
        self.corrupt = payload_v2.corruption_plan(self.cell.seed, self.files, self.plen, self.corrupt_share)
        payload_v2.apply_corruption(root, NAME, self.files, self.corrupt)
        self.cell.log(f"payload of {len(self.files)} files and torrent written, {len(self.corrupt)} pieces corrupted")
        for _ in range(int(self.cell.traffic["warm_passes"])):
            self._recheck()
        self.passes.clear()

    def _recheck(self) -> None:
        """One ``_cmd_verify`` of a v2 torrent."""
        import jax

        from torrent_tpu.codec.metainfo_v2 import parse_metainfo_v2
        from torrent_tpu.models.v2 import verify_v2

        with jax.profiler.TraceAnnotation("bench_parse_torrent"):
            with open(self.torrent_path, "rb") as f:
                data = f.read()
            v2 = parse_metainfo_v2(data)
            if v2 is None:
                raise RuntimeError("the program refused the benchmark's v2 torrent")
        top = os.path.abspath(self.cell.work_dir)
        root = os.path.join(top, v2.info.name)
        single = len(v2.info.files) == 1 and v2.info.files[0].path == (v2.info.name,)

        def read_file(path):
            fp = root if single else os.path.join(root, *path)
            if os.path.commonpath([os.path.abspath(fp), top]) != top:
                return None
            if not os.path.isfile(fp):
                return None
            return fp  # a path source: verify_v2 streams it

        marks = []
        kwargs = {"progress_cb": lambda done, total: marks.append((time.monotonic(), done))} if self.takes_progress else {}
        with jax.profiler.TraceAnnotation("bench_verify_v2"):
            res = verify_v2(read_file, v2, hasher="tpu", **kwargs)
        self.passes.append({"t_end": time.monotonic(), "res": res, "marks": marks})

    # window ---------------------------------------------------------------

    def window(self, seconds: float) -> float:
        self.at_open = self._counters()
        self.t_open = time.monotonic()
        while time.monotonic() - self.t_open < seconds:
            self._recheck()
        self.at_close = self._counters()
        return self.t_open

    # after the window -----------------------------------------------------

    def release(self) -> None:
        """Nothing of the program outlives a pass."""

    def check(self, control: bool = False) -> dict:
        root = os.path.join(self.cell.work_dir, NAME)
        ref: list = []
        for e in self.entries:
            ref += reference_v2.file_verdicts(
                os.path.join(root, *e["path"]), e["length"], e["pieces_root"], e["layer"], self.plen
            )
        program: list = []
        for p in self.passes:
            if control:
                program += reference_v2.control_verdicts(self.n_pieces)
                continue
            for e, n in zip(self.entries, self.pieces_of):
                bits = [bool(b) for b in np.asarray(p["res"].get(e["path"], ()), dtype=bool)][:n]
                program += bits + [None] * (n - len(bits))
        numbers = reference_v2.compare(program, ref * len(self.passes))
        numbers["planted_invalid"] = len(self.corrupt) * len(self.passes)
        # the second guarantee: the leaves were hashed by counted device
        # launches, and (where the program keeps both) the ledger's launch
        # entries are those launches
        launches = self.at_close["launches"] - self.at_open["launches"]
        numbers["passes_without_launch"] = {"value": len(self.passes) if launches == 0 else 0, "limit": 0}
        if self.at_close["leaf_rows"] is not None:
            ops = self.at_close["launch_ops"] - self.at_open["launch_ops"]
            numbers["launch_ops_uncounted"] = {"value": abs(ops - launches), "limit": 0}
        return numbers

    def counts(self, numbers: dict) -> dict:
        attempted = self.n_pieces * len(self.passes)
        failed = numbers["wrong_verdicts"]["value"] + numbers["missing_verdicts"]["value"]
        return {
            "attempted": attempted, "failed": failed, "bytes": self.payload_bytes * len(self.passes),
            # to the last verdict counted: the pass under way at the close is finished
            "window_s": self.passes[-1]["t_end"] - self.t_open,
            "classes": None, "failures": [],
        }

    def end_to_end(self, counts: dict) -> dict:
        return {"verify_gib_s": counts["bytes"] / 2**30 / counts["window_s"]}

    def observations(self) -> dict:
        return {
            "passes": len(self.passes),
            # the launches step_modules names: what hash_step_gib_s divides the window's bytes by
            "launches": self.at_close["launches"] - self.at_open["launches"],
            "leaf_rows": (self.at_open["leaf_rows"], self.at_close["leaf_rows"]),
        }
