"""Entry driver: one worker's share of a pod's library sweep,
``torrent-tpu fabric-verify <torrents> <data> --hasher tpu`` with the
command's defaults, no coordinator and no heartbeat directory.

A pass is what ``tools/cli.py:_fabric_verify`` does between its glob and
its result line, in its order: the sorted glob of ``torrents/*.torrent``,
``parse_metainfo`` of each, a fresh ``Storage(FsStorage(data/<stem>))`` a
torrent, ``FabricConfig`` from the command's defaults, and
``verify_library_fabric(items, sched, ...)`` itself with the command's
arguments. The command prints a result record; the comparison needs every
bit, so the driver keeps the bitfields that record is made from. What the
command leaves to its process's exit, the storages' open files, is closed
after each pass.

The command builds one scheduler a run and a sweep of a worker's shard is
one long command, so the scheduler (and with it the six lanes' planes and
staging slabs) is started once, in set-up, and the passes of the window
run through it back to back, each with a fresh executor, fresh storages
and a fresh parse of every torrent. The interpreter's start and JAX's
import are set-up too.
"""

from __future__ import annotations

import asyncio
import glob
import os
import sys
import time

import numpy as np

from benchmark.harness import payload_library, reference_library


def _say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class Driver:
    def __init__(self, cell):
        self.cell = cell
        c, t = cell.config, cell.traffic
        self.torrents = payload_library.library(c)
        self.payload_bytes = sum(x.payload_bytes for x in self.torrents)
        if self.payload_bytes != int(c["payload_bytes"]):
            raise ValueError(f"the library holds {self.payload_bytes} bytes, payload_bytes says {c['payload_bytes']}")
        self.pieces_of = [x.n_pieces for x in self.torrents]
        self.n_pieces = sum(self.pieces_of)
        self.space_bytes = sum(x.space_bytes for x in self.torrents)  # pads are hashed too
        self.corrupt_share = float(t["corrupt_share"])
        self.stall_seconds = float(c["stall_seconds"])
        self.loop = asyncio.new_event_loop()
        self.sched = None
        self.executor = None  # the last pass's
        self.passes: list[dict] = []  # per pass counted: t_end, bitfields (None where it was given up)
        self.stalled = False

    # what the program counts ------------------------------------------------

    def launch_count(self) -> int:
        return self.sched.metrics_snapshot()["launches"]

    # set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from torrent_tpu.sched import HashPlaneScheduler, SchedulerConfig
        from torrent_tpu.utils.device import enable_compile_cache

        root = self.cell.work_dir
        payload_library.write_library(root, self.cell.seed, self.torrents)
        self.corrupt = payload_library.corruption_plan(self.cell.seed, self.torrents, self.corrupt_share)
        payload_library.apply_corruption(root, self.torrents, self.corrupt)
        self.cell.log(f"library of {len(self.torrents)} torrents written, {len(self.corrupt)} pieces corrupted")
        enable_compile_cache()  # as tools/cli.py:main does for --hasher tpu
        # as _fabric_verify builds it: --batch-target, every other knob SchedulerConfig's own
        self.sched = self.loop.run_until_complete(
            HashPlaneScheduler(SchedulerConfig(batch_target=int(self.cell.config["batch_target"])), hasher="tpu").start()
        )
        for i in range(int(self.cell.traffic["warm_passes"])):
            # a lane's first launch builds its plane: programs that are not in the
            # compile cache yet are compiled inside the first warm pass
            self._sweep(stall_seconds=10 * self.stall_seconds)
            self.cell.log(f"warm pass {i + 1} done, {self.launch_count()} launches so far")
        if self.stalled:
            raise RuntimeError("a warm pass got no verdict for the stall limit")
        self.passes.clear()

    def _items(self) -> list:
        """``_fabric_verify``'s glob and parse."""
        from torrent_tpu.codec.metainfo import parse_metainfo
        from torrent_tpu.storage.storage import FsStorage, Storage

        data = os.path.join(self.cell.work_dir, "data")
        items = []
        for tf in sorted(glob.glob(os.path.join(self.cell.work_dir, "torrents", "*.torrent"))):
            with open(tf, "rb") as f:
                meta = parse_metainfo(f.read())
            if meta is None:
                raise RuntimeError(f"the program refused the benchmark's torrent {tf}")
            stem = os.path.splitext(os.path.basename(tf))[0]
            root = os.path.join(data, stem)
            if not os.path.isdir(root):
                root = data
            items.append((Storage(FsStorage(root), meta.info), meta.info))
        return items

    async def _pass(self, items, stall_seconds: float) -> list | None:
        """``verify_library_fabric`` as the command calls it, watched: a
        pass whose verified pieces stand still for the stall limit is given
        up and ``None`` returned."""
        from torrent_tpu.fabric import FabricConfig
        from torrent_tpu.parallel.bulk import verify_library_fabric

        c = self.cell.config["fabric"]
        cfg = FabricConfig(
            heartbeat_interval=float(c["heartbeat_interval"]), lapse_after=float(c["lapse_after"]),
            fault_exit_after_units=None, byzantine_f=int(c["byzantine_f"]), audit_rate=float(c["audit_rate"]),
            audit_seed=int(c["audit_seed"]), forge_receipts=False,
        )
        unit_mb = int(self.cell.config["unit_mb"])
        executors: list = []
        task = asyncio.ensure_future(
            verify_library_fabric(
                items, self.sched, nproc=None, pid=None, heartbeat_dir=None, fabric_config=cfg,
                unit_bytes=(unit_mb << 20) if unit_mb else None, executor_out=executors,
            )
        )
        seen, t_seen = -1, time.monotonic()
        while True:
            done, _ = await asyncio.wait({task}, timeout=1.0)
            if executors:
                self.executor = executors[0]
            if done:
                return task.result().bitfields
            verified = self.executor.metrics_snapshot()["pieces_verified"] if self.executor else 0
            if verified != seen:
                seen, t_seen = verified, time.monotonic()
            elif time.monotonic() - t_seen > stall_seconds:
                self._say_state(f"gave no verdict for {stall_seconds:.0f} s, at {verified} pieces verified")
                task.cancel()
                await asyncio.wait({task}, timeout=5.0)
                return None

    def _sweep(self, stall_seconds: float | None = None) -> None:
        """One ``_fabric_verify`` from its glob to its result."""
        import jax

        with jax.profiler.TraceAnnotation("bench_parse_library"):
            items = self._items()
        try:
            with jax.profiler.TraceAnnotation("bench_verify_library_fabric"):
                bitfields = self.loop.run_until_complete(self._pass(items, stall_seconds or self.stall_seconds))
        finally:
            for storage, _ in items:
                storage.method.close()
        if bitfields is None:
            self.stalled = True
        else:
            bitfields = [np.asarray(b, dtype=bool) for b in bitfields]
        self.passes.append({"t_end": time.monotonic(), "bits": bitfields})

    def _say_state(self, why: str, s: dict | None = None) -> None:
        """What a verdict that never came would be waiting behind."""
        s = s or self.sched.metrics_snapshot()
        lanes = {k: {x: v[x] for x in ("target", "launches", "mean_fill", "kernel")} for k, v in s["lane_stats"].items()}
        fabric = self.executor.metrics_snapshot() if self.executor else {}
        _say(
            f"scheduler {why}: queue_pieces {s['queue_pieces']}, queue_bytes {s['queue_bytes']}, staging {s['staging']}, "
            f"shed {s['shed_total']}, retries {s['retries']}, failed_pieces {s['failed_pieces']}, "
            f"launch_failures {s['launch_failures']}, flush_reasons {s['flush_reasons']}, lanes {lanes}; "
            f"executor: { {k: fabric.get(k) for k in ('state', 'units_done', 'units_total', 'pieces_verified', 'inflight_bytes')} }"
        )

    # window ---------------------------------------------------------------

    def window(self, seconds: float) -> float:
        self.before = self.sched.metrics_snapshot()
        self.t_open = time.monotonic()
        while time.monotonic() - self.t_open < seconds and not self.stalled:
            self._sweep()
        self.after = self.sched.metrics_snapshot()
        self.fabric = self.executor.metrics_snapshot()
        return self.t_open

    # after the window -----------------------------------------------------

    def release(self) -> None:
        """Close the scheduler, its lanes and planes with it."""
        if self.sched is not None:
            self.loop.run_until_complete(self.sched.close())
            self.sched = None
        self.loop.close()

    def abort(self) -> None:
        if self.sched is not None and not self.loop.is_closed():
            try:
                self.loop.run_until_complete(asyncio.wait_for(self.sched.close(), 10))
            except Exception as e:  # a run that failed is ending anyway: say so and go on
                _say(f"scheduler close at abort: {e!r}")
            self.sched = None

    def check(self, control: bool = False) -> dict:
        root = self.cell.work_dir
        ref: list = []
        for t in self.torrents:
            ref += reference_library.torrent_verdicts(
                os.path.join(root, "torrents", t.stem + ".torrent"), os.path.join(root, "data", t.stem)
            )
        program: list = []
        for p in self.passes:
            if control:
                program += reference_library.control_verdicts(self.n_pieces)
            elif p["bits"] is None:
                program += [None] * self.n_pieces
            else:
                for bits, n in zip(p["bits"], self.pieces_of):
                    got = [bool(b) for b in bits][:n]
                    program += got + [None] * (n - len(got))
        numbers = reference_library.compare(program, ref * len(self.passes))
        numbers["planted_invalid"] = len(self.corrupt) * len(self.passes)
        b, a = self.before, self.after
        self._say_state("after the last pass", a)
        for key in ("cpu_fallback_launches", "failed_pieces", "launch_failures"):
            numbers[key] = {"value": a[key] - b[key], "limit": 0}
        kernels = sorted({str(v.get("kernel")) for v in a["lane_stats"].values()})
        numbers["hashlib_lanes"] = {"value": sum(k == "hashlib" for k in kernels), "limit": 0}
        numbers["staging_outstanding"] = {"value": a["staging"]["outstanding"], "limit": 0}
        numbers["lane_kernels"] = kernels
        by_lane = self._launches_by_lane()
        numbers["launches"] = a["launches"] - b["launches"]
        numbers["lanes_with_launches"] = sum(1 for v in by_lane.values() if v["launches"])
        # every launch on the zero-copy road; None where the program keeps no such count
        staged = [v["staged_launches"] for v in by_lane.values()]
        numbers["staged_launches"] = None if None in staged else sum(staged)
        return numbers

    def _launches_by_lane(self) -> dict:
        """Per lane over the window: launches, pieces launched, and, where
        the program counts them, staged launches and rows."""
        out = {}
        for lane, a in self.after["lane_stats"].items():
            b = self.before["lane_stats"].get(lane, {})
            row = {
                "target": a["target"],
                "launches": a["launches"] - b.get("launches", 0),
                "pieces": round((a["mean_fill"] * a["launches"] - b.get("mean_fill", 0.0) * b.get("launches", 0)) * a["target"]),
            }
            for key in ("staged_launches", "staged_rows_total", "staged_live_rows_total"):
                row[key] = a[key] - b.get(key, 0) if key in a else None
            out[lane] = row
        return out

    def counts(self, numbers: dict) -> dict:
        attempted = self.n_pieces * len(self.passes)
        failed = numbers["wrong_verdicts"]["value"] + numbers["missing_verdicts"]["value"]
        verdicts = sum(1 for p in self.passes if p["bits"] is not None)
        return {
            "attempted": attempted, "failed": failed,
            # piece-space bytes that got a verdict: the pads' zeros are hashed like any byte
            "bytes": self.space_bytes * verdicts,
            # to the last verdict counted: the pass under way at the close is finished
            "window_s": self.passes[-1]["t_end"] - self.t_open,
            "classes": None, "failures": [],
        }

    def end_to_end(self, counts: dict) -> dict:
        return {"verify_gib_s": counts["bytes"] / 2**30 / counts["window_s"]}

    def observations(self) -> dict:
        by_lane = self._launches_by_lane()
        _say(f"launches by lane over {len(self.passes)} passes: {by_lane}")
        return {
            "sched": (self.before, self.after),
            "fabric": self.fabric,
            "passes": len(self.passes),
            "launches": self.after["launches"] - self.before["launches"],
            "launches_by_lane": by_lane,
        }
