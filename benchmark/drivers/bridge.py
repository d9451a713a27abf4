"""Entry driver: the HTTP bridge, ``BridgeServer`` started in this process
on 127.0.0.1 with the scheduler's defaults, and clients in a child process
that never imports JAX (``harness/loadgen.py``).

The server runs on an event loop in a thread of its own, as a sidecar
would; this thread talks to the child over its pipes. The scheduler's
counters are read here, before the window opens and after the last request
has been drained. The server is closed only after
the child has said that everything in flight has its verdict.
"""

from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
import threading
import time


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.plen = int(cell.config["piece_length"])
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever, name="bench-bridge", daemon=True)
        self.child: subprocess.Popen | None = None
        self.server = None
        self.before: dict = {}
        self.after: dict = {}
        self.result: dict = {}

    # plumbing -------------------------------------------------------------

    def _on_loop(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result()

    def _tell(self, **cmd) -> None:
        self.child.stdin.write(json.dumps(cmd) + "\n")
        self.child.stdin.flush()

    def _hear(self, event: str) -> dict:
        line = self.child.stdout.readline()
        if not line:
            raise RuntimeError(f"the load generator ended (code {self.child.wait()}) before {event!r}")
        msg = json.loads(line)
        if msg.get("event") != event:
            raise RuntimeError(f"the load generator said {msg!r}, not {event!r}")
        return msg

    def launch_count(self) -> int:
        return self.server.sched.metrics_snapshot()["launches"]

    def _counters(self) -> dict:
        return {"sched": self.server.sched.metrics_snapshot()}

    # set-up ---------------------------------------------------------------

    def setup(self) -> None:
        cfg, traffic = self.cell.config, self.cell.traffic
        spec = dict(traffic, seed=self.cell.seed, piece_length=self.plen)
        # the child builds its bodies while this process brings the device up
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(self.cell.root, "benchmark", "harness", "loadgen.py"), json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, bufsize=1,
            env={k: v for k, v in os.environ.items() if k != "BENCH_RUN"},
        )
        from torrent_tpu.bridge.service import BridgeServer
        from torrent_tpu.utils.device import enable_compile_cache

        enable_compile_cache()  # as bridge/service.py:main does for --hasher tpu
        self.thread.start()
        s = cfg["scheduler"]
        self.server = self._on_loop(
            BridgeServer(
                "127.0.0.1", 0, hasher="tpu",
                batch_target=int(s["batch_target"]),
                flush_deadline_ms=float(s["flush_deadline_ms"]),
                max_queue_mb=int(s["max_queue_mb"]),
                tenant_max_mb=int(s["tenant_max_mb"]),
            ).start()
        )
        self.cell.log("bridge listening")
        self._hear("ready")
        self.cell.log("load generator ready")
        self._tell(cmd="warm", port=self.server.port)
        warmed = self._hear("warmed")
        if warmed["failed"]:
            raise RuntimeError(f"{warmed['failed']} pieces failed in the warm-up")

    # window ---------------------------------------------------------------

    def window(self, seconds: float) -> float:
        self.before = self._counters()
        self._tell(cmd="go", seconds=seconds)
        closed = self._hear("closed")  # everything in flight has been awaited
        self.after = self._counters()
        return closed["t_open"]

    # after the window -----------------------------------------------------

    def release(self) -> None:
        """Close the server, the scheduler with it, and the loop."""
        async def close():
            self.server.close()
            await self.server.wait_closed()

        self._on_loop(close())
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(timeout=30)
        self.server = None

    def check(self, control: bool = False) -> dict:
        path = os.path.join(self.cell.work_dir, "loadgen_report.json")
        self._tell(cmd="report", path=path, control=control)
        self._hear("reported")
        self._tell(cmd="exit")
        self.child.wait(timeout=60)
        with open(path) as f:
            self.result = json.load(f)
        r = self.result
        lat = r["latency_ms"]
        if lat:
            from benchmark.harness.loadgen import percentile

            print(
                f"loadgen: {r['requests']} requests, due-to-verdict ms p50 {percentile(lat, 50):.2f} "
                f"p95 {percentile(lat, 95):.2f} p99 {percentile(lat, 99):.2f} max {max(lat):.2f}; "
                f"p50 by half of the window {r['p50_by_half_ms']}; sent late p99 {percentile(r['late_ms'], 99):.3f} ms; "
                f"last verdict {r['drain_s']:.3f} s after the close",
                file=sys.stderr, flush=True,
            )
        sched0, sched1 = self.before["sched"], self.after["sched"]
        # what a request that never got its reply would be waiting behind
        print(
            f"scheduler after the drain: queue_pieces {sched1['queue_pieces']}, queue_bytes {sched1['queue_bytes']}, "
            f"staging {sched1['staging']}, shed {sched1['shed_total'] - sched0['shed_total']}, "
            f"retries {sched1['retries'] - sched0['retries']}, failed_pieces {sched1['failed_pieces'] - sched0['failed_pieces']}",
            file=sys.stderr, flush=True,
        )
        kernels = sorted({str(v.get("kernel")) for v in sched1["lane_stats"].values()})
        return {
            "compared": r["attempted"],
            "reference_invalid": r["reference_invalid"],
            "wrong_verdicts": {"value": r["classes"]["wrong"], "limit": 0},
            "missing_verdicts": {"value": r["classes"]["transport"] + r["classes"]["refused"], "limit": 0},
            "cpu_fallback_launches": {
                "value": sched1["cpu_fallback_launches"] - sched0["cpu_fallback_launches"], "limit": 0
            },
            "hashlib_lanes": {"value": sum(k == "hashlib" for k in kernels), "limit": 0},
            "lane_kernels": kernels,
        }

    def counts(self, numbers: dict) -> dict:
        r = self.result
        return {
            "attempted": r["attempted"], "failed": r["failed"],
            "bytes": r["answered_pieces"] * self.plen,
            "window_s": r["t_last_verdict"] - r["t_open"],
            "classes": r["classes"], "failures": r["failures"],
        }

    def end_to_end(self, counts: dict) -> dict:
        from benchmark.harness.loadgen import percentile

        lat = self.result["latency_ms"]
        out = {"verify_gib_s": counts["bytes"] / 2**30 / counts["window_s"] if counts["window_s"] > 0 else None}
        if lat:
            out["verdict_p50_ms"] = percentile(lat, 50)
            out["verdict_p95_ms"] = percentile(lat, 95)
        return out

    def observations(self) -> dict:
        return {
            "sched": (self.before["sched"], self.after["sched"]),
            "launches": self.after["sched"]["launches"] - self.before["sched"]["launches"],
            "loadgen": self.result,
            "child_cpu_s": self.result.get("cpu_s", 0.0),
        }

    def abort(self) -> None:
        if self.child is not None and self.child.poll() is None:
            self.child.kill()
            self.child.wait()
