"""What the harness watches around a window, in the process that holds the
chip: compile events, the traced slice, process CPU time, device memory."""

from __future__ import annotations

import os
import shutil
import threading
import time

TRACE_START_FRACTION = 0.3  # of the window, where the traced slice begins
TRACE_CLEAR_SECONDS = 0.5  # requests due this long before the slice or earlier never meet the profiler


class CompileCounter:
    """Counts JAX compile requests and persistent-cache hits; a compile
    that ran the compiler is a request that was no hit. Listeners cannot
    be removed one by one, so one counter serves the process."""

    def __init__(self):
        import jax.monitoring as mon

        self.requests = 0
        self.hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "cache_hits": self.hits, "compiled": self.requests - self.hits}


class TraceSlice(threading.Thread):
    """Profiles one slice of the window from a thread of its own, so the
    window's driver is not the one that waits for the profiler. The slice
    opens ``TRACE_START_FRACTION`` into the window and closes once
    ``launches`` more launches have been counted (``count`` is the driver's
    running count) or after ``max_seconds``: a slice, not the window, and
    one measured in launches, because a scan-backend launch logs some 75,000
    device events and the profiler needs about 15 s to collect each one when
    it stops. The slice lies inside a ``bench_trace_window`` span, which
    gives the reduction the window in the trace's own clock."""

    def __init__(self, trace_dir: str, window_seconds: float, launches: int, max_seconds: float, count):
        super().__init__(name="bench-trace", daemon=True)
        self.trace_dir = trace_dir
        self.start_after = TRACE_START_FRACTION * window_seconds
        self.launches = launches
        self.max_seconds = min(max_seconds, 0.4 * window_seconds)
        self.count = count
        self.costs: dict = {}
        self.error: BaseException | None = None

    def run(self) -> None:
        import jax

        try:
            time.sleep(self.start_after)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            t0 = time.monotonic()
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            t1 = time.monotonic()
            try:
                with jax.profiler.TraceAnnotation("bench_trace_window"):
                    first = self.count()
                    while time.monotonic() - t1 < self.max_seconds and self.count() - first < self.launches:
                        time.sleep(0.002)
                    t2 = time.monotonic()
            finally:
                jax.profiler.stop_trace()
            self.costs = {"start_trace_s": t1 - t0, "slice_s": t2 - t1, "stop_trace_s": time.monotonic() - t2}
        except BaseException as e:  # reported by the harness, which joins
            self.error = e


def cpu_seconds() -> float:
    t = os.times()
    return t.user + t.system


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device."""
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def fresh_dir(path: str) -> str:
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path
