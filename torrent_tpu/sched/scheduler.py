"""Continuous-batching scheduler for the hash plane — the multi-tenant
verify queue that turns a fast single-caller plane into a servable one.

Every entry point used to dispatch its own device batches in isolation
(bridge routes, parallel/verify.py, parallel/bulk.py, session
rechecks), so concurrent small callers each paid the fixed ~55 ms
dispatch cost on mostly-empty launches (BASELINE.md: batch fill is the
dominant throughput knob — 4096-piece dispatches cap at ~67k p/s,
8192 reaches 169k). This subsystem owns all dispatch instead:

    submit ──► admission control ──► per-tenant queues ──► DRR
               (bounded bytes,        (one deque per       assembler
                shed = typed 429)      tenant per lane)       │
                                                              ▼
    awaiting callers ◄── per-launch demux ◄── device launch (full batch
                         (futures resolve      OR deadline flush, so a
                          per submission)      lone 4-piece request is
                                               never stranded)

Work items are grouped into **lanes** keyed ``(algo, piece-length
bucket)`` — the same pow-2 bucketing the bridge used, so a handful of
compiled executables serve any geometry and the compile cache survives
across callers. Each lane runs one assembler task: it flushes a launch
when the batch fills to the lane target **or** when the oldest queued
item's deadline expires (flush reasons: full / deadline / hint /
shutdown). A submitter that sends nothing more until its submission
resolves says so (``enqueue(..., flush=True)``): nothing it does can
fill the lane further, so the lane takes at once — the take the deadline
would have made, without the wait (reason ``hint`` when under target).

Fairness is deficit round-robin over queued *bytes*: each tenant's
deficit grows by ``drr_quantum × weight`` per assembly pass, so a greedy
bulk tenant cannot starve a trickle CLI verify, and low-priority tenants
(session self-heal rechecks, ``weight < 1``) yield to foreground
traffic without ever being starved.

Admission control bounds queue memory globally and per tenant. A
non-blocking submit over the bound sheds with :class:`SchedRejected`
(the bridge maps it to HTTP 429); a blocking submit waits for space —
that wait is the backpressure a streaming ingest propagates to its TCP
socket. Queue depth, batch-fill ratio, flush reasons, per-tenant served
bytes, and shed counts are exported via ``utils/metrics.py``
(``render_sched_metrics``). The obs plane (``torrent_tpu/obs``) rides
the same lifecycle: always-on log2 latency histograms (queue wait,
launch, per-tenant end-to-end) feed ``/metrics`` as real Prometheus
histograms, traced submissions get per-stage spans (enqueue →
admission/shed → lane wait → launch/retry/bisect → digest → verdict,
and for a ``submit()`` caller → wake: how late the loop ran it again),
the flight recorder dumps a black box on breaker-open and
retry-exhausted failures, and device launches are annotated in the
deep-dive profiler timeline via ``obs/profiler.py``. The pipeline
ledger (``obs/ledger.py``) additionally accounts byte/time/occupancy
at every stage boundary — lane assembly, staging-slot copies, device
puts (h2d), launches, D2H fetches, and the verdict demux — feeding the
bottleneck attributor behind ``GET /v1/pipeline`` and ``doctor
--bottleneck``; each stage entry is also a host span in the profiler's
trace, and a lane's waits (idle, flush deadline, pipeline semaphore)
are recorded beside the stages, never among them; so is ``verdict_wake``,
``submit()``'s word on the loop's lag, after the fact and without a span.
``metrics_snapshot()`` carries the sum and count of the queue-wait and
e2e histogram families (``queue_wait_s_sum`` / ``_pieces``, ``e2e_s_sum``
/ ``e2e_pieces``), so that a delta of two snapshots makes a mean.

Failure domains. A launch exception must not fail every co-batched
ticket across all tenants, so dispatch is fault-isolated in two layers:

* **Retry + bisection** (:meth:`HashPlaneScheduler._dispatch`): a
  failed launch is retried once if the error classifies as *transient*
  (device/XLA hiccups — retrying a *deterministic* payload error is
  pointless and skipped), then split in half and each half relaunched,
  recursively to ``bisect_depth``. A single poisoned ticket therefore
  fails alone — its submitter's future gets a classified
  :class:`SchedLaunchError` — while every innocent co-batched ticket
  still receives its digest.
* **Per-lane circuit breaker** (:class:`_LaneBreaker`): consecutive
  transient failures of a lane's primary plane trip the lane to the
  hashlib :class:`_CpuPlane` (the parity fallback the BASELINE contract
  keeps), so the verify plane degrades to correct-but-slower instead of
  erroring. After ``breaker_cooldown`` a half-open probe sends one
  launch back to the primary plane; success re-closes the breaker.
  Breaker state and transitions are exported in ``metrics_snapshot()``.

Both layers are driven deterministically in tests by
``torrent_tpu.sched.faults`` (a :class:`FaultPlan` wired through the
``plane_factory`` seam), so every behavior above has a CPU-only test.

Zero-copy ingest. Scheduler-fed read loops check a :class:`StagedSlab`
out of the per-(algo, bucket) ingest pools (:meth:`checkout_staging`),
land disk reads directly in its row-strided view, and submit it with
:meth:`enqueue_staged`: tickets carry :class:`SlotRow` views (no
per-piece ``bytes``), single-slab launches hit the planes'
``run_staged`` form (the slab IS the launch buffer — the ledger's
``stage`` copy stage records zero bytes), and device planes H2D the
slab outside ``_device_lock`` with donated input buffers so batch
N+1's transfer overlaps batch N's kernel. Slabs are reference counted
(one ref per ticket, released at demux on every path) and the pools'
``outstanding`` gauge must return to 0 — see ARCHITECTURE.md
"Zero-copy ingest" for ownership rules and the fallback matrix.

The SHA-1 device plane's copying road launches at the rows a flush
holds: the smallest rung of a fixed, warmed row ladder
(:func:`_row_ladder`) that takes the chunk, so a sparse deadline flush
stages, uploads and hashes 32 rows where a full take uses the plane's
whole batch. ``lane_stats`` counts every attempt's launched and pad rows.

The scheduler autopilot (``sched/control.py``) closes the observe→act
loop over these sensors: a periodic controller turns ledger/attribution
snapshot deltas into bounded actuator moves through the seams below —
``set_lane_target`` / ``set_lane_deadline`` (adaptive batching, snapped
via the planes' ``launch_geometry`` hooks), ``set_admission_factor``
(admit no faster than the limiting stage drains), and
``steer_lane_backend`` (hysteresis-guarded backend trials). With no
autopilot attached every seam stays at its static default and behavior
is bit-identical to the config.

The v2 (sha256) lanes default to the hand-tiled pallas kernel
(:class:`_Sha256PallasPlane`; ``TORRENT_TPU_SHA256_BACKEND`` /
``SchedulerConfig.sha256_backend`` select, lax.scan is the fallback).
Lane batching is plane-aware: pallas lane flush targets snap to tile
multiples (full launches waste zero pad rows), sub-tile partial flushes
round up to the 1024-row granule with ``nblocks=0`` sentinels, and
admission control charges the padded staging footprint per queued piece
rather than raw payload bytes. See ARCHITECTURE.md "The v2 hash plane".
"""

from __future__ import annotations

import asyncio
import bisect
import hashlib
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable

from torrent_tpu.analysis.sanitizer import guard_attrs, named_lock
from torrent_tpu.obs.hist import histograms
from torrent_tpu.obs.ledger import pipeline_ledger
from torrent_tpu.obs.recorder import flight_recorder
from torrent_tpu.obs.tracer import tracer
from torrent_tpu.utils.log import get_logger

log = get_logger("sched")

DIGEST_LEN = {"sha1": 20, "sha256": 32}

# latency-histogram families (torrent_tpu/obs): always-on per-stage
# distributions rendered as Prometheus histograms on every scrape
_H_QUEUE_WAIT = (
    "torrent_tpu_sched_queue_wait_seconds",
    "Seconds tickets waited in lane queues before launch assembly",
)
_H_LAUNCH = (
    "torrent_tpu_sched_launch_seconds",
    "Hash-plane launch duration per attempt (staging + device run)",
)
_H_E2E = (
    "torrent_tpu_sched_e2e_seconds",
    "Ticket enqueue-to-verdict seconds, labeled by tenant",
)


class SchedRejected(Exception):
    """Typed admission-control rejection (load shed).

    Carries enough structure for callers to surface a useful 429: the
    reason, the tenant, and the observed/limit byte figures.
    """

    def __init__(self, reason: str, tenant: str, queued_bytes: int = 0, limit_bytes: int = 0):
        super().__init__(
            f"{reason} (tenant={tenant} queued={queued_bytes}B limit={limit_bytes}B)"
        )
        self.reason = reason
        self.tenant = tenant
        self.queued_bytes = queued_bytes
        self.limit_bytes = limit_bytes


class SchedLaunchError(Exception):
    """A submission's pieces could not be hashed after retry/bisection.

    ``kind`` classifies the root cause: ``"transient"`` (device/XLA
    error that outlived the retry budget — the caller may retry later;
    the bridge maps this to 503 + Retry-After) or ``"deterministic"``
    (the payload itself makes the plane fail — retrying cannot help).
    """

    def __init__(self, message: str, kind: str, cause: Exception | None = None):
        super().__init__(message)
        self.kind = kind
        self.cause = cause
        self.__cause__ = cause


def classify_error(e: BaseException) -> str:
    """``'deterministic'`` (payload-caused, retry is pointless) or
    ``'transient'`` (device-plane hiccup, worth one retry).

    Fault-injection errors self-classify via ``sched_error_class``;
    otherwise value/shape errors are deterministic and everything else
    (XLA runtime errors, OSError, …) is assumed transient.
    """
    kind = getattr(e, "sched_error_class", None)
    if kind in ("deterministic", "transient"):
        return kind
    if isinstance(e, (ValueError, TypeError, KeyError, IndexError, AssertionError)):
        return "deterministic"
    return "transient"


@dataclass
class SchedulerConfig:
    # pieces per device launch the assembler aims to fill (per-lane
    # targets shrink for big-piece buckets so staging stays bounded)
    batch_target: int = 256
    # seconds the oldest queued item may wait before a partial flush
    flush_deadline: float = 0.02
    # global admission bound: queued + in-flight payload bytes
    max_queue_bytes: int = 256 << 20
    # per-tenant admission bound (a single tenant can't fill the queue)
    max_tenant_bytes: int = 128 << 20
    # DRR byte quantum added to each tenant's deficit per assembly pass
    drr_quantum: int = 1 << 20
    # per-lane staging budget: device batch ≈ budget / padded_len, like
    # the bridge's old staging rule, so a 16 MiB bucket can't OOM
    staging_budget: int = 128 << 20
    # launches allowed in flight per lane: 2 = double-buffer (the next
    # batch assembles and stages while the previous one runs on device,
    # matching the old stream gate's pending depth); 1 = strictly serial
    pipeline_depth: int = 2
    # auto-registered tenants beyond this bound are evicted once idle
    # (explicitly registered tenants are pinned) — bounds the state an
    # attacker can create with fresh X-Tenant values per request
    max_idle_tenants: int = 1024
    # test/extension hook: (algo, bucket, batch) -> plane with
    # .run(payloads) -> list[digest]; None = built-in planes
    plane_factory: Callable | None = None
    # relaunches of a failed batch before bisection, transient errors
    # only (a deterministic payload error skips straight to bisection)
    launch_retries: int = 1
    # max split-and-relaunch recursion isolating a poisoned ticket: a
    # depth of 12 isolates one piece out of a 4096-piece launch; past
    # the bound the surviving group fails together
    bisect_depth: int = 12
    # consecutive transient failures of a lane's primary plane before
    # the lane trips to the CPU (hashlib) fallback plane
    breaker_threshold: int = 3
    # seconds an open breaker waits before a half-open probe re-admits
    # the primary plane
    breaker_cooldown: float = 30.0
    # sha256 device backend: 'pallas' | 'scan' | 'auto' (None = the
    # TORRENT_TPU_SHA256_BACKEND env knob, defaulting to auto: pallas on
    # TPU-kind devices, scan elsewhere). A lane whose tile floor would
    # blow the staging budget falls back to scan regardless.
    sha256_backend: str | None = None


def resolve_sha256_backend(override: str | None = None) -> str:
    """``'pallas'`` or ``'scan'`` for the sha256 device plane.

    Precedence: explicit ``override`` (SchedulerConfig / bridge CLI) >
    ``TORRENT_TPU_SHA256_BACKEND`` env > ``auto``. Auto picks pallas on
    TPU-kind devices and scan everywhere else — choosing pallas
    explicitly on a CPU host runs the kernel in interpret mode (the
    deterministic parity path tests and ``doctor --v2`` use).
    """
    import os

    choice = (override or os.environ.get("TORRENT_TPU_SHA256_BACKEND") or "auto")
    choice = choice.strip().lower()
    if choice not in ("auto", "pallas", "scan"):
        raise ValueError(
            f"sha256 backend must be auto|pallas|scan, got {choice!r}"
        )
    if choice != "auto":
        return choice
    try:
        from torrent_tpu.ops.sha1_pallas import _auto_interpret

        return "scan" if _auto_interpret() else "pallas"
    except ImportError:  # pragma: no cover - jax without pallas
        return "scan"


class _Tenant:
    __slots__ = (
        "name", "weight", "max_bytes", "queued_bytes", "served_bytes",
        "served_pieces", "shed", "deficit", "pinned",
    )

    def __init__(self, name: str, weight: float = 1.0, max_bytes: int | None = None):
        self.name = name
        self.weight = weight
        self.max_bytes = max_bytes
        self.queued_bytes = 0
        self.served_bytes = 0
        self.served_pieces = 0
        self.shed = 0
        self.deficit = 0
        self.pinned = False  # register_tenant pins; auto-registered may be evicted


class _Submission:
    """One caller request of N pieces; resolves when all N demuxed.

    ``trace`` is the obs span context — ``(trace_id, parent_span_id)``
    captured at enqueue when the caller ran inside a span (bridge
    requests always do) — carried explicitly because lane assembler
    tasks and worker threads never inherit a request's contextvars.
    """

    __slots__ = ("mode", "results", "remaining", "future", "trace",
                 "traced_done", "flush", "t_resolved")

    def __init__(
        self, n: int, mode: str, loop: asyncio.AbstractEventLoop,
        flush: bool = False,
    ):
        self.mode = mode  # 'digest' | 'verify'
        # the submitter sends nothing more until this resolves: a lane
        # holding any of its tickets takes at once (_Lane.flush_pending)
        self.flush = flush
        self.results: list = [None] * n
        self.remaining = n
        self.future: asyncio.Future = loop.create_future()
        self.trace: tuple[str, str] | None = None
        # terminal digest/verdict spans recorded (a submission split
        # across launches whose halves fail separately must not get one
        # span per failing demux)
        self.traced_done = False
        # the instant the future got its result or its exception (on the
        # loop thread, in the demux): ``submit()`` measures from here how
        # late the loop ran the waiting caller again
        self.t_resolved: float | None = None

    def deliver(self, idx: int, value) -> None:
        self.results[idx] = value
        self.remaining -= 1
        if self.remaining == 0 and not self.future.done():
            self.t_resolved = time.monotonic()
            if self.mode == "verify":
                self.future.set_result(bytes(self.results))
            else:
                self.future.set_result(self.results)

    def fail(self, error: BaseException) -> None:
        if not self.future.done():
            self.t_resolved = time.monotonic()
            self.future.set_exception(error)


class _Ticket:
    """One piece in the queue: (submission, index, payload, expected).

    ``nbytes`` is the true payload size (DRR fairness, served-bytes
    accounting); ``charged`` is what admission control holds for this
    row — the padded staging footprint on device lanes, so the queue
    bound tracks what the launch actually stages, not the raw bytes.
    """

    __slots__ = ("sub", "idx", "payload", "expected", "tenant", "nbytes",
                 "charged", "ts")

    def __init__(self, sub, idx, payload, expected, tenant, ts, charged=None):
        self.sub = sub
        self.idx = idx
        self.payload = payload
        self.expected = expected
        self.tenant = tenant
        self.nbytes = len(payload)
        self.charged = self.nbytes if charged is None else charged
        self.ts = ts


# per-lane row counters bumped by worker threads under _counter_lock
_LANE_ROW_COUNTERS = (
    "pad_rows_total", "launched_rows_total",
    "staged_launches", "staged_rows_total", "staged_live_rows_total",
)


class _Lane:
    """Assembler state for one (algo, piece-length bucket) geometry."""

    __slots__ = (
        "algo", "bucket", "target", "queues", "rotation", "pending_pieces",
        "flush_pending", "event", "task", "plane", "build_lock", "sem", "inflight",
        "breaker", "cpu_plane", "backend", "deadline",
        "launches", "fill_sum", "pad_rows_total", "launched_rows_total",
        "staged_launches", "staged_rows_total", "staged_live_rows_total",
    )

    def __init__(
        self,
        algo: str,
        bucket: int,
        target: int,
        pipeline_depth: int,
        breaker: "_LaneBreaker",
        backend: str = "device",
    ):
        self.algo = algo
        self.bucket = bucket
        self.target = target
        self.queues: dict[str, deque] = {}
        self.rotation: list[str] = []
        self.pending_pieces = 0
        # queued tickets of flush=True submissions: counted where tickets
        # enter (enqueue) and leave (_drr_take) the queues, their only
        # two doors, so it is never stale; > 0 ends the fill wait
        self.flush_pending = 0
        self.event = asyncio.Event()
        self.task: asyncio.Task | None = None
        self.plane = None  # built lazily off the event loop
        # pipelined launches run _run_plane in concurrent worker threads,
        # so first-use plane construction needs a real lock
        self.build_lock = named_lock("sched.lane.build_lock")
        self.sem = asyncio.Semaphore(max(1, pipeline_depth))
        self.inflight: set[asyncio.Task] = set()
        self.breaker = breaker
        self.cpu_plane = None  # hashlib degradation plane, built lazily
        self.backend = backend  # 'cpu' | 'device' | 'scan' | 'pallas'
        # per-lane flush-deadline override (the autopilot's actuator);
        # None = the SchedulerConfig value, so controller-off behavior
        # is bit-identical to the static config
        self.deadline: float | None = None
        # per-lane observability: launch-fill and pad-row waste gauges
        self.launches = 0
        self.fill_sum = 0.0
        self.pad_rows_total = 0
        self.launched_rows_total = 0
        # the zero-copy road's own fill: launch attempts that took
        # run_staged, the rows of the slabs they handed over (what a
        # device plane uploads: the whole slab, whatever is live) and
        # the ticket rows among them. pad_rows_total stays row-exact
        # for this road (a staged slab's rows are its reader's)
        self.staged_launches = 0
        self.staged_rows_total = 0
        self.staged_live_rows_total = 0

    def oldest_ts(self) -> float:
        return min(q[0].ts for q in self.queues.values() if q)


class _LaneBreaker:
    """Per-lane circuit breaker over the primary (device) plane.

    closed → open after ``threshold`` consecutive transient failures;
    open → half_open after ``cooldown`` seconds; half_open admits ONE
    probe launch — success closes the breaker, failure re-opens it.
    Launches run in concurrent worker threads (pipeline_depth ≥ 2), so
    every state read/transition holds the lock. Deterministic payload
    failures are not device faults: they release a probe slot but never
    move the state or the failure count.
    """

    __slots__ = (
        "threshold", "cooldown", "state", "failures", "opened_at",
        "probing", "transitions", "lock", "_cells",
    )

    def __init__(self, threshold: int, cooldown: float):
        self.threshold = max(1, threshold)
        self.cooldown = cooldown
        self.state = "closed"
        self.failures = 0
        self.opened_at = 0.0
        self.probing = False  # one half-open probe in flight at a time
        self.transitions: dict[str, int] = {}
        self.lock = named_lock("sched.breaker.lock")
        # dynamic lockset checking (tsan-lite Eraser): the whole
        # state/failures/probing blob is one cell guarded by self.lock
        self._cells = guard_attrs("sched.breaker", "state")

    def _to(self, state: str) -> None:
        key = f"{self.state}->{state}"
        self.transitions[key] = self.transitions.get(key, 0) + 1
        self.state = state

    def acquire_primary(self) -> bool:
        """Whether the next launch may use the primary plane (False =
        degrade to the CPU plane for this launch)."""
        with self.lock:
            self._cells.write("state")  # probing may flip below
            if self.state == "closed":
                return True
            if (
                self.state == "open"
                and time.monotonic() - self.opened_at >= self.cooldown
            ):
                self._to("half_open")
                self.probing = False
            if self.state == "half_open" and not self.probing:
                self.probing = True
                return True
            return False

    def record_success(self) -> None:
        with self.lock:
            self._cells.write("state")
            self.probing = False
            self.failures = 0
            if self.state != "closed":
                self._to("closed")

    def record_failure(self) -> bool:
        """A transient primary-plane failure (deterministic payload
        errors go through :meth:`release_probe` instead). Returns True
        when THIS failure transitioned the breaker to open — the
        caller's flight-recorder trigger point, kept outside the lock
        (dumping under it would nest the obs locks below breaker
        state)."""
        with self.lock:
            self._cells.write("state")
            if self.state == "half_open":
                self.probing = False
                self._to("open")
                self.opened_at = time.monotonic()
                return True
            self.failures += 1
            if self.state == "closed" and self.failures >= self.threshold:
                self._to("open")
                self.opened_at = time.monotonic()
                return True
            return False

    def release_probe(self) -> None:
        with self.lock:
            self._cells.write("state")
            self.probing = False

    def snapshot(self) -> dict:
        with self.lock:
            self._cells.read("state")
            out = {
                "state": self.state,
                "consecutive_failures": self.failures,
                "transitions": dict(self.transitions),
                # readiness semantics (obs/slo.build_health): an open
                # breaker within its cooldown is a transient degradation;
                # one stuck open well past it means the half-open probe
                # path is wedged and the process should leave rotation
                "cooldown": self.cooldown,
            }
            if self.state == "open":
                out["open_age_s"] = max(0.0, time.monotonic() - self.opened_at)
            return out


# --------------------------------------------------------------- planes


def build_builtin_plane(
    hasher: str, algo: str, bucket: int, batch: int, sha256_backend: str | None = None
):
    """The plane the scheduler builds when no ``plane_factory`` is set.

    Module-level so fault injection (``sched/faults.py``) can wrap the
    real planes through the ``plane_factory`` seam without duplicating
    the construction rules. ``sha256_backend`` pins the v2 backend
    ('pallas'/'scan'); None resolves env/auto via
    :func:`resolve_sha256_backend`.
    """
    if hasher == "cpu":
        return _CpuPlane(algo)
    if algo == "sha256":
        if resolve_sha256_backend(sha256_backend) == "pallas":
            return _Sha256PallasPlane(bucket, batch)
        return _Sha256DevicePlane(bucket, batch)
    return _Sha1DevicePlane(bucket, batch)


def accepts_sha256_backend(fn) -> bool:
    """Whether a plane-factory callable takes the optional
    ``sha256_backend`` kwarg — the seam stays backward compatible with
    3-arg factories, but a factory that can take the lane's resolved
    backend must get it (a 'pallas' pin must not override a
    budget-forced scan fallback; see :meth:`_build_plane`)."""
    import inspect

    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):  # builtins/partials w/o signature
        return False
    return "sha256_backend" in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
    )


class _StagingSlots:
    """Reusable tail-zeroed staging slots shared by the device planes.

    ``hash_pieces``-style staging allocates + zeroes a fresh
    ``batch × padded_len`` buffer every launch — tens of MiB of memset on
    the hot path. Slots are checked out of a locked free list instead
    (pipelined launches run in concurrent worker threads) and remember
    each row's content extent from the previous launch, so ``stage``
    zeroes only the stale tail ``pad_in_place`` requires.
    """

    def __init__(self, rows: int, piece_len: int):
        self.rows = rows
        self.piece_len = piece_len
        self._slots: list[tuple] = []  # (padded, view, ends) free list
        self._lock = named_lock("sched.staging._lock")
        self._cells = guard_attrs("sched.staging", "free_list")
        # leak accounting: every checkout must be balanced by a checkin
        # (asserted by tests and exported via metrics_snapshot)
        self.outstanding = 0
        self.checkouts = 0

    def checkout(self) -> tuple:
        """Raw ``(padded, view, ends)`` slot checkout — the zero-copy
        ingest path fills the slot itself (disk reads land directly in
        ``view``); ``stage`` uses the same checkout for its copy path.
        The caller MUST ``checkin(slot)`` exactly once."""
        import numpy as np

        from torrent_tpu.ops.padding import alloc_padded

        with self._lock:
            self._cells.write("free_list")
            slot = self._slots.pop() if self._slots else None
            self.outstanding += 1
            self.checkouts += 1
        if slot is None:
            padded, view = alloc_padded(self.rows, self.piece_len)
            slot = (padded, view, np.zeros(self.rows, dtype=np.int64))
        return slot

    def stage(self, chunk: list[bytes], rows: int | None = None):
        """Checkout a slot and stage ``chunk`` into its first ``rows``
        rows (default: the whole slot).

        Returns ``(slot, padded, nblocks)`` with ``nblocks`` of length
        ``rows``; rows past ``len(chunk)`` are ``nblocks=0`` sentinels.
        Bounding ``rows`` to the launch (the pallas plane's tile bucket)
        skips the staging work for slot rows the launch never reads —
        untouched rows keep their recorded extents, so later reuse still
        tail-zeroes them correctly. The caller runs its launch, then
        MUST ``checkin(slot)`` (a finally block) to recycle the buffer.
        """
        import numpy as np

        from torrent_tpu.ops.padding import alloc_padded, pad_in_place

        rows = self.rows if rows is None else rows
        # pipeline-ledger "stage" boundary: the host copy into the
        # staging slot (the tracker's lock is leaf-scoped at entry/exit;
        # the copy itself runs outside any obs lock)
        with pipeline_ledger().track(
            "stage", sum(len(c) for c in chunk)
        ):
            slot = self.checkout()
            padded, view, ends = slot
            try:
                lengths = np.zeros(rows, dtype=np.int64)
                for i in range(rows):
                    n = len(chunk[i]) if i < len(chunk) else 0
                    stale = int(ends[i])
                    if stale > n:
                        padded[i, n:stale] = 0
                    if n:
                        view[i, :n] = _payload_ndarray(chunk[i])
                        lengths[i] = n
                nblocks = pad_in_place(padded[:rows], lengths)
                # content extent (message + padding) per row, for the next
                # reuse's tail zeroing — recorded before sentinels clear
                ends[:rows] = nblocks.astype(np.int64) * 64
            except Exception:
                # return the slot instead of leaking it; rows may hold
                # half-staged content past their recorded extents, so mark
                # them full-width — the next reuse tail-zeroes everything
                ends[:rows] = padded.shape[1]
                self.checkin(slot)
                raise
            nblocks[len(chunk) :] = 0  # sentinel rows: skip entirely
            return slot, padded, nblocks

    def checkin(self, slot) -> None:
        with self._lock:
            self._cells.write("free_list")
            self._slots.append(slot)
            self.outstanding -= 1

    def stats(self) -> tuple[int, int]:
        """(outstanding, checkouts) under the free-list lock — snapshot
        readers run on other threads than the checking-out workers."""
        with self._lock:
            self._cells.read("free_list")
            return self.outstanding, self.checkouts


def _payload_ndarray(p):
    """uint8 ndarray view of a ticket payload — SlotRow rows come back
    as views into their slab (no copy), bytes-likes via frombuffer."""
    import numpy as np

    if type(p) is SlotRow:
        return p.ndview()
    return np.frombuffer(p, dtype=np.uint8)


class SlotRow:
    """One staged row of a :class:`StagedSlab`, used as a ticket payload.

    Quacks enough like ``bytes`` for the scheduler's bookkeeping
    (``len``, ``startswith`` for the fault plane's poisoned-prefix
    probe) while never materializing a bytes object: CPU hashing and
    mixed-batch staging consume the numpy row view directly.
    """

    __slots__ = ("slab", "row")

    def __init__(self, slab: "StagedSlab", row: int):
        self.slab = slab
        self.row = row

    def __len__(self) -> int:
        return int(self.slab.lengths[self.row])

    def ndview(self):
        """uint8[len] view into the slab row (zero-copy)."""
        return self.slab.view[self.row, : len(self)]

    def startswith(self, prefix) -> bool:
        n = len(prefix)
        if n > len(self):
            return False
        return bytes(self.slab.view[self.row, :n]) == bytes(prefix)

    def tobytes(self) -> bytes:
        return self.ndview().tobytes()


class StagedSlab:
    """A checked-out staging slot pre-filled by the zero-copy read path.

    Owns one ``(padded, view, ends)`` slot of a scheduler ingest pool
    plus the per-row ``lengths``/``nblocks`` the read path derived —
    disk reads land directly in ``view``'s row-strided memory, rows
    that failed to read carry ``nblocks=0`` sentinels, and the whole
    slab is handed to :meth:`HashPlaneScheduler.enqueue_staged` without
    ever materializing per-piece ``bytes``.

    Lifecycle is reference counted: the creator (the reader) holds one
    reference from checkout; ``enqueue_staged`` retains one per ticket
    and the scheduler's demux releases them as verdicts resolve. The
    slot returns to its pool exactly when the count hits zero — on
    every path (success, launch failure, shed, reader abort), which is
    what the leak-counter test asserts.
    """

    __slots__ = (
        "pool", "slot", "padded", "view", "ends", "nblocks", "lengths",
        "algo", "bucket", "piece_length", "n_used", "_refs", "_lock",
        "_cells",
    )

    def __init__(self, pool: _StagingSlots, slot: tuple, algo: str,
                 bucket: int, piece_length: int):
        import numpy as np

        self.pool = pool
        self.slot = slot
        self.padded, self.view, self.ends = slot
        self.nblocks = np.zeros(pool.rows, dtype=np.int32)
        self.lengths = np.zeros(pool.rows, dtype=np.int64)
        self.algo = algo
        self.bucket = bucket
        self.piece_length = piece_length
        self.n_used = 0
        self._refs = 1  # the creator's hold
        self._lock = named_lock("sched.slab._lock")
        self._cells = guard_attrs("sched.slab", "refs")

    @property
    def rows_total(self) -> int:
        return self.pool.rows

    def prepare(self, planned_lengths) -> None:
        """Zero each row's stale tail beyond its incoming content extent
        (the reads themselves overwrite ``[0, length)``), so a reused
        slot needs no full-width memset before ``pad_in_place``."""
        import numpy as np

        n = len(planned_lengths)
        self.n_used = n
        self.lengths[:n] = np.asarray(planned_lengths, dtype=np.int64)
        self.lengths[n:] = 0
        for i in range(n):
            stale = int(self.ends[i])
            ln = int(self.lengths[i])
            if stale > ln:
                self.padded[i, ln:stale] = 0

    def finalize(self, ok) -> None:
        """Pad the first ``n_used`` rows in place and sentinel the failed
        ones (``ok[i] is False`` → ``nblocks=0``; mark-and-continue)."""
        import numpy as np

        from torrent_tpu.ops.padding import pad_in_place

        n = self.n_used
        nb = pad_in_place(self.padded[:n], self.lengths[:n])
        # dirty extent per row for the NEXT reuse's tail zeroing: padding
        # extent for hashed rows, the attempted read extent for failed
        # ones (their partial bytes are garbage the sentinel masks)
        self.ends[:n] = np.maximum(nb.astype(np.int64) * 64, self.lengths[:n])
        nb[~np.asarray(ok, dtype=bool)] = 0
        self.nblocks[:n] = nb
        self.nblocks[n:] = 0

    def row(self, i: int):
        return self.view[i, : int(self.lengths[i])]

    def retain(self, n: int = 1) -> None:
        with self._lock:
            self._cells.write("refs")
            self._refs += n

    def release(self, n: int = 1) -> None:
        with self._lock:
            self._cells.write("refs")
            self._refs -= n
            done = self._refs == 0
        if done:
            self.pool.checkin(self.slot)


def _staged_batch(payloads):
    """``(slab, rows)`` when every payload is a SlotRow of ONE slab —
    the zero-copy launch form (the plane reads the pre-staged buffer
    directly); ``None`` for mixed batches, which take the copying
    ``plane.run`` path."""
    first = payloads[0] if payloads else None
    if type(first) is not SlotRow:
        return None
    slab = first.slab
    rows = []
    for p in payloads:
        if type(p) is not SlotRow or p.slab is not slab:
            return None
        rows.append(p.row)
    return slab, rows


def _masked_nblocks(slab: StagedSlab, rows: list[int]):
    """Full-slab nblocks with every row OUTSIDE ``rows`` sentineled —
    launches always present the slab's static shape to the compiled
    plane (one executable per lane regardless of fill or bisection
    half) and the masked rows' chains never run."""
    import numpy as np

    nb = np.zeros(slab.rows_total, dtype=np.int32)
    idx = np.asarray(rows, dtype=np.int64)
    nb[idx] = slab.nblocks[idx]
    return nb


def _donating_wrapper(fn):
    """Jit-wrap ``fn(data, nblocks)`` donating the data buffer on real
    accelerators (H2D of batch N+1 then overlaps the kernel of batch N
    without doubling device-resident input memory). On the CPU backend
    donation is refused by XLA and would only warn, so the fn is
    returned unwrapped."""
    import jax

    if jax.default_backend() == "cpu":
        return fn
    return jax.jit(fn, donate_argnums=(0,))


class _CpuPlane:
    """hashlib fallback plane — the CPU-path parity backend."""

    kernel = "hashlib"  # what a launch runs, for metrics_snapshot lane_stats

    def __init__(self, algo: str):
        self._h = hashlib.sha256 if algo == "sha256" else hashlib.sha1

    @staticmethod
    def launch_geometry(n_rows: int, bucket: int) -> tuple[int, int]:
        """hashlib stages nothing: no padding, no staging footprint."""
        return n_rows, 0

    def run(self, payloads: list[bytes]) -> list[bytes]:
        h = self._h
        with pipeline_ledger().track("launch", sum(len(p) for p in payloads)):
            # SlotRow payloads hash their numpy row views directly —
            # hashlib takes any contiguous buffer, no bytes materialized
            return [h(_payload_ndarray(p) if type(p) is SlotRow else p).digest()
                    for p in payloads]

    def run_staged(self, slab: StagedSlab, rows: list[int]) -> list[bytes]:
        """Zero-copy form: hash the pre-staged rows in place."""
        h = self._h
        nb = int(slab.lengths[list(rows)].sum())
        with pipeline_ledger().track("launch", nb):
            return [h(slab.row(r)).digest() for r in rows]


_LADDER_FLOOR = 32  # rows: one uint8 tile deep on the chip, see _row_ladder


def _row_ladder(batch: int, granule: int = 1) -> tuple[int, ...]:
    """Ascending row counts a SHA-1 device plane launches at.

    Powers of two from ``_LADDER_FLOOR`` rows up, thinned (every second,
    third ... power) so that a plane holds at most five shapes, each
    rounded up to the mesh's ``granule``, and the plane's own ``batch``
    on top: 256 → (32, 64, 128, 256), 4096 → (32, 128, 512, 2048, 4096),
    a batch at or under the floor → itself alone. The floor is what the
    chip read (PERF.md, PR 25): a uint8 batch is tiled 32 rows deep, so
    a 16-row launch uploads in the same time as a 32-row one and its
    step is slower (6.3 against 5.8 ms at 256 KiB pieces).
    """
    from torrent_tpu.parallel.mesh import round_up_to_multiple

    doublings = max(0, -(-batch // _LADDER_FLOOR) - 1).bit_length()
    stride = max(1, -(-doublings // 4))
    rungs = {batch}
    r = _LADDER_FLOOR
    while r < batch:
        rungs.add(round_up_to_multiple(r, granule))
        r <<= stride
    return tuple(sorted(x for x in rungs if x <= batch))


class _Sha1DevicePlane:
    """SHA-1 device plane: one compiled TPUVerifier per bucket (the
    geometry-grouped compile cache the bulk/verify loops relied on).

    Stages into reusable per-plane :class:`_StagingSlots` instead of
    ``hash_pieces`` (which allocates + zeroes a fresh buffer every
    launch).

    The copying road (``run``) launches at the smallest rung of a fixed
    row ladder (:func:`_row_ladder`) that holds the chunk: a deadline
    flush of 14 pieces on a 256-row plane stages, uploads and hashes 32
    rows, not 256. The top rung is the plane's whole batch; on one
    device every rung takes the verifier's flat chunked upload
    (``flat_rows``), on a mesh ``upload_batch``'s sharded handle. Every
    rung is compiled and run once when the plane is built, so no later
    launch meets a shape for the first time.

    The jitted execution itself is serialized per plane
    (``_device_lock``): two worker threads entering the same compiled
    executable concurrently can deadlock inside the XLA runtime
    (observed as an intermittent pipelined-launch hang on XLA-CPU).
    Host staging — the copy + pad, the expensive host-side part — still
    overlaps across pipelined launches; only the device call is single-
    file, and the device serializes launches anyway."""

    def __init__(self, bucket: int, batch: int):
        from torrent_tpu.models.verifier import TPUVerifier

        v = self._verifier = TPUVerifier(piece_length=bucket, batch_size=batch)
        self.kernel = "pallas" if v.backend == "pallas" else "scan"
        self._slots = _StagingSlots(v.batch_size, bucket)
        self._device_lock = named_lock("sched.sha1_plane._device_lock")
        self._ladder = _row_ladder(v.batch_size, v.mesh.size)
        v.flat_rows.update(self._ladder)
        self._warm()

    def _warm(self) -> None:
        """Compile and run every rung once on an all-sentinel batch (the
        plane is built in a worker thread at the lane's first launch).
        A mesh the explicit upload cannot feed (multi-process) keeps the
        one fused full-batch launch, compiled at first use as before."""
        import numpy as np

        v = self._verifier
        slot = self._slots.checkout()
        try:
            padded = slot[0]
            if not v.upload_supported(padded):
                self._ladder = self._ladder[-1:]
                return
            for rung in self._ladder:
                handle = v.upload_batch(padded[:rung])
                np.asarray(v.digest_uploaded(handle, np.zeros(rung, dtype=np.int32)))
        finally:
            self._slots.checkin(slot)

    def _rung_for(self, n_rows: int) -> int:
        """Smallest rung holding ``n_rows`` (at most the plane's batch)."""
        return self._ladder[bisect.bisect_left(self._ladder, n_rows)]

    def launch_rows(self, n_rows: int) -> int:
        """Rows ``run`` stages, uploads and hashes for ``n_rows``
        payloads: whole batches, then the rest's rung. What the lane's
        pad-row accounting charges for the copying road."""
        b = self._ladder[-1]
        full, rest = divmod(n_rows, b)
        return full * b + (self._rung_for(rest) if rest else 0)

    @staticmethod
    def launch_geometry(n_rows: int, bucket: int) -> tuple[int, int]:
        """Any row count is a valid flush target (the ladder follows the
        chunk a launch holds, not the lane's target), so targets snap to
        themselves; staging charges the padded row width. The rows a
        launch really stages are :meth:`launch_rows`."""
        from torrent_tpu.ops.padding import padded_len_for

        return n_rows, n_rows * padded_len_for(bucket)

    def _launch_padded(self, padded, nblocks, nb: int):
        """One device launch with the real stage split: explicit upload
        (h2d, outside the device lock so batch N+1's transfer overlaps
        batch N's kernel), jitted dispatch under the lock (async — with
        a donated input buffer on real devices), blocking fetch (digest)
        back outside it. Falls back to the fused ``digest_batch`` when
        the flat upload path can't take this shape (multi-process mesh,
        odd geometry)."""
        import numpy as np

        led = pipeline_ledger()
        v = self._verifier
        if not v.upload_supported(padded):
            # fallback (multi-process mesh, odd geometry): digest_batch
            # opens its own stages — on a multi-process mesh the fused
            # `launch` that carries the moved bytes, never a zero-length
            # h2d span
            with self._device_lock:
                return v.digest_batch(padded, nblocks, nb)
        with led.track("h2d", nb, moved=padded.nbytes):
            handle = v.upload_batch(padded)
        with self._device_lock:
            with led.track("launch", nb):
                words_dev = v.digest_uploaded(handle, nblocks)
        with led.track("digest", nb):
            return np.asarray(words_dev)

    def run(self, payloads: list[bytes]) -> list[bytes]:
        from torrent_tpu.ops.padding import words_to_digests

        v = self._verifier
        b = v.batch_size
        if any(len(p) > v.piece_length for p in payloads):
            # same guard as the sha256 planes: a too-long piece would
            # fail mid-stage with the slot checked out
            raise ValueError("piece longer than plane piece_length")
        out: list[bytes] = []
        for start in range(0, len(payloads), b):
            chunk = payloads[start : start + b]
            nb = sum(len(p) for p in chunk)
            rung = self._rung_for(len(chunk))
            slot, padded, nblocks = self._slots.stage(chunk, rows=rung)
            try:
                words = self._launch_padded(padded[:rung], nblocks, nb)
                out.extend(words_to_digests(words[: len(chunk)]))
            finally:
                self._slots.checkin(slot)
        return out

    def run_staged(self, slab: StagedSlab, rows: list[int]) -> list[bytes]:
        """Zero-copy launch: the pre-staged slab IS the launch buffer —
        no ``_StagingSlots.stage`` copy, rows outside the ticket set are
        masked to ``nblocks=0`` so one static shape serves every fill
        level and bisection half."""
        from torrent_tpu.ops.padding import words_to_digests

        v = self._verifier
        if (
            slab.padded.shape[1] != v.padded_len
            or slab.rows_total > v.batch_size
            or slab.rows_total % max(1, v.mesh.size)
        ):
            # row width / mesh-divisibility mismatch: copy path. A row
            # count merely SMALLER than the verifier's (tile/mesh)
            # rounded batch is fine — upload_batch's sharded form takes
            # any mesh-divisible shape, so zero-copy launches survive
            # the batch rounding real accelerators apply.
            return self.run([SlotRow(slab, r) for r in rows])
        nb = int(slab.lengths[list(rows)].sum())
        words = self._launch_padded(
            slab.padded, _masked_nblocks(slab, rows), nb
        )
        return words_to_digests(words[rows])


class _Sha256DevicePlane:
    """SHA-256 (BEP 52) scan-backend plane — the fallback when the
    pallas kernel is unavailable (non-TPU device, ``scan`` selected, or
    a bucket whose tile floor would blow the lane staging budget)."""

    kernel = "scan"

    def __init__(self, bucket: int, batch: int):
        from torrent_tpu.ops.sha256_jax import make_sha256_fn

        self._fn = make_sha256_fn("jax")
        # donated variant for the launch: frees the device input buffer
        # as the kernel consumes it, so the next batch's H2D can reuse
        # that memory while this kernel runs (identity on CPU)
        self._fn_launch = _donating_wrapper(self._fn)
        self._bucket = bucket
        self._batch = batch
        self._slots = _StagingSlots(batch, bucket)
        # serialize the jitted call: concurrent entry from pipelined
        # worker threads can deadlock the XLA runtime (see sha1 plane)
        self._device_lock = named_lock("sched.sha256_scan_plane._device_lock")

    @staticmethod
    def launch_geometry(n_rows: int, bucket: int) -> tuple[int, int]:
        from torrent_tpu.ops.padding import padded_len_for

        return n_rows, n_rows * padded_len_for(bucket)

    def run(self, payloads: list[bytes]) -> list[bytes]:
        import jax.numpy as jnp
        import numpy as np

        from torrent_tpu.models.merkle import words32_to_digests

        if any(len(p) > self._bucket for p in payloads):
            raise ValueError("piece longer than plane piece_length")
        out: list[bytes] = []
        b = self._batch
        led = pipeline_ledger()
        for start in range(0, len(payloads), b):
            chunk = payloads[start : start + b]
            nb = sum(len(p) for p in chunk)
            slot, padded, nblocks = self._slots.stage(chunk)
            try:
                # ledger stage boundaries: the explicit device put (h2d,
                # outside the device lock so transfers overlap kernels),
                # the jitted dispatch (launch — async, donated input),
                # D2H fetch (digest). Bytes are payload bytes throughout
                # so cross-stage rates compare (the physical transfer
                # moves the padded footprint).
                with led.track("h2d", nb, moved=padded.nbytes):
                    dev_p = jnp.asarray(padded)
                    dev_n = jnp.asarray(nblocks)
                with self._device_lock:
                    with led.track("launch", nb):
                        words_dev = self._fn_launch(dev_p, dev_n)
                with led.track("digest", nb):
                    words = np.asarray(words_dev)
                out.extend(words32_to_digests(words[: len(chunk)]))
            finally:
                self._slots.checkin(slot)
        return out

    def run_staged(self, slab: StagedSlab, rows: list[int]) -> list[bytes]:
        """Zero-copy launch from a pre-staged slab (no ``stage`` copy;
        non-ticket rows masked to sentinels, static full-slab shape)."""
        import jax.numpy as jnp
        import numpy as np

        from torrent_tpu.models.merkle import words32_to_digests

        led = pipeline_ledger()
        nb = int(slab.lengths[list(rows)].sum())
        with led.track("h2d", nb, moved=slab.padded.nbytes):
            dev_p = jnp.asarray(slab.padded)
            dev_n = jnp.asarray(_masked_nblocks(slab, rows))
        with self._device_lock:
            with led.track("launch", nb):
                words_dev = self._fn_launch(dev_p, dev_n)
        with led.track("digest", nb):
            words = np.asarray(words_dev)
        return words32_to_digests(words[rows])


class _Sha256PallasPlane:
    """SHA-256 (BEP 52) pallas plane — the v2 fast path.

    The hand-tiled kernel (``ops/sha256_pallas.py``) wants tile-shaped
    batches; the old scan-only scheduler avoided it because every launch
    padded to the configured tile (default 32×128 = 4096 rows). This
    plane makes sub-tile launches cheap instead:

    * **Row-bucketed padding**: a live batch rounds up to the nearest
      ``SUB_TILE_ROWS`` (8×128 = 1024) multiple, and ``tile_sub_for_rows``
      picks the largest legal sublane count that tiles the bucketed row
      count — full-target launches keep the sweep-tuned TILE_SUB,
      partial flushes drop to smaller tiles instead of padding 4×.
    * **Sentinel rows** carry ``nblocks=0``; their chains never run and
      their stale staging contents are masked off (same contract as the
      scan plane).
    * **Reusable staging slots** (:class:`_StagingSlots`) sized to the
      lane target, with per-row stale-tail zeroing — no per-launch
      memset. The u32 view of the slot feeds the kernel's fast path
      (a u8→u32 bitcast on device lowers through a 4×-widened fusion).
    * **Per-plane launch-plan cache**: the (padded_rows → tile_sub,
      interleave2) decision is memoized per geometry; jax.jit then keys
      the compiled executable on the same statics, so a lane serves any
      fill level from a handful of executables.

    interleave2 needs ≥16 sublanes with whole-vreg halves, so 1024-row
    sub-tile launches silently run the straight kernel even when the
    knob is on (correctness is identical; the knob is a scheduling hint).
    """

    kernel = "pallas"

    def __init__(self, bucket: int, batch: int, interpret: bool | None = None):
        from torrent_tpu.ops import sha256_pallas as sp

        self._sp = sp
        self._bucket = bucket
        # slots (and the max launch) are sized to the tile-bucketed
        # target, so a lane target that is already a tile multiple
        # wastes zero pad rows at full fill
        self._batch = sp.pad_rows_for(batch)
        self._interpret = interpret
        self._slots = _StagingSlots(self._batch, bucket)
        self._plans: dict[int, tuple[int, int, bool]] = {}  # n -> (rows, ts, il2)
        # donated launch callables per (tile_sub, interleave2) — built on
        # first use from the worker thread (jax backend probe included)
        self._launch_fns: dict[tuple[int, bool], Callable] = {}
        self._device_lock = named_lock("sched.sha256_pallas_plane._device_lock")

    @staticmethod
    def launch_geometry(n_rows: int, bucket: int) -> tuple[int, int]:
        """Tile-bucketed rows; staging charges the padded footprint
        including sentinel rows."""
        from torrent_tpu.ops.padding import padded_len_for
        from torrent_tpu.ops.sha256_pallas import pad_rows_for

        rows = pad_rows_for(n_rows)
        return rows, rows * padded_len_for(bucket)

    def _plan(self, n: int) -> tuple[int, int, bool]:
        plan = self._plans.get(n)
        if plan is None:
            sp = self._sp
            rows = min(sp.pad_rows_for(n), self._batch)
            ts = sp.tile_sub_for_rows(rows)
            il2 = sp.INTERLEAVE2 and ts >= 16 and not (ts // 2) % 8
            plan = self._plans[n] = (rows, ts, il2)
        return plan

    def _launch_fn(self, ts: int, il2: bool):
        """Kernel callable for a tiling, input-donated off-CPU (the
        double-buffer memory contract; see :func:`_donating_wrapper`)."""
        fn = self._launch_fns.get((ts, il2))
        if fn is None:
            sp, interp = self._sp, self._interpret

            def base(data32, nblocks, _ts=ts, _il2=il2):
                return sp.sha256_pieces_pallas(
                    data32, nblocks, interpret=interp, tile_sub=_ts,
                    interleave2=_il2,
                )

            fn = self._launch_fns[(ts, il2)] = _donating_wrapper(base)
        return fn

    def run(self, payloads: list[bytes]) -> list[bytes]:
        import jax.numpy as jnp
        import numpy as np

        from torrent_tpu.models.merkle import words32_to_digests

        if any(len(p) > self._bucket for p in payloads):
            raise ValueError("piece longer than plane piece_length")
        out: list[bytes] = []
        b = self._batch
        led = pipeline_ledger()
        for start in range(0, len(payloads), b):
            chunk = payloads[start : start + b]
            nb = sum(len(p) for p in chunk)
            rows, ts, il2 = self._plan(len(chunk))
            slot, padded, nblocks = self._slots.stage(chunk, rows)
            try:
                # slice to the bucketed row count, reinterpret as the
                # kernel's u32 fast path (rows are 128-byte aligned so
                # the view is free and the slab contiguous)
                data32 = padded[:rows].view(np.uint32)
                # same ledger boundaries as the scan plane: explicit put
                # = h2d (outside the device lock so transfers overlap
                # kernels), jitted dispatch = launch (async, donated
                # input), fetch = digest
                with led.track("h2d", nb, moved=data32.nbytes):
                    dev_d = jnp.asarray(data32)
                    dev_n = jnp.asarray(nblocks)
                with self._device_lock:
                    with led.track("launch", nb):
                        words_dev = self._launch_fn(ts, il2)(dev_d, dev_n)
                with led.track("digest", nb):
                    words = np.asarray(words_dev)
                out.extend(words32_to_digests(words[: len(chunk)]))
            finally:
                self._slots.checkin(slot)
        return out

    def run_staged(self, slab: StagedSlab, rows: list[int]) -> list[bytes]:
        """Zero-copy launch from a pre-staged slab: tile-bucket the full
        slab row count, mask non-ticket rows to sentinels, feed the u32
        view of the slab directly — no ``stage`` copy."""
        import jax.numpy as jnp
        import numpy as np

        from torrent_tpu.models.merkle import words32_to_digests

        led = pipeline_ledger()
        launch_rows, ts, il2 = self._plan(slab.rows_total)
        if launch_rows > slab.rows_total or any(r >= launch_rows for r in rows):
            # pool slab smaller than the tile granule (or bigger than
            # the plane's max launch): copy path
            return self.run([SlotRow(slab, r) for r in rows])
        nb = int(slab.lengths[list(rows)].sum())
        nblocks = _masked_nblocks(slab, rows)[:launch_rows]
        data32 = slab.padded[:launch_rows].view(np.uint32)
        with led.track("h2d", nb, moved=data32.nbytes):
            dev_d = jnp.asarray(data32)
            dev_n = jnp.asarray(nblocks)
        with self._device_lock:
            with led.track("launch", nb):
                words_dev = self._launch_fn(ts, il2)(dev_d, dev_n)
        with led.track("digest", nb):
            words = np.asarray(words_dev)
        return words32_to_digests(words[rows])


# ------------------------------------------------------------ scheduler


class HashPlaneScheduler:
    """The shared verify queue. One instance serves every consumer of a
    process's hash plane; see the module docstring for the data flow."""

    def __init__(self, config: SchedulerConfig | None = None, hasher: str = "tpu"):
        self.config = config or SchedulerConfig()
        self.hasher = hasher
        self._tenants: dict[str, _Tenant] = {}
        self._lanes: dict[tuple[str, int], _Lane] = {}
        self._queued_bytes = 0  # queued + in-flight payload bytes
        self._closing = False
        self._space = asyncio.Event()  # pulsed on every byte release
        # metrics
        self._launches = 0
        self._fill_sum = 0.0
        self._flush_reasons = {"full": 0, "deadline": 0, "hint": 0, "shutdown": 0}
        self._shed_total = 0
        # fault-tolerance counters (satellite observability: exported
        # via metrics_snapshot -> render_sched_metrics -> /metrics)
        self._launch_failures = 0
        self._retries = 0
        self._bisections = 0
        self._cpu_fallback_launches = 0
        # the only fault counter touched off the event loop (worker
        # threads, possibly in different lanes) — needs its own lock
        self._counter_lock = named_lock("sched._counter_lock")
        self._counter_cells = guard_attrs("sched.scheduler", "fault_counters")
        self._failed_pieces = 0  # tickets that exhausted retry+bisection
        # rollup of evicted auto-registered tenants so served/shed totals
        # stay monotonic after their per-tenant series disappear
        self._evicted = {"tenants": 0, "served_bytes": 0, "served_pieces": 0, "shed": 0}
        # zero-copy ingest: reader-side staging pools per (algo, bucket)
        # — disk reads land directly in these slots and slot-carrying
        # submissions hand them to the planes without a stage copy.
        # Checked out from worker threads (read paths run off-loop).
        self._ingest_pools: dict[tuple[str, int], _StagingSlots] = {}
        self._ingest_lock = named_lock("sched._ingest_lock")
        # resolved-once sha256 backend ('pallas'/'scan'); auto-resolution
        # touches jax.devices(), which must stay off the event loop
        self._sha256_backend_resolved: str | None = None
        # autopilot actuator (sched/control.py): fraction of the
        # configured global admission budget currently admitted. 1.0 =
        # the static config exactly (the comparison short-circuits, so
        # controller-off behavior is bit-identical)
        self._admission_factor = 1.0

    # ------------------------------------------------------------ admin

    async def start(self) -> "HashPlaneScheduler":
        """Bind to the running loop (lanes spawn lazily on first use).

        Pre-resolves the sha256 backend in a worker thread: 'auto'
        probes ``jax.devices()``, which initializes the backend (seconds
        on a TPU) — that wait must never land on the serving loop
        (``chunk_for`` / enqueue call :meth:`_lane_plan` inline).
        """
        if self.hasher != "cpu" and self._sha256_backend_resolved is None:
            self._sha256_backend_resolved = await asyncio.to_thread(
                resolve_sha256_backend, self.config.sha256_backend
            )
        return self

    async def close(self) -> None:
        """Flush every pending item (reason 'shutdown') and stop lanes."""
        self._closing = True
        for lane in self._lanes.values():
            lane.event.set()
        self._space.set()
        for lane in list(self._lanes.values()):
            if lane.task is not None:
                await lane.task
            if lane.inflight:
                await asyncio.gather(*lane.inflight, return_exceptions=True)

    def register_tenant(
        self, name: str, weight: float = 1.0, max_bytes: int | None = None
    ) -> None:
        """Declare a tenant's scheduling weight / byte bound (idempotent;
        unseen tenants are auto-registered at weight 1.0 on first use)."""
        if weight <= 0:
            raise ValueError("tenant weight must be positive")
        t = self._tenants.get(name)
        if t is None:
            t = self._tenants[name] = _Tenant(name, weight, max_bytes)
        else:
            t.weight = weight
            if max_bytes is not None:
                t.max_bytes = max_bytes
        t.pinned = True

    # ---------------------------------------------------------- helpers

    @staticmethod
    def bucket_for(piece_length: int) -> int:
        """Pow-2 piece-length bucket (shared executable per bucket)."""
        return 1 << (piece_length - 1).bit_length() if piece_length > 1 else 1

    def sha256_backend(self) -> str:
        """The resolved v2 backend ('pallas'/'scan'), memoized. start()
        pre-warms this in a worker thread — 'auto' probes
        ``jax.devices()``, whose backend init must not run on the
        serving loop. An unstarted scheduler (tests, direct use)
        resolves inline on first need."""
        backend = self._sha256_backend_resolved
        if backend is None:
            backend = self._sha256_backend_resolved = resolve_sha256_backend(
                self.config.sha256_backend
            )
        return backend

    def _lane_plan(self, algo: str, bucket: int) -> tuple[str, int]:
        """(backend, flush target) for a lane — plane-aware batching.

        The base target is ``min(batch_target, staging_budget /
        padded_len)`` — big-piece buckets shrink the launch so staging
        stays bounded (the bridge's old private-buffer rule). Pallas
        sha256 lanes then snap the target to a tile multiple: UP to the
        next ``SUB_TILE_ROWS`` granule (a full launch wastes zero pad
        rows) but never past what the staging budget affords; a bucket
        whose single-tile floor already exceeds the budget falls back to
        the scan backend instead of overrunning it.
        """
        from torrent_tpu.ops.padding import padded_len_for

        cfg = self.config
        afford = max(1, cfg.staging_budget // padded_len_for(bucket))
        base = max(1, min(cfg.batch_target, afford))
        if algo != "sha256" or self.hasher == "cpu":
            return ("cpu" if self.hasher == "cpu" else "device"), base
        backend = self.sha256_backend()
        if backend == "pallas":
            from torrent_tpu.ops.sha256_pallas import (
                SUB_TILE_ROWS,
                TILE_LANE,
                TILE_SUB,
                pad_rows_for,
                tile_sub_for_rows,
            )

            if afford >= SUB_TILE_ROWS:
                target = min(
                    pad_rows_for(base), afford // SUB_TILE_ROWS * SUB_TILE_ROWS
                )
                # prefer the sweep-tuned tiling: a row count whose ONLY
                # legal tiling is the minimal tile_sub=8 (e.g. 5120 rows)
                # rounds down to a full configured-tile multiple (4096 →
                # tile_sub 32) — a slightly smaller launch on the fast
                # tiling beats a bigger one on the slow tiling. Targets
                # that tile at 16/24 sublanes stand: a user-configured
                # batch_target must not silently shrink over a mild
                # tiling preference.
                full_tile = TILE_SUB * TILE_LANE
                alt = target // full_tile * full_tile
                if alt and tile_sub_for_rows(target) == 8 < TILE_SUB:
                    target = alt
                return "pallas", target
            backend = "scan"  # tile floor would blow the staging budget
        return backend, base

    def checkout_staging(
        self, piece_length: int, n_rows: int, algo: str = "sha1"
    ) -> StagedSlab | None:
        """Check a staging slab out for the zero-copy ingest path.

        The read path (``parallel/verify.read_pieces_into``) fills the
        slab's row-strided view directly from disk, pads it in place,
        and submits it via :meth:`enqueue_staged` — no per-piece
        ``bytes``, no ``_StagingSlots.stage`` copy. Returns ``None``
        when this geometry can't take pre-staged submissions (chunk
        bigger than the lane's slab, scheduler closing) — callers then
        fall back to the ``read_pieces_chunk`` byte path. Safe to call
        from worker threads (read loops run off the event loop).

        The caller owns one reference; every path must end in
        ``slab.release()`` (directly, or via ``enqueue_staged``'s
        per-ticket refs resolving through demux).
        """
        if self._closing or algo not in DIGEST_LEN:
            return None
        bucket = self.bucket_for(piece_length)
        key = (algo, bucket)
        with self._ingest_lock:
            pool = self._ingest_pools.get(key)
        if pool is None:
            _, target = self._lane_plan(algo, bucket)
            with self._ingest_lock:
                pool = self._ingest_pools.setdefault(
                    key, _StagingSlots(target, bucket)
                )
        if n_rows > pool.rows:
            return None
        return StagedSlab(pool, pool.checkout(), algo, bucket, piece_length)

    def chunk_for(self, piece_length: int, algo: str = "sha1") -> int:
        """Effective batch target for this geometry — the lane flush
        size (plane-aware: pallas sha256 lanes snap to tile multiples).
        Stream ingests use it as their submission chunk so one
        submission maps to roughly one launch."""
        return self._lane_plan(algo, self.bucket_for(piece_length))[1]

    # -------------------------------------------- autopilot actuators
    # (sched/control.py — every setter is a no-op-able, bounded seam;
    # with no autopilot attached none of these ever runs and behavior
    # is bit-identical to the static config)

    def _lane_by_key(self, lane_key: str) -> _Lane | None:
        algo, _, bucket = lane_key.rpartition("/")
        try:
            return self._lanes.get((algo, int(bucket)))
        except ValueError:
            return None

    def set_lane_target(self, lane_key: str, target: int) -> int | None:
        """Set a lane's flush target (autopilot batch actuator).

        The applied value is clamped to the staging budget and snapped
        to what the built plane actually stages via its
        ``launch_geometry`` hook — a pallas lane's adapted target is
        always a tile multiple. Returns the applied target (None for an
        unknown lane)."""
        lane = self._lane_by_key(lane_key)
        if lane is None:
            return None
        target = max(1, int(target))
        afford = None
        if self.hasher != "cpu":
            from torrent_tpu.ops.padding import padded_len_for

            afford = max(1, self.config.staging_budget // padded_len_for(lane.bucket))
            target = min(target, afford)
        hook = (
            getattr(lane.plane, "launch_geometry", None)
            if lane.plane is not None
            else None
        )
        if hook is not None:
            rows = int(hook(target, lane.bucket)[0])
            if afford is not None and rows > afford:
                # the hook snaps UP (pallas tile granule); a snap past
                # the staging afford must round DOWN to the largest
                # granule multiple instead — same discipline as the
                # lane plan's `afford // SUB_TILE_ROWS * SUB_TILE_ROWS`.
                # When even one granule doesn't fit, the budget beats
                # the tiling and the raw afford stands.
                granule = max(1, int(hook(1, lane.bucket)[0]))
                rows = afford // granule * granule
                if rows < 1:
                    rows = afford
            if rows >= 1:
                target = rows
        elif lane.backend == "pallas":
            from torrent_tpu.ops.sha256_pallas import pad_rows_for

            rows = max(1, pad_rows_for(target))
            if afford is None or rows <= afford:
                target = rows
        lane.target = target
        lane.event.set()  # re-evaluate the flush condition now
        return lane.target

    def set_lane_deadline(self, lane_key: str, seconds: float) -> float | None:
        """Per-lane flush-deadline override (autopilot). Returns the
        applied value (None for an unknown lane)."""
        lane = self._lane_by_key(lane_key)
        if lane is None:
            return None
        lane.deadline = max(0.001, float(seconds))
        lane.event.set()
        return lane.deadline

    def set_admission_factor(self, factor: float) -> float:
        """Scale the global admission budget (autopilot). 1.0 restores
        the static config exactly; raising the factor wakes blocked
        submitters."""
        factor = min(1.0, max(0.01, float(factor)))
        raised = factor > self._admission_factor
        self._admission_factor = factor
        if raised:
            self._space.set()
        return factor

    def steer_lane_backend(self, lane_key: str, backend: str) -> str | None:
        """Steer a lane to another backend (autopilot). The plane is
        rebuilt lazily on the next launch; an in-flight launch finishes
        on the old plane (planes are stateless). Returns the new
        backend, or None when unknown lane / already there."""
        if backend not in ("cpu", "device", "scan", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        lane = self._lane_by_key(lane_key)
        if lane is None or lane.backend == backend:
            return None
        log.info(
            "steering lane %s backend %s -> %s", lane_key, lane.backend, backend
        )
        lane.backend = backend
        lane.plane = None  # next _run_plane rebuilds under build_lock
        return backend

    def control_surface(self) -> dict:
        """Per-lane + admission view the autopilot decides over (pure
        reads; the controller deltas launches/fill_sum itself)."""
        from torrent_tpu.ops.padding import padded_len_for

        cfg = self.config
        lanes: dict[str, dict] = {}
        for (algo, bucket) in sorted(self._lanes):
            lane = self._lanes[(algo, bucket)]
            if self.hasher == "cpu":
                # hashlib stages nothing: growth is bounded only by the
                # controller's own target_max_factor law
                afford = max(lane.target, cfg.batch_target) * 64
            else:
                afford = max(1, cfg.staging_budget // padded_len_for(bucket))
            # launch granule (1 = row-exact): the controller snaps its
            # grow cap to this so it never proposes a target the
            # set_lane_target snap would round back down forever
            hook = (
                getattr(lane.plane, "launch_geometry", None)
                if lane.plane is not None
                else None
            )
            if hook is not None:
                granule = max(1, int(hook(1, bucket)[0]))
            elif lane.backend == "pallas":
                from torrent_tpu.ops.sha256_pallas import SUB_TILE_ROWS

                granule = SUB_TILE_ROWS
            else:
                granule = 1
            lanes[f"{algo}/{bucket}"] = {
                "algo": algo,
                "bucket": bucket,
                "granule": granule,
                "target": lane.target,
                "base_target": self._lane_plan(algo, bucket)[1],
                "afford": afford,
                "deadline": (
                    lane.deadline if lane.deadline is not None else cfg.flush_deadline
                ),
                "base_deadline": cfg.flush_deadline,
                "backend": lane.backend,
                "launches": lane.launches,
                "fill_sum": lane.fill_sum,
                "pending": lane.pending_pieces,
            }
        return {
            "lanes": lanes,
            "admission": {
                "factor": self._admission_factor,
                "max_queue_bytes": cfg.max_queue_bytes,
                "queue_bytes": self._queued_bytes,
            },
        }

    def _lane(self, algo: str, piece_length: int) -> _Lane:
        bucket = self.bucket_for(piece_length)
        key = (algo, bucket)
        lane = self._lanes.get(key)
        if lane is None:
            backend, target = self._lane_plan(algo, bucket)
            lane = _Lane(
                algo,
                bucket,
                target,
                self.config.pipeline_depth,
                _LaneBreaker(
                    self.config.breaker_threshold, self.config.breaker_cooldown
                ),
                backend=backend,
            )
            self._lanes[key] = lane
            lane.task = asyncio.ensure_future(self._lane_loop(lane))
        return lane

    def _tenant(self, name: str) -> _Tenant:
        t = self._tenants.get(name)
        if t is None:
            t = _Tenant(name)
            self._tenants[name] = t
            if len(self._tenants) > self.config.max_idle_tenants:
                self._prune_tenants()
        return t

    def _prune_tenants(self) -> None:
        """Evict idle auto-registered tenants once past the cardinality
        bound — an attacker sending a fresh X-Tenant per request must not
        grow per-tenant state, /metrics series, or the DRR rotation
        without limit. Pinned (register_tenant) tenants are kept."""
        excess = len(self._tenants) - self.config.max_idle_tenants
        for name, t in list(self._tenants.items()):
            if excess <= 0:
                return
            if t.pinned or t.queued_bytes:
                continue
            # queued_bytes misses zero-length payloads, so check queues too
            if any(lane.queues.get(name) for lane in self._lanes.values()):
                continue
            del self._tenants[name]
            for lane in self._lanes.values():
                if lane.queues.pop(name, None) is not None:
                    lane.rotation.remove(name)
            self._evicted["tenants"] += 1
            self._evicted["served_bytes"] += t.served_bytes
            self._evicted["served_pieces"] += t.served_pieces
            self._evicted["shed"] += t.shed
            excess -= 1

    # ------------------------------------------------------------ submit

    async def enqueue(
        self,
        tenant: str,
        pieces: list[bytes],
        expected: list[bytes] | None = None,
        algo: str = "sha1",
        piece_length: int | None = None,
        wait: bool = False,
        flush: bool = False,
    ) -> asyncio.Future:
        """Queue one submission; returns a future resolving to its
        results (digest list, or ok-bytes when ``expected`` is given).

        ``wait=False`` sheds with :class:`SchedRejected` when admission
        control is over budget (the bridge's 429); ``wait=True`` blocks
        until space frees — the backpressure path for streaming ingest.

        ``flush=True`` is the submitter's word that it sends nothing
        more until this submission resolves (the fabric executor's last
        chunk of a unit, after which it only drains): its lane stops
        waiting for fill and takes at once, whatever else is queued
        there riding along — the take the flush deadline would have
        made, counted under the flush reason ``hint`` when it is under
        target (``full`` otherwise). It is a statement about the
        caller's own next step, not a setting: everyone else's
        submissions still wait for fill or ``flush_deadline``.
        """
        sub = await self._enqueue_sub(
            tenant, pieces, expected, algo, piece_length, wait, flush
        )
        return sub.future

    async def _enqueue_sub(
        self, tenant, pieces, expected, algo, piece_length, wait, flush
    ) -> _Submission:
        """:meth:`enqueue`'s body; the submission itself is what
        :meth:`submit` needs back (its ``t_resolved``, its trace)."""
        if algo not in DIGEST_LEN:
            raise ValueError(f"unknown algo {algo!r}")
        mode = "digest" if expected is None else "verify"
        if expected is not None and len(expected) != len(pieces):
            raise ValueError("expected list must match pieces")
        loop = asyncio.get_running_loop()
        sub = _Submission(len(pieces), mode, loop, flush)
        if not pieces:
            sub.future.set_result(b"" if mode == "verify" else [])
            return sub
        # span context captured HERE (the caller's task still holds it);
        # everything downstream runs in lane tasks / worker threads
        ctx = tracer().current_context()
        t_enq = time.monotonic()
        ts = self._tenant(tenant)
        plen = piece_length if piece_length else max(len(p) for p in pieces)
        bucket = self.bucket_for(plen)
        if any(len(p) > bucket for p in pieces):
            raise ValueError("piece exceeds submission piece_length")
        # Admission charges what a device launch actually stages — the
        # padded row footprint (lane-aligned padded_len per piece), not
        # the raw payload bytes; a 1-byte piece in a 16 MiB bucket still
        # pins a 16 MiB staging row. The CPU plane stages nothing, so it
        # keeps raw-byte accounting.
        if self.hasher == "cpu":
            row_cost = 0
            charged = sum(len(p) for p in pieces)
        else:
            from torrent_tpu.ops.padding import padded_len_for

            row_cost = padded_len_for(bucket)
            charged = len(pieces) * row_cost
        try:
            await self._admit(ts, charged, wait)
        except SchedRejected as e:
            if ctx is not None:
                tracer().add_span(
                    ctx[0], "sched.shed", parent_id=ctx[1], t0=t_enq,
                    status="error", tenant=tenant, reason=e.reason,
                    queued_bytes=e.queued_bytes, limit_bytes=e.limit_bytes,
                )
            raise
        t_admitted = time.monotonic()
        lane = self._lane(algo, plen)
        q = lane.queues.get(tenant)
        if q is None:
            q = lane.queues[tenant] = deque()
            lane.rotation.append(tenant)
        now = time.monotonic()
        for i, p in enumerate(pieces):
            q.append(
                _Ticket(
                    sub, i, p, expected[i] if expected else None, tenant, now,
                    charged=row_cost or len(p),
                )
            )
        lane.pending_pieces += len(pieces)
        if flush:
            lane.flush_pending += len(pieces)
        ts.queued_bytes += charged
        self._queued_bytes += charged
        lane.event.set()
        if ctx is not None:
            t_queued = time.monotonic()
            enq_id = tracer().add_span(
                ctx[0], "sched.enqueue", parent_id=ctx[1], t0=t_enq,
                t1=t_queued, tenant=tenant, algo=algo, mode=mode,
                pieces=len(pieces), charged_bytes=charged,
                lane=f"{algo}/{bucket}",
            )
            tracer().add_span(
                ctx[0], "sched.admission", parent_id=enq_id, t0=t_enq,
                t1=t_admitted, tenant=tenant, wait=wait,
            )
            # later stages (lane wait, launch, digest) hang off the
            # enqueue span — carried by the submission, not contextvars
            sub.trace = (ctx[0], enq_id)
        return sub

    async def enqueue_staged(
        self,
        tenant: str,
        slab: StagedSlab,
        rows: list[int],
        expected: list[bytes] | None = None,
        wait: bool = False,
        flush: bool = False,
    ) -> asyncio.Future:
        """Slot-carrying submission: queue the pre-staged ``rows`` of a
        :class:`StagedSlab` (from :meth:`checkout_staging`); ``wait``
        and ``flush`` as for :meth:`enqueue`.

        Tickets carry :class:`SlotRow` payloads — zero-copy views into
        the slab — and each holds one slab reference that the demux
        releases on verdict or failure, so the slot returns to its pool
        exactly when the last co-batched ticket resolves. Admission
        charging, DRR fairness, shed, retry/bisection and the breaker's
        CPU fallback all behave exactly as for byte submissions (the
        CPU plane hashes the slab rows in place). On shed/validation
        failure the retained ticket refs are released here; the
        CALLER's own reference is untouched either way.
        """
        payloads = [SlotRow(slab, r) for r in rows]
        slab.retain(len(payloads))  # one ref per ticket, released at demux
        try:
            return await self.enqueue(
                tenant,
                payloads,
                expected=expected,
                algo=slab.algo,
                piece_length=slab.piece_length,
                wait=wait,
                flush=flush,
            )
        except BaseException:
            slab.release(len(payloads))
            raise

    async def submit(self, tenant: str, pieces, expected=None, algo="sha1",
                     piece_length=None, wait: bool = False):
        """``enqueue`` + await: returns digests (or ok-bytes) directly.

        The one caller that can say how late the loop woke it: the
        submission stamps the instant the demux resolved it, and the
        time from there to this coroutine running again is the ledger
        wait ``verdict_wake`` (recorded after the fact: no span) and the
        span ``sched.wake`` of a traced request. Callers of
        :meth:`enqueue` await the future themselves and record nothing.
        """
        sub = await self._enqueue_sub(
            tenant, pieces, expected, algo, piece_length, wait, False
        )
        try:
            return await sub.future
        finally:
            if sub.t_resolved is not None:
                t_woke = time.monotonic()
                pipeline_ledger().record(
                    "verdict_wake", 0, t_woke - sub.t_resolved, wait=True
                )
                if sub.trace is not None:
                    tracer().add_span(
                        sub.trace[0], "sched.wake", parent_id=sub.trace[1],
                        t0=sub.t_resolved, t1=t_woke,
                    )

    async def _admit(self, ts: _Tenant, nbytes: int, wait: bool) -> None:
        cfg = self.config
        tenant_limit = ts.max_bytes if ts.max_bytes is not None else cfg.max_tenant_bytes

        def max_queue() -> int:
            # the autopilot's admission actuator scales the GLOBAL budget
            # only (per-tenant limits are policy, not control); at the 1.0
            # default this is exactly the static config. Re-read on every
            # evaluation: a submitter blocked under a shrunken budget must
            # observe the recovered factor when set_admission_factor wakes
            # it, not a bound baked in at entry.
            factor = self._admission_factor
            if factor < 1.0:
                return max(1, int(cfg.max_queue_bytes * factor))
            return cfg.max_queue_bytes

        def over() -> tuple[bool, int, int]:
            # The empty-queue escape exists ONLY for the blocking path: an
            # oversize submission that can never fit must be admitted once
            # the queue drains or wait=True livelocks forever. On the shed
            # path it would let one giant submission blow past both bounds
            # into an idle queue and then 429 everyone else while it drains.
            limit = max_queue()
            if self._queued_bytes + nbytes > limit and not (
                wait and self._queued_bytes == 0
            ):
                return True, self._queued_bytes, limit
            if ts.queued_bytes + nbytes > tenant_limit and not (
                wait and ts.queued_bytes == 0
            ):
                return True, ts.queued_bytes, tenant_limit
            return False, 0, 0

        while True:
            if self._closing:
                ts.shed += 1
                self._shed_total += 1
                raise SchedRejected("scheduler shutting down", ts.name)
            is_over, got, limit = over()
            if not is_over:
                return
            if not wait:
                ts.shed += 1
                self._shed_total += 1
                raise SchedRejected("queue full", ts.name, got, limit)
            # blocking backpressure: wait for the next byte release.
            # clear-then-recheck so a release between over() and wait()
            # can't be lost.
            self._space.clear()
            is_over, _, _ = over()
            if not is_over:
                return
            await self._space.wait()

    # --------------------------------------------------------- assembler

    async def _lane_loop(self, lane: _Lane) -> None:
        """One coroutine a lane, so the waits it records (``lane_idle``:
        nothing was asked of the device; ``deadline_wait``; ``sem_wait``)
        never overlap within a lane. Each is a ledger wait and a host
        span held across its ``await``."""
        cfg = self.config
        led = pipeline_ledger()
        # a lane that never had to wait for fill reads 0 s of it
        led.declare_wait("deadline_wait")
        while True:
            if lane.pending_pieces == 0:
                if self._closing:
                    return
                lane.event.clear()
                if lane.pending_pieces == 0 and not self._closing:
                    with led.track("lane_idle", wait=True):
                        await lane.event.wait()
                continue
            # oldest queued item bounds the wait: flush at target fill
            # or when its deadline expires, whichever comes first (the
            # autopilot may have set a per-lane deadline override); a
            # queued flush=True ticket ends the wait before it starts:
            # its submitter is parked on it and fills nothing
            flush_after = (
                lane.deadline if lane.deadline is not None else cfg.flush_deadline
            )
            deadline = lane.oldest_ts() + flush_after
            while (
                lane.pending_pieces < lane.target
                and not lane.flush_pending
                and not self._closing
            ):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                lane.event.clear()
                if (
                    lane.pending_pieces >= lane.target
                    or lane.flush_pending
                    or self._closing
                ):
                    break
                try:
                    with led.track("deadline_wait", wait=True):
                        await asyncio.wait_for(lane.event.wait(), remaining)
                except asyncio.TimeoutError:
                    break
            hinted = lane.flush_pending > 0  # before the take spends it
            with led.track("assemble") as assembling:
                tickets = self._drr_take(lane)
                assembling.add(sum(t.nbytes for t in tickets))
            if not tickets:
                continue
            if len(tickets) >= lane.target:
                reason = "full"
            elif self._closing:
                reason = "shutdown"
            else:
                reason = "hint" if hinted else "deadline"
            # pipelined launch: the semaphore bounds in-flight launches
            # (depth 2 = double-buffer) while this loop keeps assembling
            # the next batch during the device run — the host/device
            # overlap the old stream gate had
            with led.track("sem_wait", wait=True):
                await lane.sem.acquire()
            task = asyncio.ensure_future(self._launch(lane, tickets, reason))
            lane.inflight.add(task)
            task.add_done_callback(lambda t, lane=lane: self._launch_done(lane, t))

    def _launch_done(self, lane: _Lane, task: asyncio.Task) -> None:
        lane.inflight.discard(task)
        lane.sem.release()
        if not task.cancelled() and task.exception() is not None:
            # _launch resolves caller futures on every path, so an escape
            # here is a bug — log it rather than dropping it silently
            log.error("sched launch task error: %r", task.exception())

    def _drr_take(self, lane: _Lane) -> list[_Ticket]:
        """Deficit round-robin over queued bytes, up to the lane target."""
        cfg = self.config
        taken: list[_Ticket] = []
        target = lane.target
        while len(taken) < target:
            active = [n for n in lane.rotation if lane.queues.get(n)]
            if not active:
                break
            for name in active:
                q = lane.queues[name]
                t = self._tenants[name]
                t.deficit += max(1, int(cfg.drr_quantum * t.weight))
                while q and len(taken) < target and t.deficit >= q[0].nbytes:
                    tkt = q.popleft()
                    t.deficit -= tkt.nbytes
                    lane.pending_pieces -= 1
                    if tkt.sub.flush:
                        lane.flush_pending -= 1
                    taken.append(tkt)
                if not q:
                    t.deficit = 0  # classic DRR: no credit hoarding
                if len(taken) >= target:
                    break
        # rotate so the same tenant doesn't always lead the next pass
        if lane.rotation:
            lane.rotation.append(lane.rotation.pop(0))
        return taken

    # ------------------------------------------------------------ launch

    def _build_plane(self, lane: _Lane):
        cfg = self.config
        if lane.backend == "cpu" and self.hasher != "cpu":
            # controller-steered degradation (steer_lane_backend): like
            # the breaker's CPU fallback, this bypasses plane_factory —
            # hashlib is the parity floor, not a wrappable device plane
            return _CpuPlane(lane.algo)
        # the lane's planned backend is authoritative (it already folded
        # in the staging-budget fallback), so pass it explicitly rather
        # than re-resolving env/auto at build time — a factory holding
        # its own 'pallas' pin (bridge --fault-plan + --sha256-backend)
        # must not override a budget-forced scan fallback, or the tile
        # floor blows the staging budget the fallback exists to enforce
        sha256_backend = lane.backend if lane.backend in ("pallas", "scan") else None
        if cfg.plane_factory is not None:
            if accepts_sha256_backend(cfg.plane_factory):
                return cfg.plane_factory(
                    lane.algo, lane.bucket, lane.target,
                    sha256_backend=sha256_backend,
                )
            return cfg.plane_factory(lane.algo, lane.bucket, lane.target)
        return build_builtin_plane(
            self.hasher, lane.algo, lane.bucket, lane.target,
            sha256_backend=sha256_backend,
        )

    def _run_plane(
        self, lane: _Lane, payloads: list[bytes], obs_note: dict | None = None
    ) -> list[bytes]:
        """Worker-thread body: build the plane on first use (JAX init and
        compiles run off the event loop) and execute the launch under a
        trace annotation so batches are attributable in the timeline.

        The lane breaker gates the primary plane: while it is open,
        launches degrade to the hashlib CPU plane (correct, slower) and
        only a half-open probe touches the primary again. Transient
        primary failures feed the breaker; deterministic payload errors
        do not (the device is answering — the payload is the problem).

        ``obs_note`` carries per-launch observability facts back to the
        dispatching coroutine (plane used, breaker-open transition) —
        the flight-recorder trigger and launch-span attrs live THERE so
        no obs lock is ever taken under breaker or counter locks.
        """
        if obs_note is None:
            obs_note = {}
        if not lane.breaker.acquire_primary():
            if lane.cpu_plane is None:  # benign to race: planes are stateless
                lane.cpu_plane = _CpuPlane(lane.algo)
            with self._counter_lock:  # worker threads across lanes race this
                self._counter_cells.write("fault_counters")
                self._cpu_fallback_launches += 1
            obs_note["plane"] = "cpu_fallback"
            return lane.cpu_plane.run(payloads)
        if lane.plane is None:
            # pipelined launches reach here from concurrent worker
            # threads; double-checked lock so the plane compiles once
            with lane.build_lock:
                if lane.plane is None:
                    try:
                        lane.plane = self._build_plane(lane)
                    except Exception as e:
                        # same classification as the launch path: a
                        # deterministic build error (factory misconfig)
                        # must not masquerade as device flakiness
                        if classify_error(e) == "transient":
                            if lane.breaker.record_failure():
                                obs_note["breaker_opened"] = True
                        else:
                            lane.breaker.release_probe()
                        raise
        # zero-copy launch form: when every ticket is a SlotRow of ONE
        # pre-staged slab and the plane can consume it in place, skip
        # the stage copy entirely (mixed batches — several slabs, or
        # slab rows interleaved with byte payloads — take the copying
        # run path, which stages SlotRow views like any other payload)
        staged = _staged_batch(payloads)
        run_staged = (
            getattr(lane.plane, "run_staged", None) if staged else None
        )
        # launched rows and pad-row waste: the rows this attempt stages,
        # uploads and hashes, and how many of them hold no piece (the
        # SHA-1 plane's row ladder, tile bucketing on the pallas plane;
        # zero pad on the hashlib degradation path, which stages
        # nothing). The built plane's own word is authoritative — a
        # plane_factory plane (faults seam) may stage differently than
        # the lane plan assumed: ``launch_rows`` where the copying road
        # launches at a shape of its own choosing, else the
        # ``launch_geometry`` hook the targets snap with (a staged
        # slab's rows are its reader's: charged as handed over); a plane
        # exposing neither is taken as row-exact (FaultyPlane's
        # hook-less default agrees). Charged per actual attempt (retries
        # and bisection halves each re-stage), under the counter lock:
        # worker threads run this.
        n = len(payloads)
        rows_of = getattr(lane.plane, "launch_rows", None)
        hook = getattr(lane.plane, "launch_geometry", None)
        if rows_of is not None and run_staged is None:
            launched = rows_of(n)
        elif hook is not None:
            launched = hook(n, lane.bucket)[0]
        else:
            launched = n
        with self._counter_lock:
            self._counter_cells.write("fault_counters")
            lane.launched_rows_total += launched
            lane.pad_rows_total += launched - n
            if run_staged is not None:
                lane.staged_launches += 1
                lane.staged_rows_total += staged[0].rows_total
                lane.staged_live_rows_total += n
        if run_staged is not None:
            obs_note["staged"] = True
        try:
            if self.hasher == "cpu":
                if run_staged is not None:
                    digests = run_staged(*staged)
                else:
                    digests = lane.plane.run(payloads)
            else:
                from torrent_tpu.obs.profiler import maybe_profile_batch

                with maybe_profile_batch(f"sched_{lane.algo}_launch_b{lane.bucket}"):
                    if run_staged is not None:
                        digests = run_staged(*staged)
                    else:
                        digests = lane.plane.run(payloads)
            # contract check BEFORE record_success: a plane persistently
            # returning the wrong count must feed the breaker (and trip
            # to the CPU plane) instead of resetting it every launch
            if len(digests) != len(payloads):
                raise RuntimeError(
                    f"plane returned {len(digests)} digests for {len(payloads)} pieces"
                )
        except Exception as e:
            if classify_error(e) == "transient":
                if lane.breaker.record_failure():
                    obs_note["breaker_opened"] = True
            else:
                lane.breaker.release_probe()
            raise
        lane.breaker.record_success()
        return digests

    @staticmethod
    def _traced_subs(tickets: list[_Ticket]) -> dict[int, tuple[_Submission, float]]:
        """Distinct traced submissions in a batch with their oldest
        ticket timestamp (one obs span per submission, not per ticket)."""
        out: dict[int, tuple[_Submission, float]] = {}
        for t in tickets:
            if t.sub.trace is None:
                continue
            prev = out.get(id(t.sub))
            if prev is None or t.ts < prev[1]:
                out[id(t.sub)] = (t.sub, t.ts)
        return out

    async def _launch(self, lane: _Lane, tickets: list[_Ticket], reason: str) -> None:
        n = len(tickets)
        fill = n / lane.target
        self._launches += 1
        self._fill_sum += fill
        self._flush_reasons[reason] += 1
        lane.launches += 1
        lane.fill_sum += fill
        lane_name = f"{lane.algo}/{lane.bucket}"
        t_take = time.monotonic()
        # one lock acquisition for the whole launch's queue waits
        histograms().get(*_H_QUEUE_WAIT, lane=lane_name).observe_batch(
            [t_take - t.ts for t in tickets]
        )
        for sub, ts0 in self._traced_subs(tickets).values():
            tracer().add_span(
                sub.trace[0], "sched.lane_wait", parent_id=sub.trace[1],
                t0=ts0, t1=t_take, lane=lane_name, flush=reason, rows=n,
            )
        await self._dispatch(lane, tickets, depth=0)

    async def _dispatch(self, lane: _Lane, tickets: list[_Ticket], depth: int) -> None:
        """Run one (sub-)batch with failure-domain isolation: retry a
        transient failure once, then bisect so a poisoned ticket fails
        alone while innocent co-batched tenants still get digests. Every
        relaunch re-selects the plane, so a breaker that trips mid-
        bisection routes the surviving halves through the CPU plane."""
        cfg = self.config
        payloads = [t.payload for t in tickets]
        lane_name = f"{lane.algo}/{lane.bucket}"
        attempts = 0
        while True:
            obs_note: dict = {}
            t0 = time.monotonic()
            try:
                # digest-count contract is checked inside _run_plane, so
                # a persistent violation feeds the breaker there
                digests = await asyncio.to_thread(
                    self._run_plane, lane, payloads, obs_note
                )
            except Exception as e:  # a poisoned launch must not wedge the lane
                t1 = time.monotonic()
                histograms().get(*_H_LAUNCH, lane=lane_name).observe(t1 - t0)
                self._launch_failures += 1
                kind = classify_error(e)
                log.warning(
                    "sched launch failed (%s/%d, %d pieces, depth %d, %s): %s",
                    lane.algo, lane.bucket, len(tickets), depth, kind, e,
                )
                self._obs_launch_spans(
                    tickets, lane_name, t0, t1, depth, attempts, obs_note,
                    status="error", error=e,
                )
                if obs_note.get("breaker_opened"):
                    # black box BEFORE the state evaporates: the dump
                    # carries the breaker snapshot plus the failing
                    # tickets' span trees
                    flight_recorder().trigger(
                        "breaker_open",
                        detail={"lane": lane_name, "kind": kind,
                                "error": str(e)},
                        trace_ids=self._trace_ids(tickets),
                        snapshots={"sched": self.metrics_snapshot()},
                    )
                if kind == "transient" and attempts < cfg.launch_retries:
                    attempts += 1
                    self._retries += 1
                    continue
                if len(tickets) > 1 and depth < cfg.bisect_depth:
                    self._bisections += 1
                    mid = len(tickets) // 2
                    await self._dispatch(lane, tickets[:mid], depth + 1)
                    await self._dispatch(lane, tickets[mid:], depth + 1)
                    return
                self._failed_pieces += len(tickets)
                err = SchedLaunchError(
                    f"hash launch failed ({kind}, {len(tickets)} pieces, "
                    f"{attempts} retries): {e}",
                    kind,
                    e,
                )
                self._demux(tickets, None, error=err)
                flight_recorder().trigger(
                    "retry_exhausted",
                    detail={"lane": lane_name, "kind": kind,
                            "pieces": len(tickets), "depth": depth,
                            "retries": attempts, "error": str(e)},
                    trace_ids=self._trace_ids(tickets),
                    snapshots={"sched": self.metrics_snapshot()},
                )
                return
            t1 = time.monotonic()
            histograms().get(*_H_LAUNCH, lane=lane_name).observe(t1 - t0)
            self._obs_launch_spans(
                tickets, lane_name, t0, t1, depth, attempts, obs_note,
                status="ok",
            )
            self._demux(tickets, digests)
            return

    @staticmethod
    def _trace_ids(tickets: list[_Ticket]) -> list[str]:
        out: list[str] = []
        for t in tickets:
            if t.sub.trace is not None and t.sub.trace[0] not in out:
                out.append(t.sub.trace[0])
        return out

    def _obs_launch_spans(
        self, tickets, lane_name, t0, t1, depth, attempt, note, status,
        error=None,
    ) -> None:
        """One sched.launch span per traced submission in the batch
        (retry attempts and bisection halves each record their own,
        distinguished by the attempt/depth attrs)."""
        subs = self._traced_subs(tickets)
        if not subs:
            return
        attrs = {"lane": lane_name, "rows": len(tickets), "depth": depth,
                 "attempt": attempt}
        if note.get("plane") == "cpu_fallback":
            attrs["plane"] = "cpu_fallback"
        if note.get("staged"):
            attrs["staged"] = True
        if note.get("breaker_opened"):
            attrs["breaker_opened"] = True
        if error is not None:
            attrs["error"] = str(error)
            attrs["kind"] = classify_error(error)
        for sub, _ts0 in subs.values():
            tracer().add_span(
                sub.trace[0], "sched.launch", parent_id=sub.trace[1],
                t0=t0, t1=t1, status=status, **attrs,
            )

    def _demux(self, tickets: list[_Ticket], digests, error=None) -> None:
        """Per-launch result demux back to the awaiting submissions,
        releasing queue bytes (and any blocked submitters) as it goes."""
        with pipeline_ledger().track(
            "verdict", sum(t.nbytes for t in tickets)
        ):
            self._demux_inner(tickets, digests, error)

    def _demux_inner(self, tickets: list[_Ticket], digests, error=None) -> None:
        t_now = time.monotonic()
        e2e_by_tenant: dict[str, list[float]] = {}
        done_subs: dict[int, _Submission] = {}
        # slot-carrying tickets: release one slab ref per ticket AFTER
        # delivery (batched per slab; the slot returns to its pool when
        # the last ref drops) — on the error path too, so a launch that
        # outlives retry/bisection can never leak a staging slot
        slab_refs: dict[int, tuple[StagedSlab, int]] = {}
        for i, tkt in enumerate(tickets):
            if type(tkt.payload) is SlotRow:
                slab = tkt.payload.slab
                prev = slab_refs.get(id(slab))
                slab_refs[id(slab)] = (slab, 1 if prev is None else prev[1] + 1)
        for i, tkt in enumerate(tickets):
            # the tenant may have been pruned while a zero-byte ticket was
            # in flight — global accounting and delivery must still happen
            t = self._tenants.get(tkt.tenant)
            if t is not None:
                t.queued_bytes -= tkt.charged
            self._queued_bytes -= tkt.charged
            e2e_by_tenant.setdefault(tkt.tenant, []).append(t_now - tkt.ts)
            if error is not None:
                tkt.sub.fail(error)
                if tkt.sub.trace is not None:
                    done_subs.setdefault(id(tkt.sub), tkt.sub)
                continue
            if t is not None:
                t.served_bytes += tkt.nbytes
                t.served_pieces += 1
            d = digests[i]
            if tkt.sub.mode == "verify":
                tkt.sub.deliver(tkt.idx, 1 if d == tkt.expected else 0)
            else:
                tkt.sub.deliver(tkt.idx, d)
            if tkt.sub.trace is not None and tkt.sub.remaining == 0:
                done_subs.setdefault(id(tkt.sub), tkt.sub)
        for slab, n in slab_refs.values():
            slab.release(n)
        for tenant, vals in e2e_by_tenant.items():
            histograms().get(*_H_E2E, tenant=tenant).observe_batch(vals)
        for sub in done_subs.values():
            if sub.traced_done:
                continue
            sub.traced_done = True
            status = "error" if error is not None else "ok"
            attrs: dict = {"mode": sub.mode, "pieces": len(sub.results)}
            if error is not None:
                attrs["error"] = str(error)
            did = tracer().add_span(
                sub.trace[0], "sched.digest", parent_id=sub.trace[1],
                t0=t_now, status=status, **attrs,
            )
            if sub.mode == "verify":
                valid = sum(1 for r in sub.results if r) if error is None else 0
                tracer().add_span(
                    sub.trace[0], "sched.verdict", parent_id=did, t0=t_now,
                    status=status, valid=valid, pieces=len(sub.results),
                )
        self._space.set()  # wake admission waiters

    # ----------------------------------------------------------- metrics

    def _staging_snapshot(self) -> dict:
        # worker threads create pools under _ingest_lock; snapshot the
        # dict under it too so iteration can't race an insert
        with self._ingest_lock:
            pools = list(self._ingest_pools.values())
        # per-pool counters move under each pool's own lock (worker
        # threads mid-checkout); stats() reads them there
        stats = [p.stats() for p in pools]
        return {
            "pools": len(pools),
            "outstanding": sum(s[0] for s in stats),
            "checkouts": sum(s[1] for s in stats),
        }

    def metrics_snapshot(self) -> dict:
        """Counters for utils/metrics.py's Prometheus rendering."""
        pending = sum(l.pending_pieces for l in self._lanes.values())
        # _cpu_fallback_launches and the per-lane pad counters are
        # bumped from worker threads under _counter_lock; snapshot them
        # under it too (the other fault counters are loop-confined but
        # ride along in the same brief leaf-lock scope)
        with self._counter_lock:
            self._counter_cells.read("fault_counters")
            cpu_fallback_launches = self._cpu_fallback_launches
            # pad share over a window = Δpad ÷ Δlaunched rows; the staged
            # road's fill = Δstaged live ÷ Δstaged rows
            lane_rows = {
                key: {name: getattr(lane, name) for name in _LANE_ROW_COUNTERS}
                for key, lane in self._lanes.items()
            }
        # enqueue-to-take seconds and pieces of every launch: the sum
        # and count the queue-wait histograms already keep, over every
        # lane of the process (the series are the process's, like the
        # pipeline ledger); a delta of two snapshots makes a mean of
        # them, which log2 buckets cannot
        _, queue_wait_pieces, queue_wait_s_sum = histograms().family_snapshot(
            _H_QUEUE_WAIT[0]
        ) or (None, 0, 0.0)
        # enqueue-to-verdict likewise (a ticket's enqueue → its demux):
        # less the wait above it is the time inside a launch, assembly
        # and the hop back to the loop included
        _, e2e_pieces, e2e_s_sum = histograms().family_snapshot(_H_E2E[0]) or (
            None, 0, 0.0,
        )
        return {
            "queue_pieces": pending,
            "queue_bytes": self._queued_bytes,
            # autopilot admission actuator (1.0 = the static config)
            "admission_factor": self._admission_factor,
            "lanes": len(self._lanes),
            "launches": self._launches,
            "queue_wait_s_sum": queue_wait_s_sum,
            "queue_wait_pieces": queue_wait_pieces,
            "e2e_s_sum": e2e_s_sum,
            "e2e_pieces": e2e_pieces,
            "fill_sum": self._fill_sum,
            "mean_fill": (self._fill_sum / self._launches) if self._launches else 0.0,
            "flush_reasons": dict(self._flush_reasons),
            "shed_total": self._shed_total,
            "launch_failures": self._launch_failures,
            "retries": self._retries,
            "bisections": self._bisections,
            "cpu_fallback_launches": cpu_fallback_launches,
            "failed_pieces": self._failed_pieces,
            "breakers": {
                f"{algo}/{bucket}": lane.breaker.snapshot()
                for (algo, bucket), lane in self._lanes.items()
            },
            # per-lane launch-fill and pad-row waste (pallas tile
            # bucketing observability: a healthy tile-snapped lane shows
            # mean_fill near 1.0 and pad_rows_total near 0 under load)
            "lane_stats": {
                f"{algo}/{bucket}": {
                    "backend": lane.backend,
                    # the built plane's own word for what its launches
                    # run (pallas | scan | hashlib); None until the first
                    # launch builds it, or for a plane_factory plane
                    "kernel": getattr(lane.plane, "kernel", None),
                    "target": lane.target,
                    "deadline": (
                        lane.deadline
                        if lane.deadline is not None
                        else self.config.flush_deadline
                    ),
                    "launches": lane.launches,
                    "mean_fill": (
                        lane.fill_sum / lane.launches if lane.launches else 0.0
                    ),
                    # a lane born since the locked copy above reads 0
                    **lane_rows.get((algo, bucket), dict.fromkeys(_LANE_ROW_COUNTERS, 0)),
                }
                for (algo, bucket), lane in self._lanes.items()
            },
            # zero-copy ingest pools: outstanding must return to 0 when
            # no read/launch is in flight (slab-leak test + ops gauge)
            "staging": self._staging_snapshot(),
            "evicted": dict(self._evicted),
            "tenants": {
                name: {
                    "queued_bytes": t.queued_bytes,
                    "served_bytes": t.served_bytes,
                    "served_pieces": t.served_pieces,
                    "shed": t.shed,
                    "weight": t.weight,
                }
                for name, t in self._tenants.items()
            },
        }
