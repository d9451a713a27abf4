"""Prometheus-format metrics endpoint for a running Client.

SURVEY §5's observability row, made scrapeable: ``GET /metrics`` renders
the session counters (`Client.status()` and per-torrent `status()`) in
the Prometheus text exposition format, so standard collectors can graph
swarm health without any custom integration. Read-only, allocation-
light (one render per scrape), and independent of the bridge sidecar —
this watches the SESSION, the bridge watches the hash plane.
"""

from __future__ import annotations

import asyncio

from torrent_tpu.utils.log import get_logger

log = get_logger("utils.metrics")


# The text format escapes \\, \" and \n in a label value and nothing
# else, so a raw control character or any other line boundary that
# ``str.splitlines()`` (and a scraper's line reader) honours, such as
# \r, U+0085 or U+2028 from a wire-supplied peer id, would cut the sample
# in two. Those are written as the literal text \xNN / \uNNNN behind an
# escaped backslash.
_LABEL_ESCAPES = {
    **{c: f"\\\\x{c:02x}" for c in (*range(0x20), *range(0x7F, 0xA0))},
    **{c: f"\\\\u{c:04x}" for c in (0x2028, 0x2029)},
    ord("\\"): "\\\\",
    ord('"'): '\\"',
    ord("\n"): "\\n",
}


def _esc(value: str) -> str:
    return value.translate(_LABEL_ESCAPES)


def render_sched_metrics(sched) -> str:
    """Prometheus rendering of a hash-plane scheduler's counters.

    ``sched`` is a ``torrent_tpu.sched.HashPlaneScheduler`` (anything
    with its ``metrics_snapshot()`` contract). Served by the bridge's
    ``GET /metrics`` and appended to the session exposition when a
    ``MetricsServer`` is given a scheduler. Defensive against partial
    snapshots (a fresh or degraded component may not carry every key):
    a missing counter renders as 0, never a crash mid-scrape."""
    s = sched.metrics_snapshot()
    lines = [
        "# HELP torrent_tpu_sched_queue_pieces Pieces queued awaiting a device launch",
        "# TYPE torrent_tpu_sched_queue_pieces gauge",
        f"torrent_tpu_sched_queue_pieces {s.get('queue_pieces', 0)}",
        "# HELP torrent_tpu_sched_queue_bytes Queued + in-flight payload bytes",
        "# TYPE torrent_tpu_sched_queue_bytes gauge",
        f"torrent_tpu_sched_queue_bytes {s.get('queue_bytes', 0)}",
        "# HELP torrent_tpu_sched_lanes Compiled (algo, piece-bucket) lanes",
        "# TYPE torrent_tpu_sched_lanes gauge",
        f"torrent_tpu_sched_lanes {s.get('lanes', 0)}",
        "# HELP torrent_tpu_sched_launches_total Device launches dispatched",
        "# TYPE torrent_tpu_sched_launches_total counter",
        f"torrent_tpu_sched_launches_total {s.get('launches', 0)}",
        "# HELP torrent_tpu_sched_batch_fill_ratio Mean launch fill vs the lane target",
        "# TYPE torrent_tpu_sched_batch_fill_ratio gauge",
        f"torrent_tpu_sched_batch_fill_ratio {s.get('mean_fill', 0.0):.6f}",
        "# HELP torrent_tpu_sched_shed_total Submissions rejected by admission control",
        "# TYPE torrent_tpu_sched_shed_total counter",
        f"torrent_tpu_sched_shed_total {s.get('shed_total', 0)}",
        "# HELP torrent_tpu_sched_launch_failures_total Device launches that raised",
        "# TYPE torrent_tpu_sched_launch_failures_total counter",
        f"torrent_tpu_sched_launch_failures_total {s.get('launch_failures', 0)}",
        "# HELP torrent_tpu_sched_retries_total Failed launches retried (transient errors)",
        "# TYPE torrent_tpu_sched_retries_total counter",
        f"torrent_tpu_sched_retries_total {s.get('retries', 0)}",
        "# HELP torrent_tpu_sched_bisections_total Failed launches split to isolate a poisoned ticket",
        "# TYPE torrent_tpu_sched_bisections_total counter",
        f"torrent_tpu_sched_bisections_total {s.get('bisections', 0)}",
        "# HELP torrent_tpu_sched_cpu_fallback_launches_total Launches degraded to the CPU plane by an open breaker",
        "# TYPE torrent_tpu_sched_cpu_fallback_launches_total counter",
        f"torrent_tpu_sched_cpu_fallback_launches_total {s.get('cpu_fallback_launches', 0)}",
        "# HELP torrent_tpu_sched_failed_pieces_total Pieces whose hashing exhausted retry and bisection",
        "# TYPE torrent_tpu_sched_failed_pieces_total counter",
        f"torrent_tpu_sched_failed_pieces_total {s.get('failed_pieces', 0)}",
        "# HELP torrent_tpu_sched_evicted_tenants_total Idle auto-registered tenants evicted to bound cardinality",
        "# TYPE torrent_tpu_sched_evicted_tenants_total counter",
        f"torrent_tpu_sched_evicted_tenants_total {s.get('evicted', {}).get('tenants', 0)}",
        "# HELP torrent_tpu_sched_staging_outstanding Zero-copy ingest slabs checked out and not yet returned",
        "# TYPE torrent_tpu_sched_staging_outstanding gauge",
        f"torrent_tpu_sched_staging_outstanding {s.get('staging', {}).get('outstanding', 0)}",
        "# HELP torrent_tpu_sched_staging_checkouts_total Zero-copy ingest slab checkouts",
        "# TYPE torrent_tpu_sched_staging_checkouts_total counter",
        f"torrent_tpu_sched_staging_checkouts_total {s.get('staging', {}).get('checkouts', 0)}",
        "# HELP torrent_tpu_sched_flush_total Launch flushes by reason",
        "# TYPE torrent_tpu_sched_flush_total counter",
    ]
    for reason, n in sorted(s.get("flush_reasons", {}).items()):
        lines.append(f'torrent_tpu_sched_flush_total{{reason="{reason}"}} {n}')
    # per-lane launch fill and tile-padding waste (pallas sub-tile
    # bucketing observability: a tile-snapped lane under load should
    # show fill near 1.0 and a flat pad-rows counter)
    lane_stats = s.get("lane_stats", {})
    lines.append(
        "# HELP torrent_tpu_sched_lane_fill_ratio Mean launch fill vs this lane's target"
    )
    lines.append("# TYPE torrent_tpu_sched_lane_fill_ratio gauge")
    for lane, st in sorted(lane_stats.items()):
        lines.append(
            f'torrent_tpu_sched_lane_fill_ratio{{lane="{_esc(lane)}"}} '
            f"{st.get('mean_fill', 0.0):.6f}"
        )
    lines.append(
        "# HELP torrent_tpu_sched_launch_pad_rows_total Sentinel rows staged "
        "beyond the live batch (SHA-1 row ladder, tile-bucketed pallas launches)"
    )
    lines.append("# TYPE torrent_tpu_sched_launch_pad_rows_total counter")
    for lane, st in sorted(lane_stats.items()):
        lines.append(
            f'torrent_tpu_sched_launch_pad_rows_total{{lane="{_esc(lane)}"}} '
            f"{st.get('pad_rows_total', 0)}"
        )
    # the denominator: pad share over a window = pad rows / launched rows
    lines.append(
        "# HELP torrent_tpu_sched_launch_rows_total Rows staged, uploaded "
        "and hashed by launch attempts, pad rows included"
    )
    lines.append("# TYPE torrent_tpu_sched_launch_rows_total counter")
    for lane, st in sorted(lane_stats.items()):
        lines.append(
            f'torrent_tpu_sched_launch_rows_total{{lane="{_esc(lane)}"}} '
            f"{st.get('launched_rows_total', 0)}"
        )
    # the zero-copy road: a staged launch uploads its whole slab, so its
    # fill over a window = live rows / staged rows (pad_rows_total stays
    # row-exact there)
    for name, key, text in (
        ("staged_launches_total", "staged_launches",
         "Launch attempts that took a pre-staged slab in place (run_staged)"),
        ("staged_rows_total", "staged_rows_total",
         "Rows of the slabs handed to staged launches, live or not"),
        ("staged_live_rows_total", "staged_live_rows_total",
         "Ticket rows among the rows of staged launches"),
    ):
        lines.append(f"# HELP torrent_tpu_sched_{name} {text}")
        lines.append(f"# TYPE torrent_tpu_sched_{name} counter")
        for lane, st in sorted(lane_stats.items()):
            lines.append(
                f'torrent_tpu_sched_{name}{{lane="{_esc(lane)}"}} {st.get(key, 0)}'
            )
    lines.append(
        "# HELP torrent_tpu_sched_lane_target Pieces per launch this lane aims to fill"
    )
    lines.append("# TYPE torrent_tpu_sched_lane_target gauge")
    for lane, st in sorted(lane_stats.items()):
        lines.append(
            f'torrent_tpu_sched_lane_target{{lane="{_esc(lane)}",'
            f'backend="{_esc(st.get("backend", "device"))}"}} {st.get("target", 0)}'
        )
    # breaker lifecycle per lane: state as an enum gauge (0 closed,
    # 1 half-open, 2 open — alert on > 0) plus transition counters
    _breaker_states = {"closed": 0, "half_open": 1, "open": 2}
    lines.append(
        "# HELP torrent_tpu_sched_breaker_state Lane circuit-breaker state "
        "(0=closed device plane live, 1=half-open probing, 2=open CPU degraded)"
    )
    lines.append("# TYPE torrent_tpu_sched_breaker_state gauge")
    for lane, b in sorted(s.get("breakers", {}).items()):
        lines.append(
            f'torrent_tpu_sched_breaker_state{{lane="{_esc(lane)}"}} '
            f"{_breaker_states.get(b.get('state'), 2)}"
        )
    lines.append(
        "# HELP torrent_tpu_sched_breaker_transitions_total Breaker state transitions"
    )
    lines.append("# TYPE torrent_tpu_sched_breaker_transitions_total counter")
    for lane, b in sorted(s.get("breakers", {}).items()):
        for transition, n in sorted(b.get("transitions", {}).items()):
            lines.append(
                "torrent_tpu_sched_breaker_transitions_total"
                f'{{lane="{_esc(lane)}",transition="{_esc(transition)}"}} {n}'
            )
    per_tenant = [
        ("torrent_tpu_sched_tenant_served_bytes_total", "counter",
         "Payload bytes hashed for this tenant", "served_bytes"),
        ("torrent_tpu_sched_tenant_served_pieces_total", "counter",
         "Pieces hashed for this tenant", "served_pieces"),
        ("torrent_tpu_sched_tenant_queued_bytes", "gauge",
         "Queued + in-flight bytes for this tenant", "queued_bytes"),
        ("torrent_tpu_sched_tenant_shed_total", "counter",
         "Submissions shed for this tenant", "shed"),
    ]
    for name, kind, help_text, key in per_tenant:
        lines.append(f"# HELP {name} {help_text}")
        lines.append(f"# TYPE {name} {kind}")
        for tenant, t in sorted(s.get("tenants", {}).items()):
            lines.append(f'{name}{{tenant="{_esc(tenant)}"}} {t.get(key, 0)}')
    return "\n".join(lines) + "\n"


def render_step_metrics(stats: dict) -> str:
    """Prometheus rendering of the process's jitted SHA-1 steps.

    ``stats`` is ``torrent_tpu.models.verifier.step_cache_stats()``. A
    long-lived process that rechecks a torrent a call should show
    builds at the number of (backend, mesh) pairs it uses and reuses
    rising by one a call; builds rising with the calls means every
    call traces the scan and loads its program again."""
    return (
        "# HELP torrent_tpu_verifier_step_builds_total Sets of jitted SHA-1 steps this process has built\n"
        "# TYPE torrent_tpu_verifier_step_builds_total counter\n"
        f"torrent_tpu_verifier_step_builds_total {stats['step_builds']}\n"
        "# HELP torrent_tpu_verifier_step_reuses_total Verifiers constructed on a set of jitted steps already built\n"
        "# TYPE torrent_tpu_verifier_step_reuses_total counter\n"
        f"torrent_tpu_verifier_step_reuses_total {stats['step_reuses']}\n"
    )


def render_staging_slab_metrics(stats: dict) -> str:
    """Prometheus rendering of a recheck's kept staging pair.

    ``stats`` is ``torrent_tpu.models.verifier.staging_slab_stats()``. A
    long-lived process that rechecks a torrent a call should show
    reuses rising by one a call; allocs rising with the calls means
    geometries alternate, transient that rechecks overlap or ask for a
    pair over ``STAGING_KEEP_BYTES``: every such pass faults its two
    slabs in again."""
    lines = []
    for key, text in (
        ("staging_slab_allocs", "Recheck passes that allocated or replaced the process's kept staging pair"),
        ("staging_slab_reuses", "Recheck passes that found the kept staging pair large enough"),
        ("staging_slab_transient", "Recheck passes that took a transient staging pair: the kept one was out, or the pair is over the cap"),
    ):
        name = f"torrent_tpu_verifier_{key}_total"
        lines += [f"# HELP {name} {text}", f"# TYPE {name} counter", f"{name} {stats[key]}"]
    return "\n".join(lines) + "\n"


def render_storage_write_metrics(stats: dict) -> str:
    """Prometheus rendering of ``FsStorage``'s kept write handles.

    ``stats`` is ``torrent_tpu.storage.storage.storage_write_stats()``. A
    download should show one open a file and a reuse for every later
    segment written; opens rising with the pieces mean the handles are
    dropped between writes (routing changed, or more files than
    ``FsStorage.WRITE_HANDLES_MAX`` written in turn)."""
    lines = []
    for key, text in (
        ("write_opens", "Storage writes that opened their file"),
        ("write_reuses", "Storage writes on a file handle their backend kept open"),
    ):
        name = f"torrent_tpu_storage_{key}_total"
        lines += [f"# HELP {name} {text}", f"# TYPE {name} counter", f"{name} {stats[key]}"]
    return "\n".join(lines) + "\n"


def render_leaf_metrics(stats: dict) -> str:
    """Prometheus rendering of the v2 leaf plane's launch counters.

    ``stats`` is ``torrent_tpu.models.v2.leaf_launch_stats()``. Leaf
    batches are pow-2 bucketed, so ``rows_launched`` less ``rows_live``
    is the padding a recheck staged and uploaded, and launches under
    ``kernel="scan"`` on a TPU are the batches too small for the Pallas
    kernel."""
    families = (
        ("launches", "torrent_tpu_v2_leaf_launches_total", "v2 leaf launches by kernel"),
        ("rows_launched", "torrent_tpu_v2_leaf_rows_launched_total", "Leaf rows staged and uploaded, padding included, by kernel"),
        ("rows_live", "torrent_tpu_v2_leaf_rows_live_total", "Leaf rows that held a 16 KiB block to hash, by kernel"),
    )
    lines = []
    for key, name, text in families:
        lines += [f"# HELP {name} {text}", f"# TYPE {name} counter"]
        lines += [f'{name}{{kernel="{_esc(k)}"}} {st[key]}' for k, st in sorted(stats.items())]
    return "\n".join(lines) + "\n"


def render_author_metrics(stats: dict) -> str:
    """Prometheus rendering of the authoring road's SHA-1 launch counters.

    ``stats`` is ``torrent_tpu.models.verifier.author_launch_stats()``.
    A launch uploads a whole batch, so ``rows_launched`` less
    ``rows_live`` is the sentinel padding an authoring run uploaded."""
    lines = []
    for key, text in (
        ("launches", "Authoring SHA-1 launches (hash_pieces)"),
        ("rows_launched", "Authoring SHA-1 rows uploaded and hashed, sentinel rows included"),
        ("rows_live", "Authoring SHA-1 rows that held a piece"),
    ):
        name = f"torrent_tpu_author_sha1_{key}_total"
        lines += [f"# HELP {name} {text}", f"# TYPE {name} counter", f"{name} {stats[key]}"]
    return "\n".join(lines) + "\n"


def render_leaf_slab_metrics(stats: dict) -> str:
    """Prometheus rendering of the v2 leaf slab's counters.

    ``stats`` is ``torrent_tpu.models.v2.leaf_slab_stats()``: check-outs
    of the process's one kept slab by what they found, and leaf launches
    by how their rows were staged, and the launches that went out while
    an earlier one was in flight, and those whose slab went up in row
    blocks. A recheck that keeps allocating, or whose path sources go out
    ``copied``, has lost the kept slab or the native engine; one whose
    launches stop overlapping has lost its window."""
    lines = []
    for key, text in (
        ("leaf_slab_allocs", "Check-outs that allocated or grew the process's kept leaf slab"),
        ("leaf_slab_reuses", "Check-outs that found the kept leaf slab large enough"),
        ("leaf_slab_transient", "Check-outs that found the kept leaf slab out and took a transient one"),
    ):
        name = f"torrent_tpu_v2_{key}_total"
        lines += [f"# HELP {name} {text}", f"# TYPE {name} counter", f"{name} {stats[key]}"]
    name = "torrent_tpu_v2_leaf_launches_staged_total"
    lines += [
        f"# HELP {name} v2 leaf launches by how their rows reached the slab: read into it (direct) or copied from a resident chunk",
        f"# TYPE {name} counter",
    ]
    lines += [f'{name}{{staged="{how}"}} {stats[how]}' for how in ("copied", "direct")]
    name = "torrent_tpu_v2_leaf_launches_overlapped_total"
    lines += [
        f"# HELP {name} v2 leaf launches dispatched while an earlier one was still unfetched (verify_v2's window of one)",
        f"# TYPE {name} counter",
        f"{name} {stats['leaf_launches_overlapped']}",
    ]
    name = "torrent_tpu_v2_leaf_uploads_chunked_total"
    lines += [
        f"# HELP {name} v2 leaf launches whose slab went up in concurrent row blocks (Pallas launches); a scan launch goes up in one put",
        f"# TYPE {name} counter",
        f"{name} {stats['leaf_uploads_chunked']}",
    ]
    return "\n".join(lines) + "\n"


def render_v2_ingest_metrics(stats: dict) -> str:
    """Prometheus rendering of the session's v2 ingest counters.

    ``stats`` is ``torrent_tpu.session.v2.v2_ingest_stats()``: device
    batches, the pieces in them and the short ones among them, the
    pieces whose payload hashlib hashed (``hasher="cpu"`` and the
    batch's fallback), and the fold's grid rows, padding included."""
    lines = []
    for key, text in (
        ("batches", "v2 ingest batches put to the device leaf plane"),
        ("pieces", "Pieces in v2 ingest batches"),
        ("short_pieces", "Pieces in v2 ingest batches shorter than a full piece"),
        ("pieces_host_hashed", "v2 ingest pieces whose payload hashlib hashed"),
        ("fold_pieces_launched", "Piece rows of v2 ingest folds, padding pieces included"),
    ):
        name = f"torrent_tpu_v2_ingest_{key}_total"
        lines += [f"# HELP {name} {text}", f"# TYPE {name} counter", f"{name} {stats[key]}"]
    return "\n".join(lines) + "\n"


def render_tsan_metrics(snapshot: dict) -> str:
    """Prometheus rendering of the concurrency sanitizer's counters.

    ``snapshot`` is ``torrent_tpu.analysis.sanitizer.snapshot()``.
    Appended to ``/metrics`` (bridge and MetricsServer) only while
    TSAN mode is on — the series simply don't exist otherwise."""
    s = snapshot
    lines = [
        "# HELP torrent_tpu_lock_wait_seconds_total Seconds threads spent waiting to acquire this lock",
        "# TYPE torrent_tpu_lock_wait_seconds_total counter",
    ]
    locks = s.get("locks", {})
    for name, st in sorted(locks.items()):
        lines.append(
            f'torrent_tpu_lock_wait_seconds_total{{lock="{_esc(name)}"}} '
            f"{st['wait_total_s']:.6f}"
        )
    lines.append(
        "# HELP torrent_tpu_lock_hold_max_seconds Longest single hold observed for this lock"
    )
    lines.append("# TYPE torrent_tpu_lock_hold_max_seconds gauge")
    for name, st in sorted(locks.items()):
        lines.append(
            f'torrent_tpu_lock_hold_max_seconds{{lock="{_esc(name)}"}} '
            f"{st['hold_max_s']:.6f}"
        )
    lines.append(
        "# HELP torrent_tpu_lock_acquisitions_total Acquisitions of this lock"
    )
    lines.append("# TYPE torrent_tpu_lock_acquisitions_total counter")
    for name, st in sorted(locks.items()):
        lines.append(
            f'torrent_tpu_lock_acquisitions_total{{lock="{_esc(name)}"}} '
            f"{st['acquisitions']}"
        )
    lines.append(
        "# HELP torrent_tpu_lock_contended_total Acquisitions that waited more than 1ms"
    )
    lines.append("# TYPE torrent_tpu_lock_contended_total counter")
    for name, st in sorted(locks.items()):
        lines.append(
            f'torrent_tpu_lock_contended_total{{lock="{_esc(name)}"}} '
            f"{st['contended']}"
        )
    lines += [
        "# HELP torrent_tpu_lock_order_cycles_total Lock-order cycles observed at runtime (any nonzero value is a bug)",
        "# TYPE torrent_tpu_lock_order_cycles_total counter",
        f"torrent_tpu_lock_order_cycles_total {len(s.get('cycles', []))}",
        "# HELP torrent_tpu_lock_long_holds_total Locks flagged by the hold-time watchdog",
        "# TYPE torrent_tpu_lock_long_holds_total counter",
        f"torrent_tpu_lock_long_holds_total {s.get('long_holds', 0)}",
        "# HELP torrent_tpu_loop_stalls_total Event-loop callbacks that exceeded the stall threshold",
        "# TYPE torrent_tpu_loop_stalls_total counter",
        f"torrent_tpu_loop_stalls_total {s.get('loop_stalls', 0)}",
        "# HELP torrent_tpu_loop_stall_max_seconds Longest single event-loop callback observed",
        "# TYPE torrent_tpu_loop_stall_max_seconds gauge",
        f"torrent_tpu_loop_stall_max_seconds {s.get('loop_stall_max_s', 0.0):.6f}",
    ]
    # dynamic lockset checking (Eraser): registered cells + races
    cells = s.get("cells", {})
    lines.append(
        "# HELP torrent_tpu_guarded_cells Cell instances registered with the dynamic lockset checker"
    )
    lines.append("# TYPE torrent_tpu_guarded_cells gauge")
    for name, st in sorted(cells.items()):
        lines.append(
            f'torrent_tpu_guarded_cells{{cell="{_esc(name)}"}} '
            f"{st.get('instances', 0)}"
        )
    lines += [
        "# HELP torrent_tpu_lockset_races_total Shared-state lockset races observed at runtime (any nonzero value is a bug)",
        "# TYPE torrent_tpu_lockset_races_total counter",
        f"torrent_tpu_lockset_races_total {s.get('lockset_race_count', 0)}",
    ]
    return "\n".join(lines) + "\n"


def render_fabric_metrics(snapshot: dict) -> str:
    """Prometheus rendering of one process's verify-fabric gauges.

    ``snapshot`` is a ``torrent_tpu.fabric.FabricExecutor.
    metrics_snapshot()`` dict. Appended to the bridge's ``/metrics``
    while a fabric job exists, labeled by the process id so a pod-wide
    scrape distinguishes shards. Defensive against partial snapshots:
    missing keys render as 0, never a crash mid-scrape."""
    s = snapshot
    pid = f'pid="{s.get("pid", 0)}"'
    states = {"idle": 0, "running": 1, "done": 2, "failed": 3}
    lines = [
        "# HELP torrent_tpu_fabric_state Fabric executor state "
        "(0=idle 1=running 2=done 3=failed)",
        "# TYPE torrent_tpu_fabric_state gauge",
        f"torrent_tpu_fabric_state{{{pid}}} {states.get(s.get('state'), 3)}",
        "# HELP torrent_tpu_fabric_shard_bytes Payload bytes planned onto this process",
        "# TYPE torrent_tpu_fabric_shard_bytes gauge",
        f"torrent_tpu_fabric_shard_bytes{{{pid}}} {s.get('shard_bytes', 0)}",
        "# HELP torrent_tpu_fabric_units Work units by disposition for this process",
        "# TYPE torrent_tpu_fabric_units gauge",
        f'torrent_tpu_fabric_units{{{pid},kind="planned"}} {s.get("shard_units", 0)}',
        f'torrent_tpu_fabric_units{{{pid},kind="done"}} {s.get("units_done", 0)}',
        f'torrent_tpu_fabric_units{{{pid},kind="adopted"}} {s.get("units_adopted", 0)}',
        f'torrent_tpu_fabric_units{{{pid},kind="offered"}} {s.get("units_offered", 0)}',
        f'torrent_tpu_fabric_units{{{pid},kind="rebalanced"}} {s.get("units_rebalanced", 0)}',
        f'torrent_tpu_fabric_units{{{pid},kind="total"}} {s.get("units_total", 0)}',
        "# HELP torrent_tpu_fabric_pieces_verified_total Pieces this process verified",
        "# TYPE torrent_tpu_fabric_pieces_verified_total counter",
        f"torrent_tpu_fabric_pieces_verified_total{{{pid}}} {s.get('pieces_verified', 0)}",
        "# HELP torrent_tpu_fabric_inflight_bytes Payload bytes in scheduler futures",
        "# TYPE torrent_tpu_fabric_inflight_bytes gauge",
        f"torrent_tpu_fabric_inflight_bytes{{{pid}}} {s.get('inflight_bytes', 0)}",
        "# HELP torrent_tpu_fabric_heartbeat_age_seconds Seconds since the last successful heartbeat exchange",
        "# TYPE torrent_tpu_fabric_heartbeat_age_seconds gauge",
        f"torrent_tpu_fabric_heartbeat_age_seconds{{{pid}}} {s.get('heartbeat_age', 0.0):.3f}",
        "# HELP torrent_tpu_fabric_sentinel_checks_total Adopted-unit verdicts cross-checked by a sentinel re-hash",
        "# TYPE torrent_tpu_fabric_sentinel_checks_total counter",
        f"torrent_tpu_fabric_sentinel_checks_total{{{pid}}} {s.get('sentinel_checks', 0)}",
        "# HELP torrent_tpu_fabric_sentinel_mismatches_total Foreign verdicts rejected by the sentinel cross-check",
        "# TYPE torrent_tpu_fabric_sentinel_mismatches_total counter",
        f"torrent_tpu_fabric_sentinel_mismatches_total{{{pid}}} {s.get('sentinel_mismatches', 0)}",
        "# HELP torrent_tpu_fabric_audit_checks_total Peer claimed-ok pieces re-hashed by the Byzantine audit sampler",
        "# TYPE torrent_tpu_fabric_audit_checks_total counter",
        f"torrent_tpu_fabric_audit_checks_total{{{pid}}} {s.get('audit_checks', 0)}",
        "# HELP torrent_tpu_fabric_audit_mismatches_total Audited claimed-ok pieces that re-hashed bad (each files conviction evidence)",
        "# TYPE torrent_tpu_fabric_audit_mismatches_total counter",
        f"torrent_tpu_fabric_audit_mismatches_total{{{pid}}} {s.get('audit_mismatches', 0)}",
        "# HELP torrent_tpu_fabric_quorum_convictions_total (publisher, unit) pairs convicted on receipt evidence (structural, audit, evidence, or accusation quorum)",
        "# TYPE torrent_tpu_fabric_quorum_convictions_total counter",
        f"torrent_tpu_fabric_quorum_convictions_total{{{pid}}} {s.get('convictions', 0)}",
        "# HELP torrent_tpu_fabric_quorum_verifies_total Units this process verified as an elected quorum top-up helper",
        "# TYPE torrent_tpu_fabric_quorum_verifies_total counter",
        f"torrent_tpu_fabric_quorum_verifies_total{{{pid}}} {s.get('quorum_verifies', 0)}",
        "# HELP torrent_tpu_fabric_quorum_need Matching receipts required to cover a unit (byzantine_f + 1, clamped to nproc; 1 = the f=0 sentinel fast path)",
        "# TYPE torrent_tpu_fabric_quorum_need gauge",
        f"torrent_tpu_fabric_quorum_need{{{pid}}} {s.get('quorum_need', 1)}",
        "# HELP torrent_tpu_fabric_stragglers_total Units flagged in flight past the straggler threshold",
        "# TYPE torrent_tpu_fabric_stragglers_total counter",
        f"torrent_tpu_fabric_stragglers_total{{{pid}}} {s.get('stragglers', 0)}",
        "# HELP torrent_tpu_fabric_degraded Breaker-stuck degradation flag (unstarted units yielded)",
        "# TYPE torrent_tpu_fabric_degraded gauge",
        f"torrent_tpu_fabric_degraded{{{pid}}} {1 if s.get('degraded') else 0}",
    ]
    return "\n".join(lines) + "\n"


def render_control_metrics(snapshot: dict) -> str:
    """Prometheus rendering of the scheduler autopilot's counters.

    ``snapshot`` is ``torrent_tpu.sched.control.SchedulerAutopilot.
    metrics_snapshot()``. Appended to both ``/metrics`` endpoints while
    an autopilot is attached — the series simply don't exist otherwise.
    Defensive against partial snapshots: missing keys render as 0."""
    s = snapshot or {}
    lines = [
        "# HELP torrent_tpu_control_enabled Scheduler autopilot actuation switch (0 = observe-only)",
        "# TYPE torrent_tpu_control_enabled gauge",
        f"torrent_tpu_control_enabled {1 if s.get('enabled') else 0}",
        "# HELP torrent_tpu_control_ticks_total Controller decisions computed",
        "# TYPE torrent_tpu_control_ticks_total counter",
        f"torrent_tpu_control_ticks_total {s.get('ticks', 0)}",
        "# HELP torrent_tpu_control_admission_factor Fraction of the configured admission budget currently admitted",
        "# TYPE torrent_tpu_control_admission_factor gauge",
        f"torrent_tpu_control_admission_factor {s.get('admission_factor', 1.0):.4f}",
        "# HELP torrent_tpu_control_backend_switches_total Lane backend steers applied by the controller",
        "# TYPE torrent_tpu_control_backend_switches_total counter",
        f"torrent_tpu_control_backend_switches_total {s.get('backend_switches', 0)}",
        "# HELP torrent_tpu_control_actions_total Actuator moves applied, by actuator",
        "# TYPE torrent_tpu_control_actions_total counter",
    ]
    for actuator in ("batch_target", "flush_deadline", "admission", "backend"):
        lines.append(
            f'torrent_tpu_control_actions_total{{actuator="{actuator}"}} '
            f"{(s.get('actions') or {}).get(actuator, 0)}"
        )
    # the controller's last confirmed bottleneck as a 0/1 enum family
    from torrent_tpu.obs.ledger import PIPELINE_STAGES

    bn = s.get("bottleneck")
    lines.append(
        "# HELP torrent_tpu_control_bottleneck Stage the controller's last decision named limiting (1 = current)"
    )
    lines.append("# TYPE torrent_tpu_control_bottleneck gauge")
    for stage in PIPELINE_STAGES:
        lines.append(
            f'torrent_tpu_control_bottleneck{{stage="{stage}"}} '
            f"{1 if stage == bn else 0}"
        )
    lanes = s.get("lanes") or {}
    lines.append(
        "# HELP torrent_tpu_control_lane_target Current (possibly adapted) pieces-per-launch target per lane"
    )
    lines.append("# TYPE torrent_tpu_control_lane_target gauge")
    for lane, st in sorted(lanes.items()):
        lines.append(
            f'torrent_tpu_control_lane_target{{lane="{_esc(lane)}",'
            f'backend="{_esc(str(st.get("backend", "device")))}"}} '
            f"{st.get('target', 0)}"
        )
    lines.append(
        "# HELP torrent_tpu_control_lane_flush_deadline_seconds Current (possibly adapted) flush deadline per lane"
    )
    lines.append("# TYPE torrent_tpu_control_lane_flush_deadline_seconds gauge")
    for lane, st in sorted(lanes.items()):
        lines.append(
            f'torrent_tpu_control_lane_flush_deadline_seconds{{lane="{_esc(lane)}"}} '
            f"{st.get('deadline', 0.0):.6f}"
        )
    return "\n".join(lines) + "\n"


# per-pid series cap for the fleet rendering: a pod bigger than this
# folds the tail pids into one pid="overflow" aggregate, so /metrics
# cardinality is bounded no matter how wide the fleet plans
MAX_FLEET_PIDS = 16

_FLEET_STATUSES = ("ok", "unreported", "degraded", "lapsed", "distrusted")


def render_fleet_metrics(rollup: dict) -> str:
    """Prometheus rendering of a fleet rollup (``obs/fleet.
    aggregate_fleet`` / ``FabricExecutor.fleet_snapshot``).

    Appended to both ``/metrics`` endpoints while a fleet view exists.
    Bounded pid cardinality: the first :data:`MAX_FLEET_PIDS` scoreboard
    rows (pid order) get per-pid series; the rest fold into a single
    ``pid="overflow"`` aggregate (summed units/rates — a bounded scrape
    beats per-pid fidelity past the cap). Defensive against partial
    rollups: missing keys render as 0, never a crash mid-scrape."""
    s = rollup or {}
    rows = [r for r in s.get("scoreboard") or [] if isinstance(r, dict)]
    named = rows[:MAX_FLEET_PIDS]
    folded = rows[MAX_FLEET_PIDS:]
    bn = s.get("bottleneck") or {}
    totals = s.get("totals") or {}
    status_counts = {st: 0 for st in _FLEET_STATUSES}
    for r in rows:
        status_counts[r.get("status") or "unreported"] = (
            status_counts.get(r.get("status") or "unreported", 0) + 1
        )
    lines = [
        "# HELP torrent_tpu_fleet_processes Processes the fabric plan spans",
        "# TYPE torrent_tpu_fleet_processes gauge",
        f"torrent_tpu_fleet_processes {s.get('nproc', 0)}",
        "# HELP torrent_tpu_fleet_reporting Processes whose obs digest this view holds",
        "# TYPE torrent_tpu_fleet_reporting gauge",
        f"torrent_tpu_fleet_reporting {s.get('reporting', 0)}",
        "# HELP torrent_tpu_fleet_status Scoreboard processes by heartbeat status",
        "# TYPE torrent_tpu_fleet_status gauge",
    ]
    for st in _FLEET_STATUSES:
        lines.append(
            f'torrent_tpu_fleet_status{{status="{st}"}} {status_counts.get(st, 0)}'
        )
    lines += [
        "# HELP torrent_tpu_fleet_median_bps Fleet median achieved pipeline bytes/s",
        "# TYPE torrent_tpu_fleet_median_bps gauge",
        "torrent_tpu_fleet_median_bps "
        f"{bn.get('fleet_median_bps') or (totals.get('fleet_bps') or 0.0)}",
        "# HELP torrent_tpu_fleet_bps Summed achieved pipeline bytes/s across reporting processes",
        "# TYPE torrent_tpu_fleet_bps gauge",
        f"torrent_tpu_fleet_bps {totals.get('fleet_bps') or 0.0}",
        "# HELP torrent_tpu_fleet_limiting_process The fleet's limiting process and its limiting stage (1 = current verdict)",
        "# TYPE torrent_tpu_fleet_limiting_process gauge",
    ]
    if bn.get("stage") is not None:
        lines.append(
            "torrent_tpu_fleet_limiting_process"
            f'{{pid="{bn.get("pid", 0)}",stage="{_esc(str(bn["stage"]))}"}} 1'
        )

    def _pid_series(name, kind, help_text, get, fold=sum):
        lines.append(f"# HELP {name} {help_text}")
        lines.append(f"# TYPE {name} {kind}")
        for r in named:
            lines.append(f'{name}{{pid="{r.get("pid", 0)}"}} {get(r)}')
        if folded:
            lines.append(
                f'{name}{{pid="overflow"}} {fold(get(r) for r in folded)}'
            )

    _pid_series(
        "torrent_tpu_fleet_pid_achieved_bps", "gauge",
        "Achieved pipeline bytes/s per process (digest view)",
        lambda r: r.get("achieved_bps") or 0.0,
    )
    _pid_series(
        "torrent_tpu_fleet_pid_vs_median", "gauge",
        "Achieved rate vs the fleet median (1.0 = median; stragglers < 0.5)",
        lambda r: r.get("vs_median") or 0.0,
        # a ratio doesn't sum: the folded tail reports its WORST member —
        # the actionable straggler signal an alert on < 0.5 still catches
        fold=min,
    )
    _pid_series(
        "torrent_tpu_fleet_pid_adoption_debt", "gauge",
        "Planned-but-undone units of an unavailable process that survivors must absorb",
        lambda r: r.get("adoption_debt") or 0,
    )
    lines.append(
        "# HELP torrent_tpu_fleet_pid_units Work units by disposition per process"
    )
    lines.append("# TYPE torrent_tpu_fleet_pid_units gauge")
    for kind_name, key in (
        ("planned", "units_planned"),
        ("done", "units_done"),
        ("adopted", "units_adopted"),
    ):
        for r in named:
            lines.append(
                "torrent_tpu_fleet_pid_units"
                f'{{pid="{r.get("pid", 0)}",kind="{kind_name}"}} {r.get(key, 0) or 0}'
            )
        if folded:
            lines.append(
                "torrent_tpu_fleet_pid_units"
                f'{{pid="overflow",kind="{kind_name}"}} '
                f"{sum(r.get(key, 0) or 0 for r in folded)}"
            )
    lines += [
        "# HELP torrent_tpu_fleet_digest_dropped_total Heartbeats that shed their obs digest to fit the transport buffer",
        "# TYPE torrent_tpu_fleet_digest_dropped_total counter",
        f"torrent_tpu_fleet_digest_dropped_total {s.get('digest_drops', 0)}",
    ]
    # fleet-wide SLO budget health: the worst heartbeat-carried burn
    # rate across reporting processes (absent when no peer armed an
    # engine — the series simply don't exist)
    slo = s.get("slo")
    if isinstance(slo, dict):
        lines += [
            "# HELP torrent_tpu_fleet_slo_worst_burn_rate Worst short-window error-budget burn rate across the fleet",
            "# TYPE torrent_tpu_fleet_slo_worst_burn_rate gauge",
            "torrent_tpu_fleet_slo_worst_burn_rate"
            f'{{pid="{slo.get("pid", 0)}",objective="{_esc(str(slo.get("objective", "")))}"}} '
            f"{slo.get('worst_burn') or 0.0}",
            "# HELP torrent_tpu_fleet_slo_breaching Reporting processes whose digest carries an active SLO breach",
            "# TYPE torrent_tpu_fleet_slo_breaching gauge",
            f"torrent_tpu_fleet_slo_breaching {slo.get('breaching', 0)}",
        ]
    return "\n".join(lines) + "\n"


def render_timeline_metrics(snapshot: dict) -> str:
    """Prometheus rendering of a timeline ring
    (``obs.timeline.Timeline.snapshot()``; the caller may merge a
    ``sampler_alive`` bool in). Appended to /metrics only while a
    timeline is armed — the series simply don't exist otherwise.
    Defensive against partial snapshots: missing keys render as 0."""
    s = snapshot or {}
    # ring fill: prefer the O(1) `fill` counter (Timeline.stats()); a
    # full snapshot's sample list still works
    samples = s.get("samples") or []
    fill = s.get("fill")
    if fill is None:
        fill = len(samples) if isinstance(samples, list) else 0
    lines = [
        "# HELP torrent_tpu_timeline_samples_total Timeline samples captured since start",
        "# TYPE torrent_tpu_timeline_samples_total counter",
        f"torrent_tpu_timeline_samples_total {s.get('seq', 0)}",
        "# HELP torrent_tpu_timeline_dropped_total Samples that fell off the bounded ring",
        "# TYPE torrent_tpu_timeline_dropped_total counter",
        f"torrent_tpu_timeline_dropped_total {s.get('drops', 0)}",
        "# HELP torrent_tpu_timeline_depth Configured ring depth",
        "# TYPE torrent_tpu_timeline_depth gauge",
        f"torrent_tpu_timeline_depth {s.get('depth', 0)}",
        "# HELP torrent_tpu_timeline_ring_fill Samples currently held in the ring",
        "# TYPE torrent_tpu_timeline_ring_fill gauge",
        f"torrent_tpu_timeline_ring_fill {fill}",
    ]
    if "sampler_alive" in s:
        lines += [
            "# HELP torrent_tpu_timeline_sampler_alive Off-loop sampler thread liveness (0 = readiness problem)",
            "# TYPE torrent_tpu_timeline_sampler_alive gauge",
            f"torrent_tpu_timeline_sampler_alive {1 if s.get('sampler_alive') else 0}",
        ]
    return "\n".join(lines) + "\n"


def render_slo_metrics(report: dict | None) -> str:
    """Prometheus rendering of an SLO evaluation report
    (``obs.slo.evaluate_slo`` / ``SloEngine.report()``). Appended to
    /metrics only while an engine is armed. ``None`` (no report yet)
    renders headers with no samples — never a crash mid-scrape."""
    objectives = (report or {}).get("objectives") or {}
    lines = [
        "# HELP torrent_tpu_slo_budget_remaining Error budget remaining over the long window (1 = untouched)",
        "# TYPE torrent_tpu_slo_budget_remaining gauge",
    ]
    for name in sorted(objectives):
        obj = objectives[name] if isinstance(objectives[name], dict) else {}
        lines.append(
            f'torrent_tpu_slo_budget_remaining{{objective="{_esc(name)}"}} '
            f"{obj.get('budget_remaining', 1.0)}"
        )
    lines += [
        "# HELP torrent_tpu_slo_burn_rate Error-budget burn rate by window (1 = budget spent exactly at the window length)",
        "# TYPE torrent_tpu_slo_burn_rate gauge",
    ]
    for name in sorted(objectives):
        obj = objectives[name] if isinstance(objectives[name], dict) else {}
        lines.append(
            f'torrent_tpu_slo_burn_rate{{objective="{_esc(name)}",window="short"}} '
            f"{obj.get('burn_rate', 0.0)}"
        )
        lines.append(
            f'torrent_tpu_slo_burn_rate{{objective="{_esc(name)}",window="long"}} '
            f"{obj.get('burn_rate_long', 0.0)}"
        )
    lines += [
        "# HELP torrent_tpu_slo_breach Objective breach state (1 = page-now: fast burn or exhausted budget still erroring)",
        "# TYPE torrent_tpu_slo_breach gauge",
    ]
    for name in sorted(objectives):
        obj = objectives[name] if isinstance(objectives[name], dict) else {}
        lines.append(
            f'torrent_tpu_slo_breach{{objective="{_esc(name)}"}} '
            f"{1 if obj.get('breach') else 0}"
        )
    return "\n".join(lines) + "\n"


# per-shard series are bounded: the shard count is operator config, but
# a misconfigured 4096-shard store must still render a bounded scrape —
# shards past the cap fold into one shard="overflow" aggregate
MAX_TRACKER_SHARDS = 32


def render_tracker_metrics(snapshot: dict) -> str:
    """Prometheus rendering of the sharded announce plane
    (``server.shard.ShardedSwarmStore.metrics_snapshot()``, optionally
    carrying an ``indexer`` sub-dict from ``net.indexer.DhtIndexer``).

    Served by the tracker's own ``/metrics`` route; the announce-latency
    log2 histograms (family ``torrent_tpu_tracker_announce_seconds``)
    ride the shared obs registry and render alongside. Defensive against
    partial snapshots — a missing key renders as 0, never a crash
    mid-scrape."""
    s = snapshot or {}
    batch = s.get("batch") or {}
    shards = [sh for sh in s.get("shards") or [] if isinstance(sh, dict)]
    named = shards[:MAX_TRACKER_SHARDS]
    folded = shards[MAX_TRACKER_SHARDS:]
    lines = [
        "# HELP torrent_tpu_tracker_shards Configured announce-store shards",
        "# TYPE torrent_tpu_tracker_shards gauge",
        f"torrent_tpu_tracker_shards {s.get('n_shards', len(shards))}",
        "# HELP torrent_tpu_tracker_announces_total Announce requests processed",
        "# TYPE torrent_tpu_tracker_announces_total counter",
        f"torrent_tpu_tracker_announces_total {s.get('announces', 0)}",
        "# HELP torrent_tpu_tracker_scrapes_total Scrape requests processed",
        "# TYPE torrent_tpu_tracker_scrapes_total counter",
        f"torrent_tpu_tracker_scrapes_total {s.get('scrapes', 0)}",
        "# HELP torrent_tpu_tracker_swarms Swarms currently tracked",
        "# TYPE torrent_tpu_tracker_swarms gauge",
        f"torrent_tpu_tracker_swarms {s.get('swarms', 0)}",
        "# HELP torrent_tpu_tracker_peers Peers currently tracked across all swarms",
        "# TYPE torrent_tpu_tracker_peers gauge",
        f"torrent_tpu_tracker_peers {s.get('peers', 0)}",
        "# HELP torrent_tpu_tracker_evicted_total Peers expired by TTL sweeps",
        "# TYPE torrent_tpu_tracker_evicted_total counter",
        f"torrent_tpu_tracker_evicted_total {s.get('evicted', 0)}",
        "# HELP torrent_tpu_tracker_indexed_total Peers seeded by the DHT indexer",
        "# TYPE torrent_tpu_tracker_indexed_total counter",
        f"torrent_tpu_tracker_indexed_total {s.get('indexed', 0)}",
        "# HELP torrent_tpu_tracker_numwant_clamped_total Announces whose numwant was clamped by the reply bounds",
        "# TYPE torrent_tpu_tracker_numwant_clamped_total counter",
        f"torrent_tpu_tracker_numwant_clamped_total {s.get('numwant_clamped', 0)}",
        "# HELP torrent_tpu_tracker_batches_total Drained announce batches processed",
        "# TYPE torrent_tpu_tracker_batches_total counter",
        f"torrent_tpu_tracker_batches_total {batch.get('batches', 0)}",
        "# HELP torrent_tpu_tracker_batched_announces_total Announces that rode a drained batch",
        "# TYPE torrent_tpu_tracker_batched_announces_total counter",
        f"torrent_tpu_tracker_batched_announces_total {batch.get('announces', 0)}",
        "# HELP torrent_tpu_tracker_batch_max Largest announce batch drained in one pump cycle",
        "# TYPE torrent_tpu_tracker_batch_max gauge",
        f"torrent_tpu_tracker_batch_max {batch.get('max', 0)}",
    ]

    def _shard_series(name, kind, help_text, key):
        lines.append(f"# HELP {name} {help_text}")
        lines.append(f"# TYPE {name} {kind}")
        for i, sh in enumerate(named):
            lines.append(f'{name}{{shard="{i}"}} {sh.get(key, 0)}')
        if folded:
            lines.append(
                f'{name}{{shard="overflow"}} '
                f"{sum(sh.get(key, 0) for sh in folded)}"
            )

    _shard_series(
        "torrent_tpu_tracker_shard_swarms", "gauge",
        "Swarms tracked per shard", "swarms",
    )
    _shard_series(
        "torrent_tpu_tracker_shard_peers", "gauge",
        "Peers tracked per shard", "peers",
    )
    _shard_series(
        "torrent_tpu_tracker_shard_announces_total", "counter",
        "Announces processed per shard", "announces",
    )
    idx = s.get("indexer")
    if isinstance(idx, dict):
        harvested = idx.get("harvested") or {}
        lines += [
            "# HELP torrent_tpu_tracker_indexer_hashes Distinct info-hashes the indexer has discovered (bounded set)",
            "# TYPE torrent_tpu_tracker_indexer_hashes gauge",
            f"torrent_tpu_tracker_indexer_hashes {idx.get('hashes', 0)}",
            "# HELP torrent_tpu_tracker_indexer_harvested_total Inbound DHT queries harvested by kind",
            "# TYPE torrent_tpu_tracker_indexer_harvested_total counter",
        ]
        for kind in ("get_peers", "announce_peer"):
            lines.append(
                "torrent_tpu_tracker_indexer_harvested_total"
                f'{{kind="{kind}"}} {harvested.get(kind, 0)}'
            )
        lines += [
            "# HELP torrent_tpu_tracker_indexer_fed_peers_total Harvested peers fed into the sharded store",
            "# TYPE torrent_tpu_tracker_indexer_fed_peers_total counter",
            f"torrent_tpu_tracker_indexer_fed_peers_total {idx.get('fed_peers', 0)}",
            "# HELP torrent_tpu_tracker_indexer_crawls_total Active crawl steps completed",
            "# TYPE torrent_tpu_tracker_indexer_crawls_total counter",
            f"torrent_tpu_tracker_indexer_crawls_total {idx.get('crawls', 0)}",
            "# HELP torrent_tpu_tracker_indexer_sampled_total Info-hashes received from BEP 51 samples",
            "# TYPE torrent_tpu_tracker_indexer_sampled_total counter",
            f"torrent_tpu_tracker_indexer_sampled_total {idx.get('crawl_samples', 0)}",
        ]
    return "\n".join(lines) + "\n"


# peers named individually on a scrape; the snapshot already folds the
# rest into its own "overflow" aggregate (obs/swarm.TOP_PEERS), so the
# per-peer family cardinality is bounded no matter how wide the swarm
_SWARM_TRIGGERS = ("snub_storm", "all_peers_choked", "announce_failure_streak")

# the serve plane's fixed egress fallback matrix and bounded reject
# reasons; literals here (obs.hist imports this module, so importing
# serve_plane.telemetry back would cycle) — parity is pinned by a test
# against serve_plane.telemetry.EGRESS_PATHS/REJECT_REASONS
_SERVE_PATHS = ("sendfile", "preadv", "copy")
_SERVE_REJECT_REASONS = ("backpressure", "per_ip", "capacity", "choked")


def render_swarm_metrics(snapshot: dict) -> str:
    """Prometheus rendering of the swarm wire plane
    (``obs.swarm.SwarmTelemetry.snapshot()`` /
    ``build_swarm_snapshot``).

    Two families: process-level ``torrent_tpu_swarm_*`` (cumulative
    totals, live counts, message-kind accounting, flight-trigger
    counters) and bounded per-peer ``torrent_tpu_peer_*`` — the
    snapshot's top-K named peers plus one ``peer="overflow"`` fold.
    Defensive against partial snapshots: missing keys render as 0,
    never a crash mid-scrape."""
    s = snapshot or {}
    counts = s.get("counts") or {}
    totals = s.get("totals") or {}
    peers = {
        k: v for k, v in (s.get("peers") or {}).items() if isinstance(v, dict)
    }
    overflow = s.get("overflow") if isinstance(s.get("overflow"), dict) else None
    lines = [
        "# HELP torrent_tpu_swarm_peers Peers currently connected across all torrents (telemetry view)",
        "# TYPE torrent_tpu_swarm_peers gauge",
        f"torrent_tpu_swarm_peers {counts.get('connected', 0)}",
        "# HELP torrent_tpu_swarm_peers_snubbed Connected peers currently flagged snubbed",
        "# TYPE torrent_tpu_swarm_peers_snubbed gauge",
        f"torrent_tpu_swarm_peers_snubbed {counts.get('snubbed', 0)}",
        "# HELP torrent_tpu_swarm_peers_choking_us Connected peers currently choking us",
        "# TYPE torrent_tpu_swarm_peers_choking_us gauge",
        f"torrent_tpu_swarm_peers_choking_us {counts.get('choking_us', 0)}",
        "# HELP torrent_tpu_swarm_peers_unchoked Connected peers we are currently unchoking",
        "# TYPE torrent_tpu_swarm_peers_unchoked gauge",
        f"torrent_tpu_swarm_peers_unchoked {counts.get('unchoked_by_us', 0)}",
        "# HELP torrent_tpu_swarm_connections_total Peer connections registered since start",
        "# TYPE torrent_tpu_swarm_connections_total counter",
        f"torrent_tpu_swarm_connections_total {totals.get('connections', 0)}",
        "# HELP torrent_tpu_swarm_bytes_total Wire payload bytes by direction",
        "# TYPE torrent_tpu_swarm_bytes_total counter",
        f'torrent_tpu_swarm_bytes_total{{direction="down"}} {totals.get("bytes_down", 0)}',
        f'torrent_tpu_swarm_bytes_total{{direction="up"}} {totals.get("bytes_up", 0)}',
        "# HELP torrent_tpu_swarm_blocks_total Payload blocks received",
        "# TYPE torrent_tpu_swarm_blocks_total counter",
        f"torrent_tpu_swarm_blocks_total {totals.get('blocks', 0)}",
        "# HELP torrent_tpu_swarm_snubs_total Peer snub transitions observed",
        "# TYPE torrent_tpu_swarm_snubs_total counter",
        f"torrent_tpu_swarm_snubs_total {totals.get('snubs', 0)}",
        "# HELP torrent_tpu_swarm_endgame_cancels_total Duplicate-block cancels broadcast in endgame",
        "# TYPE torrent_tpu_swarm_endgame_cancels_total counter",
        f"torrent_tpu_swarm_endgame_cancels_total {totals.get('endgame_cancels', 0)}",
        "# HELP torrent_tpu_swarm_rejects_total BEP 6 RejectRequests received",
        "# TYPE torrent_tpu_swarm_rejects_total counter",
        f"torrent_tpu_swarm_rejects_total {totals.get('rejects', 0)}",
        "# HELP torrent_tpu_swarm_announce_total Tracker announces by outcome",
        "# TYPE torrent_tpu_swarm_announce_total counter",
        f'torrent_tpu_swarm_announce_total{{result="ok"}} {totals.get("announce_ok", 0)}',
        f'torrent_tpu_swarm_announce_total{{result="failed"}} {totals.get("announce_failed", 0)}',
        "# HELP torrent_tpu_swarm_announce_failure_streak Consecutive announce failures right now",
        "# TYPE torrent_tpu_swarm_announce_failure_streak gauge",
        f"torrent_tpu_swarm_announce_failure_streak {totals.get('announce_streak', 0)}",
        "# HELP torrent_tpu_swarm_messages_total Wire messages by kind (bounded kind set)",
        "# TYPE torrent_tpu_swarm_messages_total counter",
    ]
    msgs = s.get("msgs") or {}
    for kind in sorted(msgs):
        m = msgs[kind] if isinstance(msgs[kind], dict) else {}
        lines.append(
            f'torrent_tpu_swarm_messages_total{{kind="{_esc(str(kind))}"}} '
            f"{m.get('count', 0)}"
        )
    lines.append(
        "# HELP torrent_tpu_swarm_message_bytes_total Wire message payload bytes by kind"
    )
    lines.append("# TYPE torrent_tpu_swarm_message_bytes_total counter")
    for kind in sorted(msgs):
        m = msgs[kind] if isinstance(msgs[kind], dict) else {}
        lines.append(
            f'torrent_tpu_swarm_message_bytes_total{{kind="{_esc(str(kind))}"}} '
            f"{m.get('bytes', 0)}"
        )
    lines.append(
        "# HELP torrent_tpu_swarm_flight_triggers_total Swarm flight-recorder dumps by trigger"
    )
    lines.append("# TYPE torrent_tpu_swarm_flight_triggers_total counter")
    triggers = s.get("triggers") or {}
    for reason in _SWARM_TRIGGERS:
        lines.append(
            f'torrent_tpu_swarm_flight_triggers_total{{reason="{reason}"}} '
            f"{triggers.get(reason, 0)}"
        )

    def _peer_series(name, kind, help_text, get, overflow_get=None):
        lines.append(f"# HELP {name} {help_text}")
        lines.append(f"# TYPE {name} {kind}")
        for key in sorted(peers):
            lines.append(f'{name}{{peer="{_esc(str(key))}"}} {get(peers[key])}')
        if overflow is not None:
            lines.append(
                f'{name}{{peer="overflow"}} '
                f"{(overflow_get or get)(overflow)}"
            )

    _peer_series(
        "torrent_tpu_peer_bytes_down_total", "counter",
        "Payload bytes received from this peer",
        lambda p: p.get("bytes_down", 0),
    )
    _peer_series(
        "torrent_tpu_peer_bytes_up_total", "counter",
        "Payload bytes served to this peer",
        lambda p: p.get("bytes_up", 0),
    )
    _peer_series(
        "torrent_tpu_peer_blocks_total", "counter",
        "Payload blocks received from this peer",
        lambda p: p.get("blocks", 0),
    )
    from torrent_tpu.obs.hist import BUCKET_BOUNDS as _RTT_BOUNDS

    def _rtt_p99(p):
        rtt = p.get("block_rtt") or {}
        if rtt.get("p99_overflow"):
            # the p99 landed in the +Inf bucket: report the top finite
            # bound so a `p99 > threshold` alert FIRES — rendering 0
            # would report best-case latency exactly when latency is
            # pathological (the PR 14 Infinity/None inversion)
            return _RTT_BOUNDS[-1]
        return rtt.get("p99_s") or 0

    _peer_series(
        "torrent_tpu_peer_block_rtt_p99_seconds", "gauge",
        "p99 block round-trip upper bound for this peer (log2 buckets; "
        "overflow reports the top finite bound)",
        _rtt_p99,
    )
    _peer_series(
        "torrent_tpu_peer_pipeline_depth", "gauge",
        "Outstanding block requests to this peer right now",
        lambda p: (p.get("pipeline") or {}).get("depth", 0),
        # the overflow fold sums live depths across the folded peers
        overflow_get=lambda o: o.get("depth", 0),
    )
    _peer_series(
        "torrent_tpu_peer_choking_us", "gauge",
        "1 while this peer is choking us",
        lambda p: 1 if (p.get("state") or {}).get("peer_choking") else 0,
        # a 0/1 flag doesn't fold; the overflow row reports the folded
        # snubbed-peer count's complement as 0 (alerts key on named rows)
        overflow_get=lambda o: 0,
    )
    _peer_series(
        "torrent_tpu_peer_snubs_total", "counter",
        "Snub transitions this peer accumulated",
        lambda p: p.get("snubs", 0),
    )
    return "\n".join(lines) + "\n"


def render_serve_metrics(snapshot: dict) -> str:
    """Prometheus rendering of the seeder plane
    (``serve_plane.telemetry.ServeTelemetry.snapshot()`` /
    ``build_serve_snapshot``).

    Process-level ``torrent_tpu_serve_*``: egress bytes/blocks by path
    (the zero-copy fallback matrix — ``sendfile``/``preadv``/``copy``),
    reject accounting by reason, choke-round counters plus a real
    log2-bucket duration histogram, and accept-gate evictions. Bounded
    per-peer ``torrent_tpu_serve_peer_*``: the snapshot's top-K
    uploaded-to peers plus one ``peer="overflow"`` fold. Defensive
    against partial snapshots: missing keys render as 0, never a crash
    mid-scrape."""
    s = snapshot if isinstance(snapshot, dict) else {}

    def _d(v):
        return v if isinstance(v, dict) else {}

    def _n(v):
        ok = isinstance(v, (int, float)) and not isinstance(v, bool)
        return v if ok else 0

    counts = _d(s.get("counts"))
    totals = _d(s.get("totals"))
    paths = {
        k: v for k, v in _d(s.get("paths")).items() if isinstance(v, dict)
    }
    choke = _d(s.get("choke"))
    last = _d(choke.get("last"))
    round_s = _d(choke.get("round_s"))
    peers = {
        k: v for k, v in _d(s.get("peers")).items() if isinstance(v, dict)
    }
    overflow = s.get("overflow") if isinstance(s.get("overflow"), dict) else None
    lines = [
        "# HELP torrent_tpu_serve_peers Peers currently tracked by the serve plane",
        "# TYPE torrent_tpu_serve_peers gauge",
        f"torrent_tpu_serve_peers {_n(counts.get('serving'))}",
        "# HELP torrent_tpu_serve_bytes_total Payload bytes served by egress path",
        "# TYPE torrent_tpu_serve_bytes_total counter",
    ]
    # the fixed fallback-matrix columns always render (a dashboard can
    # rate() them from first scrape); unexpected extras append sorted
    path_names = list(_SERVE_PATHS) + sorted(
        k for k in paths if k not in _SERVE_PATHS
    )
    for p in path_names:
        row = paths.get(p) or {}
        lines.append(
            f'torrent_tpu_serve_bytes_total{{path="{_esc(str(p))}"}} '
            f"{_n(row.get('bytes'))}"
        )
    lines.append(
        "# HELP torrent_tpu_serve_blocks_total Payload blocks served by egress path"
    )
    lines.append("# TYPE torrent_tpu_serve_blocks_total counter")
    for p in path_names:
        row = paths.get(p) or {}
        lines.append(
            f'torrent_tpu_serve_blocks_total{{path="{_esc(str(p))}"}} '
            f"{_n(row.get('blocks'))}"
        )
    lines.append(
        "# HELP torrent_tpu_serve_rejects_total Serve-side rejections by reason"
    )
    lines.append("# TYPE torrent_tpu_serve_rejects_total counter")
    for reason in _SERVE_REJECT_REASONS:
        lines.append(
            f'torrent_tpu_serve_rejects_total{{reason="{reason}"}} '
            f"{_n(totals.get(f'rejects_{reason}'))}"
        )
    lines += [
        "# HELP torrent_tpu_serve_gate_evictions_total Idle peers evicted by the accept gate",
        "# TYPE torrent_tpu_serve_gate_evictions_total counter",
        f"torrent_tpu_serve_gate_evictions_total {_n(totals.get('gate_evictions'))}",
        "# HELP torrent_tpu_serve_queue_cancels_total Queued requests removed by BEP 3 Cancel before a worker served them",
        "# TYPE torrent_tpu_serve_queue_cancels_total counter",
        f"torrent_tpu_serve_queue_cancels_total {_n(totals.get('queue_cancels'))}",
        "# HELP torrent_tpu_serve_choke_rounds_total Unchoke rounds completed",
        "# TYPE torrent_tpu_serve_choke_rounds_total counter",
        f"torrent_tpu_serve_choke_rounds_total {_n(totals.get('rounds'))}",
        "# HELP torrent_tpu_serve_optimistic_rotations_total Optimistic unchoke slot rotations",
        "# TYPE torrent_tpu_serve_optimistic_rotations_total counter",
        f"torrent_tpu_serve_optimistic_rotations_total {_n(totals.get('optimistic_rotations'))}",
        "# HELP torrent_tpu_serve_unchoked Peers unchoked by the last choke round",
        "# TYPE torrent_tpu_serve_unchoked gauge",
        f"torrent_tpu_serve_unchoked {_n(last.get('unchoked'))}",
        "# HELP torrent_tpu_serve_interested Interested candidates seen by the last choke round",
        "# TYPE torrent_tpu_serve_interested gauge",
        f"torrent_tpu_serve_interested {_n(last.get('interested'))}",
        "# HELP torrent_tpu_serve_choke_round_seconds Choke-round wall duration (log2 buckets)",
        "# TYPE torrent_tpu_serve_choke_round_seconds histogram",
    ]
    from torrent_tpu.obs.hist import BUCKET_BOUNDS as _ROUND_BOUNDS

    bucket_counts = choke.get("round_counts")
    bucket_counts = bucket_counts if isinstance(bucket_counts, list) else []
    cum = 0
    for i, bound in enumerate(_ROUND_BOUNDS):
        c = bucket_counts[i] if i < len(bucket_counts) else 0
        cum += c if isinstance(c, int) else 0
        lines.append(
            f'torrent_tpu_serve_choke_round_seconds_bucket{{le="{bound:.10g}"}} {cum}'
        )
    count = _n(round_s.get("count"))
    lines.append(
        f'torrent_tpu_serve_choke_round_seconds_bucket{{le="+Inf"}} {count}'
    )
    total_s = _n(round_s.get("mean_s")) * count
    lines.append(f"torrent_tpu_serve_choke_round_seconds_sum {total_s:.9g}")
    lines.append(f"torrent_tpu_serve_choke_round_seconds_count {count}")

    def _serve_peer_series(name, kind, help_text, get):
        lines.append(f"# HELP {name} {help_text}")
        lines.append(f"# TYPE {name} {kind}")
        for key in sorted(peers):
            lines.append(f'{name}{{peer="{_esc(str(key))}"}} {get(peers[key])}')
        if overflow is not None:
            lines.append(f'{name}{{peer="overflow"}} {get(overflow)}')

    _serve_peer_series(
        "torrent_tpu_serve_peer_bytes_total", "counter",
        "Payload bytes served to this peer",
        lambda p: _n(p.get("bytes_up")),
    )
    _serve_peer_series(
        "torrent_tpu_serve_peer_blocks_total", "counter",
        "Payload blocks served to this peer",
        lambda p: _n(p.get("blocks")),
    )
    _serve_peer_series(
        "torrent_tpu_serve_peer_rejects_total", "counter",
        "Requests from this peer rejected by the serve plane",
        lambda p: _n(p.get("rejects")),
    )
    return "\n".join(lines) + "\n"


def render_metrics(client) -> str:
    """The /metrics payload for one Client (Prometheus text format 0.0.4).

    Session-level figures come from ``Client.status()`` — the single
    aggregation every status surface shares — so /metrics can never
    silently diverge from it."""
    status = client.status()
    lines = [
        "# HELP torrent_tpu_torrents Torrents registered in this client",
        "# TYPE torrent_tpu_torrents gauge",
        f"torrent_tpu_torrents {len(client.torrents)}",
        "# HELP torrent_tpu_peers Connected peers across all torrents",
        "# TYPE torrent_tpu_peers gauge",
        f"torrent_tpu_peers {status['peers']}",
        "# HELP torrent_tpu_downloaded_bytes_total Payload bytes downloaded",
        "# TYPE torrent_tpu_downloaded_bytes_total counter",
        f"torrent_tpu_downloaded_bytes_total {status['downloaded']}",
        "# HELP torrent_tpu_uploaded_bytes_total Payload bytes uploaded",
        "# TYPE torrent_tpu_uploaded_bytes_total counter",
        f"torrent_tpu_uploaded_bytes_total {status['uploaded']}",
    ]
    def of_status(key):
        return lambda t: status["torrents"][t.metainfo.info_hash.hex()][key]

    per_torrent = [
        ("torrent_tpu_torrent_peers", "gauge", "Connected peers", lambda t: len(t.peers)),
        (
            "torrent_tpu_torrent_pieces_have",
            "gauge",
            "Verified pieces on disk",
            lambda t: t.bitfield.count(),
        ),
        (
            "torrent_tpu_torrent_pieces_total",
            "gauge",
            "Pieces in the torrent",
            lambda t: t.info.num_pieces,
        ),
        (
            "torrent_tpu_torrent_left_bytes",
            "gauge",
            "Wanted bytes not yet verified",
            lambda t: t.left,
        ),
        (
            "torrent_tpu_torrent_downloaded_bytes_total",
            "counter",
            "Payload bytes downloaded",
            lambda t: t.downloaded,
        ),
        (
            "torrent_tpu_torrent_uploaded_bytes_total",
            "counter",
            "Payload bytes uploaded",
            lambda t: t.uploaded,
        ),
        # a piece at the judge has an owner (session/torrent.py): how many
        # are there now, how often that kept a second peer off one, and
        # the deliveries that reached the judge for a piece that had one (0)
        (
            "torrent_tpu_torrent_judging",
            "gauge",
            "Pieces at the judge (verdict awaited or being written)",
            of_status("judging"),
        ),
        (
            "torrent_tpu_torrent_judging_skips_total",
            "counter",
            "Picker scans that passed over a piece at the judge, and late blocks of one dropped",
            of_status("judging_skips"),
        ),
        (
            "torrent_tpu_torrent_duplicate_judged_total",
            "counter",
            "Complete deliveries of a piece already valid or at the judge",
            of_status("duplicate_judged"),
        ),
        # the peer loop hands a piece to a judge task and goes on: how often
        # its peer had one there already, how often the bound held it, and
        # the most pieces at the judge at once
        (
            "torrent_tpu_torrent_judge_overlapped_total",
            "counter",
            "Deliveries handed to the judge while the same peer had a piece there",
            of_status("judge_overlapped"),
        ),
        (
            "torrent_tpu_torrent_judge_bound_waits_total",
            "counter",
            "Hand-offs that first waited for the peer's oldest verdict",
            of_status("judge_bound_waits"),
        ),
        (
            "torrent_tpu_torrent_judging_peak",
            "gauge",
            "Most pieces at the judge at once since the torrent started",
            of_status("judging_peak"),
        ),
    ]
    for name, kind, help_text, get in per_torrent:
        lines.append(f"# HELP {name} {help_text}")
        lines.append(f"# TYPE {name} {kind}")
        for ih, t in client.torrents.items():
            labels = f'info_hash="{ih.hex()}",name="{_esc(str(t.info.name))}"'
            lines.append(f"{name}{{{labels}}} {get(t)}")
    # state as a labeled 0/1 family (the Prometheus idiom for enums)
    lines.append("# HELP torrent_tpu_torrent_state Torrent lifecycle state (1 = current)")
    lines.append("# TYPE torrent_tpu_torrent_state gauge")
    for ih, t in client.torrents.items():
        current = t.state.name.lower()
        for state in ("stopped", "checking", "downloading", "seeding"):
            lines.append(
                f'torrent_tpu_torrent_state{{info_hash="{ih.hex()}",state="{state}"}} '
                f"{1 if state == current else 0}"
            )
    from torrent_tpu.storage.storage import storage_write_stats

    return "\n".join(lines) + "\n" + render_storage_write_metrics(storage_write_stats())


class MetricsServer:
    """``GET /metrics`` + ``GET /v1/swarm`` for one Client; anything
    else is 404. ``/v1/swarm`` serves the swarm wire plane's bounded
    per-peer telemetry snapshot (obs/swarm) as JSON — the same payload
    the bridge's route answers, so ``torrent-tpu top --swarm`` can
    point at either endpoint.

    ``scheduler``: optionally a hash-plane scheduler whose queue/fill/
    shed counters are appended to the session exposition, so one scrape
    covers both the swarm and the verify queue it feeds; without one,
    the client's own ingest scheduler (``hasher="tpu"``) is rendered.
    ``fabric``: optionally a running ``FabricExecutor`` — its per-shard
    gauges AND its fleet rollup (``torrent_tpu_fleet_*``) join the same
    exposition, so the session endpoint carries the swarm-wide view just
    like the bridge's does.
    ``controller``: optionally a ``SchedulerAutopilot`` whose
    ``torrent_tpu_control_*`` series join the exposition too — both
    /metrics endpoints carry the observe→act loop's state."""

    def __init__(self, client, host: str = "127.0.0.1", scheduler=None, fabric=None,
                 controller=None):
        self.client = client
        self.scheduler = scheduler
        self.fabric = fabric
        self.controller = controller
        self.host = host
        self.port: int | None = None
        self._server: asyncio.AbstractServer | None = None
        self._handlers: set[asyncio.Task] = set()

    async def start(self, port: int = 0) -> "MetricsServer":
        self._server = await asyncio.start_server(self._accept, self.host, port)
        self.port = self._server.sockets[0].getsockname()[1]
        return self

    def _accept(self, reader, writer):
        # tracked so close() can cancel a stalled scraper's handler
        task = asyncio.ensure_future(self._handle(reader, writer))
        self._handlers.add(task)
        task.add_done_callback(self._handlers.discard)

    def close(self) -> None:
        if self._server is not None:
            self._server.close()
        for task in list(self._handlers):
            task.cancel()

    async def _handle(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter):
        try:
            request = await asyncio.wait_for(reader.readline(), timeout=10)
            while True:
                line = await asyncio.wait_for(reader.readline(), timeout=10)
                if line in (b"\r\n", b"\n", b""):
                    break
            parts = request.split()
            if (
                len(parts) >= 2
                and parts[0] == b"GET"
                and parts[1].split(b"?")[0] == b"/v1/swarm"
            ):
                import json as _json

                from torrent_tpu.obs.swarm import swarm_telemetry
                from torrent_tpu.serve_plane.telemetry import serve_telemetry

                payload = swarm_telemetry().snapshot()
                serve_obs = serve_telemetry()
                if serve_obs.active():
                    # the serving-side view rides the same endpoint: who
                    # we are feeding, over which egress paths
                    payload["serve"] = serve_obs.snapshot()
                body = _json.dumps(payload, sort_keys=True).encode()
                status = "200 OK"
                ctype = "application/json"
            elif len(parts) >= 2 and parts[0] == b"GET" and parts[1].split(b"?")[0] == b"/metrics":
                text = render_metrics(self.client)
                # a hasher='tpu' client's own ingest scheduler (tenant
                # "ingest") is in its exposition without being handed over
                sched = self.scheduler or self.client.ingest_scheduler
                if sched is not None:
                    text += render_sched_metrics(sched)
                if self.fabric is not None:
                    text += render_fabric_metrics(self.fabric.metrics_snapshot())
                    text += render_fleet_metrics(self.fabric.fleet_snapshot())
                if self.controller is not None:
                    text += render_control_metrics(
                        self.controller.metrics_snapshot()
                    )
                from torrent_tpu.obs import render_obs_metrics

                text += render_obs_metrics()
                # SLO-series parity with the bridge: when this process
                # armed an engine (obs/slo), its budget/burn/breach
                # series join the session exposition too
                from torrent_tpu.obs.slo import armed as _slo_armed

                engine = _slo_armed()
                if engine is not None:
                    text += render_slo_metrics(engine.report())
                from torrent_tpu.analysis import sanitizer

                if sanitizer.is_enabled():
                    text += render_tsan_metrics(sanitizer.snapshot())
                body = text.encode()
                status = "200 OK"
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            else:
                body = b"not found\n"
                status = "404 Not Found"
                ctype = "text/plain"
            writer.write(
                (
                    f"HTTP/1.1 {status}\r\nContent-Type: {ctype}\r\n"
                    f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
                ).encode("latin-1")
                + body
            )
            await writer.drain()
        except (ConnectionError, asyncio.TimeoutError, asyncio.LimitOverrunError, ValueError, OSError):
            pass
        finally:
            writer.close()
