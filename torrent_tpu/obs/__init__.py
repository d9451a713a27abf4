"""torrent_tpu.obs — the observability plane.

Three tiers over the same ticket lifecycle, cheapest first:

1. **Latency histograms** (``obs/hist``) and the **pipeline ledger**
   (``obs/ledger`` + ``obs/attrib``): always-on fixed-log2-bucket
   per-stage distributions (queue wait, launch, end-to-end per tenant,
   bridge request) plus byte/time/occupancy accounting at every
   pipeline stage boundary (read → stage → h2d → launch → digest →
   verdict) feeding a bottleneck attributor — rendered as real
   Prometheus series on every ``/metrics`` scrape and served as
   ``GET /v1/pipeline`` / ``torrent-tpu top`` / ``doctor
   --bottleneck``.
2. **Span tracer** (``obs/tracer``): per-trace span trees — trace IDs
   minted at the bridge (``X-Trace-Id`` honored/emitted), threaded
   through the scheduler's ticket lifecycle and the fabric's units,
   served by ``GET /v1/trace?id=…``.
3. **Profiler** (``obs/profiler``): ``jax.profiler`` device-timeline
   capture of the first N batches (``TORRENT_TPU_PROFILE``), the
   deep-dive tier.

Plus the **fleet plane** (``obs/fleet``): a compact deterministic
per-process obs digest carried on every fabric heartbeat, merged into a
swarm-wide rollup with two-level bottleneck attribution (limiting
process → its limiting stage) and a straggler scoreboard — served as
``GET /v1/fleet``, ``torrent_tpu_fleet_*`` Prometheus series,
``torrent-tpu top --fleet``, and ``doctor --fleet``.

Plus the **flight recorder** (``obs/recorder``): a bounded ring of
recent spans + component snapshots, dumped as redacted black-box JSON
on breaker-open, retry-exhausted failure, fabric distrust, or an
observed lock-order cycle — ``GET /v1/trace``, ``torrent-tpu trace
dump``, ``doctor --trace``.

Everything here locks via ``analysis.sanitizer.named_lock`` (obs locks
are leaves of the lock-order graph) and keeps exchanged/dumped bytes
deterministic: monotonic-only timestamps, sorted keys.
"""

import sys

from torrent_tpu.obs.attrib import attribute, format_report
from torrent_tpu.obs.fleet import (
    DIGEST_MAX_BYTES,
    aggregate_fleet,
    local_fleet_snapshot,
    obs_digest,
)
from torrent_tpu.obs.hist import (
    HistogramRegistry,
    LogHistogram,
    histograms,
    merge_snapshots,
)
from torrent_tpu.obs.ledger import (
    PIPELINE_STAGES,
    PipelineLedger,
    pipeline_ledger,
    render_pipeline_metrics,
)
from torrent_tpu.obs.recorder import FlightRecorder, flight_recorder
from torrent_tpu.obs.swarm import (
    SwarmTelemetry,
    build_swarm_snapshot,
    swarm_telemetry,
)
from torrent_tpu.obs.slo import (
    SloEngine,
    SloObjective,
    build_health,
    evaluate_slo,
    parse_objectives,
)
from torrent_tpu.obs.timeline import (
    Timeline,
    TimelineSampler,
    build_sample,
    replay_report,
)
from torrent_tpu.obs.tracer import (
    Span,
    Tracer,
    fabric_trace_id,
    heartbeat_span_context,
    tracer,
    valid_trace_id,
)

__all__ = [
    "DIGEST_MAX_BYTES",
    "FlightRecorder",
    "HistogramRegistry",
    "LogHistogram",
    "PIPELINE_STAGES",
    "PipelineLedger",
    "SloEngine",
    "SloObjective",
    "Span",
    "SwarmTelemetry",
    "Timeline",
    "TimelineSampler",
    "Tracer",
    "aggregate_fleet",
    "build_swarm_snapshot",
    "attribute",
    "build_health",
    "build_sample",
    "evaluate_slo",
    "parse_objectives",
    "replay_report",
    "fabric_trace_id",
    "flight_recorder",
    "format_report",
    "heartbeat_span_context",
    "histograms",
    "local_fleet_snapshot",
    "merge_snapshots",
    "obs_digest",
    "pipeline_ledger",
    "render_obs_metrics",
    "render_pipeline_metrics",
    "swarm_telemetry",
    "tracer",
    "valid_trace_id",
]


def render_obs_metrics() -> str:
    """The obs plane's /metrics contribution: every latency-histogram
    family, the pipeline ledger's per-stage series + bottleneck verdict,
    the swarm wire-plane families (``torrent_tpu_swarm_*`` + bounded
    ``torrent_tpu_peer_*``), the seeder plane's ``torrent_tpu_serve_*``
    (only once this process has actually served — tracker-only scrapes
    stay lean), the jitted SHA-1 steps' build and reuse counters and a
    recheck's kept staging pair's (only once this process has imported
    the verifier, and so JAX), the v2
    leaf plane's launch, row and slab counters (once ``models/v2`` is in), and the
    flight-recorder dump counters. Appended by both the bridge's
    ``/metrics`` and the session ``MetricsServer``."""
    from torrent_tpu.serve_plane.telemetry import serve_telemetry
    from torrent_tpu.utils.metrics import (
        render_leaf_metrics,
        render_leaf_slab_metrics,
        render_serve_metrics,
        render_staging_slab_metrics,
        render_step_metrics,
        render_swarm_metrics,
    )

    serve_obs = serve_telemetry()
    verifier = sys.modules.get("torrent_tpu.models.verifier")
    v2 = sys.modules.get("torrent_tpu.models.v2")
    return (
        histograms().render()
        + render_pipeline_metrics()
        + (render_step_metrics(verifier.step_cache_stats()) if verifier else "")
        + (render_staging_slab_metrics(verifier.staging_slab_stats()) if verifier else "")
        + (render_leaf_metrics(v2.leaf_launch_stats()) if v2 else "")
        + (render_leaf_slab_metrics(v2.leaf_slab_stats()) if v2 else "")
        + render_swarm_metrics(swarm_telemetry().snapshot())
        + (
            render_serve_metrics(serve_obs.snapshot())
            if serve_obs.active()
            else ""
        )
        + flight_recorder().render_metrics()
    )
