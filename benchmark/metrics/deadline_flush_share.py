"""Share of the scheduler's launches in the window that the flush deadline
sent, not a full take: Δ``flush_reasons["deadline"]`` ÷ Δ``launches`` of
``metrics_snapshot()``. ``None`` where the run has no scheduler, the program
keeps no flush reasons, or nothing was launched."""
SOURCE = "sched_snapshot"


def read(obs):
    if obs.get("sched") is None:
        return None
    before, after = obs["sched"]
    if "deadline" not in after.get("flush_reasons", {}):
        return None
    launches = after["launches"] - before["launches"]
    flushed = after["flush_reasons"]["deadline"] - before.get("flush_reasons", {}).get("deadline", 0)
    return 100.0 * flushed / launches if launches else None
