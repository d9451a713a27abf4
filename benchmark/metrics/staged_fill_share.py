"""Share of the rows that zero-copy launches uploaded in the window that
held a piece: Δ``staged_live_rows_total`` ÷ Δ``staged_rows_total`` over the
lanes of ``metrics_snapshot()["lane_stats"]`` (a staged slab is launched
whole, at its lane's target, whatever rows are live). ``None`` where the run
has no scheduler, the program keeps no such counters, or no launch was
staged."""
SOURCE = "sched_snapshot"


def read(obs):
    if obs.get("sched") is None:
        return None
    before, after = obs["sched"]
    rows = live = 0
    for lane, a in after.get("lane_stats", {}).items():
        if "staged_rows_total" not in a:
            return None
        b = before.get("lane_stats", {}).get(lane, {})
        rows += a["staged_rows_total"] - b.get("staged_rows_total", 0)
        live += a["staged_live_rows_total"] - b.get("staged_live_rows_total", 0)
    return 100.0 * live / rows if rows else None
