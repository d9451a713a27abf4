"""Pieces per scheduler launch in the window: each lane's fill times its
target, from ``metrics_snapshot()`` deltas."""
SOURCE = "sched_snapshot"


def read(obs):
    if obs.get("sched") is None:
        return None
    before, after = obs["sched"]
    launches = after["launches"] - before["launches"]
    if not launches:
        return None
    pieces = 0.0
    for lane, a in after["lane_stats"].items():
        b = before["lane_stats"].get(lane, {"mean_fill": 0.0, "launches": 0})
        pieces += (a["mean_fill"] * a["launches"] - b["mean_fill"] * b["launches"]) * a["target"]
    return pieces / launches
