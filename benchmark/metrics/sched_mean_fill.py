"""Mean fill of the scheduler's launches in the window, from
``metrics_snapshot()`` deltas."""
SOURCE = "sched_snapshot"


def read(obs):
    if obs.get("sched") is None:
        return None
    before, after = obs["sched"]
    launches = after["launches"] - before["launches"]
    return 100.0 * (after["fill_sum"] - before["fill_sum"]) / launches if launches else None
