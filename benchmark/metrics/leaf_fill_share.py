"""Share of the leaf rows launched in the window that held a block to
hash: the program's counters ``rows_live`` over ``rows_launched``, both
kernels together (``models/v2.leaf_launch_stats``). Leaf batches are pow-2
bucketed, so the rest is padding that was staged and uploaded. ``None``
where the program keeps no such counters."""
SOURCE = "process"


def window_delta(obs, key):
    """The counter ``key`` over the window, by kernel; ``None`` without
    the counters."""
    before, after = obs.get("leaf_rows") or (None, None)
    if after is None:
        return None
    return {k: st[key] - ((before or {}).get(k) or {}).get(key, 0) for k, st in after.items()}


def read(obs):
    launched, live = window_delta(obs, "rows_launched"), window_delta(obs, "rows_live")
    if not launched or not sum(launched.values()):
        return None
    return 100.0 * sum(live.values()) / sum(launched.values())
