"""Pieces a v2 ingest batch: the program's counters ``pieces`` over
``batches`` (``session/v2.v2_ingest_stats``), Δ over the window. A batch
is what finished together while the one before was judged. ``None`` where
the program keeps no such counters (the parent of the PR that added them),
or took no batch."""
SOURCE = "process"


def window_delta(obs, key):
    """The counter ``key`` over the window; ``None`` without the counters."""
    pair = obs.get("v2_ingest")
    if pair is None:
        return None
    before, after = pair
    return after[key] - before[key]


def read(obs):
    batches = window_delta(obs, "batches")
    if not batches:
        return None
    return window_delta(obs, "pieces") / batches
