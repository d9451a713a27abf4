"""Mean time of a piece from its enqueue to its verdict's demux, from
``metrics_snapshot()`` deltas: the sum and count the histogram family
``torrent_tpu_sched_e2e_seconds`` keeps, over every tenant. Less
``sched_wait_ms`` (enqueue to the launch's take) it is the time inside a
launch, assembly and the hop back to the loop included. ``None`` where the
program exports no such sum, or no piece got a verdict."""
SOURCE = "sched_snapshot"


def read(obs):
    if obs.get("sched") is None:
        return None
    before, after = obs["sched"]
    if "e2e_s_sum" not in after:
        return None
    pieces = after["e2e_pieces"] - before.get("e2e_pieces", 0)
    return 1000.0 * (after["e2e_s_sum"] - before.get("e2e_s_sum", 0.0)) / pieces if pieces else None
