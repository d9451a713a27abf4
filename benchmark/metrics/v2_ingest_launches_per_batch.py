"""Leaf launches a v2 ingest batch: the window's leaf launches over both
kernels (``models/v2.leaf_launch_stats``) over its batches
(``session/v2.v2_ingest_stats``). Each piece of a batch is a launch of its
own today, so this reads the pieces a batch; one launch a batch would read
1. ``None`` where the program keeps no batch counter, or took no batch."""
SOURCE = "process"


def read(obs):
    from benchmark.harness.manifest import load_reader

    batches = load_reader(obs["root"], "v2_ingest_pieces_per_batch").window_delta(obs, "batches")
    launches = load_reader(obs["root"], "leaf_fill_share").window_delta(obs, "launches")
    if not batches or launches is None:
        return None
    return sum(launches.values()) / batches
