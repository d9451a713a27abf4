"""Share of the window's leaf launches that ran the scan backend and not
the Pallas kernel (a batch under 1,024 rows): the program's launch counters
by kernel (``models/v2.leaf_launch_stats``). ``None`` where the program
keeps no such counters."""
SOURCE = "process"


def read(obs):
    from benchmark.harness.manifest import load_reader

    launches = load_reader(obs["root"], "leaf_fill_share").window_delta(obs, "launches")
    if not launches or not sum(launches.values()):
        return None
    return 100.0 * launches.get("scan", 0) / sum(launches.values())
