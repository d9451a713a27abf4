"""Share of the window spent in the merkle fold above the leaves: busy
seconds of the ledger stage ``merkle`` (each flush of the batched reduction
and each file's piece-layer check, v2 recheck) over the window. ``None``
where the program has no such stage."""
SOURCE = "ledger"


def read(obs):
    before, after = (snap["stages"].get("merkle") for snap in obs["ledger"])
    if after is None:
        return None
    return 100.0 * (after["busy_s"] - (before or {}).get("busy_s", 0.0)) / obs["window_s"]
