"""Share of the bytes uploaded in the window that were payload: Δ``bytes``
÷ Δ``moved_bytes`` of the ledger stage ``h2d`` (the rest is SHA padding and
rows that held no piece). ``None`` where the program counts no moved
bytes, or nothing was uploaded."""
SOURCE = "ledger"


def read(obs):
    before, after = (snap["stages"].get("h2d", {}) for snap in obs["ledger"])
    if "moved_bytes" not in after:
        return None
    moved = after["moved_bytes"] - before.get("moved_bytes", 0)
    return 100.0 * (after["bytes"] - before.get("bytes", 0)) / moved if moved else None
