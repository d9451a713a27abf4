"""Busy seconds of the host ledger's ``read`` and ``stage`` stages over the
window (the two stages the ledger times soundly)."""
SOURCE = "ledger"


def read(obs):
    before, after = obs["ledger"]
    busy = 0.0
    for stage in ("read", "stage"):
        busy += after["stages"].get(stage, {}).get("busy_s", 0.0)
        busy -= before["stages"].get(stage, {}).get("busy_s", 0.0)
    return 100.0 * busy / obs["window_s"]
