"""Share of the window a lane spent parked on its flush deadline with
pieces queued: busy seconds of the ledger wait ``deadline_wait`` over the
window (lanes wait side by side, so it can pass 100 where several hold
pieces at once). ``None`` where the program keeps no such wait."""
SOURCE = "ledger"


def wait_share(obs, wait):
    """Busy seconds of one ledger wait over the window, in per cent."""
    before, after = (snap.get("waits", {}).get(wait) for snap in obs["ledger"])
    if after is None:
        return None
    return 100.0 * (after["busy_s"] - (before or {}).get("busy_s", 0.0)) / obs["window_s"]


def read(obs):
    return wait_share(obs, "deadline_wait")
