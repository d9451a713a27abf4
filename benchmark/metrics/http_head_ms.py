"""Mean time of a request from the server's accept callback to its headers
parsed: the ledger wait ``http_head`` (``bridge/service.py`` records it after
the fact, one op a request), Δseconds ÷ Δops over the window. ``None`` where
the program keeps no such entry (the parent of the PR that added it)."""
SOURCE = "ledger"


def entry_mean_ms(obs, table, name):
    """Δ``busy_s`` ÷ Δ``ops`` of one ledger entry (``table`` is ``stages`` or
    ``waits``) over the window, in milliseconds: a mean, because means of
    the phases add up to the mean of the whole and medians do not."""
    before, after = (snap.get(table, {}).get(name) for snap in obs["ledger"])
    if after is None:
        return None
    ops = after["ops"] - (before or {}).get("ops", 0)
    return 1000.0 * (after["busy_s"] - (before or {}).get("busy_s", 0.0)) / ops if ops else None


def read(obs):
    return entry_mean_ms(obs, "waits", "http_head")
