"""Mean time from the instant the scheduler resolved a submission (its
demux, on the loop thread) to the waiting ``submit()`` running again: the
ledger wait ``verdict_wake``, Δseconds ÷ Δops over the window: how late the
loop wakes a request whose verdict is ready. ``None`` where the program
keeps no such entry."""
SOURCE = "ledger"


def read(obs):
    from benchmark.harness.manifest import load_reader

    return load_reader(obs["root"], "http_head_ms").entry_mean_ms(obs, "waits", "verdict_wake")
