"""Mean time the event loop spent on a request between its body and its
``submit``: ``bdecode`` and the checks, the ledger stage ``decode`` (a span
``sched_decode`` too), Δseconds ÷ Δops over the window. ``None`` where the
program keeps no such stage."""
SOURCE = "ledger"


def read(obs):
    from benchmark.harness.manifest import load_reader

    return load_reader(obs["root"], "http_head_ms").entry_mean_ms(obs, "stages", "decode")
