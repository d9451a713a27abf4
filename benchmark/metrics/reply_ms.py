"""Mean time the event loop spent answering a request, ``_reply`` entered to
``writer.close()`` called (encode, write, drain, close): the ledger stage
``reply`` (a span ``sched_reply`` too), Δseconds ÷ Δops over the window.
``None`` where the program keeps no such stage."""
SOURCE = "ledger"


def read(obs):
    from benchmark.harness.manifest import load_reader

    return load_reader(obs["root"], "http_head_ms").entry_mean_ms(obs, "stages", "reply")
