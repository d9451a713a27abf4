"""Mean time of a request from its headers parsed to its body read
(``readexactly(content_length)`` returned): the ledger wait ``http_body``,
Δseconds ÷ Δops over the window. ``None`` where the program keeps no such
entry."""
SOURCE = "ledger"


def read(obs):
    from benchmark.harness.manifest import load_reader

    return load_reader(obs["root"], "http_head_ms").entry_mean_ms(obs, "waits", "http_body")
