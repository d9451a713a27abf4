"""A piece's mean verdict latency as the peer loop that delivered its last
block waits for it: the ledger wait ``ingest_verdict_wait`` (one entry a
piece put to the judge, ``session/torrent.py:_finish_piece``), Δseconds ÷
Δentries over the window, in ms. ``None`` where the program keeps no such
wait (the parent of the PR that added it), or judged nothing."""
SOURCE = "ledger"


def read(obs):
    from benchmark.harness.manifest import load_reader

    return load_reader(obs["root"], "http_head_ms").entry_mean_ms(obs, "waits", "ingest_verdict_wait")
