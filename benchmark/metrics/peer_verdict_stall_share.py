"""Share of a peer's time spent asking for nothing: while a piece is judged
the peer loop that delivered its last block requests no further block
(``pipeline_depth`` is one piece). Busy seconds of the ledger wait
``ingest_verdict_wait`` over the window times the cell's peers (the loops
wait side by side), in per cent. ``None`` where the program keeps no such
wait."""
SOURCE = "ledger"


def read(obs):
    from benchmark.harness.manifest import load_reader

    share = load_reader(obs["root"], "deadline_wait_share").wait_share(obs, "ingest_verdict_wait")
    return None if share is None or not obs.get("peers") else share / obs["peers"]
