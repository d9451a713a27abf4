"""A v2 ingest batch's mean time from its start in the worker thread to its
roots: the ledger wait ``ingest_v2_batch`` (one entry a batch,
``session/v2.ingest_verdicts``; its leaf launches and fold run inside it),
Δseconds ÷ Δentries over the window, in ms. ``None`` where the program keeps
no such wait (the parent of the PR that added it), or took no batch."""
SOURCE = "ledger"


def read(obs):
    from benchmark.harness.manifest import load_reader

    return load_reader(obs["root"], "http_head_ms").entry_mean_ms(obs, "waits", "ingest_v2_batch")
