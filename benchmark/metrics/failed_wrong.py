"""Pieces of the window that failed as ``wrong`` (see
``harness/loadgen.py:classify``). The three classes sum to ``failed``."""
SOURCE = "loadgen"


def read(obs):
    lg = obs.get("loadgen")
    return float(lg["classes"]["wrong"]) if lg else None
