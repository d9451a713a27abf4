"""Pieces of the window that failed as ``transport`` (see
``harness/loadgen.py:classify``). The three classes sum to ``failed``."""
SOURCE = "loadgen"


def read(obs):
    lg = obs.get("loadgen")
    return float(lg["classes"]["transport"]) if lg else None
