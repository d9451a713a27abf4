"""Useful payload bytes of a launch (the window's bytes with a verdict over
its launches: live rows only, by the benchmark's own count) over the mean
device time of the hash step's XLA modules in the traced slice. Whatever
implements the step, scan or Pallas, it reads the same work."""
SOURCE = "trace"


def bytes_per_step_second(obs):
    t = obs["trace"]
    if t is None or not t["step_launches"] or not obs.get("launches") or not obs["bytes"]:
        return None
    return (obs["bytes"] / obs["launches"]) / (t["step_s"] / t["step_launches"])


def read(obs):
    rate = bytes_per_step_second(obs)
    return None if rate is None else rate / 2**30
