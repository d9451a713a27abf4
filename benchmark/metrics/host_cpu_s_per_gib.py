"""CPU seconds of this process and of the load generator over the window,
per GiB that got a verdict."""
SOURCE = "process"


def read(obs):
    return obs["cpu_s"] / (obs["bytes"] / 2**30) if obs["bytes"] else None
