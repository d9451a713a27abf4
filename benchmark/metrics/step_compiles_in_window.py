"""Compile requests inside the window that ran the compiler (requests less
persistent-cache hits). Expected 0: a program found in the cache is loaded,
not compiled."""
SOURCE = "process"


def read(obs):
    before, after = obs["compiles"]
    return float(after["compiled"] - before["compiled"])
