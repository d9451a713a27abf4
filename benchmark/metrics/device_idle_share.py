"""Share of the traced window in which no operation ran on the device: on a
mesh, of the least idle device (the others wait for it)."""
SOURCE = "trace"


def read(obs):
    t = obs["trace"]
    return None if t is None else 100.0 * t["idle_share"]
