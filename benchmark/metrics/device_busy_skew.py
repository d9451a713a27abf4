"""How unevenly a mesh's devices work over the traced slice: the busiest
device's busy seconds less the least busy one's, as a share of the busiest's.
Near 0 the chips work as one; near 100 one works and the others wait. ``None``
where the trace holds fewer than two devices that ran the step (one chip, a
rehearsal): there is no mesh to be uneven."""
SOURCE = "trace"


def read(obs):
    t = obs["trace"]
    if t is None or t["step_devices"] < 2:
        return None
    busy = t["busy_s_by_device"].values()
    return 100.0 * (max(busy) - min(busy)) / max(busy)
