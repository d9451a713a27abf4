"""``hash_step_gib_s`` times the hash's int32 operations per byte, over the
int32 issue ceiling of the devices the step runs on. The ceiling is INFERRED
(see ``harness/peaks.json``), its upper end: a share over 100 % cannot be."""
SOURCE = "trace"


def read(obs):
    from benchmark.harness.manifest import load_reader

    rate = load_reader(obs["root"], "hash_step_gib_s").bytes_per_step_second(obs)
    if rate is None:
        return None
    peaks, kind = obs["peaks"], obs["device"]["kind"]
    if kind not in peaks["devices"]:
        raise KeyError(f"device kind {kind!r} is not in harness/peaks.json")
    ops = rate * peaks["work"][obs["algo"]]["int32_ops_per_byte"]
    # a sharded step runs on step_devices chips at once: the ceiling is theirs together
    return 100.0 * ops / (peaks["devices"][kind]["int32_ops_per_s"] * obs["trace"]["step_devices"])
