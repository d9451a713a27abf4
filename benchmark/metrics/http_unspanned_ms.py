"""What the server spends on a request under none of its phases: the mean of
the ledger wait ``http_request`` (accept to the reply written and the socket
closed) less the six phase means (head, body, decode, enqueue to verdict,
wake, reply). It measures the measurement, as ``idle_unattributed_share``
does: the route's dispatch, admission before the tickets' stamp, the reply's
encoding. Exact where a request carries one piece (the scheduler's mean is a
piece's). ``None`` where any of the seven is missing."""
SOURCE = "ledger"

PHASES = ("http_head_ms", "http_body_ms", "decode_ms", "sched_e2e_ms", "verdict_wake_ms", "reply_ms")


def read(obs):
    from benchmark.harness.manifest import load_reader

    whole = load_reader(obs["root"], "http_head_ms").entry_mean_ms(obs, "waits", "http_request")
    parts = [load_reader(obs["root"], name).read(obs) for name in PHASES]
    if whole is None or None in parts:
        return None
    return whole - sum(parts)
