"""How late the open loop sent, against its schedule: 99th percentile."""
SOURCE = "loadgen"


def read(obs):
    from benchmark.harness.loadgen import percentile

    lg = obs.get("loadgen")
    return percentile(lg["late_ms"], 99) if lg and lg["late_ms"] else None
