"""The tail beyond `verdict_p95_ms.obs`: 99th percentile of due-to-verdict,
over the same requests, those due before the traced slice opens."""
SOURCE = "loadgen"


def read(obs):
    from benchmark.harness.loadgen import latencies_due_before, percentile

    lg = obs.get("loadgen")
    calm = latencies_due_before(lg, obs["undisturbed_s"]) if lg else []
    return percentile(calm, 99) if calm else None
