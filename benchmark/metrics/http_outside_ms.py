"""What a request spends outside the server: sent to closed on the client's
clock (mean ``latency_ms`` less mean ``late_ms`` of the generator's report)
less accept to closed on the server's (the mean of the ledger wait
``http_request``): connect, the kernel's accept queue, the generator's own
loop. ``None`` unless every request of the window was answered (the two
means are then over the same requests) and the program keeps the total."""
SOURCE = "loadgen"


def read(obs):
    from benchmark.harness.manifest import load_reader

    lg = obs.get("loadgen")
    if not lg or not lg["late_ms"] or len(lg["latency_ms"]) != len(lg["late_ms"]):
        return None
    server = load_reader(obs["root"], "http_head_ms").entry_mean_ms(obs, "waits", "http_request")
    if server is None:
        return None
    n = len(lg["late_ms"])
    return sum(lg["latency_ms"]) / n - sum(lg["late_ms"]) / n - server
