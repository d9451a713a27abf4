"""95th percentile of due-to-verdict, over the requests due before the traced
slice opens (the profiler loads the server after it): some 2,600 at the live
cell's rate, 130 beyond it. Not bounded end to end: a host that stands still
now and then moves it by more than any bound may allow (PERF.md §2)."""
SOURCE = "loadgen"


def read(obs):
    from benchmark.harness.loadgen import latencies_due_before, percentile

    lg = obs.get("loadgen")
    calm = latencies_due_before(lg, obs["undisturbed_s"]) if lg else []
    return percentile(calm, 95) if calm else None
