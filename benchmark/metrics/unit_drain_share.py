"""Share of the window the fabric executor spent parked on its oldest
launch's verdict, reading nothing meanwhile: busy seconds of the ledger wait
``unit_drain`` over the window. ``None`` where the program keeps no such
wait (the parent of the PR that added it)."""
SOURCE = "ledger"


def read(obs):
    from benchmark.harness.manifest import load_reader

    return load_reader(obs["root"], "deadline_wait_share").wait_share(obs, "unit_drain")
