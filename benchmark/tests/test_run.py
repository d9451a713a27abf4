"""Whole runs of the harness on the CPU (rehearsal sizes), past the look
for a chip: a sound run is correct; the control is not; and a timed path
broken underneath is not — an answer altered where it is produced, half of
the batch left out. (The cells have no exchange between chips to leave
out and no training state to leave unchanged.)"""

import pytest

from benchmark import run as bench


def _run(workload, hook=None, control=0, seed=2147483777):
    args = bench.parse_args(
        ["--workload", workload, "--seed", str(seed), "--seconds", "2", "--trace", "0",
         "--rehearse", "1", "--control", str(control)]
    )
    line, code = bench.run(args, driver_hook=hook)
    assert code == 0
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    return line


CELLS = ["recheck-256k.bulk", "bridge-256k.live", "bridge-256k.stream"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_control_is_not(workload):
    line = _run(workload)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["checks"]["reference_invalid"] > 0  # the traffic plants invalid pieces
    bad = _run(workload, control=1)
    assert bad["correct"] is False
    assert bad["checks"]["wrong_verdicts"]["value"] == bad["checks"]["reference_invalid"]


def _break_at_window(driver, break_it):
    """Break the path when the window opens: a warm-up that fails ends the
    run with no result at all, which is not what is under test."""
    window = driver.window

    def broken_window(seconds):
        break_it()
        return window(seconds)

    driver.window = broken_window


def _alter_recheck(monkeypatch, how):
    from torrent_tpu.models.verifier import TPUVerifier

    real = TPUVerifier.verify_storage

    def broken(self, *a, **kw):
        bits = real(self, *a, **kw)
        if how == "altered":
            bits[0] = not bits[0]
        else:  # half of the batch left out
            bits[len(bits) // 2 :] = False
        return bits

    monkeypatch.setattr(TPUVerifier, "verify_storage", broken)


def _alter_bridge(monkeypatch, how):
    from torrent_tpu.sched import scheduler

    real = scheduler._Sha1DevicePlane.run

    def broken(self, payloads):
        digests = real(self, payloads)
        if how == "altered":
            digests[0] = bytes(20)
        else:
            digests[len(digests) // 2 :] = [bytes(20)] * (len(digests) - len(digests) // 2)
        return digests

    monkeypatch.setattr(scheduler._Sha1DevicePlane, "run", broken)


@pytest.mark.parametrize("how", ["altered", "half_left_out"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(monkeypatch, workload, how):
    alter = _alter_recheck if workload.startswith("recheck") else _alter_bridge
    line = _run(workload, hook=lambda d: _break_at_window(d, lambda: alter(monkeypatch, how)))
    assert line["correct"] is False
    assert line["failed"] > 0 and line["checks"]["wrong_verdicts"]["value"] > 0


def test_cpu_fallback_is_not_correct(monkeypatch):
    """A launch that ran on the hashlib plane breaks the configuration's
    second guarantee, though every verdict is right."""
    from torrent_tpu.sched import scheduler

    trip = lambda: monkeypatch.setattr(scheduler._LaneBreaker, "acquire_primary", lambda self: False)
    line = _run("bridge-256k.live", hook=lambda d: _break_at_window(d, trip))
    assert line["checks"]["cpu_fallback_launches"]["value"] > 0
    assert line["correct"] is False
