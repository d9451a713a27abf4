"""The readers of a live request's phases (PR 34), each on a hand-made
``obs``: ledger pairs, a scheduler snapshot pair and a generator's report
whose means can be worked out by hand. On the parent's shapes (no ``waits``
key, no ``e2e_s_sum``, a request unanswered) a reader returns ``None``,
never 0; what is under no phase plus the six phases is the whole, to the
float."""

import os

import pytest

from benchmark.harness import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PHASES = ("http_head_ms", "http_body_ms", "decode_ms", "sched_e2e_ms", "verdict_wake_ms", "reply_ms")
ALL = PHASES + ("http_unspanned_ms", "http_outside_ms")


def reader(name):
    return manifest.load_reader(ROOT, name)


def entry(busy_s, ops, nbytes=0):
    return {"busy_s": busy_s, "bytes": nbytes, "moved_bytes": 0, "ops": ops, "active": 0, "max_active": 0}


def make_obs():
    """Ten requests before the window, four inside it. By hand, a request
    of the window: head 0.5 ms, body 2, decode 0.25, enqueue to verdict 21,
    wake 1.5, reply 0.125, the whole 26 ms: 0.625 ms under no phase. The
    client: sent to closed 30 ms, so 4 ms outside the server."""
    before = {
        "stages": {"decode": entry(1.0, 10), "reply": entry(2.0, 10), "stage": entry(9.0, 3)},
        "waits": {
            "http_head": entry(0.1, 10), "http_body": entry(0.2, 10), "verdict_wake": entry(0.3, 10),
            "http_request": entry(5.0, 10), "deadline_wait": entry(7.0, 70),
        },
    }
    after = {
        "stages": {"decode": entry(1.001, 14), "reply": entry(2.0005, 14), "stage": entry(9.5, 4)},
        "waits": {
            "http_head": entry(0.102, 14), "http_body": entry(0.208, 14, 4 << 18), "verdict_wake": entry(0.306, 14),
            "http_request": entry(5.104, 14), "deadline_wait": entry(7.4, 74),
        },
    }
    sched = ({"e2e_s_sum": 1.0, "e2e_pieces": 10, "queue_wait_s_sum": 0.5, "queue_wait_pieces": 10},
             {"e2e_s_sum": 1.084, "e2e_pieces": 14, "queue_wait_s_sum": 0.548, "queue_wait_pieces": 14})
    loadgen = {"latency_ms": [31.0, 33.0, 30.0, 34.0], "late_ms": [1.0, 3.0, 0.0, 4.0], "requests": 4}
    return {"ledger": (before, after), "sched": sched, "loadgen": loadgen, "window_s": 20.0, "root": ROOT}


BY_HAND = {
    "http_head_ms": 0.5, "http_body_ms": 2.0, "decode_ms": 0.25, "sched_e2e_ms": 21.0,
    "verdict_wake_ms": 1.5, "reply_ms": 0.125, "http_unspanned_ms": 0.625, "http_outside_ms": 4.0,
}


@pytest.mark.parametrize("name", ALL)
def test_each_reader_gives_the_mean_worked_out_by_hand(name):
    assert reader(name).read(make_obs()) == pytest.approx(BY_HAND[name], abs=1e-9)
    assert reader(name + ".live").read(make_obs()) == pytest.approx(BY_HAND[name], abs=1e-9)


def test_an_entry_born_inside_the_window_reads_from_zero():
    obs = make_obs()
    before, after = obs["ledger"]
    obs["ledger"] = ({"stages": {}, "waits": {}}, after)
    assert reader("http_head_ms").read(obs) == pytest.approx(1000 * 0.102 / 14)
    assert reader("decode_ms").read(obs) == pytest.approx(1000 * 1.001 / 14)


def test_the_parts_and_what_is_under_none_make_the_whole_to_the_float():
    obs = make_obs()
    whole = reader("http_head_ms").entry_mean_ms(obs, "waits", "http_request")
    assert whole == pytest.approx(26.0)
    parts = sum(reader(name).read(obs) for name in PHASES)
    assert reader("http_unspanned_ms").read(obs) + parts == pytest.approx(whole, rel=0, abs=1e-12)
    # and the builder's identity: what the client saw is late + outside + the whole
    lg = obs["loadgen"]
    client = sum(lg["latency_ms"]) / 4
    assert sum(lg["late_ms"]) / 4 + reader("http_outside_ms").read(obs) + whole == pytest.approx(client)


def parent_ledger():
    """The parent's program: stages without ``decode`` or ``reply``, waits
    without the request's four (before PR 24 no ``waits`` key at all)."""
    obs = make_obs()
    for snap in obs["ledger"]:
        for name in ("decode", "reply"):
            del snap["stages"][name]
        for name in ("http_head", "http_body", "verdict_wake", "http_request"):
            del snap["waits"][name]
    return obs


@pytest.mark.parametrize("name", [n for n in ALL if n != "sched_e2e_ms"])
def test_on_the_parents_ledger_a_reader_finds_nothing(name):
    assert reader(name).read(parent_ledger()) is None
    obs = parent_ledger()
    for snap in obs["ledger"]:
        del snap["waits"]
    assert reader(name).read(obs) is None


def test_on_the_parents_scheduler_snapshot_the_e2e_mean_finds_nothing():
    obs = make_obs()
    obs["sched"] = tuple({k: v for k, v in s.items() if not k.startswith("e2e")} for s in obs["sched"])
    assert reader("sched_e2e_ms").read(obs) is None
    assert reader("http_unspanned_ms").read(obs) is None  # a part is missing: no remainder
    assert reader("http_outside_ms").read(obs) == pytest.approx(4.0)  # it needs the whole only
    assert reader("sched_e2e_ms").read(dict(obs, sched=None)) is None  # a cell without a scheduler


@pytest.mark.parametrize("name", ALL)
def test_a_window_without_requests_reads_nothing(name):
    obs = make_obs()
    obs["ledger"] = (obs["ledger"][1], obs["ledger"][1])
    obs["sched"] = (obs["sched"][1], obs["sched"][1])
    obs["loadgen"] = {"latency_ms": [], "late_ms": [], "requests": 0}
    assert reader(name).read(obs) is None


@pytest.mark.parametrize(
    "loadgen",
    [
        None,  # a cell without a generator
        {"latency_ms": [31.0, 33.0, 30.0], "late_ms": [1.0, 3.0, 0.0, 4.0], "requests": 4},  # one unanswered
    ],
)
def test_outside_needs_every_request_answered(loadgen):
    assert reader("http_outside_ms").read(dict(make_obs(), loadgen=loadgen)) is None


def test_the_manifest_lists_the_eight_for_the_live_cell_only():
    m = manifest.load_manifest(ROOT)
    mine = {p["name"]: p for p in m["per_layer"] if p["name"] in {n + ".live" for n in ALL}}
    assert set(mine) == {n + ".live" for n in ALL}
    for p in mine.values():
        assert p["workloads"] == ["bridge-256k.live"] and p["moves"] == "verdict_p50_ms"
        assert p["unit"] == "ms" and p["better"] == "lower"
    assert {n for n, p in mine.items() if p["layer"] == "scheduler"} == {"sched_e2e_ms.live", "verdict_wake_ms.live"}
    assert mine["sched_e2e_ms.live"]["source"] == "program_counter"
    assert mine["http_outside_ms.live"]["source"] == "host_clock"
