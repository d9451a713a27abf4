"""``BENCHMARK.json`` against the files it names and the contract's limits
that can be checked without a run."""

import json
import os
import re

import pytest

from benchmark.harness import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def m():
    return manifest.load_manifest(ROOT)


def test_keys_names_and_limits(m):
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert e["source"] in ("host_clock", "device_trace") and 0.01 <= e["bound"] <= 0.25
    for p in m["per_layer"]:
        assert set(p) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert p["source"] in SOURCES
    names = [x["name"] for s in ("configs", "workloads", "end_to_end", "per_layer") for x in m[s]]
    names += [w["traffic"] for w in m["workloads"]] + [k for c in m["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    metric_names = [x["name"] for s in ("end_to_end", "per_layer") for x in m[s]]
    assert len(metric_names) == len(set(metric_names))
    for x in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
    for text in [c["source"] for c in m["configs"]] + [x["why"] for x in m["configs"] + m["workloads"]] + [
        p["layer"] for p in m["per_layer"]
    ]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 2)


def test_every_named_file_is_there(m):
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    for c in m["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert c["file"].startswith("benchmark/")
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert cfg["guarantees"] and "assumed" in cfg
        assert os.path.exists(os.path.join(ROOT, "benchmark", "drivers", cfg["driver"] + ".py"))
    for w in m["workloads"]:
        manifest.load_cell(ROOT, w["name"])
    for p in m["per_layer"]:
        reader = manifest.load_reader(ROOT, p["name"])
        assert reader.SOURCE in ("trace", "sched_snapshot", "ledger", "loadgen", "process") and callable(reader.read)


def test_every_cell_reports_what_its_metrics_move(m):
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in m["workloads"]:
        mine = [e["name"] for e in manifest.metrics_for(m, "end_to_end", w["name"])]
        assert "setup_s" in mine and len(mine) >= 2
        assert manifest.metrics_for(m, "per_layer", w["name"])
    for p in m["per_layer"]:
        assert p["moves"] in e2e
        for cell in p["workloads"]:
            assert cell in e2e[p["moves"]].get("workloads", [cell]), (p["name"], cell)


def test_peaks_name_their_sources():
    with open(os.path.join(ROOT, "benchmark", "harness", "peaks.json")) as f:
        peaks = json.load(f)
    for dev in peaks["devices"].values():
        for key in [k for k in dev if not k.endswith("_source")]:
            assert dev[key + "_source"]
        assert "INFERRED" in dev["int32_ops_per_s_source"]
    assert all(w["source"] for w in peaks["work"].values())
