"""``BENCHMARK.json`` and the files it names: a cell's configuration, its
traffic mix, its entry driver and its per-layer metric readers are each a
file found by name, so a later PR adds files and entries and edits none."""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass


@dataclass
class Cell:
    root: str
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    manifest: dict
    seed: int = 0
    work_dir: str = ""
    log: object = print  # the harness's stderr logger, with the time since the process began


def load_manifest(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> Cell:
    """The cell ``<config>.<traffic>``. One that ``BENCHMARK.json`` does not
    list (a sweep's rate, a cell kept for later) still runs, from its two
    files, and prints no metric: the manifest names what a cell reports."""
    m = load_manifest(root)
    config_name, _, traffic_name = workload.rpartition(".")
    listed = next((w for w in m["workloads"] if w["name"] == workload), None)
    if listed is not None:
        config_file = next(c["file"] for c in m["configs"] if c["name"] == listed["config"])
    else:
        config_file = os.path.join("benchmark", "configs", config_name + ".json")
    try:
        with open(os.path.join(root, config_file)) as f:
            config = json.load(f)
        with open(os.path.join(root, "benchmark", "traffic", traffic_name + ".json")) as f:
            traffic = json.load(f)
    except FileNotFoundError as e:
        raise SystemExit(f"no workload {workload!r}: {e.filename} is missing") from None
    chips = int(listed["chips"] if listed else config["chips"])
    return Cell(root, workload, chips, config_name, config, traffic_name, traffic, m)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(root: str, name: str):
    return _module(os.path.join(root, "benchmark", "drivers", name + ".py"), "bench_driver_" + name)


def load_reader(root: str, metric: str):
    """``benchmark/metrics/<metric>.py``: ``SOURCE`` and ``read(obs)``. A
    file that only says ``ALIAS_OF = "<other>"`` is that other reader
    under a second name (the same quantity moving another end-to-end
    metric in another cell)."""
    mod = _module(os.path.join(root, "benchmark", "metrics", metric + ".py"), "bench_metric_" + metric.replace(".", "_"))
    alias = getattr(mod, "ALIAS_OF", None)
    return load_reader(root, alias) if alias else mod


def metrics_for(manifest: dict, section: str, workload: str) -> list[dict]:
    return [m for m in manifest[section] if workload in m.get("workloads", [workload])]
