"""Summarize a record bank — markdown table or machine-readable trajectory.

The bank is the directory this script sits in. Default mode walks its
`live/<metric>.json` (stable best-record names) plus the loose `*.json`
rung artifacts beside the script, and prints one row per metric with
value, vs_baseline, measurement shape, platform, and when/where it was
measured — so a reviewer can check every performance claim against its
artifact in one look.

``--trajectory [OUT]`` instead aggregates EVERY banked record — the
stable live names, their timestamped audit copies (the per-metric
history), and the loose rung artifacts — into one machine-readable
``BENCH_trajectory.json`` (schema ``torrent-tpu-bench-trajectory/1``)
for the ``torrent-tpu bench --compare`` regression gate. Shape caveats
are preserved: a record carrying a ``like_for_like`` annotation (e.g. a
B=512 narrow-batch record that must not be compared to the B=8192
flagship) is marked ``non_like_for_like: true`` so the comparator never
gates across shapes.

Usage:
  python .bench/summarize.py [--all]          markdown table (--all
                                              lists rung artifacts too)
  python .bench/summarize.py --trajectory [OUT]   write the trajectory
                                              (default OUT: repo root
                                              BENCH_trajectory.json)
"""

from __future__ import annotations

import glob
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
TRAJECTORY_SCHEMA = "torrent-tpu-bench-trajectory/1"


def _load(path: str) -> dict | None:
    try:
        with open(path) as f:
            rec = json.load(f)
    except Exception:
        return None
    return rec if isinstance(rec, dict) and rec.get("metric") else None


def _when(rec: dict) -> str:
    return (
        rec.get("banked_at_utc")
        or rec.get("measured_at_utc")
        or rec.get("provenance", "")
    )


def _normalize(rec: dict, artifact: str) -> dict:
    """One trajectory entry: the comparator's like-for-like fields up
    front, the full source record's remaining fields preserved."""
    out = {
        "metric": rec.get("metric"),
        "value": rec.get("value"),
        "unit": rec.get("unit"),
        "vs_baseline": rec.get("vs_baseline"),
        "batch": rec.get("batch"),
        "platform": rec.get("platform"),
        "banked_at_utc": _when(rec),
        "artifact": artifact,
        # a like_for_like annotation exists ONLY to caveat a shape: its
        # PRESENCE means "do not gate other shapes against this record"
        # (an author writing `"like_for_like": false` means that too)
        "non_like_for_like": "like_for_like" in rec,
    }
    for key in ("shape", "like_for_like", "provenance", "pre_median_contract",
                "status", "n_runs", "spread", "end_to_end_pps",
                "h2d_mib_s", "rung", "ledger",
                # the controller A/B record schema (bench controller):
                # both sides of the A/B, the throttle that framed it,
                # and the decision trail that produced the win — banked
                # WITH the rate so the regression gate stays auditable
                "ab", "decision", "fault",
                # the announce rung schema (bench announce): the storm
                # shape, the cross-shard occupancy proof, and the
                # latency summary ride the banked rate (same treatment
                # the controller rung got)
                "clients", "swarms", "shards", "shards_hit", "numwant",
                "announces", "rates", "latency", "shard_occupancy", "store",
                "contract",
                # the timeline/SLO plane schema (PR 14): the smoke rung
                # brackets the run in timeline samples and embeds the
                # default-contract SLO verdict — a clean rung banks
                # zero burn, so a regression investigator can see
                # whether the slower record was also BURNING budget
                "timeline", "slo",
                # the swarm wire-plane rung schema (bench swarm): the
                # telemetry facts (block-RTT p99, snubs, endgame
                # cancels) ride the banked rate, and the embedded
                # ledger already carries the recv-stage breakdown —
                # a swarm regression must name the wire, not guess
                "swarm",
                # the seeder-plane rung schema (bench seed): the crowd
                # size, block service tail, and the egress fallback
                # matrix + choke counters ride the banked upload rate —
                # an upload regression must say whether zero-copy
                # disengaged, the reactor shed, or rotation stalled
                "leechers", "block_p50_ms", "block_p99_ms", "blocks",
                "bytes_up", "serve",
                # the comparator's full like-for-like shape key
                "piece_kb", "bytes", "nproc"):
        if key in rec:
            out[key] = rec[key]
    return out


def collect_records(include_loose: bool = True) -> list[dict]:
    """Every banked record, normalized: stable live names + timestamped
    audit copies + (optionally) loose rung artifacts, null-filtered."""
    records = []
    for path in sorted(glob.glob(os.path.join(BENCH, "live", "*.json"))):
        rec = _load(path)
        if rec and rec.get("value") is not None:
            records.append(_normalize(rec, "live/" + os.path.basename(path)))
    if include_loose:
        for path in sorted(glob.glob(os.path.join(BENCH, "*.json"))):
            rec = _load(path)
            if rec and rec.get("value") is not None:
                records.append(_normalize(rec, os.path.basename(path)))
    records.sort(key=lambda r: (r["metric"] or "", r["banked_at_utc"] or ""))
    return records


def write_trajectory(out_path: str) -> dict:
    records = collect_records(include_loose=True)
    # Preserve self-banked records (`torrent-tpu bench --bank`): they
    # exist ONLY in the trajectory file, not under .bench/, so a
    # regeneration must merge them or it silently disarms the CI
    # comparator they armed. Discriminator: aggregated records carry
    # an "artifact" pointer into .bench/; banked ones don't.
    try:
        with open(out_path) as f:
            prev = json.load(f)
        prev_records = prev.get("records", []) if isinstance(prev, dict) else prev
    except Exception:
        prev_records = []
    records += [
        r for r in prev_records
        if isinstance(r, dict) and r.get("metric") and not r.get("artifact")
    ]
    records.sort(key=lambda r: (r.get("metric") or "",
                                r.get("banked_at_utc")
                                or r.get("measured_at_utc") or ""))
    data = {
        "schema": TRAJECTORY_SCHEMA,
        "generated_by": "python .bench/summarize.py --trajectory",
        "records": records,
    }
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, out_path)
    return data


def main() -> None:
    args = sys.argv[1:]
    if args and args[0] == "--trajectory":
        out = (
            args[1]
            if len(args) > 1
            else os.path.join(os.path.dirname(BENCH), "BENCH_trajectory.json")
        )
        data = write_trajectory(out)
        n = len(data["records"])
        # banked (bench --bank) records may not carry the flag at all
        caveated = sum(1 for r in data["records"] if r.get("non_like_for_like"))
        metrics = len({r["metric"] for r in data["records"]})
        print(
            f"wrote {out}: {n} records across {metrics} metrics "
            f"({caveated} carry shape caveats)"
        )
        return

    rows = []
    for path in sorted(glob.glob(os.path.join(BENCH, "live", "*.json"))):
        name = os.path.basename(path)
        # skip timestamped audit copies: metric.<stamp>.json
        if name.count(".") > 1:
            continue
        rec = _load(path)
        # same null filter as the --all branch: a null record landing
        # in live/ must never print as the current best
        if rec and rec.get("value") is not None:
            rows.append((rec, "live/" + name))
    if "--all" in args:
        for path in sorted(glob.glob(os.path.join(BENCH, "*.json"))):
            rec = _load(path)
            if rec and rec.get("value") is not None:
                rows.append((rec, os.path.basename(path)))
    print("| metric | value | vs_baseline | batch | platform | measured | artifact |")
    print("|---|---|---|---|---|---|---|")
    for rec, src in rows:
        print(
            f"| {rec['metric']} | {rec.get('value')} {rec.get('unit', '')} "
            f"| {rec.get('vs_baseline')} | {rec.get('batch', '—')} "
            f"| {rec.get('platform', '?')} | {_when(rec)} | {src} |"
        )


if __name__ == "__main__":
    main()
