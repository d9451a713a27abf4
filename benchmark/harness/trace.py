"""From a profiler trace to numbers: the device's busy and idle time, the
device time of the hash step's modules, the operations that took most
time, and the idle gaps by what the host was doing.

``load_xplane`` turns an ``.xplane.pb`` into a small plain structure (what
the tests keep a recording of); ``reduce`` works on that structure alone,
so it loads no profiler and no TPU library.

A scan-backend launch logs some hundred thousand device operations, so a
slice of half a second holds millions. The module line (one event per
launch) gives the busy time to the microsecond — operations inside a module
run back to back, which ``reduce`` checks on the launches it samples — and
the operation line is read for the first ``OPS_LAUNCHES`` launches only, for
the ranking of operations.

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops", "events": [[name, start_ns, dur_ns], ...]}]}]}
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench_trace_window"
# host spans that may explain an idle gap: the benchmark's own, and the
# program's annotation around a scheduler launch
SPAN_PREFIXES = ("bench_", "sched_")
OPS_LAUNCHES = 2  # launches whose device operations are read for the ranking


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, host_prefixes=SPAN_PREFIXES, ops_launches: int = OPS_LAUNCHES) -> dict:
    """Keep the device planes' module line, the operation line as far as
    the end of the ``ops_launches``-th module, and of the host plane only
    the named spans (a trace holds far more than is read)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            by_name = {ln.name: ln for ln in plane.lines}
            modules = [[e.name, e.start_ns, e.duration_ns] for e in by_name[MODULES_LINE].events] if MODULES_LINE in by_name else []
            ops = []
            if modules and OPS_LINE in by_name:
                first = sorted(modules, key=lambda m: m[1])[:ops_launches]
                stop = first[-1][1] + first[-1][2]
                for e in by_name[OPS_LINE].events:  # in time order: stop early
                    if e.start_ns > stop:
                        break
                    ops.append([e.name, e.start_ns, e.duration_ns])
            lines = [{"name": MODULES_LINE, "events": modules}, {"name": OPS_LINE, "events": ops}]
        elif plane.name == HOST_PLANE:
            lines = []
            for ln in plane.lines:
                evs = [[e.name, e.start_ns, e.duration_ns] for e in ln.events if e.name.startswith(host_prefixes)]
                if evs:
                    lines.append({"name": ln.name, "events": evs})
        else:
            continue
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _clip(events, lo, hi):
    """``(start, end, name)`` of the events' parts inside ``[lo, hi]``."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((s, e, name))
    return out


def _union(intervals):
    """Merged ``[start, end]`` lists of possibly overlapping intervals."""
    merged = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _line(plane, name):
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def op_name(name: str) -> str:
    """``%while.19 = (s32[]...) while(...)`` → ``while.19``: without the HLO
    proto the profiler names an operation by its whole HLO text."""
    return name.split(" = ", 1)[0].lstrip("%")


def module_base(name: str) -> str:
    """``jit__verify_flat(123456)`` → ``jit__verify_flat``."""
    return name.split("(", 1)[0]


def reduce(ir: dict, step_modules) -> dict | None:
    """The trace's numbers, or ``None`` where it shows no device module (a
    CPU rehearsal): a reader that finds nothing returns nothing.

    The window is the ``bench_trace_window`` host span if the trace has
    one, else the extent of the device modules. Seconds throughout.
    """
    devices = [p for p in ir["planes"] if p["name"].startswith(DEVICE_PLANE)]
    host = [ev for p in ir["planes"] if p["name"] == HOST_PLANE for ln in p["lines"] for ev in ln["events"]]
    window = [ev for ev in host if ev[0] == WINDOW_SPAN]
    if window:
        lo, hi = window[0][1], window[0][1] + window[0][2]
    else:
        every = [ev for p in devices for ev in _line(p, MODULES_LINE)]
        if not every:
            return None
        lo, hi = min(e[1] for e in every), max(e[1] + e[2] for e in every)
    per_device = []
    for plane in sorted(devices, key=lambda p: p["name"]):
        raw = _line(plane, MODULES_LINE)
        modules = _clip(raw, lo, hi)
        busy = _union(modules)
        # the step's launches that lie wholly inside the window
        whole = [(s, s + d) for n, s, d in raw if module_base(n) in step_modules and s >= lo and s + d <= hi]
        per_device.append(
            {
                "plane": plane["name"],
                "busy": busy,
                "busy_s": sum(e - s for s, e in busy) / 1e9,
                "step_s": sum(e - s for s, e in whole) / 1e9,
                "step_launches": len(whole),
                "modules": sorted({module_base(n) for _, _, n in modules}),
                "ops": _line(plane, OPS_LINE),
                "raw_modules": raw,
            }
        )
    if not any(d["busy"] for d in per_device):
        return None
    window_s = (hi - lo) / 1e9
    # the least idle device speaks for a mesh: the others wait for it
    lead = max(per_device, key=lambda d: d["busy_s"])
    by_op: dict[str, float] = {}
    for n, _, d in lead["ops"]:
        n = op_name(n)
        by_op[n] = by_op.get(n, 0.0) + d / 1e9
    spans = _clip([ev for ev in host if ev[0] != WINDOW_SPAN], lo, hi)
    return {
        "window_s": window_s,
        "busy_s_mean": sum(d["busy_s"] for d in per_device) / len(per_device),
        "busy_s_by_device": {d["plane"]: d["busy_s"] for d in per_device},
        "idle_share_by_device": {d["plane"]: 1 - d["busy_s"] / window_s for d in per_device},
        "lead_device": lead["plane"],
        "idle_share": 1 - lead["busy_s"] / window_s,
        # a sharded step runs on every device at once: its time is the lead device's
        "step_s": lead["step_s"],
        "step_launches": lead["step_launches"],
        "step_devices": sum(1 for d in per_device if d["step_launches"]),
        "modules_seen": lead["modules"],
        "device_ops": sorted(by_op.items(), key=lambda kv: -kv[1])[:10],
        "ops_cover_modules": _ops_cover(lead["ops"], lead["raw_modules"]),
        "idle_gaps": _gaps_by_span(lead["busy"], spans, lo, hi),
    }


def _ops_cover(ops, modules) -> float | None:
    """Over the sampled launches: the union of the device operations as a
    share of the modules' own time. Near 1 means a module's interval is
    busy time, which is what lets the module line stand for the device."""
    if not ops:
        return None
    stop = max(s + d for _, s, d in ops)
    sampled = [(s, s + d) for _, s, d in modules if s + d <= stop]
    if not sampled:
        return None
    inside = _union(_clip(ops, min(s for s, _ in sampled), max(e for _, e in sampled)))
    return sum(e - s for s, e in inside) / sum(e - s for s, e in sampled)


def _gaps_by_span(busy, spans, lo, hi, floor_ns=10_000):
    """Idle gaps of one device, summed under the host span that covers each
    gap's middle (the innermost, which is the shortest); ``no_host_span``
    where none does. Names carry the count: ``sched_sha1_launch_b262144_x17``."""
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    total: dict[str, list] = {}
    for s, e in gaps:
        if e - s < floor_ns:
            name = f"gaps_under_{floor_ns // 1000}_us"
        else:
            mid = (s + e) / 2
            cover = [(b - a, n) for a, b, n in spans if a <= mid <= b]
            name = min(cover)[1] if cover else "no_host_span"
        acc = total.setdefault(name, [0, 0.0])
        acc[0] += 1
        acc[1] += (e - s) / 1e9
    ranked = sorted(total.items(), key=lambda kv: -kv[1][1])[:10]
    return [[f"{name}_x{n}", secs] for name, (n, secs) in ranked]


def summarize_planes(path: str, top: int = 12, cap: int = 300_000) -> dict:
    """What a trace holds, for a first look by hand: every plane and line
    with the names that took most time, over at most ``cap`` events a line."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for ln in plane.lines:
            acc: dict[str, list] = {}
            n = 0
            for e in ln.events:
                n += 1
                if n > cap:
                    break
                a = acc.setdefault(e.name, [0, 0.0])
                a[0] += 1
                a[1] += e.duration_ns
            ranked = sorted(acc.items(), key=lambda kv: -kv[1][1])[:top]
            lines[ln.name] = {"events": n, "top": [[k, c, d / 1e9] for k, (c, d) in ranked]}
        out[plane.name] = lines
    return out


def clip_ir(ir: dict, modules: int = 6, ops: int = 300) -> dict:
    """A recording small enough to keep with the tests: the first few
    modules of each device, the first few hundred operations, and the host
    spans over that stretch."""
    planes, hi = [], 0
    for p in ir["planes"]:
        if p["name"].startswith(DEVICE_PLANE):
            mods = sorted(_line(p, MODULES_LINE), key=lambda e: e[1])[:modules]
            hi = max([hi] + [e[1] + e[2] for e in mods])
            planes.append({"name": p["name"], "lines": [
                {"name": MODULES_LINE, "events": mods}, {"name": OPS_LINE, "events": _line(p, OPS_LINE)[:ops]}]})
    for p in ir["planes"]:
        if p["name"] == HOST_PLANE:
            planes.append({"name": p["name"], "lines": [
                {"name": ln["name"], "events": [e for e in ln["events"] if e[1] <= hi and e[0] != WINDOW_SPAN]}
                for ln in p["lines"]]})
    return {"planes": planes}
