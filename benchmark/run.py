"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run: it loads, warms, measures for ``--seconds``, checks
what the window produced against the plain reference, prints one JSON
line last on standard output and exits. It needs a TPU with as many chips
as the cell asks for and exits non-zero, printing no result, without one.

``--rehearse 1`` is for a sandbox without a chip (``JAX_PLATFORMS=cpu``):
it runs the same code at the tiny sizes each configuration and traffic
file gives under ``rehearse``, names the device it ran on, and writes no
metric at all — a CPU's times are not the device's.

``--control 1`` puts the control (``harness/reference.py``) in the
program's place at the comparison; such a run has to print ``correct:
false``. ``--dump-trace <file>`` writes what the trace holds, to look at
by hand.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import manifest, observe  # noqa: E402


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    p.add_argument("--dump-trace", default=None)
    return p.parse_args(argv)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def tlog(what: str) -> None:
    log(f"t+{time.monotonic() - T_START:.1f} s: {what}")


def require_device(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it; exits 3 unless it is a TPU with the
    chips the cell asks for (or the CPU a rehearsal was told to use)."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if rehearse:
        if os.environ.get("JAX_PLATFORMS") != "cpu" or device["platform"] != "cpu":
            log("a rehearsal runs with JAX_PLATFORMS=cpu, and only there")
            raise SystemExit(3)
    elif device["platform"] != "tpu" or device["count"] < chips:
        log(f"the cell needs {chips} TPU chip(s); JAX found {device}")
        raise SystemExit(3)
    return device


def apply_rehearsal(cell) -> None:
    for part in (cell.config, cell.traffic):
        part.update(part.get("rehearse", {}))


def run(args, driver_hook=None) -> tuple[dict, int]:
    """One run; returns the result line and the exit code. ``driver_hook``
    lets a test break the timed path underneath before the set-up."""
    cell = manifest.load_cell(ROOT, args.workload)
    try:
        import torrent_tpu  # noqa: F401  the system under test
    except ImportError:
        log("the program (torrent_tpu) is not in this checkout")
        raise SystemExit(2)
    if args.rehearse:
        apply_rehearsal(cell)
        if cell.chips > 1:
            os.environ.setdefault("XLA_FLAGS", f"--xla_force_host_platform_device_count={cell.chips}")
    device = require_device(cell.chips, bool(args.rehearse))
    cell.seed = args.seed
    cell.log = tlog
    tlog(f"device up: {device}")
    cell.work_dir = observe.fresh_dir(os.path.join(ROOT, ".bench_work", f"{args.workload}.{os.getpid()}"))
    compiles = observe.CompileCounter()
    driver = manifest.load_driver(ROOT, cell.config["driver"]).Driver(cell)
    if driver_hook is not None:
        driver_hook(driver)
    try:
        return _measure(args, cell, device, driver, compiles)
    finally:
        if hasattr(driver, "abort"):
            driver.abort()
        shutil.rmtree(cell.work_dir, ignore_errors=True)


def _read_per_layer(args, cell, tracer, obs, wanted, dev, line) -> dict:
    """Reduce the trace, let every wanted reader read, and put the device's
    busy time and the breakdown where the contract wants them."""
    from benchmark.harness import trace as tr

    xplane = tr.find_xplane(tracer.trace_dir)
    if args.dump_trace:
        with open(args.dump_trace, "w") as f:
            json.dump(tr.summarize_planes(xplane), f, indent=1)
    tlog(f"reading {os.path.getsize(xplane) >> 20} MiB of trace")
    ir = tr.load_xplane(xplane)
    if args.dump_trace:
        with open(args.dump_trace + ".ir.json", "w") as f:
            json.dump(tr.clip_ir(ir), f)
    reduced = tr.reduce(ir, set(cell.config["step_modules"]))
    tlog("trace reduced")
    with open(os.path.join(ROOT, "benchmark", "harness", "peaks.json")) as f:
        obs.update(trace=reduced, peaks=json.load(f))
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s_mean"]
        dev["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": [list(x) for x in reduced["device_ops"]], "idle_gaps": reduced["idle_gaps"]}
        shown = ("window_s", "busy_s_by_device", "idle_share_by_device", "lead_device", "step_s",
                 "step_launches", "step_devices", "modules_seen", "ops_cover_modules")
        log("trace:", json.dumps({k: reduced[k] for k in shown}))
    return {m["name"]: manifest.load_reader(ROOT, m["name"]).read(obs) for m in wanted}


def _measure(args, cell, device, driver, compiles):
    from torrent_tpu.obs.ledger import pipeline_ledger

    driver.setup()
    tlog("set up")
    tracer = None
    if args.trace:
        tracer = observe.TraceSlice(
            os.path.join(cell.work_dir, "trace"), args.seconds, int(cell.config["trace_launches"]),
            float(cell.config["trace_max_seconds"]), driver.launch_count,
        )
    before = {"compiles": compiles.snapshot(), "ledger": pipeline_ledger().snapshot(), "cpu": observe.cpu_seconds()}
    if tracer:
        tracer.start()
    t_open = driver.window(args.seconds)
    setup_s = t_open - T_START
    after = {"compiles": compiles.snapshot(), "ledger": pipeline_ledger().snapshot(), "cpu": observe.cpu_seconds()}
    if tracer:
        tracer.join()
        if tracer.error is not None:
            raise tracer.error
        tlog(f"profiler {tracer.costs}")
    memory_peak = observe.memory_peak_bytes()
    driver.release()
    tlog("window and trace closed, checking")
    numbers = driver.check(control=bool(args.control))
    counts = driver.counts(numbers)

    limits_held = all(v["value"] <= v["limit"] for v in numbers.values() if isinstance(v, dict))
    correct = bool(limits_held and counts["failed"] == 0 and counts["attempted"] > 0)
    line: dict = {"correct": correct, "attempted": counts["attempted"], "failed": counts["failed"]}
    dev = dict(device, memory_peak_bytes=memory_peak)
    wanted = manifest.metrics_for(cell.manifest, "per_layer" if args.trace else "end_to_end", cell.name)
    if args.trace:
        extra = driver.observations()
        obs = dict(
            extra,
            window_s=counts["window_s"],
            bytes=counts["bytes"],
            cpu_s=after["cpu"] - before["cpu"] + extra.get("child_cpu_s", 0.0),
            ledger=(before["ledger"], after["ledger"]),
            compiles=(before["compiles"], after["compiles"]),
            device=device,
            undisturbed_s=tracer.start_after - observe.TRACE_CLEAR_SECONDS,
            root=ROOT,
            algo=cell.config["algo"],
        )
        values = _read_per_layer(args, cell, tracer, obs, wanted, dev, line)
    else:
        values = dict(driver.end_to_end(counts), setup_s=setup_s)
    metrics = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in wanted if values.get(m["name"]) is not None
    }
    if args.rehearse:
        # a CPU's numbers are never written under a device metric's name
        line["rehearsal"] = {"would_report": sorted(metrics)}
        metrics = {}
    line["metrics"] = metrics
    line["device"] = dev
    line["checks"] = numbers  # each number compared beside its limit; last in the line

    for f in counts["failures"]:
        log("failed operation:", json.dumps(f))
    if counts["failures"]:
        out = os.path.join(ROOT, ".bench_work", "failures")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, f"{cell.name}.seed{cell.seed}.json"), "w") as f:
            json.dump({"classes": counts["classes"], "failures": counts["failures"]}, f, indent=1)
    log(f"window: {counts['window_s']:.3f} s, set-up {setup_s:.3f} s, compiles in window "
        f"{after['compiles']['compiled'] - before['compiles']['compiled']} "
        f"(cache hits {after['compiles']['cache_hits'] - before['compiles']['cache_hits']})")
    log("checks:", json.dumps(numbers))
    return line, 0


def main(argv=None) -> int:
    args = parse_args(argv)
    line, code = run(args)
    print(json.dumps(line), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
