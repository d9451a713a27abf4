"""Fabric scaling measurement (r7 rung): spawn N fabric-verify worker
processes over the shared-directory heartbeat transport against one
synthetic library and report wall-clock GiB/s. One JSON line per run on
stdout: {"nproc", "rep", "seconds", "gib_per_sec", "pieces", "valid",
"device", "per_process", "fleet_bottleneck"}.

This launcher never imports JAX: with ``--hasher tpu`` each worker is
handed its own chip through its environment (``utils.device.worker_env``).
``device`` is what worker 0's JAX reported, not the flag, every entry of
``per_process`` carries its own worker's, and the run fails unless each
is one TPU chip: more workers than chips is refused by the surplus
worker's fatal backend init, never measured on the host's CPU.

``per_process`` embeds every worker's pipeline-ledger breakdown (stage
busy/bytes/utilization, bottleneck verdict, overlap) straight from its
result record, and ``fleet_bottleneck`` is worker 0's two-level fleet
verdict (limiting process → its limiting stage) — so a banked fabric
rate carries its own per-process attribution instead of a bare number.

The library is built once (deterministic seed) and reused across runs;
each run gets a fresh heartbeat dir. Workers are plain OS processes —
no jax.distributed — so the run shape matches tests/test_fabric.py's
two-process smoke and scales to any local process count.
"""

import argparse
import glob
import json
import os
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_library(root: str, n_torrents: int, mb_per: int, piece_kb: int):
    from torrent_tpu.tools.make_torrent import make_torrent

    tdir = os.path.join(root, "torrents")
    ddir = os.path.join(root, "data")
    if glob.glob(os.path.join(tdir, "*.torrent")):
        return tdir, ddir  # reuse the previously built library
    os.makedirs(tdir, exist_ok=True)
    rng = np.random.default_rng(5)
    plen = piece_kb << 10
    for t in range(n_torrents):
        droot = os.path.join(ddir, f"fab{t}")
        os.makedirs(droot, exist_ok=True)
        payload = os.path.join(droot, "payload.bin")
        size = (mb_per << 20) + (t + 1) * (plen // 3)  # ragged tails differ
        with open(payload, "wb") as f:
            # chunked writes keep resident memory bounded
            left = size
            while left > 0:
                n = min(left, 64 << 20)
                f.write(rng.integers(0, 256, n, dtype=np.uint8).tobytes())
                left -= n
        with open(os.path.join(tdir, f"fab{t}.torrent"), "wb") as f:
            f.write(
                make_torrent(payload, "http://bench.invalid/announce", piece_length=plen)
            )
    return tdir, ddir


def run_once(tdir, ddir, hb, nproc, hasher, batch_target):
    from torrent_tpu.utils.device import worker_env

    base = dict(os.environ)
    base["PYTHONPATH"] = REPO + os.pathsep + base.get("PYTHONPATH", "")
    errs = [open(os.path.join(hb, f"worker_{p}.err"), "w+") for p in range(nproc)]
    t0 = time.perf_counter()
    workers = {
        p: subprocess.Popen(
            [
                sys.executable, "-m", "torrent_tpu", "fabric-verify",
                tdir, ddir, "--hasher", hasher,
                "--num-processes", str(nproc), "--process-id", str(p),
                "--heartbeat-dir", hb, "--batch-target", str(batch_target),
                "--result-file", os.path.join(hb, f"result_{p}.json"),
            ],
            env=worker_env(base, hasher, p),
            stdout=subprocess.DEVNULL, stderr=errs[p],
        )
        for p in range(nproc)
    }
    try:
        # the first worker to fail fails the run at once: its peers would
        # otherwise adopt its units and bank a rate for fewer processes
        running = dict(workers)
        while running:
            for p, w in list(running.items()):
                if w.poll() is None:
                    continue
                del running[p]
                if w.returncode != 0:
                    errs[p].seek(0)
                    raise RuntimeError(
                        f"worker {p} rc={w.returncode}: {errs[p].read()[-1500:]}"
                    )
            if time.perf_counter() - t0 > 3600:
                raise RuntimeError(f"workers {sorted(running)} still running after 3600 s")
            time.sleep(0.01)
    finally:
        for w in workers.values():
            if w.poll() is None:
                w.kill()
                w.wait()
        for f in errs:
            f.close()
    seconds = time.perf_counter() - t0
    # every worker's result file: its device as its own JAX reported it,
    # and its pipeline-ledger attribution, so the rung's record explains
    # its rate instead of just banking it
    recs = [json.load(open(os.path.join(hb, f"result_{p}.json"))) for p in range(nproc)]
    rec = recs[0]
    if rec["n_valid"] != rec["n_pieces"]:
        raise RuntimeError(f"incomplete verify: {rec['n_valid']}/{rec['n_pieces']}")
    per_process = []
    for p, wrec in enumerate(recs):
        device = wrec.get("device") or {}
        if hasher != "cpu" and (device.get("platform"), device.get("count")) != ("tpu", 1):
            raise RuntimeError(f"worker {p} did not hash on its own chip: device={device}")
        led = wrec.get("ledger") or {}
        per_process.append(
            {
                "pid": wrec.get("pid", p),
                "device": device,
                "pieces_verified": wrec.get("pieces_verified"),
                "units_done": wrec.get("units_done"),
                "units_adopted": wrec.get("units_adopted"),
                "wall_s": led.get("wall_s"),
                "stages": led.get("stages"),
                "bottleneck": led.get("bottleneck"),
                "overlap": led.get("overlap"),
            }
        )
    rec["per_process"] = per_process
    return seconds, rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workdir", required=True, help="library + heartbeat scratch")
    ap.add_argument("--nproc", type=int, required=True)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--torrents", type=int, default=8)
    ap.add_argument("--mb-per-torrent", type=int, default=64)
    ap.add_argument("--piece-kb", type=int, default=1024)
    ap.add_argument("--hasher", choices=("cpu", "tpu"), default="cpu")
    ap.add_argument("--batch-target", type=int, default=256)
    args = ap.parse_args()

    tdir, ddir = build_library(
        args.workdir, args.torrents, args.mb_per_torrent, args.piece_kb
    )
    total_bytes = sum(
        os.path.getsize(p)
        for p in glob.glob(os.path.join(ddir, "*", "payload.bin"))
    )
    for rep in range(args.reps):
        hb = os.path.join(args.workdir, f"hb_{args.nproc}_{rep}")
        os.makedirs(hb, exist_ok=True)
        seconds, rec = run_once(
            tdir, ddir, hb, args.nproc, args.hasher, args.batch_target
        )
        fleet = rec.get("fleet") or {}
        print(
            json.dumps(
                {
                    "nproc": args.nproc,
                    "rep": rep,
                    "seconds": round(seconds, 3),
                    "gib_per_sec": round(total_bytes / seconds / 2**30, 4),
                    "pieces": rec["n_pieces"],
                    "valid": rec["n_valid"],
                    "plan": rec["plan"],
                    "hasher": args.hasher,
                    "device": rec.get("device"),
                    "per_process": rec.get("per_process", []),
                    "fleet_bottleneck": fleet.get("bottleneck"),
                }
            ),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
