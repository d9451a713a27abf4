"""Entry driver: the download itself, ``torrent-tpu download <torrent>
<dir> --hasher tpu --no-resume`` as one long-lived client taking a queue
of downloads from eight seeders on 127.0.0.1.

The leecher is what ``tools/cli.py:_download`` builds for those flags,
``Client(ClientConfig(hasher="tpu", resume=False))`` with nothing else
set, in this process (which holds the chip). A download is what the
command does between its parse and its ``on_complete``: ``parse_metainfo``
of the torrent, ``Client.add`` into an empty directory, the peers'
addresses as an announce would return them, and the wait for the last
piece; then the torrent is removed and the next one added. The seeders
are ``Client(hasher="cpu")`` processes of this file's ``seed`` command
(``JAX_PLATFORMS=cpu``: they never touch the chip), started in set-up and
kept for the whole run.

Every delivery the leecher judges is recorded, index and outcome in
order: from ``Torrent.on_piece_verdict`` where the program publishes it,
else from ``_finish_piece``'s own return value (a parent of the PR that
added this cell). After the window the comparison runs the planted
download, outside the timing: the leecher alone with a ninth seeder whose
copy has one byte flipped in a seeded 1/8 of the pieces, until the
leecher has dropped it; then the same directory again with the honest
seeders, to the end.
"""

from __future__ import annotations

import asyncio
import hashlib
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

FALLBACK_FAMILY = "torrent_tpu_ingest_verify_seconds"
SEEDER_START_SECONDS = 120.0


def _say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# the seeders ----------------------------------------------------------------


async def _seed(torrent_path: str, data_dir: str, port_file: str, claim_all: bool) -> None:
    """One seeder: a ``hasher="cpu"`` client that holds the whole payload
    and serves until its standard input closes. ``claim_all`` writes a
    resume file that claims every piece first, so that the client serves
    what the directory holds without judging it (the poisoner)."""
    from torrent_tpu.codec.metainfo import parse_metainfo
    from torrent_tpu.session.client import Client, ClientConfig

    with open(torrent_path, "rb") as f:
        meta = parse_metainfo(f.read())
    if claim_all:
        from torrent_tpu.session.resume import FsResumeStore, ResumeData
        from torrent_tpu.utils.bitfield import Bitfield

        n = meta.info.num_pieces
        full = Bitfield(n)
        for i in range(n):
            full.set(i)
        FsResumeStore(data_dir).save(ResumeData(meta.info_hash, n, full.to_bytes(), completed_reported=True))
    client = Client(ClientConfig(host="127.0.0.1", hasher="cpu", resume=claim_all))
    await client.start()
    try:
        t = await client.add(meta, data_dir)
        if not t.bitfield.complete:
            raise RuntimeError(f"the seeder holds {t.bitfield.count()} of {meta.info.num_pieces} pieces")
        with open(port_file + ".tmp", "w") as f:
            f.write(str(client.port))
        os.replace(port_file + ".tmp", port_file)
        await asyncio.get_running_loop().run_in_executor(None, sys.stdin.buffer.read)
    finally:
        await client.close()


class _Seeders:
    """The seeder processes of a run."""

    def __init__(self, work_dir: str, torrent_path: str):
        self.work_dir, self.torrent_path = work_dir, torrent_path
        self.procs: list[tuple[subprocess.Popen, str]] = []

    def start(self, data_dir: str, claim_all: bool = False) -> None:
        port_file = os.path.join(self.work_dir, f"seeder{len(self.procs)}.port")
        argv = [sys.executable, os.path.abspath(__file__), "seed", self.torrent_path, data_dir, port_file]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        proc = subprocess.Popen(argv + (["--claim-all"] if claim_all else []), stdin=subprocess.PIPE, env=env)
        self.procs.append((proc, port_file))

    def ports(self) -> list[int]:
        """Every seeder's port, once each has come up seeding."""
        out, t0 = [], time.monotonic()
        for proc, port_file in self.procs:
            while not os.path.exists(port_file):
                if proc.poll() is not None:
                    raise RuntimeError(f"a seeder ended with code {proc.returncode} before it was up")
                if time.monotonic() - t0 > SEEDER_START_SECONDS:
                    raise RuntimeError("a seeder was not up in time")
                time.sleep(0.05)
            with open(port_file) as f:
                out.append(int(f.read()))
        return out

    def cpu_seconds(self) -> float:
        """CPU seconds the seeders have used so far (they are the load's
        other half); 0.0 where ``/proc`` does not say."""
        total = 0.0
        for proc, _ in self.procs:
            try:
                with open(f"/proc/{proc.pid}/stat") as f:
                    fields = f.read().rsplit(")", 1)[1].split()
                total += (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")
            except (OSError, IndexError, ValueError):
                return 0.0
        return total

    def stop(self) -> None:
        for proc, _ in self.procs:
            if proc.poll() is None:
                proc.stdin.close()
        for proc, _ in self.procs:
            try:
                proc.wait(10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        self.procs.clear()


# the driver -----------------------------------------------------------------


class Driver:
    def __init__(self, cell):
        self.cell = cell
        c = cell.config
        self.piece_length = int(c["piece_length"])
        self.length = int(c["payload_bytes"])
        self.n_pieces = -(-self.length // self.piece_length)
        self.n_peers = int(c["peers"])
        self.stall_seconds = float(c["stall_seconds"])
        self.name = "payload.bin"
        self.loop = asyncio.new_event_loop()
        self.client = None
        self.seeders: _Seeders | None = None
        self.downloads: list[dict] = []  # per download: dir, t_start, t_end, events, stalled
        self.planted: dict = {}

    # what the program counts ------------------------------------------------

    def launch_count(self) -> int:
        """Ledger ``launch`` entries: a count a parent has too."""
        from torrent_tpu.obs.ledger import pipeline_ledger

        return pipeline_ledger().snapshot()["stages"].get("launch", {}).get("ops", 0)

    def _fallback_verdicts(self) -> int:
        from torrent_tpu.obs.hist import histograms

        return histograms().get(FALLBACK_FAMILY, plane="hashlib_fallback").snapshot()[1]

    def _sched_snapshot(self) -> dict | None:
        sched = getattr(self.client, "ingest_scheduler", None)
        return None if sched is None else sched.metrics_snapshot()

    # set-up ---------------------------------------------------------------

    def _plan(self) -> dict:
        """``{piece: offset of the flipped byte}`` of the poisoner's copy:
        ``round(share * n)`` pieces, among them the first piece in its
        first block, the short last piece and one piece in its last block."""
        import numpy as np

        n, plen = self.n_pieces, self.piece_length
        block = int(self.cell.config["block_bytes"])
        rng = np.random.Generator(np.random.Philox([self.cell.seed, 0x5E]))
        k = max(3, round(float(self.cell.config["planted_share"]) * n))
        others = [int(i) for i in rng.permutation(np.arange(1, n - 1))[: k - 2]]
        tail = self.length - (n - 1) * plen
        plan = {0: int(rng.integers(8, block)), n - 1: int(rng.integers(8, tail)),
                others[0]: int(rng.integers(plen - block, plen))}
        for i in others[1:]:
            plan[i] = int(rng.integers(8, plen))
        return plan

    def _write_copy(self, root: str, corrupt: dict) -> list:
        from benchmark.harness import payload

        os.makedirs(root)
        path = os.path.join(root, self.name)
        digests = payload.write_payload(path, self.cell.seed, self.n_pieces, self.piece_length, corrupt)
        os.truncate(path, self.length)
        return digests

    def setup(self) -> None:
        from benchmark.harness import bencode
        from torrent_tpu.utils.device import enable_compile_cache

        work = self.cell.work_dir
        self.src_dir, self.bad_dir = os.path.join(work, "source"), os.path.join(work, "poisoned")
        self.plan = self._plan()
        digests = self._write_copy(self.src_dir, {})
        self._write_copy(self.bad_dir, self.plan)
        # the short last piece's digest is hashlib's over the bytes the file keeps of it
        with open(os.path.join(self.src_dir, self.name), "rb") as f:
            f.seek((self.n_pieces - 1) * self.piece_length)
            digests[-1] = hashlib.sha1(f.read()).digest()
        info = {"length": self.length, "name": self.name, "piece length": self.piece_length, "pieces": b"".join(digests)}
        self.torrent_path = os.path.join(work, "payload.torrent")
        with open(self.torrent_path, "wb") as f:
            f.write(bencode.encode({"announce": "", "info": info}))
        self.cell.log(f"payload of {self.n_pieces} pieces written twice, {len(self.plan)} pieces planted")
        self.seeders = _Seeders(work, self.torrent_path)
        for _ in range(self.n_peers):
            self.seeders.start(self.src_dir)
        self.seeders.start(self.bad_dir, claim_all=True)
        enable_compile_cache()  # as tools/cli.py:main does for --hasher tpu
        self.client = self.loop.run_until_complete(self._leecher())
        *self.honest, self.poisoner = self.seeders.ports()
        self.cell.log(f"{self.n_peers} seeders and the poisoner are up")
        for i in range(int(self.cell.traffic["warm_downloads"])):
            d = self._download_into(f"warm{i}", self.honest, 10 * self.stall_seconds)
            if d["stalled"]:
                raise RuntimeError("a warm download got no verdict for the stall limit")
            self.cell.log(f"warm download {i + 1} done in {d['t_end'] - d['t_start']:.2f} s, {self.launch_count()} launches so far")
        self.downloads.clear()

    async def _leecher(self):
        """``_download``'s client for ``--hasher tpu --no-resume``."""
        from torrent_tpu.session.client import Client, ClientConfig

        client = Client(ClientConfig(hasher="tpu", resume=False))
        await client.start()
        return client

    # a download -----------------------------------------------------------

    @staticmethod
    def _observe(torrent, events: list) -> None:
        """Record every delivery the leecher judges, in order."""
        if hasattr(torrent, "on_piece_verdict"):
            torrent.on_piece_verdict = lambda index, outcome: events.append((index, outcome))
            return
        finish = torrent._finish_piece  # a parent publishes nothing: the method's own return value

        async def watched(partial):
            outcome = await finish(partial)
            if outcome != "stale":
                events.append((partial.index, outcome))
            return outcome

        torrent._finish_piece = watched

    async def _run(self, dest: str, ports: list[int], stall_seconds: float, until_dropped: bool = False) -> dict:
        """One ``Client.add`` of the torrent into ``dest`` with the peers
        at ``ports``, to its completion (or, ``until_dropped``, until the
        leecher has refused a delivery and has no peer left); given up
        where no piece is judged for ``stall_seconds``."""
        from torrent_tpu.codec.metainfo import parse_metainfo
        from torrent_tpu.net.types import AnnouncePeer

        with open(self.torrent_path, "rb") as f:
            meta = parse_metainfo(f.read())
        os.makedirs(dest, exist_ok=True)
        events: list = []
        out = {"dir": dest, "events": events, "stalled": False, "t_start": time.monotonic()}
        torrent = await self.client.add(meta, dest)
        out["adopted"] = torrent.bitfield.count()
        self._observe(torrent, events)
        torrent._connect_new_peers([AnnouncePeer(ip="127.0.0.1", port=p) for p in ports])
        seen, t_seen = -1, time.monotonic()
        while True:
            if until_dropped:
                await asyncio.sleep(0.02)
                if not torrent.peers and any(outcome != "ok" for _, outcome in events):
                    break
            else:
                try:
                    await asyncio.wait_for(torrent.on_complete.wait(), 1.0)
                    break
                except asyncio.TimeoutError:
                    pass
            if len(events) != seen:
                seen, t_seen = len(events), time.monotonic()
            elif time.monotonic() - t_seen > stall_seconds:
                _say(f"download into {dest} judged nothing for {stall_seconds:.0f} s: {torrent.status()}, scheduler {self._sched_snapshot()}")
                out["stalled"] = True
                break
        out["t_end"] = time.monotonic()
        await self.client.remove(meta.info_hash)
        return out

    def _download_into(self, name: str, ports: list[int], stall_seconds: float | None = None, **kw) -> dict:
        import jax

        dest = os.path.join(self.cell.work_dir, "leech", name)
        with jax.profiler.TraceAnnotation("bench_download"):
            d = self.loop.run_until_complete(self._run(dest, ports, stall_seconds or self.stall_seconds, **kw))
        self.downloads.append(d)
        return d

    # window ---------------------------------------------------------------

    def window(self, seconds: float) -> float:
        self.before = {"sched": self._sched_snapshot(), "fallback": self._fallback_verdicts(),
                       "launches": self.launch_count(), "seeders_cpu": self.seeders.cpu_seconds()}
        self.t_open = time.monotonic()
        while time.monotonic() - self.t_open < seconds and not (self.downloads and self.downloads[-1]["stalled"]):
            self._download_into(f"timed{len(self.downloads)}", self.honest)
        self.after = {"sched": self._sched_snapshot(), "fallback": self._fallback_verdicts(),
                      "launches": self.launch_count(), "seeders_cpu": self.seeders.cpu_seconds()}
        return self.t_open

    # after the window -----------------------------------------------------

    def _close_client(self) -> None:
        if self.client is not None:
            self.loop.run_until_complete(asyncio.wait_for(self.client.close(), 30))
            self.client = None

    def release(self) -> None:
        """Close the leecher: its torrents, its scheduler, its lanes."""
        sched = getattr(self.client, "ingest_scheduler", None)
        self._close_client()
        self.closed = None if sched is None else sched.metrics_snapshot()

    def abort(self) -> None:
        try:
            if not self.loop.is_closed():
                self._close_client()
                self.loop.close()
        except Exception as e:  # a run that failed is ending anyway: say so and go on
            _say(f"leecher close at abort: {e!r}")
        if self.seeders is not None:
            self.seeders.stop()

    def _planted_download(self) -> None:
        """Outside the window, on a leecher of its own: alone with the
        poisoner until the leecher has dropped it, then the same directory
        again (the ban is the torrent's) with the honest seeders."""
        self.client = self.loop.run_until_complete(self._leecher())
        try:
            one = self._download_into("planted", [self.poisoner], until_dropped=True)
            from benchmark.harness import reference_session

            one["on_disk"] = reference_session.piece_digests(
                os.path.join(one["dir"], self.name), self.length, self.piece_length
            )
            two = self._download_into("planted", self.honest)
        finally:
            self._close_client()
        del self.downloads[-2:]
        self.planted = {"one": one, "two": two}

    def check(self, control: bool = False) -> dict:
        from benchmark.harness import reference_session as ref

        torrent = ref.read_torrent(self.torrent_path)
        held = ref.copy_verdicts(os.path.join(self.src_dir, self.name), torrent)
        held_bad = ref.copy_verdicts(os.path.join(self.bad_dir, self.name), torrent)
        self._planted_download()
        self.seeders.stop()

        def program(events):
            if not control:
                return events
            return [(i, "ok" if valid else "corrupt") for (i, _), valid in zip(events, ref.control_verdicts(len(events)))]

        sums = {"compared": 0, "reference_invalid": 0, "wrong_verdicts": 0, "missing_verdicts": 0}

        def add(numbers: dict) -> None:
            for key in sums:
                sums[key] += numbers[key]

        # every timed download: its verdict events, then its file, piece by piece
        disk_mismatch = 0
        for d in self.downloads:
            add(ref.compare_deliveries(program(d["events"]), held, every="valid"))
            disk_mismatch += sum(1 for ok in ref.copy_verdicts(os.path.join(d["dir"], self.name), torrent) if not ok)
        timed = dict(sums)
        # the planted download: alone with the poisoner, then the honest seeders
        one, two = self.planted["one"], self.planted["two"]
        first = ref.compare_deliveries(program(one["events"]), held_bad, every="valid")
        first["missing_verdicts"] = 0 if any(o != "ok" for _, o in one["events"]) else 1  # it met no planted piece
        add(first)
        second = ref.compare_deliveries(program(two["events"]), held, every="valid")
        accepted = {i for i, o in one["events"] if o == "ok"}
        # a piece the first phase wrote is adopted by the recheck, not delivered again
        again = {i for i, o in two["events"] if o == "ok"}
        second["missing_verdicts"] = sum(1 for i in range(self.n_pieces) if i not in accepted and i not in again)
        add(second)
        bad_digests = ref.piece_digests(os.path.join(self.bad_dir, self.name), self.length, self.piece_length)
        planted_on_disk = sum(1 for i in self.plan if one["on_disk"][i] in (bad_digests[i], torrent["digests"][i]))
        disk_mismatch += sum(1 for i in accepted if one["on_disk"][i] != torrent["digests"][i])
        disk_mismatch += sum(1 for ok in ref.copy_verdicts(os.path.join(two["dir"], self.name), torrent) if not ok)

        numbers: dict = {
            "compared": sums["compared"],
            "reference_invalid": sums["reference_invalid"],
            "wrong_verdicts": {"value": sums["wrong_verdicts"], "limit": 0},
            "missing_verdicts": {"value": sums["missing_verdicts"], "limit": 0},
            "disk_mismatch": {"value": disk_mismatch, "limit": 0},
            "planted_on_disk": {"value": planted_on_disk, "limit": 0},
            "stalled_downloads": {"value": sum(d["stalled"] for d in self.downloads + [one, two]), "limit": 0},
            "hashlib_fallback_verdicts": {"value": self.after["fallback"] - self.before["fallback"], "limit": 0},
            "downloads": len(self.downloads),
            "timed_deliveries": timed["compared"],
            "planted_pieces": len(self.plan),
            "planted_refused": sorted({i for i, o in one["events"] if o != "ok"}),
            "planted_phase_one_deliveries": len(one["events"]),
            "planted_adopted": two["adopted"],
            "launches": self.after["launches"] - self.before["launches"],
        }
        # what only a program with the scheduler on this road can be asked
        b, a = self.before["sched"], self.after["sched"]
        if a is not None:
            for key in ("cpu_fallback_launches", "launch_failures", "failed_pieces"):
                numbers[key] = {"value": a[key] - b[key], "limit": 0}
            kernels = sorted({str(v.get("kernel")) for v in a["lane_stats"].values()})
            numbers["hashlib_lanes"] = {"value": sum(k == "hashlib" for k in kernels), "limit": 0}
            numbers["lane_kernels"] = kernels
            numbers["verdicts_pending"] = {"value": a["queue_pieces"] + self.closed["queue_pieces"], "limit": 0}
            numbers["staging_outstanding"] = {"value": self.closed["staging"]["outstanding"], "limit": 0}
            numbers["ingest_pieces"] = a["tenants"]["ingest"]["served_pieces"] - b["tenants"]["ingest"]["served_pieces"]
        return numbers

    def counts(self, numbers: dict) -> dict:
        failed = sum(numbers[k]["value"] for k in ("wrong_verdicts", "missing_verdicts", "disk_mismatch", "planted_on_disk"))
        valid = sum(1 for d in self.downloads for i in {i for i, o in d["events"] if o == "ok"})
        short = sum(1 for d in self.downloads if (self.n_pieces - 1, "ok") in d["events"])
        tail = self.length - (self.n_pieces - 1) * self.piece_length
        return {
            "attempted": numbers["compared"], "failed": failed,
            # payload bytes of the pieces judged valid in the timed downloads
            "bytes": valid * self.piece_length - short * (self.piece_length - tail),
            # to the last verdict counted: the download under way at the close is finished
            "window_s": self.downloads[-1]["t_end"] - self.t_open,
            "classes": None, "failures": [],
        }

    def end_to_end(self, counts: dict) -> dict:
        return {"verify_gib_s": counts["bytes"] / 2**30 / counts["window_s"]}

    def observations(self) -> dict:
        took = [round(d["t_end"] - d["t_start"], 3) for d in self.downloads]
        _say(f"{len(self.downloads)} timed downloads, seconds each: {took}")
        b, a = self.before, self.after
        return {
            "sched": None if a["sched"] is None else (b["sched"], a["sched"]),
            "launches": a["launches"] - b["launches"],
            "child_cpu_s": a["seeders_cpu"] - b["seeders_cpu"],
            "peers": self.n_peers,
            "downloads": len(self.downloads),
        }


if __name__ == "__main__":
    if len(sys.argv) < 5 or sys.argv[1] != "seed":
        raise SystemExit("usage: session.py seed <torrent> <dir> <port file> [--claim-all]")
    asyncio.run(_seed(sys.argv[2], sys.argv[3], sys.argv[4], "--claim-all" in sys.argv[5:]))
