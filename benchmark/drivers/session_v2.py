"""Entry driver: a v2 download, ``torrent-tpu download <torrent> <dir>
--hasher tpu --no-resume`` of a pure BEP 52 torrent, as one long-lived
client taking a queue of downloads from eight seeders on 127.0.0.1.

It is ``session-256k``'s driver (``drivers/session.py``) on the session's
v2 arm: the same leecher, ``Client(ClientConfig(hasher="tpu",
resume=False))`` in this process (which holds the chip), the same window,
warm download, planted phase and stall limit. What differs is the
torrent and its road in: the payload is ``harness/payload_v2.py``'s
release mix, the torrent a pure-v2 one whose roots and layers come from
``harness/reference_v2.py``'s fold, and every download (and every seeder)
hands the torrent's bytes to ``Client.add_torrent_bytes``, as the command
does, which parses it to the v2 session view. The seeders are
``Client(hasher="cpu")`` processes of this file's ``seed`` command
(``JAX_PLATFORMS=cpu``).

Every delivery the leecher judges is recorded from
``Torrent.on_piece_verdict``. The planted phase puts each kind of
corrupt delivery (``FORCED``) to the judge through a poisoner that holds
that piece alone, before the poisoner of every piece; a kind never
refused there fails the run (``forced_kinds_unrefused``), since the
timed downloads hold only honest deliveries. Where the program counts its v2 ingest
(``session/v2.v2_ingest_stats``) the driver reads the counters around
the window; a parent without them is held to its verdicts alone.
"""

from __future__ import annotations

import asyncio
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.drivers import session as v1  # noqa: E402
from benchmark.harness import payload_v2, reference_v2  # noqa: E402
from benchmark.harness.reference_v2 import BLOCK  # noqa: E402

NAME = "payload"
STAMP = 16  # bytes of a leaf that payload_v2 stamps with its file and leaf index
# the kinds of delivery the planted phase must see refused, in the order _plan picks them
FORCED = ("full_first_leaf", "full_last_block", "tail", "single_piece_file")


async def _seed(torrent_path: str, data_dir: str, port_file: str, claim: str | None) -> None:
    """One seeder: a ``hasher="cpu"`` client that holds the whole payload
    and serves until its standard input closes. ``claim``: from a resume
    file that claims every piece (``"all"``, the poisoner) or the pieces
    of a comma-separated list (a poisoner of one kind of piece), with no
    recheck."""
    from torrent_tpu.session.client import Client, ClientConfig

    with open(torrent_path, "rb") as f:
        data = f.read()
    claimed = None
    if claim is not None:
        from torrent_tpu.codec.metainfo import parse_any_metainfo
        from torrent_tpu.session.resume import FsResumeStore, ResumeData
        from torrent_tpu.session.v2 import v2_session_meta
        from torrent_tpu.utils.bitfield import Bitfield

        meta, info_hash = parse_any_metainfo(data)
        n = v2_session_meta(meta).info.num_pieces
        claimed = range(n) if claim == "all" else [int(i) for i in claim.split(",")]
        held = Bitfield(n)
        for i in claimed:
            held.set(i)
        FsResumeStore(data_dir).save(ResumeData(info_hash, n, held.to_bytes(), completed_reported=held.complete))
    client = Client(ClientConfig(host="127.0.0.1", hasher="cpu", resume=claim is not None))
    await client.start()
    try:
        t = await client.add_torrent_bytes(data, data_dir)
        want = t.info.num_pieces if claimed is None else len(claimed)
        if t.bitfield.count() != want:
            raise RuntimeError(f"the seeder holds {t.bitfield.count()} of {want} pieces")
        with open(port_file + ".tmp", "w") as f:
            f.write(str(client.port))
        os.replace(port_file + ".tmp", port_file)
        await asyncio.get_running_loop().run_in_executor(None, sys.stdin.buffer.read)
    finally:
        await client.close()


class _Seeders(v1._Seeders):
    """``session.py``'s seeder processes, running this file's ``seed``."""

    def start(self, data_dir: str, claim: str | None = None) -> None:
        import subprocess

        port_file = os.path.join(self.work_dir, f"seeder{len(self.procs)}.port")
        argv = [sys.executable, os.path.abspath(__file__), "seed", self.torrent_path, data_dir, port_file]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        proc = subprocess.Popen(argv + (["--claim", claim] if claim else []), stdin=subprocess.PIPE, env=env)
        self.procs.append((proc, port_file))

    def end(self, first: int) -> None:
        """End the seeders from the ``first``-th on; ``stop`` then only
        reaps them."""
        for proc, _ in self.procs[first:]:
            if proc.poll() is None:
                proc.stdin.close()
                proc.wait(10)


class Driver(v1.Driver):
    def __init__(self, cell):
        super().__init__(cell)
        c = cell.config
        self.files = payload_v2.file_plan(c["files"])
        if sum(n for _, n in self.files) != self.length:
            raise ValueError(f"the file mix holds {sum(n for _, n in self.files)} bytes, payload_bytes says {self.length}")
        # the session's pieces: every file's in file-tree order, each file starting on a piece
        self.pieces = []  # (file index, start in the file, length)
        for f, (_, length) in enumerate(self.files):
            for p in range(reference_v2.num_pieces(length, self.piece_length)):
                start = p * self.piece_length
                self.pieces.append((f, start, min(self.piece_length, length - start)))
        self.n_pieces = len(self.pieces)
        self.single = {i for i, (f, _, _) in enumerate(self.pieces) if self.files[f][1] <= self.piece_length}

    # what the program counts ------------------------------------------------

    def _counters(self) -> dict:
        from torrent_tpu.models import v2
        from torrent_tpu.session import v2 as session_v2

        ingest = getattr(session_v2, "v2_ingest_stats", None)
        return {"fallback": self._fallback_verdicts(), "launches": self.launch_count(),
                "leaf_rows": v2.leaf_launch_stats(), "v2_ingest": ingest() if ingest else None,
                "seeders_cpu": self.seeders.cpu_seconds()}

    # set-up ---------------------------------------------------------------

    def _plan(self) -> dict:
        """``{piece: offset in its file of the flipped byte}`` of the
        poisoner's copy: ``round(share * n)`` pieces, the first four a
        full piece in its first leaf, a full piece in its last block, a
        multi-piece file's short tail and a file of one piece (``FORCED``),
        and never a byte of a leaf's stamp."""
        import numpy as np

        rng = np.random.Generator(np.random.Philox([self.cell.seed, 0x5E2]))
        plen = self.piece_length
        full = [i for i, (_, _, n) in enumerate(self.pieces) if n == plen]
        tails = [i for i, (f, _, n) in enumerate(self.pieces) if n < plen and i not in self.single]
        single = sorted(self.single)
        first, last = (int(i) for i in rng.choice(full, size=2, replace=False))
        picked = {first: int(rng.integers(STAMP, BLOCK)), last: int(rng.integers(plen - BLOCK + STAMP, plen))}
        for pool in (tails, single):
            i = int(rng.choice(pool))
            picked[i] = int(rng.integers(STAMP, self.pieces[i][2]))
        k = max(len(picked), round(float(self.cell.config["planted_share"]) * self.n_pieces))
        rest = [i for i in range(self.n_pieces) if i not in picked]
        for i in rng.choice(rest, size=k - len(picked), replace=False):
            at = int(rng.integers(0, self.pieces[i][2]))
            picked[int(i)] = at if at % BLOCK >= STAMP else min(at + STAMP, self.pieces[i][2] - 1)
        return {i: self.pieces[i][1] + off for i, off in picked.items()}

    def _write_copies(self) -> None:
        """The payload twice, clean and poisoned, and the torrent: a pure-v2
        one as ``payload_v2.write_torrent`` writes it, with no tracker (the
        leecher is given its peers)."""
        from benchmark.harness import bencode

        work = self.cell.work_dir
        self.src_dir, self.bad_dir = os.path.join(work, "source"), os.path.join(work, "poisoned")
        self.plan = self._plan()
        self.forced = list(self.plan)[: len(FORCED)]
        entries = payload_v2.write_payload(self.src_dir, NAME, self.cell.seed, self.files, self.piece_length)
        payload_v2.write_payload(self.bad_dir, NAME, self.cell.seed, self.files, self.piece_length)
        flips = {(self.pieces[i][0], i): off for i, off in self.plan.items()}
        payload_v2.apply_corruption(self.bad_dir, NAME, self.files, flips)
        tree: dict = {}
        for e in entries:
            node = tree
            for part in e["path"]:
                node = node.setdefault(part, {})
            node[""] = {"length": e["length"], "pieces root": e["pieces_root"]}
        info = {"file tree": tree, "meta version": 2, "name": NAME, "piece length": self.piece_length}
        layers = {e["pieces_root"]: b"".join(e["layer"]) for e in entries if e["layer"]}
        self.torrent_path = os.path.join(work, "payload.torrent")
        with open(self.torrent_path, "wb") as f:
            f.write(bencode.encode({"announce": "", "info": info, "piece layers": layers}))

    def setup(self) -> None:
        from torrent_tpu.utils.device import enable_compile_cache

        work = self.cell.work_dir
        self._write_copies()
        self.cell.log(f"payload of {len(self.files)} files, {self.n_pieces} pieces written twice, {len(self.plan)} pieces planted")
        self.seeders = _Seeders(work, self.torrent_path)
        for _ in range(self.n_peers):
            self.seeders.start(self.src_dir)
        self.seeders.start(self.bad_dir, claim="all")
        for k, i in enumerate(self.forced):
            # a poisoner of one kind: the poisoned copy (a link to it) and a resume file of its own
            own = os.path.join(work, f"forced{k}")
            os.makedirs(own)
            os.symlink(os.path.join(self.bad_dir, NAME), os.path.join(own, NAME))
            self.seeders.start(own, claim=str(i))
        enable_compile_cache()  # as tools/cli.py:main does for --hasher tpu
        self.client = self.loop.run_until_complete(self._leecher())
        ports = self.seeders.ports()
        self.honest, self.poisoner = ports[: self.n_peers], ports[self.n_peers]
        self.forced_ports = ports[self.n_peers + 1 :]
        self.cell.log(f"{self.n_peers} seeders, the poisoner and {len(self.forced_ports)} poisoners of one piece are up")
        for i in range(int(self.cell.traffic["warm_downloads"])):
            d = self._download_into(f"warm{i}", self.honest, 10 * self.stall_seconds)
            if d["stalled"]:
                raise RuntimeError("a warm download got no verdict for the stall limit")
            self.cell.log(f"warm download {i + 1} done in {d['t_end'] - d['t_start']:.2f} s, {self.launch_count()} launches so far")
        self.downloads.clear()

    # a download -----------------------------------------------------------

    async def _run(self, dest: str, ports: list[int], stall_seconds: float, until_dropped: bool = False) -> dict:
        """``session.py``'s, with the torrent's bytes handed to
        ``Client.add_torrent_bytes`` (the v2 session view)."""
        from torrent_tpu.net.types import AnnouncePeer

        with open(self.torrent_path, "rb") as f:
            data = f.read()
        os.makedirs(dest, exist_ok=True)
        events: list = []
        out = {"dir": dest, "events": events, "stalled": False, "t_start": time.monotonic()}
        torrent = await self.client.add_torrent_bytes(data, dest)
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
                v1._say(f"download into {dest} judged nothing for {stall_seconds:.0f} s: {torrent.status()}")
                out["stalled"] = True
                break
        out["t_end"] = time.monotonic()
        await self.client.remove(torrent.metainfo.info_hash)
        return out

    # window ---------------------------------------------------------------

    def window(self, seconds: float) -> float:
        self.before = self._counters()
        self.t_open = time.monotonic()
        while time.monotonic() - self.t_open < seconds and not (self.downloads and self.downloads[-1]["stalled"]):
            self._download_into(f"timed{len(self.downloads)}", self.honest)
        self.after = self._counters()
        took = [round(d["t_end"] - d["t_start"], 3) for d in self.downloads]
        self.cell.log(f"{len(took)} timed downloads, seconds each: {took}")
        return self.t_open

    # after the window -----------------------------------------------------

    def release(self) -> None:
        self._close_client()

    def _planted_download(self) -> None:
        """Outside the window, on a leecher of its own, into one directory,
        each step a fresh add (a ban is the torrent's): alone with each
        poisoner of one kind in turn until the leecher has dropped it, so
        that every kind of ``FORCED`` is put to the judge; those poisoners
        ended (they lack the rest, and would fetch it from the leecher);
        alone with the poisoner of every piece until it is dropped; then
        with the honest seeders."""
        from benchmark.harness import reference_session_v2

        self.client = self.loop.run_until_complete(self._leecher())
        n = len(self.downloads)
        try:
            forced = [self._download_into("planted", [port], until_dropped=True) for port in self.forced_ports]
            self.seeders.end(self.n_peers + 1)
            one = self._download_into("planted", [self.poisoner], until_dropped=True)
            one["on_disk"] = reference_session_v2.piece_roots(one["dir"], self.torrent)
            two = self._download_into("planted", self.honest)
        finally:
            self._close_client()
        del self.downloads[n:]
        self.planted = {"forced": forced, "one": one, "two": two}

    def check(self, control: bool = False) -> dict:
        from benchmark.harness import reference_session_v2 as ref

        self.torrent = torrent = ref.read_torrent(self.torrent_path)
        held = ref.copy_verdicts(self.src_dir, torrent)
        held_bad = ref.copy_verdicts(self.bad_dir, torrent)
        if control:
            judged = ref.copy_verdicts(self.src_dir, torrent, control=True)
            judged_bad = ref.copy_verdicts(self.bad_dir, torrent, control=True)
        self._planted_download()
        self.seeders.stop()

        def program(events, bad=False):
            """The leecher's events, or the control's verdicts of the same deliveries."""
            if not control:
                return events
            verdicts = judged_bad if bad else judged
            return [(i, "ok" if verdicts[i] else "corrupt") for i, _ in events]

        sums = {"compared": 0, "reference_invalid": 0, "wrong_verdicts": 0, "missing_verdicts": 0}
        single_valid = 0  # deliveries of a file of one piece that the reference calls valid: the control's wrong ones

        def add(numbers: dict, events, copy) -> None:
            nonlocal single_valid
            for key in sums:
                sums[key] += numbers[key]
            single_valid += sum(1 for i, _ in events if i in self.single and copy[i])

        disk_mismatch = 0
        for d in self.downloads:
            add(ref.compare_deliveries(program(d["events"]), held, every="valid"), d["events"], held)
            disk_mismatch += sum(1 for ok in ref.copy_verdicts(d["dir"], torrent) if not ok)
        timed = dict(sums)
        one, two = self.planted["one"], self.planted["two"]
        # the poisoners of one kind: each of their deliveries against the poisoned copy, and every kind refused
        forced_events = [e for d in self.planted["forced"] for e in d["events"]]
        alone = ref.compare_deliveries(program(forced_events, bad=True), held_bad, every="valid")
        alone["missing_verdicts"] = 0  # what they must have judged is counted by kind below
        add(alone, forced_events, held_bad)
        forced_refused = {i for i, o in program(forced_events, bad=True) if o != "ok"}
        first = ref.compare_deliveries(program(one["events"], bad=True), held_bad, every="valid")
        first["missing_verdicts"] = 0 if any(o != "ok" for _, o in one["events"]) else 1  # it met no planted piece
        add(first, one["events"], held_bad)
        second = ref.compare_deliveries(program(two["events"]), held, every="valid")
        accepted = {i for i, o in forced_events + one["events"] if o == "ok"}
        again = {i for i, o in two["events"] if o == "ok"}
        second["missing_verdicts"] = sum(1 for i in range(self.n_pieces) if i not in accepted and i not in again)
        add(second, two["events"], held)
        bad_roots = ref.piece_roots(self.bad_dir, torrent)
        expected = [e for _, _, e in torrent["pieces"]]
        planted_on_disk = sum(1 for i in self.plan if one["on_disk"][i] in (bad_roots[i], expected[i]))
        disk_mismatch += sum(1 for i in accepted if one["on_disk"][i] != expected[i])
        disk_mismatch += sum(1 for ok in ref.copy_verdicts(two["dir"], torrent) if not ok)

        b, a = self.before, self.after
        numbers: dict = {
            "compared": sums["compared"],
            "reference_invalid": sums["reference_invalid"],
            "wrong_verdicts": {"value": sums["wrong_verdicts"], "limit": 0},
            "missing_verdicts": {"value": sums["missing_verdicts"], "limit": 0},
            "disk_mismatch": {"value": disk_mismatch, "limit": 0},
            "planted_on_disk": {"value": planted_on_disk, "limit": 0},
            "forced_kinds_unrefused": {"value": sum(1 for i in self.forced if i not in forced_refused), "limit": 0},
            "stalled_downloads": {"value": sum(d["stalled"] for d in self.downloads + self.planted["forced"] + [one, two]),
                                  "limit": 0},
            "hashlib_fallback_verdicts": {"value": a["fallback"] - b["fallback"], "limit": 0},
            "downloads": len(self.downloads),
            "timed_deliveries": timed["compared"],
            "single_piece_deliveries_valid": single_valid,
            "planted_pieces": len(self.plan),
            "forced_deliveries": len(forced_events),
            "forced_unrefused": [kind for kind, i in zip(FORCED, self.forced) if i not in forced_refused],
            "planted_refused": sorted({i for i, o in one["events"] if o != "ok"}),
            "planted_phase_one_deliveries": len(one["events"]),
            "planted_adopted": two["adopted"],
            "launches": a["launches"] - b["launches"],
            "leaf_launches": sorted([k, st["launches"] - b["leaf_rows"].get(k, {}).get("launches", 0)]
                                    for k, st in a["leaf_rows"].items()),
        }
        # what only a program that counts its v2 ingest can be asked
        if a["v2_ingest"] is not None:
            for key, n in a["v2_ingest"].items():
                numbers["v2_ingest_" + key] = n - b["v2_ingest"][key]
            numbers["pieces_host_hashed"] = {"value": numbers.pop("v2_ingest_pieces_host_hashed"), "limit": 0}
        return numbers

    def counts(self, numbers: dict) -> dict:
        failed = sum(numbers[k]["value"] for k in ("wrong_verdicts", "missing_verdicts", "disk_mismatch", "planted_on_disk"))
        valid = sum(self.pieces[i][2] for d in self.downloads for i in {i for i, o in d["events"] if o == "ok"})
        return {
            "attempted": numbers["compared"], "failed": failed,
            # payload bytes of the pieces judged valid in the timed downloads
            "bytes": valid,
            # to the last verdict counted: the download under way at the close is finished
            "window_s": self.downloads[-1]["t_end"] - self.t_open,
            "classes": None, "failures": [],
        }

    def observations(self) -> dict:
        b, a = self.before, self.after
        return {
            "launches": a["launches"] - b["launches"],
            "leaf_rows": (b["leaf_rows"], a["leaf_rows"]),
            "v2_ingest": None if a["v2_ingest"] is None else (b["v2_ingest"], a["v2_ingest"]),
            "child_cpu_s": a["seeders_cpu"] - b["seeders_cpu"],
            "peers": self.n_peers,
            "downloads": len(self.downloads),
        }


if __name__ == "__main__":
    if len(sys.argv) not in (5, 7) or sys.argv[1] != "seed" or sys.argv[5:6] not in ([], ["--claim"]):
        raise SystemExit("usage: session_v2.py seed <torrent> <dir> <port file> [--claim all|<piece>,...]")
    asyncio.run(_seed(sys.argv[2], sys.argv[3], sys.argv[4], sys.argv[6] if len(sys.argv) == 7 else None))
