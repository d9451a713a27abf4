"""The load generator: one child process that never imports JAX.

It reads one JSON spec on its command line, builds its bodies from the
seed, and then obeys one-line commands on stdin (``warm``, ``go``,
``report``), answering each with one JSON line on stdout. Traffic is data:
``mode`` and the numbers of the traffic file decide everything.

* ``open`` — an open loop: requests are due at fixed instants drawn from
  the seed (the same set of gaps for every seed, in another order), each
  one ``POST /v1/verify`` with one piece. Latency runs from the instant a
  request was *due* to its verdict.
* ``closed`` — ``clients`` callers, each posting ``/v1/stream/verify``
  bodies of ``frames`` frames back to back.

What counts as failed (see ``classify``): no verdict, or a wrong one. At
the close of the window no new request starts; every request already sent
is awaited to its verdict — nothing is cancelled — and the server is
closed only after this process has said ``closed``.
"""

from __future__ import annotations

import asyncio
import gc
import hashlib
import json
import math
import os
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

from benchmark.harness import bencode, payload, reference

TRANSPORT, REFUSED, WRONG = "transport", "refused", "wrong"


# ---------------------------------------------------------------- classes


def classify(status: int | None, error: str | None, verdicts, expected, frame_failed: int = 0):
    """Per-piece classes of one finished request: a list as long as
    ``expected`` holding ``None`` (a right verdict, however late) or one of
    ``transport`` / ``refused`` / ``wrong``.

    transport: no reply, a connection error, a time limit, a reply that
    cannot be read. refused: an HTTP status other than 200 (429, 500,
    503 ...), or frames the stream route reported as ``failed``. wrong: a
    verdict that differs from the reference, or that a 200 left out."""
    n = len(expected)
    if error is not None or status is None:
        return [TRANSPORT] * n
    if status != 200:
        return [REFUSED] * n
    if verdicts is None:
        return [TRANSPORT] * n
    out = []
    for i in range(n):
        if i >= len(verdicts):
            out.append(WRONG)
        elif bool(verdicts[i]) != bool(expected[i]):
            out.append(WRONG)
        else:
            out.append(None)
    # frames the stream route says it could not hash come back as ok=0 and
    # only as a count: charge it to ok=0 frames, those that mismatch first
    zeros = sorted((i for i in range(min(n, len(verdicts))) if not verdicts[i]), key=lambda i: out[i] is None)
    for i in zeros[:frame_failed]:
        out[i] = REFUSED
    return out


# ------------------------------------------------------------------- http


async def post(host: str, port: int, path: str, headers: dict, body, limit_s: float):
    """One request on a connection of its own (the bridge answers
    ``Connection: close``). Returns ``(status, body, error)``; never
    raises for what the network or the server does."""
    writer = None
    try:
        async with asyncio.timeout(limit_s):
            reader, writer = await asyncio.open_connection(host, port)
            head = f"POST {path} HTTP/1.1\r\nHost: {host}\r\nContent-Length: {len(body)}\r\n"
            head += "".join(f"{k}: {v}\r\n" for k, v in headers.items()) + "\r\n"
            writer.write(head.encode("latin-1"))
            writer.write(body)
            await writer.drain()
            raw = await reader.read(-1)
        head_end = raw.find(b"\r\n\r\n")
        if head_end < 0 or not raw.startswith(b"HTTP/1."):
            return None, b"", f"unreadable reply of {len(raw)} bytes"
        return int(raw[9:12]), raw[head_end + 4 :], None
    except TimeoutError:
        return None, b"", f"time limit of {limit_s} s"
    except (OSError, asyncio.IncompleteReadError, ValueError) as e:
        return None, b"", f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


def parse_verify(status, body, error):
    """``(verdicts, frame_failed, error)`` from a 200 of either route."""
    if error is not None or status != 200:
        return None, 0, error
    try:
        reply = bencode.decode(body)
        return list(reply[b"ok"]), int(reply.get(b"failed", 0)), None
    except (ValueError, KeyError, TypeError) as e:
        return None, 0, f"unreadable verdicts: {e}"


# --------------------------------------------------------------- schedule


def open_schedule(seed: int, rate: float, seconds: float):
    """Due instants of an open loop. The gaps are the quantiles of the
    exponential distribution at ``rate`` — the same multiset for every
    seed, so every seed offers the same number of requests over the same
    time — shuffled by the seed."""
    n = max(1, round(rate * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / gaps.sum()
    rng = np.random.Generator(np.random.Philox([seed, 0xA1]))
    rng.shuffle(gaps)
    return np.cumsum(gaps) - gaps[0]


def spread(seed: int, salt: int, n: int, k: int):
    """``n`` draws from ``range(k)``, as even as ``n`` allows, shuffled."""
    picks = np.arange(n) % k
    np.random.Generator(np.random.Philox([seed, salt])).shuffle(picks)
    return picks


# ------------------------------------------------------------------ modes


class Generator:
    def __init__(self, spec: dict):
        self.spec = spec
        self.seed = int(spec["seed"])
        self.plen = int(spec["piece_length"])
        self.host = "127.0.0.1"
        self.port = 0
        self.limit_s = float(spec["request_limit_s"])
        self.drain_s = float(spec["drain_limit_s"])
        self.records: list[dict] = []
        self.bodies: list[bytes] = []
        # per body: the pieces as sent (for the reference) and their digests
        self.sent: list[list[tuple[bytes, bytes]]] = []
        self.build()

    # bodies -------------------------------------------------------------

    def build(self) -> None:
        spec, plen = self.spec, self.plen
        base = payload.base_block(self.seed, plen)
        if spec["mode"] == "open":
            # one body per pool piece and state: clean, corrupted
            pool = int(spec["pool_pieces"])
            for i in range(pool):
                row = payload.piece(base, i)
                digest = hashlib.sha1(row).digest()
                bad = row.copy()
                payload.flip(bad, 8 + (self.seed + 7919 * i) % (plen - 8))
                for data in (row.tobytes(), bad.tobytes()):
                    self.bodies.append(bencode.encode({"pieces": [data], "expected": [digest]}))
                    self.sent.append([(data, digest)])
        else:
            frames, clients = int(spec["frames"]), int(spec["clients"])
            for c in range(clients):
                corrupt = payload.corruption_plan(
                    self.seed + c, frames, float(spec["corrupt_share"]), plen
                )
                parts, sent = [], []
                for f in range(frames):
                    row = payload.piece(base, c * frames + f)
                    digest = hashlib.sha1(row).digest()
                    if f in corrupt:
                        payload.flip(row, corrupt[f])
                    data = row.tobytes()
                    parts += [plen.to_bytes(4, "big"), data, digest]
                    sent.append((data, digest))
                self.bodies.append(b"".join(parts))
                self.sent.append(sent)

    def reference(self) -> list[list[bool]]:
        """hashlib over every body as it was sent: the plain reference."""
        return [[reference.piece_verdict(d, exp) for d, exp in sent] for sent in self.sent]

    # one request --------------------------------------------------------

    async def one(self, body_idx: int, path: str, headers: dict, due: float, t0: float):
        sent_at = time.monotonic()
        status, body, error = await post(self.host, self.port, path, headers, self.bodies[body_idx], self.limit_s)
        done = time.monotonic()
        verdicts, frame_failed, error = parse_verify(status, body, error)
        self.records.append(
            {
                "body": body_idx,
                "due": due - t0,
                "late": sent_at - due,
                "done": done - t0,
                "status": status,
                "error": error,
                "detail": body[:120].decode("latin-1") if status not in (None, 200) else None,
                "verdicts": verdicts,
                "frame_failed": frame_failed,
            }
        )

    # windows ------------------------------------------------------------

    async def warm(self) -> int:
        """Send what the window will send, a little of it: every body
        shape once, and (open loop) a burst, so the first launches and the
        first full batches are not the window's."""
        spec, t0 = self.spec, time.monotonic()
        n0 = len(self.records)
        if spec["mode"] == "open":
            for k in (1, int(spec["warm_burst"]), int(spec["warm_burst"])):
                await asyncio.gather(
                    *(
                        self.one(i % len(self.bodies), "/v1/verify", {"X-Tenant": "warm"}, time.monotonic(), t0)
                        for i in range(k)
                    )
                )
        else:
            hdr = {"X-Piece-Length": self.plen, "X-Tenant": "warm"}
            for _ in range(int(spec["warm_rounds"])):
                await asyncio.gather(
                    *(
                        self.one(c, "/v1/stream/verify", hdr, time.monotonic(), t0)
                        for c in range(len(self.bodies))
                    )
                )
        warm, self.records = self.records[n0:], self.records[:n0]
        ref = self.reference()
        bad = 0
        for r in warm:
            classes = classify(r["status"], r["error"], r["verdicts"], ref[r["body"]], r["frame_failed"])
            bad += sum(c is not None for c in classes)
        return bad

    async def window_open(self, seconds: float) -> dict:
        spec = self.spec
        due = open_schedule(self.seed, float(spec["rate_per_s"]), seconds)
        n = len(due)
        tenants = spread(self.seed, 0xB2, n, int(spec["tenants"]))
        pool = spread(self.seed, 0xB3, n, int(spec["pool_pieces"]))
        # the same number of corrupted requests whatever the seed
        k_bad = max(1, round(float(spec["corrupt_share"]) * n))
        bad = np.zeros(n, dtype=bool)
        bad[np.random.Generator(np.random.Philox([self.seed, 0xB4])).choice(n, k_bad, replace=False)] = True
        tasks = []
        t0 = time.monotonic()
        for i in range(n):
            wait = t0 + due[i] - time.monotonic()
            # always yield, so requests already started make progress even
            # when this loop runs behind its schedule
            await asyncio.sleep(max(wait, 0.0))
            body = 2 * int(pool[i]) + int(bad[i])
            hdr = {"X-Tenant": f"tenant{tenants[i]}"}
            tasks.append(asyncio.create_task(self.one(body, "/v1/verify", hdr, t0 + due[i], t0)))
        t_closed = time.monotonic()
        drained = await self.drain(tasks)
        return {"t_open": t0, "t_closed_to_new": t_closed, "offered": n, "undrained": drained}

    async def window_closed(self, seconds: float) -> dict:
        hdr = {"X-Piece-Length": self.plen}
        t0 = time.monotonic()

        async def client(c: int):
            h = dict(hdr, **{"X-Tenant": f"client{c}"})
            while time.monotonic() - t0 < seconds:
                now = time.monotonic()
                await self.one(c, "/v1/stream/verify", h, now, t0)

        tasks = [asyncio.create_task(client(c)) for c in range(len(self.bodies))]
        drained = await self.drain(tasks, seconds)
        return {"t_open": t0, "t_closed_to_new": t0 + seconds, "offered": len(self.records), "undrained": drained}

    async def drain(self, tasks, still_sending_s: float = 0.0) -> int:
        """Await everything in flight; only past the drain limit is a
        request given up (its pieces then count as ``transport``)."""
        if not tasks:
            return 0
        _, pending = await asyncio.wait(tasks, timeout=still_sending_s + self.limit_s + self.drain_s)
        for t in pending:
            t.cancel()
        return len(pending)

    # report -------------------------------------------------------------

    def report(self, window: dict, offered_bodies, control=False) -> dict:
        """Classes, latencies and counts of the window; ``offered_bodies``
        are the requests that never finished (drain limit). With
        ``control`` the control's answers stand in the program's place."""
        ref = self.reference()
        if control:
            for r in self.records:
                r["verdicts"] = [int(v) for v in reference.control_verdicts(len(ref[r["body"]]))]
        per_class = {TRANSPORT: 0, REFUSED: 0, WRONG: 0}
        failures, lat_ms, lat_due_s, late_ms, last = [], [], [], [], 0.0
        pieces = answered = 0
        ref_invalid = 0
        for r in self.records:
            expected = ref[r["body"]]
            classes = classify(r["status"], r["error"], r["verdicts"], expected, r["frame_failed"])
            pieces += len(expected)
            ref_invalid += sum(1 for e in expected if not e)
            late_ms.append(r["late"] * 1e3)
            n_ok = sum(c is None for c in classes)
            answered += n_ok
            if r["verdicts"] is not None:
                # every request that got verdicts, right or wrong, however late
                lat_ms.append((r["done"] - r["due"]) * 1e3)
                lat_due_s.append(r["due"])
                last = max(last, r["done"])
            for i, c in enumerate(classes):
                if c is not None:
                    per_class[c] += 1
                    if len(failures) < 50:
                        failures.append(
                            {"class": c, "status": r["status"], "error": r["error"] or r["detail"], "piece": i,
                             "body": r["body"], "due_s": round(r["due"], 6), "done_s": round(r["done"], 6)}
                        )
        for n_lost in offered_bodies:
            pieces += n_lost
            per_class[TRANSPORT] += n_lost
            failures.append({"class": TRANSPORT, "status": None, "error": "not finished at the drain limit", "pieces": n_lost})
        # a backlog that grows shows as a second half slower than the first
        span = max((r["due"] for r in self.records), default=0.0)
        halves = [[], []]
        for r in self.records:
            if r["verdicts"] is not None:
                halves[int(r["due"] > span / 2)].append((r["done"] - r["due"]) * 1e3)
        return {
            "p50_by_half_ms": [percentile(h, 50) if h else None for h in halves],
            "drain_s": window["t_open"] + last - window["t_closed_to_new"],
            "attempted": pieces,
            "failed": sum(per_class.values()),
            "classes": per_class,
            "failures": failures,
            "requests": len(self.records),
            "answered_pieces": answered,
            "reference_invalid": ref_invalid,
            "t_open": window["t_open"],
            "t_last_verdict": window["t_open"] + last,
            "latency_ms": lat_ms,
            "latency_due_s": lat_due_s,
            "late_ms": late_ms,
            "piece_length": self.plen,
        }


def latencies_due_before(report: dict, until_s: float) -> list[float]:
    """Due-to-verdict of the requests that were due in the first
    ``until_s`` seconds of the window. A traced run reads its tails from
    these: once the traced slice closes, the profiler collects its events
    in the server's process for a minute or two and every later request
    waits behind it."""
    return [ms for ms, due in zip(report["latency_ms"], report["latency_due_s"]) if due < until_s]


def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation between order statistics."""
    v = sorted(values)
    if not v:
        raise ValueError("no samples")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


async def main(spec: dict) -> None:
    loop = asyncio.get_running_loop()
    gen = Generator(spec)
    reader = asyncio.StreamReader()
    await loop.connect_read_pipe(lambda: asyncio.StreamReaderProtocol(reader), sys.stdin)

    def say(obj: dict) -> None:
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    say({"event": "ready", "bodies": len(gen.bodies), "body_bytes": sum(map(len, gen.bodies))})
    window = None
    while True:
        line = await reader.readline()
        if not line:
            return
        cmd = json.loads(line)
        if cmd["cmd"] == "warm":
            gen.port = int(cmd["port"])
            say({"event": "warmed", "failed": await gen.warm()})
        elif cmd["cmd"] == "go":
            gc.collect()
            gc.freeze()
            cpu0 = time.process_time()
            if spec["mode"] == "open":
                window = await gen.window_open(float(cmd["seconds"]))
            else:
                window = await gen.window_closed(float(cmd["seconds"]))
            window["cpu_s"] = time.process_time() - cpu0
            say({"event": "closed", **window})
        elif cmd["cmd"] == "report":
            lost = [len(gen.sent[0])] * int(window["undrained"])
            out = gen.report(window, lost, bool(cmd.get("control")))
            out["cpu_s"] = window["cpu_s"]
            with open(cmd["path"], "w") as f:
                json.dump(out, f)
            say({"event": "reported", "path": cmd["path"]})
        elif cmd["cmd"] == "exit":
            return


if __name__ == "__main__":
    asyncio.run(main(json.loads(sys.argv[1])))
