"""wire-taint: untrusted swarm input must be validated before it sizes,
indexes, seeks, paths, loops, or charges anything.

Sources are the decode boundaries where attacker bytes become Python
values: bencode decoding, peer-wire message parsing, handshake reads,
raw datagram handlers. Sinks are the places a remote-supplied number or
name becomes dangerous: allocation sizes, staging-slab geometry, IO
offsets+lengths, file-path construction, loop bounds, DRR charge
amounts. A flow from source to sink must pass a registered validation
**barrier** (:data:`BARRIERS`) — piece-geometry checks, server-side
clamps, the structural ``if x > CAP: raise`` idiom — or carry a
``# sanitized-by: <barrier>`` annotation on the sink line naming the
out-of-band check that covers it. Annotations naming a barrier that is
not registered are themselves findings (a typo'd suppression must not
silently disable the gate).

Each finding carries the full machine-traced flow (source →
propagation → sink); the lint CLI emits it as SARIF ``codeFlows`` so a
finding reads as an attack path, not a line number.
"""

from __future__ import annotations

import re

from torrent_tpu.analysis.findings import Finding
from torrent_tpu.analysis.passes.dataflow import Registries, TaintAnalysis

PASS_NAME = "wire-taint"

# ---------------------------------------------------------------- model

# calls whose RETURN VALUE is attacker-controlled wire data
SOURCE_CALLS: dict[str, str] = {
    "bdecode": "bencode decode",
    "bdecode_prefix": "bencode decode",
    "bdecode_with_info_span": "bencode decode",
    "decode_message": "peer-wire message decode",
    "read_message": "peer-wire message read",
    "read_handshake_head": "peer handshake",
    "read_handshake_peer_id": "peer handshake",
}

# functions whose PARAMETERS arrive straight off the wire
SOURCE_PARAMS: dict[str, frozenset[str]] = {
    "DHTNode._on_datagram": frozenset({"data"}),
    "LSDResponder._on_datagram": frozenset({"data"}),
    "_on_datagram": frozenset({"data"}),
}

# registered validation barriers: calling one of these sanitizes its
# arguments (guard barriers) / returns a clean value (value barriers).
# ``# sanitized-by:`` annotations must name an entry here.
BARRIERS: frozenset[str] = frozenset(
    {
        "validate_requested_block",
        "validate_received_block",
        "clamp_numwant",
        "clamp_digest",
        "check",          # codec/valid.py combinator verdicts
        "parse_info",     # metainfo validation funnels
        "parse_v2_info_dict",
        "hex",            # hex-encode: output alphabet is [0-9a-f] —
                          # cannot traverse paths, cannot act as a size
        "min",            # the clamp builtin (value barrier)
        # annotation-only vocabulary (hyphenated names never match a
        # call; they exist for # sanitized-by on sites the engine can't
        # judge structurally):
        "len-guard",      # structural: if len(x) > CAP / if x > CAP: raise
        "bounded-copy",   # bytearray/bytes copy of an already-received
                          # buffer — allocation bounded by that buffer
    }
)

# sink calls by bare/tail name: name -> (kind, positional arg idxs|None=all)
SINK_CALLS: dict[str, tuple[str, tuple[int, ...] | None]] = {
    "bytearray": ("allocation size", (0,)),
    "range": ("loop bound", None),
    "read_batch": ("batched IO geometry", None),
    "preadv": ("vectored read offset/length", None),
    "pread": ("read offset/length", None),
    "read_into": ("read offset/length", None),
    "readexactly": ("read length", (0,)),
    "checkout_staging": ("staging slab geometry", (0, 1)),
    # slab, rows: its tenant, expected, wait and flush are no geometry
    "enqueue_staged": ("staged submit geometry", (1, 2)),
    "seek": ("file offset", (0,)),
    "joinpath": ("file-path construction", None),
    "truncate": ("file size", (0,)),
    "charge": ("DRR charge amount", (1,)),
}

# sink calls by dotted name (module-qualified callables)
SINK_DOTTED: dict[str, tuple[str, tuple[int, ...] | None]] = {
    "os.path.join": ("file-path construction", None),
    "os.pread": ("read offset/length", (1, 2)),
    "os.preadv": ("vectored read offset", None),
}

_SANITIZED_RE = re.compile(r"#\s*sanitized-by:\s*([A-Za-z_][\w.-]*)")


def registries() -> Registries:
    return Registries(
        source_calls=dict(SOURCE_CALLS),
        source_params=dict(SOURCE_PARAMS),
        barrier_calls=frozenset(b for b in BARRIERS if b.isidentifier()),
        sink_calls=dict(SINK_CALLS),
        sink_dotted=dict(SINK_DOTTED),
    )


def annotations_by_line(source: str) -> dict[int, str]:
    """``# sanitized-by: <barrier>`` annotations, keyed by 1-based line."""
    out: dict[int, str] = {}
    for i, text in enumerate(source.splitlines(), start=1):
        m = _SANITIZED_RE.search(text)
        if m:
            out[i] = m.group(1)
    return out


def run(index, files) -> list[Finding]:
    analysis = TaintAnalysis(index, registries())
    ann: dict[str, dict[int, str]] = {
        mf.path: annotations_by_line(mf.source) for mf in files
    }

    findings: list[Finding] = []
    consumed: set[tuple[str, int]] = set()
    seen: set[tuple[str, str, str]] = set()
    for hit in analysis.hits:
        barrier = ann.get(hit.module, {}).get(hit.line)
        if barrier is not None:
            consumed.add((hit.module, hit.line))
            if barrier in BARRIERS:
                continue  # deliberate, named, registered — suppressed
            findings.append(
                Finding(
                    PASS_NAME,
                    hit.module,
                    hit.line,
                    hit.sink_note,
                    f"sanitized-by names unregistered barrier "
                    f"{barrier!r} (not in BARRIERS) — suppression is "
                    f"inert; register the barrier or fix the flow",
                )
            )
            continue
        source_note = hit.trace.steps[0].note if hit.trace.steps else "wire input"
        key = (hit.module, hit.sink_note, source_note)
        if key in seen:
            continue  # one finding per (module, sink, source) family
        seen.add(key)
        findings.append(
            Finding(
                PASS_NAME,
                hit.module,
                hit.line,
                hit.sink_note,
                f"{source_note} reaches {hit.kind} sink {hit.sink_note} "
                f"without a registered validation barrier "
                f"(# sanitized-by: <barrier> for deliberate exceptions)",
                flow=tuple(s.as_tuple() for s in hit.trace.steps),
            )
        )

    # a sanitized-by annotation nothing consumed is stale or misplaced —
    # it suggests a validated flow that the engine does not even see
    for path, lines in ann.items():
        for line, barrier in lines.items():
            if (path, line) in consumed:
                continue
            if barrier not in BARRIERS:
                findings.append(
                    Finding(
                        PASS_NAME,
                        path,
                        line,
                        "annotation",
                        f"sanitized-by names unregistered barrier "
                        f"{barrier!r} (not in BARRIERS)",
                    )
                )
    return findings
