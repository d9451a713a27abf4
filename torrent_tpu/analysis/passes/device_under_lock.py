"""``device-under-lock`` — only ``_device_lock`` may guard plane entry.

PR 2's intermittent deadlock was exactly this shape: two worker threads
entering the same compiled executable concurrently wedged the XLA
runtime, and the fix was one designated lock (``_device_lock``) whose
ONLY job is serializing device entry. Holding any *other* lock across a
jit dispatch / kernel launch / collective couples that lock's hold time
to device latency (seconds to minutes of compile)
and recreates the hazard: whoever contends that lock is now blocked on
the device.

Flags any device-entry call (``common.DEVICE_CALL_NAMES``, ``jnp.*`` /
``jax.*`` rooted calls) made — directly or through resolved calls —
while a lock other than ``_device_lock`` is held.
"""

from __future__ import annotations

from torrent_tpu.analysis.findings import Finding
from torrent_tpu.analysis.passes.common import PackageIndex

PASS_NAME = "device-under-lock"

ALLOWED = frozenset({"_device_lock"})


def _bad_held(held) -> list[str]:
    return [h for h in held if h not in ALLOWED]


def run(index: PackageIndex, files=None) -> list[Finding]:
    findings: list[Finding] = []
    for fn in index.functions:
        for site in fn.device:
            for lock in _bad_held(site.held):
                findings.append(
                    Finding(
                        PASS_NAME,
                        fn.module,
                        site.line,
                        fn.qualname,
                        f"device entry {site.token} while holding {lock}",
                    )
                )
        for site in fn.calls:
            bad = _bad_held(site.held)
            if not bad:
                continue
            callee = index.resolve(fn, site)
            if callee is None or not index.transitive_device(callee):
                continue
            for lock in bad:
                findings.append(
                    Finding(
                        PASS_NAME,
                        fn.module,
                        site.line,
                        fn.qualname,
                        f"call to {callee.qualname} enters the device "
                        f"while holding {lock}",
                    )
                )
    return findings
