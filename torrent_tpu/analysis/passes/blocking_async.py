"""``blocking-in-async`` — no synchronous stalls on the event loop.

The PR 3 / PR 4 hazard class: a coroutine that calls ``time.sleep``,
does sync file or socket IO, blocks on a ``Future.result()``, probes
``jax.devices()`` (backend init: seconds on a TPU, longer on a busy one)
or dispatches jitted work stalls the WHOLE serving loop — every
concurrent connection, heartbeat, and deadline timer stops with it.

Scope: ``async def`` bodies in the packages that run event loops —
``bridge/``, ``session/``, ``fabric/``, ``net/``. Nested synchronous
``def``s inside a coroutine are exempt: that is exactly the
``asyncio.to_thread(worker)`` idiom the rule wants work moved into.
"""

from __future__ import annotations

import ast

from torrent_tpu.analysis.findings import Finding
from torrent_tpu.analysis.passes.common import (
    PackageIndex,
    dotted_name,
    tail_name,
)

PASS_NAME = "blocking-in-async"

SCOPE_DIRS = frozenset({"bridge", "session", "fabric", "net"})

# full dotted names that block
BLOCKING_DOTTED = frozenset(
    {
        "time.sleep",
        "jax.devices",
        "socket.socket",
        "socket.create_connection",
        "socket.getaddrinfo",
        "socket.gethostbyname",
        "subprocess.run",
        "subprocess.check_output",
        "subprocess.check_call",
        "subprocess.call",
        "os.system",
    }
)
# attribute tails that block regardless of receiver. ".result" is
# flagged only on zero-argument calls (the Future.result() shape) —
# domain methods named result(args...) are not futures.
BLOCKING_TAILS = frozenset({"block_until_ready"})
# builtins that block
BLOCKING_BUILTINS = frozenset({"open", "input"})
# jit dispatch: any call rooted at jnp enqueues device work synchronously
BLOCKING_ROOTS = ("jnp",)


def _in_scope(path: str) -> bool:
    parts = path.split("/")
    # repo-relative: torrent_tpu/<dir>/... (fixtures: <pkg>/<dir>/...)
    return len(parts) >= 3 and parts[1] in SCOPE_DIRS


def _blocking_token(call: ast.Call) -> str | None:
    dn = dotted_name(call.func)
    if dn:
        if dn in BLOCKING_DOTTED:
            return dn
        if dn.split(".", 1)[0] in BLOCKING_ROOTS:
            return dn
    if isinstance(call.func, ast.Name) and call.func.id in BLOCKING_BUILTINS:
        return call.func.id
    tail = tail_name(call.func)
    if tail in BLOCKING_TAILS:
        return f".{tail}()"
    if tail == "result" and not call.args and not call.keywords:
        return ".result()"
    return None


class _CoroWalker(ast.NodeVisitor):
    """Visits one coroutine body, not descending into nested defs."""

    def __init__(self):
        self.hits: list[tuple[str, int]] = []

    def visit_FunctionDef(self, node):  # nested sync def: to_thread idiom
        pass

    def visit_AsyncFunctionDef(self, node):  # nested coroutine: own entry
        pass

    def visit_Lambda(self, node):
        pass

    def visit_Call(self, node):
        token = _blocking_token(node)
        if token:
            self.hits.append((token, node.lineno))
        self.generic_visit(node)


def run(index: PackageIndex, files=None) -> list[Finding]:
    findings: list[Finding] = []
    for fn in index.functions:
        if not fn.is_async or not _in_scope(fn.module):
            continue
        w = _CoroWalker()
        for stmt in fn.node.body:
            w.visit(stmt)
        for token, line in w.hits:
            findings.append(
                Finding(
                    PASS_NAME,
                    fn.module,
                    line,
                    fn.qualname,
                    f"blocking call {token} in coroutine",
                )
            )
    return findings
