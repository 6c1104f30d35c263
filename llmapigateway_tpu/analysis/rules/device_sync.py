"""device-sync-discipline: implicit device→host syncs on the event loop.

``async-blocking`` catches the classic blocking primitives, but the
device-sync family has quieter spellings this codebase actually uses:
``.block_until_ready()`` on an array, ``np.asarray(...)`` / ``np.array(...)``
of a JAX value (a synchronous device fetch), and ``float()``/``int()`` of a
device array. Any of these reachable from a serving-layer ``async def``
stalls every in-flight SSE stream for as long as the device takes to
answer.

Some helpers sync *by design* (e.g. the engine's worker-thread fetch
paths reached via documented loop-side accessors that only touch host
mirrors). Those opt out with a ``# device-sync: ok`` marker on their
``def`` line (or within the signature) — the marker is the
documentation: it says a human has checked the receiver is host data or
the sync is intentional. The whole-program pass (analysis/program.py)
extends this rule transitively through sync helpers in ANY module using
the PR 5 call graph; functions dispatched to worker threads
(``asyncio.to_thread`` / ``run_in_executor`` / ``Thread(target=)``)
create no call edge, so worker-side fetch code is never flagged.
"""
from __future__ import annotations

import ast

from ..core import Finding, Rule
from ._util import call_name, references_module

_JAX_ROOTS = frozenset({"jax", "jnp"})
_NP_ROOTS = ("np", "numpy")

DEVICE_SYNC_OK_MARK = "device-sync: ok"


def classify_device_sync(node: ast.Call) -> str | None:
    """The message describing why this Call is (or may be) a device→host
    sync, or None. Shared with the whole-program pass so the lexical and
    transitive layers can never disagree."""
    name = call_name(node)
    if name == "jax.block_until_ready":
        return ("jax.block_until_ready() waits for the device on the "
                "event loop")
    if name == "jax.device_get":
        return "jax.device_get() is a synchronous device->host fetch"
    func = node.func
    if (isinstance(func, ast.Attribute)
            and func.attr == "block_until_ready"
            and not node.args and not node.keywords):
        return (".block_until_ready() waits for the device on the event "
                "loop")
    if (isinstance(func, ast.Attribute) and func.attr == "item"
            and not node.args and not node.keywords):
        return ".item() forces a device->host sync on the event loop"
    if (name is not None and "." in name
            and name.split(".")[0] in _NP_ROOTS
            and name.split(".")[-1] in ("asarray", "array")
            and node.args and references_module(node.args[0], _JAX_ROOTS)):
        return (f"{name}() of a JAX value is a synchronous device->host "
                f"fetch")
    if (isinstance(func, ast.Name) and func.id in ("float", "int")
            and node.args
            and references_module(node.args[0], _JAX_ROOTS)):
        return (f"{func.id}() of a JAX value is a synchronous "
                f"device->host fetch")
    return None


def sync_ok_marked(fn_node: ast.AST, lines: list[str]) -> bool:
    """True when the function carries the ``# device-sync: ok`` marker as
    a TRAILING comment on its ``def`` line or a later signature line
    (multi-line signatures work). Standalone comment lines are ignored —
    a comment *about* the marker between signature and body must not
    arm it."""
    body = getattr(fn_node, "body", None)
    last = max(fn_node.lineno, (body[0].lineno - 1) if body
               else fn_node.lineno)
    for ln in range(fn_node.lineno, last + 1):
        if ln > len(lines):
            break
        line = lines[ln - 1]
        if line.lstrip().startswith("#"):
            continue
        if DEVICE_SYNC_OK_MARK in line:
            return True
    return False


class DeviceSyncRule(Rule):
    name = "device-sync-discipline"
    description = ("implicit device->host syncs (.block_until_ready(), "
                   "np.asarray/float of JAX values) inside serving-layer "
                   "async defs; the whole-program pass extends this "
                   "transitively through sync helpers in any module — "
                   "documented helpers opt out with `# device-sync: ok` "
                   "on the def line")
    dirs = ("server", "routing", "providers")

    def check(self, tree: ast.Module, source: str,
              relpath: str) -> list[Finding]:
        lines = source.splitlines()
        findings: list[Finding] = []
        for node in ast.walk(tree):
            if isinstance(node, ast.AsyncFunctionDef):
                if sync_ok_marked(node, lines):
                    continue
                self._check_async_body(node, relpath, findings)
        return findings

    def _check_async_body(self, fn: ast.AsyncFunctionDef, relpath: str,
                          findings: list[Finding]) -> None:
        # Like async-blocking: skip nested SYNC defs (worker payloads);
        # nested async defs are still on the loop.
        stack: list[ast.AST] = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            if isinstance(node, ast.FunctionDef):
                continue
            stack.extend(ast.iter_child_nodes(node))
            if isinstance(node, ast.Call):
                msg = classify_device_sync(node)
                if msg is not None:
                    findings.append(self.finding(
                        relpath, node,
                        f"{msg} — offload via asyncio.to_thread, or mark "
                        f"the helper `# device-sync: ok` if the receiver "
                        f"is host data"))


RULE = DeviceSyncRule()
