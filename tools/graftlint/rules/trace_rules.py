"""Rule family 2 — trace purity.

A host sync (`.item()`, `block_until_ready`, `device_get`) or Python
side effect (wall-clock reads, prints, env reads, IO, mutation of
module/closure state) inside a traced body either crashes at trace
time, silently bakes one request's value into every later execution of
the compiled program, or forces a device->host round trip in the middle
of the device program — the tail-latency cliffs the paper's read path
exists to avoid. The ONLY sanctioned device->host bridge is
`io_callback` (the `_step_poll` deadline poll in ops/scoring's stepped
tile loop is the exemplar), which core.py's traced-context computation
already exempts as a host half.

Traced contexts come from `Package.traced()`: jit-decorated functions,
bodies handed to lax control flow / pallas_call / shard_map, and their
package-resolvable callees, to a fixpoint.
"""

from __future__ import annotations

import ast

from ..core import Finding, Package, call_name, calls_in

RULE = "trace-purity"

# unambiguous host syncs / side effects: flagged anywhere inside a
# traced body. (Plain float()/int() on statics is legitimate trace-time
# Python, so casts are NOT in this list — `.item()` is the sync spelling
# this codebase would use on a traced value.)
_FORBIDDEN_TAILS = {
    "item": "host sync `.item()`",
    "block_until_ready": "host sync `block_until_ready`",
    "device_get": "host transfer `jax.device_get`",
    "copy_to_host_async": "host transfer `copy_to_host_async`",
    "tolist": "host sync `.tolist()`",
    "print": "side effect `print(...)`",
    "sleep": "side effect `time.sleep`",
    # utils/profiler.py: a traced body runs once, while tracing, so a
    # phase entered there would time the trace and nothing after it
    # (the executor's `_phase` / `_launch` are the same mechanism)
    "phase": "host timer `phase(...)`",
    "_phase": "host timer `phase(...)`",
    "_launch": "host timer `phase(...)`",
    "waited": "host timer `waited(...)`",
}
_FORBIDDEN_DOTTED = {
    "time.time": "wall-clock read `time.time()`",
    "time.monotonic": "wall-clock read `time.monotonic()`",
    "time.perf_counter": "wall-clock read `time.perf_counter()`",
    "_time.perf_counter": "wall-clock read `perf_counter()`",
    "np.asarray": "host materialization `np.asarray(...)`",
    "np.array": "host materialization `np.array(...)`",
    "numpy.asarray": "host materialization `np.asarray(...)`",
    "np.ascontiguousarray": "host materialization",
    "os.environ.get": "env read `os.environ`",
    "os.getenv": "env read `os.getenv`",
    "open": "file IO `open(...)`",
}
# mutating method calls on names from an enclosing scope
_MUTATORS = {"append", "update", "setdefault", "extend", "add", "pop",
             "clear", "remove"}


def _local_stores(func: ast.FunctionDef) -> set[str]:
    names = {a.arg for a in func.args.args + func.args.kwonlyargs
             + func.args.posonlyargs}
    if func.args.vararg:
        names.add(func.args.vararg.arg)
    if func.args.kwarg:
        names.add(func.args.kwarg.arg)
    for n in ast.walk(func):
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
            names.add(n.id)
        elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and n is not func:
            names.add(n.name)
        elif isinstance(n, ast.comprehension):
            for t in ast.walk(n.target):
                if isinstance(t, ast.Name):
                    names.add(t.id)
    return names


def _walk_own(func: ast.FunctionDef):
    stack = list(ast.iter_child_nodes(func))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


def check(pkg: Package) -> list[Finding]:
    findings: list[Finding] = []
    traced = pkg.traced()
    for fi, why in traced.values():
        m = fi.module
        locals_ = _local_stores(fi.node)
        # closure variables of an enclosing function are TRACE-LOCAL
        # (fresh per trace) — mutating a parent's memo dict or a pallas
        # out_ref closure is not persisted host state; only module-level
        # names are
        p = fi.parent
        while p is not None:
            locals_ |= _local_stores(p.node)
            p = p.parent
        for call in calls_in(fi.node, skip_nested=True):
            name = call_name(call)
            tail = name.split(".")[-1] if name else ""
            msg = _FORBIDDEN_DOTTED.get(name) or (
                _FORBIDDEN_TAILS.get(tail)
                if tail in _FORBIDDEN_TAILS else None)
            if tail == "print" and name != "print":
                msg = None          # obj.print() is not the builtin
            if msg:
                findings.append(Finding(
                    RULE, m.relpath, call.lineno, call.col_offset,
                    f"{msg} inside traced code ({why}) — route through "
                    f"io_callback or move to bind time"))
                continue
            # closure/global mutation via method call
            if tail in _MUTATORS and isinstance(call.func, ast.Attribute) \
                    and isinstance(call.func.value, ast.Name) \
                    and call.func.value.id not in locals_:
                findings.append(Finding(
                    RULE, m.relpath, call.lineno, call.col_offset,
                    f"mutation `{call.func.value.id}.{tail}(...)` of "
                    f"enclosing-scope state inside traced code ({why}) — "
                    f"trace-time mutation escapes the trace cache"))
        # closure/global mutation via subscript store: CACHE[k] = v
        # (nested defs are traced — and checked — in their own right)
        for n in _walk_own(fi.node):
            if isinstance(n, (ast.Assign, ast.AugAssign)):
                targets = n.targets if isinstance(n, ast.Assign) \
                    else [n.target]
                for t in targets:
                    if isinstance(t, ast.Subscript) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id not in locals_:
                        findings.append(Finding(
                            RULE, m.relpath, n.lineno, n.col_offset,
                            f"subscript store into enclosing-scope "
                            f"`{t.value.id}[...]` inside traced code "
                            f"({why})"))
    return findings
