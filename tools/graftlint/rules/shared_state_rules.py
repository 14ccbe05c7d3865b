"""Rule family 6 — shared-state races (Eraser-style lockset pass).

Lock DISCIPLINE (family 5) checks what you do while holding a lock;
this family checks lock SUFFICIENCY: every piece of state reachable
from more than one thread must have a non-empty COMMON lockset across
all of its access sites — the classic Eraser algorithm, run statically
over the hot-path modules the dispatch/traffic/resident/repack/tiering
stack made deeply concurrent.

What counts as shared:

  * instance attributes of a SHARED CLASS — a class that owns a lock
    attribute (it declared itself concurrent), has a method discovered
    as a thread entry (``threading.Thread(target=...)``, pool
    ``submit``/``execute``, ``weakref.finalize`` callbacks, io_callback
    host halves), or whose instances are published at module level
    (``pager = TilePager()``) or into an attribute of another shared
    class (``self._m1 = EWMA()``), to a fixpoint;
  * module-level globals of a hot module that are REBOUND or mutated
    (subscript store / mutator method on a plain container) from
    function scope — the module list itself declares these modules
    concurrent, so every such write needs a lock.

Locksets are computed lexically (``with lock:`` regions, the
``if lock.acquire(...):`` try-acquire idiom) plus the codebase's
``*_locked`` naming convention: a method whose name ends in ``_locked``
inherits the intersection of the locks held at its same-class call
sites (to a small fixpoint, so ``_trim_locked`` -> ``_evict_locked``
chains resolve).

Exemptions, in the order they are applied:

  * attributes/globals whose every write happens in ``__init__`` /
    module scope (init-confinement: publication is the only hand-off);
  * attributes initialized to an internally-synchronized object — a
    stdlib threading/queue primitive or a PACKAGE class that owns a
    lock attribute (``CounterMetric``, ``TilePager``, ...): method
    calls on such an attribute serialize themselves (rebinding the
    attribute still counts);
  * a DECLARED GIL-atomic attribute: ``# graftlint: ok(
    shared-state-race): why`` on the attribute's ``__init__``
    assignment line (or the comment block above it) exempts the
    attribute package-wide — the declaration is the audit trail that a
    single-op counter read/write is intentionally unlocked. Declared,
    never assumed;
  * ordinary same-line suppressions via the existing machinery.

One finding per racy attribute/global (at its worst access site), so
the initial package run is triageable fix-by-fix.
"""

from __future__ import annotations

import ast
import os

from ..core import (Finding, FuncInfo, Module, Package, call_name,
                    dotted)

RULE = "shared-state-race"

# the hot-path modules the issue names: the concurrency surface built
# by PRs 3-11. Snippet modules (test fixtures) always count hot.
# `devbuild` joined with the device-parallel builder (ISSUE 16): every
# refresh/compaction thread mutates its config + counters.
# `membership` joined with elastic pod membership (ISSUE 19): ledger,
# lease, and abandoned-seq state are hit from exec handlers, heartbeat
# threads, and driver retries at once.
# `profiler` joined with the always-on phase timers (ISSUE 25): REST,
# pool and leader threads all feed its module-level registry.
_HOT_MODULES = {"dispatch", "traffic", "resident", "repack", "tiering",
                "executor", "cache", "faults", "metrics", "devbuild",
                "membership", "profiler"}

# stdlib constructor tails whose instances serialize themselves (or are
# thread-confined by construction, like threading.local); package
# classes that OWN a lock attribute are computed, not listed
_SYNC_TAILS = {"Lock", "RLock", "Condition", "Event", "Semaphore",
               "BoundedSemaphore", "Barrier", "local", "Queue",
               "SimpleQueue", "LifoQueue", "PriorityQueue", "ref",
               "WeakValueDictionary", "WeakKeyDictionary",
               "WeakSet"}
_CONTAINER_TAILS = {"dict", "list", "set", "OrderedDict", "defaultdict",
                    "deque", "Counter"}
# method calls that mutate a plain container receiver
_MUTATORS = {"append", "extend", "insert", "add", "update", "setdefault",
             "pop", "popitem", "remove", "discard", "clear",
             "appendleft", "extendleft", "move_to_end", "sort",
             "reverse"}


def _hot(m: Module) -> bool:
    base = m.relpath.rsplit("/", 1)[-1].removesuffix(".py")
    return m.snippet or base in _HOT_MODULES


def _mod_tag(m: Module) -> str:
    return os.path.splitext(os.path.basename(m.relpath))[0]


# ---------------------------------------------------------------------------
# init-value classification
# ---------------------------------------------------------------------------

def _init_kind(value: ast.AST, sync_classes: set[str]) -> str:
    """'sync' | 'container' | 'other' for an __init__/module-level
    assignment's right-hand side."""
    if isinstance(value, (ast.Dict, ast.List, ast.Set, ast.ListComp,
                          ast.DictComp, ast.SetComp)):
        return "container"
    if isinstance(value, ast.Call):
        tail = call_name(value).split(".")[-1]
        if tail in _SYNC_TAILS or tail in sync_classes:
            return "sync"
        if tail in _CONTAINER_TAILS:
            return "container"
    return "other"


def _class_locks(m: Module) -> dict[str, set[str]]:
    """class name -> its OWN lock attribute names. Computed directly
    (not from Module.locks, whose suffix keying collides when several
    classes in one module all name their lock `_lock`)."""
    out: dict[str, set[str]] = {}
    for fi in m.functions:
        if not fi.class_name:
            continue
        for n in ast.walk(fi.node):
            if isinstance(n, ast.Assign) and isinstance(n.value,
                                                        ast.Call):
                base = call_name(n.value).split(".")[-1]
                if base not in ("Lock", "RLock", "Condition"):
                    continue
                for t in n.targets:
                    if isinstance(t, ast.Attribute) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id == "self":
                        out.setdefault(fi.class_name,
                                       set()).add(t.attr)
    return out


def _lock_owning_classes(pkg: Package) -> set[str]:
    """Bare names of package classes that own a lock attribute — their
    instances are treated as internally synchronized receivers."""
    owners: set[str] = set()
    for m in pkg.modules:
        owners.update(_class_locks(m))
    return owners


# ---------------------------------------------------------------------------
# access-site collection with a held-lock stack
# ---------------------------------------------------------------------------

class _Site:
    __slots__ = ("key", "kind", "line", "col", "func", "locks",
                 "in_init")

    def __init__(self, key, kind, node, func, locks, in_init):
        self.key = key
        self.kind = kind          # "write" | "mutate" | "read"
        self.line = node.lineno
        self.col = getattr(node, "col_offset", 0)
        self.func = func
        self.locks = frozenset(locks)
        self.in_init = in_init


def _lock_key(m: Module, expr: ast.AST, pkg: Package) -> str | None:
    name = dotted(expr)
    if not name:
        return None
    suffix = name.split(".", 1)[1] if name.startswith("self.") else name
    li = m.locks.get(suffix)
    if li is not None:
        return li.key
    hits = [mm.locks[suffix] for mm in pkg.modules
            if suffix in mm.locks]
    return hits[0].key if len(hits) == 1 else None


def _collect_func(m: Module, fi: FuncInfo, pkg: Package,
                  inherited: frozenset,
                  self_calls: "list[tuple[str, frozenset]]",
                  sites: list[_Site],
                  attr_mode: bool, globals_: set[str],
                  own_locks: set[str] = frozenset()) -> None:
    """Walk one function, tracking held locks, emitting access sites.

    attr_mode: collect `self.X` accesses (class pass); otherwise
    collect module-global writes (global pass). `self_calls` receives
    (bare method name, held set) for every `self.meth()` call so the
    `_locked` inheritance fixpoint can run. `own_locks` are the
    enclosing class's OWN lock attribute names — `with self.X:` keys
    per class, immune to same-suffix collisions across classes."""
    in_init = fi.name == "__init__"
    mod = _mod_tag(m)

    def lock_of(expr):
        name = dotted(expr)
        if name.startswith("self.") and \
                name.split(".", 1)[1] in own_locks:
            return f"{mod}.{fi.class_name}.{name.split('.', 1)[1]}"
        return _lock_key(m, expr, pkg)

    def emit(key, kind, node, held):
        sites.append(_Site(key, kind, node, fi, held, in_init))

    def scan_expr(node: ast.AST, held: frozenset) -> None:
        """Accesses inside one expression/simple statement."""
        consumed: set[int] = set()
        for n in ast.walk(node):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            if isinstance(n, ast.Call):
                # local calls feed the `_locked` inheritance pass:
                # `self.meth()` for the class pass, bare-name calls
                # for module-level helpers
                cn = call_name(n)
                if cn.startswith("self.") and cn.count(".") == 1:
                    self_calls.append((cn.split(".")[1], held))
                elif cn and "." not in cn:
                    self_calls.append((cn, held))
                # mutator call on a tracked receiver
                if isinstance(n.func, ast.Attribute) and \
                        n.func.attr in _MUTATORS:
                    recv = n.func.value
                    if attr_mode and isinstance(recv, ast.Attribute) \
                            and isinstance(recv.value, ast.Name) \
                            and recv.value.id == "self":
                        emit(recv.attr, "mutate", n, held)
                        consumed.add(id(recv))
                    elif not attr_mode and isinstance(recv, ast.Name) \
                            and recv.id in globals_:
                        emit(recv.id, "mutate", n, held)
            elif isinstance(n, ast.Subscript):
                base = n.value
                if isinstance(n.ctx, (ast.Store, ast.Del)):
                    if attr_mode and isinstance(base, ast.Attribute) \
                            and isinstance(base.value, ast.Name) \
                            and base.value.id == "self":
                        emit(base.attr, "mutate", n, held)
                        consumed.add(id(base))
                    elif not attr_mode and isinstance(base, ast.Name) \
                            and base.id in globals_:
                        emit(base.id, "mutate", n, held)
        if not attr_mode:
            return
        for n in ast.walk(node):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            if isinstance(n, ast.Attribute) and \
                    isinstance(n.value, ast.Name) and \
                    n.value.id == "self" and id(n) not in consumed:
                if isinstance(n.ctx, (ast.Store, ast.Del)):
                    emit(n.attr, "write", n, held)
                else:
                    emit(n.attr, "read", n, held)

    def scan_global_assigns(s: ast.stmt, held: frozenset) -> None:
        """Rebinding writes to module globals (requires a `global`
        declaration somewhere in the function — a bare Name store
        without one is a local)."""
        targets = []
        if isinstance(s, ast.Assign):
            targets = s.targets
        elif isinstance(s, (ast.AugAssign, ast.AnnAssign)):
            targets = [s.target]
        for t in targets:
            for n in ast.walk(t):
                if isinstance(n, ast.Name) and n.id in globals_ and \
                        n.id in declared_global:
                    emit(n.id, "write", n, held)

    declared_global: set[str] = set()
    for n in ast.walk(fi.node):
        if isinstance(n, ast.Global):
            declared_global.update(n.names)

    def visit(stmts: list[ast.stmt], held: frozenset) -> None:
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue               # processed as their own function
            if isinstance(s, ast.With):
                extra = set()
                for item in s.items:
                    scan_expr(item.context_expr, held)
                    lk = lock_of(item.context_expr)
                    if lk is not None:
                        extra.add(lk)
                visit(s.body, held | frozenset(extra))
                continue
            if isinstance(s, ast.If):
                scan_expr(s.test, held)
                extra = set()
                for call in [n for n in ast.walk(s.test)
                             if isinstance(n, ast.Call)]:
                    if call_name(call).split(".")[-1] == "acquire" and \
                            isinstance(call.func, ast.Attribute):
                        lk = lock_of(call.func.value)
                        if lk is not None:
                            extra.add(lk)
                visit(s.body, held | frozenset(extra))
                visit(s.orelse, held)
                continue
            if isinstance(s, (ast.For, ast.AsyncFor)):
                scan_expr(s.iter, held)
                scan_expr(s.target, held)
                scan_global_assigns(s, held)
                visit(s.body, held)
                visit(s.orelse, held)
                continue
            if isinstance(s, ast.While):
                scan_expr(s.test, held)
                visit(s.body, held)
                visit(s.orelse, held)
                continue
            if isinstance(s, ast.Try):
                visit(s.body, held)
                for h in s.handlers:
                    visit(h.body, held)
                visit(s.orelse, held)
                visit(s.finalbody, held)
                continue
            scan_expr(s, held)
            if not attr_mode:
                scan_global_assigns(s, held)

    visit(fi.node.body, inherited)


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

def _shared_classes(m: Module, pkg: Package,
                    lock_owners: set[str]) -> dict[str, str]:
    """class name -> why-shared for one hot module."""
    shared: dict[str, str] = {}
    class_names = {fi.class_name for fi in m.functions if fi.class_name}
    for name in class_names:
        if name in lock_owners:
            shared.setdefault(name, "owns a lock")
    for fi, why in pkg.thread_entries().values():
        if fi.module is m and fi.class_name:
            shared.setdefault(fi.class_name, f"thread entry ({why})")
    # module-level publication: stats = TieringStats()
    for node in m.tree.body:
        if isinstance(node, ast.Assign) and isinstance(node.value,
                                                       ast.Call):
            tail = call_name(node.value).split(".")[-1]
            if tail in class_names:
                shared.setdefault(tail, "published at module level")
    # fixpoint: instances stored into attributes of shared classes
    changed = True
    while changed:
        changed = False
        for fi in m.functions:
            if fi.class_name not in shared:
                continue
            for n in ast.walk(fi.node):
                if isinstance(n, ast.Assign) and \
                        isinstance(n.value, ast.Call):
                    tail = call_name(n.value).split(".")[-1]
                    if tail in class_names and tail not in shared:
                        shared[tail] = \
                            f"published via {fi.qualname}"
                        changed = True
    return shared


def _locked_inheritance(m: Module, pkg: Package,
                        funcs: list[FuncInfo],
                        locked_fns: dict[str, FuncInfo],
                        attr_mode: bool, globals_: set[str],
                        own_of) -> dict[int, frozenset]:
    """`*_locked` convention, shared by the class pass (methods called
    as `self.X_locked()`) and the global pass (module helpers like the
    executor's `_autotune_persist_locked`): each such function inherits
    the INTERSECTION of the locks held at its call sites, iterated to
    a small fixpoint so `_trim_locked` -> `_evict_locked` chains
    resolve. `own_of(fi)` supplies the enclosing class's own lock
    names for per-class `with self.X:` keying."""
    inherited: dict[int, frozenset] = {
        id(fi.node): frozenset() for fi in funcs}
    if not locked_fns:
        return inherited
    for _round in range(3):
        changed = False
        # collect call sites with the CURRENT inheritance estimate
        calls: dict[str, list[frozenset]] = {n: [] for n in locked_fns}
        for fi in funcs:
            recs: list[tuple[str, frozenset]] = []
            _collect_func(m, fi, pkg, inherited[id(fi.node)], recs,
                          [], attr_mode, globals_, own_of(fi))
            for name, held in recs:
                if name in calls:
                    calls[name].append(held)
        for name, fi in locked_fns.items():
            sites = calls[name]
            new = (frozenset.intersection(*sites) if sites
                   else frozenset())
            if new != inherited[id(fi.node)]:
                inherited[id(fi.node)] = new
                changed = True
        if not changed:
            break
    return inherited


def _inherited_locks(methods: list[FuncInfo], m: Module,
                     pkg: Package,
                     own_locks: set[str]) -> dict[int, frozenset]:
    return _locked_inheritance(
        m, pkg, methods,
        {fi.name: fi for fi in methods
         if fi.name.endswith("_locked")},
        True, set(), lambda _fi: own_locks)


def check(pkg: Package) -> list[Finding]:
    findings: list[Finding] = []
    lock_owners = _lock_owning_classes(pkg)
    for m in pkg.modules:
        if not _hot(m):
            continue
        findings.extend(_check_classes(m, pkg, lock_owners))
        findings.extend(_check_globals(m, pkg, lock_owners))
    return findings


def _check_classes(m: Module, pkg: Package,
                   lock_owners: set[str]) -> list[Finding]:
    out: list[Finding] = []
    mod = _mod_tag(m)
    shared = _shared_classes(m, pkg, lock_owners)
    cls_locks = _class_locks(m)
    for cls, why in sorted(shared.items()):
        methods = [fi for fi in m.functions if fi.class_name == cls]
        if not methods:
            continue
        own_locks = cls_locks.get(cls, set())
        inherited = _inherited_locks(methods, m, pkg, own_locks)
        sites: list[_Site] = []
        for fi in methods:
            _collect_func(m, fi, pkg, inherited[id(fi.node)], [],
                          sites, True, set(), own_locks)
        # init facts: attr -> (kind, def line)
        init_info: dict[str, tuple[str, int]] = {}
        for fi in methods:
            if fi.name != "__init__":
                continue
            for n in ast.walk(fi.node):
                targets, value = [], None
                if isinstance(n, ast.Assign):
                    targets, value = n.targets, n.value
                elif isinstance(n, ast.AnnAssign) and \
                        n.value is not None:
                    targets, value = [n.target], n.value
                for t in targets:
                    if isinstance(t, ast.Attribute) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id == "self":
                        kind = _init_kind(value, lock_owners)
                        prev = init_info.get(t.attr)
                        if prev is None or prev[0] != "sync":
                            init_info[t.attr] = (kind, n.lineno)
        by_attr: dict[str, list[_Site]] = {}
        for s in sites:
            by_attr.setdefault(s.key, []).append(s)
        for attr, attr_sites in sorted(by_attr.items()):
            key = f"{mod}.{cls}.{attr}"
            if attr in own_locks or attr in m.locks:
                continue               # the lock itself
            kind, def_line = init_info.get(attr, ("other", 0))
            writes = [s for s in attr_sites if not s.in_init
                      and (s.kind == "write"
                           or (s.kind == "mutate"
                               and kind == "container"))]
            if not writes:
                continue               # init-confined (or sync-managed)
            if def_line:
                sup = m.suppression_for(def_line, RULE)
                if sup is not None:
                    sup.used = True    # declared GIL-atomic/confined
                    continue
            reads = [s for s in attr_sites
                     if not s.in_init and s.kind == "read"]
            relevant = writes + reads
            common = frozenset.intersection(
                *[s.locks for s in relevant])
            if common:
                continue
            site = next((s for s in writes if not s.locks),
                        next((s for s in reads if not s.locks),
                             writes[0]))
            out.append(Finding(
                RULE, m.relpath, site.line, site.col,
                f"`{key}` has no common lockset across its "
                f"{len(writes)} write / {len(reads)} read site(s) "
                f"(class is shared: {why}) — unlocked {site.kind} in "
                f"{site.func.qualname}. Guard every access with one "
                f"lock, confine writes to __init__, or declare the "
                f"attribute at its definition line"))
    return out


def _check_globals(m: Module, pkg: Package,
                   lock_owners: set[str]) -> list[Finding]:
    out: list[Finding] = []
    mod = _mod_tag(m)
    # module-level bindings + their init classification
    globals_: dict[str, tuple[str, int]] = {}

    def harvest(stmts):
        for node in stmts:
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        globals_.setdefault(
                            t.id, (_init_kind(node.value, lock_owners),
                                   node.lineno))
            elif isinstance(node, ast.AnnAssign) and \
                    isinstance(node.target, ast.Name):
                kind = (_init_kind(node.value, lock_owners)
                        if node.value is not None else "other")
                globals_.setdefault(node.target.id, (kind, node.lineno))
            elif isinstance(node, (ast.If, ast.Try)):
                harvest(getattr(node, "body", []))
                harvest(getattr(node, "orelse", []))
                harvest(getattr(node, "finalbody", []))

    harvest(m.tree.body)
    if not globals_:
        return out
    names = set(globals_)
    cls_locks = _class_locks(m)

    def own(fi):
        return cls_locks.get(fi.class_name or "", set())

    inherited = _locked_inheritance(
        m, pkg, m.functions,
        {fi.name: fi for fi in m.functions
         if fi.class_name is None and fi.name.endswith("_locked")},
        False, names, own)
    sites: list[_Site] = []
    for fi in m.functions:
        # methods can mutate module globals too — collect everywhere
        _collect_func(m, fi, pkg, inherited[id(fi.node)], [], sites,
                      False, names, own(fi))
    by_name: dict[str, list[_Site]] = {}
    for s in sites:
        kind, _ln = globals_[s.key]
        if s.kind == "mutate" and kind != "container":
            continue   # method call on a synchronized/opaque object
        by_name.setdefault(s.key, []).append(s)
    for name, wsites in sorted(by_name.items()):
        kind, def_line = globals_[name]
        sup = m.suppression_for(def_line, RULE)
        if sup is not None:
            sup.used = True
            continue
        common = frozenset.intersection(*[s.locks for s in wsites])
        if common:
            continue
        site = next((s for s in wsites if not s.locks), wsites[0])
        out.append(Finding(
            RULE, m.relpath, site.line, site.col,
            f"module global `{mod}.{name}` is written from function "
            f"scope with no common lockset ({len(wsites)} write "
            f"site(s)) — unlocked {site.kind} in {site.func.qualname}."
            f" Guard the writes with one module lock or declare the "
            f"global at its definition line"))
    return out
