"""Shared machinery for the thread-safety pass family (round 13):
thread-context derivation over the PR 4 call graph, the threadctx.py
ownership-registry parser, and attribute-mutation site collection with
lexical lockset tracking.

THREAD CONTEXTS. Every project function is assigned the set of thread
contexts it is statically reachable from:

- ``loop``          — `async def` bodies, plus anything they call
  synchronously, plus functions posted to a loop via
  `call_soon_threadsafe` / `run_coroutine_threadsafe` /
  `threadctx.call_threadsafe`;
- ``worker:<qual>`` — one context per thread-submission ROOT: a
  function handed to `asyncio.to_thread`, `run_in_executor`,
  `executor.submit` (the ops/staging.py pool), or
  `threading.Thread(target=...)`,
  plus everything it calls synchronously. Each root is its OWN
  context: two different submissions may run on different pool
  threads concurrently;
- ``atexit``        — `atexit.register` / `signal.signal` targets
  (shutdown runs them on whatever thread the interpreter exits on).

Propagation is a fixed point over resolvable, unwrapped call edges
(wrapped calls execute in the callee's submitted context, which the
seeding already covers). A function reachable from two or more
distinct contexts is MULTI-CONTEXT; a class whose attribute mutations
span two or more contexts is what the ownership registry exists to
govern.

This is a best-effort over-approximation in exactly the PR 4 spirit:
dynamic dispatch the resolver cannot see is covered by the runtime
twin (spacedrive_tpu/threadctx.py armed with the sanitizer).
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

from ..core import FuncInfo, Project, dotted, own_body_walk

LOOP = "loop"
ATEXIT = "atexit"

CENTRAL = "spacedrive_tpu/threadctx.py"

# Calls whose function-reference ARGUMENTS run on a worker thread.
_WORKER_SUBMITTERS = {"to_thread", "run_in_executor", "submit"}
# Calls whose function-reference arguments run on the EVENT LOOP
# (posted from any thread) — the sanctioned hand-off shapes.
LOOP_POSTERS = {"call_soon_threadsafe", "run_coroutine_threadsafe",
                "call_threadsafe", "call_soon", "call_later"}
# Shutdown-hook registrars.
_SHUTDOWN_REGISTRARS = {"atexit.register", "signal.signal"}


def _fn_key(fn: FuncInfo) -> str:
    return f"{fn.src.relpath}::{fn.qual}"


def _memo(project: Project, key, build):
    """Per-Project memo for the pure whole-tree analyses this module
    provides: three passes share a lint run's Project, and re-deriving
    the context fixed point or the mutation-site sweep per pass would
    double the analyzer's wall time for identical results. The cache
    rides the Project instance, so a fresh load (tests, --changed
    re-index) naturally starts cold."""
    cache = getattr(project, "_threads_memo", None)
    if cache is None:
        cache = {}
        project._threads_memo = cache
    if key not in cache:
        cache[key] = build()
    return cache[key]


def _callable_args(call: ast.Call) -> List[ast.AST]:
    out = list(call.args)
    out.extend(kw.value for kw in call.keywords)
    return out


def thread_contexts(project: Project) -> Dict[str, Set[str]]:
    """func key ("relpath::qual") → context-label set. Functions
    reachable from no known root map to an empty set (ambient
    bench/test drivers — single-threaded by construction). Memoized
    per Project (shared by all three thread passes)."""
    return _memo(project, "contexts", lambda: _thread_contexts(project))


def _thread_contexts(project: Project) -> Dict[str, Set[str]]:
    idx = project.index
    contexts: Dict[str, Set[str]] = {_fn_key(f): set()
                                     for f in idx.funcs}

    # -- seeds -------------------------------------------------------------
    for fn in idx.funcs:
        if fn.is_async:
            contexts[_fn_key(fn)].add(LOOP)
        for node in own_body_walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func)
            if d is None:
                continue
            last = d.rsplit(".", 1)[-1]
            if last in _WORKER_SUBMITTERS:
                for arg in _callable_args(node):
                    ad = dotted(arg)
                    if ad is None:
                        continue
                    target = idx.resolve(fn, ad)
                    if target is not None and not target.is_async:
                        contexts[_fn_key(target)].add(
                            f"worker:{target.qual}")
            elif last in LOOP_POSTERS:
                for arg in _callable_args(node):
                    ad = dotted(arg)
                    if ad is None:
                        continue
                    target = idx.resolve(fn, ad)
                    if target is not None:
                        contexts[_fn_key(target)].add(LOOP)
            elif d in _SHUTDOWN_REGISTRARS:
                for arg in _callable_args(node):
                    ad = dotted(arg)
                    if ad is None:
                        continue
                    target = idx.resolve(fn, ad)
                    if target is not None:
                        contexts[_fn_key(target)].add(ATEXIT)
            elif last == "Thread" and d.split(".")[0] in ("threading",
                                                          "Thread"):
                for kw in node.keywords:
                    if kw.arg != "target":
                        continue
                    ad = dotted(kw.value)
                    if ad is None:
                        continue
                    target = idx.resolve(fn, ad)
                    if target is not None:
                        contexts[_fn_key(target)].add(
                            f"worker:{target.qual}")

    # -- fixed-point propagation over resolvable sync call edges -----------
    changed = True
    while changed:
        changed = False
        for fn in idx.funcs:
            src_ctx = contexts[_fn_key(fn)]
            if not src_ctx:
                continue
            for site in fn.calls:
                if site.wrapped:
                    continue  # executes in a submitted context
                callee = idx.resolve(fn, site.name)
                if callee is None:
                    continue
                if callee.is_async:
                    # A worker cannot RUN an async callee by calling
                    # it; a loop context calling it is already loop.
                    continue
                dst = contexts[_fn_key(callee)]
                add = src_ctx - dst
                if add:
                    dst |= add
                    changed = True
    return contexts


# -- ownership-registry parsing (AST: the linted tree is never imported) ----

_KIND_FACTORIES = {"loop_only", "single_thread", "guarded_by",
                   "atomic_counter", "immutable_after_init"}


def _parse_attr_contract(node: ast.AST) -> Optional[Tuple[str,
                                                          Optional[str]]]:
    """(kind, lock) for a `guarded_by("x")` / `loop_only()` value."""
    if not isinstance(node, ast.Call):
        return None
    d = dotted(node.func)
    if d is None:
        return None
    last = d.rsplit(".", 1)[-1]
    if last not in _KIND_FACTORIES:
        return None
    lock = None
    if last == "guarded_by" and node.args and \
            isinstance(node.args[0], ast.Constant):
        lock = str(node.args[0].value)
    return last, lock


def declared_owners_from_tree(tree: ast.Module) -> Dict[str, Dict]:
    """name → {site, attrs: {attr: (kind, lock)}, lineno} for every
    literal `declare_owner(...)` call in one module AST."""
    out: Dict[str, Dict] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.args):
            continue
        d = dotted(node.func)
        if d is None or d.rsplit(".", 1)[-1] != "declare_owner":
            continue
        name = node.args[0]
        if not (isinstance(name, ast.Constant)
                and isinstance(name.value, str)):
            continue
        spec = {"site": "", "attrs": {}, "lineno": node.lineno}
        if len(node.args) > 1 and isinstance(node.args[1], ast.Constant):
            spec["site"] = str(node.args[1].value)
        attrs_node = node.args[2] if len(node.args) > 2 else None
        for kw in node.keywords:
            if kw.arg == "site" and isinstance(kw.value, ast.Constant):
                spec["site"] = str(kw.value.value)
            if kw.arg == "attrs":
                attrs_node = kw.value
        if isinstance(attrs_node, ast.Dict):
            for k, v in zip(attrs_node.keys, attrs_node.values):
                if not (isinstance(k, ast.Constant)
                        and isinstance(k.value, str)):
                    continue
                parsed = _parse_attr_contract(v)
                if parsed is not None:
                    spec["attrs"][k.value] = parsed
        out[name.value] = spec
    return out


def declared_owners(root: str, project: Project) -> Dict[str, Dict]:
    """The ownership table: the central registry plus any declarations
    inside the analyzed files themselves (how the per-pass fixtures
    self-declare). Memoized per Project."""
    return _memo(project, ("owners", root),
                 lambda: _declared_owners(root, project))


def _declared_owners(root: str, project: Project) -> Dict[str, Dict]:
    out: Dict[str, Dict] = {}
    in_project = {src.relpath for src in project.files}
    if CENTRAL not in in_project:
        path = os.path.join(root, CENTRAL)
        try:
            tree = ast.parse(open(path, encoding="utf-8").read())
            out.update(declared_owners_from_tree(tree))
        except (OSError, SyntaxError):
            pass
    for src in project.files:
        out.update(declared_owners_from_tree(src.tree))
    return out


def owners_by_class(declared: Dict[str, Dict]) -> Dict[str, Dict]:
    """ClassName → owner spec (class names are unique by registry
    construction — threadctx.declare_owner enforces it)."""
    out: Dict[str, Dict] = {}
    for name, spec in declared.items():
        site = spec.get("site", "")
        if "::" in site:
            out[site.split("::", 1)[1]] = {"name": name, **spec}
    return out


def class_hierarchy(project: Project) -> Dict[str, List[str]]:
    """class name → base-class terminal names, project-wide (name-
    keyed: the registry enforces unique class names for its members,
    and for unregistered classes a rare collision only widens the
    contract lookup). Memoized per Project."""
    return _memo(project, "hierarchy",
                 lambda: _class_hierarchy(project))


def _class_hierarchy(project: Project) -> Dict[str, List[str]]:
    out: Dict[str, List[str]] = {}
    for src in project.files:
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            bases = []
            for b in node.bases:
                d = dotted(b)
                if d is not None:
                    bases.append(d.rsplit(".", 1)[-1])
            out.setdefault(node.name, bases)
    return out


def effective_owner(cls_name: str, by_class: Dict[str, Dict],
                    hierarchy: Dict[str, List[str]]) -> Optional[Dict]:
    """The contract governing `cls_name`: its own declaration merged
    over its ancestors' (nearest wins — the runtime twin composes the
    same way down the MRO). None when no ancestor is declared."""
    merged_attrs: Dict[str, Tuple[str, Optional[str]]] = {}
    found: Optional[Dict] = None
    seen: Set[str] = set()

    def visit(name: str) -> None:
        nonlocal found
        if name in seen:
            return
        seen.add(name)
        for base in hierarchy.get(name, []):
            visit(base)
        spec = by_class.get(name)
        if spec is not None:
            found = spec
            merged_attrs.update(spec["attrs"])

    visit(cls_name)
    if found is None:
        return None
    return {**found, "attrs": merged_attrs}


# -- attribute-mutation site collection -------------------------------------

# `update` and `insert` are deliberately absent: `report.update(db)` /
# `db.insert(table, row)` are domain-object methods far more often
# than list/dict mutations in this tree — the ambiguity produced only
# false attributions (subscript writes still catch dict updates).
CONTAINER_MUTATORS = {
    "append", "appendleft", "extend", "remove", "clear",
    "add", "discard", "setdefault", "popitem",
}

_INIT_NAMES = {"__init__", "__post_init__", "__new__", "__set_name__"}


class MutationSite:
    """One write to `<receiver>.<attr>`: the receiver resolved to a
    class name (via self, a registered-class annotation, or a local
    construction), the lexical lockset held at the write, and whether
    it is an augmented (`+=`) update or a container mutation."""

    __slots__ = ("cls_name", "attr", "fn", "lineno", "locks", "aug",
                 "container", "in_init", "self_recv")

    def __init__(self, cls_name: str, attr: str, fn: FuncInfo,
                 lineno: int, locks: frozenset, aug: bool,
                 container: bool, in_init: bool, self_recv: bool):
        self.cls_name = cls_name
        self.attr = attr
        self.fn = fn
        self.lineno = lineno
        self.locks = locks
        self.aug = aug
        self.container = container
        self.in_init = in_init
        self.self_recv = self_recv


def _lock_of(expr: ast.AST) -> Optional[str]:
    from .lock_discipline import lock_name

    ln = lock_name(expr)
    if ln is not None:
        return ln
    # `with db.tx():` / `with sync.write_ops():` hold the database's
    # write lock for the whole block (store/db.py acquires
    # `_write_lock` on entry) — model it so guarded_by("_write_lock")
    # contracts are checkable at tx-protected mutation sites.
    if isinstance(expr, ast.Call):
        d = dotted(expr.func)
        if d is not None and d.rsplit(".", 1)[-1] in ("tx",
                                                      "write_ops"):
            return "_write_lock"
    return None


def _annotation_classes(fn: FuncInfo, known: Set[str]) -> Dict[str, str]:
    """param name → class name, for parameters annotated with a known
    (registered or project) class — `stats: Optional[Stats]` resolves
    `stats.h2d_bytes += ...` to Stats."""
    out: Dict[str, str] = {}
    args_node = getattr(fn.node, "args", None)
    if args_node is None:
        return out
    every = (list(args_node.posonlyargs) + list(args_node.args)
             + list(args_node.kwonlyargs))
    for a in every:
        if a.annotation is None:
            continue
        for sub in ast.walk(a.annotation):
            if isinstance(sub, ast.Name) and sub.id in known:
                out[a.arg] = sub.id
                break
            if isinstance(sub, ast.Attribute) and sub.attr in known:
                out[a.arg] = sub.attr
                break
    return out


def _local_constructions(fn: FuncInfo, known: Set[str]) -> Dict[str, str]:
    """local name → class name for `x = KnownClass(...)` in this body."""
    out: Dict[str, str] = {}
    for node in own_body_walk(fn.node):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)):
            continue
        d = dotted(node.value.func)
        if d is None:
            continue
        last = d.rsplit(".", 1)[-1]
        if last not in known:
            continue
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                out[tgt.id] = last
    return out


def collect_mutations(project: Project,
                      known_classes: Set[str]) -> List[MutationSite]:
    """Every attribute-mutation site attributable to a class: `self.x`
    writes inside methods, and `recv.x` writes where `recv` is a
    parameter annotated with — or a local constructed from — a class
    in `known_classes`. Tracks the lexical with-lock stack so contract
    checks can test guard coverage. Memoized per Project + known set
    (shared-mutation and guard-consistency sweep the same tree)."""
    return _memo(project, ("mutations", frozenset(known_classes)),
                 lambda: _collect_mutations(project, known_classes))


def _collect_mutations(project: Project,
                       known_classes: Set[str]) -> List[MutationSite]:
    sites: List[MutationSite] = []
    for fn in project.index.funcs:
        ann = _annotation_classes(fn, known_classes)
        local = _local_constructions(fn, known_classes)
        in_init = fn.name in _INIT_NAMES

        def resolve_recv(expr: ast.AST) -> Optional[Tuple[str, str,
                                                          bool]]:
            """(cls_name, attr, is_self) for `<recv>.<attr>` nodes."""
            if not isinstance(expr, ast.Attribute):
                return None
            base = expr.value
            if isinstance(base, ast.Name):
                if base.id == "self" and fn.cls is not None:
                    return fn.cls, expr.attr, True
                cls = ann.get(base.id) or local.get(base.id)
                if cls is not None:
                    return cls, expr.attr, False
            return None

        def note(node: ast.AST, locks: Tuple[str, ...]) -> None:
            lockset = frozenset(locks)
            if isinstance(node, (ast.Assign, ast.AugAssign,
                                 ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for tgt in targets:
                    leaves = (tgt.elts if isinstance(
                        tgt, (ast.Tuple, ast.List)) else [tgt])
                    for leaf in leaves:
                        container = False
                        if isinstance(leaf, ast.Subscript):
                            leaf = leaf.value
                            container = True
                        r = resolve_recv(leaf)
                        if r is None:
                            continue
                        cls_name, attr, is_self = r
                        sites.append(MutationSite(
                            cls_name, attr, fn, node.lineno, lockset,
                            isinstance(node, ast.AugAssign),
                            container, in_init, is_self))
            elif isinstance(node, ast.Call):
                d = dotted(node.func)
                if d is None or d.split(".")[-1] not in \
                        CONTAINER_MUTATORS or \
                        not isinstance(node.func, ast.Attribute):
                    return
                r = resolve_recv(node.func.value)
                if r is None:
                    return
                cls_name, attr, is_self = r
                sites.append(MutationSite(
                    cls_name, attr, fn, node.lineno, lockset,
                    False, True, in_init, is_self))
            elif isinstance(node, ast.Delete):
                for tgt in node.targets:
                    leaf = tgt
                    container = False
                    if isinstance(leaf, ast.Subscript):
                        leaf = leaf.value
                        container = True
                    r = resolve_recv(leaf)
                    if r is None:
                        continue
                    cls_name, attr, is_self = r
                    sites.append(MutationSite(
                        cls_name, attr, fn, node.lineno, lockset,
                        False, container, in_init, is_self))

        def walk(nodes, locks: Tuple[str, ...]) -> None:
            for node in nodes:
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.Lambda)):
                    continue  # nested bodies run in their own context
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    # async with asyncio locks guard contracts too.
                    new = list(locks)
                    for item in node.items:
                        ln = _lock_of(item.context_expr)
                        if ln is not None:
                            new.append(ln)
                    walk(node.body, tuple(new))
                    continue
                note(node, locks)
                walk(list(ast.iter_child_nodes(node)), locks)

        walk(fn.node.body, ())
    return sites
