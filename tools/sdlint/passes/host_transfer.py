"""Pass: host-transfer — no stray D2H fetches in device pipelines.

A `np.asarray(...)`, `.item()`, implicit `bool(arr)`, or
`block_until_ready()` on a live device value forces a synchronous
device→host round trip, and in the identify loop a single stray
fetch serializes the chunk's dispatch behind its own fetch.
The discipline: every transfer of jit results happens at a DECLARED
point — a `with jit_registry.io("<contract>"):` scope whose contract
(ops/jit_registry.py) is declared `host_transfer=True` — or runs
off-loop via to_thread, or is baselined with a reason.

Detection is lexical over "device-consumer" functions — those whose
body calls a registered jit entry point (by bound callable name) or
`jax.device_put`:

- `undeclared-transfer` — np.asarray / np.array / jax.device_get /
  `.item()` / `.block_until_ready()` outside any io(...) scope.
  np.asarray *inside the argument list* of a jit-entry call is input
  prep (H2D), not a result fetch, and is exempt;
- `implicit-host-cast` / `implicit-host-bool` — `int()/float()/bool()`
  or a bare `if`/`while` test over a variable assigned from a jit
  entry call: the hidden `__bool__`/`__float__` is a full D2H sync;
- `undeclared-io` — an `io(name)` scope whose name is not a declared
  host_transfer contract (the registry must stay authoritative).

Dataflow through variables ACROSS functions is out of scope by design
(same note as blocking-async): the runtime transfer guard armed by the
sanitizer inside `device_scope()` regions covers that half.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from ..core import Finding, FuncInfo, Project, dotted, own_body_walk
from .jit_stability import _tracked_name, declared_contracts

PASS = "host-transfer"

_THREAD_WRAPPERS = {"to_thread", "run_in_executor", "submit"}


def _jit_entry_names(project: Project, contracts: Dict[str, dict]
                     ) -> Set[str]:
    """Callable names that dispatch registered device work: contract
    site terminals plus every tracked/jit-decorated def in the tree
    (fixtures carry their own local jits)."""
    names: Set[str] = set()
    for c in contracts.values():
        qual = c["site"].split("::", 1)[-1]
        if qual:
            names.add(qual.rsplit(".", 1)[-1])
    for fn in project.index.funcs:
        node = fn.node
        decos = getattr(node, "decorator_list", [])
        for deco in decos:
            if dotted(deco) in ("jax.jit", "jit") \
                    or _tracked_name(deco) is not None:
                names.add(fn.name)
            if isinstance(deco, ast.Call) and dotted(deco.func) \
                    and dotted(deco.func).rsplit(".", 1)[-1] == "partial":
                if deco.args and dotted(deco.args[0]) in ("jax.jit",
                                                          "jit"):
                    names.add(fn.name)
    return names


def _io_scope_name(with_node: ast.With) -> Optional[str]:
    for item in with_node.items:
        ce = item.context_expr
        if isinstance(ce, ast.Call) and dotted(ce.func) is not None \
                and dotted(ce.func).rsplit(".", 1)[-1] == "io" \
                and ce.args and isinstance(ce.args[0], ast.Constant) \
                and isinstance(ce.args[0].value, str):
            return ce.args[0].value
    return None


class HostTransferPass:
    name = PASS

    def run(self, project: Project) -> List[Finding]:
        contracts = declared_contracts(project.root)
        jit_names = _jit_entry_names(project, contracts)
        findings: List[Finding] = []
        for fn in project.index.funcs:
            if self._is_consumer(fn, jit_names):
                self._check_fn(fn, jit_names, contracts, findings)
        return findings

    @staticmethod
    def _is_consumer(fn: FuncInfo, jit_names: Set[str]) -> bool:
        for site in fn.calls:
            last = site.name.rsplit(".", 1)[-1]
            if last in jit_names or site.name in ("jax.device_put",
                                                  "device_put"):
                return True
        return False

    def _check_fn(self, fn: FuncInfo, jit_names: Set[str],
                  contracts: Dict[str, dict],
                  findings: List[Finding]) -> None:
        src = fn.src
        wrapped_ids = {id(s.node) for s in fn.calls if s.wrapped}
        # argument subtrees of jit-entry calls: input prep, not fetch
        prep_ids: Set[int] = set()
        jit_vars: Set[str] = set()
        for node in own_body_walk(fn.node):
            if isinstance(node, ast.Call) and dotted(node.func):
                last = dotted(node.func).rsplit(".", 1)[-1]
                if last in jit_names:
                    for arg in list(node.args) + [kw.value
                                                  for kw in node.keywords]:
                        for sub in ast.walk(arg):
                            prep_ids.add(id(sub))
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call) \
                    and dotted(node.value.func) is not None \
                    and dotted(node.value.func).rsplit(".", 1)[-1] \
                    in jit_names:
                jit_vars.add(node.targets[0].id)

        def emit(code: str, ident: str, msg: str, lineno: int) -> None:
            findings.append(Finding(
                PASS, code, src.relpath, fn.qual, ident, msg, lineno))

        def walk(node: ast.AST, declared: bool) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)) and node is not fn.node:
                return
            if isinstance(node, ast.With):
                name = _io_scope_name(node)
                if name is not None:
                    c = contracts.get(name)
                    if c is None or not c.get("host_transfer"):
                        emit("undeclared-io", name,
                             f"io({name!r}) is not a declared "
                             f"host_transfer contract in the jit "
                             f"registry", node.lineno)
                    for child in node.body:
                        walk(child, True)
                    return
            if isinstance(node, (ast.If, ast.While)) \
                    and isinstance(node.test, ast.Name) \
                    and node.test.id in jit_vars:
                emit("implicit-host-bool", node.test.id,
                     f"bare truth test over jit result "
                     f"`{node.test.id}` forces a blocking D2H sync "
                     f"(fetch explicitly inside a declared io scope)",
                     node.lineno)
            if isinstance(node, ast.Call):
                self._check_call(node, declared, wrapped_ids, prep_ids,
                                 jit_vars, emit)
            for child in ast.iter_child_nodes(node):
                walk(child, declared)

        for stmt in ast.iter_child_nodes(fn.node):
            walk(stmt, False)

    @staticmethod
    def _check_call(node: ast.Call, declared: bool, wrapped_ids: Set[int],
                    prep_ids: Set[int], jit_vars: Set[str], emit) -> None:
        if declared or id(node) in wrapped_ids:
            return
        d = dotted(node.func)
        idiom = None
        if d is not None:
            parts = d.split(".")
            last = parts[-1]
            base = ".".join(parts[:-1])
            if last in ("asarray", "array") and base in ("np", "numpy"):
                idiom = "np." + last
            elif d in ("jax.device_get", "device_get"):
                idiom = "device_get"
            elif last in ("int", "float", "bool") and len(parts) == 1 \
                    and node.args and isinstance(node.args[0], ast.Name) \
                    and node.args[0].id in jit_vars:
                emit("implicit-host-cast", f"{last}:{node.args[0].id}",
                     f"{last}() over jit result `{node.args[0].id}` is "
                     f"an implicit D2H sync (fetch inside a declared io "
                     f"scope)", node.lineno)
                return
        if isinstance(node.func, ast.Attribute):
            if node.func.attr == "item" and not node.args:
                idiom = ".item()"
            elif node.func.attr == "block_until_ready":
                idiom = "block_until_ready"
        if idiom is None or id(node) in prep_ids:
            return
        emit("undeclared-transfer", f"{idiom}:{d or '?'}",
             f"`{idiom}` in a device-consumer function outside any "
             f"declared io(...) scope — wrap the fetch in "
             f"jit_registry.io(<contract>) (host_transfer=True), "
             f"offload via to_thread, or baseline with a reason",
             node.lineno)
