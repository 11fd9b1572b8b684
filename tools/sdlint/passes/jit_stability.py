"""Pass: jit-stability — every jax.jit entry point under contract.

`spacedrive_tpu/ops/jit_registry.py` is the single source of truth for
the engine's jit surface: each entry point declares its trace budget,
static argnames, boundary dtypes, and shape-bucket policy, and binds
itself to the declaration with the `jit_registry.tracked("name")`
wrapper (which also does the runtime retrace accounting). This pass
enforces the binding and the compile-stability idioms around it:

- `unregistered-jit`  — a jit site (decorated def or `jax.jit(...)`
  assignment) with no `tracked(...)` binding: its trace behavior is
  invisible to both the registry and the retrace sanitizer;
- `unknown-jit-name`  — `tracked("x")` where no contract `x` exists;
- `call-time-jit`     — `jax.jit(fn)` constructed inside a function
  body whose contract is not a declared FACTORY: a fresh jit wrapper
  per call throws away the trace cache (the shape an early identify
  pipeline shipped — every calibration pause recompiled the kernel);
- `jit-in-loop`       — `jax.jit(...)` lexically inside a for/while:
  strictly worse than call-time construction;
- `static-args-mismatch` / `static-argnums` — the site's
  static_argnames drifted from the contract, or positional
  static_argnums are used (brittle under signature edits);
- `unhashable-static-arg` — a call site passes a list/dict/set literal
  for a declared static argname (TypeError at trace time, or a fresh
  trace per call if wrapped);
- `value-dependent-shape` — an argument to a registered jit callable
  is built inline with a `len(...)`-derived shape (`np.zeros(len(x))`
  at the boundary): Python-value-dependent shapes must go through the
  staging size classes / pow2 buckets, never raw lengths;
- `undeclared-donation` — a jit site passes `donate_argnums` /
  `donate_argnames` that its governing contract does not declare.
  Donation is a caller-visible semantic (the buffer is CONSUMED —
  reuse after the call raises), so it lives on the declared contract
  surface: the `donate_argnums` field of `declare_jit`. Declaring
  donation never forces it — undonated variants of the same contract
  stay legal (SDTPU_DONATE_BUFFERS=off).

The resolver is lexical by design: transfers and shapes that flow
through variables across functions are the runtime sanitizer's half
(retrace counters + transfer guard in spacedrive_tpu/sanitize.py).
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Tuple

from ..core import Finding, Project, SourceFile, dotted

PASS = "jit-stability"
CENTRAL = "spacedrive_tpu/ops/jit_registry.py"

_CREATION_FNS = {"zeros", "empty", "ones", "full"}


def declared_contracts(root: str) -> Dict[str, dict]:
    """Contracts from `declare_jit(...)` calls in the central registry
    (AST — the linted tree is never imported)."""
    path = os.path.join(root, CENTRAL)
    out: Dict[str, dict] = {}
    try:
        tree = ast.parse(open(path, encoding="utf-8").read())
    except (OSError, SyntaxError):
        return out
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and dotted(node.func) == "declare_jit" and node.args):
            continue
        name = node.args[0]
        if not (isinstance(name, ast.Constant)
                and isinstance(name.value, str)):
            continue
        site = ""
        if len(node.args) > 1 and isinstance(node.args[1], ast.Constant):
            site = str(node.args[1].value)
        c = {"site": site, "kind": "entry", "static_argnames": (),
             "host_transfer": False, "donate_argnums": ()}
        for kw in node.keywords:
            if kw.arg == "kind" and isinstance(kw.value, ast.Constant):
                c["kind"] = kw.value.value
            elif kw.arg == "static_argnames":
                c["static_argnames"] = _str_tuple(kw.value)
            elif kw.arg == "host_transfer" \
                    and isinstance(kw.value, ast.Constant):
                c["host_transfer"] = bool(kw.value.value)
            elif kw.arg == "donate_argnums":
                c["donate_argnums"] = _int_tuple(kw.value)
        out[name.value] = c
    return out


def _str_tuple(node: ast.AST) -> Tuple[str, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                vals.append(el.value)
        return tuple(vals)
    return ()


def _int_tuple(node: ast.AST) -> Tuple[int, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, int):
                vals.append(el.value)
        return tuple(vals)
    return ()


def _is_jit_expr(node: ast.AST) -> bool:
    return dotted(node) in ("jax.jit", "jit")


def _partial_jit_call(node: ast.AST) -> Optional[ast.Call]:
    """The `functools.partial(jax.jit, ...)` form, or None."""
    if isinstance(node, ast.Call):
        d = dotted(node.func)
        if d is not None and d.rsplit(".", 1)[-1] == "partial" \
                and node.args and _is_jit_expr(node.args[0]):
            return node
    return None


def _tracked_name(call: ast.AST) -> Optional[str]:
    """`jit_registry.tracked("name")` → "name"."""
    if isinstance(call, ast.Call):
        d = dotted(call.func)
        if d is not None and d.rsplit(".", 1)[-1] == "tracked" \
                and call.args and isinstance(call.args[0], ast.Constant) \
                and isinstance(call.args[0].value, str):
            return call.args[0].value
    return None


def _static_args_of(deco: ast.AST) -> Tuple[Tuple[str, ...], bool]:
    """(static_argnames, uses_static_argnums) from a jit decorator."""
    call = _partial_jit_call(deco)
    if call is None and isinstance(deco, ast.Call) \
            and _is_jit_expr(deco.func):
        call = deco
    if call is None:
        return (), False
    names: Tuple[str, ...] = ()
    nums = False
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            names = _str_tuple(kw.value)
        elif kw.arg == "static_argnums":
            nums = True
    return names, nums


def _donation_of(deco: ast.AST) -> Tuple[bool, Tuple[int, ...]]:
    """(site donates at all, parseable donated argnums) from a jit
    decorator or call. donate_argnames (string form) counts as
    donation with no parseable nums — the authorization check still
    applies, the subset check degrades to 'contract must declare
    donation'."""
    call = _partial_jit_call(deco)
    if call is None and isinstance(deco, ast.Call) \
            and _is_jit_expr(deco.func):
        call = deco
    if call is None:
        return False, ()
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            return True, _int_tuple(kw.value)
        if kw.arg == "donate_argnames":
            return True, ()
    return False, ()


class _SiteVisitor(ast.NodeVisitor):
    """One file: jit defs/calls with qualnames, loop/tracked context."""

    def __init__(self, src: SourceFile, contracts: Dict[str, dict],
                 findings: List[Finding], bound_names: Dict[str, str]):
        self.src = src
        self.contracts = contracts
        self.findings = findings
        self.bound_names = bound_names  # callable name -> contract name
        self._stack: List[str] = []     # class/function qual parts
        self._fn_depth = 0
        self._loop_depth = 0
        self._factory_depth = 0         # inside a declared-factory def
        self._factory_contracts: List[dict] = []
        self._tracked_ctx: List[Optional[str]] = []

    # -- helpers ------------------------------------------------------

    def _qual(self, name: str = "") -> str:
        parts = self._stack + ([name] if name else [])
        return ".".join(parts)

    def _emit(self, code: str, qual: str, ident: str, msg: str,
              lineno: int) -> None:
        self.findings.append(Finding(
            PASS, code, self.src.relpath, qual, ident, msg, lineno))

    def _under_factory(self) -> bool:
        return self._factory_depth > 0

    def _contract_of_site(self, qual: str) -> Optional[dict]:
        site = f"{self.src.relpath}::{qual}"
        for c in self.contracts.values():
            if c["site"] == site:
                return c
        return None

    # -- structure ----------------------------------------------------

    def visit_ClassDef(self, node: ast.ClassDef):
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    def _visit_fn(self, node):
        qual = self._qual(node.name)
        self._check_def(node, qual)
        contract = self._contract_of_site(qual)
        is_factory = contract is not None and contract["kind"] == "factory"
        self._stack.append(node.name)
        self._fn_depth += 1
        self._factory_depth += 1 if is_factory else 0
        if is_factory:
            self._factory_contracts.append(contract)
        self.generic_visit(node)
        if is_factory:
            self._factory_contracts.pop()
        self._factory_depth -= 1 if is_factory else 0
        self._fn_depth -= 1
        self._stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def visit_For(self, node):
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    visit_While = visit_For
    visit_AsyncFor = visit_For

    # -- jit-decorated defs -------------------------------------------

    def _check_def(self, node, qual: str) -> None:
        jit_deco = None
        tracked = None
        for deco in node.decorator_list:
            if _is_jit_expr(deco) or _partial_jit_call(deco) is not None \
                    or (isinstance(deco, ast.Call)
                        and _is_jit_expr(deco.func)):
                jit_deco = deco
            name = _tracked_name(deco)
            if name is not None:
                tracked = name
        if jit_deco is None:
            return
        if self._loop_depth:
            self._emit(
                "jit-in-loop", qual, qual,
                "jit-decorated def inside a loop: a fresh traced "
                "function (and compile) per iteration", node.lineno)
        if tracked is None:
            if self._under_factory():
                return  # the factory's contract covers its inner jit
            self._emit(
                "unregistered-jit", qual, qual,
                f"jit entry point {qual!r} has no jit_registry.tracked "
                f"binding (declare a contract in {CENTRAL} and wrap "
                f"the jit with tracked(name))", node.lineno)
            return
        self._bind(tracked, node.name, qual, jit_deco, node.lineno)

    def _bind(self, name: str, callable_name: str, qual: str,
              jit_site: ast.AST, lineno: int) -> None:
        contract = self.contracts.get(name)
        if contract is None:
            self._emit(
                "unknown-jit-name", qual, name,
                f"tracked({name!r}) has no declared contract in "
                f"{CENTRAL}", lineno)
            return
        self.bound_names[callable_name] = name
        self._check_donation(jit_site, contract, name, qual, lineno)
        site_names, nums = _static_args_of(jit_site)
        if nums:
            self._emit(
                "static-argnums", qual, qual,
                "positional static_argnums are brittle under signature "
                "edits — use static_argnames", lineno)
        if tuple(site_names) != tuple(contract["static_argnames"]):
            self._emit(
                "static-args-mismatch", qual, name,
                f"site static_argnames {tuple(site_names)} != declared "
                f"{tuple(contract['static_argnames'])} for contract "
                f"{name!r}", lineno)

    # -- jax.jit(...) call expressions --------------------------------

    def visit_Call(self, node: ast.Call):
        tname = _tracked_name(node)
        if tname is None and isinstance(node.func, ast.Call):
            # the assignment form: tracked("name")(jax.jit(fn))
            tname = _tracked_name(node.func)
        if tname is not None:
            self._tracked_ctx.append(tname)
            self.generic_visit(node)
            self._tracked_ctx.pop()
            return
        if _is_jit_expr(node.func):
            self._check_jit_call(node)
        else:
            self._check_boundary_call(node)
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign):
        # bind `x = tracked("name")(jax.jit(fn))` targets so call sites
        # of x get the boundary checks
        if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Call) \
                and isinstance(node.value.func, ast.Call):
            name = _tracked_name(node.value.func)
            if name is not None and name in self.contracts:
                self.bound_names[node.targets[0].id] = name
        self.generic_visit(node)

    def _check_donation(self, jit_site: ast.AST, contract: Optional[dict],
                        cname: str, qual: str, lineno: int) -> None:
        donates, nums = _donation_of(jit_site)
        if not donates or contract is None:
            return
        declared = tuple(contract.get("donate_argnums") or ())
        if not declared or not set(nums) <= set(declared):
            self._emit(
                "undeclared-donation", qual, cname,
                f"jit site donates argnums {nums or '(dynamic)'} but "
                f"contract {cname!r} declares donate_argnums="
                f"{declared} — donation consumes the caller's buffers "
                f"and must be part of the declared surface (add "
                f"donate_argnums to the declare_jit in {CENTRAL})",
                lineno)

    def _check_jit_call(self, node: ast.Call) -> None:
        qual = self._qual()
        tracked = self._tracked_ctx[-1] if self._tracked_ctx else None
        # Donation authorization applies wherever the jit is built —
        # module level, factory body, or tracked assignment form.
        gov_name, gov = None, None
        if tracked is not None and tracked in self.contracts:
            gov_name, gov = tracked, self.contracts[tracked]
        elif self._factory_contracts:
            gov = self._factory_contracts[-1]
            gov_name = next((n for n, c in self.contracts.items()
                             if c is gov), "?")
        if gov is not None:
            self._check_donation(node, gov, gov_name, qual, node.lineno)
        if self._loop_depth:
            self._emit(
                "jit-in-loop", qual, qual or "module",
                "jax.jit(...) inside a loop: a fresh traced function "
                "(and compile) per iteration", node.lineno)
        if self._fn_depth == 0:
            # module level: fine if bound via tracked(...)
            if tracked is None:
                self._emit(
                    "unregistered-jit", qual, "module",
                    f"module-level jax.jit(...) without a "
                    f"jit_registry.tracked binding (declare it in "
                    f"{CENTRAL})", node.lineno)
            elif tracked not in self.contracts:
                self._emit(
                    "unknown-jit-name", qual, tracked,
                    f"tracked({tracked!r}) has no declared contract in "
                    f"{CENTRAL}", node.lineno)
            return
        if self._under_factory():
            return
        if tracked is not None and tracked in self.contracts \
                and self.contracts[tracked]["kind"] == "factory":
            return
        self._emit(
            "call-time-jit", qual, qual,
            "jax.jit(fn) constructed at call time: every invocation "
            "builds a fresh jit wrapper and retraces (cache the jit at "
            "module level, or declare the enclosing function as a "
            f"factory contract in {CENTRAL})", node.lineno)

    # -- call sites of bound jit callables ----------------------------

    def _check_boundary_call(self, node: ast.Call) -> None:
        d = dotted(node.func)
        if d is None:
            return
        cname = self.bound_names.get(d.rsplit(".", 1)[-1])
        if cname is None:
            return
        contract = self.contracts[cname]
        qual = self._qual()
        for kw in node.keywords:
            if kw.arg in contract["static_argnames"] and isinstance(
                    kw.value, (ast.List, ast.Dict, ast.Set)):
                self._emit(
                    "unhashable-static-arg", qual, f"{d}:{kw.arg}",
                    f"static arg {kw.arg!r} of {cname!r} is an "
                    f"unhashable {type(kw.value).__name__.lower()} "
                    f"literal (TypeError at trace time)", kw.value.lineno)
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if self._len_shaped(arg):
                self._emit(
                    "value-dependent-shape", qual, d,
                    f"argument of registered jit {cname!r} is built "
                    f"inline with a len()-derived shape — route it "
                    f"through the staging size classes / pow2 buckets "
                    f"so the compiled-program count stays bounded",
                    arg.lineno)

    @staticmethod
    def _len_shaped(arg: ast.AST) -> bool:
        if not (isinstance(arg, ast.Call) and dotted(arg.func)):
            return False
        terminal = dotted(arg.func).rsplit(".", 1)[-1]
        if terminal not in _CREATION_FNS:
            return False
        for sub in ast.walk(arg):
            if isinstance(sub, ast.Call) and dotted(sub.func) == "len":
                return True
        return False


class JitStabilityPass:
    name = PASS

    def run(self, project: Project) -> List[Finding]:
        contracts = declared_contracts(project.root)
        findings: List[Finding] = []
        # Pre-seed the callable-name → contract map from the declared
        # SITES so call-site checks (unhashable statics, len-shapes)
        # work regardless of file visit order; tracked bindings
        # discovered during the sweep extend it for fixture-local and
        # assignment-form jits (same-file call sites only, by design —
        # cross-file callables are expected to be contract sites).
        bound: Dict[str, str] = {}
        for name, c in contracts.items():
            qual = c["site"].split("::", 1)[-1]
            if qual:
                bound.setdefault(qual.rsplit(".", 1)[-1], name)
        for src in project.files:
            _SiteVisitor(src, contracts, findings, bound).visit(src.tree)
        return findings
