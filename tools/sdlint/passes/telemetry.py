"""Pass: telemetry — the PR 3 metric-namespace lint, folded into sdlint.

Semantics unchanged from the original `tools/telemetry_lint.py` (which
remains as a thin CLI shim over this module): every metric family must
be registered in `spacedrive_tpu/telemetry.py`, under a string-literal
name, collision-free, following `sd_<layer>_<what>` (layers now
include `sanitize`, the runtime sanitizer's counters). See the module
docstring of the shim for the rule-by-rule rationale.

Round 14 extends the same discipline to SPAN NAMES: a name passed to
`span()`/`device_span()` (tracing.py) is `<family>` or
`<family>/<variant>`, and the family must be declared via
`declare_span()` at the bottom of spacedrive_tpu/tracing.py (the hot-loop
`phase()` helper names its profiler annotations the same way) — the
observable-name contract metric families already have, applied to the
trace surface the flight recorder exports. Codes:

- ``span-undeclared`` — a literal (or constant f-string prefix) whose
  family is not declared centrally;
- ``span-dynamic``    — a name with no resolvable constant family
  (bare variable, f-string with no `family/` prefix): an unauditable
  span namespace;
- ``span-central``    — a `declare_span()` call outside tracing.py.

Round 15 extends it to the HEALTH ENGINE's read surface: the
saturation engine (spacedrive_tpu/health.py) may only read metric
families listed in its module-bottom `READS` table, and every listed
family must be centrally registered — so the observatory can never
silently depend on a family that was renamed or removed. Codes:

- ``health-read-undeclared`` — a READS key that is not registered in
  spacedrive_tpu/telemetry.py;
- ``health-read-unlisted``   — a `sd_*` string literal in health.py
  outside the READS table (and not one of its own emitted
  `sd_health_*` families).
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import List, Set, Tuple

from ..core import Finding, Project, dotted

PASS = "telemetry"

FACTORY_NAMES = {"counter", "gauge", "histogram"}
CLASS_NAMES = {"Counter", "Gauge", "Histogram"}
NAME_RE = re.compile(
    r"^sd_(jobs?|identifier|cas|validator|sync|p2p|store|api|event"
    r"|trace|sanitize|jit|task|timeout|chan|pipeline|stage|race|health"
    r"|sql|fleet|obs|chaos|backoff|incident|persist|wire)"
    r"_[a-z0-9_]+$")

CENTRAL_MODULE = "telemetry.py"

SPAN_FUNCS = {"span", "device_span", "phase"}
SPAN_CENTRAL = "spacedrive_tpu/tracing.py"

HEALTH_MODULE = "health.py"


def health_reads_from_tree(tree: ast.Module) -> dict:
    """READS keys (family → key lineno) plus the key-node id set, from
    a parsed health.py: the module-level ``READS`` dict literal
    (plain or annotated assignment)."""
    reads: dict = {}
    key_ids: Set[int] = set()
    for node in tree.body:
        tgt = val = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            tgt, val = node.targets[0].id, node.value
        elif isinstance(node, ast.AnnAssign) \
                and isinstance(node.target, ast.Name):
            tgt, val = node.target.id, node.value
        if tgt == "READS" and isinstance(val, ast.Dict):
            for k in val.keys:
                if isinstance(k, ast.Constant) and \
                        isinstance(k.value, str):
                    reads[k.value] = k.lineno
                    key_ids.add(id(k))
    return {"reads": reads, "key_ids": key_ids}


def health_reads(root: str) -> dict:
    """The READS table parsed from spacedrive_tpu/health.py (family →
    lineno) — the static half of the runtime parity test."""
    path = os.path.join(root, "spacedrive_tpu", HEALTH_MODULE)
    try:
        tree = ast.parse(open(path, encoding="utf-8").read())
    except (OSError, SyntaxError):
        return {}
    return health_reads_from_tree(tree)["reads"]


def health_problems(path: str, tree: ast.Module,
                    declared_families: Set[str]
                    ) -> List[Tuple[int, str, str, str]]:
    """The health-engine read-surface checks over a parsed health.py:
    (lineno, code, ident, msg) tuples."""
    parsed = health_reads_from_tree(tree)
    reads, key_ids = parsed["reads"], parsed["key_ids"]
    out: List[Tuple[int, str, str, str]] = []
    for fam, lineno in sorted(reads.items()):
        if fam not in declared_families:
            out.append((
                lineno, "health-read-undeclared", fam,
                f"health engine READS entry {fam!r} is not registered "
                "in spacedrive_tpu/telemetry.py"))
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and \
                isinstance(node.value, str) and \
                node.value.startswith("sd_") and id(node) not in key_ids:
            if node.value in reads or node.value.startswith("sd_health_"):
                continue
            out.append((
                node.lineno, "health-read-unlisted", node.value,
                f"sd_* literal {node.value!r} outside the READS table "
                "— every family the health engine reads must be "
                "listed there (spacedrive_tpu/health.py bottom)"))
    return out


def declared_span_families(root: str) -> Set[str]:
    """Family names from `declare_span("...")` calls in tracing.py."""
    path = os.path.join(root, SPAN_CENTRAL)
    out: Set[str] = set()
    try:
        tree = ast.parse(open(path, encoding="utf-8").read())
    except (OSError, SyntaxError):
        return out
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and node.args:
            f = node.func
            name = f.id if isinstance(f, ast.Name) else (
                f.attr if isinstance(f, ast.Attribute) else None)
            if name == "declare_span":
                arg = node.args[0]
                if isinstance(arg, ast.Constant) and \
                        isinstance(arg.value, str):
                    out.add(arg.value)
    return out


def _span_imports(tree: ast.Module) -> Tuple[dict, Set[str]]:
    """(function aliases, module aliases) for the tracing span
    surface: `from ..tracing import span as trace_span` binds a
    FUNCTION alias; `import spacedrive_tpu.tracing as tr` (or
    `from spacedrive_tpu import tracing`) binds a MODULE alias whose
    `.span(...)` calls must be checked too — the aliased-module
    spelling was the review-round bypass."""
    funcs: dict = {}
    modules: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if node.module and node.module.split(".")[-1] == "tracing":
                for alias in node.names:
                    if alias.name in SPAN_FUNCS | {"declare_span"}:
                        funcs[alias.asname or alias.name] = alias.name
            # `from spacedrive_tpu import tracing [as tr]` AND the
            # pure-relative `from .. import tracing [as tr]` (where
            # node.module is None) both bind a module alias.
            for alias in node.names:
                if alias.name == "tracing":
                    modules.add(alias.asname or alias.name)
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.split(".")[-1] == "tracing":
                    modules.add(alias.asname or alias.name)
    return funcs, modules


def _call_target(node: ast.Call) -> Tuple[str, str]:
    """(base, attr) of the called thing: ("", "counter") for a bare
    name, ("telemetry", "counter") for an attribute call."""
    f = node.func
    if isinstance(f, ast.Name):
        return "", f.id
    if isinstance(f, ast.Attribute):
        base = f.value.id if isinstance(f.value, ast.Name) else "?"
        return base, f.attr
    return "?", "?"


def _telemetry_imports(tree: ast.Module) -> set:
    """Factory/class names this module imported FROM the telemetry
    module — a bare `counter(...)` call is only a registration if the
    name actually came from there (crypto code has an unrelated local
    `counter()` closure, for instance)."""
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and \
                node.module.split(".")[-1] == "telemetry":
            for alias in node.names:
                if alias.name in FACTORY_NAMES | CLASS_NAMES:
                    names.add(alias.asname or alias.name)
    return names


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: str, is_central: bool, from_telemetry: set,
                 names_seen: dict, problems: List[str],
                 span_aliases: dict = None, span_families: Set[str] = None,
                 is_span_central: bool = False,
                 span_problems: List[Tuple[int, str, str, str]] = None,
                 span_modules: Set[str] = None):
        self.path = path
        self.is_central = is_central
        self.from_telemetry = from_telemetry
        self.names_seen = names_seen
        self.problems = problems
        self.span_aliases = span_aliases or {}
        self.span_modules = span_modules or set()
        self.span_families = span_families if span_families is not None \
            else set()
        self.is_span_central = is_span_central
        self.span_problems = span_problems if span_problems is not None \
            else []
        self.depth = 0  # function nesting (0 = module level)

    def visit_FunctionDef(self, node):
        self.depth += 1
        self.generic_visit(node)
        self.depth -= 1

    visit_AsyncFunctionDef = visit_FunctionDef

    # -- span-name discipline ----------------------------------------------

    def _span_call_target(self, node: ast.Call):
        """The original tracing function name this call binds to, or
        None when it is not a span-surface call. Covers bare/renamed
        function imports AND every module spelling — `tracing.span`,
        `tr.span` (aliased import), `spacedrive_tpu.tracing.span`
        (fully dotted)."""
        base, attr = _call_target(node)
        if base == "" and attr in self.span_aliases:
            return self.span_aliases[attr]
        if attr in SPAN_FUNCS | {"declare_span"}:
            d = dotted(node.func)
            if d is not None and "." in d:
                mod = d.rsplit(".", 1)[0]
                if mod == "tracing" or mod.endswith(".tracing") \
                        or mod in self.span_modules:
                    return attr
        return None

    def _check_span_call(self, node: ast.Call) -> None:
        target = self._span_call_target(node)
        if target is None or self.is_span_central:
            return
        if target == "declare_span":
            self.span_problems.append((
                node.lineno, "span-central",
                "declare_span",
                "span family declared outside the central registry "
                "(declare it in spacedrive_tpu/tracing.py)"))
            return
        if not node.args:
            self.span_problems.append((
                node.lineno, "span-dynamic", target,
                f"{target}() without a positional name literal — span "
                "names must start with a declared family"))
            return
        name_node = node.args[0]
        if isinstance(name_node, ast.Constant) and \
                isinstance(name_node.value, str):
            family = name_node.value.split("/", 1)[0]
            if family not in self.span_families:
                self.span_problems.append((
                    node.lineno, "span-undeclared", name_node.value,
                    f"span family {family!r} is not declared via "
                    "declare_span() in spacedrive_tpu/tracing.py"))
            return
        if isinstance(name_node, ast.JoinedStr):
            first = name_node.values[0] if name_node.values else None
            if isinstance(first, ast.Constant) and \
                    isinstance(first.value, str) and "/" in first.value:
                family = first.value.split("/", 1)[0]
                if family not in self.span_families:
                    self.span_problems.append((
                        node.lineno, "span-undeclared",
                        f"{family}/<dynamic>",
                        f"span family {family!r} is not declared via "
                        "declare_span() in spacedrive_tpu/tracing.py"))
                return
            self.span_problems.append((
                node.lineno, "span-dynamic", target,
                "f-string span name with no constant `family/` prefix "
                "— the variant may be dynamic, the family may not"))
            return
        self.span_problems.append((
            node.lineno, "span-dynamic", target,
            "non-literal span name — span names must be `family` or "
            "`family/<variant>` with a declared, greppable family"))

    def visit_Call(self, node: ast.Call):
        self.generic_visit(node)
        self._check_span_call(node)
        base, attr = _call_target(node)
        qualified = base in ("telemetry", "REGISTRY")
        is_factory = attr in FACTORY_NAMES and (
            qualified or (base == "" and (
                attr in self.from_telemetry or self.is_central)))
        is_class = attr in CLASS_NAMES and (
            base == "telemetry"
            or (base == "" and attr in self.from_telemetry))
        if not (is_factory or is_class):
            return
        where = f"{self.path}:{node.lineno}"
        if not self.is_central:
            kind = "instantiated" if is_class else "registered"
            self.problems.append(
                f"{where}: metric family {kind} outside the central "
                f"registry (define it in spacedrive_tpu/telemetry.py "
                f"and import it)")
            return
        if self.depth > 0:
            return  # telemetry.py plumbing (wrapper/registry bodies)
        if not node.args:
            return
        name_node = node.args[0]
        if not (isinstance(name_node, ast.Constant)
                and isinstance(name_node.value, str)):
            self.problems.append(
                f"{where}: metric name must be a string literal "
                f"(static namespace)")
            return
        name = name_node.value
        if name in self.names_seen:
            self.problems.append(
                f"{where}: metric name collision: {name!r} already "
                f"registered at {self.names_seen[name]}")
        else:
            self.names_seen[name] = where
        if not NAME_RE.match(name):
            self.problems.append(
                f"{where}: {name!r} breaks the naming scheme "
                f"sd_<layer>_<what> (layers: jobs/identifier/cas/"
                f"validator/sync/p2p/store/api/event/trace/sanitize/jit/"
                f"task/timeout/chan/"
                f"pipeline/stage/race/health/sql/fleet/obs/chaos/"
                f"backoff/incident/persist/wire)")


def lint_source(path: str, src: str, is_central: bool,
                names_seen: dict, problems: List[str],
                span_families: Set[str] = None,
                is_span_central: bool = False,
                span_problems: List[Tuple[int, str, str, str]] = None
                ) -> None:
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        problems.append(f"{path}: unparseable: {e}")
        return
    span_aliases, span_modules = _span_imports(tree)
    _Visitor(path, is_central, _telemetry_imports(tree),
             names_seen, problems,
             span_aliases=span_aliases,
             span_modules=span_modules,
             span_families=span_families,
             is_span_central=is_span_central,
             span_problems=span_problems).visit(tree)


def run_lint(package_dir: str) -> List[str]:
    """Lint every .py under package_dir; returns problem strings.
    (The telemetry_lint.py shim's public API — kept verbatim; span
    problems land in the same string list.)"""
    problems: List[str] = []
    names_seen: dict = {}
    span_families = declared_span_families(os.path.dirname(
        os.path.abspath(package_dir)))
    # Central module first so cross-file collisions blame the outlier.
    paths: List[str] = []
    for root, _dirs, files in os.walk(package_dir):
        if "__pycache__" in root:
            continue
        for fn in sorted(files):
            if fn.endswith(".py"):
                paths.append(os.path.join(root, fn))
    paths.sort(key=lambda p: (os.path.basename(p) != CENTRAL_MODULE, p))
    for path in paths:
        with open(path, encoding="utf-8") as f:
            src = f.read()
        span_problems: List[Tuple[int, str, str, str]] = []
        lint_source(path, src,
                    is_central=os.path.basename(path) == CENTRAL_MODULE,
                    names_seen=names_seen, problems=problems,
                    span_families=span_families,
                    is_span_central=path.replace(os.sep, "/").endswith(
                        SPAN_CENTRAL),
                    span_problems=span_problems)
        for lineno, _code, _ident, msg in span_problems:
            problems.append(f"{path}:{lineno}: {msg}")
    # Health-engine read surface (needs the full declared-name set,
    # so it runs after the walk).
    for path in paths:
        if os.path.basename(path) != HEALTH_MODULE:
            continue
        try:
            tree = ast.parse(open(path, encoding="utf-8").read())
        except (OSError, SyntaxError):
            continue
        for lineno, _code, _ident, msg in health_problems(
                path, tree, set(names_seen)):
            problems.append(f"{path}:{lineno}: {msg}")
    return problems


def main(argv: List[str]) -> int:
    pkg = argv[1] if len(argv) > 1 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "..", "spacedrive_tpu")
    pkg = os.path.normpath(pkg)
    problems = run_lint(pkg)
    for p in problems:
        print(p, file=sys.stderr)
    if problems:
        print(f"telemetry lint: {len(problems)} problem(s)",
              file=sys.stderr)
        return 1
    print("telemetry lint: clean")
    return 0


_LINE_RE = re.compile(r"^(?P<path>.*?):(?P<line>\d+): (?P<msg>.*)$")


class TelemetryPass:
    name = PASS

    def run(self, project: Project) -> List[Finding]:
        problems: List[str] = []
        names_seen: dict = {}
        span_families = declared_span_families(project.root)
        files = sorted(
            project.files,
            key=lambda f: (os.path.basename(f.relpath) != CENTRAL_MODULE,
                           f.relpath))
        findings: List[Finding] = []
        for src in files:
            span_problems: List[Tuple[int, str, str, str]] = []
            lint_source(
                src.relpath, src.src,
                is_central=os.path.basename(src.relpath) == CENTRAL_MODULE,
                names_seen=names_seen, problems=problems,
                span_families=span_families,
                is_span_central=src.relpath == SPAN_CENTRAL,
                span_problems=span_problems)
            for lineno, code, ident, msg in span_problems:
                findings.append(Finding(
                    PASS, code, src.relpath, "", ident, msg, lineno))
        for src in files:
            if os.path.basename(src.relpath) != HEALTH_MODULE:
                continue
            for lineno, code, ident, msg in health_problems(
                    src.relpath, src.tree, set(names_seen)):
                findings.append(Finding(
                    PASS, code, src.relpath, "", ident, msg, lineno))
        for prob in problems:
            m = _LINE_RE.match(prob)
            if m:
                findings.append(Finding(
                    PASS, "namespace", m.group("path"), "",
                    m.group("msg")[:80], m.group("msg"),
                    int(m.group("line"))))
            else:
                findings.append(Finding(
                    PASS, "namespace", prob.split(":", 1)[0], "",
                    prob[:80], prob, 0))
        return findings
