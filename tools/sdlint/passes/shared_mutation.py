"""Pass: shared-mutation — every mutable attribute of a multi-context
class obeys its declared ownership contract.

Two review rounds were once spent hand-fixing exactly this bug class:
a pipeline stats object's plain `+=` from two device streams lost
updates, and the stage-pool gauge clobbered across a concurrent pool
swap. The
contract table lives in `spacedrive_tpu/threadctx.py` (one
`declare_owner(...)` per class, one kind per mutable attribute); this
pass derives thread contexts from the call graph (`_threads.py`:
event loop, per-submission worker roots, atexit) and checks every
attribute-mutation site against the table — the lockset half reuses
the PR 4 lock-discipline lexical machinery.

Codes:

- ``unguarded-write``     — a post-init write to a `guarded_by(L)`
  attribute outside a lexical `with <L>:` block (the encoded
  `stats.h2d_bytes` `+=` shape).
- ``wrong-context-write`` — a `loop_only` attribute written from a
  function reachable from a worker/atexit context.
- ``multi-thread-write``  — a `single_thread` attribute whose mutation
  sites span two or more distinct thread contexts.
- ``non-atomic-write``    — an `atomic_counter` attribute mutated by
  anything other than an augmented numeric update (the declaration
  waives bare `+=` statistics, nothing stronger).
- ``post-init-write``     — an `immutable_after_init` attribute
  written outside `__init__`/`__post_init__`.
- ``undeclared-attr``     — a post-init mutation of an attribute the
  class's contract does not name (contracts must stay complete, or
  they rot).
- ``undeclared-class``    — attribute mutations of an UNregistered
  class spanning two or more thread contexts: declare it in
  threadctx.py (or serialize it onto one context).

The runtime twin (`threadctx.arm`, installed with the sanitizer)
covers the dynamic-dispatch half: armed classes record (thread id,
held lockset) per write and raise `data_race` in tier-1.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Set, Tuple

from ..core import Finding, Project
from ._threads import (
    MutationSite,
    class_hierarchy,
    collect_mutations,
    declared_owners,
    effective_owner,
    owners_by_class,
    thread_contexts,
)

PASS = "shared-mutation"


def _class_def_lines(project: Project) -> Dict[Tuple[str, str], int]:
    out: Dict[Tuple[str, str], int] = {}
    for src in project.files:
        for node in ast.walk(src.tree):
            if isinstance(node, ast.ClassDef):
                out[(src.relpath, node.name)] = node.lineno
    return out


class SharedMutationPass:
    name = PASS

    def run(self, project: Project) -> List[Finding]:
        declared = declared_owners(project.root, project)
        by_class = owners_by_class(declared)
        hierarchy = class_hierarchy(project)
        contexts = thread_contexts(project)
        known = set(by_class)
        sites = collect_mutations(project, known)
        # Contract lookup follows inheritance (Gauge under Counter),
        # memoized per class name.
        owner_of: Dict[str, object] = {}

        def owner(cls_name: str):
            if cls_name not in owner_of:
                owner_of[cls_name] = effective_owner(
                    cls_name, by_class, hierarchy)
            return owner_of[cls_name]
        def_lines = _class_def_lines(project)
        findings: List[Finding] = []
        seen: Set[str] = set()

        def emit(f: Finding) -> None:
            if f.key() not in seen:
                seen.add(f.key())
                findings.append(f)

        def ctx_of(site: MutationSite) -> Set[str]:
            return contexts.get(
                f"{site.fn.src.relpath}::{site.fn.qual}", set())

        # -- registered classes: contract enforcement ----------------------
        by_attr: Dict[Tuple[str, str], List[MutationSite]] = {}
        for s in sites:
            if owner(s.cls_name) is not None and not s.in_init \
                    and not s.attr.startswith("_sdtpu"):
                by_attr.setdefault((s.cls_name, s.attr), []).append(s)

        for (cls_name, attr), group in sorted(by_attr.items()):
            spec = owner(cls_name)
            contract = spec["attrs"].get(attr)
            first = min(group, key=lambda s: (s.fn.src.relpath,
                                              s.lineno))
            if contract is None:
                emit(Finding(
                    PASS, "undeclared-attr", first.fn.src.relpath,
                    first.fn.qual, f"{cls_name}.{attr}",
                    f"`{cls_name}.{attr}` is mutated outside __init__ "
                    f"but the owner contract {spec['name']!r} declares "
                    "no kind for it — add loop_only / single_thread / "
                    "guarded_by / atomic_counter / "
                    "immutable_after_init in threadctx.py",
                    first.lineno))
                continue
            kind, lock = contract
            if kind == "guarded_by":
                # Lexical lock identity is the terminal attr name (the
                # lock-discipline convention): guarded_by supports a
                # dotted runtime path ("db._write_lock").
                lock_term = (lock or "").split(".")[-1]
                for s in group:
                    if lock_term not in s.locks:
                        emit(Finding(
                            PASS, "unguarded-write", s.fn.src.relpath,
                            s.fn.qual, f"{cls_name}.{attr}",
                            f"`{cls_name}.{attr}` is declared "
                            f"guarded_by({lock!r}) but this "
                            + ("augmented update"
                               if s.aug else "write")
                            + f" holds {sorted(s.locks) or 'no lock'}"
                            " — a concurrent writer loses updates "
                            "(the lost-update stats shape)",
                            s.lineno))
            elif kind == "loop_only":
                for s in group:
                    bad = {c for c in ctx_of(s) if c != "loop"}
                    if bad:
                        emit(Finding(
                            PASS, "wrong-context-write",
                            s.fn.src.relpath, s.fn.qual,
                            f"{cls_name}.{attr}",
                            f"`{cls_name}.{attr}` is declared "
                            f"loop_only but `{s.fn.qual}` is reachable "
                            f"from {sorted(bad)} — post through "
                            "threadctx.call_threadsafe or re-declare",
                            s.lineno))
            elif kind == "single_thread":
                labels: Set[str] = set()
                for s in group:
                    labels |= ctx_of(s)
                if len(labels) >= 2:
                    emit(Finding(
                        PASS, "multi-thread-write",
                        first.fn.src.relpath, first.fn.qual,
                        f"{cls_name}.{attr}",
                        f"`{cls_name}.{attr}` is declared "
                        f"single_thread but its writers span contexts "
                        f"{sorted(labels)} — guard it or serialize "
                        "the writers",
                        first.lineno))
            elif kind == "atomic_counter":
                for s in group:
                    if not s.aug or s.container:
                        emit(Finding(
                            PASS, "non-atomic-write", s.fn.src.relpath,
                            s.fn.qual, f"{cls_name}.{attr}",
                            f"`{cls_name}.{attr}` is declared "
                            "atomic_counter: only bare augmented "
                            "numeric updates are waived — this "
                            + ("container mutation" if s.container
                               else "rebind")
                            + " needs a real contract",
                            s.lineno))
            elif kind == "immutable_after_init":
                for s in group:
                    emit(Finding(
                        PASS, "post-init-write", s.fn.src.relpath,
                        s.fn.qual, f"{cls_name}.{attr}",
                        f"`{cls_name}.{attr}` is declared "
                        "immutable_after_init but is written outside "
                        "__init__",
                        s.lineno))

        # -- unregistered classes: multi-context detection ------------------
        grouped: Dict[Tuple[str, str], List[MutationSite]] = {}
        for s in sites:
            if owner(s.cls_name) is not None or not s.self_recv \
                    or s.in_init:
                continue
            grouped.setdefault(
                (s.fn.src.relpath, s.cls_name), []).append(s)
        for (relpath, cls_name), group in sorted(grouped.items()):
            labels = set()
            for s in group:
                labels |= ctx_of(s)
            if len(labels) < 2:
                continue
            attrs = sorted({s.attr for s in group})
            emit(Finding(
                PASS, "undeclared-class", relpath, "", cls_name,
                f"class `{cls_name}` mutates {attrs} from contexts "
                f"{sorted(labels)} without an ownership contract — "
                "declare it in spacedrive_tpu/threadctx.py "
                "(declare_owner) so the race recorder can arm it",
                def_lines.get((relpath, cls_name),
                              group[0].lineno)))
        return findings
