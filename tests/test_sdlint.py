"""sdlint framework: per-pass fixtures, the tree gate, baseline policy.

This is the tier-1 hook that replaced the direct telemetry_lint run:
`test_tree_clean_within_baseline` runs ALL twenty passes (five
concurrency/invariant + the round-10 device trio + the round-11
lifecycle trio + the round-12 resource trio + the round-13
thread-safety trio + the round-16 store trio: sql-discipline,
tx-shape, schema-parity) over the repo and fails on any finding not
in tools/sdlint/baseline.json (which may only shrink — budget
enforced here too). The per-pass tests pin each pass to a known-positive /
known-negative fixture pair under tests/fixtures/sdlint/, including
the encoded PR 1 store/db.py reader-registration deadlock shape
(locks_bad.Pr1Database), the encoded call-time-jit shape
(jit_bad.call_time), the encoded watcher.py:375 dropped-task shape
(lifecycle_bad.old_loop_spawn), and the encoded stats lost-update shape
(race_bad._transfer's bare `+=`).
"""

import os

from tools.sdlint import Baseline, load_project, run_passes
from tools.sdlint.baseline import DEFAULT_PATH
from tools.sdlint.passes import PASSES, get_passes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tests", "fixtures", "sdlint")


def _lint_fixture(filename, pass_name):
    project = load_project(ROOT, [os.path.join(FIXTURES, filename)])
    return run_passes(project, get_passes([pass_name]))


# -- blocking-async ---------------------------------------------------------

def test_blocking_async_flags_known_positives():
    found = _lint_fixture("blocking_bad.py", "blocking-async")
    idents = {f.ident for f in found}
    quals = {f.qual for f in found}
    assert "direct:db.query" in idents              # sqlite on the loop
    assert "direct:time.sleep" in idents
    assert any(i.startswith("via:helper:") for i in idents), idents
    assert "passes_db_handle" in quals              # report.update(lib.db)


def test_blocking_async_passes_known_negatives():
    assert _lint_fixture("blocking_ok.py", "blocking-async") == []


# -- lock-discipline --------------------------------------------------------

def test_lock_discipline_catches_pr1_deadlock_shape():
    """The encoded PR 1 regression: fut.result() while holding
    _write_lock, with registration serialized on the same lock."""
    found = _lint_fixture("locks_bad.py", "lock-discipline")
    waits = [f for f in found if f.code == "wait-under-lock"]
    assert any(f.qual == "Pr1Database.commit_group"
               and "_write_lock" in f.ident for f in waits), found


def test_lock_discipline_other_positives():
    found = _lint_fixture("locks_bad.py", "lock-discipline")
    codes = {f.code for f in found}
    assert "await-under-lock" in codes
    assert "nested-write-tx" in codes
    cycles = [f for f in found if f.code == "lock-order-cycle"]
    assert any("a_lock" in f.ident and "b_lock" in f.ident
               for f in cycles), found


def test_lock_discipline_passes_known_negatives():
    assert _lint_fixture("locks_ok.py", "lock-discipline") == []


# -- crdt-parity ------------------------------------------------------------

def test_crdt_parity_flags_silent_shared_writes():
    found = _lint_fixture("crdt_bad.py", "crdt-parity")
    idents = {f.ident for f in found}
    assert idents == {"tag", "object"}, found


def test_crdt_parity_passes_known_negatives():
    assert _lint_fixture("crdt_ok.py", "crdt-parity") == []


# -- flag-registry ----------------------------------------------------------

def test_flag_registry_flags_known_positives():
    found = _lint_fixture("flags_bad.py", "flag-registry")
    by_code = {}
    for f in found:
        by_code.setdefault(f.code, set()).add(f.ident)
    assert "SDTPU_NOT_A_REAL_FLAG" in by_code.get("undeclared-flag", set())
    assert "SDTPU_TELEMETRY" in by_code.get("environ-read", set())
    assert "SDTPU_PROFILE" in by_code.get("environ-read", set())


def test_flag_registry_passes_known_negatives():
    assert _lint_fixture("flags_ok.py", "flag-registry") == []


# -- telemetry (the folded-in PR 3 lint) ------------------------------------

def test_telemetry_pass_flags_rogue_registration():
    found = _lint_fixture("telemetry_bad.py", "telemetry")
    assert any("outside the central" in f.message for f in found), found


def test_telemetry_pass_passes_known_negatives():
    assert _lint_fixture("telemetry_ok.py", "telemetry") == []


def test_telemetry_lint_shim_api_intact():
    """tools/telemetry_lint.py keeps its pre-sdlint public surface."""
    from tools import telemetry_lint

    assert callable(telemetry_lint.run_lint)
    assert callable(telemetry_lint.lint_source)
    assert telemetry_lint.NAME_RE.match("sd_sanitize_violations_total")


def test_span_name_discipline_flags_known_positives():
    """The round-14 span-name half of the telemetry pass: undeclared
    families (literal and f-string variant), fully-dynamic names, and
    a declare_span outside tracing.py."""
    found = _lint_fixture("spans_bad.py", "telemetry")
    by_code = {}
    for f in found:
        by_code.setdefault(f.code, set()).add(f.ident)
    assert "totally.rogue.family" in by_code.get("span-undeclared", set())
    assert "rogue_family/<dynamic>" in by_code.get("span-undeclared",
                                                   set())
    # the aliased-module, fully-dotted, and relative-import-aliased
    # spellings must not bypass the family check
    assert "rogue.via.alias" in by_code.get("span-undeclared", set())
    assert "rogue.via.dotted" in by_code.get("span-undeclared", set())
    assert "rogue.via.relative" in by_code.get("span-undeclared", set())
    assert {"span", "device_span"} <= by_code.get("span-dynamic", set())
    assert "declare_span" in by_code.get("span-central", set())


def test_span_name_discipline_covers_the_phase_helper():
    """tracing.phase() names profiler annotations from the same declared
    families: an undeclared family and a non-literal name are findings."""
    found = _lint_fixture("spans_bad.py", "telemetry")
    assert any(f.code == "span-undeclared" and f.ident == "rogue.phase/pack"
               for f in found), found
    assert any(f.code == "span-dynamic" and f.ident == "phase"
               for f in found), found


def test_span_name_discipline_passes_known_negatives():
    """Declared families through every import spelling — including a
    dynamic VARIANT under a declared family, and a local function that
    happens to be named span — are clean."""
    assert _lint_fixture("spans_ok.py", "telemetry") == []


def test_span_families_declared_for_every_tree_literal():
    """Static↔runtime parity for the span registry: the AST-parsed
    declaration set matches tracing.SPAN_FAMILIES, and the whole-tree
    telemetry pass reports zero span-* findings (every span literal in
    the tree resolves to a declared family)."""
    from spacedrive_tpu import tracing
    from tools.sdlint.passes.telemetry import declared_span_families

    static = declared_span_families(ROOT)
    assert static == set(tracing.SPAN_FAMILIES)
    project = load_project(ROOT)
    found = run_passes(project, get_passes(["telemetry"]))
    span_findings = [f for f in found if f.code.startswith("span-")]
    assert span_findings == [], [f.text() for f in span_findings]


# -- jit-stability (round 10: the device-contract pass) ---------------------

def test_jit_stability_flags_known_positives():
    found = _lint_fixture("jit_bad.py", "jit-stability")
    codes = {f.code for f in found}
    assert codes == {
        "unregistered-jit", "unknown-jit-name", "static-args-mismatch",
        "static-argnums", "call-time-jit", "jit-in-loop",
        "unhashable-static-arg", "value-dependent-shape",
        "undeclared-donation"}, codes
    # the encoded call-time shape is the canonical positive
    assert any(f.code == "call-time-jit" and f.qual == "call_time"
               for f in found)
    assert any(f.code == "undeclared-donation"
               and f.qual == "donates_undeclared" for f in found)


def test_jit_stability_passes_known_negatives():
    assert _lint_fixture("jit_ok.py", "jit-stability") == []


def test_every_registry_contract_site_exists():
    """Contracts must point at real code: each declared site's file and
    qualname resolve in the tree (a renamed function must rename its
    contract, or the factory/association rules silently stop applying)."""
    from tools.sdlint.passes.jit_stability import declared_contracts

    project = load_project(ROOT)
    quals = {f"{f.src.relpath}::{f.qual}"
             for f in project.index.funcs}
    classes = set()
    for src in project.files:
        import ast as _ast
        for node in _ast.walk(src.tree):
            if isinstance(node, _ast.ClassDef):
                classes.add(f"{src.relpath}::{node.name}")
    for name, c in declared_contracts(ROOT).items():
        assert c["site"] in quals | classes, (
            f"contract {name!r} points at missing site {c['site']!r}")


def test_jit_registry_static_runtime_drift():
    """The AST-parsed contract table and the runtime registry cannot
    drift (the channel/timeout drift check, for jit): every statically
    visible contract resolves at runtime with the SAME static_argnames
    and donate_argnums — the two fields whose drift silently changes
    call semantics (a retrace per call, or a consumed caller buffer)."""
    from spacedrive_tpu.ops import jit_registry
    from tools.sdlint.passes.jit_stability import declared_contracts

    static = declared_contracts(ROOT)
    assert set(static) == set(jit_registry.CONTRACTS)
    donated = set()
    for name, c in static.items():
        runtime = jit_registry.CONTRACTS[name]
        assert tuple(c["static_argnames"]) == runtime.static_argnames, name
        assert tuple(c["donate_argnums"]) == runtime.donate_argnums, name
        if runtime.donate_argnums:
            donated.add(name)
    # the donated CAS dispatch's contract is declared on both sides
    assert "blake3.donated" in donated


# -- dtype-discipline -------------------------------------------------------

def test_dtype_discipline_flags_known_positives():
    found = _lint_fixture("dtype_bad.py", "dtype-discipline")
    codes = {f.code for f in found}
    assert codes == {"implicit-dtype", "builtin-dtype-cast",
                     "mixed-sign-arith"}, codes
    mixed = {f.qual for f in found if f.code == "mixed-sign-arith"}
    assert "mixed_direct" in mixed
    # the interprocedural half: the uint32 arrives via a helper's return
    assert "mixed_via_helper" in mixed


def test_dtype_discipline_passes_known_negatives():
    assert _lint_fixture("dtype_ok.py", "dtype-discipline") == []


# -- host-transfer ----------------------------------------------------------

def test_host_transfer_flags_known_positives():
    found = _lint_fixture("transfer_bad.py", "host-transfer")
    codes = {f.code for f in found}
    assert codes == {"undeclared-transfer", "implicit-host-bool",
                     "implicit-host-cast", "undeclared-io"}, codes
    idioms = {f.ident for f in found if f.code == "undeclared-transfer"}
    assert any(i.startswith("np.asarray") for i in idioms)
    assert any(i.startswith(".item()") for i in idioms)
    assert any(i.startswith("block_until_ready") for i in idioms)
    assert any(i.startswith("device_get") for i in idioms)


def test_host_transfer_passes_known_negatives():
    """Declared io scopes, jit-input prep, to_thread offload, and
    jit-free host code are all sanctioned."""
    assert _lint_fixture("transfer_ok.py", "host-transfer") == []


# -- task-lifecycle (round 11: the lifecycle trio) --------------------------

def test_task_lifecycle_flags_known_positives():
    found = _lint_fixture("lifecycle_bad.py", "task-lifecycle")
    codes = {f.code for f in found}
    assert codes == {"dropped-task", "deprecated-get-event-loop",
                     "spawn-in-loop"}, codes
    # the watcher.py:375 shape: a dynamic-receiver chain whose result
    # is discarded — both the deprecated loop AND the dropped task
    quals = {f.qual for f in found if f.code == "dropped-task"}
    assert {"fire_and_forget", "old_loop_spawn"} <= quals, found
    assert any(f.qual == "old_loop_spawn"
               and f.code == "deprecated-get-event-loop" for f in found)


def test_task_lifecycle_passes_known_negatives():
    assert _lint_fixture("lifecycle_ok.py", "task-lifecycle") == []


# -- cancellation-safety -----------------------------------------------------

def test_cancellation_safety_flags_known_positives():
    found = _lint_fixture("cancel_bad.py", "cancellation-safety")
    codes = {f.code for f in found}
    assert codes == {"swallow-cancel", "await-in-finally",
                     "no-cancel-point",
                     "dropped-exception-callback"}, codes
    swallow = {f.qual for f in found if f.code == "swallow-cancel"}
    # the pre-PR mdns/discovery stop() conflation is pinned
    assert {"swallow_bare", "swallow_base", "conflated_reap"} <= swallow
    cb = [f for f in found if f.code == "dropped-exception-callback"]
    assert len(cb) == 2, cb  # container method + task-ignoring lambda


def test_cancellation_safety_passes_known_negatives():
    assert _lint_fixture("cancel_ok.py", "cancellation-safety") == []


# -- timeout-discipline ------------------------------------------------------

def test_timeout_discipline_flags_known_positives():
    found = _lint_fixture("timeout_bad.py", "timeout-discipline")
    codes = {f.code for f in found}
    assert codes == {"no-timeout", "unnamed-timeout",
                     "undeclared-timeout", "dynamic-timeout-name"}, codes
    roots = {f.ident for f in found if f.code == "no-timeout"}
    assert "tunnel.recv" in roots and "tunnel.send" in roots
    assert "reader.readexactly" in roots


def test_timeout_discipline_passes_known_negatives():
    """with_timeout on declared names, deadline blocks, non-net
    awaits, and the ws async-for exemption are all sanctioned."""
    assert _lint_fixture("timeout_ok.py", "timeout-discipline") == []


def test_timeout_fixture_names_are_really_declared():
    """The OK fixture leans on real registry names — a renamed budget
    must rename the fixture (and every call site) with it."""
    from tools.sdlint.passes.timeout_discipline import declared_timeouts

    declared = declared_timeouts(ROOT)
    for name in ("p2p.header_recv", "p2p.frame_send", "p2p.handshake"):
        assert name in declared, name


def test_every_with_timeout_site_name_resolves_at_runtime():
    """The static table and the runtime registry cannot drift: every
    name the AST parser sees must resolve through timeouts.budget()."""
    from spacedrive_tpu import timeouts
    from tools.sdlint.passes.timeout_discipline import declared_timeouts

    static = declared_timeouts(ROOT)
    assert set(static) == set(timeouts.TIMEOUTS)
    for name in static:
        assert timeouts.budget(name) > 0


# -- queue-discipline (round 12: the resource trio) -------------------------

def test_queue_discipline_flags_known_positives():
    found = _lint_fixture("queue_bad.py", "queue-discipline")
    codes = {f.code for f in found}
    assert codes == {"bare-queue", "unbounded-deque-channel",
                     "unregistered-put", "unregistered-send-buffer",
                     "undeclared-channel", "dynamic-channel-name"}, codes
    # the pre-registry jobs run-queue shape: an unbounded deque the
    # class both appends to and pops from
    assert any(f.code == "unbounded-deque-channel"
               and f.ident == "self.backlog" for f in found)
    # put_nowait on a bare self-attr queue AND on a local bare queue
    puts = {f.ident for f in found if f.code == "unregistered-put"}
    assert {"self.inbox.put_nowait", "q.put_nowait"} <= puts, puts


def test_queue_discipline_passes_known_negatives():
    assert _lint_fixture("queue_ok.py", "queue-discipline") == []


# -- backpressure ------------------------------------------------------------

def test_backpressure_flags_known_positives():
    found = _lint_fixture("backpressure_bad.py", "backpressure")
    codes = {f.code for f in found}
    assert codes == {"nowait-on-block", "unbounded-fanout",
                     "burst-without-drain"}, codes
    assert any(f.ident == "self.requests.put_nowait" for f in found)
    assert any(f.ident == "tunnel.send_nowait" for f in found)


def test_backpressure_passes_known_negatives():
    """Budgeted block puts, shed-policy nowait puts, windowed bursts
    with a drain point, and call-only fan-outs are all sanctioned."""
    assert _lint_fixture("backpressure_ok.py", "backpressure") == []


# -- unbounded-growth --------------------------------------------------------

def test_unbounded_growth_flags_known_positives():
    found = _lint_fixture("growth_bad.py", "unbounded-growth")
    assert {f.code for f in found} == {"grow-only"}
    idents = {(f.qual, f.ident) for f in found}
    assert ("LeakyActor", "self.seen") in idents      # subscript growth
    assert ("LeakyActor", "self.log") in idents       # append growth
    assert ("", "SEEN_GLOBAL") in idents              # module level


def test_unbounded_growth_passes_known_negatives():
    """Eviction paths (including closure unsubscribes), maxlen
    deques, registry channels/caches, fixed-slot lists, and
    short-lived classes are all sanctioned."""
    assert _lint_fixture("growth_ok.py", "unbounded-growth") == []


def test_chan_fixture_names_are_really_declared():
    """The fixtures lean on real registry names — a renamed channel
    must rename the fixtures (and every call site) with it."""
    from tools.sdlint.passes.queue_discipline import declared_channels

    declared = declared_channels(ROOT)
    for name in ("sync.ingest.events", "sync.ingest.requests",
                 "p2p.tunnel.frames", "p2p.route_cache"):
        assert name in declared, name


def test_channel_registry_static_runtime_drift():
    """The static table and the runtime registry cannot drift (the
    PR 6 timeout check, for channels): every AST-visible declaration
    resolves at runtime, every runtime contract is AST-visible, and
    every declared channel is actually CONSTRUCTED somewhere in the
    tree with a literal name the registry knows."""
    import ast as _ast

    from spacedrive_tpu import channels
    from tools.sdlint.passes.queue_discipline import declared_channels

    static = declared_channels(ROOT)
    assert set(static) == set(channels.CHANNELS)
    for name in static:
        assert channels.capacity(name) >= 1
        c = channels.CHANNELS[name]
        assert c.policy in channels.POLICIES
        if c.policy == "block" and c.kind == "queue":
            from spacedrive_tpu import timeouts
            assert c.put_budget in timeouts.TIMEOUTS
    # every declared channel constructed somewhere (fixtures excluded)
    project = load_project(ROOT)
    constructed = set()
    for src in project.files:
        for node in _ast.walk(src.tree):
            if not isinstance(node, _ast.Call):
                continue
            from tools.sdlint.core import dotted
            d = dotted(node.func)
            if d is None:
                continue
            if d.rsplit(".", 1)[-1] in ("channel", "window",
                                        "bounded_dict") and node.args:
                arg = node.args[0]
                if isinstance(arg, _ast.Constant) and \
                        isinstance(arg.value, str):
                    constructed.add(arg.value)
    missing = set(static) - constructed
    assert not missing, (
        f"declared but never constructed in the tree: {missing} — "
        "prune the contract or adopt it")


# -- shared-mutation (round 13: the thread-safety trio) ---------------------

def test_shared_mutation_flags_known_positives():
    found = _lint_fixture("race_bad.py", "shared-mutation")
    codes = {f.code for f in found}
    assert codes == {
        "unguarded-write", "wrong-context-write", "multi-thread-write",
        "non-atomic-write", "post-init-write", "undeclared-attr",
        "undeclared-class"}, codes
    # the encoded PR 8 shape: a guarded counter bumped bare from a
    # run_in_executor device-stream target
    assert any(f.code == "unguarded-write"
               and f.ident == "RaceStats.h2d_bytes"
               and f.qual == "_transfer" for f in found), found
    assert any(f.code == "undeclared-class" and f.ident == "BareShared"
               for f in found), found


def test_shared_mutation_passes_known_negatives():
    """Guarded executor writes, loop-side loop_only use, one-context
    single_thread, atomic_counter `+=`, init-bound immutables, and
    single-context unregistered classes are all sanctioned."""
    assert _lint_fixture("race_ok.py", "shared-mutation") == []


# -- thread-boundary ---------------------------------------------------------

def test_thread_boundary_flags_known_positives():
    found = _lint_fixture("boundary_bad.py", "thread-boundary")
    codes = {f.code for f in found}
    assert codes == {"loop-call-from-thread",
                     "raw-threadsafe-handoff"}, codes
    idents = {f.ident for f in found
              if f.code == "loop-call-from-thread"}
    assert {"self.inbox.put_nowait", "self.events.emit", "tasks.spawn",
            "asyncio.ensure_future", "q.put_nowait"} <= idents, idents
    # the old sync_net/api shape: the raw primitive, not the helper
    assert any(f.code == "raw-threadsafe-handoff"
               and f.qual == "Pump.legacy_post" for f in found)


def test_thread_boundary_passes_known_negatives():
    """call_threadsafe hand-offs, loop-side channel/spawn/emit use,
    and ambient sync drivers are all sanctioned."""
    assert _lint_fixture("boundary_ok.py", "thread-boundary") == []


# -- guard-consistency -------------------------------------------------------

def test_guard_consistency_flags_known_positives():
    found = _lint_fixture("guard_bad.py", "guard-consistency")
    assert {f.code for f in found} == {"mixed-guard"}
    idents = {f.ident for f in found}
    assert idents == {"Cache.entries", "Cache.hits",
                      "TwoLocks.state"}, idents


def test_guard_consistency_passes_known_negatives():
    """Consistent guards, guard supersets, the tx-implies-write-lock
    model, init-time writes, never-guarded work lists, and registered
    classes are all out of scope."""
    assert _lint_fixture("guard_ok.py", "guard-consistency") == []


def test_race_fixture_contract_kinds_cover_the_registry():
    """The fixture pair exercises every declared contract kind — a new
    kind added to threadctx.KINDS must grow the fixtures with it."""
    from spacedrive_tpu import threadctx
    from tools.sdlint.passes._threads import declared_owners_from_tree

    import ast as _ast
    for fixture in ("race_bad.py", "race_ok.py"):
        tree = _ast.parse(
            open(os.path.join(FIXTURES, fixture), encoding="utf-8")
            .read())
        owners = declared_owners_from_tree(tree)
        kinds = {kind for spec in owners.values()
                 for kind, _lock in spec["attrs"].values()}
        assert kinds == set(threadctx.KINDS), (fixture, kinds)


# -- --changed incremental mode ---------------------------------------------

def test_reverse_closure_includes_transitive_callers():
    from tools.sdlint.core import reverse_closure_files

    project = load_project(ROOT)
    closure = reverse_closure_files(
        project, ["spacedrive_tpu/channels.py"])
    assert "spacedrive_tpu/channels.py" in closure
    # jobs/manager constructs registry channels -> it re-lints
    assert "spacedrive_tpu/jobs/manager.py" in closure
    # files with no call path INTO channels stay out of scope
    assert "spacedrive_tpu/sync/hlc.py" not in closure
    assert "spacedrive_tpu/locations/paths.py" not in closure


def test_changed_mode_scopes_and_exits_clean(monkeypatch, capsys):
    import tools.sdlint.__main__ as cli

    monkeypatch.setattr(cli, "git_changed_paths",
                        lambda root, ref: ["spacedrive_tpu/flags.py"])
    rc = cli.main(["--changed"])
    captured = capsys.readouterr()
    assert rc == 0, captured.out
    assert "reverse-closure scope" in captured.err


def test_changed_mode_falls_back_on_deleted_files(monkeypatch, capsys):
    """A deleted/renamed in-scope module cannot seed the closure (its
    callers are exactly what the change can break) — the run must
    widen to the whole tree, never silently skip."""
    import tools.sdlint.__main__ as cli

    monkeypatch.setattr(
        cli, "git_changed_paths",
        lambda root, ref: ["spacedrive_tpu/never_existed.py"])
    rc = cli.main(["--changed"])
    captured = capsys.readouterr()
    assert rc == 0, captured.out
    assert "falling back to a full-tree run" in captured.err


def test_changed_mode_with_nothing_touched(monkeypatch, capsys):
    import tools.sdlint.__main__ as cli

    monkeypatch.setattr(cli, "git_changed_paths",
                        lambda root, ref: [])
    assert cli.main(["--changed", "HEAD~1"]) == 0
    assert "no lintable files changed" in capsys.readouterr().out


def test_changed_mode_cannot_rewrite_baseline():
    import pytest

    import tools.sdlint.__main__ as cli

    with pytest.raises(SystemExit):
        cli.main(["--changed", "--update-baseline"])
    with pytest.raises(SystemExit):
        cli.main(["--changed", "--write-baseline"])


# -- the tree gate (runs all five passes; tier-1's CI hook) -----------------

def test_tree_clean_within_baseline():
    project = load_project(ROOT)
    findings = run_passes(project)
    baseline = Baseline.load()
    new, _old, _stale = baseline.split(findings)
    assert not new, (
        "new sdlint findings (fix them — the baseline only shrinks):\n"
        + "\n".join(f.text() for f in new))


def test_baseline_within_budget_and_entries_reasoned():
    baseline = Baseline.load(DEFAULT_PATH)
    assert len(baseline.entries) <= baseline.budget, (
        f"baseline grew past its budget ({len(baseline.entries)} > "
        f"{baseline.budget}): entries were added by hand — fix the "
        "findings instead (tools/sdlint/baseline.py policy)")
    for key, reason in baseline.entries.items():
        assert reason.strip(), f"baseline entry without a reason: {key}"


def test_baseline_prune_never_adds():
    bl = Baseline({"stale::key": "gone", "live::key": "still here"}, 2)
    from tools.sdlint.core import Finding

    live = Finding("p", "c", "f.py", "q", "i", "msg", 1)
    bl.entries = {live.key(): "still here", "stale::key": "gone"}
    dropped = bl.prune([live])
    assert dropped == ["stale::key"]
    assert set(bl.entries) == {live.key()}
    assert bl.budget == 1


def test_every_registered_pass_ran_on_tree():
    assert set(PASSES) == {
        "blocking-async", "lock-discipline", "crdt-parity",
        "flag-registry", "telemetry", "jit-stability",
        "dtype-discipline", "host-transfer", "task-lifecycle",
        "cancellation-safety", "timeout-discipline",
        "queue-discipline", "backpressure", "unbounded-growth",
        "shared-mutation", "thread-boundary", "guard-consistency",
        "sql-discipline", "tx-shape", "schema-parity",
        "io-durability", "crash-atomicity", "tmp-hygiene",
        "wire-discipline", "schema-drift", "proto-compat"}


DEVICE_PASSES = ("jit-stability", "dtype-discipline", "host-transfer")


def test_device_pass_baseline_entries_individually_reasoned():
    """Round-10 hygiene: every baselined device-pass finding carries
    its OWN reason — no blanket waivers copy-pasted across entries
    (the concurrency passes grandfathered a shared bench-CLI reason;
    the device family starts stricter)."""
    baseline = Baseline.load(DEFAULT_PATH)
    dev = {k: v for k, v in baseline.entries.items()
           if k.split("::", 1)[0] in DEVICE_PASSES}
    assert dev, "expected the tools-CLI device findings to be baselined"
    for key, reason in dev.items():
        assert len(reason.strip()) >= 20, f"thin reason on {key}"
    assert len(set(dev.values())) == len(dev), (
        "duplicate device-pass baseline reasons — write one per entry")


def test_subset_run_cannot_wipe_other_pass_baseline(tmp_path):
    """--passes jit-stability --update-baseline must not judge (or
    prune) the concurrency passes' entries."""
    import json
    import shutil

    from tools.sdlint.__main__ import main

    bl = tmp_path / "baseline.json"
    shutil.copy(DEFAULT_PATH, bl)
    before = json.load(open(bl))["findings"]
    rc = main(["--passes", "jit-stability", "--update-baseline",
               "--baseline", str(bl)])
    assert rc == 0
    after = json.load(open(bl))["findings"]
    keep = {k: v for k, v in before.items()
            if k.split("::", 1)[0] != "jit-stability"}
    assert all(after.get(k) == v for k, v in keep.items()), (
        "subset run dropped other passes' baseline entries")


def test_cli_passes_with_no_value_lists_passes(capsys):
    from tools.sdlint.__main__ import main

    assert main(["--passes"]) == 0
    out = capsys.readouterr().out.split()
    assert set(PASSES) <= set(out)


def test_stats_runs_all_passes_under_the_tier1_budget():
    """`python -m tools.sdlint --stats` is the analyzer's own perf
    gate: per-pass counts + wall-time, with the whole-tree total
    pinned under 30s so pass growth can't silently blow up tier-1
    (the container's 2-core/9p weather included in the margin)."""
    from tools.sdlint.__main__ import stats

    rows = stats(ROOT)
    names = [n for n, _c, _s in rows]
    assert names[0] == "index" and names[-1] == "total"
    assert set(PASSES) <= set(names)
    total_s = rows[-1][2]
    assert total_s < 30.0, (
        f"sdlint whole-tree run took {total_s:.1f}s — the analyzer "
        "must stay under 30s or tier-1 eats the overrun")


def test_cli_stats_prints_a_row_per_pass(capsys):
    # Format-only check, so run over the tiny fixture tree: the perf
    # test above already paid for the one whole-tree sweep tier-1 needs.
    from tools.sdlint.__main__ import main

    assert main(["--stats", "--root", FIXTURES]) == 0
    out = capsys.readouterr().out
    for name in PASSES:
        assert name in out


def test_cli_timeout_table_covers_every_declared_budget(capsys):
    from tools.sdlint.__main__ import main

    assert main(["--timeout-table"]) == 0
    out = capsys.readouterr().out
    from spacedrive_tpu import timeouts

    for name in timeouts.TIMEOUTS:
        assert f"`{name}`" in out


def test_cli_owner_table_covers_every_declared_owner(capsys):
    from tools.sdlint.__main__ import main

    assert main(["--owner-table"]) == 0
    out = capsys.readouterr().out
    from spacedrive_tpu import threadctx

    for name in threadctx.CONTRACTS:
        assert f"`{name}`" in out


def test_cli_chan_table_covers_every_declared_channel(capsys):
    from tools.sdlint.__main__ import main

    assert main(["--chan-table"]) == 0
    out = capsys.readouterr().out
    from spacedrive_tpu import channels

    for name in channels.CHANNELS:
        assert f"`{name}`" in out
    for c in channels.CHANNELS.values():
        assert c.policy in out


def test_baseline_budget_is_minimal_and_reasons_unique():
    """Round-11 hygiene (the PR 5 uniqueness test, tightened): the
    budget must be EXACTLY the entry count — a bump that leaves
    headroom lets findings sneak in silently — and any lifecycle-pass
    entry must carry its own substantial reason, not a copy-paste."""
    baseline = Baseline.load(DEFAULT_PATH)
    assert baseline.budget == len(baseline.entries), (
        f"budget {baseline.budget} != {len(baseline.entries)} entries: "
        "the bump must be the minimum required")
    lifecycle = {k: v for k, v in baseline.entries.items()
                 if k.split("::", 1)[0] in (
                     "task-lifecycle", "cancellation-safety",
                     "timeout-discipline",
                     "queue-discipline", "backpressure",
                     "unbounded-growth",
                     "shared-mutation", "thread-boundary",
                     "guard-consistency")}
    # Today the lifecycle, resource AND thread-safety passes run CLEAN
    # (zero baselined entries — round 13's initial findings were all
    # fixed outright: the validator cross-thread emit, the SyncManager
    # cache lock, the high-water compare-and-set, the two raw
    # threadsafe hand-off sites); if one is ever added it needs a
    # unique, substantial reason.
    for key, reason in lifecycle.items():
        assert len(reason.strip()) >= 20, f"thin reason on {key}"
    assert len(set(lifecycle.values())) == len(lifecycle), (
        "duplicate lifecycle/resource baseline reasons — write one "
        "per entry")


# -- flags registry integration --------------------------------------------

def test_flag_table_covers_every_declared_flag():
    from spacedrive_tpu import flags

    table = flags.flag_table_markdown()
    for name in flags.FLAGS:
        assert f"`{name}`" in table


def test_flags_get_parses_and_defaults(monkeypatch):
    from spacedrive_tpu import flags

    monkeypatch.delenv("SDTPU_TELEMETRY_INTERVAL", raising=False)
    assert flags.get("SDTPU_TELEMETRY_INTERVAL") == 15.0
    monkeypatch.setenv("SDTPU_TELEMETRY_INTERVAL", "2.5")
    assert flags.get("SDTPU_TELEMETRY_INTERVAL") == 2.5
    monkeypatch.setenv("SDTPU_TELEMETRY_INTERVAL", "junk")
    assert flags.get("SDTPU_TELEMETRY_INTERVAL") == 15.0  # defensive
    import pytest

    with pytest.raises(KeyError):
        flags.get("SDTPU_NEVER_DECLARED")
    # strict flags fail LOUD on malformed values (a fuzz-seed typo must
    # not silently replay the default corpus)
    monkeypatch.setenv("SDTPU_FUZZ_SEEDS", "5 9")
    with pytest.raises(ValueError):
        flags.get("SDTPU_FUZZ_SEEDS")
    monkeypatch.setenv("SDTPU_FUZZ_SEEDS", "5,9")
    assert flags.get("SDTPU_FUZZ_SEEDS") == [5, 9]


# -- health-engine read surface (round 15) ----------------------------------

def test_health_reads_static_runtime_parity():
    """The AST-parsed READS table and the runtime one cannot drift,
    and every family the health engine reads — plus every sd_health_*
    family it emits — must resolve in the central registry (the
    span-family/channel drift check, for the observatory)."""
    from spacedrive_tpu import health, telemetry
    from tools.sdlint.passes.telemetry import health_reads

    static = health_reads(ROOT)
    assert static, "READS table not found in spacedrive_tpu/health.py"
    assert set(static) == set(health.READS)
    for fam in health.READS:
        assert telemetry.REGISTRY.get(fam) is not None, fam
    for fam in ("sd_health_state", "sd_health_samples_total"):
        assert telemetry.REGISTRY.get(fam) is not None, fam


def test_health_read_lint_catches_violations(tmp_path):
    """Positive fixtures for the two new telemetry-pass codes: a
    READS key missing from the central registry, and a sd_* literal
    outside the table. The engine's own sd_health_* families are
    exempt (they are centrally declared by the existing rule)."""
    from tools.telemetry_lint import run_lint

    pkg = tmp_path / "fakepkg"
    pkg.mkdir()
    (pkg / "telemetry.py").write_text(
        "def counter(name, help=''):\n    return None\n\n\n"
        "A = counter('sd_jobs_a_total')\n")
    (pkg / "health.py").write_text(
        "READS = {\n"
        "    'sd_jobs_a_total': 'fine, centrally registered',\n"
        "    'sd_jobs_missing_total': 'NOT registered',\n"
        "}\n"
        "X = 'sd_jobs_unlisted_total'\n"
        "Y = 'sd_health_own_total'\n")
    problems = run_lint(str(pkg))
    text = "\n".join(problems)
    assert "'sd_jobs_missing_total' is not registered" in text
    assert "'sd_jobs_unlisted_total' outside the READS table" in text
    assert "sd_health_own_total" not in text
    assert "'sd_jobs_a_total'" not in text


# -- sql-discipline / tx-shape / schema-parity (the round-16 store trio) ----

def test_sql_discipline_flags_known_positives():
    found = _lint_fixture("sql_bad.py", "sql-discipline")
    by_code = {}
    for f in found:
        by_code.setdefault(f.code, set()).add(f.ident)
    assert "SELECT * FROM object WHERE id = ?" in \
        by_code.get("sql-literal", set())
    assert "INSERT INTO tag (pub_id) VALUES (?)" in \
        by_code.get("sql-literal", set())
    # the literal hidden behind a local variable still resolves
    assert "SELECT id FROM location" in by_code.get("sql-literal", set())
    assert any("UPDATE" in i for i in by_code.get("sql-dynamic", set()))
    assert "conn.execute" in by_code.get("sql-opaque", set())
    assert "store.totally.unknown_statement" in \
        by_code.get("run-unknown", set())
    assert "db.run" in by_code.get("run-dynamic-name", set())
    assert "node.object_delete" in by_code.get("write-no-conn", set())
    assert "library.db.execute" in \
        by_code.get("read-via-write-path", set())
    assert "rogue.statement" in by_code.get("sql-central", set())


def test_sql_discipline_passes_known_negatives():
    assert _lint_fixture("sql_ok.py", "sql-discipline") == []


def test_tx_shape_flags_known_positives():
    found = _lint_fixture("txshape_bad.py", "tx-shape")
    by_code = {}
    for f in found:
        by_code.setdefault(f.code, set()).add((f.qual, f.ident))
    loops = by_code.get("tx-in-loop", set())
    # all five spellings of commit-per-item
    assert ("tx_per_item", "db.tx") in loops
    assert ("run_tx_per_item", "db.run_tx") in loops
    assert ("helper_per_item", "db.insert") in loops
    assert ("opener_in_loop", "_opens_tx") in loops
    assert ("write_tx_per_item", "db.write_tx") in loops
    blocking = {i for _, i in by_code.get("blocking-in-tx", set())}
    assert {"time.sleep", "open"} <= blocking
    assert any(q == "await_inside_tx"
               for q, _ in by_code.get("await-in-tx", set()))
    assert ("nested_chain", "_opens_tx") in \
        by_code.get("nested-tx-chain", set())
    assert ("row_at_a_time", "identifier.link_paths") in \
        by_code.get("executemany-candidate", set())


def test_tx_shape_passes_known_negatives():
    assert _lint_fixture("txshape_ok.py", "tx-shape") == []


def _lint_source(tmp_path, relpath, source, pass_name):
    """Lint a synthetic snippet under a chosen repo-relative path —
    actor-bypass is scoped by relpath (product vs store vs tools), so
    the fixture directory cannot exercise it."""
    from tools.sdlint.core import Project, SourceFile
    p = tmp_path / "snippet.py"
    p.write_text(source)
    src = SourceFile(str(p), relpath)
    return run_passes(Project(ROOT, [src]), get_passes([pass_name]))


_BYPASS_SRC = '''
def direct_tx(db):
    with db.tx() as conn:
        conn.execute("DELETE FROM t")


def direct_run_tx(library):
    library.db.run_tx("node.object_delete", (1,))


def through_actor(db):
    with db.write_tx() as conn:
        conn.execute("DELETE FROM t")
'''


def test_tx_shape_actor_bypass_flags_product_raw_tx(tmp_path):
    found = _lint_source(tmp_path, "spacedrive_tpu/fake_writer.py",
                         _BYPASS_SRC, "tx-shape")
    by = {(f.qual, f.code) for f in found}
    assert ("direct_tx", "actor-bypass") in by
    assert ("direct_run_tx", "actor-bypass") in by
    assert ("through_actor", "actor-bypass") not in by


def test_tx_shape_actor_bypass_exempts_engine_room_and_tools(tmp_path):
    for rel in ("spacedrive_tpu/store/fake.py", "tools/fake.py"):
        found = _lint_source(tmp_path, rel, _BYPASS_SRC, "tx-shape")
        assert not [f for f in found if f.code == "actor-bypass"], rel


def test_tx_shape_actor_bypass_honors_inline_waiver(tmp_path):
    src = (
        "def bootstrap(db):\n"
        "    # sdlint: ok[tx-shape]\n"
        "    with db.tx() as conn:\n"
        "        conn.execute('DELETE FROM t')\n")
    found = _lint_source(tmp_path, "spacedrive_tpu/fake_boot.py", src,
                         "tx-shape")
    assert not [f for f in found if f.code == "actor-bypass"]


def test_schema_parity_flags_known_positives():
    found = _lint_fixture("schema_bad.py", "schema-parity")
    by_code = {}
    for f in found:
        by_code.setdefault(f.code, set()).add(f.ident)
    assert "fixture.ghost_table:warp_core" in \
        by_code.get("unknown-table", set())
    assert "fixture.ghost_column:flux_capacitance" in \
        by_code.get("unknown-column", set())
    assert "fixture.ghost_qualified:tag.wormhole" in \
        by_code.get("unknown-column", set())
    assert "fixture.drifted_tables" in \
        by_code.get("tables-drift", set())
    assert "fixture.sequential_scan:file_path" in \
        by_code.get("unindexed-filter", set())


def test_schema_parity_passes_known_negatives():
    assert _lint_fixture("schema_ok.py", "schema-parity") == []


def test_sql_registry_static_runtime_parity():
    """The AST view the passes judge must equal the runtime registry
    the auditor enforces — name for name, verb for verb, shape for
    shape (same drift contract as the channel/owner registries)."""
    from spacedrive_tpu.store import statements
    from tools.sdlint.passes import _sql

    decls = _sql.registry_decls(ROOT)
    runtime = dict(statements.STATEMENTS)
    runtime.update(statements.SHAPES)
    assert set(decls) == set(runtime), (
        set(decls) ^ set(runtime))
    for name, d in decls.items():
        st = runtime[name]
        assert d.verb == st.verb, name
        assert d.shape == st.shape, name
        assert d.tx_required == st.tx_required, name
        assert tuple(d.tables) == st.tables, name
        assert d.coverage == st.coverage, name
    # the pass-side constant sets mirror statements.py
    from tools.sdlint.passes import schema_parity

    assert schema_parity.LARGE_TABLES == set(statements.LARGE_TABLES)


def test_every_write_statement_is_tx_scoped():
    """THE acceptance invariant for ROADMAP item 4's actor split:
    no write-verb contract exists outside transaction scope — the
    registry refuses autocommit writes at declare time, and this
    pins the whole current inventory."""
    from spacedrive_tpu.store import statements

    writes = [st for st in statements.all_statements()
              if st.verb == "write"]
    assert writes, "inventory lost its writes?"
    for st in writes:
        assert st.tx_required, f"{st.name} is an autocommit write"
    # and the registry enforces it for future declarations
    import pytest

    with pytest.raises(statements.SqlContractError):
        statements.declare_stmt(
            "fixture.autocommit", "DELETE FROM tag WHERE id = ?",
            verb="write", tables=("tag",), tx_required=False)


def test_every_declared_statement_is_referenced():
    """Inventory↔usage drift: every exact statement name appears at a
    run()/run_many()/run_tx() call site (or inside store/db.py's
    engine room), every shape's pattern matches at least one dynamic
    call site — no dead contracts. tools-coverage statements may live
    in tools/ only."""
    import ast

    from tools.sdlint.core import load_project
    from tools.sdlint.passes import _sql

    project = load_project(ROOT)
    decls = _sql.registry_decls(ROOT)
    shapes = _sql.ShapeIndex(decls)
    used_names = set()
    matched_shapes = set()
    for src in project.files:
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            for arg in list(node.args) + [k.value for k in node.keywords]:
                if isinstance(arg, ast.Constant) and \
                        isinstance(arg.value, str) and arg.value in decls:
                    used_names.add(arg.value)
                dyn = _sql.dynamic_sql_expr(arg)
                if dyn is not None:
                    hit = shapes.match(dyn)
                    if hit is not None:
                        matched_shapes.add(hit.name)
    # db.py builds the helper shapes' SQL from dicts (not matchable
    # statically) and executes store.init/last_rowid internally.
    engine_bound = {n for n in decls
                    if n.startswith("store.helper.")}
    unused = [n for n, d in decls.items()
              if not d.shape and n not in used_names
              and n not in engine_bound
              and n != "store.init.instance_count"]
    assert not unused, f"declared but never referenced: {unused}"
    dead_shapes = [n for n, d in decls.items()
                   if d.shape and n not in matched_shapes
                   and n not in engine_bound]
    assert not dead_shapes, f"shapes matching no call site: {dead_shapes}"


# -- io-durability / crash-atomicity / tmp-hygiene (round 19) ---------------

def test_io_durability_flags_known_positives():
    found = _lint_fixture("durability_bad.py", "io-durability")
    by_code = {}
    for f in found:
        by_code.setdefault(f.code, set()).add(f.qual)
    assert "bare_config_save" in by_code.get("bare-write", set())
    assert "promote_by_rename" in by_code.get("rename-no-tmp", set())
    assert "replace_without_flush" in \
        by_code.get("replace-no-fsync", set())
    assert "writes_unknown_artifact" in \
        by_code.get("artifact-undeclared", set())
    assert "writes_computed_name" in \
        by_code.get("artifact-dynamic", set())


def test_io_durability_passes_known_negatives():
    assert _lint_fixture("durability_ok.py", "io-durability") == []


def test_crash_atomicity_flags_known_positives():
    found = _lint_fixture("atomicity_bad.py", "crash-atomicity")
    multi = {f.qual for f in found if f.code == "multi-commit"}
    assert "restore_pair" in multi
    assert "Creator.create" in multi       # artifact + DB row
    rmw = {f.qual for f in found if f.code == "rmw-unguarded"}
    assert "bump_generation" in rmw


def test_crash_atomicity_passes_known_negatives():
    assert _lint_fixture("atomicity_ok.py", "crash-atomicity") == []


def test_tmp_hygiene_flags_known_positives():
    found = _lint_fixture("tmphygiene_bad.py", "tmp-hygiene")
    by_code = {}
    for f in found:
        by_code.setdefault(f.code, set()).add(f.qual)
    assert {"forgets_entirely", "keeps_named_file"} <= \
        by_code.get("tmp-no-cleanup", set())
    assert "happy_path_only" in by_code.get("tmp-leak-on-error", set())


def test_tmp_hygiene_passes_known_negatives():
    assert _lint_fixture("tmphygiene_ok.py", "tmp-hygiene") == []


def test_cli_artifact_table_covers_every_declared_artifact(capsys):
    from tools.sdlint.__main__ import main

    assert main(["--artifact-table"]) == 0
    out = capsys.readouterr().out
    from spacedrive_tpu import persist

    for name in persist.ARTIFACTS:
        assert f"`{name}`" in out
    for a in persist.ARTIFACTS.values():
        assert a.kind in out and a.fsync in out


def test_persist_registry_static_runtime_parity():
    """Registry↔usage drift, both directions: every persist call site
    with a literal artifact name references a DECLARED artifact, and
    every declared artifact is WRITTEN (or swept) somewhere in the
    product/tools tree — no dead declarations, no shadow artifacts."""
    import ast

    from spacedrive_tpu import persist
    from tools.sdlint.passes.io_durability import (NAMED_APIS,
                                                   declared_artifacts)

    static = declared_artifacts(ROOT)
    assert set(static) == set(persist.ARTIFACTS), (
        "the AST view of declare_artifact() calls must match the "
        "imported registry")

    project = load_project(ROOT)
    referenced = set()
    for src in project.files:
        if src.relpath == "spacedrive_tpu/persist.py":
            continue
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            from tools.sdlint.core import dotted

            d = dotted(node.func)
            if d is None or d.rsplit(".", 1)[-1] not in NAMED_APIS:
                continue
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                referenced.add(node.args[0].value)
    undeclared = referenced - set(persist.ARTIFACTS)
    assert not undeclared, (
        f"persist call sites name undeclared artifacts: {undeclared}")
    dead = set(persist.ARTIFACTS) - referenced
    assert not dead, (
        f"declared artifacts never written anywhere: {dead}")


# -- wire-discipline / schema-drift / proto-compat (round 20) ---------------

def test_wire_discipline_flags_known_positives():
    found = _lint_fixture("wire_bad.py", "wire-discipline")
    by_code = {}
    for f in found:
        by_code.setdefault(f.code, set()).add(f.ident)
    assert "non-literal" in by_code.get("computed-declaration", set())
    assert "t=ping" in by_code.get("raw-kind-literal", set())
    assert "wire.pack" in by_code.get("dynamic-kind", set())
    assert {"fx.no.such.message", "fxgroup"} <= \
        by_code.get("undeclared-kind", set())
    assert "ok" in by_code.get("raw-value-literal", set())


def test_wire_discipline_passes_known_negatives():
    assert _lint_fixture("wire_ok.py", "wire-discipline") == []


def test_schema_drift_flags_known_positives():
    found = _lint_fixture("wire_drift_bad.py", "schema-drift")
    by_code = {}
    for f in found:
        by_code.setdefault(f.code, set()).add(f.ident)
    assert "p2p.pair.request.extra" in \
        by_code.get("smuggled-field", set())
    assert {"p2p.pair.request.library_name",
            "p2p.pair.request.listen_port",
            "p2p.pair.request.instance",
            "clone.ack.fast"} <= by_code.get("missing-field", set())
    assert {"sync.pull.request.cursor", "sync.pull.page.total"} <= \
        by_code.get("unknown-field-read", set())


def test_schema_drift_passes_known_negatives():
    # includes the reassignment case: once a name stops holding the
    # unpacked frame, its reads leave the schema's jurisdiction
    assert _lint_fixture("wire_drift_ok.py", "schema-drift") == []


def test_proto_compat_flags_known_positives():
    found = _lint_fixture("wire_compat_bad.py", "proto-compat")
    by_code = {}
    for f in found:
        by_code.setdefault(f.code, set()).add(f.ident)
    assert "fx.compat.msg" in by_code.get("schema-no-bump", set())
    assert "fx.compat.unsnapshotted" in \
        by_code.get("missing-snapshot", set())
    assert "fx.compat.ghost" in by_code.get("removed-message", set())
    assert "proto-compare" in by_code.get("adhoc-version-check", set())


def test_proto_compat_passes_known_negatives():
    # fx.ok.bumped changed shape WITH a version bump — clean
    assert _lint_fixture("wire_compat_ok.py", "proto-compat") == []


def test_proto_compat_raw_decode_scoped_to_p2p():
    """msgpack.unpackb outside the tunnel seam is flagged in the p2p
    plane only; the discovery beacon's two decodes carry documented
    waivers (its UDP envelope is pre-tunnel, signed, its own format)."""
    import ast

    from tools.sdlint.passes.proto_compat import ProtoCompatPass

    project = load_project(ROOT)
    found = ProtoCompatPass().run(project)
    raw = [f for f in found if f.code == "raw-decode"]
    assert {f.path for f in raw} == {"spacedrive_tpu/p2p/discovery.py"}
    src = {s.relpath: s for s in project.files}[
        "spacedrive_tpu/p2p/discovery.py"]
    for f in raw:
        line = src.lines[f.lineno - 1]
        assert "sdlint: ok[proto-compat]" in line, (
            f"undocumented raw decode at discovery.py:{f.lineno}")


def test_wire_baseline_snapshot_matches_registry():
    """The committed wire_baseline.json IS the current registry — a
    declaration change without `--write-wire-baseline` (and a version
    bump) must fail here and in the proto-compat pass."""
    import json

    from spacedrive_tpu.p2p import wire

    with open(os.path.join(ROOT, "tools", "sdlint",
                           "wire_baseline.json"),
              encoding="utf-8") as f:
        committed = json.load(f)["messages"]
    assert committed == wire.baseline_snapshot()


def test_wire_registry_static_runtime_parity():
    """The AST view of declare_message() calls in wire.py must match
    the imported registry message-for-message, token-for-token — a
    computed declaration would silently blind all three passes."""
    from spacedrive_tpu.p2p import wire
    from tools.sdlint.passes import _wire

    static = _wire.registry_decls(ROOT)
    assert set(static) == set(wire.MESSAGES), (
        "the AST view of declare_message() calls must match the "
        "imported registry")
    versions = _wire.proto_versions(ROOT)
    assert versions == wire.PROTO_VERSIONS
    for name, decl in static.items():
        assert _wire.snapshot_entry(decl, versions) == \
            wire.baseline_snapshot()[name], name


def test_cli_wire_table_covers_every_declared_message(capsys):
    from tools.sdlint.__main__ import main

    assert main(["--wire-table"]) == 0
    out = capsys.readouterr().out
    from spacedrive_tpu.p2p import wire

    for name in wire.MESSAGES:
        assert f"`{name}`" in out
