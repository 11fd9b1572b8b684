"""Central SDTPU_* environment-flag registry.

Every environment flag the engine reads is DECLARED here — name,
parsed default, parser, and a docstring — and READ through `get()` /
`raw()`. Scattered `os.environ["SDTPU_*"]` reads made the flag surface
unauditable (round-7 review: ~10 literals across six layers, none
discoverable without grep); `tools/sdlint`'s flag-registry pass now
fails the build on any SDTPU_* literal that is not declared here and on
any direct environ read of one outside this module. Writers (benches
and tests toggling a flag via `os.environ[...] = ...` or
`monkeypatch.setenv`) are unaffected — reads go live to the
environment on every call, so toggles keep working mid-process.

Design constraints (same as telemetry.py, which imports this module):
pure stdlib, imports nothing from the package — every layer can import
it without cycles.

README's flag table is generated from this registry
(`python -m tools.sdlint --flag-table`).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "Flag", "FLAGS", "declare", "get", "raw", "flag_table_markdown",
    "parse_str", "parse_onoff", "parse_flag1", "parse_float",
    "parse_int", "parse_int_csv",
]


# -- parsers ----------------------------------------------------------------
# Each takes the RAW environment string (never None) and returns the
# typed value; a ValueError falls back to the flag's default, matching
# the defensive parsing every migrated call site already had.

def parse_str(v: str) -> str:
    return v


def parse_onoff(v: str) -> bool:
    """Kill-switch semantics: anything but off/0/false is ON."""
    return v.strip().lower() not in ("off", "0", "false")


def parse_flag1(v: str) -> bool:
    """Opt-in semantics: only 1/on/true/yes enable."""
    return v.strip().lower() in ("1", "on", "true", "yes")


def parse_float(v: str) -> float:
    return float(v)


def parse_int(v: str) -> int:
    return int(v)


def parse_int_csv(v: str) -> List[int]:
    return [int(s) for s in v.split(",") if s.strip()]


@dataclass(frozen=True)
class Flag:
    name: str
    default: Any
    parse: Callable[[str], Any]
    doc: str
    # strict=True: a malformed value RAISES instead of falling back to
    # the default. For flags where a typo silently changing behavior is
    # worse than a crash (fuzz seeds replaying the wrong corpus, a
    # batch budget ignoring the operator) — matches the loud parsing
    # their pre-registry call sites had.
    strict: bool = False


FLAGS: Dict[str, Flag] = {}


def declare(name: str, default: Any, parse: Callable[[str], Any] = parse_str,
            doc: str = "", strict: bool = False) -> Flag:
    if not name.startswith("SDTPU_"):
        raise ValueError(f"flag {name!r} must start with SDTPU_")
    if name in FLAGS:
        raise ValueError(f"flag {name!r} declared twice")
    f = Flag(name, default, parse, doc, strict)
    FLAGS[name] = f
    return f


def raw(name: str) -> Optional[str]:
    """The raw environment string, or None when unset. The flag must be
    declared — an unknown name is a programming error, not a lookup
    miss (that is the whole point of the registry)."""
    if name not in FLAGS:
        raise KeyError(f"undeclared flag {name!r} (declare it in "
                       "spacedrive_tpu/flags.py)")
    return os.environ.get(name)


def get(name: str) -> Any:
    """Parsed value: parser over the live environment, the declared
    default when unset, empty, or unparseable. Reads are NOT cached —
    benches and tests toggle flags mid-process (sync_bench flips
    SDTPU_CLONE_PASSTHROUGH per phase); call sites that need one-shot
    semantics cache on their side (tracing's profiler probe)."""
    flag = FLAGS.get(name)
    if flag is None:
        raise KeyError(f"undeclared flag {name!r} (declare it in "
                       "spacedrive_tpu/flags.py)")
    v = os.environ.get(name)
    if v is None or v == "":
        return flag.default
    try:
        return flag.parse(v)
    except (ValueError, TypeError):
        if flag.strict:
            raise ValueError(
                f"{name}={v!r}: unparseable (see its declaration in "
                f"spacedrive_tpu/flags.py)")
        return flag.default


def flag_table_markdown() -> str:
    """README's generated flag table (one row per declared flag)."""
    out = ["| Flag | Default | Meaning |", "| --- | --- | --- |"]
    for name in sorted(FLAGS):
        f = FLAGS[name]
        default = "unset" if f.default is None else repr(f.default)
        doc = " ".join(f.doc.split())
        out.append(f"| `{name}` | {default} | {doc} |")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# THE flag namespace. Keep alphabetical; every entry is enforced by the
# sdlint flag-registry pass (undeclared literals fail the build).
# ---------------------------------------------------------------------------

declare(
    "SDTPU_CHAN_SCALE", 1.0, parse_float,
    "Global multiplier over every declared channel capacity "
    "(channels.py registry; README's generated channel table lists "
    "the per-channel defaults). Read at channel construction, not "
    "per put.")

declare(
    "SDTPU_CHAOS", "", parse_str,
    "Chaos-plane arming spec (chaos.py): `<point>=<fault>[,...];...` "
    "with faults delay:<dur>[:<prob>] or one of error/drop/"
    "disconnect/wedge/corrupt[:<prob>], e.g. "
    "`p2p.tunnel.frame=drop:0.01,delay:50ms`. "
    "Point names must be declared fault points; undeclared names and "
    "kinds a point did not declare are REFUSED at parse. Read at "
    "import / chaos.rearm_from_env(); empty = disarmed (one flag "
    "check per injection site).")

declare(
    "SDTPU_CHAOS_SEED", 0, parse_int,
    "Deterministic RNG seed for the armed chaos plane (chaos.py): "
    "each fault point draws from its own Random seeded (seed, point "
    "name), so a failing storm replays exactly under the same seed "
    "regardless of how concurrent sites interleave.", strict=True)

declare(
    "SDTPU_CLONE_PASSTHROUGH", True, parse_onoff,
    "Kill switch for the full-library-clone blob pass-through fast "
    "path (p2p/sync_net.py). `off` forces the per-op pull loop.")

declare(
    "SDTPU_DEVICE_PIPELINE", "", lambda v: v.strip().lower(),
    "CAS device-pipeline override (ops/staging.py): `force`/`1` always "
    "stage through the accelerator, `off`/`0` always use the host "
    "planes; unset probes the H2D link once per process.")

declare(
    "SDTPU_DISPATCH_LOG", False, lambda v: v == "1",
    "When `1`, every device CAS dispatch appends its batch geometry to "
    "ops/blake3_jax.DISPATCH_LOG (driver/dryrun artifacts read it).")

declare(
    "SDTPU_FLEET_INTERVAL_S", 10.0, parse_float,
    "Seconds between fleet-observatory poll rounds (fleet.py, "
    "supervised under node/fleet): each round pulls every paired "
    "peer's obs.health snapshot into its bounded per-peer ring and "
    "re-merges the fleet view. A peer whose last good snapshot is "
    "older than 2x this interval is marked stale-degraded.")

declare(
    "SDTPU_FUZZ_SEEDS", [7, 23], parse_int_csv,
    "Comma-separated RNG seeds the sync fuzz suite replays "
    "(tests/test_sync_fuzz.py).", strict=True)

declare(
    "SDTPU_H2D_GBPS", None, parse_float,
    "Pin the host→device link-rate probe to a fixed GB/s "
    "(ops/staging.py) instead of measuring — benchmark pinning and "
    "slow-link hosts.")

declare(
    "SDTPU_DONATE_BUFFERS", True, parse_onoff,
    "Kill switch for donated device buffers on the identify path "
    "(ops/blake3_jax.py cas_hasher's donated CAS dispatch): the "
    "donated program consumes its staged input buffers at dispatch so "
    "each batch's H2D lands in recycled allocator space instead of "
    "growing the in-flight footprint, and the identifier hashes ahead "
    "on device-engaged runs only with it on. `off` pins the undonated "
    "program (the CPU-mesh test suite sets it to dodge a ~45 s "
    "duplicate compile per kernel variant).")

declare(
    "SDTPU_HEALTH_INTERVAL_S", 5.0, parse_float,
    "Seconds between health-observatory sampler ticks (health.py, "
    "supervised under node/health): each tick spools delta-snapshots "
    "of every metric family into the health.series rings, re-"
    "evaluates per-subsystem saturation, and emits a HealthSnapshot "
    "event.")

declare(
    "SDTPU_HEALTH_TOPK", 3, parse_int,
    "Bottleneck-attribution depth of the health observatory "
    "(health.py): the top-k resources driving each non-ok subsystem "
    "state, ranked by severity then evidence score, served by "
    "node.health and rendered by tools/sd_top.py.", strict=True)

declare(
    "SDTPU_INCIDENTS", True, parse_onoff,
    "Incident observatory master switch (incidents.py): when on, "
    "Node bootstrap installs the process-global black box and wires "
    "every detection surface (health states, backoff give-ups, "
    "count-mode sanitizer violations, crash markers) to snapshot-"
    "freeze evidence bundles. `off` makes install() a no-op.")

declare(
    "SDTPU_INCIDENT_DEGRADED_WINDOWS", 3, parse_int,
    "Consecutive health samples a subsystem must hold `degraded` "
    "before the incident observatory opens a health.degraded bundle "
    "(incidents.py) — brief wobbles don't produce postmortems; "
    "`saturated` always fires immediately.", strict=True)

declare(
    "SDTPU_INCIDENT_STORE_MB", 16.0, parse_float,
    "Byte cap (MB) on the on-disk incident-bundle store "
    "(incidents.py): crossing it evicts oldest bundles first and "
    "counts sd_incident_dropped_total. The count cap is the declared "
    "incidents.store channel capacity.")

declare(
    "SDTPU_INCIDENT_WINDOW_S", 60.0, parse_float,
    "Per-fingerprint rate-limit window for incident bundles "
    "(incidents.py): repeat firings of the same subsystem + resource "
    "+ trigger kind inside the window collapse into "
    "sd_incident_deduped_total instead of new bundles.")

declare(
    "SDTPU_FS_AUDIT", "auto", lambda v: v.strip().lower(),
    "Runtime fs auditor (persist.py, armed with the sanitizer): "
    "interposes os.replace/os.fsync, checks the fsync-file -> rename "
    "-> fsync-dir ordering each declared artifact's policy promises, "
    "and flags raw product-module renames outside the persist seam "
    "(persist_undeclared_write / persist_unfsynced_rename — raised "
    "in tier-1, counted in production). `off` skips arming (plain "
    "os.replace/os.fsync, zero overhead); `auto` follows "
    "SDTPU_SANITIZE. Read once at sanitize.install().")

declare(
    "SDTPU_PERSIST_CRASHPOINT", "", parse_str,
    "`<artifact>:<edge>` — SIGKILL this process at the named "
    "declared durability edge inside the persist seam "
    "(persist.crashpoint). How tools/crash_grid.py children die at "
    "every edge of every declared artifact systematically; empty "
    "(default) disables the kill switch.")

declare(
    "SDTPU_LOG_JSON", False, parse_flag1,
    "When on, a JSON-line formatter is installed on the "
    "`spacedrive_tpu` logger (tracing.install_json_logging): every "
    "record carries ts/level/logger/msg plus the CURRENT trace/span "
    "id (the tracing contextvar survives to_thread), so log lines "
    "correlate with node.spans and exported traces.")

declare(
    "SDTPU_LOG_RING", True, parse_onoff,
    "Bounded in-memory log ring (tracing.LogRing, capacity declared "
    "as the tracing.logring channel): installed at Node bootstrap "
    "next to the JSON formatter, it keeps the newest trace/span-"
    "stamped records in-process so incident bundles can freeze a log "
    "tail instead of pointing at unrecoverable stderr.")

declare(
    "SDTPU_PROFILE", None, parse_str,
    "Directory for a jax profiler trace; set → device_span() regions "
    "are captured (tracing.py; probed once per process, "
    "reset_profiler_cache() re-arms).")

declare(
    "SDTPU_RACE_GUARD", "auto", lambda v: v.strip().lower(),
    "Cross-thread race recorder (threadctx.py, armed with the "
    "sanitizer): declared owner classes record (thread id, held "
    "lockset) per attribute write and flag data_race violations. "
    "`off` skips arming (zero overhead); `auto` follows "
    "SDTPU_SANITIZE. Read once at sanitize.install().")

declare(
    "SDTPU_RETRACE_GUARD", "auto", lambda v: v.strip().lower(),
    "jit retrace counter (ops/jit_registry.py, armed with the "
    "sanitizer): `off` disables cache-size accounting and the "
    "per-contract max_traces budget check; `auto` follows "
    "SDTPU_SANITIZE.")

declare(
    "SDTPU_SQL_AUDIT", "auto", lambda v: v.strip().lower(),
    "Runtime SQL auditor (store/sqlaudit.py, armed with the "
    "sanitizer): every executed statement is matched against the "
    "contract registry (store/statements.py) — undeclared statements "
    "and autocommit writes are sanitizer violations (raised in "
    "tier-1, counted in production). `off` skips arming (plain "
    "sqlite3 connections, zero overhead); `auto` follows "
    "SDTPU_SANITIZE. Read once at sanitize.install().")

declare(
    "SDTPU_SQL_EXPLAIN", 0, parse_int,
    "EXPLAIN-sampling period of the runtime SQL auditor: every Nth "
    "execution of a declared read over a registered large table runs "
    "EXPLAIN QUERY PLAN, and full-table scans count into "
    "sd_sql_scan_total{name}. 0 (default) disables sampling.",
    strict=True)

declare(
    "SDTPU_SANITIZE", False, parse_flag1,
    "Opt-in runtime sanitizer (sanitize.py): event-loop stall "
    "detector, lock-order cycle check, write-lock-held-across-await "
    "assertion. Tier-1 runs with it on.")

declare(
    "SDTPU_SANITIZE_MODE", "count", lambda v: v.strip().lower(),
    "`raise` (tests): a detected violation raises at the detection "
    "point; `count` (production): violations only increment "
    "sd_sanitize_* telemetry and record into sanitize.violations().")

declare(
    "SDTPU_SANITIZE_STALL_S", 1.0, parse_float,
    "Event-loop stall threshold in seconds: one callback/task step "
    "hogging the loop longer than this is a sanitizer violation.")

declare(
    "SDTPU_SHARDED_CAS", "auto", lambda v: v.strip().lower(),
    "`off` pins the single-device CAS program even on multi-device "
    "hosts (ops/blake3_jax.py; the CPU-mesh test suite sets it to "
    "dodge a ~50s shard_map compile per batch grid).")

declare(
    "SDTPU_SPAN_RING", 512, parse_int,
    "Capacity of the tracing span ring buffer (tracing.py "
    "recent_spans / node.spans / the flight-recorder export). Read "
    "once at import — the ring is module-global; "
    "tracing.configure_span_ring() re-reads it for tests/embedders.",
    strict=True)

declare(
    "SDTPU_STAGE_NATIVE", "auto", lambda v: v.strip().lower(),
    "Packed native staging backend for the device CAS pipeline "
    "(ops/staging.py stage_batch_native → native sd_stage_batch): "
    "`auto`/`on` stage whole batches straight into pooled page-aligned "
    "buffers in the kernel's message layout when libsdio.so is "
    "available; `off` forces the classic stage_files + "
    "build_cas_messages host path with PURE-PYTHON readers (the "
    "classic path's own native pread helpers are pinned off too — "
    "one flag, the whole native staging plane). Fails closed to the "
    "Python path "
    "when the shared object is missing, per-file on bad rows "
    "(ENOENT/EACCES/short read). Read per batch, so a process can A/B "
    "the backends.")

declare(
    "SDTPU_STAGE_POOL_BUFFERS", 0, parse_int,
    "Cap on the staging buffer pool (ops/staging.py StagePool): "
    "pooled page-aligned H2D source pages live-recycled at batch "
    "retirement. 0 = the declared ops.stage.pool channel capacity "
    "(the registry ceiling); a positive value narrows below it — it "
    "never raises it. When the pool is exhausted the batch degrades "
    "to the Python staging path instead of allocating past the bound.",
    strict=True)

declare(
    "SDTPU_STORE_ACTOR", True, parse_onoff,
    "Kill switch for the per-library single-writer group-commit actor "
    "(store/actor.py): `off` degrades Database.write_tx() to the raw "
    "serialized tx() path — one commit per caller, no coalescing — "
    "which is how load_bench measures the before/after write-path "
    "attribution. Read per write_tx entry, so benches can flip it "
    "mid-process.")

declare(
    "SDTPU_STORE_GROUP_LATENCY_S", 0.004, parse_float,
    "Group-commit latency bound of the storage write actor "
    "(store/actor.py): once a group is open, how long the writer "
    "thread waits for more batches to coalesce before committing. "
    "Small = snappier single writers; large = fatter transactions "
    "under storm. The bound is a wait-for-MORE-work budget — a "
    "running batch body never counts against it.")

declare(
    "SDTPU_STORE_GROUP_MAX", 32, parse_int,
    "Group-commit size bound of the storage write actor "
    "(store/actor.py): at most this many queued write batches "
    "coalesce into one fat transaction before the actor commits "
    "(sd_store_group_size records what it actually achieves).",
    strict=True)

declare(
    "SDTPU_STORE_READ_POOL", 4, parse_int,
    "Idle read-only connections the per-library pool keeps warm "
    "(store/db.py): reads borrow a query_only connection instead of "
    "minting one per thread, so concurrent readers stop serializing "
    "on (and stop multiplying) the writer's WAL connection. Borrows "
    "past the cap open a transient connection that closes on "
    "release.", strict=True)

declare(
    "SDTPU_TASK_REAP_S", 5.0, parse_float,
    "Grace period the task supervisor's shutdown reap (tasks.py, "
    "driven by Node.shutdown) waits for cancelled tasks before "
    "declaring them orphaned (a sanitizer violation).")

declare(
    "SDTPU_TELEMETRY", True, parse_onoff,
    "Kill switch for the node-wide metrics registry (telemetry.py): "
    "`off` reduces every increment to one flag check.")

declare(
    "SDTPU_TELEMETRY_INTERVAL", 15.0, parse_float,
    "Seconds between periodic TelemetrySnapshot events on the node "
    "event bus (node.py TelemetryReporter).")

declare(
    "SDTPU_TIMEOUT_SCALE", 1.0, parse_float,
    "Global multiplier over every declared network-await budget "
    "(timeouts.py registry; README's generated timeout table lists "
    "the per-site defaults).")

declare(
    "SDTPU_TRANSFER_GUARD", "auto", lambda v: v.strip().lower(),
    "JAX device-to-host transfer guard inside device_scope()/io() "
    "regions (ops/jit_registry.py, armed with the sanitizer): `auto` "
    "follows SDTPU_SANITIZE_MODE (raise -> disallow, count -> log), "
    "`raise`/`log` force a level, `off` disables.")

declare(
    "SDTPU_VAL_BATCH_BYTES", None, parse_int,
    "Device-validator batch budget in bytes (objects/validator.py); "
    "unset uses the 64 MiB default sized for PCIe/ICI links.",
    strict=True)

declare(
    "SDTPU_WATCHER", "", lambda v: v.strip().lower(),
    "`poll` forces the polling watcher fallback even where inotify is "
    "available (locations/watcher.py; how Linux CI exercises it).")

declare(
    "SDTPU_WIRE_AUDIT", "auto", lambda v: v.strip().lower(),
    "Runtime wire auditor (p2p/wire.py, armed with the sanitizer): "
    "every frame crossing the pack/unpack seam — both tunnel "
    "directions and the stub transports' pack calls — is matched "
    "against its declared message contract; an undeclared kind, a "
    "schema mismatch, a size-cap breach, or a version-const skew is "
    "a `wire_violation` (raised in tier-1, counted in production, "
    "sd_wire_violations_total{kind}). `off` skips arming (pack/"
    "unpack still validate, zero audit overhead); `auto` follows "
    "SDTPU_SANITIZE. Read once at sanitize.install().")
