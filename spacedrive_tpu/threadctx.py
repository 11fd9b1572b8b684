"""Shared-state ownership registry — the thread-safety twin of
channels.py's capacity table and timeouts.py's budget table.

Every class whose methods are reachable from more than one THREAD
CONTEXT (the event loop, `asyncio.to_thread`/executor submit targets,
the ops/staging.py pool workers, atexit/signal shutdown hooks)
DECLARES a contract per mutable attribute here — which thread(s) may
write it and under which lock. An earlier multi-stream identify
pipeline burned two review rounds hand-fixing exactly this bug class: a
stats object's plain `+=` lost updates at more than one device stream,
and the stage-pool gauge clobbered across a concurrent pool swap.
With the contracts machine-readable, tools/sdlint checks them
statically (shared-mutation / thread-boundary / guard-consistency
passes) and this module checks them dynamically (the
`__setattr__`/container write recorder armed by `sanitize.install()`).

Contract kinds (one per attribute):

- ``loop_only``             — written only from the event-loop thread
  (ws pumps, channel internals, sync-net bookkeeping).
- ``single_thread``         — written from exactly one thread, whichever
  thread first writes it (bench stats finalized by their driver).
- ``guarded_by("<lock>")``  — every post-init write holds the named
  lock attribute of the same instance (the store/telemetry idiom).
- ``atomic_counter``        — a statistics counter deliberately updated
  with bare `+=` from multiple threads: the declaration is a VISIBLE
  waiver that a lost update only skews a statistic, never corrupts
  state. The static pass allows only augmented numeric updates; the
  runtime twin counts its writes but never raises.
- ``immutable_after_init``  — bound during construction, then frozen
  (config snapshots, contract records).

Runtime twin (armed by `sanitize.install()` unless
`SDTPU_RACE_GUARD=off`): each declared class's `__setattr__` is wrapped
to record (thread id, held tracked-lock set) per post-init write, and
declared list/dict/set attributes are wrapped so in-place container
mutation records too. Writes to one attribute from two or more threads
with an EMPTY lockset intersection — or any second-thread write to a
`loop_only`/`single_thread` attribute, or any post-init write to an
`immutable_after_init` one — raise a ``data_race`` sanitizer violation
in tier-1 (`raise` mode) and count into
`sd_race_candidates_total{cls_attr}` in production (`count` mode);
every tracked write counts into `sd_race_tracked_writes_total`.
Lockset membership comes from two sources: the sanitizer's tracked-lock
stack (store locks), and — for `guarded_by` attrs — the named guard
object itself reporting `locked()` at the write, so plain
`threading.Lock` guards participate without migrating to tracked locks.

Disarmed cost is ZERO: no class is wrapped until `arm()` runs, so
production default (`SDTPU_SANITIZE` unset) never sees the recorder.

This module also owns the ONE sanctioned cross-thread loop hand-off,
`call_threadsafe(loop, cb, *args)`: the raw
`loop.call_soon_threadsafe(...)` idiom crashes the posting executor
thread with `RuntimeError: Event loop is closed` when shutdown wins the
race; the helper swallows exactly that shape (counting it into
`sd_race_handoff_closed_total`) and re-raises everything else. The
thread-boundary pass treats this helper — and the raw
`call_soon_threadsafe`/`run_coroutine_threadsafe` primitives — as the
sanctioned shapes for loop-affine calls from executor threads.

Design constraints (same as flags.py / timeouts.py / channels.py):
stdlib + flags/telemetry only, importable from every layer without
cycles. The classes a contract points at are imported lazily at arm
time, never at module import.
"""

from __future__ import annotations

import importlib
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

from . import flags
from .telemetry import (
    RACE_CANDIDATES,
    RACE_HANDOFF_CLOSED,
    RACE_TRACKED_WRITES,
)

__all__ = [
    "AttrContract", "OwnerContract", "CONTRACTS", "declare_owner",
    "loop_only", "single_thread", "guarded_by", "atomic_counter",
    "immutable_after_init", "arm", "disarm", "armed", "armed_classes",
    "call_threadsafe", "temporary_owner", "owner_table_markdown",
]

KINDS = ("loop_only", "single_thread", "guarded_by", "atomic_counter",
         "immutable_after_init")


@dataclass(frozen=True)
class AttrContract:
    kind: str
    lock: Optional[str] = None  # guard attr name for guarded_by


def loop_only() -> AttrContract:
    return AttrContract("loop_only")


def single_thread() -> AttrContract:
    return AttrContract("single_thread")


def guarded_by(lock: str) -> AttrContract:
    if not lock:
        raise ValueError("guarded_by needs a lock attribute name")
    return AttrContract("guarded_by", lock)


def atomic_counter() -> AttrContract:
    return AttrContract("atomic_counter")


def immutable_after_init() -> AttrContract:
    return AttrContract("immutable_after_init")


@dataclass(frozen=True)
class OwnerContract:
    name: str                       # dotted id: "<module>.<Class>"
    site: str                       # "path/to/file.py::ClassName"
    attrs: Mapping[str, AttrContract]
    doc: str


CONTRACTS: Dict[str, OwnerContract] = {}


def declare_owner(name: str, site: str,
                  attrs: Mapping[str, AttrContract],
                  doc: str = "") -> OwnerContract:
    if name in CONTRACTS:
        raise ValueError(f"owner {name!r} declared twice")
    if "::" not in site:
        raise ValueError(f"owner {name!r}: site must be "
                         "'path/to/file.py::ClassName'")
    cls_name = site.split("::", 1)[1]
    for other in CONTRACTS.values():
        if other.site.split("::", 1)[1] == cls_name:
            raise ValueError(
                f"owner {name!r}: class name {cls_name!r} already "
                f"claimed by {other.name!r} — the static pass resolves "
                "receivers by class name, which must stay unique")
    for attr, c in attrs.items():
        if c.kind not in KINDS:
            raise ValueError(f"owner {name!r}.{attr}: unknown contract "
                             f"kind {c.kind!r}")
    oc = OwnerContract(name, site, dict(attrs), doc)
    CONTRACTS[name] = oc
    return oc


# -- runtime twin -----------------------------------------------------------

_armed = False
_record: Optional[Callable[[str, str, bool], None]] = None
_held_fn: Optional[Callable[[], List[str]]] = None
# cls → (had_own_setattr, orig_setattr, merged_attr_contracts)
_wrapped: Dict[type, Tuple[bool, Any, Dict[str, AttrContract]]] = {}
_tls = threading.local()

_STATE_ATTR = "_sdtpu_write_state"


def armed() -> bool:
    return _armed


def armed_classes() -> List[type]:
    return list(_wrapped)


def _resolve_site(site: str) -> type:
    path, cls_name = site.split("::", 1)
    module = path[:-3].replace("/", ".") if path.endswith(".py") else path
    mod = importlib.import_module(module)
    cls = getattr(mod, cls_name)
    if not isinstance(cls, type):
        raise TypeError(f"site {site!r} resolves to {cls!r}, not a class")
    return cls


class _WriteState:
    """Per-(instance, attr) write history: writer thread ids and the
    running intersection of locksets held at each write. Mutated
    lock-free — set.add and slot rebinds are effectively atomic under
    the GIL, and a lost lockset narrowing only makes the detector
    miss, never false-positive harder."""

    __slots__ = ("threads", "common")

    def __init__(self, tid: int, locks: frozenset):
        self.threads = {tid}
        self.common = locks


def _locks_now(obj: Any, c: AttrContract) -> frozenset:
    held = frozenset(_held_fn()) if _held_fn is not None else frozenset()
    if c.kind == "guarded_by":
        guard: Any = obj
        for part in c.lock.split("."):  # "db._write_lock" chains
            guard = getattr(guard, part, None)
            if guard is None:
                break
        locked = getattr(guard, "locked", None)
        if locked is not None:
            try:
                is_held = bool(locked())
            except Exception:  # RLock.locked absent on older runtimes
                is_held = False
            if is_held:
                # The named guard participates even when it is a plain
                # threading.Lock: locked() at the write means this
                # (or, rarely, a racing) thread holds it — good
                # enough for a sanitizer whose static half pins the
                # bare-write shape.
                held |= {f"{c.lock}#{id(guard)}"}
    return held


def _note_write(obj: Any, cls_name: str, attr: str,
                c: AttrContract) -> None:
    if getattr(_tls, "busy", False) or obj is RACE_TRACKED_WRITES:
        # The recorder's own metrics must not re-enter it: a registry
        # reset zeroes RACE_TRACKED_WRITES while holding its lock, and
        # counting that write would take the same lock again.
        return
    _tls.busy = True
    try:
        RACE_TRACKED_WRITES.inc()
        state = obj.__dict__.get(_STATE_ATTR)
        if state is None:
            state = {}
            object.__setattr__(obj, _STATE_ATTR, state)
        tid = threading.get_ident()
        locks = _locks_now(obj, c)
        rec = state.get(attr)
        if rec is None:
            state[attr] = _WriteState(tid, locks)
            if c.kind != "immutable_after_init":
                return
            rec = state[attr]
        else:
            rec.threads.add(tid)
            rec.common = rec.common & locks
        racy = False
        if c.kind == "immutable_after_init":
            racy = True  # any post-init write mutates a frozen attr
        elif len(rec.threads) >= 2:
            if c.kind in ("loop_only", "single_thread"):
                racy = True
            elif c.kind == "guarded_by" and not rec.common:
                racy = True
            # atomic_counter: multi-thread bare increments are the
            # declared, visible waiver — counted, never raised.
        if racy:
            RACE_CANDIDATES.labels(cls_attr=f"{cls_name}.{attr}").inc()
            if _record is not None:
                _record(
                    "data_race",
                    f"{cls_name}.{attr} ({c.kind}"
                    + (f" {c.lock!r}" if c.lock else "")
                    + f") written from {len(rec.threads)} thread(s) "
                    f"with lockset intersection "
                    f"{sorted(rec.common) or '{}'}",
                    True)
    finally:
        _tls.busy = False


# -- tracked containers -----------------------------------------------------
# Declared list/dict/set attributes are replaced (at assignment time,
# while armed) with subclasses whose mutators record like __setattr__
# does — `self._counts[i] += 1` and `stats.samples.append(...)` are
# writes too. deque/custom containers are NOT wrapped (the registry
# channels already meter themselves); the static pass still sees their
# mutation sites.
#
# CONSTRAINT: the wrap is a tracked COPY, so assigning a container to
# a declared attr transfers ownership — a caller that keeps mutating
# its own reference afterwards (`rows = []; stats.samples = rows;
# rows.append(x)`) diverges from the attribute under an armed
# sanitizer. No declared site aliases this way (they assign literals
# or field defaults); keep it that way when declaring new container
# attrs.

def _tracking(cls_name: str, attr: str, c: AttrContract):
    def note(self) -> None:
        owner = self._sdtpu_owner
        if owner is not None and _armed:
            _note_write(owner, cls_name, attr, c)
    return note


def _wrap_container(value: Any, owner: Any, cls_name: str, attr: str,
                    c: AttrContract) -> Any:
    base = None
    if type(value) is list:
        base = _TrackedList
    elif type(value) is dict:
        base = _TrackedDict
    elif type(value) is set:
        base = _TrackedSet
    if base is None:
        return value
    wrapped = base(value)
    wrapped._sdtpu_owner = owner
    wrapped._sdtpu_note = _tracking(cls_name, attr, c).__get__(wrapped)
    return wrapped


class _TrackedList(list):
    _sdtpu_owner: Any = None

    def _sdtpu_note(self):  # replaced per-instance
        pass

    def append(self, *a):
        self._sdtpu_note()
        return list.append(self, *a)

    def extend(self, *a):
        self._sdtpu_note()
        return list.extend(self, *a)

    def insert(self, *a):
        self._sdtpu_note()
        return list.insert(self, *a)

    def pop(self, *a):
        self._sdtpu_note()
        return list.pop(self, *a)

    def remove(self, *a):
        self._sdtpu_note()
        return list.remove(self, *a)

    def clear(self):
        self._sdtpu_note()
        return list.clear(self)

    def __setitem__(self, *a):
        self._sdtpu_note()
        return list.__setitem__(self, *a)

    def __delitem__(self, *a):
        self._sdtpu_note()
        return list.__delitem__(self, *a)

    def __iadd__(self, other):
        self._sdtpu_note()
        list.extend(self, other)
        return self


class _TrackedDict(dict):
    _sdtpu_owner: Any = None

    def _sdtpu_note(self):
        pass

    def __setitem__(self, *a):
        self._sdtpu_note()
        return dict.__setitem__(self, *a)

    def __delitem__(self, *a):
        self._sdtpu_note()
        return dict.__delitem__(self, *a)

    def pop(self, *a):
        self._sdtpu_note()
        return dict.pop(self, *a)

    def popitem(self):
        self._sdtpu_note()
        return dict.popitem(self)

    def setdefault(self, *a):
        self._sdtpu_note()
        return dict.setdefault(self, *a)

    def update(self, *a, **kw):
        self._sdtpu_note()
        return dict.update(self, *a, **kw)

    def clear(self):
        self._sdtpu_note()
        return dict.clear(self)


class _TrackedSet(set):
    _sdtpu_owner: Any = None

    def _sdtpu_note(self):
        pass

    def add(self, *a):
        self._sdtpu_note()
        return set.add(self, *a)

    def discard(self, *a):
        self._sdtpu_note()
        return set.discard(self, *a)

    def remove(self, *a):
        self._sdtpu_note()
        return set.remove(self, *a)

    def pop(self):
        self._sdtpu_note()
        return set.pop(self)

    def clear(self):
        self._sdtpu_note()
        return set.clear(self)

    def update(self, *a):
        self._sdtpu_note()
        return set.update(self, *a)


# -- class wrapping ---------------------------------------------------------

def _make_setattr(cls: type, merged: Dict[str, AttrContract], orig):
    cls_name = cls.__name__

    def __setattr__(self, name, value):
        c = merged.get(name)
        if c is None or not _armed:
            orig(self, name, value)
            return
        first = name not in self.__dict__
        if not isinstance(value, (_TrackedList, _TrackedDict,
                                  _TrackedSet)):
            value = _wrap_container(value, self, cls_name, name, c)
        orig(self, name, value)
        if first:
            # The initializing write establishes the attr (dataclass
            # field defaults, __init__ bodies) — ownership tracking
            # starts at the first REBIND.
            return
        _note_write(self, cls_name, name, c)

    return __setattr__


def _wrap_class(cls: type, merged: Dict[str, AttrContract]) -> None:
    if cls in _wrapped:
        return
    had_own = "__setattr__" in cls.__dict__
    orig = cls.__setattr__
    _wrapped[cls] = (had_own, orig, merged)
    cls.__setattr__ = _make_setattr(cls, merged, orig)


def arm(mode: str,
        record: Callable[[str, str, bool], None],
        held_fn: Optional[Callable[[], List[str]]] = None) -> None:
    """Arm the write recorder over every declared class (called by
    sanitize.install; `SDTPU_RACE_GUARD=off` disables, `auto` follows
    the sanitizer). `record(kind, detail, may_raise)` is
    sanitize._record — the raise/count split is its decision; `held_fn`
    returns the calling thread's tracked-lock graph ids."""
    global _armed, _record, _held_fn
    del mode  # the record callback owns the raise/count split
    if flags.get("SDTPU_RACE_GUARD") == "off":
        return
    _record = record
    _held_fn = held_fn
    resolved: Dict[type, OwnerContract] = {}
    for oc in CONTRACTS.values():
        resolved[_resolve_site(oc.site)] = oc
    for cls in resolved:
        # Contracts compose down the MRO: a subclass of a declared base
        # (Gauge under Counter) inherits the base's attr contracts and
        # may add its own.
        merged: Dict[str, AttrContract] = {}
        for base in reversed(cls.__mro__):
            if base in resolved:
                merged.update(resolved[base].attrs)
        _wrap_class(cls, merged)
    _armed = True


def disarm() -> None:
    """Restore every wrapped class (tests). Instances keep any tracked
    containers already installed; with _armed False they record
    nothing."""
    global _armed, _record, _held_fn
    _armed = False
    _record = None
    _held_fn = None
    for cls, (had_own, orig, _merged) in _wrapped.items():
        if had_own:
            cls.__setattr__ = orig
        else:
            try:
                del cls.__setattr__
            except AttributeError:
                pass
    _wrapped.clear()


class temporary_owner:
    """Test scaffold: declare + wrap one class for the duration of a
    with-block (the seeded-race tests arm throwaway classes without
    touching the real registry)."""

    def __init__(self, cls: type, **attrs: AttrContract):
        self.cls = cls
        self.attrs = attrs

    def __enter__(self):
        if self.cls in _wrapped:
            # Silently no-opping here would test NOTHING, and __exit__
            # would then strip the REGISTRY's wrap for the rest of the
            # process — a quietly disarmed recorder is the worst
            # outcome a test scaffold can produce.
            raise RuntimeError(
                f"{self.cls.__name__} is already wrapped (declared in "
                "the central registry?) — temporary_owner is for "
                "throwaway test classes only")
        _wrap_class(self.cls, dict(self.attrs))
        return self.cls

    def __exit__(self, *exc):
        had_own, orig, _merged = _wrapped.pop(self.cls)
        if had_own:
            self.cls.__setattr__ = orig
        else:
            try:
                del self.cls.__setattr__
            except AttributeError:
                pass
        return False


# -- the sanctioned cross-thread loop hand-off ------------------------------

def call_threadsafe(loop, callback: Callable, *args) -> bool:
    """Post `callback(*args)` onto `loop` from any thread, tolerating a
    loop torn down mid-shutdown: the raw
    `loop.call_soon_threadsafe(...)` raises `RuntimeError: Event loop
    is closed` into the posting executor thread when shutdown wins the
    race (the old p2p/sync_net + api/server crash shape). Returns True
    when the callback was scheduled; a closed/absent loop returns False
    and counts into `sd_race_handoff_closed_total` (the work is
    shutdown-moot by definition: peers re-pull on reconnect, ws
    subscribers are gone). Any other RuntimeError re-raises — this
    helper swallows exactly the closed-loop shape, nothing else."""
    if loop is None or loop.is_closed():
        RACE_HANDOFF_CLOSED.inc()
        return False
    try:
        loop.call_soon_threadsafe(callback, *args)
    except RuntimeError as e:
        if "closed" not in str(e).lower():
            raise
        RACE_HANDOFF_CLOSED.inc()
        return False
    return True


def owner_table_markdown() -> str:
    """docs table: one row per declared owner class."""
    out = ["| Owner | Site | Attr contracts |", "| --- | --- | --- |"]
    for name in sorted(CONTRACTS):
        oc = CONTRACTS[name]
        kinds = ", ".join(
            f"`{a}`: {c.kind}" + (f"({c.lock})" if c.lock else "")
            for a, c in sorted(oc.attrs.items()))
        out.append(f"| `{name}` | `{oc.site}` | {kinds} |")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# THE ownership namespace. Keep alphabetical by name; every entry is
# enforced statically by the sdlint shared-mutation pass (an undeclared
# multi-context class, an undeclared mutable attribute, or a write that
# breaks its contract fails the build) and dynamically by the armed
# write recorder above. Sites must resolve (tests/test_threadctx.py
# pins static↔runtime parity and that every declared class is
# constructed somewhere in the tree).
# ---------------------------------------------------------------------------

declare_owner(
    "channels.Metered", "spacedrive_tpu/channels.py::_Metered",
    {
        "high_water": guarded_by("_hw_lock"),
    },
    "Depth/high-water accounting shared by Channel/Window/BoundedDict: "
    "instances are loop-affine, but the per-NAME high-water compare-"
    "and-set must stay monotone even under the threaded stress test, "
    "so it runs under the module-wide _hw_lock.")

declare_owner(
    "channels.BoundedDict", "spacedrive_tpu/channels.py::BoundedDict",
    {
        "_d": loop_only(),
    },
    "Registry LRU caches (p2p.route_cache): resolved and invalidated "
    "by loop-side p2p code only.")

declare_owner(
    "channels.Channel", "spacedrive_tpu/channels.py::Channel",
    {
        "_slots": loop_only(),
        "_keys": loop_only(),
        "_getters": loop_only(),
        "_space": loop_only(),
    },
    "Bounded channel internals: waiter futures are loop-affine by "
    "construction (the thread-boundary pass routes cross-thread "
    "producers through call_threadsafe). The nowait slot surface "
    "additionally tolerates GIL-atomic use from worker threads with "
    "no parked waiters — the jobs run-queue construction path and the "
    "threaded shed stress test — which the deque keeps exact.")

declare_owner(
    "channels.Window", "spacedrive_tpu/channels.py::Window",
    {
        "_depth": guarded_by("_depth_lock"),
    },
    "External-buffer depth tracker: the tunnel send_nowait window "
    "notes from its owning loop, while the staging buffer pool's "
    "window is noted from stage and retire executor threads — every "
    "depth mutation serializes on the window's internal _depth_lock leaf.")

declare_owner(
    "staging.StagePool", "spacedrive_tpu/ops/staging.py::StagePool",
    {
        "_free": guarded_by("_lock"),
        "_total": guarded_by("_lock"),
        "_high_water": guarded_by("_lock"),
    },
    "Native staging buffer pool: leases are acquired on the stage "
    "executor threads and released on the retirer, so the free list "
    "and allocation accounting all move under the pool's _lock leaf; "
    "occupancy is metered through the declared ops.stage.pool "
    "window.")

declare_owner(
    "timeouts.Backoff", "spacedrive_tpu/timeouts.py::Backoff",
    {
        "tries": single_thread(),
        "_gave_up_counted": single_thread(),
    },
    "One failing operation's retry-ladder state (timeouts.py "
    "declare_backoff registry): instances are strictly per-use-site — "
    "a commit retry lives inside one tx() call's thread, a "
    "RetrySchedule ladder belongs to its owning loop — so the ladder "
    "counter is single-thread by construction; distinct sites get "
    "distinct instances, never a shared one.")

declare_owner(
    "fleet.FleetMonitor", "spacedrive_tpu/fleet.py::FleetMonitor",
    {
        "_peers": guarded_by("_lock"),
        "_last": guarded_by("_lock"),
        "_task": guarded_by("_lock"),
    },
    "Fleet observatory poller: the supervised poll loop mutates the "
    "peer records and the cached merged view, while rspc handlers, "
    "the sd_top CLI, and bench embedders read them on demand — the "
    "peer map, last view, and task handle all move under the "
    "monitor's _lock leaf.")

declare_owner(
    "flight.FlightRecorder", "spacedrive_tpu/flight.py::FlightRecorder",
    {
        "ring": immutable_after_init(),
    },
    "Flight-recorder timeline ring: the identifier's hashing threads "
    "and its hash-ahead worker all record phases — every ring put "
    "runs under the recorder's _lock; the ring channel itself is "
    "bound at construction and never rebound.")

declare_owner(
    "health.HealthMonitor", "spacedrive_tpu/health.py::HealthMonitor",
    {
        "_cursors": guarded_by("_lock"),
        "_series": guarded_by("_lock"),
        "_prev_t": guarded_by("_lock"),
        "_last": guarded_by("_lock"),
        "_task": guarded_by("_lock"),
    },
    "Health observatory sampler: ticked by its supervised loop task, "
    "sampled on demand by rspc handlers and bench CLIs — per-series "
    "cursors, rings, and the cached snapshot all mutate under the "
    "monitor's _lock leaf.")

declare_owner(
    "incidents.IncidentObservatory",
    "spacedrive_tpu/incidents.py::IncidentObservatory",
    {
        "_index": guarded_by("_lock"),
        "_last_fired": guarded_by("_lock"),
        "_dedup": guarded_by("_lock"),
        "_store_bytes": guarded_by("_lock"),
        "_closed": guarded_by("_lock"),
        "_degraded_streak": guarded_by("_lock"),
    },
    "Incident observatory capture engine: triggers fire from the "
    "health sampler loop, backoff ladders on arbitrary threads, and "
    "the sanitizer's recording sites — the bundle index, dedup "
    "windows, store accounting, and degraded-streak map all move "
    "under the observatory's _lock leaf (health samples arrive from "
    "whichever thread asked the monitor to sample).")

declare_owner(
    "store.Database", "spacedrive_tpu/store/db.py::Database",
    {
        "_all_conns": guarded_by("_conns_lock"),
        "_closed": guarded_by("_conns_lock"),
        "_local": guarded_by("_conns_lock"),
        "_read_pool": guarded_by("_conns_lock"),
        "_commits": guarded_by("_write_lock"),
    },
    "The store: every job thread and the loop share one Database per "
    "library. Connection registration/teardown — and the read-only "
    "pool's borrow/release free-list — serialize on the _conns_lock "
    "leaf (the PR 1 deadlock fix); the WAL-check commit counter only "
    "moves inside a tx, which holds _write_lock.")

declare_owner(
    "store.WriteActor", "spacedrive_tpu/store/actor.py::WriteActor",
    {
        "_stopping": guarded_by("_lock"),
        "_thread": guarded_by("_lock"),
        "_q": guarded_by("_lock"),
        "groups": single_thread(),
        "batches": single_thread(),
    },
    "Per-library single-writer group-commit actor: every product "
    "writer enqueues tickets (producers + the stop path mutate the "
    "lifecycle flags under the actor's _lock/condition leaf), while "
    "the shard tallies are the writer thread's alone — group "
    "formation state itself lives in _run_group locals, and the "
    "ticket handshake events are the cross-thread edges.")

declare_owner(
    "sync.HLC", "spacedrive_tpu/sync/hlc.py::HLC",
    {
        "_last": guarded_by("_lock"),
    },
    "Hybrid logical clock: ticked from every op-writing thread; "
    "monotonicity IS the CRDT ordering guarantee, so _last only moves "
    "under its lock.")

declare_owner(
    "sync.SyncManager", "spacedrive_tpu/sync/manager.py::SyncManager",
    {
        "_instance_ids": guarded_by("_meta_lock"),
        "timestamps": guarded_by("_meta_lock"),
        "_solo": guarded_by("_meta_lock"),
        "_sync_indexes_ready": guarded_by("_meta_lock"),
        "_op_log_high": guarded_by("_meta_lock"),
        "_has_shared_tombstones": guarded_by("_meta_lock"),
        "_on_created": loop_only(),
    },
    "Per-library sync engine: the in-memory caches (watermark vector, "
    "instance map, solo flag, clone fast-path facts) are mutated from "
    "to_thread job steps, loop-side ingest, and pairing — all under "
    "the _meta_lock leaf. The created-callback list is loop-side "
    "component wiring.")

declare_owner(
    "telemetry.Counter", "spacedrive_tpu/telemetry.py::Counter",
    {
        "_value": guarded_by("_lock"),
    },
    "Counter/Gauge sample cell: inc/set from any thread (jobs workers, "
    "device streams, the loop) under the per-metric leaf lock.")

declare_owner(
    "telemetry.Histogram", "spacedrive_tpu/telemetry.py::Histogram",
    {
        "_counts": guarded_by("_lock"),
        "_sum": guarded_by("_lock"),
        "_count": guarded_by("_lock"),
    },
    "Histogram cells: observe() is one bisect + three adds under the "
    "metric lock, from any thread.")

declare_owner(
    "telemetry.Metric", "spacedrive_tpu/telemetry.py::_Metric",
    {
        "_children": guarded_by("_lock"),
    },
    "Label-child map: double-checked read, creation under the parent "
    "lock — child creation races resolve to one cached child.")

declare_owner(
    "telemetry.MetricsRegistry",
    "spacedrive_tpu/telemetry.py::MetricsRegistry",
    {
        "_metrics": guarded_by("_lock"),
    },
    "The process-global name → metric map: registration happens at "
    "import time from any importing thread.")
