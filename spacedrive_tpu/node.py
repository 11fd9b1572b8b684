"""Node bootstrap: construct every service in order, own the event bus.

Mirrors `Node::new` (/root/reference/core/src/lib.rs:58-144): config
manager → libraries → job manager → (p2p later), with the library-load
hook wiring cold-resume, exactly the ordering the reference marks
ordering-sensitive (lib.rs:134-138). The event bus is the CoreEvent
channel (api/mod.rs:17-23) as a plain callback fan-out.
"""

from __future__ import annotations

import asyncio
import json
import os
import time
import uuid as uuidlib
from typing import Any, Callable, Dict, List, Optional

from . import flags, persist, tasks, telemetry, tracing
from .fleet import FleetMonitor
from .health import HealthMonitor
from .jobs.manager import JobManager
from .library import Libraries, Library
from .store.db import uuid_bytes

NODE_CONFIG_VERSION = 1
NODE_CONFIG_NAME = "node_state.sdconfig"


class EventBus:
    """CoreEvent fan-out: JobProgress / JobUpdate / InvalidateOperation.

    Delivery discipline (round 12): in-process subscribers are
    SYNCHRONOUS callbacks on purpose — every registered callback is a
    cheap filter (the api procedures' on_event closures), so the emit
    loop holds no buffer at all and cannot grow one. The moment
    delivery crosses to a consumer that can stall — every websocket
    subscription — it goes through a bounded registry channel instead
    (api/server.py WsSubscriptionPump, channels.py `api.ws`):
    per-subscriber depth capped, TelemetrySnapshot frames coalesced to
    the newest, slow consumers shed into sd_chan_shed_total{api.ws}.
    A callback that does heavy work inline would show up as a
    loop_stall sanitizer violation, which is the enforcement half of
    this contract."""

    def __init__(self):
        self._subs: List[Callable[[dict], None]] = []

    def subscribe(self, cb: Callable[[dict], None]) -> Callable[[], None]:
        self._subs.append(cb)
        return lambda: self._subs.remove(cb)

    def emit(self, event: dict) -> None:
        for cb in list(self._subs):
            try:
                cb(event)
            except Exception:
                pass

    def invalidate_query(self, library_id, key: str) -> None:
        """invalidate_query! macro semantics (api/utils/invalidate.rs:131)."""
        self.emit({"type": "InvalidateOperation",
                   "library_id": str(library_id), "key": key})


def migrate_node_config(raw: dict) -> dict:
    """Versioned config migrator (util/migrator.rs:33-41 semantics):
    upgrade step by step from raw['version'] to NODE_CONFIG_VERSION."""
    version = raw.get("version", 0)
    if version > NODE_CONFIG_VERSION:
        raise ValueError(
            f"config version {version} is newer than supported "
            f"{NODE_CONFIG_VERSION} (time traveling backwards?)")
    while version < NODE_CONFIG_VERSION:
        if version == 0:
            raw.setdefault("id", uuidlib.uuid4().hex)
            raw.setdefault("name", "spacedrive-tpu-node")
            raw.setdefault("features", [])
        version += 1
        raw["version"] = version
    return raw


class NodeConfig:
    """node_state.sdconfig (node/config.rs:22-43)."""

    def __init__(self, path: str):
        self.path = path
        if os.path.exists(path):
            with open(path) as f:
                raw = json.load(f)
        else:
            raw = {}
        raw = migrate_node_config(raw)
        self.raw = raw
        self.save()

    @property
    def id(self) -> bytes:
        return bytes.fromhex(self.raw["id"])

    @property
    def name(self) -> str:
        return self.raw["name"]

    @property
    def features(self) -> List[str]:
        return list(self.raw.get("features", []))

    def toggle_feature(self, feature: str) -> bool:
        """BackendFeature toggle (api/mod.rs:28-48); returns new state."""
        feats = set(self.raw.get("features", []))
        if feature in feats:
            feats.remove(feature)
            enabled = False
        else:
            feats.add(feature)
            enabled = True
        self.raw["features"] = sorted(feats)
        self.save()
        return enabled

    def save(self) -> None:
        persist.atomic_write("node.config", self.path,
                             json.dumps(self.raw, indent=2))


class OrphanRemover:
    """Deletes objects with zero file_paths; 1-minute tick or on demand
    (core/src/object/orphan_remover.rs:17-40)."""

    TICK_S = 60

    def __init__(self, library: Library, owner: str = "orphan-remover"):
        self.library = library
        self._owner = owner
        self._task: Optional[asyncio.Task] = None

    def invoke(self) -> int:
        db = self.library.db
        rows = db.run("node.orphan_objects")
        if not rows:
            return 0
        sync = self.library.sync
        from .sync.manager import cascade_local_fks

        ops = [sync.shared_delete("object", r["pub_id"]) for r in rows]
        with sync.write_ops(ops) as conn:
            for r in rows:
                # membership rows (tags/labels/albums/spaces) have no
                # DDL ON DELETE — a raw delete would FK-fail and abort
                # the whole batch (round-5 review finding)
                cascade_local_fks(conn, "object", r["id"])
                db.run("node.object_delete", (r["id"],), conn=conn)
        return len(rows)

    def start(self) -> None:
        async def loop():
            while True:
                await asyncio.sleep(self.TICK_S)
                await asyncio.to_thread(self.invoke)
        self._task = tasks.spawn(
            f"orphan/{self.library.id.hex[:8]}", loop(), owner=self._owner)

    def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None


class TelemetryReporter:
    """Periodic TelemetrySnapshot events on the node event bus: the
    webui's (and any subscriber's) push-based view of the metrics
    registry — the same snapshot `node.metrics` serves on demand.
    Interval from SDTPU_TELEMETRY_INTERVAL seconds (default 15); the
    loop skips emission entirely while telemetry is disabled."""

    DEFAULT_INTERVAL_S = 15.0

    def __init__(self, events: EventBus,
                 interval_s: Optional[float] = None,
                 owner: str = "telemetry-reporter"):
        self.events = events
        if interval_s is None:
            interval_s = flags.get("SDTPU_TELEMETRY_INTERVAL")
        self.interval_s = max(0.05, interval_s)
        self._owner = owner
        self._task: Optional[asyncio.Task] = None

    def emit_snapshot(self) -> None:
        self.events.emit({
            "type": "TelemetrySnapshot",
            "ts": time.time(),
            "metrics": telemetry.snapshot(),
        })

    def start(self) -> None:
        async def loop():
            while True:
                await asyncio.sleep(self.interval_s)
                if telemetry.enabled():
                    self.emit_snapshot()
        if self._task is None:
            self._task = tasks.spawn(
                "telemetry-reporter", loop(), owner=self._owner)

    def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None


class LoopLagProbe:
    """Event-loop lag: one task that sleeps a fixed INTERVAL_S and
    observes how late it wakes into sd_event_loop_lag_seconds. Lateness
    is time the loop spent in other callbacks past the due time — an
    rspc handler running on the loop (search.paths) shows here before
    any client sees its tail. Ten wake-ups a second; always on."""

    INTERVAL_S = 0.1

    def __init__(self, owner: str = "loop-lag"):
        self._owner = owner
        self._task: Optional[asyncio.Task] = None

    def start(self) -> None:
        async def loop():
            clock = asyncio.get_running_loop().time
            while True:
                due = clock() + self.INTERVAL_S
                await asyncio.sleep(self.INTERVAL_S)
                telemetry.EVENT_LOOP_LAG.observe(max(0.0, clock() - due))
        if self._task is None:
            self._task = tasks.spawn("loop-lag", loop(), owner=self._owner)

    def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None


class Node:
    def __init__(self, data_dir: str):
        # Production nodes honor SDTPU_SANITIZE=1 too: violations count
        # into sd_sanitize_* telemetry (mode `count`) instead of
        # raising. No-op (and zero overhead) when the flag is unset.
        from . import sanitize
        sanitize.install()
        # SDTPU_LOG_JSON: trace-correlated structured logging — a
        # no-op when the flag is off, one handler per process when on.
        tracing.install_json_logging()
        # SDTPU_LOG_RING (default on): bounded in-memory log ring so
        # incident bundles can freeze a trace-stamped log tail.
        tracing.install_log_ring()
        self.data_dir = os.path.abspath(data_dir)
        os.makedirs(self.data_dir, exist_ok=True)
        self.config = NodeConfig(os.path.join(self.data_dir, NODE_CONFIG_NAME))
        # Root of this node's supervisor ownership tree (tasks.py):
        # every long-lived component spawns under it, shutdown() reaps
        # it. Process-unique so two nodes in one test never cross-reap.
        self.task_owner = tasks.unique_owner("node")
        self.events = EventBus()
        self.libraries = Libraries(self.data_dir)
        self.jobs = JobManager(
            on_event=self.events.emit,
            services={"data_dir": self.data_dir, "node": self},
            owner=f"{self.task_owner}/jobs",
        )
        self.orphan_removers: Dict[uuidlib.UUID, OrphanRemover] = {}
        self.telemetry_reporter = TelemetryReporter(
            self.events, owner=f"{self.task_owner}/reporter")
        # Health observatory (health.py): delta-samples every metric
        # family into bounded rings and attributes saturation; serves
        # node.health and the sd_health_state{subsystem} gauges.
        self.health = HealthMonitor(
            self.events, owner=f"{self.task_owner}/health",
            node_id=self.config.id.hex(), node_name=self.config.name)
        # Fleet observatory (fleet.py): polls paired peers' obs.health
        # snapshots into bounded rings and merges the per-(node,
        # subsystem) fleet view; serves fleet.health / fleet.metrics /
        # fleet.trace.export.
        self.fleet = FleetMonitor(self, owner=f"{self.task_owner}/fleet")
        self.loop_lag = LoopLagProbe(owner=f"{self.task_owner}/loop-lag")
        # Incident observatory (incidents.py): the always-on black
        # box. install() is process-global and idempotent (first node
        # wins, like the sanitizer); recovery of a prior crash's
        # partially-written bundle happens here, before any trigger
        # can fire. SDTPU_INCIDENTS=off → None.
        from . import incidents
        self.incidents = incidents.install(
            dir_path=os.path.join(self.data_dir, "incidents"),
            monitor=self.health, events=self.events,
            node_id=self.config.id.hex(), node_name=self.config.name)
        self.p2p = None  # created by start_p2p (P2PManager)
        # Thumbnailer actor (lib.rs:116 Thumbnailer::new): constructed at
        # bootstrap (cache version migration runs here), loop starts with
        # the node.
        from .media.actor import Thumbnailer
        self.thumbnailer = Thumbnailer(self)
        self._started = False
        self.libraries.on_event(self._on_library_event)
        # Warm the native I/O plane at bootstrap (may compile libsdio.so
        # once) so watcher-triggered hot paths never hit a cold build.
        from . import native as _native
        _native.available()

    # -- lifecycle (ordering-sensitive: lib.rs:134-138) --------------------

    async def start(self) -> None:
        """Load libraries, cold-resume their interrupted jobs, start
        actors."""
        self._started = True
        self.thumbnailer.start()
        try:
            self.telemetry_reporter.start()
            self.health.start()
            self.fleet.start()
            self.loop_lag.start()
        except RuntimeError:
            pass  # no running loop (sync tests); node.metrics and the
            # on-demand node.health / fleet.health samples still work
        self.libraries.init()
        # Dev seed (util/debug_initializer.rs): data-dir init.json.
        # BEFORE cold_resume so reset_on_startup never deletes a library
        # whose interrupted jobs were just re-dispatched; errors are
        # contained — a bad seed file must not become a boot loop.
        from .debug_init import apply_init_file

        try:
            await apply_init_file(self)
        except Exception as e:
            self.events.emit({"type": "DebugInitError", "error": str(e)})
        for lib in self.libraries.list():
            # one resume sweep per LIBRARY — each is its own database
            await self.jobs.cold_resume(lib)  # sdlint: ok[tx-shape]
            self._ensure_actors(lib)

    def _on_library_event(self, kind: str, library: Library) -> None:
        if kind == "load":
            self._ensure_actors(library)
        elif kind == "delete":
            remover = self.orphan_removers.pop(library.id, None)
            if remover:
                remover.stop()
        # query invalidation for the frontend
        self.events.invalidate_query(library.id, "library.list")

    def _ensure_actors(self, library: Library) -> None:
        if library.id not in self.orphan_removers:
            remover = OrphanRemover(
                library, owner=f"{self.task_owner}/orphan-remover")
            try:
                remover.start()
            except RuntimeError:
                pass  # no running loop (sync tests); invoke() still works
            self.orphan_removers[library.id] = remover

    async def start_p2p(self, host: str = "0.0.0.0", port: int = 0,
                        enable_discovery: bool = True) -> int:
        """Bring up the p2p plane: listener + discovery + the
        NetworkedLibraries sync fan-out (lib.rs:102 P2PManager::new +
        p2p.start at :138). Returns the bound port."""
        from .p2p.manager import P2PManager
        from .p2p.sync_net import NetworkedLibraries

        if self.p2p is None:
            self.p2p = P2PManager(self, enable_discovery=enable_discovery)
            NetworkedLibraries(self, self.p2p)
        if self.p2p.server is not None:
            return self.p2p.port  # already listening; don't double-bind
        return await self.p2p.start(host, port)

    async def shutdown(self) -> None:
        """Node::shutdown (lib.rs:205): pause jobs, stop actors, then
        reap the supervisor subtree as the backstop — anything a
        component forgot (a mid-flight origin fan-out, a watcher scan,
        an auth poll whose subscriber vanished) is cancelled-and-
        gathered by ownership tree BEFORE the library DBs close, so
        cancellation cleanup can still write. A task that survives the
        reap grace is an orphan: counted in sd_task_orphaned_total and
        raised as a sanitizer violation in tier-1."""
        await self.jobs.shutdown()
        self.telemetry_reporter.stop()
        self.health.stop()
        self.fleet.stop()
        self.loop_lag.stop()
        await self.thumbnailer.stop()
        if self.p2p is not None:
            await self.p2p.stop()
        for remover in self.orphan_removers.values():
            remover.stop()
        try:
            await tasks.reap(self.task_owner)
        finally:
            # The DBs close even when the reap raises on an orphan
            # (raise mode): an aborted shutdown must not leak open
            # library handles on top of the orphaned task.
            for lib in self.libraries.list():
                lib.db.close()
            # The shared staging executor (ops/staging.py) is module-
            # global — threads the supervisor reap cannot see. Close it
            # explicitly (off-loop: the close waits for in-flight
            # reads; shielded so a cancelled shutdown still completes
            # the pool close instead of abandoning it half-torn-down);
            # a later identify in this process just re-creates it, so
            # multi-node tests stay correct.
            from .ops import staging as _staging
            await asyncio.shield(
                asyncio.to_thread(_staging.shutdown_stage_pool))

    async def close(self) -> None:
        """Alias for shutdown() — the supervisor docs' name for the
        reap edge."""
        await self.shutdown()

    # -- convenience -------------------------------------------------------

    def create_library(self, name: str, lib_id=None) -> Library:
        lib = self.libraries.create(
            name, node_name=self.config.name, node_pub_id=self.config.id,
            lib_id=lib_id)
        return lib
