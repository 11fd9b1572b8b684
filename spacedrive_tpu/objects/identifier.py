"""FileIdentifierJob: CAS-ID every orphan file_path, link/create objects.

The flagship hot path (SURVEY.md §3.3). Behavior mirrors the reference job
(/root/reference/core/src/object/file_identifier/file_identifier_job.rs:72-309
and mod.rs:100-331): cursor-paginated chunks of CHUNK_SIZE orphans
(object_id IS NULL, is_dir = 0), per chunk: compute kind + CAS ID, write
cas_ids via sync, link file_paths to existing objects matching by cas_id,
create objects for the rest.

TPU-first deviations:
- the per-chunk hashing is a *batched* staged pipeline
  (ops/staging.cas_ids_for_files) on the configured backend
  ("oracle" | "numpy" | "jax" | "auto") instead of per-file streaming;
- files in one chunk sharing a cas_id share ONE new object (the reference
  creates an object per file_path and only dedups against earlier chunks).
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from ..sync.crdt import OpKind, uuid4_bytes_batch

from .. import flags
from ..files import resolve_kind
from ..jobs.job import EarlyFinish, JobContext, StatefulJob, StepOutcome, register_job
from ..locations.file_path_helper import materialized_like, sub_path_children_mat
from ..locations.paths import IsolatedPath
from ..ops import jit_registry, staging
from ..ops.staging import cas_ids_for_files
from ..telemetry import IDENT_FILES, IDENT_PHASE_SECONDS, IDENT_PREFETCH_FAILURES

_log = logging.getLogger(__name__)

CHUNK_SIZE = 100  # file_identifier/mod.rs:36

# The identifier's one op per identified file: cas_id + object link
# together, per-field LWW on apply (sync/crdt.py OpKind.multi_update).
LINK_KIND = OpKind.multi_update(("cas_id", "object_id"))


def _usable_cpus() -> int:
    """CPUs this process may actually run on (Linux affinity masks and
    container quotas make os.cpu_count() a lie in pods)."""
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def _hash_ahead(device_engaged: bool) -> bool:
    """Whether the identifier stages and hashes chunk k+1 in a worker
    thread while chunk k's transaction commits.

    It needs a second USABLE core (affinity/cgroup-aware, not
    cpu_count): measured on a 1-core host it LOSES ~8% (WAL +
    synchronous=NORMAL commits don't fsync, so there is no IO wait to
    hide under — only GIL contention). On device-engaged runs it also
    needs buffer donation (SDTPU_DONATE_BUFFERS): donation is what makes
    a second in-flight device batch safe — the worker's stage+H2D+hash
    of chunk k+1 then no longer pins a second batch's device inputs
    against chunk k's in-flight dispatch, so the device stream stays fed
    through the whole db+commit phase. With donation off, device runs
    keep the serial shape."""
    return _usable_cpus() > 1 and (
        not device_engaged or bool(flags.get("SDTPU_DONATE_BUFFERS")))


def orphan_filters(location_id: int, cursor: int,
                   sub_mat_path: Optional[str]) -> Tuple[str, list]:
    """WHERE clause for orphan file_paths
    (orphan_path_filters, file_identifier_job.rs:245-270)."""
    where = ("object_id IS NULL AND is_dir = 0 AND location_id = ? "
             "AND id >= ?")
    params: list = [location_id, cursor]
    where = materialized_like(where, params, sub_mat_path)
    return where, params


def _in_chunks(seq: List, n: int = 900):
    for i in range(0, len(seq), n):
        yield seq[i:i + n]


def stage_file_list(rows: List[Dict[str, Any]], location_id: int,
                    location_path: str) -> List[Tuple[str, int]]:
    """Orphan rows → (absolute path, size) pairs for the staged hasher.

    Inlines IsolatedPath.from_db_row().join_on() — same string algebra
    (paths.py:112-154), minus one dataclass per file; ~4 µs/file
    matters at 1M. The sep check keeps non-POSIX parity."""
    files: List[Tuple[str, int]] = []
    base = os.fspath(location_path)
    sep_fix = os.sep != "/"
    # rel never starts with a separator (materialized_path[1:] strips
    # the leading "/"), so join(base, rel) is exactly base+sep+rel —
    # os.path.join's per-call scan was ~0.9 s of a 200k identify.
    if not base.endswith(os.sep):
        base += os.sep
    for r in rows:
        name = r["name"] or ""
        ext = r["extension"] or ""
        rel = (f"{r['materialized_path'][1:]}{name}.{ext}" if ext
               else f"{r['materialized_path'][1:]}{name}")
        if sep_fix:
            rel = rel.replace("/", os.sep)
        size = int.from_bytes(r["size_in_bytes_bytes"] or b"", "big")
        files.append((base + rel, size))
    return files


@dataclass
class TxBatch:
    """Per-transaction bookkeeping when the job batches several chunks
    into one commit (identify_chunk's `conn` mode): cas-map keys added
    inside the open transaction (popped back out if it rolls back) and
    the op count whose created-broadcast waits for the commit."""

    cas_added: List[str] = field(default_factory=list)
    n_ops: int = 0


def identify_chunk(library, location_id: int, location_path: str,
                   rows: List[Dict[str, Any]], backend: str = "auto",
                   timings: Optional[Dict[str, float]] = None,
                   prehashed: Optional[Tuple] = None,
                   cas_map: Optional[Dict[str, Tuple[int, bytes]]] = None,
                   conn=None, batch: Optional[TxBatch] = None,
                   ) -> Tuple[int, int, List[str]]:
    """The identifier's per-chunk kernel (identifier_job_step,
    mod.rs:100-331): batched CAS hashing, cas_id writes, object
    linking/creation — all through sync. Returns (linked, created,
    errors). Shared by the job and the shallow/watcher path.

    Without `conn`, all writes land in ONE transaction per chunk (the
    reference batches per pass, mod.rs:144/167/231; one atomic chunk is
    strictly tighter and 3× fewer commits), with executemany for the
    row loops so Python stays out of the per-file statement path. With
    `conn` + `batch`, the caller owns a transaction spanning SEVERAL
    chunks (FileIdentifierJob commit batching — WAL commit overhead
    amortizes across a step): domain+op writes land on that connection,
    cas-map additions are recorded in `batch.cas_added` so the caller
    can roll them back, and the created-broadcast is deferred to
    `batch.n_ops` until the caller commits. `timings` (optional)
    accumulates per-phase seconds: prep / hash / db / ops.

    `prehashed` = (files, ids, read_errors) from the job's hash-ahead
    pipeline (chunk i+1 staged+hashed in a worker thread while chunk
    i's transaction commits — CPU overlapping the fsync wait).

    `cas_map` (job-lifetime; updated as each chunk's writes land, keyed
    back out on rollback) trades the per-chunk in-tx probes for memory.
    Concurrency note: an object committed by ANOTHER writer (watcher
    shallow-identify, sync ingest) mid-run is invisible to the map, so
    the same content can transiently get a second object row — the
    dedup job collapses those, and the reference is strictly more
    duplicative (it creates an object per file_path within a chunk,
    mod.rs:231-331).
    """
    t = timings if timings is not None else {}

    def _mark(phase: str, t0: float) -> float:
        t1 = time.perf_counter()
        t[phase] = t.get(phase, 0.0) + (t1 - t0)
        return t1

    db, sync = library.db, library.sync
    tp = time.perf_counter()
    if prehashed is not None:
        files, ids, read_errors = prehashed
        tp = _mark("prep", tp)
    else:
        files = stage_file_list(rows, location_id, location_path)
        tp = _mark("prep", tp)

        # ---- batched hashing (the TPU-fed kernel) ----
        ids, read_errors = cas_ids_for_files(files, backend=backend)
        tp = _mark("hash", tp)
    kinds = {
        i: int(resolve_kind(files[i][0], ext=rows[i]["extension"] or ""))
        for i in ids
    }
    tp = _mark("prep", tp)

    linked = created = n_ops = 0
    own_tx = conn is None
    with (db.write_tx() if own_tx else nullcontext(conn)) as conn:
        # ---- link targets: existing objects by cas_id (mod.rs:167-225).
        # With a preloaded cas_map (the job's whole-library dict,
        # maintained across chunks) the per-chunk IN() probes vanish —
        # ~15% of the 1M wall. Without one, query as before.
        if cas_map is not None:
            existing = cas_map
        else:
            cas_list = sorted({c for c in ids.values() if c})
            existing = {}
            for chunk in _in_chunks(cas_list):
                ph = ",".join("?" for _ in chunk)
                # binds the declared identifier.cas_links shape
                for r in conn.execute(
                    f"SELECT fp.cas_id AS cas_id, o.id AS oid, "
                    f"o.pub_id AS opub "
                    f"FROM file_path fp JOIN object o ON o.id = fp.object_id "
                    f"WHERE fp.cas_id IN ({ph})", chunk):
                    existing.setdefault(r["cas_id"], (r["oid"], r["opub"]))
        tp = _mark("db_link", tp)

        # ---- resolve every row to an object: link or create ------------
        by_cas: Dict[str, bytes] = {}
        pub_of: Dict[int, bytes] = {}
        new_objects: List[Tuple[bytes, int, Any]] = []
        create_specs: List[Tuple] = []
        oid_of: Dict[bytes, int] = {}
        fresh_pubs = uuid4_bytes_batch(len(ids))  # one urandom syscall
        for i, cas_id in ids.items():
            hit = existing.get(cas_id) if cas_id is not None else None
            if hit is not None:
                oid_of[hit[1]] = hit[0]
                pub_of[i] = hit[1]
                linked += 1
            elif cas_id is not None and cas_id in by_cas:
                pub_of[i] = by_cas[cas_id]  # same-chunk duplicate
            else:
                opub = fresh_pubs[len(new_objects)]
                date_created = rows[i]["date_created"]
                new_objects.append((opub, kinds[i], date_created))
                create_specs.append((opub, "c", None, None, {
                    "kind": kinds[i], "date_created": date_created}))
                if cas_id is not None:
                    by_cas[cas_id] = opub
                pub_of[i] = opub
        tp = _mark("ops", tp)

        # ---- domain writes: objects + ONE file_path update pass --------
        library.db.run_many("identifier.object_insert", new_objects,
                            conn=conn)
        created = len(new_objects)
        if new_objects:
            # Consecutive rowids: inside one tx each rowid-table insert
            # gets max(rowid)+1 and we hold the write lock, so the batch
            # occupies [last-n+1, last] in insertion order — no SELECT-
            # back of n rows. One probe guards the assumption.
            last = library.db.run("store.last_rowid", conn=conn)
            first = last - len(new_objects) + 1
            probe = library.db.run("identifier.object_by_pub",
                                   (new_objects[0][0],), conn=conn)
            if probe is not None and probe["id"] == first:
                for k, (opub, _, _) in enumerate(new_objects):
                    oid_of[opub] = first + k
            else:  # fall back to the slow exact lookup
                for chunk in _in_chunks([p for p, _, _ in new_objects]):
                    ph = ",".join("?" for _ in chunk)
                    # binds the declared identifier.objects_by_pubs shape
                    for r in conn.execute(
                        f"SELECT id, pub_id FROM object "
                            f"WHERE pub_id IN ({ph})", chunk):
                        oid_of[r["pub_id"]] = r["id"]
        library.db.run_many(
            "identifier.link_paths",
            [(cas_id, oid_of[pub_of[i]], rows[i]["id"])
             for i, cas_id in ids.items()], conn=conn)
        tp = _mark("db_write", tp)

        # ---- op log: object creates, then ONE multi-field update per
        # file_path ({cas_id, object_id} in a single "u:cas_id+object_id"
        # op — the reference's three per-field passes, mod.rs:144/231/167,
        # are 3 op rows/file; this is ≤2 and 1 for linked files). Creates
        # go first so their HLC stamps precede the links and in-order
        # ingest resolves the object FK.
        n_ops += sync.bulk_shared_ops(conn, "object", create_specs)
        n_ops += sync.bulk_shared_ops(conn, "file_path", [
            (rows[i]["pub_id"], LINK_KIND, None, None,
             {"cas_id": cas_id, "object_id": pub_of[i]})
            for i, cas_id in ids.items()])
        tp = _mark("ops", tp)
    if own_tx:
        _mark("db_commit", tp)
    if cas_map is not None:
        # Job-lifetime map: with our own tx it updates only AFTER the
        # commit above (a rolled-back chunk must not leave uncommitted
        # rowids/pub_ids in the map for later chunks). Inside a caller-
        # owned multi-chunk tx it updates NOW — the next chunk in the
        # same transaction must dedup against these objects — and the
        # added keys ride in batch.cas_added so the caller pops them
        # back out if the whole transaction rolls back.
        for c, opub in by_cas.items():
            cas_map[c] = (oid_of[opub], opub)
        if not own_tx and batch is not None:
            batch.cas_added.extend(by_cas)
    if own_tx:
        # Standalone callers (watcher shallow-identify) count here; the
        # job path counts once per commit group in _step instead.
        if linked:
            IDENT_FILES.labels(outcome="linked").inc(linked)
        if created:
            IDENT_FILES.labels(outcome="created").inc(created)
        if read_errors:
            IDENT_FILES.labels(outcome="skipped").inc(len(read_errors))
    if n_ops:
        if own_tx:
            sync._notify_created()
        elif batch is not None:
            batch.n_ops += n_ops  # broadcast after the caller commits
    return linked, created, list(read_errors.values())


@register_job
class FileIdentifierJob(StatefulJob):
    NAME = "file_identifier"
    IS_BATCHED = True

    def __init__(self, *, location_id: int, sub_path: Optional[str] = None,
                 backend: str = "auto",
                 device_batch: Optional[int] = None):
        """`device_batch` decouples the device batch from the reference's
        100-file step (SURVEY.md §7 hard part 2): steps page the cursor
        in device_batch-file chunks (e.g. 4096-16384), each staged and
        hashed as ONE batched device call. Checkpointing stays exact —
        the cursor advances per step, and replayed chunks are idempotent
        (cas_id/object updates keyed by row id)."""
        if device_batch is not None and device_batch < 1:
            raise ValueError(f"device_batch must be >= 1, got {device_batch}")
        super().__init__(location_id=location_id, sub_path=sub_path,
                         backend=backend, device_batch=device_batch)
        self.location_id = location_id
        self.sub_path = sub_path
        self.backend = backend
        self.device_batch = device_batch

    @property
    def chunk_size(self) -> int:
        return self.device_batch or CHUNK_SIZE

    async def init(self, ctx: JobContext):
        db = ctx.db
        from ..locations.file_path_helper import load_location
        loc = load_location(db, self.location_id)
        sub_mat = sub_path_children_mat(self.location_id, self.sub_path)
        where, params = orphan_filters(self.location_id, 0, sub_mat)
        # binds the declared identifier.orphan_count shape
        count = db.query_one(
            f"SELECT COUNT(*) AS n FROM file_path WHERE {where}", params)["n"]
        if count == 0:
            raise EarlyFinish("no orphan file paths")
        chunk = self.chunk_size
        device_engaged = self.device_batch is not None or self.backend == "jax"
        if self.device_batch is None and self.backend in ("auto", "jax"):
            # Auto device engagement (VERDICT r1 item 3): big scans step
            # in device-batch chunks when the link probe says the device
            # pipeline beats the native plane (ops/staging.py policy).
            from ..ops.staging import auto_device_batch

            auto = auto_device_batch(count)
            if auto is not None:
                chunk = auto
                device_engaged = True
        if (self.device_batch is None and chunk == CHUNK_SIZE
                and self.backend in ("auto", "native")
                and count >= staging.AUTO_DEVICE_MIN_ORPHANS):
            # Big scan staying on the host plane: step in large chunks so
            # the per-chunk orchestration (page fetch, op build, commit)
            # amortizes — the wall is the host pipeline, not the hash.
            # Native-plane only: it streams per file in C++, while the
            # numpy fallback stages dense [B, 100 KiB] arrays per chunk
            # (~420 MiB at 4096) and must keep the small reference step.
            from .. import native as _native
            if _native.available():
                chunk = staging.AUTO_NATIVE_BATCH
        # Bulk-load trick for big scans: the cas_id/object_id indexes on
        # file_path exist for READ paths (dedup grouping, object →
        # paths lookups); during identify they only eat random B-tree
        # inserts — 2 index rows per file, measured ~15-20% of the 1M
        # wall in page churn. Drop them for the run and rebuild sorted
        # in finalize (~2-4 s/1M rows). Crash-safe: Database open
        # re-executes the eager CREATE INDEX IF NOT EXISTS DDL (SIGKILL
        # → next open rebuilds); cancel/failure restore via cleanup().
        # The cas_id index is only droppable when the preloaded cas map
        # will replace its probes — otherwise the per-chunk IN()
        # fallbacks would become full table scans.
        rebuild = count >= self.BULK_DROP_MIN_ORPHANS
        cas_preload = (await asyncio.to_thread(
            db.run, "store.object_count"))["n"] <= self.CAS_PRELOAD_MAX
        if rebuild:
            # One tiny DDL pair at job start; not worth a thread hop.
            # sdlint: ok[blocking-async]
            with db.write_tx() as conn:
                if cas_preload:
                    conn.execute(
                        "DROP INDEX IF EXISTS idx_file_path_cas_id")
                conn.execute(
                    "DROP INDEX IF EXISTS idx_file_path_object_id")
        # Commit batching: one transaction per STEP covering several
        # hash chunks — WAL commit overhead (page flushes shared across
        # chunks) amortizes, measured ~4.5 s of the 1M identify as
        # per-chunk commits. Capped so a commit group stays ≤ ~16k
        # files: the crash checkpoint's cursor only advances per step,
        # so a SIGKILL replays at most one commit group (idempotent,
        # keyed by row id), and pause latency stays bounded.
        # Two configurations keep one chunk per step: device-engaged
        # runs (their chunks are already 8-16k files, and a second
        # device dispatch must never run under the held write lock) and
        # hash-ahead runs (see _hash_ahead) — there the worker hash of
        # chunk k+1 overlapping chunk k's whole db+commit phase is
        # worth more than amortized commits, and batching would
        # serialize the worker behind the group. Commit batching
        # targets the remaining case: the single-core host plane, where
        # nothing overlaps anyway and per-chunk commits were pure
        # overhead.
        hash_ahead = _hash_ahead(device_engaged)
        commit_every = (1 if device_engaged or hash_ahead
                        else max(1, min(8, 16384 // chunk)))
        data = {
            "location_path": loc["path"],
            "sub_mat_path": sub_mat,
            "rebuild_indexes": rebuild,
            "cas_preload": cas_preload,
            # The resolved step size rides in `data` so pause/resume
            # replays use the same pagination the steps were counted for.
            "chunk_size": chunk,
            "commit_every": commit_every,
            # Stage+hash chunk i+1 in a worker thread while chunk i's
            # transaction commits (_hash_ahead says when).
            "hash_ahead": hash_ahead,
            "cursor": 0,
            "linked": 0, "created": 0, "skipped": 0, "total_orphans": count,
        }
        steps = [{"chunk": i}
                 for i in range(-(-count // (chunk * commit_every)))]
        ctx.progress(task_count=len(steps),
                     message=f"identifying {count} orphan paths")
        return data, steps

    async def execute_step(self, ctx, data, step, step_number):
        return await asyncio.to_thread(self._step, ctx, data)

    def _fetch_page(self, ctx: JobContext, data: Dict[str, Any],
                    cursor: int) -> List[Dict[str, Any]]:
        where, params = orphan_filters(
            self.location_id, cursor, data["sub_mat_path"])
        # sqlite3.Row supports ["name"] access directly — no dict() copy.
        # binds the declared identifier.orphan_page shape
        return ctx.db.query(
            f"SELECT * FROM file_path WHERE {where} ORDER BY id ASC LIMIT ?",
            params + [data.get("chunk_size") or self.chunk_size])

    # Above this many existing objects the whole-library cas_id map is
    # not preloaded (memory: ~150 B/entry) and chunks fall back to the
    # per-chunk IN() probes.
    CAS_PRELOAD_MAX = 2_000_000
    # At or above this many orphans, the file_path cas_id/object_id
    # indexes are dropped for the run and rebuilt in finalize.
    BULK_DROP_MIN_ORPHANS = 100_000

    def _get_cas_map(self, ctx: JobContext, data: Dict[str, Any]):
        """Whole-library cas_id → (object id, pub_id) dict, built once
        per job run and maintained by identify_chunk — replaces ~250
        IN()-probe queries per 1M files. Rebuilt from the DB on resume,
        so replayed chunks link to pre-crash objects idempotently.

        The engage decision was made at init ("cas_preload" in data) —
        the same decision that gated dropping the cas_id probe index;
        deciding here again could diverge and leave probes unindexed.
        Pre-change resumed jobs (no key) decide now, their index is
        still in place."""
        m = getattr(self, "_cas_map", None)
        if m is not None:
            return None if m is False else m  # {} stays engaged
        enabled = data.get("cas_preload")
        if enabled is None:
            enabled = ctx.db.run("store.object_count")["n"] \
                <= self.CAS_PRELOAD_MAX
        if not enabled:
            self._cas_map = False
            return None
        m = {}
        for r in ctx.db.run("identifier.cas_map"):
            m.setdefault(r["c"], (r["oid"], r["opub"]))
        self._cas_map = m
        return m

    def _fetch_and_hash(self, ctx: JobContext, data: Dict[str, Any],
                        cursor: int):
        """Worker-thread body of the hash-ahead pipeline: page fetch,
        file staging, batched hashing — everything before the tx. Safe
        to run against the live DB: the page past the previous chunk's
        last row id is untouched by that chunk's updates. Returns
        (rows, prehashed, per-phase seconds) — the worker times its own
        phases so overlapped hashing is still attributed to `hash`, not
        smeared into the consumer's wait (the split perf_smoke
        reports)."""
        w: Dict[str, float] = {}
        t0 = time.perf_counter()
        rows = self._fetch_page(ctx, data, cursor)
        w["fetch"] = time.perf_counter() - t0
        if not rows:
            return rows, None, w
        t0 = time.perf_counter()
        files = stage_file_list(
            rows, self.location_id, data["location_path"])
        w["prep"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        # Round 10: the bucketed identify hash runs inside the
        # sanitizer's device scope — an undeclared retrace or host
        # transfer in this exact loop is what the jit registry's
        # contracts forbid (raise mode in tier-1, counters in prod).
        with jit_registry.device_scope("identify.hash"):
            ids, read_errors = cas_ids_for_files(
                files, backend=self.backend)
        w["hash"] = time.perf_counter() - t0
        return rows, (files, ids, read_errors), w

    def _timed_fetch(self, ctx: JobContext, data: Dict[str, Any],
                     cursor: int):
        """Fetch-only prefetch body (non-hash-ahead hosts)."""
        t0 = time.perf_counter()
        rows = self._fetch_page(ctx, data, cursor)
        return rows, None, {"fetch": time.perf_counter() - t0}

    def _take_page(self, ctx: JobContext, data: Dict[str, Any],
                   cursor: int, timings: Dict[str, float]):
        """One chunk's orphan page (+ prehashed payload when the
        prefetch was a hash-ahead one), honoring a matching prefetch.

        Phase accounting: worker-measured fetch/prep/hash seconds merge
        into `timings` at their TRUE cost; the time this thread spent
        blocked on the worker lands in `overlap_wait` (the un-hidden
        remainder of the overlap — with perfect overlap it tends to 0).
        Overlapped phases can therefore sum past step_total; the split
        is cost attribution, not a wall-clock partition."""
        tf = time.perf_counter()
        pre = getattr(self, "_prefetch", None)
        rows = prehashed = wtimings = None
        if pre is not None and pre[0] == cursor:
            try:
                rows, prehashed, wtimings = pre[1].result()
            except Exception:  # noqa: BLE001 — re-run inline, recorded
                _log.exception("identifier hash-ahead chunk at cursor %d "
                               "failed; re-running it inline", cursor)
                IDENT_PREFETCH_FAILURES.inc()
                rows = prehashed = wtimings = None
        self._prefetch = None
        if wtimings:
            for k, v in wtimings.items():
                timings[k] = timings.get(k, 0.0) + v
            timings["overlap_wait"] = (timings.get("overlap_wait", 0.0)
                                       + time.perf_counter() - tf)
        if rows is None:
            t0 = time.perf_counter()
            rows = self._fetch_page(ctx, data, cursor)
            timings["fetch"] = (timings.get("fetch", 0.0)
                                + time.perf_counter() - t0)
        return (rows if rows else None), prehashed

    def _stage_and_hash(self, rows, data: Dict[str, Any],
                        timings: Dict[str, float]):
        """Inline (main-thread) staging + batched hashing of one page —
        the path taken when the prefetch was fetch-only. Runs with the
        successor prefetch already in flight, so the next page's SELECT
        (or fetch+hash) hides under this work."""
        tp = time.perf_counter()
        files = stage_file_list(
            rows, self.location_id, data["location_path"])
        t1 = time.perf_counter()
        timings["prep"] = timings.get("prep", 0.0) + t1 - tp
        with jit_registry.device_scope("identify.hash"):
            ids, read_errors = cas_ids_for_files(
                files, backend=self.backend)
        timings["hash"] = (timings.get("hash", 0.0)
                           + time.perf_counter() - t1)
        return files, ids, read_errors

    def _step(self, ctx: JobContext, data: Dict[str, Any]) -> StepOutcome:
        tf = time.perf_counter()
        timings = data.setdefault("phase_s", {})
        # Registry mirror of the phase split: `timings` accumulates for
        # the job report; the per-step DELTA lands on the node-wide
        # phase counters so /metrics shows live attribution mid-run
        # (and perf_smoke --telemetry sources its split from here).
        phase_before = dict(timings)
        # _submit (not a raw _pool().submit): survives another Node's
        # concurrent shutdown_stage_pool() by landing on a fresh pool.
        from ..ops.staging import _submit

        # Phase 1 — collect the whole commit group OUTSIDE any
        # transaction: fetch + stage + hash never run (or wait) under
        # the held write lock. Per chunk: take the page, submit the
        # successor's prefetch, THEN hash inline — so the next page's
        # SELECT (or worker fetch+hash) hides under this chunk's
        # hashing, and the last submitted prefetch (the next step's
        # first chunk) hides under phase 2's db work. Hash-ahead hosts
        # run commit_every=1 (set at init), so their worker hash of
        # chunk k+1 overlaps chunk k's whole phase 2 — the per-chunk
        # overlap the round-5 pipeline had.
        commit_every = data.get("commit_every") or 1
        cursor = data["cursor"]
        chunks: List[tuple] = []
        for _ in range(commit_every):
            rows, prehashed = self._take_page(ctx, data, cursor, timings)
            if rows is None:
                break
            cursor = rows[-1]["id"] + 1
            if data.get("hash_ahead"):
                if prehashed is None:
                    # Cold start (no matching hash-ahead prefetch —
                    # job start or post-resume): hash THIS chunk
                    # before submitting the next chunk's worker, so a
                    # device backend's first-call jit compile happens
                    # once, serially. Two threads tracing the same
                    # cold program concurrently buy no overlap (the
                    # second blocks on the compile anyway) and
                    # stretch every event-loop callback under the
                    # GIL — observed as sanitizer loop stalls on
                    # 2-core hosts. Warm chunks keep the submit-first
                    # order, so steady-state overlap is unchanged.
                    prehashed = self._stage_and_hash(rows, data,
                                                     timings)
                self._prefetch = (cursor, _submit(
                    self._fetch_and_hash, ctx, data, cursor))
            else:
                # Fetch-only prefetch (host planes): submit BEFORE the
                # inline hash every chunk — the next page fetch hides
                # under this chunk's hashing.
                self._prefetch = (cursor, _submit(
                    self._timed_fetch, ctx, data, cursor))
                if prehashed is None:
                    prehashed = self._stage_and_hash(rows, data,
                                                     timings)
            chunks.append((rows, prehashed))
        if not chunks:
            return StepOutcome()

        # Phase 2 — ONE transaction for the whole commit group
        # (commit_every chunks): WAL pages dirtied by several chunks
        # flush once at the group commit instead of per chunk, and the
        # write lock covers DB WORK ONLY (sub-second for a ~16k-file
        # group). The cursor in `data` — what the 3 s crash checkpoint
        # serializes — only advances after the commit, so a SIGKILL
        # replays at most one commit group, idempotently
        # (cas_id/object updates keyed by row id).
        cas_map = self._get_cas_map(ctx, data)
        batch = TxBatch()
        linked = created = 0
        errors: List[str] = []
        db = ctx.db
        try:
            with db.write_tx() as conn:
                for rows, prehashed in chunks:
                    lk, cr, errs = identify_chunk(
                        ctx.library, self.location_id,
                        data["location_path"], rows, self.backend,
                        timings=timings, prehashed=prehashed,
                        cas_map=cas_map, conn=conn, batch=batch)
                    linked += lk
                    created += cr
                    errors.extend(errs)
                t_commit = time.perf_counter()
        except BaseException:
            # The rolled-back transaction's objects never existed: pop
            # their cas-map entries or later chunks would link
            # file_paths to phantom row ids.
            if cas_map is not None:
                for c in batch.cas_added:
                    cas_map.pop(c, None)
            raise
        timings["db_commit"] = (timings.get("db_commit", 0.0)
                                + time.perf_counter() - t_commit)
        if batch.n_ops:
            ctx.library.sync._notify_created()
        data["cursor"] = cursor
        timings["step_total"] = (timings.get("step_total", 0.0)
                                 + time.perf_counter() - tf)
        for phase, total in timings.items():
            delta = total - phase_before.get(phase, 0.0)
            if delta > 0:
                IDENT_PHASE_SECONDS.labels(phase=phase).inc(delta)
        if linked:
            IDENT_FILES.labels(outcome="linked").inc(linked)
        if created:
            IDENT_FILES.labels(outcome="created").inc(created)
        if errors:
            IDENT_FILES.labels(outcome="skipped").inc(len(errors))
        data["linked"] += linked
        data["created"] += created
        data["skipped"] += len(errors)
        ctx.progress(message=(
            f"identified {data['linked'] + data['created']} of "
            f"{data['total_orphans']} paths"))
        return StepOutcome(
            errors=errors,
            metadata={
                "total_objects_linked": data["linked"],
                "total_objects_created": data["created"],
                "total_skipped": data["skipped"],
                "cursor": data["cursor"],
            },
        )

    @staticmethod
    def _restore_indexes(db) -> None:
        """Recreate the bulk-dropped read indexes. Idempotent (IF NOT
        EXISTS): a no-op when they were never dropped."""
        with db.write_tx() as conn:
            conn.execute(
                "CREATE INDEX IF NOT EXISTS idx_file_path_cas_id "
                "ON file_path (cas_id)")
            conn.execute(
                "CREATE INDEX IF NOT EXISTS idx_file_path_object_id "
                "ON file_path (object_id)")

    async def cleanup(self, ctx, data):
        """Cancel/failure path: finalize never runs, so restore the
        indexes here (data may be None — restore unconditionally, it
        is free when they exist)."""
        await asyncio.to_thread(self._restore_indexes, ctx.db)

    async def finalize(self, ctx, data, metadata):
        if data.get("rebuild_indexes"):
            t0 = time.perf_counter()
            self._restore_indexes(ctx.db)
            data.setdefault("phase_s", {})["index_rebuild"] = (
                time.perf_counter() - t0)
        # Publish the per-phase wall-time breakdown (fetch/prep/hash/db/
        # ops seconds across all chunks) so workload runs can see where
        # the ms/file goes — the profile VERDICT r2 asked for.
        phase = data.get("phase_s")
        if phase:
            metadata["phase_ms"] = {
                k: round(v * 1000.0, 1) for k, v in sorted(phase.items())}
            metadata["chunk_size"] = data.get("chunk_size")
        return metadata
