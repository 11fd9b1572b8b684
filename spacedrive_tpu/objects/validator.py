"""ObjectValidatorJob: full-file BLAKE3 integrity checksums.

Mirrors the reference job
(/root/reference/core/src/object/validation/validator_job.rs:78-218 and
validation/hash.rs:10-24): every file_path in the location with
integrity_checksum IS NULL gets a full-file checksum written through sync.

Deviation for throughput: steps are CHUNKed batches (the reference does
one file per step), and each batch hashes files concurrently on a thread
pool with the streaming oracle, or on-device for the "jax" backend: every
file that fits one shard of the streaming mesh is packed into batched
Pallas grids, and only files whose chunk chain spans several shards
stream through sequence-sharded windows (ops/seqhash.py).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
from contextlib import nullcontext
from typing import Any, Dict, List, Optional, Tuple

from .. import tracing
from ..jobs.job import EarlyFinish, JobContext, StatefulJob, StepOutcome, register_job
from ..locations.paths import IsolatedPath
from ..ops.cas import file_checksum
from ..telemetry import VAL_BATCHED_READ, VAL_PAGE_COMPARE, VAL_PAGE_ROWS

CHUNK_SIZE = 10


@register_job
class ObjectValidatorJob(StatefulJob):
    NAME = "object_validator"
    IS_BATCHED = True

    def __init__(self, *, location_id: int, sub_path: Optional[str] = None,
                 backend: str = "auto", mode: str = "fill"):
        """mode="fill" (reference semantics, validator_job.rs:78-218):
        checksum every file_path whose integrity_checksum IS NULL.
        mode="verify" (net-new): re-hash files that ALREADY have a
        checksum and report mismatches — bit-rot/corruption detection,
        which the reference never does."""
        if mode not in ("fill", "verify"):
            raise ValueError(f"unknown validator mode {mode!r}")
        super().__init__(location_id=location_id, sub_path=sub_path,
                         backend=backend, mode=mode)
        self.location_id = location_id
        self.sub_path = sub_path
        self.backend = backend
        self.mode = mode

    def _init_sync(self, ctx: JobContext):
        """Cursor-paginated steps (same shape as the identifier): the
        resumable state is (WHERE, cursor, counters) — O(1) regardless
        of scan size. The old design serialized every pending row into
        the step list, which made the 3 s crash checkpoint re-msgpack
        ~100 MB of state at 1M files."""
        db = ctx.db
        from ..locations.file_path_helper import job_prologue
        checksum_filter = ("integrity_checksum IS NULL"
                           if self.mode == "fill"
                           else "integrity_checksum IS NOT NULL")
        loc, where, params = job_prologue(
            db, self.location_id, self.sub_path,
            f"location_id = ? AND is_dir = 0 AND {checksum_filter}",
            [self.location_id])
        # binds the declared identifier.orphan_count shape
        count = db.query_one(
            f"SELECT COUNT(*) AS n FROM file_path WHERE {where}",
            params)["n"]
        if count == 0:
            raise EarlyFinish("nothing to validate")
        chunk = CHUNK_SIZE
        from .. import native
        if (self.backend in ("auto", "native", "jax")
                and native.available() and count >= 4096):
            # Big scans on the batched planes step in large chunks so the
            # per-step orchestration amortizes (identifier rationale).
            chunk = 2048
        data = {"location_path": loc["path"], "where": where,
                "params": list(params), "cursor": 0, "chunk": chunk,
                "validated": 0, "mismatched": 0}
        steps = [{} for _ in range(-(-count // chunk))]
        ctx.progress(task_count=len(steps))
        return data, steps

    async def execute_step(self, ctx, data, step, step_number):
        outcome = await asyncio.to_thread(self._step, ctx, data, step)
        # IntegrityViolation events are collected by the worker-thread
        # step body and emitted HERE, back on the event loop: EventBus
        # fan-out is loop-affine (sdlint thread-boundary), and the
        # relay costs nothing — the step has to return before the next
        # one dispatches anyway.
        events = (outcome.metadata.pop("_integrity_events", [])
                  if outcome.metadata else [])
        if events:
            node = ctx.services.get("node")
            if node is not None:
                for ev in events:
                    node.events.emit(ev)
        return outcome

    def _fetch_rows(self, db, data) -> List[Dict[str, Any]]:
        # binds the declared validator.page shape
        rows = db.query(
            f"SELECT id, pub_id, materialized_path, name, extension, "
            f"integrity_checksum, size_in_bytes_bytes "
            f"FROM file_path WHERE {data['where']} "
            f"AND id >= ? ORDER BY id LIMIT ?",
            list(data["params"]) + [data["cursor"], data["chunk"]])
        return [{
            "id": r["id"], "pub_id": r["pub_id"],
            "materialized_path": r["materialized_path"],
            "name": r["name"] or "", "extension": r["extension"] or "",
            "size": int.from_bytes(r["size_in_bytes_bytes"] or b"", "big"),
            "expected": r["integrity_checksum"],
        } for r in rows]

    # Files at or under this size batch-hash MANY per device dispatch
    # (amortizing the per-dispatch latency — VERDICT r4 item 4). The
    # split raises it to one streaming shard where a shard is larger
    # (_stream_plan): only files that span several shards stream.
    SMALL_FILE_CAP = 4 << 20
    # Padded grid bytes per batched dispatch, sized for a PCIe-attached
    # chip; SDTPU_VAL_BATCH_BYTES overrides it for slower links.
    BATCH_BYTES = 64 << 20
    BATCH_ROWS = 512

    @property
    def batch_bytes(self) -> int:
        from .. import flags

        env = flags.get("SDTPU_VAL_BATCH_BYTES")
        return env if env else self.BATCH_BYTES

    @classmethod
    def _stream_plan(cls, n_devices: int) -> Tuple[int, int, int]:
        """(D, shard_chunks, cap) of the streaming mesh on n_devices.

        Streaming windows need a power-of-two device count (subtree
        alignment), so D is the largest power of two in n_devices; each
        device's shard is a power-of-two count of 1 KiB chunks, an 8 MiB
        window across the mesh, 64 chunks at least. A file of at most
        `cap` bytes fits one shard (or SMALL_FILE_CAP, if larger) and
        takes the batched grid: 8 MiB on one device, 4 MiB from two."""
        D = 1 << (n_devices.bit_length() - 1)
        shard_chunks = max(64, (8 << 20) // (D * 1024))
        shard_chunks = 1 << (shard_chunks - 1).bit_length()
        return D, shard_chunks, max(cls.SMALL_FILE_CAP, shard_chunks * 1024)

    def _checksums_jax(self, jobs, errors):
        """Device checksums, two regimes split at the cap of
        _stream_plan:

        - files that fit one streaming shard: sorted by size (tight
          shared chunk grid) and packed into ONE batched dispatch per
          ~BATCH_BYTES page via checksums_words_batched — the page pays
          the host↔device RPC latency once instead of once per file;
        - files whose chunk chain spans several shards: each streamed
          through mesh-window sequence sharding (bounded memory at any
          size, ops/seqhash.py)."""
        import os as _os

        import jax

        from ..ops import jit_registry
        from ..ops.blake3_jax import checksums_words_batched
        from ..ops.seqhash import sharded_file_checksum
        from ..parallel.mesh import batch_mesh

        devices = list(jax.devices())
        D, shard_chunks, cap = self._stream_plan(len(devices))
        small, big = [], []
        with tracing.phase("validator.phase/rows", VAL_PAGE_ROWS):
            for r, path in jobs:
                try:
                    sz = _os.path.getsize(path)
                except OSError as e:
                    errors.append(f"{path}: {e}")
                    continue
                (small if sz <= cap else big).append((r, path, sz))

            small.sort(key=lambda t: t[2])

        def _padded_row(sz: int) -> int:
            # the dispatch grid pads every row to the batch's pow2 max
            # chunk count — ascending size order means the CURRENT file
            # sets that max, so charge its padded cost to the budget
            chunks = max(1, -(-max(sz, 1) // 1024))
            return (1 << (chunks - 1).bit_length()) * 1024

        i = 0
        while i < len(small):
            batch, blobs = [], []
            # One read phase a page, not a clock pair per file.
            with tracing.phase("validator.phase/read", VAL_BATCHED_READ):
                while i < len(small) and len(batch) < self.BATCH_ROWS:
                    r, path, sz = small[i]
                    # Budget the PADDED grid, not the raw payload: one
                    # multi-MiB file after 500 tiny ones would otherwise
                    # balloon the dispatch to rows × pow2(max) ≈ GiBs of
                    # zeros.
                    if batch and (len(batch) + 1) * _padded_row(sz) \
                            > self.batch_bytes:
                        break
                    i += 1
                    try:
                        with open(path, "rb") as f:
                            data = f.read()
                    except OSError as e:
                        errors.append(f"{path}: {e}")
                        continue
                    blobs.append(data)
                    batch.append((r, path))
            if blobs:
                # Guarded dispatch (round 10): the page's only
                # sanctioned fetch is checksums_words_batched's
                # declared io("cas.checksums") — a stray D2H here
                # raises under the tier-1 sanitizer.
                with jit_registry.device_scope("validator.batched"):
                    hexes = checksums_words_batched(blobs)
                for (r, path), hx in zip(batch, hexes):
                    yield r, path, hx

        if not big:
            return
        # On e.g. a 6- or 12-device mesh stream over the largest
        # power-of-two subset instead of erroring on every file.
        # batch_mesh is cached per device tuple, so this per-step call
        # returns the SAME Mesh object every step — seqhash's
        # _sharded_reduce keys its trace cache on the mesh static arg,
        # and a fresh mesh per step would risk a retrace per step
        # (jit-registry contract seqhash.reduce).
        mesh = batch_mesh(devices[:D])
        for r, path, _sz in big:
            try:
                yield r, path, sharded_file_checksum(
                    mesh, path, shard_chunks=shard_chunks)
            except (OSError, ValueError) as e:
                errors.append(f"{path}: {e}")

    def _step(self, ctx: JobContext, data, step) -> StepOutcome:
        db, sync = ctx.db, ctx.library.sync
        loc_path = data["location_path"]
        # The device path's page-level phases (sd_validator_* path=page).
        device = self.backend == "jax"
        with (tracing.phase("validator.phase/rows", VAL_PAGE_ROWS)
              if device else nullcontext()):
            rows = self._fetch_rows(db, data)
            jobs: List[Tuple[dict, str]] = []
            for r in rows:
                iso = IsolatedPath.from_db_row(
                    self.location_id, False, r["materialized_path"],
                    r["name"], r["extension"])
                jobs.append((r, iso.join_on(loc_path)))
        if not rows:
            return StepOutcome()
        # Advance past this page only once it is fully processed (end of
        # this method) — an interrupted step replays the same page, and
        # the guarded UPDATE keeps the replay idempotent.
        next_cursor = rows[-1]["id"] + 1

        errors: List[str] = []
        results: List[Tuple[dict, str, str]] = []  # (row, path, checksum)

        from .. import native
        if device and jobs:
            # Device plane: each file's chunk chain is sequence-sharded
            # across the mesh and streamed in windows (ops/seqhash.py
            # StreamingShardedChecksum) — bounded memory at any file
            # size, oracle-exact. Explicit opt-in: on slow host→device
            # links the native streamer wins (ops/staging.py policy).
            for r, path, checksum in self._checksums_jax(jobs, errors):
                results.append((r, path, checksum))
        elif native.available() and jobs:
            # Batched native plane: one call, pooled pread + C++ BLAKE3.
            # DB sizes route small files to the cross-file SIMD groups
            # without a stat sweep (partition hint only — stale sizes
            # re-route at read time, never change a digest).
            import numpy as np
            hexes, status = native.checksum_files(
                [p for _, p in jobs],
                sizes_hint=np.array([r["size"] for r, _ in jobs],
                                    dtype=np.uint64))
            for (r, path), checksum, st in zip(jobs, hexes, status):
                if checksum is None:
                    errors.append(
                        f"{path}: "
                        f"{native.STATUS_MESSAGES.get(int(st), 'error')}")
                else:
                    results.append((r, path, checksum))
        else:
            def one(r, path):
                return r, path, file_checksum(path)

            with concurrent.futures.ThreadPoolExecutor(
                    max_workers=CHUNK_SIZE) as pool:
                futs = [pool.submit(one, r, p) for r, p in jobs]
                for fut in futs:
                    try:
                        results.append(fut.result())
                    except OSError as e:
                        errors.append(str(e))

        if self.mode == "verify":
            # Net-new corruption pass: compare against the stored
            # checksum; mismatches are non-fatal errors + events, never
            # silently "repaired" (the stored value is the evidence).
            # This body runs in a to_thread worker: EventBus emit is
            # loop-affine, so violations ride the outcome metadata and
            # execute_step emits them after the hop back to the loop.
            integrity_events = []
            with (tracing.phase("validator.phase/compare", VAL_PAGE_COMPARE)
                  if device else nullcontext()):
                for r, path, checksum in results:
                    if checksum != r.get("expected"):
                        data["mismatched"] += 1
                        errors.append(
                            f"CHECKSUM MISMATCH {path}: stored "
                            f"{r.get('expected')}, current {checksum}")
                        integrity_events.append({
                            "type": "IntegrityViolation",
                            "file_path_id": r["id"], "path": path,
                        })
            data["validated"] += len(results)
            data["cursor"] = next_cursor
            ctx.progress(message=(
                f"verified {data['validated']} files, "
                f"{data['mismatched']} mismatches"))
            return StepOutcome(errors=errors, metadata={
                "validated": data["validated"],
                "mismatched": data["mismatched"],
                "_integrity_events": integrity_events})

        with db.write_tx() as conn:
            db.run_many(
                "validator.fill_checksum",
                [(checksum, r["id"]) for r, _p, checksum in results],
                conn=conn)
            n_ops = sync.bulk_shared_ops(conn, "file_path", [
                (r["pub_id"], "u:integrity_checksum", "integrity_checksum",
                 checksum, None) for r, _p, checksum in results])
        if n_ops:
            sync._notify_created()
        data["validated"] += len(results)
        data["cursor"] = next_cursor
        ctx.progress(message=f"validated {data['validated']} files")
        return StepOutcome(errors=errors,
                           metadata={"validated": data["validated"]})

    async def finalize(self, ctx, data, metadata):
        return metadata
