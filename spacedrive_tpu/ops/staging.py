"""CAS staging: files → dense payload batches → CAS IDs, per backend.

This is the feeding layer between the filesystem and the batched BLAKE3
backends (SURVEY.md §7 phase 5 / hard-part 2 "feeding the beast"): the
reference hashes one file at a time inside per-file async tasks
(/root/reference/core/src/object/file_identifier/mod.rs:107-134 →
core/src/object/cas.rs:23-62); here whole batches are staged into dense
arrays and hashed at once.

Size classes keep device grids canonical (two compiled shapes only):
- LARGE (> 100 KiB): exactly 57,344 sampled bytes per row → [B, 57344].
- SMALL (≤ 100 KiB): whole file, zero-padded → [B, 102400] with lens.
Empty files get no CAS ID (cas_id = None), matching FileMetadata::new
(mod.rs:80-88).

Backends:
- "oracle": streaming pure-Python blake3 per file (the parity oracle).
- "numpy":  batched pad-and-mask blake3 on CPU.
- "jax":    the jitted device path (TPU when available).
"""

from __future__ import annotations

import atexit
import concurrent.futures
import itertools
import logging
import os
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import cas, jit_registry
from .. import channels, chaos, flags, persist, tracing
from ..flight import RECORDER
from ..telemetry import (
    CAS_PHASE_PACK,
    CAS_PHASE_STAGE,
    STAGE_BATCHES,
    STAGE_FALLBACK_FILES,
    STAGE_H2D_PROBE_FAILURES,
    STAGE_NATIVE_BYTES,
    STAGE_POOL_BUFFERS,
    STAGE_POOL_HIGH_WATER,
    STAGE_POOL_WORKERS,
)

# Monotone hashing-chunk ordinal for the flight recorder's "identify"
# scope: host-plane chunks get timeline lanes too, so the export shows
# the hash-ahead cadence next to the jax backend's per-chunk lanes.
_CHUNK_SEQ = itertools.count(1).__next__

_log = logging.getLogger(__name__)

_STAGE_POOL: Optional[concurrent.futures.ThreadPoolExecutor] = None
_ATEXIT_REGISTERED = False
# Serializes pool-identity transitions (create / swap-out) WITH their
# gauge updates, so sd_stage_pool_workers always describes the current
# _STAGE_POOL — a shutdown racing a re-create cannot clobber the fresh
# pool's gauge with a late 0. The long shutdown(wait=True) itself runs
# outside the lock.
_POOL_LOCK = threading.Lock()


def _pool() -> concurrent.futures.ThreadPoolExecutor:
    """The shared staging executor, created lazily and visible to the
    lifecycle machinery: `sd_stage_pool_workers` reports its size (0
    when down), `shutdown_stage_pool()` is the explicit close hook
    `Node.shutdown()` drives (with an atexit backstop for bench CLIs
    that never build a Node), and the next submit after a shutdown
    simply re-creates the pool — multiple Nodes in one process share
    it safely."""
    global _STAGE_POOL, _ATEXIT_REGISTERED
    with _POOL_LOCK:
        if _STAGE_POOL is None:
            workers = min(32, (os.cpu_count() or 4) * 2)
            _STAGE_POOL = concurrent.futures.ThreadPoolExecutor(
                max_workers=workers,
                thread_name_prefix="cas-stage",
            )
            STAGE_POOL_WORKERS.set(workers)
            if not _ATEXIT_REGISTERED:
                _ATEXIT_REGISTERED = True
                atexit.register(shutdown_stage_pool)
        return _STAGE_POOL


def _submit(fn, *args) -> concurrent.futures.Future:
    """Submit to the shared pool, surviving a concurrent
    shutdown_stage_pool(): with two Nodes in one process, node A's
    shutdown can close the pool between node B's `_pool()` lookup and
    its `.submit()` — the RuntimeError retry clears the dead executor
    (only if nobody re-created it yet) and lands on a fresh one."""
    global _STAGE_POOL
    pool = _pool()
    try:
        return pool.submit(fn, *args)
    except RuntimeError:
        with _POOL_LOCK:
            if _STAGE_POOL is pool:
                _STAGE_POOL = None
                STAGE_POOL_WORKERS.set(0)
        return _pool().submit(fn, *args)


def shutdown_stage_pool(wait: bool = True) -> None:
    """Tear down the shared staging executor. Idempotent; in-flight
    reads finish when `wait` (the default — a half-staged batch must
    not observe freed numpy views). Wired into `Node.shutdown()` so
    the pool's threads no longer outlive the supervisor reap
    invisibly, and registered atexit as the backstop. The gauge zeroes
    AT the swap, under the lock: a pool re-created while this thread
    still drains the old one keeps its own (non-zero) gauge."""
    global _STAGE_POOL
    with _POOL_LOCK:
        pool, _STAGE_POOL = _STAGE_POOL, None
        if pool is not None:
            STAGE_POOL_WORKERS.set(0)
    if pool is not None:
        pool.shutdown(wait=wait)


@dataclass
class StagedBatch:
    """Dense payload arrays for one size class."""

    indexes: List[int]          # positions in the caller's file list
    payloads: np.ndarray        # [B, P] uint8, zero-padded
    sizes: np.ndarray           # [B] uint64 declared file sizes
    payload_lens: np.ndarray    # [B] int32 real payload bytes per row


def _read_large(path: str, size: int, out: np.ndarray) -> None:
    """Sampled read into a 57,344-byte row (cas.rs:23-59 spec)."""
    with open(path, "rb") as f:
        pos = 0
        spec = cas.sample_spec(size)
        for offset, length in spec[:-1]:
            f.seek(offset)
            chunk = f.read(length)
            if len(chunk) != length:
                raise EOFError(f"{path}: short read at {offset}")
            out[pos:pos + length] = np.frombuffer(chunk, dtype=np.uint8)
            pos += length
        f.seek(-cas.HEADER_OR_FOOTER_SIZE, os.SEEK_END)
        chunk = f.read(cas.HEADER_OR_FOOTER_SIZE)
        if len(chunk) != cas.HEADER_OR_FOOTER_SIZE:
            raise EOFError(f"{path}: short footer read")
        out[pos:pos + len(chunk)] = np.frombuffer(chunk, dtype=np.uint8)


def _stage_files_native(
    files, large_idx, small_idx, empty_idx,
) -> Tuple[StagedBatch, StagedBatch, List[int], Dict[int, str]]:
    """Native plane staging (native/sdio.cpp): pooled pread into dense
    rows, no Python in the per-file loop."""
    from .. import native

    errors: Dict[int, str] = {}
    sizes = np.array([s for _, s in files], dtype=np.uint64)

    lpaths = [files[i][0] for i in large_idx]
    large, lstatus = native.stage_large(
        lpaths, sizes[large_idx] if large_idx else np.zeros(0, np.uint64))
    spaths = [files[i][0] for i in small_idx]
    small_wide, slens, sstatus = native.stage_small(
        spaths, cap=cas.MINIMUM_FILE_SIZE)
    small = small_wide[:, :cas.MINIMUM_FILE_SIZE]

    def filter_ok(idx_list, payloads, status, lens=None):
        bad_rows = np.nonzero(status != native.OK)[0]
        for row in bad_rows:
            errors[idx_list[row]] = (
                f"{files[idx_list[row]][0]}: "
                f"{native.STATUS_MESSAGES.get(int(status[row]), 'error')}")
        if len(bad_rows) == 0:
            return idx_list, payloads, lens
        ok = np.nonzero(status == native.OK)[0]
        return ([idx_list[r] for r in ok], payloads[ok],
                lens[ok] if lens is not None else None)

    large_idx, large, _ = filter_ok(large_idx, large, lstatus)
    small_idx, small, slens = filter_ok(small_idx, small, sstatus, slens)

    large_batch = StagedBatch(
        large_idx, large,
        sizes[large_idx] if large_idx else np.zeros(0, np.uint64),
        np.full((len(large_idx),), cas.LARGE_PAYLOAD_SIZE, dtype=np.int32))
    small_batch = StagedBatch(
        small_idx, small,
        sizes[small_idx] if small_idx else np.zeros(0, np.uint64),
        slens if slens is not None else np.zeros(0, np.int32))
    return large_batch, small_batch, empty_idx, errors


def _size_classes(
    files: Sequence[Tuple[str, int]],
) -> Tuple[List[int], List[int], List[int]]:
    """Indexes of the large (> 100 KiB), small (1 B to 100 KiB) and empty
    files, by declared size."""
    large_idx = [i for i, (_, s) in enumerate(files)
                 if s > cas.MINIMUM_FILE_SIZE]
    small_idx = [i for i, (_, s) in enumerate(files)
                 if 0 < s <= cas.MINIMUM_FILE_SIZE]
    empty_idx = [i for i, (_, s) in enumerate(files) if s == 0]
    return large_idx, small_idx, empty_idx


def stage_files(
    files: Sequence[Tuple[str, int]],
) -> Tuple[StagedBatch, StagedBatch, List[int], Dict[int, str]]:
    """Stage (path, size) pairs into dense per-class batches.

    Returns (large_batch, small_batch, empty_indexes, errors) where
    errors maps file index → message (unreadable files are skipped, the
    caller records them as non-fatal job errors — JobRunErrors semantics).
    """
    large_idx, small_idx, empty_idx = _size_classes(files)
    errors: Dict[int, str] = {}

    from .. import native as _native
    # SDTPU_STAGE_NATIVE=off is the WHOLE native-staging escape hatch:
    # it pins not just the packed path (stage_batch_native) but these
    # classic native reads too, so "off" really means the pure-Python
    # readers.
    mode = str(flags.get("SDTPU_STAGE_NATIVE") or "auto")
    if mode not in ("off", "0", "no", "false") and _native.available():
        return _stage_files_native(files, large_idx, small_idx, empty_idx)

    large = np.zeros((len(large_idx), cas.LARGE_PAYLOAD_SIZE), dtype=np.uint8)
    small = np.zeros((len(small_idx), cas.MINIMUM_FILE_SIZE), dtype=np.uint8)
    small_lens = np.zeros((len(small_idx),), dtype=np.int32)

    def read_one(kind: str, row: int, idx: int) -> None:
        path, size = files[idx]
        try:
            if kind == "large":
                _read_large(path, size, large[row])
            else:
                with open(path, "rb") as f:
                    data = f.read(cas.MINIMUM_FILE_SIZE + 1)
                if len(data) > cas.MINIMUM_FILE_SIZE:
                    raise EOFError(
                        f"{path}: grew past declared size {size}")
                small[row, :len(data)] = np.frombuffer(data, dtype=np.uint8)
                small_lens[row] = len(data)
        except OSError as e:
            errors[idx] = f"{path}: {e}"
        except EOFError as e:
            errors[idx] = str(e)

    jobs = [("large", row, idx)
            for row, idx in enumerate(large_idx)] + \
           [("small", row, idx)
            for row, idx in enumerate(small_idx)]
    if threading.current_thread().name.startswith("cas-stage"):
        # Already ON a stage-pool worker (the identifier's hash-ahead
        # stages whole chunks through the same executor): submitting
        # the per-file reads back into the pool and blocking on them
        # can starve — outer jobs >= workers pins every worker on a
        # batch whose inner reads never get a thread. Nested staging
        # reads inline.
        for job in jobs:
            read_one(*job)
    else:
        futures = [_submit(read_one, *job) for job in jobs]
        for fut in futures:
            fut.result()

    sizes = np.array([s for _, s in files], dtype=np.uint64)
    large_batch = StagedBatch(
        large_idx, large, sizes[large_idx] if large_idx else
        np.zeros((0,), np.uint64),
        np.full((len(large_idx),), cas.LARGE_PAYLOAD_SIZE, dtype=np.int32))
    small_batch = StagedBatch(
        small_idx, small, sizes[small_idx] if small_idx else
        np.zeros((0,), np.uint64), small_lens)
    return large_batch, small_batch, empty_idx, errors


# -- native packed staging (zero-copy H2D feed) ----------------------------
#
# The classic path above stages per-class payload matrices and then
# pays a full build_cas_messages pass — allocate a fresh [B, C*1024]
# buffer, write prefixes, copy every payload — before each H2D. The
# packed path below hands the C plane (native/sdio.cpp sd_stage_batch)
# a POOLED, page-aligned buffer and has it write the kernel's message
# layout directly: le64(size) ‖ payload ‖ zeros per row, per-row status
# for file-by-file degradation. The pooled pages are the H2D sources
# (np.frombuffer views, no copy) and recycle at batch RETIREMENT, so
# the pool is a declared bounded resource (ops.stage.pool window).


@dataclass
class StageLease:
    """One checked-out pooled page: `arr` is the [rows, stride] uint8
    zero-copy view over the anonymous mapping `buf`. Release returns
    the PAGE to the pool — the numpy views die with the lease holder,
    and the mapping itself is only reclaimed by GC once no view (or
    jax host alias) can reach it."""

    buf: "object"            # mmap.mmap backing pages
    nbytes: int              # pooled capacity of buf (>= rows*stride)
    arr: np.ndarray          # [rows, stride] uint8 view for this batch
    _pool: "StagePool"
    _released: bool = False

    def release(self) -> None:
        if not self._released:
            self._released = True
            self._pool.release(self)


class StagePool:
    """Bounded pool of page-aligned staging pages (the jax backend's
    H2D sources). Anonymous mmap allocations are page-aligned by
    construction; a free page is reused for any batch whose rows fit
    its capacity. Checkouts are metered through the declared
    ops.stage.pool window — the capacity there (narrowable via
    SDTPU_STAGE_POOL_BUFFERS, never raisable) IS the bound: an
    exhausted pool returns None and the caller degrades to the Python
    staging path rather than allocating past it."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._free: List[Tuple[object, int]] = []  # [(mmap, nbytes)]
        self._total = 0
        self._high_water = 0
        self._win = channels.window("ops.stage.pool")

    def _cap(self) -> int:
        cap = self._win.capacity
        narrowed = int(flags.get("SDTPU_STAGE_POOL_BUFFERS") or 0)
        if narrowed > 0:
            cap = min(cap, narrowed)
        return max(1, cap)

    def acquire(self, rows: int, stride: int) -> Optional[StageLease]:
        import mmap as _mmap

        need = rows * stride
        with self._lock:
            mm = None
            # Smallest free page that fits; an all-too-small free list
            # drops one page (GC reclaims it once unreferenced) and
            # allocates at the new size — total never passes the cap.
            fits = [i for i, (_, cap) in enumerate(self._free)
                    if cap >= need]
            if fits:
                mm, nbytes = self._free.pop(
                    min(fits, key=lambda i: self._free[i][1]))
            elif self._total < self._cap():
                self._total += 1
            elif self._free:
                self._free.pop(0)
            else:
                return None  # every page checked out: degrade, not grow
            if mm is None:
                nbytes = need
                mm = _mmap.mmap(-1, need)
            in_use = self._total - len(self._free)
            if in_use > self._high_water:
                self._high_water = in_use
                STAGE_POOL_HIGH_WATER.set(in_use)
            STAGE_POOL_BUFFERS.set(in_use)
            self._win.note_put()
        arr = np.frombuffer(mm, dtype=np.uint8,
                            count=need).reshape(rows, stride)
        return StageLease(mm, nbytes, arr, self)

    def release(self, lease: StageLease) -> None:
        with self._lock:
            self._free.append((lease.buf, lease.nbytes))
            self._win.note_pop()
            STAGE_POOL_BUFFERS.set(self._total - len(self._free))


_BUF_POOL_LOCK = threading.Lock()
_STAGE_BUF_POOL: Optional[StagePool] = None


def stage_buffer_pool() -> StagePool:
    """The process-wide staging page pool (one declared window meters
    every checkout)."""
    global _STAGE_BUF_POOL
    with _BUF_POOL_LOCK:
        if _STAGE_BUF_POOL is None:
            _STAGE_BUF_POOL = StagePool()
        return _STAGE_BUF_POOL


@dataclass
class NativeStaged:
    """A natively staged packed batch: row i corresponds to files[i].
    `words`/`lengths` are the kernel operands ([B, C, 256] uint32 view
    over the pooled page + [B] int32 message lengths); rows listed in
    `errors` failed BOTH the native reader and the per-file Python
    retry (their rows are scrubbed to the 8-byte prefix; ignore their
    digests), `empty_rows` are declared-empty files (no CAS ID)."""

    words: np.ndarray
    lengths: np.ndarray
    lease: StageLease
    errors: Dict[int, str]
    empty_rows: List[int]
    fallback_files: int = 0

    def release(self) -> None:
        self.lease.release()


def _grid_for(payload_cap: int) -> Tuple[int, int]:
    """(chunk grid C, row stride) for a payload class — the exact
    build_cas_messages shape."""
    c = max(1, -(-(cas.SIZE_PREFIX_LEN + payload_cap) // 1024))
    return c, c * 1024


def stage_batch_native(
    files: Sequence[Tuple[str, int]],
    pool: Optional[StagePool] = None,
    rows: Optional[int] = None,
) -> Optional[NativeStaged]:
    """Stage a batch straight into a pooled packed buffer via the C
    plane, or None to degrade the WHOLE batch to the Python path
    (flag off, libsdio.so missing — the fail-closed ladder — or pool
    exhausted). Individual bad rows (vanished file, permission, short
    read, injected EIO) degrade PER FILE: the Python reader retries
    into the same packed row, and only a row failing both lands in
    `errors`. Byte parity with stage_files + build_cas_messages is
    pinned by tests/test_staging_native.py.

    The page's row stride is the small class's grid when any file is
    small, else the large class's. `rows` (default: one per file) sizes
    the page for a padded batch: rows past len(files) are left as the
    pool hands them over, stale bytes included, for a caller that
    dispatches them with length 0."""
    mode = str(flags.get("SDTPU_STAGE_NATIVE") or "auto")
    if mode in ("off", "0", "no", "false"):
        return None
    from .. import native
    if not native.available():
        return None  # fail closed: the classic Python path
    n = len(files)
    if n == 0:
        return None
    rows = n if rows is None else rows
    if rows < n:
        raise ValueError(f"stage_batch_native: {rows} rows for {n} files")
    sizes = np.array([s for _, s in files], dtype=np.uint64)
    any_small = bool(np.any((sizes > 0) & (sizes <= cas.MINIMUM_FILE_SIZE)))
    payload_cap = cas.MINIMUM_FILE_SIZE if any_small \
        else cas.LARGE_PAYLOAD_SIZE
    grid_c, stride = _grid_for(payload_cap)
    lease = (pool or stage_buffer_pool()).acquire(rows, stride)
    if lease is None:
        return None  # bounded resource: degrade instead of growing
    try:
        msg_lens, status = native.stage_batch(
            [p for p, _ in files], sizes, lease.arr, payload_cap)
        if chaos.armed_point("stage.native.read"):
            f = chaos.hit("stage.native.read", only=("delay",))
            if f is not None:
                chaos.apply_sync(f)
            # Per-row draws so a probability storm speckles the batch
            # (file-by-file degradation) instead of all-or-nothing.
            for r in range(n):
                f = chaos.hit("stage.native.read",
                              only=("error", "corrupt"))
                if f is not None:
                    status[r] = (native.ERR_IO if f.kind == "error"
                                 else native.ERR_SHORT_READ)
        errors: Dict[int, str] = {}
        empty_rows: List[int] = []
        fallback = 0
        for r in np.nonzero(status != native.OK)[0]:
            r = int(r)
            if int(status[r]) == native.ERR_EMPTY:
                empty_rows.append(r)
                continue
            # Per-file fallback ladder: the Python oracle reader, into
            # the SAME packed row (zero-copy invariants hold — only
            # the bytes of this row change).
            path, size = files[r]
            row = lease.arr[r]
            try:
                if size > cas.MINIMUM_FILE_SIZE:
                    _read_large(path, size,
                                row[8:8 + cas.LARGE_PAYLOAD_SIZE])
                    plen = cas.LARGE_PAYLOAD_SIZE
                else:
                    with open(path, "rb") as fobj:
                        data = fobj.read(cas.MINIMUM_FILE_SIZE + 1)
                    if len(data) > cas.MINIMUM_FILE_SIZE:
                        raise EOFError(
                            f"{path}: grew past declared size {size}")
                    row[8:8 + len(data)] = np.frombuffer(data,
                                                         dtype=np.uint8)
                    plen = len(data)
                row[8 + plen:] = 0  # pooled page: scrub stale residue
                msg_lens[r] = 8 + plen
                status[r] = native.OK
                fallback += 1
            except (OSError, EOFError) as e:
                errors[r] = f"{path}: {e}"
                row[8:] = 0
                msg_lens[r] = 8
        words = lease.arr.view("<u4").reshape(rows, grid_c, 256)
        STAGE_BATCHES.labels(backend="native").inc()
        STAGE_NATIVE_BYTES.inc(int(msg_lens.sum()))
        if fallback:
            STAGE_FALLBACK_FILES.inc(fallback)
        return NativeStaged(words, msg_lens, lease, errors, empty_rows,
                            fallback)
    except BaseException:
        lease.release()
        raise


# -- backends --------------------------------------------------------------


def _cas_ids_oracle(files, large, small) -> Dict[int, str]:
    out: Dict[int, str] = {}
    for batch in (large, small):
        for row, idx in enumerate(batch.indexes):
            payload = batch.payloads[row, :batch.payload_lens[row]].tobytes()
            out[idx] = cas.cas_id_of_payload(int(batch.sizes[row]), payload)
    return out


def _cas_ids_numpy(files, large, small) -> Dict[int, str]:
    # Deliberately jax-free: this is the fallback when jax is unavailable.
    from . import blake3_batch as bb
    out: Dict[int, str] = {}
    for batch in (large, small):
        if not batch.indexes:
            continue
        words, lengths = bb.build_cas_messages(
            batch.payloads, batch.sizes, batch.payload_lens)
        cvs = bb.blake3_batch(np, words, lengths)
        digests = np.stack(cvs, axis=1)
        for row, cid in enumerate(bb.digests_to_cas_ids(digests)):
            out[batch.indexes[row]] = cid
    return out


def _cas_ids_jax(files, chunk: int):
    """The jax backend's chunk. Each size class is staged once by the
    native packed stager straight into its device grid, a pooled page of
    cas_rows(n) rows at the class's stride (cas.phase/stage); what is
    left before the dispatch is the lengths vector (cas.phase/pack).
    A class the packed stager declines (no native plane, flag off, pool
    exhausted) takes the classic path: stage_files, then cas_ids_jax's
    build_cas_messages and bucket pad. Both paths dispatch and fetch
    through cas_ids_words, each phase counted in
    sd_cas_phase_seconds_total and annotated on a running profiler
    capture. The flight recorder's identify lanes come from the same
    boundaries: `stage` is the reads plus the class's packing, `h2d` the
    dispatch, `retire` the fetch; the kernel itself is on the device
    trace, not on a host-clock lane."""
    from .blake3_jax import cas_hasher, cas_ids_jax, cas_ids_words, cas_rows

    trace = tracing.current_trace_id()
    hasher, n_dev = cas_hasher()
    large_idx, small_idx, empty_idx = _size_classes(files)
    errors: Dict[int, str] = {}
    ids: Dict[int, Optional[str]] = {}
    # (indexes into `files`, NativeStaged or the classic StagedBatch)
    classes: List[Tuple[List[int], object]] = []
    try:
        with tracing.phase("cas.phase/stage", CAS_PHASE_STAGE) as st:
            for idx in (large_idx, small_idx):
                if not idx:
                    continue
                class_files = [files[i] for i in idx]
                packed = stage_batch_native(
                    class_files, rows=cas_rows(len(idx), n_dev))
                if packed is not None:
                    errors.update(
                        (idx[r], msg) for r, msg in packed.errors.items())
                    classes.append((idx, packed))
                    continue
                STAGE_BATCHES.labels(backend="python").inc()
                large, small, _empty, errs = stage_files(class_files)
                errors.update((idx[r], msg) for r, msg in errs.items())
                batch = large if large.indexes else small
                if batch.indexes:
                    classes.append(([idx[r] for r in batch.indexes], batch))
        t = st.t0
        for idx, staged in classes:
            marks: List[Tuple[float, float, float]] = []
            if isinstance(staged, NativeStaged):
                with tracing.phase("cas.phase/pack", CAS_PHASE_PACK) as pack:
                    lengths = np.zeros((staged.words.shape[0],), np.int32)
                    lengths[:len(idx)] = staged.lengths
                    # A row both readers failed carries no message: its
                    # ID is dropped, and its bytes count nowhere.
                    lengths[list(staged.errors)] = 0
                cids = cas_ids_words(staged.words, lengths, len(idx),
                                     hasher, n_dev, pack.t1, marks)
            else:
                cids = cas_ids_jax(staged.payloads, staged.sizes,
                                   staged.payload_lens, marks=marks)
            ids.update(zip(idx, cids))
            packed_at, dispatched, fetched = marks[0]
            for lane, t0, t1 in (("stage", t, packed_at),
                                 ("h2d", packed_at, dispatched),
                                 ("retire", dispatched, fetched)):
                RECORDER.record(lane, batch=chunk, t0=t0, t1=t1,
                                device="jax", scope="identify", trace=trace,
                                files=len(files))
            t = fetched
    finally:
        # The pages are the transfers' host sources: they go back to the
        # pool only once the digests are fetched, or the dispatch failed.
        for _idx, staged in classes:
            if isinstance(staged, NativeStaged):
                staged.release()
    return ids, empty_idx, errors


_BACKENDS = {
    "oracle": _cas_ids_oracle,
    "numpy": _cas_ids_numpy,
}


# Below this batch size the device round-trip (H2D over the host link +
# dispatch + possible first compile) costs more than the fused native
# path; watcher-triggered single-file updates must never block on
# accelerator init. Identifier steps (100/step, reference parity) stay
# native — the device backend engages for the large analytics batches
# (dedup/pHash/bench) or when a job pins backend="jax".
JAX_MIN_BATCH = 256

# Auto device engagement for the identifier (VERDICT r1 item 3): scans
# with at least this many orphans consider the device pipeline, stepping
# in AUTO_DEVICE_BATCH-file chunks so each step is one device call.
AUTO_DEVICE_MIN_ORPHANS = 4096
AUTO_DEVICE_BATCH = 16384  # amortizes ~7-10 ms per-dispatch overhead

# When the device pipeline is NOT engaged, big scans still step in large
# chunks so the per-chunk Python/SQL orchestration (orphan page fetch,
# op building, transaction commit) amortizes over thousands of files
# instead of the reference's 100 (file_identifier/mod.rs:36). The native
# C++ plane streams per file, so chunk size costs no extra memory.
# Sized by interleaved A/B on the 1M corpus (the bench host's IO
# weather swings 2x between windows, so only same-window pairs count):
# 4096 beat 16384 in both interleaved pairs (53/58 s vs 69/80 s);
# sequential runs had earlier suggested the opposite, confounded by
# weather. Bigger chunks also grow the crash-replay window 4x.
AUTO_NATIVE_BATCH = 4096

# The CAS pipeline is H2D-bound end-to-end (the pallas kernel sustains
# ~30 GB/s, the AVX2 native plane ~3.5 GB/s): shipping bytes to the
# device only pays when the host→device link is faster than the native
# plane hashes. Probed once per process; SDTPU_DEVICE_PIPELINE=force/off
# overrides.
NATIVE_PLANE_GBPS = 3.5
_H2D_GBPS: Optional[float] = None


_H2D_PROBE_TTL = 3600.0


def _h2d_cache_path() -> Optional[str]:
    """Probe-cache file inside a private 0700 per-user dir (a fixed name
    directly in world-writable /tmp could be pre-created or symlinked by
    another local user). Returns None when no safe dir can be had."""
    import stat
    import tempfile

    d = os.path.join(tempfile.gettempdir(), f"sdtpu-{os.getuid()}")
    try:
        os.makedirs(d, mode=0o700, exist_ok=True)
        st = os.lstat(d)
        if (not stat.S_ISDIR(st.st_mode) or st.st_uid != os.getuid()
                or st.st_mode & 0o077):
            return None
    except OSError:
        return None
    return os.path.join(d, "h2d_probe.json")


def _h2d_probe_key() -> Optional[str]:
    """Cache key binding a probe result to the device set it measured:
    backend platform + device count. The on-disk cache outlives the
    process — without the key, a stale CPU-backend probe (a laptop
    run, a tier-1 test) would mis-calibrate the policy on a bench host
    for up to the TTL. None (jax unavailable) disables the disk cache
    rather than trusting an unkeyed entry."""
    try:
        import jax
    except ImportError:
        return None
    devs = jax.devices()
    return f"{devs[0].platform}:{len(devs)}"


# Advisory last-writer-wins probe cache: a racing writer's value is
# as good as ours (same link, same hour) and a torn LOGICAL state is
# impossible — each write replaces the whole doc and a stale/invalid
# doc is simply re-probed.
# sdlint: ok[crash-atomicity]
def h2d_gbps() -> float:
    """Measured host→device link bandwidth, probed once per process and
    cached on disk for an hour (the probe itself costs a round trip, and
    every identifier-job init consults it).

    Sync is a FULL D2H fetch of the buffer (a sliced fetch would
    compile a slice program). The full round trip measures H2D+D2H;
    assuming a roughly symmetric link the per-direction rate is
    2*nbytes/rt — the right go/no-go signal for the H2D-bound CAS
    pipeline. A probe that raises is logged and counted
    (sd_stage_h2d_probe_failures_total) and reads as 0 GB/s.

    SDTPU_H2D_GBPS overrides (tests, benchmark pinning).
    """
    global _H2D_GBPS
    if _H2D_GBPS is not None:
        return _H2D_GBPS
    env = flags.get("SDTPU_H2D_GBPS")
    if env is not None:
        _H2D_GBPS = env
        return _H2D_GBPS
    import json
    import time

    cache = _h2d_cache_path()
    key = _h2d_probe_key() if cache is not None else None
    if cache is not None and key is not None:
        try:
            with open(cache) as f:
                saved = json.load(f)
            # Entries are only valid for the SAME backend + device set
            # that measured them (pre-key entries have no "key" and
            # re-probe once).
            if (time.time() - saved["t"] < _H2D_PROBE_TTL
                    and saved.get("key") == key):
                _H2D_GBPS = float(saved["gbps"])
                return _H2D_GBPS
        except Exception:
            pass
    ok = False
    try:
        import jax

        buf = np.zeros((8 << 20,), dtype=np.uint8)
        with jit_registry.io("staging.h2d_probe"):
            np.asarray(jax.device_put(buf))  # warm
            t0 = time.perf_counter()
            np.asarray(jax.device_put(buf))
            rt = time.perf_counter() - t0
        # Round trip moves the buffer twice; assuming a roughly
        # symmetric link, one direction runs at 2*nbytes/rt.
        _H2D_GBPS = 2 * buf.nbytes / rt / 1e9
        ok = True
    except Exception:  # noqa: BLE001 — routing must survive; recorded
        _log.exception("host->device link probe failed; auto routing "
                       "stays on the host plane")
        STAGE_H2D_PROBE_FAILURES.inc()
        _H2D_GBPS = 0.0
    if ok and cache is not None and key is not None:
        # Only successful probes are cached: a transient jax/device
        # failure must stay per-process, not poison an hour of runs.
        try:
            persist.atomic_write(
                "stage.h2d_cache", cache,
                json.dumps({"t": time.time(), "gbps": _H2D_GBPS,
                            "key": key}))
        except OSError:
            pass
    return _H2D_GBPS


def device_pipeline_worthwhile() -> bool:
    """True when staging→H2D→kernel beats the native CPU plane."""
    mode = flags.get("SDTPU_DEVICE_PIPELINE")
    if mode in ("force", "1"):
        return True
    if mode in ("off", "0"):
        return False
    try:
        import jax
    except ImportError:
        return False
    if jax.devices()[0].platform != "tpu":
        return False
    return h2d_gbps() > NATIVE_PLANE_GBPS


def auto_device_batch(orphan_count: int) -> Optional[int]:
    """Device step size for an identifier scan, or None to stay native.

    Engages the device for big scans (≥ AUTO_DEVICE_MIN_ORPHANS) when
    the link probe says the device pipeline wins (or is forced).
    """
    if orphan_count < AUTO_DEVICE_MIN_ORPHANS:
        return None
    if not device_pipeline_worthwhile():
        return None
    return AUTO_DEVICE_BATCH


def default_backend(batch_size: int = JAX_MIN_BATCH) -> str:
    """"jax" for device-worthy batches when jax is importable; below that
    the fused native C++ path when built, else batched numpy."""
    from .. import native as _native
    if batch_size < JAX_MIN_BATCH:
        return "native" if _native.available() else "numpy"
    try:
        import jax  # noqa: F401
        return "jax"
    except Exception:
        return "native" if _native.available() else "numpy"


def _cas_ids_native_fused(
    files: Sequence[Tuple[str, int]],
) -> Tuple[Dict[int, Optional[str]], Dict[int, str]]:
    """Fused native stage+hash — one C call for the whole batch."""
    from .. import native

    digests, status = native.cas_digests(
        [p for p, _ in files], np.array([s for _, s in files], np.uint64))
    ids: Dict[int, Optional[str]] = {}
    errors: Dict[int, str] = {}
    for i, st in enumerate(status):
        if st == native.OK:
            ids[i] = digests[i].tobytes().hex()[:16]
        elif st == native.ERR_EMPTY:
            ids[i] = None  # no CAS ID for empty files (mod.rs:86)
        else:
            errors[i] = (f"{files[i][0]}: "
                         f"{native.STATUS_MESSAGES.get(int(st), 'error')}")
    return ids, errors


def cas_ids_for_files(
    files: Sequence[Tuple[str, int]], backend: str = "auto",
) -> Tuple[Dict[int, Optional[str]], Dict[int, str]]:
    """(path, size) pairs → {index: cas_id | None for empty}, {index: error}.

    The identifier job's per-chunk kernel: stage + batch hash + format.
    """
    from ..telemetry import (
        IDENT_BATCHES,
        IDENT_BATCH_FILES,
        IDENT_BYTES_HASHED,
        IDENT_DEVICE_FALLBACK,
        IDENT_READ_ERRORS,
    )
    from ..tracing import device_span

    if backend == "auto":
        backend = default_backend(len(files))
        if backend == "jax" and not device_pipeline_worthwhile():
            # The CAS pipeline is H2D-bound: a device-worthy *batch size*
            # is not enough when the host→device link is slower than the
            # native plane hashes (compute-bound callers like phash make
            # their own call via default_backend directly).
            from .. import native as _native
            backend = "native" if _native.available() else "numpy"
            IDENT_DEVICE_FALLBACK.inc()
    IDENT_BATCHES.labels(backend=backend).inc()
    IDENT_BATCH_FILES.observe(len(files))
    # Payload-byte accounting (what the hashers actually consume): one
    # pass over the size list, ~ns/file against a ms/file pipeline.
    IDENT_BYTES_HASHED.inc(sum(
        cas.LARGE_PAYLOAD_SIZE if s > cas.MINIMUM_FILE_SIZE else s
        for _, s in files))
    chunk = _CHUNK_SEQ()
    if backend == "native":
        with device_span("cas_ids/native", batch=len(files)):
            t0 = time.perf_counter()
            ids, errors = _cas_ids_native_fused(files)
            # Fused stage+hash is one C call: one timeline event, on
            # the kernel lane (there is no separable stage phase).
            RECORDER.record(
                "kernel", batch=chunk, t0=t0, t1=time.perf_counter(),
                device="native", scope="identify",
                trace=tracing.current_trace_id(), files=len(files))
        if errors:
            IDENT_READ_ERRORS.inc(len(errors))
        return ids, errors
    # Staging (the file reads) belongs INSIDE the span on every backend
    # so cross-backend span timings stay comparable. The jax backend
    # additionally runs under the sanitizer's D2H transfer guard: the
    # only sanctioned fetch in this region is cas_ids_jax's declared
    # io("cas.ids") scope — anything else raises in tier-1.
    if backend == "jax":
        with device_span("cas_ids/jax", batch=len(files)), \
                jit_registry.device_scope("cas_ids/jax"):
            ids, empty_idx, errors = _cas_ids_jax(files, chunk)
    else:
        with device_span(f"cas_ids/{backend}", batch=len(files)):
            trace = tracing.current_trace_id()
            t0 = time.perf_counter()
            large, small, empty_idx, errors = stage_files(files)
            t1 = time.perf_counter()
            ids = dict(_BACKENDS[backend](files, large, small))
            # Host-plane chunks get stage/kernel lanes (scope
            # "identify"): the exporter shows the hash-ahead chunk
            # cadence.
            RECORDER.record("stage", batch=chunk, t0=t0, t1=t1,
                            device=backend, scope="identify", trace=trace,
                            files=len(files))
            RECORDER.record("kernel", batch=chunk, t0=t1,
                            t1=time.perf_counter(), device=backend,
                            scope="identify", trace=trace,
                            files=len(files))
    for idx in empty_idx:
        ids[idx] = None  # "We can't do shit with empty files" (mod.rs:86)
    for idx in errors:
        ids.pop(idx, None)
    if errors:
        IDENT_READ_ERRORS.inc(len(errors))
    return ids, errors
