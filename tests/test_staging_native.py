"""Packed native staging (native/sdio.cpp sd_stage_batch → ops/staging
stage_batch_native): byte parity with the classic Python path, the
per-file and whole-batch degradation ladders, pooled-page recycling,
and the chaos seam — all CPU-only tier-1.

The acceptance shape: native digests must be bit-identical to the
Python CAS oracle across the WHOLE degradation matrix — healthy rows,
fallback rows, and scrubbed error rows alike — because the kernel
consumes whatever bytes staging hands it.
"""

import os
import struct
import subprocess

import numpy as np
import pytest

from spacedrive_tpu import chaos, flags, native
from spacedrive_tpu.ops import cas, staging

requires_native = pytest.mark.skipif(
    not native.available(), reason="native libsdio unavailable")


def _write(path: str, data: bytes) -> int:
    with open(path, "wb") as f:
        f.write(data)
    return len(data)


def _pattern(n: int, seed: int = 0) -> bytes:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, n, dtype=np.uint8).tobytes()


def _oracle_payload(path: str, declared: int) -> bytes:
    """The Python reader's payload for one file — the CAS oracle's
    input bytes."""
    if declared <= cas.MINIMUM_FILE_SIZE:
        with open(path, "rb") as f:
            return f.read()
    out = np.zeros(cas.LARGE_PAYLOAD_SIZE, np.uint8)
    staging._read_large(path, declared, out)
    return out.tobytes()


def _expect_row(declared: int, payload: bytes, stride: int) -> bytes:
    row = struct.pack("<Q", declared) + payload
    return row + b"\x00" * (stride - len(row))


@requires_native
def test_make_stage_selftest():
    """Satellite: `make -C native stage` builds and runs the C-level
    self-test (layout, statuses, sampled offsets, pooled-page
    scrubbing) with no Python in the loop."""
    native_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "native")
    res = subprocess.run(["make", "-C", native_dir, "stage"],
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert "sd_stage_batch self-test: OK" in res.stdout


@requires_native
def test_byte_parity_across_split(tmp_path, monkeypatch):
    """Byte-for-byte parity with the classic path across the
    large/small boundary (102399 / 102400 / 102401) and a deep-sample
    large file: prefix, payload, and zero tail per packed row."""
    monkeypatch.setenv("SDTPU_STAGE_NATIVE", "on")
    sizes = [102399, cas.MINIMUM_FILE_SIZE, 102401, 150000, 4242]
    files = []
    for i, n in enumerate(sizes):
        p = str(tmp_path / f"f{i}.bin")
        files.append((p, _write(p, _pattern(n, seed=i))))

    staged = staging.stage_batch_native(files)
    assert staged is not None
    try:
        assert staged.errors == {} and staged.empty_rows == []
        assert staged.fallback_files == 0
        stride = staged.lease.arr.shape[1]
        for r, (p, declared) in enumerate(files):
            payload = _oracle_payload(p, declared)
            assert int(staged.lengths[r]) == 8 + len(payload)
            got = staged.lease.arr[r].tobytes()
            assert got == _expect_row(declared, payload, stride), \
                f"row {r} ({declared}B) diverges from the oracle"
        # words is a zero-copy view over the SAME pooled page
        assert staged.words.base is not None
        assert np.shares_memory(staged.words, staged.lease.arr)
    finally:
        staged.release()


@requires_native
def test_digest_parity_with_cas_oracle(tmp_path, monkeypatch):
    """CAS IDs computed from the packed rows equal the pure-Python
    oracle's — the end contract every staging backend must meet."""
    monkeypatch.setenv("SDTPU_STAGE_NATIVE", "on")
    files = []
    for i, n in enumerate((300, 102400, 120000, 103000)):
        p = str(tmp_path / f"d{i}.bin")
        files.append((p, _write(p, _pattern(n, seed=10 + i))))
    staged = staging.stage_batch_native(files)
    assert staged is not None
    try:
        for r, (p, declared) in enumerate(files):
            payload = staged.lease.arr[
                r, 8:int(staged.lengths[r])].tobytes()
            assert cas.cas_id_of_payload(declared, payload) == \
                cas.cas_id_of_payload(declared, _oracle_payload(p, declared))
    finally:
        staged.release()


@requires_native
def test_per_file_degradation_matrix(tmp_path, monkeypatch):
    """One batch, every ladder rung at once: a healthy row stays
    native, a vanished file (ENOENT) and a truncated file (short read)
    fail BOTH readers into `errors` with their rows scrubbed to the
    8-byte prefix, an empty file lands in `empty_rows`, and a grown
    file (real bytes past the declared size) is refused by both."""
    monkeypatch.setenv("SDTPU_STAGE_NATIVE", "on")
    ok = str(tmp_path / "ok.bin")
    _write(ok, _pattern(120000, seed=1))
    gone = str(tmp_path / "gone.bin")
    short = str(tmp_path / "short.bin")
    _write(short, _pattern(4096, seed=2))  # declared 150000: truncated
    empty = str(tmp_path / "empty.bin")
    _write(empty, b"")
    grew = str(tmp_path / "grew.bin")
    # declared small (5000) but the real bytes crossed the small-class
    # cap — the only grow the whole-file reader can (and must) refuse,
    # exactly like the classic path's MINIMUM+1 sentinel read
    _write(grew, _pattern(cas.MINIMUM_FILE_SIZE + 600, seed=3))

    files = [(ok, 120000), (gone, 120000), (short, 150000),
             (empty, 0), (grew, 5000)]
    staged = staging.stage_batch_native(files)
    assert staged is not None
    try:
        assert sorted(staged.errors) == [1, 2, 4]
        assert staged.empty_rows == [3]
        assert staged.fallback_files == 0
        # the healthy row is untouched by its neighbors' failures
        payload = staged.lease.arr[0, 8:int(staged.lengths[0])].tobytes()
        assert payload == _oracle_payload(ok, 120000)
        # failed + empty rows: prefix only, tail scrubbed (the kernel
        # hashes full blocks — stale residue would corrupt digests)
        for r in (1, 2, 3, 4):
            assert int(staged.lengths[r]) == 8
            assert not staged.lease.arr[r, 8:].any()
        # error parity with the classic path: same rows, same classes
        _l, _s, empty_idx, perrors = staging.stage_files(files)
        assert sorted(perrors) == sorted(staged.errors)
        assert empty_idx == staged.empty_rows
    finally:
        staged.release()


@requires_native
def test_chaos_injected_eio_falls_back_per_file(tmp_path, monkeypatch):
    """Satellite: the declared stage.native.read fault point. A
    probability-1.0 error storm marks every native row failed; the
    per-file Python ladder re-reads them all into the SAME pooled rows
    and digest parity still holds (fallback is invisible to the
    kernel)."""
    monkeypatch.setenv("SDTPU_STAGE_NATIVE", "on")
    files = []
    for i, n in enumerate((120000, 50000, 102401)):
        p = str(tmp_path / f"c{i}.bin")
        files.append((p, _write(p, _pattern(n, seed=20 + i))))
    chaos.arm("stage.native.read=error:1.0", seed=11)
    try:
        staged = staging.stage_batch_native(files)
        assert staged is not None
        try:
            assert staged.errors == {}
            assert staged.fallback_files == len(files)
            for r, (p, declared) in enumerate(files):
                payload = staged.lease.arr[
                    r, 8:int(staged.lengths[r])].tobytes()
                assert payload == _oracle_payload(p, declared)
        finally:
            staged.release()
    finally:
        chaos.disarm()
    assert not chaos.armed_point("stage.native.read")


def test_whole_batch_fallback_flag_off(tmp_path, monkeypatch):
    """SDTPU_STAGE_NATIVE=off declines the packed path entirely — the
    fail-closed ladder's top rung."""
    monkeypatch.setenv("SDTPU_STAGE_NATIVE", "off")
    p = str(tmp_path / "x.bin")
    files = [(p, _write(p, _pattern(120000)))]
    assert staging.stage_batch_native(files) is None


def test_whole_batch_fallback_so_missing(tmp_path, monkeypatch):
    """A missing shared object degrades the WHOLE batch, silently and
    correctly, whatever the flag says."""
    monkeypatch.setenv("SDTPU_STAGE_NATIVE", "on")
    monkeypatch.setattr(native, "available", lambda: False)
    p = str(tmp_path / "x.bin")
    files = [(p, _write(p, _pattern(120000)))]
    assert staging.stage_batch_native(files) is None


@requires_native
def test_pool_exhaustion_degrades_not_grows(tmp_path, monkeypatch):
    """The pool is a declared bounded resource: with every page checked
    out, stage_batch_native returns None (degrade to Python) instead of
    allocating past the bound; a release makes it available again."""
    monkeypatch.setenv("SDTPU_STAGE_NATIVE", "on")
    monkeypatch.setenv("SDTPU_STAGE_POOL_BUFFERS", "1")
    pool = staging.StagePool()
    p = str(tmp_path / "x.bin")
    files = [(p, _write(p, _pattern(120000)))]
    held = pool.acquire(4, 58368)
    assert held is not None
    assert staging.stage_batch_native(files, pool=pool) is None
    held.release()
    staged = staging.stage_batch_native(files, pool=pool)
    assert staged is not None
    staged.release()


@requires_native
def test_pool_recycles_pages_and_scrubs_residue(tmp_path, monkeypatch):
    """Recycled pages are reused (bounded allocation) and every packed
    row's tail is rewritten — batch B staged into batch A's dirty page
    must not inherit A's bytes."""
    monkeypatch.setenv("SDTPU_STAGE_NATIVE", "on")
    pool = staging.StagePool()
    big = str(tmp_path / "big.bin")
    big2 = str(tmp_path / "big2.bin")
    # two large rows (2 x 58368B) so the page fits batch B's one
    # small-grid row (103424B) and MUST be reused, not reallocated
    files_a = [(big, _write(big, _pattern(150000, seed=5))),
               (big2, _write(big2, _pattern(150000, seed=7)))]
    small = str(tmp_path / "small.bin")
    files_b = [(small, _write(small, _pattern(600, seed=6)))]

    a = staging.stage_batch_native(files_a, pool=pool)
    assert a is not None
    page_a = id(a.lease.buf)
    a.release()
    assert pool._total == 1 and len(pool._free) == 1

    b = staging.stage_batch_native(files_b, pool=pool)
    assert b is not None
    try:
        # same pooled page, reshaped for the small grid
        assert id(b.lease.buf) == page_a
        assert pool._total == 1
        assert int(b.lengths[0]) == 8 + 600
        assert not b.lease.arr[0, 8 + 600:].any(), \
            "stale residue from the previous batch survived the scrub"
        payload = b.lease.arr[0, 8:608].tobytes()
        assert payload == _oracle_payload(small, 600)
    finally:
        b.release()


@requires_native
def test_overlap_pipeline_digest_parity_and_pool_drain(tmp_path,
                                                       monkeypatch):
    """Chunk after chunk through the jax backend's packed path, as the
    identifier steps: every CAS ID equals the oracle's over the same
    payload bytes, every size class stages natively, and every pooled
    page is back on the free list once its chunk's digests are
    fetched."""
    monkeypatch.setenv("SDTPU_STAGE_NATIVE", "on")
    pool = staging.stage_buffer_pool()
    before = _stage_batches()
    chunks = [(300, 120000, 4242, 150000),
              (cas.MINIMUM_FILE_SIZE, cas.MINIMUM_FILE_SIZE + 1),
              (9000, 8000, 7000)]
    for c, sizes in enumerate(chunks):
        files = []
        for i, n in enumerate(sizes):
            p = str(tmp_path / f"c{c}_{i}.bin")
            files.append((p, _write(p, _pattern(n, seed=100 + 10 * c + i))))
        ids, errors = staging.cas_ids_for_files(files, backend="jax")
        assert not errors
        for i, (p, n) in enumerate(files):
            assert ids[i] == cas.cas_id_of_payload(
                n, _oracle_payload(p, n)), (c, i)
        assert _in_use(pool) == 0, c
    with pool._lock:
        assert pool._total == len(pool._free)
    assert len(pool._win) == 0
    # two classes, two classes, one class
    assert _stage_batches()["native"] - before["native"] == 5


@requires_native
def test_stage_native_flag_modes(tmp_path, monkeypatch):
    """auto (default) and on both engage when the .so is present; the
    off spellings all decline."""
    p = str(tmp_path / "x.bin")
    files = [(p, _write(p, _pattern(120000)))]
    for mode in ("auto", "on", "1"):
        monkeypatch.setenv("SDTPU_STAGE_NATIVE", mode)
        staged = staging.stage_batch_native(files)
        assert staged is not None, mode
        staged.release()
    for mode in ("off", "0", "no", "false"):
        monkeypatch.setenv("SDTPU_STAGE_NATIVE", mode)
        assert staging.stage_batch_native(files) is None, mode


def test_stage_pool_lifecycle_and_gauge():
    """The shared staging executor has an explicit lifecycle —
    sd_stage_pool_workers reports its size, shutdown zeroes it and
    drops the pool, the next use re-creates it."""
    from spacedrive_tpu.telemetry import STAGE_POOL_WORKERS

    pool = staging._pool()
    assert STAGE_POOL_WORKERS.value > 0
    staging.shutdown_stage_pool()
    assert staging._STAGE_POOL is None
    assert STAGE_POOL_WORKERS.value == 0
    staging.shutdown_stage_pool()  # idempotent
    again = staging._pool()        # lazily re-created for later users
    assert again is not pool
    assert STAGE_POOL_WORKERS.value > 0


# -- the identifier's jax backend through the packed path ------------------
#
# cas_ids_for_files(backend="jax") stages each size class once, straight
# into a pooled page of the class's bucketed rows. At most 8 files a
# class keeps every batch on the two (8, C, 256) grids, compiled once.


def _in_use(pool) -> int:
    with pool._lock:
        return pool._total - len(pool._free)


def _stage_batches():
    from spacedrive_tpu.telemetry import STAGE_BATCHES

    return {b: STAGE_BATCHES.labels(backend=b).value
            for b in ("native", "python")}


def _mixed_batch(root, seed: int):
    """Small, large and empty files plus a vanished one (declared 7,000
    bytes, never written) and one that grew past the small class
    (declared 5,000, holds 100 KiB + 600)."""
    os.makedirs(root, exist_ok=True)
    files = []
    for i, n in enumerate((300, 4242, cas.MINIMUM_FILE_SIZE, 102401,
                           150000, 0)):
        p = os.path.join(root, f"m{i}.bin")
        files.append((p, _write(p, _pattern(n, seed=seed + i))))
    files.append((os.path.join(root, "gone.bin"), 7000))
    grew = os.path.join(root, "grew.bin")
    _write(grew, _pattern(cas.MINIMUM_FILE_SIZE + 600, seed=seed + 9))
    files.append((grew, 5000))
    return files


@requires_native
def test_jax_backend_packed_path_parity_on_dirty_pages(tmp_path,
                                                       monkeypatch):
    """CAS IDs through the packed path equal the oracle's, on pooled
    pages a batch of other files left dirty, and the failed files are
    the classic path's (SDTPU_STAGE_NATIVE=off)."""
    pool = staging.StagePool()
    monkeypatch.setattr(staging, "stage_buffer_pool", lambda: pool)
    monkeypatch.setenv("SDTPU_STAGE_NATIVE", "on")
    dirty = []
    for i, n in enumerate([120000] * 8 + [90000] * 8):
        p = str(tmp_path / f"dirty{i}.bin")
        dirty.append((p, _write(p, _pattern(n, seed=40 + i))))
    ids, errors = staging.cas_ids_for_files(dirty, backend="jax")
    assert not errors and len(ids) == len(dirty)
    assert pool._total == 2 and _in_use(pool) == 0

    files = _mixed_batch(str(tmp_path / "mixed"), seed=60)
    ids, errors = staging.cas_ids_for_files(files, backend="jax")
    assert pool._total == 2, "the dirty pages were not reused"
    assert sorted(errors) == [6, 7]
    assert ids[5] is None  # the empty file gets no CAS ID
    for i, (p, size) in enumerate(files):
        if i in errors or size == 0:
            assert i not in ids or ids[i] is None
            continue
        assert ids[i] == cas.generate_cas_id(p, size), i

    monkeypatch.setenv("SDTPU_STAGE_NATIVE", "off")
    ids_off, errors_off = staging.cas_ids_for_files(files, backend="jax")
    assert sorted(errors_off) == sorted(errors)
    assert ids_off == ids


@requires_native
@pytest.mark.parametrize("mode,grows", [("on", "native"), ("off", "python")])
def test_jax_backend_stages_each_class_once(tmp_path, monkeypatch, mode,
                                            grows):
    """A two-class batch is two staged batches: both packed (native)
    with the flag on, both classic (python) with it off."""
    monkeypatch.setenv("SDTPU_STAGE_NATIVE", mode)
    files = []
    for i, n in enumerate((700, 120000, 5000, 130000)):
        p = str(tmp_path / f"e{i}.bin")
        files.append((p, _write(p, _pattern(n, seed=80 + i))))
    before = _stage_batches()
    ids, errors = staging.cas_ids_for_files(files, backend="jax")
    assert not errors
    assert all(ids[i] == cas.generate_cas_id(p, n)
               for i, (p, n) in enumerate(files))
    after = _stage_batches()
    other = "python" if grows == "native" else "native"
    assert after[grows] - before[grows] == 2
    assert after[other] == before[other]


@requires_native
def test_jax_backend_returns_its_pages(tmp_path, monkeypatch):
    """The pooled pages go back to the pool once the digests are
    fetched, and when the dispatch raises."""
    from spacedrive_tpu.ops import blake3_jax

    pool = staging.StagePool()
    monkeypatch.setattr(staging, "stage_buffer_pool", lambda: pool)
    monkeypatch.setenv("SDTPU_STAGE_NATIVE", "on")
    files = []
    for i, n in enumerate((2000, 140000)):
        p = str(tmp_path / f"l{i}.bin")
        files.append((p, _write(p, _pattern(n, seed=90 + i))))
    ids, errors = staging.cas_ids_for_files(files, backend="jax")
    assert not errors and len(ids) == 2
    assert pool._total == 2 and _in_use(pool) == 0

    def failing_hasher(words, lengths):
        raise RuntimeError("dispatch failed")

    monkeypatch.setattr(blake3_jax, "cas_hasher",
                        lambda hasher=None: (failing_hasher, 1))
    with pytest.raises(RuntimeError, match="dispatch failed"):
        staging.cas_ids_for_files(files, backend="jax")
    assert pool._total == 2 and _in_use(pool) == 0
