"""Validator verify mode: bit-rot detection (net-new vs the reference,
which only fills NULL checksums and never re-verifies)."""

import asyncio
import os

import pytest

from spacedrive_tpu.jobs.report import JobStatus
from spacedrive_tpu.locations.indexer_job import IndexerJob
from spacedrive_tpu.locations.manager import create_location
from spacedrive_tpu.node import Node
from spacedrive_tpu.objects.validator import ObjectValidatorJob


def _run(coro):
    return asyncio.run(coro)


def test_verify_mode_detects_corruption(tmp_path):
    src = tmp_path / "loc"
    src.mkdir()
    (src / "good.bin").write_bytes(b"intact" * 100)
    (src / "bad.bin").write_bytes(b"victim" * 100)
    node = Node(str(tmp_path / "data"))
    lib = node.create_library("t")
    events = []
    node.events.subscribe(
        lambda e: e.get("type") == "IntegrityViolation"
        and events.append(e))

    async def main():
        loc = create_location(lib, str(src))
        for job in (IndexerJob(location_id=loc),
                    ObjectValidatorJob(location_id=loc)):  # fill pass
            jid = await node.jobs.ingest(lib, job)
            assert await node.jobs.wait(jid) in (
                JobStatus.COMPLETED, JobStatus.COMPLETED_WITH_ERRORS)
        assert lib.db.query_one(
            "SELECT COUNT(*) AS n FROM file_path "
            "WHERE integrity_checksum IS NOT NULL")["n"] == 2

        # Silent corruption: same size, different bytes, old mtime kept.
        st = (src / "bad.bin").stat()
        (src / "bad.bin").write_bytes(b"C" * 600)  # len("victim"*100)
        os.utime(src / "bad.bin", (st.st_atime, st.st_mtime))

        jid = await node.jobs.ingest(lib, ObjectValidatorJob(
            location_id=loc, mode="verify"))
        status = await node.jobs.wait(jid)
        assert status == JobStatus.COMPLETED_WITH_ERRORS
        row = lib.db.query_one(
            "SELECT errors_text FROM job WHERE id = ?", (jid,))
        assert "CHECKSUM MISMATCH" in row["errors_text"]
        assert "bad.bin" in row["errors_text"]
        assert "good.bin" not in row["errors_text"]
        assert events and events[0]["path"].endswith("bad.bin")
        # the stored checksum is untouched evidence
        stored = lib.db.query_one(
            "SELECT integrity_checksum FROM file_path WHERE name='bad'")
        assert stored["integrity_checksum"]  # unchanged, not 'repaired'
        await node.shutdown()
    _run(main())


def test_legit_edit_invalidates_and_reheals(tmp_path):
    """A legitimate file edit is NOT corruption: the rescan invalidates
    cas_id/checksum/object link, the pipeline re-identifies + re-fills,
    and a verify pass then runs clean."""
    import time as _time

    src = tmp_path / "loc"
    src.mkdir()
    (src / "doc.bin").write_bytes(b"version-one" * 50)
    node = Node(str(tmp_path / "data"))
    lib = node.create_library("t")

    async def main():
        from spacedrive_tpu.objects.identifier import FileIdentifierJob

        loc = create_location(lib, str(src))
        for job in (IndexerJob(location_id=loc),
                    FileIdentifierJob(location_id=loc),
                    ObjectValidatorJob(location_id=loc)):
            jid = await node.jobs.ingest(lib, job)
            assert await node.jobs.wait(jid) == JobStatus.COMPLETED
        old = lib.db.query_one(
            "SELECT cas_id, integrity_checksum FROM file_path "
            "WHERE name='doc'")

        _time.sleep(0.02)
        (src / "doc.bin").write_bytes(b"version-TWO" * 70)  # real edit
        for job in (IndexerJob(location_id=loc),
                    FileIdentifierJob(location_id=loc),
                    ObjectValidatorJob(location_id=loc),
                    ObjectValidatorJob(location_id=loc, mode="verify")):
            jid = await node.jobs.ingest(lib, job)
            status = await node.jobs.wait(jid)
            assert status == JobStatus.COMPLETED, (job.NAME, status)
        new = lib.db.query_one(
            "SELECT cas_id, integrity_checksum FROM file_path "
            "WHERE name='doc'")
        assert new["cas_id"] != old["cas_id"]
        assert new["integrity_checksum"] != old["integrity_checksum"]
        await node.shutdown()
    _run(main())


def test_verify_mode_clean_completes(tmp_path):
    src = tmp_path / "loc"
    src.mkdir()
    (src / "a.bin").write_bytes(b"fine" * 50)
    node = Node(str(tmp_path / "data"))
    lib = node.create_library("t")

    async def main():
        loc = create_location(lib, str(src))
        for job in (IndexerJob(location_id=loc),
                    ObjectValidatorJob(location_id=loc),
                    ObjectValidatorJob(location_id=loc, mode="verify")):
            jid = await node.jobs.ingest(lib, job)
            assert await node.jobs.wait(jid) == JobStatus.COMPLETED
        await node.shutdown()
    _run(main())


@pytest.mark.parametrize("n_devices,shard_chunks,cap", [
    (1, 8192, 8 << 20), (2, 4096, 4 << 20),
    (4, 2048, 4 << 20), (8, 1024, 4 << 20)])
def test_stream_plan_caps_the_batched_path_at_one_shard(
        n_devices, shard_chunks, cap):
    """The batched/streamed split follows the streaming shard: on one
    device a shard is 8 MiB, so every file up to 8 MiB is batched; from
    four devices the shard is under SMALL_FILE_CAP, which holds."""
    D, got_chunks, got_cap = ObjectValidatorJob._stream_plan(n_devices)
    assert D == n_devices
    assert got_chunks == shard_chunks
    assert got_cap == cap
