"""CAS-ID sampling semantics vs the reference algorithm (cas.rs)."""

import os
import random
import struct

import pytest

from spacedrive_tpu.ops.blake3_ref import Blake3
from spacedrive_tpu.ops.cas import (
    HEADER_OR_FOOTER_SIZE,
    LARGE_PAYLOAD_SIZE,
    MINIMUM_FILE_SIZE,
    SAMPLE_COUNT,
    SAMPLE_SIZE,
    file_checksum,
    generate_cas_id,
    sample_spec,
)


def make_file(tmp_path, name, data: bytes):
    p = tmp_path / name
    p.write_bytes(data)
    return p


def test_small_file_spec():
    assert sample_spec(0) == [(0, 0)]
    assert sample_spec(MINIMUM_FILE_SIZE) == [(0, MINIMUM_FILE_SIZE)]


def test_large_file_spec_shape():
    for size in [MINIMUM_FILE_SIZE + 1, 200_000, 10_000_000, 5_000_000_001]:
        spec = sample_spec(size)
        assert len(spec) == 2 + SAMPLE_COUNT
        assert spec[0] == (0, HEADER_OR_FOOTER_SIZE)
        assert spec[-1] == (size - HEADER_OR_FOOTER_SIZE, HEADER_OR_FOOTER_SIZE)
        jump = (size - 2 * HEADER_OR_FOOTER_SIZE) // SAMPLE_COUNT
        for k in range(SAMPLE_COUNT):
            off, ln = spec[1 + k]
            assert ln == SAMPLE_SIZE
            assert off == HEADER_OR_FOOTER_SIZE + k * jump
            assert off + ln <= size  # read_exact must succeed
        assert sum(ln for _, ln in spec) == LARGE_PAYLOAD_SIZE


def manual_cas(data: bytes) -> str:
    """Independent re-derivation: hash prefix + explicitly sliced payload."""
    size = len(data)
    h = Blake3()
    h.update(struct.pack("<Q", size))
    if size <= MINIMUM_FILE_SIZE:
        h.update(data)
    else:
        jump = (size - 2 * HEADER_OR_FOOTER_SIZE) // SAMPLE_COUNT
        h.update(data[:HEADER_OR_FOOTER_SIZE])
        for k in range(SAMPLE_COUNT):
            off = HEADER_OR_FOOTER_SIZE + k * jump
            h.update(data[off : off + SAMPLE_SIZE])
        h.update(data[size - HEADER_OR_FOOTER_SIZE :])
    return h.hexdigest()[:16]


def test_cas_id_matches_manual(tmp_path):
    rng = random.Random(42)
    for size in [0, 1, 1000, MINIMUM_FILE_SIZE, MINIMUM_FILE_SIZE + 1, 150_000, 400_000]:
        data = os.urandom(size)
        p = make_file(tmp_path, f"f{size}", data)
        got = generate_cas_id(p)
        assert got == manual_cas(data), f"size={size}"
        assert len(got) == 16


def test_checksum(tmp_path):
    data = os.urandom(3_000_000)  # spans multiple 1 MiB blocks
    p = make_file(tmp_path, "big", data)
    from spacedrive_tpu.ops.blake3_ref import blake3_hex

    got = file_checksum(p)
    assert got == blake3_hex(data)
    assert len(got) == 64


def test_backends_agree_on_real_files(tmp_path):
    """oracle / numpy / native(if built) produce identical CAS IDs."""
    from spacedrive_tpu import native
    from spacedrive_tpu.ops.staging import cas_ids_for_files

    rng = random.Random(5)
    files = []
    for i, size in enumerate([0, 17, 1024, MINIMUM_FILE_SIZE,
                              MINIMUM_FILE_SIZE + 1, 250_000, 800_000]):
        p = make_file(tmp_path, f"b{i}.bin",
                      bytes(rng.getrandbits(8) for _ in range(size)))
        files.append((str(p), size))

    oracle, err = cas_ids_for_files(files, backend="oracle")
    assert not err
    numpy_ids, err = cas_ids_for_files(files, backend="numpy")
    assert not err
    assert numpy_ids == oracle
    if native.available():
        native_ids, err = cas_ids_for_files(files, backend="native")
        assert not err
        assert native_ids == oracle


# -- auto device engagement policy (VERDICT r1 item 3) ----------------------


def test_auto_device_batch_policy(monkeypatch):
    """Big scans engage the device only when the link probe wins; small
    scans and slow links stay native. SDTPU_DEVICE_PIPELINE overrides."""
    from spacedrive_tpu.ops import staging

    monkeypatch.setenv("SDTPU_DEVICE_PIPELINE", "force")
    assert staging.auto_device_batch(100) is None  # below min orphans
    assert staging.auto_device_batch(100_000) == staging.AUTO_DEVICE_BATCH

    monkeypatch.setenv("SDTPU_DEVICE_PIPELINE", "off")
    assert staging.auto_device_batch(100_000) is None

    # Unset: CPU test platform is not a TPU, so the probe declines.
    monkeypatch.delenv("SDTPU_DEVICE_PIPELINE", raising=False)
    assert staging.device_pipeline_worthwhile() is False
    assert staging.auto_device_batch(100_000) is None


def test_identifier_auto_resolves_device_chunk(tmp_path, monkeypatch):
    """backend=auto + forced device pipeline: init records the device
    step size in job data so resume pages identically."""
    import asyncio

    import numpy as np

    from spacedrive_tpu.locations.indexer_job import IndexerJob
    from spacedrive_tpu.locations.manager import create_location
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.objects.identifier import FileIdentifierJob
    from spacedrive_tpu.ops import staging

    corpus = tmp_path / "corpus"
    corpus.mkdir()
    rng = np.random.default_rng(5)
    for i in range(8):
        (corpus / f"f{i}.bin").write_bytes(rng.bytes(300))

    monkeypatch.setenv("SDTPU_DEVICE_PIPELINE", "force")
    monkeypatch.setattr(staging, "AUTO_DEVICE_MIN_ORPHANS", 4)

    async def scenario():
        node = Node(str(tmp_path / "data"))
        await node.start()
        try:
            lib = node.create_library("lib")
            loc = create_location(lib, str(corpus))
            await node.jobs.wait(await node.jobs.ingest(
                lib, IndexerJob(location_id=loc)))

            job = FileIdentifierJob(location_id=loc, backend="auto")
            jid = await node.jobs.ingest(lib, job)
            await node.jobs.wait(jid)
            # All 8 orphans identified in device-batch-paged steps.
            return lib.db.query_one(
                "SELECT COUNT(*) AS n FROM file_path "
                "WHERE cas_id IS NOT NULL")["n"]
        finally:
            await node.shutdown()

    assert asyncio.run(scenario()) == 8


@pytest.mark.parametrize(
    "device,donate,cpus,hash_ahead,commit_every", [
        (False, "on", 1, False, 8),
        (False, "on", 2, True, 1),
        (True, "on", 2, True, 1),
        (True, "off", 2, False, 1),
        (True, "on", 1, False, 1),
    ], ids=["host-1cpu", "host-2cpu", "device-donate-2cpu",
            "device-nodonate-2cpu", "device-donate-1cpu"])
def test_identifier_hash_ahead_rule(tmp_path, monkeypatch, device, donate,
                                    cpus, hash_ahead, commit_every):
    """The identifier hashes chunk k+1 ahead of chunk k's commit only
    with a second usable core, and on device-engaged runs only with
    buffer donation on. A step commits one chunk unless neither
    applies: the single-core host plane groups 8 reference-size chunks
    per commit."""
    import asyncio
    from types import SimpleNamespace

    from spacedrive_tpu.locations.indexer_job import IndexerJob
    from spacedrive_tpu.locations.manager import create_location
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.objects import identifier

    corpus = tmp_path / "corpus"
    corpus.mkdir()
    for i in range(3):
        (corpus / f"f{i}.bin").write_bytes(bytes([i]) * 300)
    monkeypatch.setattr(identifier, "_usable_cpus", lambda: cpus)
    monkeypatch.setenv("SDTPU_DONATE_BUFFERS", donate)
    assert identifier._hash_ahead(device) is hash_ahead

    async def scenario():
        node = Node(str(tmp_path / "data"))
        await node.start()
        try:
            lib = node.create_library("lib")
            loc = create_location(lib, str(corpus))
            await node.jobs.wait(await node.jobs.ingest(
                lib, IndexerJob(location_id=loc)))
            job = identifier.FileIdentifierJob(
                location_id=loc, backend="jax" if device else "numpy")
            ctx = SimpleNamespace(db=lib.db, progress=lambda **kw: None)
            return await job.init(ctx)
        finally:
            await node.shutdown()

    data, steps = asyncio.run(scenario())
    assert data["hash_ahead"] is hash_ahead
    assert data["commit_every"] == commit_every
    assert data["chunk_size"] == identifier.CHUNK_SIZE
    assert len(steps) == 1
