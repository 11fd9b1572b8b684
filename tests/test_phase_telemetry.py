"""In-program phase timing: the hash phase of the device CAS path, the
validator's checksum paths and the node's event-loop lag, counted in the
registry and annotated on the profiler's clock (tracing.phase)."""

import asyncio
import glob
import time

import jax
import numpy as np
import pytest

from spacedrive_tpu import tasks, telemetry, tracing

# 200 small files, an empty one and 3 sampled large ones: two size
# classes, so two compiled grids, shared by the tests of this module.
SIZES = [200 + 37 * i for i in range(200)] + [0] + [110_000] * 3

CAS_PHASES = {
    "stage": telemetry.CAS_PHASE_STAGE,
    "pack": telemetry.CAS_PHASE_PACK,
    "dispatch": telemetry.CAS_PHASE_DISPATCH,
    "fetch": telemetry.CAS_PHASE_FETCH,
}


def _write_files(root, sizes, seed=5):
    rng = np.random.default_rng(seed)
    files = []
    for i, size in enumerate(sizes):
        p = root / f"f{i:04d}.bin"
        p.write_bytes(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
        files.append((str(p), size))
    return files


def _bucket(n):
    from spacedrive_tpu.ops.blake3_jax import _bucket_b

    return _bucket_b(n) if n else 0


def test_cas_phases_and_grid_bytes_on_the_jax_backend(tmp_path):
    """cas_ids_for_files(backend="jax") over a few hundred files advances
    all four phases, and the device-bytes counter equals the grids the
    file sizes imply: each size class padded to 57 or 101 KiB chunks a
    row and to its row bucket."""
    from spacedrive_tpu.ops import cas
    from spacedrive_tpu.ops.staging import cas_ids_for_files

    files = _write_files(tmp_path, SIZES)
    sizes = SIZES
    before = {k: c.value for k, c in CAS_PHASES.items()}
    grid0 = telemetry.CAS_DEVICE_BYTES.value
    msg0 = telemetry.CAS_MESSAGE_BYTES.value
    ids, errors = cas_ids_for_files(files, backend="jax")
    assert not errors
    assert ids[200] is None  # the empty file gets no CAS ID
    assert ids[0] == cas.generate_cas_id(files[0][0], sizes[0])
    assert ids[203] == cas.generate_cas_id(files[203][0], sizes[203])
    for k, c in CAS_PHASES.items():
        assert c.value > before[k], k
    n_small = sum(1 for s in sizes if 0 < s <= cas.MINIMUM_FILE_SIZE)
    n_large = sum(1 for s in sizes if s > cas.MINIMUM_FILE_SIZE)
    assert telemetry.CAS_DEVICE_BYTES.value - grid0 == (
        _bucket(n_small) * 101 * 1024 + _bucket(n_large) * 57 * 1024)
    assert telemetry.CAS_MESSAGE_BYTES.value - msg0 == sum(
        8 + (s if s <= cas.MINIMUM_FILE_SIZE else cas.LARGE_PAYLOAD_SIZE)
        for s in sizes if s > 0)


def test_validator_phases_cover_both_paths(tmp_path):
    """A verify pass on the jax backend advances sd_validator_* under
    both paths, with file bytes and file counts equal to the corpus."""
    from spacedrive_tpu.jobs.report import JobStatus
    from spacedrive_tpu.locations.indexer_job import IndexerJob
    from spacedrive_tpu.locations.manager import create_location
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.objects.validator import ObjectValidatorJob

    class LowCap(ObjectValidatorJob):
        """A cap low enough for a tiny corpus to take both paths."""
        SMALL_FILE_CAP = 4096

    # On the suite's 8-device mesh a streaming shard is 1 MiB, so the
    # split cap is 1 MiB: only the last file spans several shards.
    cap = LowCap._stream_plan(len(jax.devices()))[2]
    src = tmp_path / "loc"
    src.mkdir()
    sizes = [300, 1000, 4096, 5000, 20_000, 1_250_000]
    _write_files(src, sizes)
    small = [s for s in sizes if s <= cap]
    big = [s for s in sizes if s > cap]
    assert small and big
    phase = {
        (labels["path"], labels["phase"]): c for labels, c in
        telemetry.VALIDATOR_PHASE_SECONDS.samples()}
    counts0 = {k: c.value for k, c in phase.items()}
    b0 = {k: c.value for k, c in {
        "bf": telemetry.VAL_BATCHED_FILE_BYTES,
        "sf": telemetry.VAL_STREAMED_FILE_BYTES,
        "bg": telemetry.VAL_BATCHED_GRID_BYTES,
        "sg": telemetry.VAL_STREAMED_GRID_BYTES,
        "bn": telemetry.VAL_BATCHED_FILES,
        "sn": telemetry.VAL_STREAMED_FILES}.items()}

    async def main():
        node = Node(str(tmp_path / "data"))
        await node.start()
        try:
            lib = node.create_library("v")
            loc = create_location(lib, str(src))
            for job in (IndexerJob(location_id=loc),
                        ObjectValidatorJob(location_id=loc),
                        LowCap(location_id=loc, backend="jax",
                               mode="verify")):
                status = await node.jobs.wait(
                    await node.jobs.ingest(lib, job))
                assert status == JobStatus.COMPLETED, (job.NAME, status)
        finally:
            await node.shutdown()

    asyncio.run(main())
    moved = {k for k, c in phase.items() if c.value > counts0[k]}
    assert {("page", "rows"), ("page", "compare"), ("batched", "read"),
            ("batched", "pack"), ("batched", "device"),
            ("streamed", "read"), ("streamed", "pack")} <= moved, moved
    # every file that fits one shard is batched: nothing reaches the
    # host hash of a single-shard stream
    assert ("streamed", "host_hash") not in moved, moved
    assert telemetry.VAL_BATCHED_FILE_BYTES.value - b0["bf"] == sum(small)
    assert telemetry.VAL_STREAMED_FILE_BYTES.value - b0["sf"] == sum(big)
    assert telemetry.VAL_BATCHED_FILES.value - b0["bn"] == len(small)
    assert telemetry.VAL_STREAMED_FILES.value - b0["sn"] == len(big)
    assert telemetry.VAL_BATCHED_GRID_BYTES.value - b0["bg"] >= sum(small)
    assert telemetry.VAL_STREAMED_GRID_BYTES.value - b0["sg"] >= sum(big)


def test_validator_routes_single_shard_files_to_the_batched_grid(tmp_path):
    """With a 1 MiB streaming shard (8-device mesh), a 500 KB file above
    SMALL_FILE_CAP is hashed on the batched grid and a 1.2 MiB file is
    streamed; neither reaches the host hash, and both digests are
    BLAKE3's."""
    from spacedrive_tpu.objects.validator import ObjectValidatorJob
    from spacedrive_tpu.ops.blake3_batch import blake3_batch_np

    class LowCap(ObjectValidatorJob):
        SMALL_FILE_CAP = 4096

    assert LowCap._stream_plan(len(jax.devices()))[1:] == (1024, 1 << 20)
    files = _write_files(tmp_path, [500_000, 1_250_000], seed=9)
    counters = {"batched": telemetry.VAL_BATCHED_FILES,
                "streamed": telemetry.VAL_STREAMED_FILES,
                "host_hash": telemetry.VAL_STREAMED_HOST_HASH}
    before = {k: c.value for k, c in counters.items()}
    job = LowCap(location_id=1, backend="jax", mode="verify")
    errors = []
    got = {path: hx for _r, path, hx in job._checksums_jax(
        [({"id": i}, path) for i, (path, _size) in enumerate(files)],
        errors)}
    assert not errors, errors
    moved = {k: c.value - before[k] for k, c in counters.items()}
    assert moved == {"batched": 1, "streamed": 1, "host_hash": 0}, moved
    for path, _size in files:
        with open(path, "rb") as f:
            assert got[path] == blake3_batch_np([f.read()])[0].hex(), path


def test_event_loop_lag_probe_sees_a_blocked_loop(tmp_path):
    """Blocking the node's loop for 200 ms puts at least 0.15 s into the
    lag histogram's sum; the probe task is gone after shutdown."""
    from spacedrive_tpu.node import Node

    async def main():
        node = Node(str(tmp_path / "data"))
        await node.start()
        try:
            live = {r.name for r in tasks.live(node.task_owner)}
            assert "loop-lag" in live, live
            await asyncio.sleep(0.15)  # the probe is asleep on its 100 ms
            s0 = telemetry.EVENT_LOOP_LAG.sum
            time.sleep(0.2)  # the whole loop stalls here
            await asyncio.sleep(0.15)
            assert telemetry.EVENT_LOOP_LAG.sum - s0 >= 0.15
        finally:
            await node.shutdown()
        assert not [r for r in tasks.live(node.task_owner)
                    if r.name == "loop-lag"]
        assert node.loop_lag._task is None

    asyncio.run(main())


def test_phase_without_a_capture_counts_and_raises_nothing():
    c = telemetry.CAS_PHASE_FETCH
    v0 = c.value
    with tracing.phase("cas.phase/fetch", c) as ph:
        time.sleep(0.01)
    assert ph.t1 - ph.t0 >= 0.01
    assert c.value - v0 == pytest.approx(ph.t1 - ph.t0)
    with pytest.raises(KeyError):
        with tracing.phase("cas.phase/fetch", c):
            raise KeyError("body errors propagate")
    assert c.value > v0 + 0.01


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
    return out


def test_phases_and_spans_share_the_profiler_clock(tmp_path):
    """Under a profiler capture the program did not start, the host plane
    holds the four cas.phase annotations inside cas_ids/jax, inside the
    job span that wraps them."""
    import jax

    from spacedrive_tpu.ops.staging import cas_ids_for_files

    files = _write_files(tmp_path, SIZES)
    cas_ids_for_files(files, backend="jax")  # compile outside the capture
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        with tracing.span("job/phase_probe"):
            ids, errors = cas_ids_for_files(files, backend="jax")
    finally:
        jax.profiler.stop_trace()
    assert not errors and len(ids) == len(SIZES)
    events = _host_events(trace_dir)
    by_name = {}
    for name, s, e in events:
        by_name.setdefault(name, []).append((s, e))
    (job,) = by_name["job/phase_probe"]
    (outer,) = by_name["cas_ids/jax"]
    assert job[0] <= outer[0] and outer[1] <= job[1]
    for ph in ("stage", "pack", "dispatch", "fetch"):
        spans = by_name[f"cas.phase/{ph}"]
        assert spans and all(outer[0] <= s and e <= outer[1]
                             for s, e in spans), ph
