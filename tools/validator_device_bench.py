"""Real-chip jax-validator workload figure (VERDICT r3 item 9).

Runs the ObjectValidatorJob with backend="jax" — each file's chunk
chain streamed through StreamingShardedChecksum on the LOCAL device
mesh (one chip on the bench host) — against a small real corpus
through the full job system, and prints one JSON line with files/s and
MB/s. This is the honest single-device long-context-plane number the
virtual-mesh figure in PARITY.md explicitly is not.

Run ALONE (a chip belongs to one process). Corpus is deliberately
small: the figure characterizes this host's link, not the kernel.

Usage: python tools/validator_device_bench.py [n_files] [file_kb]
       python tools/validator_device_bench.py --kernel [n_files] [file_kb]

--kernel prints the KERNEL-SIDE figure instead (VERDICT r5 weak #5):
the checksum hasher behind checksums_words_batched timed as ITERS
chained executions inside one jit with a loop-carried dependency, so
the number excludes the dispatch +
D2H sync of any per-call wall timing. files/s + GB/s on whatever
device jax resolves (labeled as such).
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import sys
import time

# Keep each batched dispatch's padded grid small: 8 MiB ≈ 32 files at
# 256 KiB — still a 32× dispatch amortization over round 4's 1-file
# dispatches.
os.environ.setdefault("SDTPU_VAL_BATCH_BYTES", str(8 << 20))

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


async def run(n_files: int, file_kb: int) -> None:
    from spacedrive_tpu.locations.manager import (create_location,
                                                  scan_location)
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.objects.validator import ObjectValidatorJob

    from spacedrive_tpu import persist

    # Bench harness: blocking corpus teardown on the (idle) loop
    # at exit is the measured run's own cleanup.
    # sdlint: ok[blocking-async]
    with persist.scratch("bench.workdir") as tmp:
        corpus = os.path.join(tmp, "corpus")
        os.makedirs(corpus)
        rng = random.Random(3)
        total_bytes = 0
        for i in range(n_files):
            data = rng.randbytes(file_kb * 1024)
            with open(os.path.join(corpus, f"f{i}.bin"), "wb") as f:
                f.write(data)
            total_bytes += len(data)

        node = Node(os.path.join(tmp, "data"))
        await node.start()
        lib = node.create_library("valbench")
        loc = create_location(lib, corpus)
        await scan_location(node.jobs, lib, loc, backend="native",
                            with_media=False)
        await node.jobs.wait_idle()

        t0 = time.perf_counter()
        jid = await node.jobs.ingest(
            lib, ObjectValidatorJob(location_id=loc, backend="jax", mode="fill"))
        await node.jobs.wait(jid)
        dt = time.perf_counter() - t0
        n_done = lib.db.run("bench.checksum_count")["n"]
        # Same-weather comparator: the round-4 ONE-DISPATCH-PER-FILE path
        # (streaming sequence-sharded windows) on a subset — the
        # amortization claim is only honest against the per-file rate
        # measured in the SAME run.
        import glob

        import jax

        from spacedrive_tpu.ops.seqhash import sharded_file_checksum
        from spacedrive_tpu.parallel.mesh import batch_mesh

        mesh = batch_mesh(list(jax.devices())[:1])
        subset = sorted(glob.glob(os.path.join(corpus, "*.bin")))[
            :min(20, n_files)]
        sharded_file_checksum(mesh, subset[0])  # compile outside the timer
        t0 = time.perf_counter()
        for p_ in subset:
            sharded_file_checksum(mesh, p_)
        per_file_dt = (time.perf_counter() - t0) / len(subset)
        per_file_fps = 1.0 / per_file_dt

        print(json.dumps({
            "metric": "validator_jax_device_files_per_sec",
            "value": round(n_done / dt, 2),
            "unit": "files/s",
            "mb_per_sec": round(total_bytes / dt / 1e6, 2),
            "files": n_done,
            "file_kb": file_kb,
            "seconds": round(dt, 2),
            "backend": "jax (batched small-file dispatches + StreamingShardedChecksum for large)",
            "batched_small_files": True,
            "per_file_dispatch_files_per_sec": round(per_file_fps, 2),
            "batch_amortization_x": round((n_done / dt) / per_file_fps, 1),
        }))
        await node.shutdown()


def kernel_figure(n_files: int, file_kb: int, iters: int = 30) -> None:
    """Chained-in-jit throughput of the batched-validator checksum
    kernel (ops/blake3_jax hasher over a checksums_words_batched-shaped
    grid): ITERS executions chained through lax.scan
    with a loop-carried dependency so per-iteration wall is
    t_fixed/ITERS + t_marginal, best-of-3."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp
    import numpy as np

    from spacedrive_tpu.ops import blake3_jax as bj
    from spacedrive_tpu.ops.blake3_batch import (CHUNK_LEN,
                                                 WORDS_PER_CHUNK,
                                                 digests_to_hex)

    B = n_files
    blob_len = file_kb * 1024
    # The same shared pow2 chunk grid checksums_words_batched packs
    # pages into (equal sizes here: the bench characterizes the kernel,
    # not the padding policy).
    C = max(1, -(-blob_len // CHUNK_LEN))
    C = 1 << (C - 1).bit_length()
    rng = np.random.default_rng(7)
    buf = np.zeros((B, C * CHUNK_LEN), dtype=np.uint8)
    buf[:, :blob_len] = rng.integers(0, 256, size=(B, blob_len),
                                     dtype=np.uint8)
    words = buf.view("<u4").reshape(B, C, WORDS_PER_CHUNK)
    lengths = np.full(B, blob_len, dtype=np.int32)

    @jax.jit
    def looped(w, l):
        def body(acc, _):
            out = bj._blake3_impl_best(
                w, l | (acc[0, 0] & 1).astype(l.dtype))
            return out, None
        acc, _ = lax.scan(body, jnp.zeros((B, 8), jnp.uint32),
                          None, length=iters)
        return acc

    w = jax.device_put(words)
    l = jax.device_put(lengths)
    r = looped(w, l)
    np.asarray(r.ravel()[0])  # compile + warm
    t = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        r = looped(w, l)
        np.asarray(r.ravel()[0])
        t = min(t, (time.perf_counter() - t0) / iters)

    # Correctness spot check against the streaming oracle/native plane.
    hexes = digests_to_hex(bj.blake3_words(words, lengths)[:2])
    from spacedrive_tpu import native
    if native.available():
        for i in range(2):
            expect = native.blake3_digest(
                buf[i, :blob_len].tobytes()).hex()
            assert hexes[i] == expect, (i, hexes[i], expect)

    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "validator_kernel_files_per_sec",
        "value": round(B / t, 1),
        "unit": "files/s",
        "gb_per_sec": round(B * blob_len / t / 1e9, 3),
        "files": B,
        "file_kb": file_kb,
        "iters": iters,
        "chunk_grid_C": C,
        "device": f"{dev.platform}:{getattr(dev, 'device_kind', '?')}",
        "methodology": "ITERS chained in one jit, best-of-3",
    }))


if __name__ == "__main__":
    argv = [a for a in sys.argv[1:] if a != "--kernel"]
    n = int(argv[0]) if argv else 100
    kb = int(argv[1]) if len(argv) > 1 else 256
    if "--kernel" in sys.argv[1:]:
        kernel_figure(n, kb)
    else:
        asyncio.run(run(n, kb))
