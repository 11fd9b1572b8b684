"""Round benchmark: batched CAS-ID generation throughput on device.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

The workload is the FileIdentifierJob hot kernel (SURVEY.md §3.3): for a
batch of large files, hash the 8-byte size prefix + 57,344 sampled bytes
with BLAKE3 and truncate to 16 hex chars
(/root/reference/core/src/object/cas.rs:23-62 semantics).

`vs_baseline` is the speedup over THIS REPO'S NATIVE C++ AVX2 PLANE
(native/sdio.cpp `sd_blake3_many`, 8-way message-parallel AVX2 lanes) on
the bench host's CPU — the honest stand-in for the reference's CPU path
(the SIMD `blake3` crate behind cas.rs; the reference publishes no
numbers, BASELINE.md). Round 1 compared against the repo's numpy
fallback, which inflated the ratio ~8×; this baseline is the fastest
CPU implementation in the repo.

Timing methodology: the device number chains ITERS kernel executions
inside one jitted scan with a loop-carried dependency, timed with a
single D2H sync, so per-dispatch overhead is amortized out of the
kernel number (tools/perf_probe.py documents this). The kernel number
excludes H2D; `h2d_gbps` and `e2e_overlapped_files_per_sec`
(steady-state depth-N pipeline = max(transfer, compute) per device
stream) are reported alongside so the end-to-end story is explicit.

Needs a TPU: without one it exits non-zero before measuring anything,
and the JSON line names the device it ran on.
"""

from __future__ import annotations

import json
import time

import numpy as np

# Batch size amortizes the chip's per-dispatch overhead. 16 K files is
# also the identifier's device step size (ops/staging.AUTO_DEVICE_BATCH).
# ITERS amortizes the fixed sync cost of ONE timed call.
B = 16384
ITERS = 60
MSG_BYTES = 57352  # 8-byte size prefix + 57,344 sampled bytes


def main() -> None:
    from spacedrive_tpu.xla_env import enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py needs a TPU; the default JAX device "
                         f"is {dev.platform!r}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}

    from spacedrive_tpu.ops import blake3_jax as bj

    rng = np.random.default_rng(0)
    payloads = rng.integers(0, 256, size=(B, 57344), dtype=np.uint8)
    sizes = rng.integers(200_000, 50_000_000, size=B).astype(np.uint64)
    words, lengths = bj.build_cas_messages(payloads, sizes)

    # Device path: pallas kernel on TPU (blake3_words dispatches), timed
    # as ITERS chained executions inside one jit (see module docstring).
    import jax.lax as lax
    import jax.numpy as jnp

    @jax.jit
    def looped(w, l):
        def body(acc, _):
            out = bj._blake3_impl_best(w, l | (acc[0, 0] & 1).astype(l.dtype))
            return out, None
        acc, _ = lax.scan(body, jnp.zeros((B, 8), jnp.uint32),
                          None, length=ITERS)
        return acc

    w = jax.device_put(words)
    l = jax.device_put(lengths)
    r = looped(w, l)
    np.asarray(r.ravel()[0])  # compile + warm
    t_kernel = float("inf")
    for _ in range(3):  # best-of-3
        t0 = time.perf_counter()
        r = looped(w, l)
        np.asarray(r.ravel()[0])
        t_kernel = min(t_kernel, (time.perf_counter() - t0) / ITERS)
    device_fps = B / t_kernel

    # Correctness spot check against the streaming oracle.
    out = bj.blake3_words(words, lengths)
    cas_ids = bj.digests_to_cas_ids(out)
    from spacedrive_tpu.ops.cas import cas_id_of_payload

    for i in (0, B // 2, B - 1):
        expect = cas_id_of_payload(int(sizes[i]), payloads[i].tobytes())
        assert cas_ids[i] == expect, (i, cas_ids[i], expect)

    # Honest CPU baseline: the repo's AVX2 C++ plane, same messages.
    from spacedrive_tpu import native

    if native.available():
        lens = np.full(B, payloads.shape[1], np.int32)
        native.blake3_many(payloads[:64], lens[:64], sizes[:64])  # warm
        cpu_fps = 0.0
        for _ in range(3):  # best-of-3, symmetric with the device side
            t0 = time.perf_counter()
            native.blake3_many(payloads, lens, sizes)
            cpu_fps = max(cpu_fps, B / (time.perf_counter() - t0))
        baseline_name = "native C++ AVX2 blake3_many (this repo, bench host CPU)"
    else:  # no native build: fall back to numpy (and say so)
        from spacedrive_tpu.ops import blake3_batch as bb

        t0 = time.perf_counter()
        bb.blake3_batch(np, words[:128], lengths[:128])
        cpu_fps = 128 / (time.perf_counter() - t0)
        baseline_name = "numpy batched blake3 (native plane unavailable)"

    # H2D link measurement (marker-synced full fetch; a sliced fetch
    # would compile a second program). A 117 MB slice ×2 instead of the
    # full 956 MB ×3 — the per-byte rate is what matters.
    probe = np.ascontiguousarray(words[:2048])
    np.asarray(jax.device_put(np.zeros(16, np.uint8)))  # warm the path
    t0 = time.perf_counter()
    for _ in range(2):  # fixed sync cost alone
        np.asarray(jax.device_put(np.zeros(16, np.uint8)))
    t_sync = (time.perf_counter() - t0) / 2
    t0 = time.perf_counter()
    for _ in range(2):
        jax.device_put(probe)
        np.asarray(jax.device_put(np.zeros(16, np.uint8)))
    per_probe = (time.perf_counter() - t0) / 2
    # scale only the TRANSFER portion by the byte ratio — extrapolating
    # the fixed sync overhead would understate fast links ~35%
    t_h2d = (max(per_probe - t_sync, 1e-4)
             * (words.nbytes / probe.nbytes) + t_sync)

    # MEASURED depth-N pipeline (ops/overlap.py): concurrent C++ staging
    # of batches i+1..i+k overlaps H2D+kernel of batch i across the
    # device ring, digests retired with a one-batch lag.
    # Corpus is sparse files sized so the run is ~20-40 s
    # at the probed link speed (the sum of stage+transfer+kernel serial
    # would be strictly larger; the bound field is what a perfect
    # pipeline would sustain from the same run's component times).
    import shutil
    import tempfile

    from spacedrive_tpu.ops import overlap

    link_bps = words.nbytes / t_h2d
    # Slow links: shrink the per-batch payload so the pipeline + its two
    # calibration brackets stay inside the bench timeout. The
    # steady-state shape is unchanged — only fewer files per batch.
    pb = 2048 if link_bps >= 50e6 else 512
    per_batch_s = pb * MSG_BYTES / max(link_bps, 1e6)
    n_batches = int(max(3, min(12, 30.0 / max(per_batch_s, 0.25))))
    proot = tempfile.mkdtemp(prefix="sdtpu-overlap-")
    try:
        pipeline_batches = overlap.make_sparse_corpus(
            proot, pb * n_batches, 120_000, pb)
        _res, pstats = overlap.run_overlapped(pipeline_batches)
    finally:
        shutil.rmtree(proot, ignore_errors=True)
    e2e_fps = pstats.files_per_sec          # measured, not a formula
    breport = pstats.bound_report()         # same-run bound accounting

    print(json.dumps({
        "metric": "cas_ids_per_sec_large_files",
        "device": device,
        "value": round(device_fps, 1),
        "unit": "files/s",
        "vs_baseline": round(device_fps / cpu_fps, 2),
        "baseline": baseline_name,
        "baseline_files_per_sec": round(cpu_fps, 1),
        "bytes_per_sec": round(device_fps * MSG_BYTES, 0),
        "h2d_gbps": round(words.nbytes / t_h2d / 1e9, 2),
        "e2e_overlapped_files_per_sec": round(e2e_fps, 1),
        "e2e_overlapped_bound_files_per_sec":
            round(pstats.bound_files_per_sec, 1),
        # Same-run bound accounting (VERDICT r5 weak #1): calibration
        # now interleaves with the measurement (ops/overlap.py pauses
        # the pipeline mid-run), so measured-vs-bound compares within
        # one weather window; when measured still lands < 0.9× bound,
        # `reason` explains it from THIS run's calibration spread.
        "e2e_overlapped_bound_ratio": breport["ratio"],
        "e2e_overlap_calibrations": breport["calibrations"],
        "e2e_overlap_binding_spread": breport["binding_component_spread"],
        "e2e_overlapped_bound_reason": breport["reason"],
        # Depth-N pipeline shape of the measured run: how many batches
        # were in flight, across which device ring, and how much of the
        # staged footprint the donated kernel recycled.
        "pipeline_depth": pstats.depth,
        "pipeline_depth_high_water": pstats.depth_high_water,
        "pipeline_devices": pstats.n_devices,
        "pipeline_per_device_batches": pstats.per_device_batches,
        "pipeline_donated": pstats.donate,
        "pipeline_donated_reuse": pstats.donated_reuse,
        "pipeline_h2d_gbps_measured":
            round(pstats.h2d_bytes / pstats.h2d_s / 1e9, 3)
            if pstats.h2d_s else 0.0,
        "pipeline_stall_s": {
            "stage": round(pstats.stage_s, 3),
            "retire": round(pstats.retire_stall_s, 3),
            "calibration": round(pstats.calibration_s, 3),
        },
        "e2e_overlap_components_s": {
            "stage": round(pstats.t_stage_1, 3),
            "h2d": round(pstats.t_h2d_1, 3),
            "kernel_fetch": round(pstats.t_kernel_1, 3),
            "stage_post": round(pstats.t_stage_2, 3),
            "h2d_post": round(pstats.t_h2d_2, 3),
            "kernel_fetch_post": round(pstats.t_kernel_2, 3),
        },
    }))


if __name__ == "__main__":
    main()
