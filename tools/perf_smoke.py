"""End-to-end perf smoke: the BASELINE.md benchmark configs, runnable.

Generates a deterministic corpus (tools/make_corpus.py), runs the real
pipeline through the real job system — index → identify → validate →
exact-dup — and prints one JSON line per stage with files/sec
(BASELINE.json configs 1–3; config 4 runs when images are requested,
config 5 is this with --files 1000000 across multiple locations).

    python tools/perf_smoke.py --files 10000 [--backend auto] [--images 300]

--telemetry resets the node-wide metrics registry before the run and
sources the identify stage's hash-vs-host phase_split from the SAME
`sd_identifier_phase_seconds_total` counters production serves on
GET /metrics (instead of the job report's metadata), then appends a
final {"stage": "telemetry"} line with the full registry snapshot.
--json PATH additionally writes every stage line (and the snapshot,
when --telemetry is on) as one BENCH_r*-style artifact.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _registry_phase_split():
    """The identify hash-vs-host split, read from the SAME registry
    counters GET /metrics serves (sd_identifier_phase_seconds_total) —
    production-visible numbers, not job-report metadata."""
    from spacedrive_tpu import telemetry

    fam = telemetry.snapshot().get(
        "sd_identifier_phase_seconds_total", {})
    phases = {e["labels"]["phase"]: float(e["value"])
              for e in fam.get("labeled", [])}
    hash_ms = phases.get("hash", 0.0) * 1000.0
    stage_ms = phases.get("prep", 0.0) * 1000.0
    host_ms = sum(v for k, v in phases.items()
                  if k not in ("hash", "prep", "step_total",
                               "overlap_wait")) * 1000.0
    total = hash_ms + stage_ms + host_ms
    if not total:
        return None
    return {
        "hash_ms": round(hash_ms, 1),
        "stage_ms": round(stage_ms, 1),
        "host_ms": round(host_ms, 1),
        "host_pct": round(100.0 * host_ms / total, 1),
        "source": "registry",
    }


async def run(files: int, backend: str, images: int, keep: str | None,
              device_batch: int | None = None, small: bool = False,
              validate_backend: str | None = None,
              with_telemetry: bool = False, json_out: str = "",
              trace_out: str = ""):
    from tools.make_corpus import make_corpus

    from spacedrive_tpu import telemetry
    from spacedrive_tpu.jobs.report import JobStatus
    from spacedrive_tpu.locations.indexer_job import IndexerJob
    from spacedrive_tpu.locations.manager import create_location
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.objects.dedup import exact_duplicate_groups
    from spacedrive_tpu.objects.identifier import FileIdentifierJob
    from spacedrive_tpu.objects.validator import ObjectValidatorJob

    lines: list = []
    health_problems: list = []

    def emit(line: dict) -> None:
        lines.append(line)
        print(json.dumps(line), flush=True)

    monitor = None
    if with_telemetry:
        # The artifact should cover THIS run only, not whatever the
        # process did before (the registry is process-global).
        telemetry.reset()
        # Arm the SQL auditor in COUNT mode so the `sql` stage carries
        # per-statement counts and the tx histogram on unsanitized
        # bench runs (violations count, never raise). Before any
        # Database opens — the factory is read per connection.
        from spacedrive_tpu import sanitize
        from spacedrive_tpu.store import sqlaudit

        if not sqlaudit.armed():
            sqlaudit.arm("count", sanitize.record)
        # Whole-run health window: cursors established here, sampled
        # once at the end — the artifact's `health` stage shows what
        # saturated DURING the run, next to the numbers it explains.
        from spacedrive_tpu.health import HealthMonitor

        monitor = HealthMonitor()
    if trace_out:
        # Same per-run hygiene for the flight recorder: the exported
        # timeline + span ring should cover this run only.
        from spacedrive_tpu import flight, tracing

        flight.RECORDER.clear()
        tracing.clear_span_ring()

    from spacedrive_tpu import persist

    # Bench harness: blocking corpus teardown on the (idle) loop
    # at exit is the measured run's own cleanup.
    # sdlint: ok[blocking-async]
    with persist.scratch("bench.workdir", keep=keep) as root:
        corpus = os.path.join(root, "corpus")
        t0 = time.perf_counter()
        stats = make_corpus(corpus, files=files, dup_rate=0.1, images=images,
                            small_only=small)
        stats.pop("planted")
        emit({"stage": "corpus", "seconds":
              round(time.perf_counter() - t0, 2), **stats})

        node = Node(os.path.join(root, "data"))
        await node.start()
        lib = node.create_library("perf")
        loc = create_location(lib, corpus)

        async def stage(name, job):
            t0 = time.perf_counter()
            jid = await node.jobs.ingest(lib, job)
            status = await node.jobs.wait(jid)
            dt = time.perf_counter() - t0
            assert status in (JobStatus.COMPLETED,
                              JobStatus.COMPLETED_WITH_ERRORS), (name, status)
            n = lib.db.run("bench.file_count")["n"]
            line = {
                "stage": name, "seconds": round(dt, 2),
                "files": n, "files_per_sec": round(n / dt, 1),
                "status": int(status),
            }
            from spacedrive_tpu.jobs.report import JobReport
            row = lib.db.run("jobs.report.by_id", (jid,))
            report = JobReport.from_row(row) if row else None
            if report and report.metadata.get("phase_ms"):
                # Where the ms/file goes (fetch/prep/hash/db/ops), summed
                # over all chunks — the e2e profile, not the kernel number.
                pm = report.metadata["phase_ms"]
                line["phase_ms"] = pm
                line["chunk_size"] = report.metadata.get("chunk_size")
                # The hash-vs-host split as a tracked artifact: how much of
                # the accounted COST is hashing versus host-side
                # serialization (op log, domain writes, commits, paging) —
                # the ratio the op-log work is judged by, printed per run
                # instead of reconstructed from README prose. Phases are
                # true per-phase costs even when overlapped (the identifier
                # merges worker-measured times and books the consumer's
                # stall separately as overlap_wait), so this is cost
                # attribution, not a wall-clock partition.
                hash_ms = pm.get("hash", 0.0)
                stage_ms = pm.get("prep", 0.0)  # hashing-pipeline staging
                host_ms = sum(v for k, v in pm.items()
                              if k not in ("hash", "prep", "step_total",
                                           "overlap_wait"))
                total = hash_ms + stage_ms + host_ms
                if total:
                    line["phase_split"] = {
                        "hash_ms": round(hash_ms, 1),
                        "stage_ms": round(stage_ms, 1),
                        "host_ms": round(host_ms, 1),
                        "host_pct": round(100.0 * host_ms / total, 1),
                    }
            if with_telemetry and name == "identify":
                # Same split, sourced from the live registry counters the
                # /metrics endpoint serves — the production-visible number.
                reg_split = _registry_phase_split()
                if reg_split:
                    line["phase_split"] = reg_split
            emit(line)
            return dt

        await stage("index", IndexerJob(location_id=loc))
        await stage("identify", FileIdentifierJob(location_id=loc,
                                                  backend=backend,
                                                  device_batch=device_batch))
        await stage("validate", ObjectValidatorJob(
            location_id=loc, backend=validate_backend or "auto"))
        if validate_backend:
            # Second pass in verify mode re-hashes everything through the
            # SAME backend, giving a workload-level files/s figure for the
            # sequence-sharded device plane (VERDICT r2 item 9) — the fill
            # pass above already consumed the NULL checksums.
            await stage(f"validate_{validate_backend}_verify",
                        ObjectValidatorJob(location_id=loc,
                                           backend=validate_backend,
                                           mode="verify"))

        t0 = time.perf_counter()
        groups = exact_duplicate_groups(lib, location_id=loc)
        emit({
            "stage": "exact_dup", "seconds":
            round(time.perf_counter() - t0, 2),
            "duplicate_groups": len(groups),
        })

        if images:
            from spacedrive_tpu.objects.dedup import NearDupDetectorJob

            await stage("near_dup",
                        NearDupDetectorJob(location_id=loc, threshold=10))
            near = lib.db.run("bench.phash_count")["n"]
            pairs = lib.db.run("bench.pair_count")["n"]
            emit({"stage": "near_dup_hashed", "hashed_images": near,
                  "near_dup_pairs": pairs})

        n_objects = lib.db.run("store.object_count")["n"]
        n_paths = lib.db.run("bench.identified_count")["n"]
        emit({
            "stage": "summary", "identified_paths": n_paths,
            "objects": n_objects,
            "dedup_collapsed": n_paths - n_objects,
        })
        await node.shutdown()
        if with_telemetry:
            # The full registry snapshot — the same counters /metrics and
            # node.metrics serve — embedded so future perf PRs report phase
            # splits from production telemetry, not ad-hoc prints.
            emit({"stage": "telemetry", "metrics": telemetry.snapshot()})
            # Compile-stability proof for the artifact: per-contract trace
            # counts vs their declared budgets (ops/jit_registry.py). A
            # bench run whose jit section shows counts ≤ budget proves the
            # identify pipeline hit only canonical shapes — no silent
            # recompiles hiding in the measured wall.
            from spacedrive_tpu.ops import jit_registry

            traces = jit_registry.trace_counts()
            emit({"stage": "jit", "traces": traces, "budgets": {
                name: jit_registry.CONTRACTS[name].max_traces
                for name in traces
            }, "over_budget": sorted(
                name for name, n in traces.items()
                if n > jit_registry.CONTRACTS[name].max_traces)})
            # Staging-shape proof next to the jit stage: the stage pool's
            # registry families — so a bench artifact shows HOW the
            # identify stream was fed, not just how fast it went.
            snap = telemetry.snapshot()
            emit({"stage": "pipeline",
                  "metrics": {name: value for name, value in snap.items()
                              if name.startswith("sd_stage_pool_")}})
            # Saturation evidence next to the numbers: subsystem states +
            # top attribution over the WHOLE run's window (the monitor's
            # cursors were established before the corpus stage), schema-
            # gated like the trace artifact.
            from spacedrive_tpu import health as health_mod

            hsnap = monitor.sample()
            health_problems.extend(
                health_mod.validate_health_snapshot(hsnap))
            for p in health_problems:
                print(f"HEALTH SCHEMA: {p}", file=sys.stderr)
            emit({"stage": "health",
                  "window_s": hsnap["window_s"],
                  "states": hsnap["states"],
                  "attribution": hsnap["attribution"]})
            # Store-seam evidence (round 16): which declared statements
            # the run actually executed, by count and by rows, plus the
            # per-tx statement histogram — a commit-per-item regression
            # in any job shows up RIGHT HERE as a 1-2-statement spike.
            from spacedrive_tpu.store import sqlaudit

            emit({"stage": "sql", **sqlaudit.stage_summary()})
        if json_out:
            # One small artifact at teardown; the measured stages
            # are over.
            # sdlint: ok[blocking-async]
            persist.atomic_write("bench.artifact", json_out, json.dumps({
                "metric": "perf_smoke",
                "files": files, "backend": backend,
                "telemetry_enabled": with_telemetry,
                "stages": lines,
            }, indent=1))
        trace_problems: list = []
        if trace_out:
            # The run's flight-recorder export: job/rpc spans + identify
            # timeline lanes as one Chrome-trace artifact next to the
            # BENCH JSON. Schema-gated (shared write_trace_artifact
            # helper) so a malformed trace fails the bench run, not the
            # person opening it later.
            from spacedrive_tpu import flight

            trace_problems = await asyncio.to_thread(
                flight.write_trace_artifact, trace_out, "perf_smoke")
            for p in trace_problems:
                print(f"TRACE SCHEMA: {p}", file=sys.stderr)
            if not trace_problems:
                print(f"trace artifact: {trace_out}", file=sys.stderr)
    if trace_problems or health_problems:
        # Exit non-zero AFTER the scratch cleanup above: a schema
        # regression must fail the run, not also leak a multi-GB
        # sdtpu-perf-* tempdir per attempt.
        raise SystemExit(1)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--files", type=int, default=10000)
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--device-batch", type=int, default=None)
    ap.add_argument("--images", type=int, default=0)
    ap.add_argument("--keep", help="reuse/keep this directory")
    ap.add_argument("--small", action="store_true",
                    help="small files only (100k/1M-scale runs)")
    ap.add_argument("--validate-backend", default=None,
                    choices=("jax", "native", "oracle"),
                    help="pin the validator backend and add a verify-mode "
                         "pass timed on it (e.g. jax on a virtual mesh)")
    ap.add_argument("--virtual-devices", type=int, default=0,
                    help="force a CPU platform with N virtual devices "
                         "(the multi-chip test mesh) before any jax use")
    ap.add_argument("--telemetry", action="store_true",
                    help="reset the metrics registry, source the "
                         "identify phase split from it, and append the "
                         "registry snapshot to the output")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write all stage lines (+ telemetry snapshot) "
                         "as one BENCH-style JSON artifact")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="export the run's flight-recorder timeline + "
                         "span ring as a schema-validated Chrome-trace "
                         "JSON artifact")
    args = ap.parse_args()
    if args.virtual_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.virtual_devices} "
            + os.environ.get("XLA_FLAGS", ""))
        os.environ["JAX_PLATFORMS"] = "cpu"
    from spacedrive_tpu.xla_env import enable_compile_cache

    enable_compile_cache()
    asyncio.run(run(args.files, args.backend, args.images, args.keep,
                    args.device_batch, args.small, args.validate_backend,
                    with_telemetry=args.telemetry, json_out=args.json,
                    trace_out=args.trace))
