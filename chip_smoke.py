"""Chip smoke: the VDFS hot path on a TPU, end to end through the node.

    python chip_smoke.py              # one chip: the product path
    python chip_smoke.py --chips 4    # four chips: the multi-chip paths only

One chip: generate a library from --seed (tools/make_corpus.py), boot a
Node with its API server as `python -m spacedrive_tpu serve` does,
create the library and two locations over the websocket API, run
IndexerJob -> FileIdentifierJob -> ObjectValidatorJob(backend="jax") ->
NearDupDetectorJob through node.jobs (the call the jobs.* procedures
make), and answer library.statistics / search.duplicates /
search.nearDuplicates over the websocket. Then check, against
references that share no device code with the path under test:

- every CAS ID and every validator checksum equals the native C++
  plane's, and a few equal the pure-Python BLAKE3 oracle's;
- the duplicate groups are the generator's planted groups;
- the near-dup pairs are a numpy all-pairs sweep over the same pHashes;
- every identify batch ran on backend "jax" with no fallback, and the
  Pallas BLAKE3 kernel traced (not the jnp scan).

Four chips (--chips 4): the paths that exist only across chips — the
mesh-sharded CAS hasher (through the identifier job), the validator's
sequence-sharded checksum mesh and the 2x2 Hamming tile mesh — each
compared with the native plane or numpy on the same corpus, and each
shown to use all four devices.

Earlier lines are JSON records (device, per-phase and compile seconds,
the H2D rate the link probe saw, peak device bytes, per-device batch
counts). The last line is {"ok": true, "device": {...}}. Any failure
exits non-zero before that line; without a TPU the script fails at
once. The compile cache is JAX_COMPILATION_CACHE_DIR when set, else
<checkout>/.jax_cache (spacedrive_tpu/xla_env.py).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import multiprocessing
import os
import shutil
import sys
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.abspath(__file__))
GIB = 1 << 30


@dataclass(frozen=True)
class Plan:
    """Corpus shape of one run, in files. `main` holds the identifier's
    device steps of AUTO_DEVICE_BATCH files plus the pHash images;
    `validate` is the validator's location (BASELINE config 2). With
    no validate files, the `mixed` part goes under `main` instead."""
    main_files: int
    images: int
    validate_files: int   # capped mix
    validate_mixed: int   # uncapped mix: the multi-MiB tail reaches the
    #                       validator's sequence-sharded windows
    max_size: int         # cap on the generator's size mix, bytes
    parts: int            # corpus written by this many processes


PLANS = {
    1: Plan(main_files=50_000, images=1_536, validate_files=9_800,
            validate_mixed=200, max_size=192 << 10, parts=8),
    4: Plan(main_files=4_500, images=512, validate_files=0,
            validate_mixed=200, max_size=192 << 10, parts=8),
}
NEAR_DUP_THRESHOLD = 10


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


# -- corpus (no JAX: runs in spawned generator processes) -------------------

def _make_part(job):
    out, kw = job
    from tools.make_corpus import make_corpus

    return out, make_corpus(out, **kw)


def _uniform_capped_mean(a: int, b: int, cap: int) -> float:
    """E[min(U[a, b), cap)]."""
    if cap >= b:
        return (a + b) / 2
    if cap <= a:
        return float(cap)
    p = (cap - a) / (b - a)
    return p * (a + cap) / 2 + (1 - p) * cap


def _mean_file_bytes(max_size: int) -> float:
    """Mean size of make_corpus's mixed-size draw under a cap."""
    cap = max_size or (8 << 20)
    return (0.5 * _uniform_capped_mean(256, 100 << 10, cap)
            + 0.4 * _uniform_capped_mean((100 << 10) + 1, 1 << 20, cap)
            + 0.1 * _uniform_capped_mean(1 << 20, 8 << 20, cap))


def corpus_jobs(lib: str, plan: Plan, seed: int) -> list:
    """(part dir, make_corpus kwargs) for every generator process."""
    jobs = []

    def split(loc: str, tag: str, files: int, max_size: int) -> None:
        per = -(-files // plan.parts)
        for p in range(plan.parts):
            n = min(per, files - p * per)
            if n > 0:
                jobs.append((os.path.join(lib, loc, f"{tag}{p:02d}"), {
                    "files": n, "dup_rate": 0.1, "max_size": max_size,
                    "seed": seed * 1000 + len(jobs)}))

    split("main", "p", plan.main_files, plan.max_size)
    jobs.append((os.path.join(lib, "main", "images"), {
        "files": 0, "images": plan.images, "seed": seed * 1000 + len(jobs)}))
    split("validate", "v", plan.validate_files, plan.max_size)
    jobs.append((os.path.join(
            lib, "validate" if plan.validate_files else "main", "mixed"), {
        "files": plan.validate_mixed, "dup_rate": 0.1,
        "seed": seed * 1000 + len(jobs)}))
    return jobs


def generate_corpus(lib: str, plan: Plan, seed: int) -> dict:
    """Write the library in parallel spawned processes (none touches
    JAX). Returns the planted duplicate groups, each a set of
    location-relative paths as search.duplicates spells them."""
    est = (plan.main_files + plan.validate_files) * _mean_file_bytes(
        plan.max_size) + plan.validate_mixed * _mean_file_bytes(0) \
        + plan.images * 8192
    os.makedirs(lib, exist_ok=True)
    free = shutil.disk_usage(lib).free
    emit({"phase": "disk", "corpus_bytes_est": int(est),
          "free_bytes": free})
    if free < 1.2 * est + GIB:
        raise SystemExit(f"not enough disk for the corpus: {free} B free, "
                         f"~{int(est)} B needed")
    jobs = corpus_jobs(lib, plan, seed)
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(len(jobs), os.cpu_count() or 1)) as pool:
        done = pool.map(_make_part, jobs)
    parent = {}  # location-relative path -> planted source
    files = 0
    for out, stats in done:
        loc, part = os.path.relpath(out, lib).split(os.sep, 1)
        files += stats["files"] + stats["images"]
        for dup, src in stats["planted"]:
            parent[(loc, f"/{part}/{dup}")] = (loc, f"/{part}/{src}")

    def root(k):
        while k in parent:
            k = parent[k]
        return k

    groups: dict = {}
    for k in parent:
        groups.setdefault(root(k), {root(k)}).add(k)
    emit({"phase": "corpus_made", "files": files,
          "planted_groups": len(groups)})
    return {frozenset(p for _loc, p in g) for g in groups.values()}


# -- device bookkeeping -----------------------------------------------------

class CompileLog:
    """JAX monitoring listener: backend compile seconds (cache hits
    included — a warm persistent cache shows up as lower seconds), cache
    hit/miss counts, and the names of every function traced, nested jit
    bodies included."""

    def __init__(self):
        import jax.monitoring as monitoring

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.cache = {"hits": 0, "misses": 0}
        self.traced: set = set()
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        with self._lock:
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1
            elif event == "/jax/core/compile/jaxpr_trace_duration":
                self.traced.add(kw.get("fun_name"))

    def _event(self, event, **kw):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache["misses"] += 1


class Phases:
    def __init__(self, compiles: CompileLog):
        self.compiles = compiles
        self.seconds: dict = {}

    @contextmanager
    def __call__(self, name: str, **extra):
        c0, t0 = self.compiles.seconds, time.perf_counter()
        yield extra
        dt = time.perf_counter() - t0
        self.seconds[name] = dt
        emit({"phase": name, "seconds": dt,
              "compile_seconds": self.compiles.seconds - c0, **extra})


def require_tpu(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX's default device "
                         f"is {devs[0].platform!r}")
    if len(devs) != chips:
        raise SystemExit(f"--chips {chips} needs {chips} TPU devices; "
                         f"JAX sees {len(devs)}")
    return devs


# -- the node -----------------------------------------------------------------

class Rpc:
    """The websocket frames webui.js sends, over one connection."""

    def __init__(self, ws):
        self.ws = ws
        self._id = 0

    async def call(self, kind: str, path: str, input_: dict):
        import aiohttp

        self._id += 1
        await self.ws.send_json({"id": self._id, "type": kind,
                                 "path": path, "input": input_})
        while True:
            msg = await asyncio.wait_for(self.ws.receive(), timeout=600)
            if msg.type != aiohttp.WSMsgType.TEXT:
                raise RuntimeError(f"{path}: websocket closed ({msg})")
            frame = json.loads(msg.data)
            if frame.get("id") != self._id:
                continue  # a subscription event
            if frame["type"] == "error":
                raise RuntimeError(f"{path}: {frame}")
            return frame["result"]


async def run_job(node, lib, job) -> dict:
    """Run one job through node.jobs; it must end COMPLETED with no
    errors. Returns its report metadata."""
    from spacedrive_tpu.jobs.report import JobReport, JobStatus

    jid = await node.jobs.ingest(lib, job)
    status = await node.jobs.wait(jid)
    report = JobReport.from_row(lib.db.run("jobs.report.by_id", (jid,)))
    if status != JobStatus.COMPLETED or report.errors_text:
        raise RuntimeError(f"{job.NAME} ended {JobStatus(status).name}: "
                           f"{report.errors_text[:5]}")
    return report.metadata


async def with_node(work: str, body):
    """Boot a Node and its API server (as `serve` does, minus p2p), run
    body(node, rpc_connect), shut both down."""
    import aiohttp

    from spacedrive_tpu.api.server import ApiServer
    from spacedrive_tpu.node import Node

    node = Node(os.path.join(work, "node"))
    await node.start()
    server = ApiServer(node)
    port = await server.start("127.0.0.1", 0)
    try:
        async with aiohttp.ClientSession() as http:
            def connect():
                return http.ws_connect(f"http://127.0.0.1:{port}/rspc",
                                       max_msg_size=0)
            return await body(node, connect)
    finally:
        await server.stop()
        await node.shutdown()


async def create_library(connect, lib_dir: str, locs) -> tuple:
    async with connect() as ws:
        rpc = Rpc(ws)
        info = await rpc.call("mutation", "library.create",
                              {"name": "chip-smoke"})
        lid = info["uuid"]
        ids = {}
        for loc in locs:
            ids[loc] = await rpc.call("mutation", "locations.create", {
                "library_id": lid, "path": os.path.join(lib_dir, loc),
                "dry_run": True})
    return lid, ids


def file_rows(lib, lib_dir: str, loc_names: dict) -> list:
    """Every file row: (absolute path, size, cas_id, checksum, loc)."""
    by_id = {v: k for k, v in loc_names.items()}
    rows = lib.db.query(
        "SELECT location_id, materialized_path, name, extension, cas_id, "
        "integrity_checksum, size_in_bytes_bytes FROM file_path "
        "WHERE is_dir = 0")
    out = []
    for r in rows:
        rel = r["materialized_path"] + r["name"] + (
            f".{r['extension']}" if r["extension"] else "")
        loc = by_id[r["location_id"]]
        out.append((os.path.join(lib_dir, loc) + rel,
                    int.from_bytes(r["size_in_bytes_bytes"], "big"),
                    r["cas_id"], r["integrity_checksum"], loc))
    return out


def check_cas_ids(rows: list, oracle_n: int = 4) -> int:
    """Device CAS IDs == native plane for every file; a few == oracle."""
    import numpy as np

    from spacedrive_tpu import native
    from spacedrive_tpu.ops.cas import generate_cas_id

    digests, status = native.cas_digests(
        [r[0] for r in rows], np.array([r[1] for r in rows], np.uint64))
    bad = [r[0] for r, d, s in zip(rows, digests, status)
           if s != native.OK or d.tobytes().hex()[:16] != r[2]]
    if bad:
        raise AssertionError(f"{len(bad)} CAS IDs differ from the native "
                             f"plane, e.g. {bad[:3]}")
    # Oracle: the smallest file and the first sampled (>100 KiB) one.
    small = min(rows, key=lambda r: r[1])
    large = [r for r in rows if r[1] > 100 << 10][:oracle_n - 1]
    for r in [small, *large]:
        if generate_cas_id(r[0], r[1]) != r[2]:
            raise AssertionError(f"CAS ID differs from the oracle: {r[0]}")
    return len(rows)


def check_checksums(rows: list, oracle_n: int = 3) -> int:
    """Validator checksums == native plane for every file it covered;
    the smallest few == the pure-Python oracle."""
    import numpy as np

    from spacedrive_tpu import native
    from spacedrive_tpu.ops.cas import file_checksum

    hexes, status = native.checksum_files(
        [r[0] for r in rows],
        sizes_hint=np.array([r[1] for r in rows], np.uint64))
    bad = [r[0] for r, h, s in zip(rows, hexes, status)
           if s != native.OK or h != r[3]]
    if bad:
        raise AssertionError(f"{len(bad)} checksums differ from the native "
                             f"plane, e.g. {bad[:3]}")
    for r in sorted(rows, key=lambda r: r[1])[:oracle_n]:
        if file_checksum(r[0]) != r[3]:
            raise AssertionError(f"checksum differs from the oracle: {r[0]}")
    return len(rows)


def hamming_numpy(a, b):
    """[n, W] x [m, W] uint32 digests -> [n, m] Hamming distances."""
    import numpy as np

    x = (a[:, None, :] ^ b[None, :, :]).astype(np.uint32)
    return np.unpackbits(x.view(np.uint8), axis=-1).sum(
        axis=-1).astype(np.int32)


def numpy_near_pairs(digests, threshold: int) -> set:
    """(i, j), i < j, with Hamming distance <= threshold — all pairs."""
    import numpy as np

    ii, jj = np.nonzero(np.triu(hamming_numpy(digests, digests)
                                <= threshold, k=1))
    return set(zip(ii.tolist(), jj.tolist()))


def dispatch_counts() -> dict:
    """CAS/checksum device dispatches per device index, from the
    identifier's dispatch log (a sharded dispatch counts on each of its
    n_dev devices)."""
    from spacedrive_tpu.ops.blake3_jax import DISPATCH_LOG

    counts: dict = {}
    for d in DISPATCH_LOG:
        for i in range(d["n_dev"]):
            counts[i] = counts.get(i, 0) + 1
    return counts


def check_pallas(compiles: CompileLog) -> list:
    """The Pallas contracts (blake3.pallas.words / .chunk_fast) traced
    and the jnp scan did not."""
    from spacedrive_tpu.ops import blake3_pallas, jit_registry

    names = {jit_registry.CONTRACTS[c].site.rsplit("::", 1)[1]
             for c in ("blake3.pallas.words", "blake3.pallas.chunk_fast")}
    jnp_scan = jit_registry.CONTRACTS["blake3.jnp"].site.rsplit("::", 1)[1]
    if (not blake3_pallas.supported() or not names <= compiles.traced
            or jnp_scan in compiles.traced):
        raise AssertionError(
            f"the Pallas kernel did not trace: "
            f"{sorted(n for n in compiles.traced if 'blake3' in str(n))}")
    return sorted(names)


# -- one chip: the product path ---------------------------------------------

def smoke_one_chip(args, plan, work, lib_dir, compiles, phase) -> None:
    import jax
    import numpy as np

    from spacedrive_tpu import telemetry
    from spacedrive_tpu.locations.indexer_job import IndexerJob
    from spacedrive_tpu.objects.dedup import NearDupDetectorJob
    from spacedrive_tpu.objects.identifier import FileIdentifierJob
    from spacedrive_tpu.objects.validator import ObjectValidatorJob
    from spacedrive_tpu.ops import staging
    from spacedrive_tpu.ops.phash import phash_from_bytes

    with phase("corpus"):
        planted = generate_corpus(lib_dir, plan, args.seed)
    results = {}

    async def body(node, connect):
        with phase("library"):
            lid, locs = await create_library(
                connect, lib_dir, ("main", "validate"))
        lib = next(iter(node.libraries.list()))
        with phase("index"):
            for loc in locs.values():
                await run_job(node, lib, IndexerJob(location_id=loc))
        with phase("identify") as extra:
            for name, loc in locs.items():
                meta = await run_job(node, lib, FileIdentifierJob(
                    location_id=loc))
                extra[f"{name}_chunk_size"] = meta.get("chunk_size")
        with phase("validate"):
            await run_job(node, lib, ObjectValidatorJob(
                location_id=locs["validate"], backend="jax"))
        with phase("near_dup"):
            await run_job(node, lib, NearDupDetectorJob(
                location_id=locs["main"], threshold=NEAR_DUP_THRESHOLD))
        with phase("queries"):
            async with connect() as ws:
                rpc = Rpc(ws)
                results["stats"] = await rpc.call(
                    "query", "library.statistics", {"library_id": lid})
                results["dups"] = await rpc.call(
                    "query", "search.duplicates",
                    {"library_id": lid, "limit": 1_000_000})
                results["near"] = await rpc.call(
                    "query", "search.nearDuplicates",
                    {"library_id": lid, "limit": 1_000_000,
                     "max_distance": NEAR_DUP_THRESHOLD})
        results["rows"] = file_rows(lib, lib_dir, locs)
        results["phash_rows"] = lib.db.run(
            "dedup.phashes_for_location", (locs["main"],))

    asyncio.run(with_node(work, body))
    rows = results["rows"]

    with phase("check") as extra:
        extra["cas_ids"] = check_cas_ids(rows)
        extra["checksums"] = check_checksums(
            [r for r in rows if r[4] == "validate"])
        got = {frozenset(g["paths"]) for g in results["dups"]}
        if got != planted:
            raise AssertionError(
                f"duplicate groups differ from the planted set: "
                f"{len(got - planted)} unexpected, "
                f"{len(planted - got)} missing")
        extra["duplicate_groups"] = len(got)
        ph = results["phash_rows"]
        oids = [r["object_id"] for r in ph]
        want = {tuple(sorted((oids[i], oids[j])))
                for i, j in numpy_near_pairs(
                    np.stack([phash_from_bytes(r["phash"]) for r in ph]),
                    NEAR_DUP_THRESHOLD)
                if oids[i] != oids[j]}
        pairs = {(p["object_a"], p["object_b"]) for p in results["near"]}
        if pairs != want:
            raise AssertionError(
                f"near-dup pairs differ from numpy all-pairs: "
                f"{len(pairs - want)} unexpected, {len(want - pairs)} "
                "missing")
        extra["images_hashed"] = len(ph)
        extra["near_dup_pairs"] = len(pairs)
        stats = results["stats"]
        n_objects = len({r[2] for r in rows})
        if (stats["total_object_count"] != n_objects
                or int(stats["total_bytes_used"])
                != sum(r[1] for r in rows)):
            raise AssertionError(f"library.statistics disagrees: {stats} "
                                 f"vs {n_objects} objects")

    # The device was used, and nothing fell back.
    snap = telemetry.snapshot()

    def counter(name):
        fam = snap.get(name, {})
        if "labeled" in fam:
            return {tuple(e["labels"].values())[0]: e["value"]
                    for e in fam["labeled"]}
        return fam.get("value", 0)

    backends = counter("sd_identifier_batches_total")
    checks = {
        "identify_batches_by_backend": backends,
        "device_fallback": counter("sd_identifier_device_fallback_total"),
        "prefetch_failures":
            counter("sd_identifier_prefetch_failures_total"),
        "h2d_probe_failures": counter("sd_stage_h2d_probe_failures_total"),
    }
    if (set(backends) != {"jax"} or checks["device_fallback"]
            or checks["prefetch_failures"]):
        raise AssertionError(f"identify left the device: {checks}")
    pallas = check_pallas(compiles)
    h2d = staging.h2d_gbps()  # the rate `auto` routing would use
    if counter("sd_stage_h2d_probe_failures_total"):
        raise AssertionError("the host->device link probe failed")
    emit({"phase": "device", **checks,
          "pallas_traced": pallas,
          "h2d_gbps_probe": h2d,
          "auto_routes_to_device": h2d > staging.NATIVE_PLANE_GBPS,
          "per_device_batches": dispatch_counts(),
          "peak_bytes_in_use":
              (jax.devices()[0].memory_stats() or {}).get(
                  "peak_bytes_in_use")})


# -- four chips: the paths that exist only across chips --------------------

def smoke_four_chips(args, plan, work, lib_dir, compiles, phase) -> None:
    import jax
    import numpy as np

    from spacedrive_tpu.locations.indexer_job import IndexerJob
    from spacedrive_tpu.objects.identifier import FileIdentifierJob
    from spacedrive_tpu.objects.validator import ObjectValidatorJob
    from spacedrive_tpu.ops.blake3_jax import DISPATCH_LOG, sharded_hasher
    from spacedrive_tpu.ops.hamming import make_sharded_hamming
    from spacedrive_tpu.ops.phash import phash_files
    from spacedrive_tpu.parallel.mesh import tile_mesh

    devs = jax.devices()
    with phase("corpus"):
        generate_corpus(lib_dir, plan, args.seed)
    results = {}

    async def body(node, connect):
        with phase("library"):
            _lid, locs = await create_library(connect, lib_dir, ("main",))
        lib = next(iter(node.libraries.list()))
        with phase("index"):
            for loc in locs.values():
                await run_job(node, lib, IndexerJob(location_id=loc))
        with phase("identify_sharded"):
            await run_job(node, lib, FileIdentifierJob(
                location_id=locs["main"]))
        with phase("validate_seqhash_mesh"):
            await run_job(node, lib, ObjectValidatorJob(
                location_id=locs["main"], sub_path="mixed",
                backend="jax"))
        results["rows"] = file_rows(lib, lib_dir, locs)

    asyncio.run(with_node(work, body))
    rows = results["rows"]
    hasher, n_dev = sharded_hasher()
    sharded = [d for d in DISPATCH_LOG if d["n_dev"] == len(devs)]
    if hasher is None or n_dev != len(devs) or not sharded or any(
            d["n_dev"] != len(devs) for d in DISPATCH_LOG):
        raise AssertionError(f"CAS dispatches did not shard over all "
                             f"{len(devs)} chips: {DISPATCH_LOG[:4]}")

    with phase("hamming_tile_mesh") as extra:
        paths = sorted(
            os.path.join(d, f)
            for d, _, fs in os.walk(os.path.join(lib_dir, "main", "images"))
            for f in fs if f.endswith(".png"))
        hashes, errors = phash_files(paths, backend="jax")
        if errors:
            raise AssertionError(errors[:3])
        mesh = tile_mesh(devs)
        r, c = mesh.devices.shape
        N = (len(hashes) // (r * c)) * (r * c)
        d = np.stack([hashes[i] for i in range(N)]).astype(np.uint32)
        dist = make_sharded_hamming(mesh)(d, d)
        used = {s.device.id for s in dist.addressable_shards}
        if not np.array_equal(np.asarray(dist), hamming_numpy(d, d)):
            raise AssertionError("sharded Hamming differs from numpy")
        extra.update(mesh=[r, c], digests=N, devices_used=sorted(used))

    with phase("check") as extra:
        extra["cas_ids"] = check_cas_ids(rows)
        extra["checksums"] = check_checksums(
            [r for r in rows if r[3] is not None])
        big = [r for r in rows if r[3] is not None and r[1] > 4 << 20]
        if not big:
            raise AssertionError("no file reached the seqhash mesh")
        extra["seqhash_files"] = len(big)

    per_device = dispatch_counts()
    if len(per_device) != len(devs) or len(used) != len(devs):
        raise AssertionError(
            f"not every chip was used: sharded {per_device}, "
            f"hamming {sorted(used)}")
    emit({"phase": "device",
          "sharded_cas_per_device_batches": per_device,
          "hamming_devices": sorted(used),
          "peak_bytes_in_use": [
              (dv.memory_stats() or {}).get("peak_bytes_in_use")
              for dv in devs]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(PLANS), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", default=os.path.join(ROOT, ".chip_smoke"),
                    help="scratch dir for the corpus and the node "
                         "(emptied before and after the run)")
    args = ap.parse_args(argv)
    plan = PLANS[args.chips]

    # The identifier must take the device path; dispatches are logged
    # for the per-device counts.
    os.environ["SDTPU_DEVICE_PIPELINE"] = "force"
    os.environ["SDTPU_DISPATCH_LOG"] = "1"

    from spacedrive_tpu.xla_env import enable_compile_cache

    cache_dir = enable_compile_cache()
    compiles = CompileLog()
    t_start = time.perf_counter()
    devs = require_tpu(args.chips)
    emit({"phase": "start", "device_kind": devs[0].device_kind,
          "devices": len(devs), "compile_cache": cache_dir,
          "cpus": os.cpu_count()})
    phase = Phases(compiles)

    # Processes this run starts (make, corpus generators) never import
    # JAX: the chip stays with this process.
    from spacedrive_tpu import native

    with phase("native_build"):
        if not native.available():
            raise SystemExit("the native plane (native/sdio.cpp) did "
                             "not build")

    work = os.path.abspath(args.workdir)
    shutil.rmtree(work, ignore_errors=True)
    lib_dir = os.path.join(work, "lib")
    try:
        smoke = smoke_one_chip if args.chips == 1 else smoke_four_chips
        smoke(args, plan, work, lib_dir, compiles, phase)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    emit({"phase": "total", "seconds": time.perf_counter() - t_start,
          "compile_seconds": compiles.seconds,
          "compiles": compiles.compiles, "compile_cache": compiles.cache,
          "phase_seconds": phase.seconds})
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
