"""The general traffic generator: rounds, as a traffic file describes them.

A round is one whole unit of user work, run back to back as a closed
loop from the window's start; the round in flight when the window's
seconds run out runs to its end and counts.

- "scan": a user adds a folder. `library.create`, then `locations.create`
  without `dry_run`, as the web UI sends them; the node runs its own
  chain IndexerJob -> FileIdentifierJob -> MediaProcessorJob, and the
  round ends when the chain's last job ends. Every round starts from a
  library that has never seen the folder. An optional browse client
  scrolls the library of the round in flight (browse.py).
- "verify": the recurring integrity pass. ObjectValidatorJob(mode=
  "verify") through node.jobs, as `jobs.objectValidator` ingests it, on
  the configuration's backend, over a location that set-up indexed and
  filled with checksums and in which it planted bit rot.

Each runner's `check()` runs after the node has shut down and compares
what the window produced with the reference (check.py).
"""

from __future__ import annotations

import asyncio
import os
import random
import time
import uuid
from typing import List

from . import check, corpus
from .spec import location_parts

WRITERS = 8
CHAIN_OK = 2        # JobStatus.COMPLETED
CHAIN_OK_ERR = 6    # JobStatus.COMPLETED_WITH_ERRORS


class _Rounds:
    def __init__(self, ctx):
        self.ctx = ctx
        self.run = ctx.run
        cfg, traffic = self.run.cell.config, self.run.cell.traffic
        self.parts = location_parts(cfg, traffic)
        self.root = os.path.join(ctx.work, "corpus", cfg["location"]["name"])
        self.files: List[corpus.FileSpec] = []
        self.browse = None

    async def write_corpus(self) -> None:
        t = time.perf_counter()
        os.makedirs(self.root, exist_ok=True)
        self.files = await asyncio.to_thread(
            corpus.write_location, self.root, self.parts, self.run.seed,
            min(WRITERS, os.cpu_count() or 1))
        print(f'{{"phase": "corpus", "files": {len(self.files)}, '
              f'"bytes": {sum(f.size for f in self.files)}, '
              f'"seconds": {time.perf_counter() - t}}}', flush=True)
        self.run.mark("corpus")

    async def window(self, t0: float, seconds: int) -> List[dict]:
        if self.browse is not None:
            self.browse.send("start", t0)
        rounds = []
        while True:
            rounds.append(await self.round())
            if time.perf_counter() - t0 >= seconds:
                return rounds

    async def finish(self) -> None:
        if self.browse is not None:
            queries = await self.browse.finish(self.run.t1)
            self.run.queries = [q for q in queries
                                if q[0] >= self.run.t0]
            lags = sorted(q[1] - q[0] for q in self.run.queries)
            if lags:
                print(f'{{"phase": "browse", "queries": {len(lags)}, '
                      f'"send_lag_p50_s": {lags[len(lags) // 2]}, '
                      f'"send_lag_max_s": {lags[-1]}}}', flush=True)

    async def collect(self) -> None:
        pass

    def close(self) -> None:
        if self.browse is not None:
            self.browse.kill()

    def _job_spans(self, t0: float, t1: float) -> list:
        """(name, status) of the jobs that ended in [t0, t1]; their host
        spans go to the run for the trace's idle attribution."""
        out = []
        for s, e, name, status in self.ctx.jobs.between(t0, t1):
            self.run.spans.append((f"job/{name}", int(s * 1e9), int(e * 1e9)))
            out.append((name, status))
        return out


class ScanRounds(_Rounds):
    async def setup(self) -> None:
        await self.write_corpus()
        browse = self.run.cell.traffic.get("browse")
        if browse:
            from .browse import BrowseClient

            self.browse = BrowseClient(self.ctx.port, self.run.seed, browse)
            await self.browse.ready()
            self.run.mark("browse_start")
        for _ in range(int(self.run.cell.traffic.get("warmup_rounds", 0))):
            rec = await self.round(warm=True)
            await self.ctx.rpc.call("mutation", "library.delete",
                                    {"id": rec["library"]})
        self.run.mark("warmup")

    async def round(self, warm: bool = False) -> dict:
        rpc, run = self.ctx.rpc, self.run
        t0 = time.perf_counter()
        with run.span("round"):
            with run.span("library.create"):
                lib = await rpc.call("mutation", "library.create",
                                     {"name": f"perfbench-{t0:.3f}"})
            lid = lib["uuid"]
            if self.browse is not None and not warm:
                self.browse.send("target", lid)
            with run.span("locations.create"):
                await rpc.call("mutation", "locations.create",
                               {"library_id": lid, "path": self.root})
            await self.ctx.node.jobs.wait_idle()
        t1 = time.perf_counter()
        jobs = self._job_spans(t0, t1)
        sizes = [f.size for f in self.files]
        return {"kind": "scan", "warm": warm, "t0": t0, "t1": t1,
                "library": lid, "files": len(sizes), "sizes": sizes,
                "file_bytes": sum(sizes), "jobs": jobs,
                "ok": len(jobs) == 3 and all(s == CHAIN_OK
                                             for _n, s in jobs)}

    async def collect(self) -> None:
        for r in self.run.window_rounds():
            r["statistics"] = await self.ctx.rpc.call(
                "query", "library.statistics", {"library_id": r["library"]})
            r["db"] = os.path.join(self.ctx.node.libraries.dir,
                                   f"{r['library']}.db")

    def check(self):
        return check.scan(self.run, self.files)


class VerifyRounds(_Rounds):
    async def setup(self) -> None:
        from spacedrive_tpu.locations.indexer_job import IndexerJob
        from spacedrive_tpu.objects.validator import ObjectValidatorJob

        cfg = self.run.cell.config
        await self.write_corpus()
        rpc = self.ctx.rpc
        lib = await rpc.call("mutation", "library.create",
                             {"name": "perfbench-integrity"})
        self.lid = lib["uuid"]
        self.loc = await rpc.call("mutation", "locations.create", {
            "library_id": self.lid, "path": self.root, "dry_run": True})
        self.lib = self.ctx.node.libraries.get(uuid.UUID(self.lid))
        for job in (IndexerJob(location_id=self.loc),
                    ObjectValidatorJob(location_id=self.loc, mode="fill",
                                       backend=cfg["fill_backend"])):
            jid = await self.ctx.node.jobs.ingest(self.lib, job)
            status = await self.ctx.node.jobs.wait(jid)
            if status != CHAIN_OK:
                raise RuntimeError(f"set-up job {job.NAME} ended {status}")
            self.run.mark(f"{job.NAME}_{getattr(job, 'mode', 'run')}")
        self.corrupted = plant_bit_rot(
            self.root, self.files, self.run.seed,
            self.run.cell.traffic["bit_rot"])
        for _ in range(int(self.run.cell.traffic.get("warmup_rounds", 0))):
            await self.round(warm=True)
        self.run.mark("warmup")

    async def round(self, warm: bool = False) -> dict:
        from spacedrive_tpu.objects.validator import ObjectValidatorJob

        run, jobs = self.run, self.ctx.node.jobs
        t0 = time.perf_counter()
        with run.span("round"):
            jid = await jobs.ingest(self.lib, ObjectValidatorJob(
                location_id=self.loc, mode="verify",
                backend=run.cell.config["verify_backend"]))
            status = await jobs.wait(jid)
        t1 = time.perf_counter()
        self._job_spans(t0, t1)
        sizes = [f.size for f in self.files]
        return {"kind": "verify", "warm": warm, "t0": t0, "t1": t1,
                "job_id": jid, "status": int(status), "files": len(sizes),
                "sizes": sizes,
                "file_bytes": sum(sizes),
                "ok": int(status) in (CHAIN_OK, CHAIN_OK_ERR)}

    async def collect(self) -> None:
        self.db = os.path.join(self.ctx.node.libraries.dir,
                               f"{self.lid}.db")

    def check(self):
        return check.verify(self.run, self.files, self.corrupted, self.db,
                            self.root)


def plant_bit_rot(root: str, files, seed: int, params: dict) -> dict:
    """Flip one byte in files drawn from the seed, in size strata so the
    streamed multi-MiB path, the sampled range and small files all carry
    some. Returns {rel path: (offset, new content)}."""
    rng = random.Random(seed * 7919 + 17)
    out = {}
    for lo, hi, count in params["strata"]:
        pool = sorted(f.rel for f in files if lo < f.size <= hi)
        for rel in rng.sample(pool, min(count, len(pool))):
            spec = next(f for f in files if f.rel == rel)
            off = rng.randrange(spec.size)
            data = bytearray(corpus.content(seed, spec))
            data[off] ^= 0x5A
            with open(root + rel, "r+b") as f:
                f.seek(off)
                f.write(bytes([data[off]]))
            out[rel] = (off, bytes(data))
    return out


RUNNERS = {"scan": ScanRounds, "verify": VerifyRounds}
