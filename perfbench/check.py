"""The comparison that decides `correct`: the window's answers against the
plain reference (reference/blake3_np.py) over the bytes the seed made.

It reads the libraries' SQLite files directly, after the node has shut
down, and imports nothing of the system under test. Every number it
returns is a count of wrong answers, compared exactly: its limit is 0.
The reference digests are computed once per run, for each distinct
content, in spawned processes (`reference`).

scan (every round of the window):
- files_missing: files of the folder with no row, rows with no file,
  and rows left without a CAS ID or an object;
- cas_id_mismatch: CAS IDs unequal to the reference's, every file of
  every round (the reference IDs are computed once per run);
- object_groups_mismatch: sets of files sharing an object, against the
  planted duplicate groups (symmetric difference, in groups);
- statistics_mismatch: `library.statistics` object count and bytes
  against the folder's distinct contents and total size;
- chain_jobs_failed: chain jobs that did not end COMPLETED.

verify (every pass of the window):
- stored_checksum_mismatch: the checksums set-up stored, every file,
  against the reference over the bytes as written;
- verdict_mismatch: files reported mismatched that were not rotted, plus
  rotted files not reported, plus passes that did not validate every file;
- reported_checksum_mismatch: the checksum a pass reported for a rotted
  file against the reference over the rotted bytes.
"""

from __future__ import annotations

import multiprocessing
import os
import re
import sqlite3
from typing import Dict, List, Sequence, Tuple

from . import corpus
from .reference import blake3_np

REF_PROCESSES = 8
REF_BLOCK_BYTES = 64 << 20
Check = Tuple[str, float, float]
_MISMATCH = re.compile(
    r"CHECKSUM MISMATCH (.+): stored (\S+), current ([0-9a-f]{64})$")


def _connect(path: str) -> sqlite3.Connection:
    db = sqlite3.connect(f"file:{path}?mode=ro", uri=True)
    db.row_factory = sqlite3.Row
    return db


def _rel(row) -> str:
    ext = row["extension"]
    return row["materialized_path"] + row["name"] + (f".{ext}" if ext else "")


def _reference_block(job) -> List[str]:
    kind, seed, specs = job
    digest = blake3_np.cas_ids if kind == "cas" else blake3_np.checksums
    return digest([corpus.content(seed, spec) for spec in specs])


def reference(kind: str, seed: int, files: Sequence[corpus.FileSpec]
              ) -> Dict[str, str]:
    """{rel: reference digest} of every file: "cas" for its CAS ID,
    "checksum" for its full-file checksum. Each distinct content is
    hashed once, in blocks of about REF_BLOCK_BYTES."""
    roots: Dict[Tuple[int, int], corpus.FileSpec] = {}
    for f in files:
        roots.setdefault((f.part, f.root), f)
    blocks, block, size = [], [], 0
    for spec in sorted(roots.values(), key=lambda f: f.size):
        block.append(spec)
        size += spec.size
        if size >= REF_BLOCK_BYTES:
            blocks.append(block)
            block, size = [], 0
    if block:
        blocks.append(block)
    jobs = [(kind, seed, b) for b in blocks]
    n = min(REF_PROCESSES, os.cpu_count() or 1, len(jobs))
    if n <= 1:
        digests = [_reference_block(j) for j in jobs]
    else:
        with multiprocessing.get_context("spawn").Pool(n) as pool:
            digests = pool.map(_reference_block, jobs, chunksize=1)
    by_root = {(spec.part, spec.root): d for b, ds in zip(blocks, digests)
               for spec, d in zip(b, ds)}
    return {f.rel: by_root[(f.part, f.root)] for f in files}


def scan(run, files: Sequence[corpus.FileSpec]) -> List[Check]:
    by_rel = {f.rel: f for f in files}
    planted = set(corpus.planted_groups(files))
    roots = {(f.part, f.root) for f in files}
    total = sum(f.size for f in files)
    want = reference("cas", run.seed, files)
    missing = cas_bad = groups_bad = stats_bad = jobs_bad = 0
    for r in run.window_rounds():
        jobs_bad += sum(1 for _n, s in r["jobs"] if s != 2) + max(
            0, 3 - len(r["jobs"]))
        with _connect(r["db"]) as db:
            rows = db.execute(
                "SELECT materialized_path, name, extension, cas_id, "
                "object_id FROM file_path WHERE is_dir = 0").fetchall()
        got = {_rel(row): row for row in rows}
        missing += len(set(by_rel) ^ set(got)) + sum(
            1 for row in rows if row["cas_id"] is None
            or row["object_id"] is None)
        by_object: Dict[int, set] = {}
        for rel, row in got.items():
            by_object.setdefault(row["object_id"], set()).add(rel)
        grouped = {frozenset(g) for g in by_object.values() if len(g) > 1}
        groups_bad += len(grouped ^ planted)
        cas_bad += sum(1 for rel, row in got.items()
                       if rel in want and row["cas_id"] != want[rel])
        stats = r["statistics"]
        stats_bad += int(stats["total_object_count"] != len(roots)) + int(
            int(stats["total_bytes_used"]) != total)
    return [("files_missing", missing, 0),
            ("cas_id_mismatch", cas_bad, 0),
            ("object_groups_mismatch", groups_bad, 0),
            ("statistics_mismatch", stats_bad, 0),
            ("chain_jobs_failed", jobs_bad, 0)]


def verify(run, files: Sequence[corpus.FileSpec], corrupted: dict,
           db_path: str, root: str) -> List[Check]:
    import msgpack

    by_rel = {f.rel: f for f in files}
    with _connect(db_path) as db:
        rows = db.execute(
            "SELECT materialized_path, name, extension, integrity_checksum "
            "FROM file_path WHERE is_dir = 0").fetchall()
        reports = {r["id"]: r for r in db.execute(
            "SELECT id, status, metadata, errors_text FROM job").fetchall()}
    stored = {_rel(row): row["integrity_checksum"] for row in rows}
    want = reference("checksum", run.seed, files)
    stored_bad = len(set(by_rel) ^ set(stored)) + sum(
        1 for rel, got in stored.items() if rel in want and got != want[rel])
    rotted = blake3_np.checksums([corrupted[rel][1] for rel in
                                  sorted(corrupted)])
    want_current = dict(zip(sorted(corrupted), rotted))
    verdict_bad = reported_bad = 0
    for r in run.window_rounds():
        rep = reports.get(r["job_id"])
        if rep is None:
            verdict_bad += len(files)
            continue
        meta = msgpack.unpackb(rep["metadata"], raw=False) \
            if rep["metadata"] else {}
        verdict_bad += abs(int(meta.get("validated", 0)) - len(files))
        flagged = {}
        for err in (rep["errors_text"] or "").split("\n\x1e\n"):
            m = _MISMATCH.match(err.strip())
            if m and m.group(1).startswith(root):
                flagged[m.group(1)[len(root):]] = m.group(3)
        verdict_bad += len(set(flagged) ^ set(corrupted))
        reported_bad += sum(1 for rel, cur in flagged.items()
                            if rel in want_current
                            and cur != want_current[rel])
    return [("stored_checksum_mismatch", stored_bad, 0),
            ("verdict_mismatch", verdict_bad, 0),
            ("reported_checksum_mismatch", reported_bad, 0),
            ("passes_failed", sum(1 for r in run.window_rounds()
                                  if not r["ok"]), 0)]
