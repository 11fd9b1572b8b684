"""The benchmark's folder generator (adapted from tools/make_corpus.py).

A location is a list of parts; each part is one directory written with
make_corpus's shape: a three-level tree of 14 nested directories, files
named fNNNNNN.<extension> (the part's, default "bin"), sizes drawn from
weighted uniform size classes and clamped to an optional cap, and
planted exact duplicates that copy one of the last 256 files written.

What is drawn from what:

- the layout (directory tree, names, sizes, which files duplicate
  which) comes from the part's fixed `layout_seed`, so every run of a
  cell does the same work: the same sizes, the same device batch
  shapes, the same planted groups;
- the bytes of every file come from `--seed`: file i of part p holds
  PCG64(SeedSequence([seed, p, root(i)])) bytes, where root(i) is the
  file a duplicate copies.

Nothing here imports JAX, so the writer processes never touch the chip.
"""

from __future__ import annotations

import multiprocessing
import os
import random
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

DEPTH = 3
RECENT_BLOBS = 256


@dataclass(frozen=True)
class FileSpec:
    rel: str      # location-relative path, "/<part>/d0_1/f000001.<ext>"
    size: int
    part: int
    index: int    # index within the part
    root: int     # index of the file whose bytes this one holds


def _size_draw(rng: random.Random, classes: Sequence[Sequence[float]],
               cap: int) -> int:
    r = rng.random()
    acc = 0.0
    for weight, lo, hi in classes:
        acc += weight
        if r < acc:
            break
    n = rng.randrange(int(lo), int(hi))
    return min(n, cap) if cap > 0 else n


def plan_part(part_no: int, part: dict) -> Tuple[List[str], List[FileSpec]]:
    """(directories, files) of one part, from its layout seed alone."""
    rng = random.Random(part["layout_seed"])
    name, ext = part["name"], part.get("extension", "bin")
    dirs = [f"/{name}"]
    for d in range(DEPTH):
        for i in range(min(2 ** (d + 1), 8)):
            dirs.append(f"{rng.choice(dirs)}/d{d}_{i}")
    files: List[FileSpec] = []
    recent: List[int] = []
    by_index: Dict[int, FileSpec] = {}
    for i in range(part["files"]):
        rel = f"{rng.choice(dirs)}/f{i:06d}.{ext}"
        if recent and rng.random() < part["dup_rate"]:
            src = by_index[rng.choice(recent)]
            spec = FileSpec(rel, src.size, part_no, i, src.root)
        else:
            spec = FileSpec(rel, _size_draw(rng, part["size_classes"],
                                            part.get("max_file_bytes", 0)),
                            part_no, i, i)
        files.append(spec)
        by_index[i] = spec
        recent.append(i)
        if len(recent) > RECENT_BLOBS:
            recent.pop(0)
    return dirs, files


def plan_location(parts: Sequence[dict]) -> Tuple[List[str], List[FileSpec]]:
    dirs: List[str] = []
    files: List[FileSpec] = []
    for p, part in enumerate(parts):
        d, f = plan_part(p, part)
        dirs += d
        files += f
    return dirs, files


def content(seed: int, spec: FileSpec) -> bytes:
    """The bytes of one file under `seed`."""
    ss = np.random.SeedSequence([seed % (1 << 64), spec.part, spec.root])
    return np.random.Generator(np.random.PCG64(ss)).bytes(spec.size)


def planted_groups(files: Sequence[FileSpec]) -> List[frozenset]:
    """Location-relative paths of every set of files with equal bytes,
    for sets of two or more."""
    groups: Dict[Tuple[int, int], set] = {}
    for f in files:
        groups.setdefault((f.part, f.root), set()).add(f.rel)
    return [frozenset(g) for g in groups.values() if len(g) > 1]


def _write_slice(job) -> int:
    root, seed, specs = job
    written = 0
    for spec in specs:
        data = content(seed, spec)
        with open(root + spec.rel, "wb") as f:
            f.write(data)
        written += len(data)
    return written


def write_location(root: str, parts: Sequence[dict], seed: int,
                   processes: int) -> List[FileSpec]:
    """Write the location under `root` with `processes` spawned writers;
    returns its files."""
    dirs, files = plan_location(parts)
    for d in dirs:
        os.makedirs(root + d, exist_ok=True)
    n = max(1, min(processes, len(files)))
    jobs = [(root, seed, files[k::n]) for k in range(n)]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(n) as pool:
        pool.map(_write_slice, jobs)
    return files
