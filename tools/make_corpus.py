"""Deterministic synthetic corpus generator.

The reference's `packages/test-files/` is empty in the snapshot
(populated by external scripts), so the benchmark configs of
BASELINE.md must run against a generated corpus. This produces a
reproducible (seeded) tree with the properties the identification
pipeline cares about:

- a size mix straddling the 100 KiB sampled-hash threshold
  (cas.rs:15 semantics) with a long tail of multi-MiB files,
- exact duplicates at a configurable rate (CAS-ID dedup, config 3),
- near-duplicate images: base PNGs plus slightly-perturbed variants
  (pHash Hamming near-dup, config 4),
- nested directories for walker/rule coverage.

    python tools/make_corpus.py OUT_DIR --files 10000 --dup-rate 0.1 \
        --images 200 --seed 0
"""

from __future__ import annotations

import argparse
import json
import os
import random


def make_corpus(out_dir: str, files: int = 1000, dup_rate: float = 0.1,
                images: int = 0, seed: int = 0, depth: int = 3,
                small_only: bool = False, max_size: int = 0) -> dict:
    """small_only caps files at 8 KiB — the 100k/1M-scale configs, where
    generating the default multi-MiB tail would dominate the run.
    max_size > 0 clamps every sampled size to at most that many bytes
    (the mix keeps its shape below the cap).

    The returned stats carry `planted`: (duplicate, source) path pairs
    relative to out_dir, one per planted exact duplicate — the ground
    truth for duplicate groups. corpus.json keeps the counts only."""
    rng = random.Random(seed)
    os.makedirs(out_dir, exist_ok=True)
    dirs = [out_dir]
    for d in range(depth):
        for i in range(min(2 ** (d + 1), 8)):
            p = os.path.join(rng.choice(dirs), f"d{d}_{i}")
            os.makedirs(p, exist_ok=True)
            dirs.append(p)

    stats = {"files": 0, "bytes": 0, "duplicates": 0, "images": 0}
    planted = []  # (duplicate, source), relative to out_dir
    blobs = []  # (payload reference) for duplicate sampling

    def mixed_size() -> int:
        if small_only:
            return rng.randrange(256, 8 * 1024)
        r = rng.random()
        if r < 0.50:
            return rng.randrange(256, 100 * 1024)          # whole-file CAS
        if r < 0.90:
            return rng.randrange(100 * 1024 + 1, 1 << 20)  # sampled CAS
        return rng.randrange(1 << 20, 8 << 20)             # multi-MiB

    def size_sample() -> int:
        n = mixed_size()
        return min(n, max_size) if max_size > 0 else n

    for i in range(files):
        path = os.path.join(rng.choice(dirs), f"f{i:06d}.bin")
        if blobs and rng.random() < dup_rate:
            src = rng.choice(blobs)
            with open(src, "rb") as f:
                payload = f.read()
            stats["duplicates"] += 1
            planted.append((os.path.relpath(path, out_dir),
                            os.path.relpath(src, out_dir)))
        else:
            payload = rng.randbytes(size_sample())
        with open(path, "wb") as f:
            f.write(payload)
        blobs.append(path)
        if len(blobs) > 256:
            blobs.pop(0)
        stats["files"] += 1
        stats["bytes"] += len(payload)

    if images:
        from PIL import Image, ImageDraw

        img_dir = os.path.join(out_dir, "images")
        os.makedirs(img_dir, exist_ok=True)
        bases = max(1, images // 3)
        for b in range(bases):
            im = Image.new("RGB", (256, 192), (
                rng.randrange(256), rng.randrange(256), rng.randrange(256)))
            draw = ImageDraw.Draw(im)
            for _ in range(6):
                x0, y0 = rng.randrange(200), rng.randrange(150)
                draw.rectangle(
                    [x0, y0, x0 + rng.randrange(8, 56),
                     y0 + rng.randrange(8, 42)],
                    fill=(rng.randrange(256), rng.randrange(256),
                          rng.randrange(256)))
            im.save(os.path.join(img_dir, f"img{b:04d}.png"))
            stats["images"] += 1
            # near-dup variants: tiny brightness/crop perturbations that
            # keep the DCT signature close (Hamming ≤ threshold).
            for v in range((images - bases) // bases + 1):
                if stats["images"] >= images:
                    break
                var = im.point(lambda px, d=v: min(255, px + 2 + d))
                var.save(os.path.join(img_dir, f"img{b:04d}_v{v}.png"))
                stats["images"] += 1

    with open(os.path.join(out_dir, "corpus.json"), "w") as f:
        json.dump({"seed": seed, **stats}, f)
    return {**stats, "planted": planted}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("out_dir")
    ap.add_argument("--files", type=int, default=1000)
    ap.add_argument("--dup-rate", type=float, default=0.1)
    ap.add_argument("--images", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--small", action="store_true")
    args = ap.parse_args()
    stats = make_corpus(args.out_dir, args.files, args.dup_rate,
                        args.images, args.seed, small_only=args.small)
    stats.pop("planted")
    print(json.dumps(stats))
