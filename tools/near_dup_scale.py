"""Near-dup at scale: 1M-digest LSH validation with measured recall.

BASELINE.json config 4/5 requires near-dup search beyond the ~100k
exact-all-pairs ceiling (SURVEY.md §7 hard-part 4). This tool validates
the production path (ops/hamming.near_dup_pairs_lsh — the exact code the
NearDupDetectorJob compare step runs past ALL_PAIRS_LIMIT) on synthetic
64-bit pHashes with planted near-dups:

1. N random digests + P planted pairs at Hamming distance ≤ threshold.
2. Run the LSH pipeline; measure wall time and planted-pair recall.
3. On a 100k subset, also run the exact tiled all-pairs and report
   LSH-vs-exact recall (ground truth, not just planted).

    python tools/near_dup_scale.py --n 1000000 [--planted 5000]

Prints one JSON line per stage.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])  # repo root

import numpy as np  # noqa: E402


def make_digests(n: int, planted: int, threshold: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    digests = rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32)
    # Plant pairs: copy row i to row j with ≤ threshold flipped bits.
    # src and dst are drawn as ONE disjoint sample: a dst that doubled
    # as another pair's src would be overwritten after being copied
    # (chained overwrite), silently invalidating the earlier plant and
    # capping measurable recall below 1.0.
    both = rng.choice(n, size=2 * planted, replace=False)
    src, dst = both[:planted], both[planted:]
    flips = rng.integers(0, threshold + 1, size=len(src))
    digests[dst] = digests[src]
    for k in range(len(src)):
        bits = rng.choice(64, size=flips[k], replace=False)
        for b in bits:
            digests[dst[k], b // 32] ^= np.uint32(1) << np.uint32(b % 32)
    pairs = {(min(a, b), max(a, b)) for a, b in zip(src.tolist(),
                                                   dst.tolist())}
    return digests, pairs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--planted", type=int, default=5000)
    ap.add_argument("--threshold", type=int, default=10)
    ap.add_argument("--subset", type=int, default=100_000)
    args = ap.parse_args()

    from spacedrive_tpu.ops.hamming import (
        near_dup_pairs_device, near_dup_pairs_lsh)

    digests, planted = make_digests(args.n, args.planted, args.threshold)

    def recall_of(pairs) -> float:
        s = set(pairs)
        return (sum(1 for p in planted if p in s) / len(planted)
                if planted else 1.0)

    # Production path: exact two-pass device sweep at full N.
    t0 = time.perf_counter()
    exact = near_dup_pairs_device(digests, args.threshold)
    dt = time.perf_counter() - t0
    print(json.dumps({
        "stage": "exact_device", "n": args.n, "seconds": round(dt, 2),
        "digests_per_sec": round(args.n / dt, 1),
        "pairs_found": len(exact),
        "planted": len(planted),
        "planted_recall": round(recall_of(exact), 4),
    }), flush=True)

    # CPU LSH fallback: record its honest (lossy) recall + runtime.
    t0 = time.perf_counter()
    lsh = near_dup_pairs_lsh(digests, args.threshold)
    dt = time.perf_counter() - t0
    exact_set = set(exact)
    print(json.dumps({
        "stage": "lsh_fallback", "n": args.n, "seconds": round(dt, 2),
        "pairs_found": len(lsh),
        "planted_recall": round(recall_of(lsh), 4),
        "recall_vs_exact": round(
            len(exact_set & set(lsh)) / len(exact_set), 4)
        if exact_set else 1.0,
    }), flush=True)


if __name__ == "__main__":
    main()
