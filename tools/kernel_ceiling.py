"""Kernel-ceiling probe: how the CAS kernel's rate moves with batch
and chain length.

This sweep times the production kernel (ops/blake3_jax
_blake3_impl_best — the Pallas chunk-stage kernel on TPU) across batch
sizes and chain lengths with the scan-chained single-sync methodology
(per-call walls include the dispatch and sync, not only the kernel):

- if throughput grows with B or ITERS, per-dispatch/per-scan overhead
  is still being amortized (attackable);
- if it is flat, the sustained rate IS the kernel's pipeline rate.

    python tools/kernel_ceiling.py [--quick]

Prints one JSON line per (B, ITERS) config. A chip belongs to one
process: never run it next to another process that uses the TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import numpy as np  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    from spacedrive_tpu.ops import blake3_jax as bj

    configs = ([(16384, 10), (16384, 30)] if args.quick else
               [(4096, 10), (16384, 10), (16384, 30), (32768, 10)])
    rng = np.random.default_rng(0)
    for B, iters in configs:
        payloads = rng.integers(0, 256, size=(B, 57344), dtype=np.uint8)
        sizes = rng.integers(200_000, 5_000_000, size=B).astype(np.uint64)
        words, lengths = bj.build_cas_messages(payloads, sizes)

        @jax.jit
        def looped(w, l, _iters=iters, _B=B):
            def body(acc, _):
                out = bj._blake3_impl_best(
                    w, l | (acc[0, 0] & 1).astype(l.dtype))
                return out, None
            acc, _ = lax.scan(body, jnp.zeros((_B, 8), jnp.uint32),
                              None, length=_iters)
            return acc

        w = jax.device_put(words)
        l = jax.device_put(lengths)
        t0 = time.perf_counter()
        np.asarray(looped(w, l))  # compile + warm + full fetch
        compile_s = time.perf_counter() - t0
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = looped(w, l)
            np.asarray(out)  # full (small) fetch = the only real sync
            best = min(best, (time.perf_counter() - t0) / iters)
        fps = B / best
        print(json.dumps({
            "B": B, "iters": iters,
            "files_per_sec": round(fps, 1),
            "per_dispatch_ms": round(best * 1000, 2),
            "compile_s": round(compile_s, 1),
        }), flush=True)


if __name__ == "__main__":
    main()
