"""Instruction-mix floor probe for the CAS kernel (round-4 accounting).

Question (VERDICT r3 item 7): is the kernel's measured ~2.5M files/s at
its instruction-mix floor, or is there headroom? Answer it by measuring
the floor DIRECTLY: chain the kernel's own 7-round BLAKE3 compression
body (`blake3_batch.compress_cv` — adds, xors, shift+or rotations,
diagonal rolls; nothing else: no message staging, no chunk masking,
no tree reduce) behind a non-foldable carry, fit the marginal time per
compression exactly as tools/kernel_ceiling.py fits the full kernel
(two chain lengths split fixed RPC from marginal compute), and convert:

    floor_files_per_sec = 1 / (t_compress * compressions_per_file)

A large-mode CAS file is 57 chunks x 16 blocks + 56 tree parents
= 968 compressions. If the full kernel's measured marginal rate is
within ~15% of this pure-ALU floor, the remaining 1-utilization is the
compression math itself (the VPU lowering of rotate as shift+shift+or,
roll data movement), not schedulable overhead — the accounting the
round-3 verdict asked to see. Static op count per compression (the
x-axis of that accounting): 7 rounds x 2 vector-G x 4 words x
(6 add + 4 xor + 4 rot x 3) + 6 rolls/round + output fold
= 1,232 ALU ops (+ the 8-xor output fold = 1,240) + 168
roll-moves per 64-byte block.

Run ALONE — a chip belongs to one process. Chunked dispatches of a few
seconds; a D2H fetch is the sync.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

COMPRESSIONS_PER_FILE = 57 * 16 + 56  # chunk blocks + tree parents
ALU_OPS_PER_COMPRESSION = 1240
ROLL_MOVES_PER_COMPRESSION = 168


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from spacedrive_tpu.ops.blake3_batch import compress_cv

    B, C = 2048, 57  # the production large-mode grid slice
    rng = np.random.default_rng(0)
    cv0 = [rng.integers(0, 2**32, (B, C), dtype=np.uint32)
           for _ in range(8)]
    m0 = [rng.integers(0, 2**32, (B, C), dtype=np.uint32)
          for _ in range(16)]

    UNROLL = 4

    def make(iters: int):
        @jax.jit
        def f(cv, m):
            def step(carry, _):
                out = list(carry)
                for k in range(UNROLL):
                    # crypto chaining: nothing here constant-folds
                    out = compress_cv(jnp, out, m, out[0], out[1],
                                      jnp.uint32(64), jnp.uint32(0))
                return tuple(out), None
            out, _ = lax.scan(step, tuple(cv), None, length=iters)
            return out[0]
        return f

    def timed(f, cv, m):
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            _ = np.asarray(f(cv, m)).ravel()[0]  # D2H = the only sync
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    cvd = [jnp.asarray(a) for a in cv0]
    md = [jnp.asarray(a) for a in m0]
    rows = []
    for iters in (256, 1024):
        f = make(iters)
        _ = np.asarray(f(cvd, md)).ravel()[0]  # compile+warm
        dt = timed(f, cvd, md)
        n_compress = iters * UNROLL * B * C
        rows.append((iters, dt, n_compress))
        print(json.dumps({
            "probe": "compress_chain", "iters": iters, "unroll": UNROLL,
            "seconds": round(dt, 4),
            "compressions": n_compress,
        }), flush=True)

    # fit: dt = t_fixed + n_compress * t_marg  (two points)
    (i1, dt1, n1), (i2, dt2, n2) = rows
    t_marg = (dt2 - dt1) / (n2 - n1)
    t_fixed = dt1 - n1 * t_marg
    compress_rate = 1.0 / t_marg
    alu_rate = compress_rate * ALU_OPS_PER_COMPRESSION
    floor_files = compress_rate / COMPRESSIONS_PER_FILE
    print(json.dumps({
        "metric": "cas_instruction_mix_floor",
        "t_fixed_ms": round(t_fixed * 1e3, 2),
        "t_marginal_ns_per_compression": round(t_marg * 1e9, 3),
        "compressions_per_sec": f"{compress_rate:.4e}",
        "alu_u32_ops_per_sec": f"{alu_rate:.4e}",
        "alu_ops_per_compression": ALU_OPS_PER_COMPRESSION,
        "roll_moves_per_compression": ROLL_MOVES_PER_COMPRESSION,
        "compressions_per_file": COMPRESSIONS_PER_FILE,
        "floor_files_per_sec": f"{floor_files:.4e}",
        "note": "pure-ALU compression chain, no staging/masking/tree; "
                "compare to the full kernel's measured marginal "
                "(tools/kernel_ceiling.py, ~2.5M files/s r3)",
    }), flush=True)


if __name__ == "__main__":
    main()
