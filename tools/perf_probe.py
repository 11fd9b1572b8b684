"""Kernel timing probe: chained in-jit loops.

Per-call wall timing includes each dispatch and sync. To time the
kernel itself, run N kernel executions INSIDE one jitted program,
serialized by a loop-carried data dependency XLA cannot fold away
(`lengths | (acc & 1)` — value-unknown at compile time), and time the
whole program with one D2H sync at the end.

Usage (run from the repo root, real chip):
    python tools/perf_probe.py

Prints files/s for: the AVX2 C++ plane (the honest CPU baseline), the
jnp scan path, the Pallas kernel, plus H2D link bandwidth and the
steady-state overlapped-pipeline estimate.
"""

from __future__ import annotations

import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])  # repo root

import numpy as np  # noqa: E402

B = 2048
ITERS = 20
MSG_BYTES = 57352  # 8-byte size prefix + 57,344 sampled bytes


def make_batch():
    from spacedrive_tpu.ops import blake3_jax as bj

    rng = np.random.default_rng(0)
    payloads = rng.integers(0, 256, size=(B, 57344), dtype=np.uint8)
    sizes = rng.integers(200_000, 50_000_000, size=B).astype(np.uint64)
    words, lengths = bj.build_cas_messages(payloads, sizes)
    return payloads, sizes, words, lengths


def native_files_per_sec(payloads, sizes) -> float:
    from spacedrive_tpu import native

    if not native.available():
        return 0.0
    lens = np.full(B, payloads.shape[1], np.int32)
    native.blake3_many(payloads[:64], lens[:64], sizes[:64])  # warm pool
    t0 = time.perf_counter()
    iters = 3
    for _ in range(iters):
        native.blake3_many(payloads, lens, sizes)
    return B * iters / (time.perf_counter() - t0)


def device_loop_timer(body_fn, words, lengths, iters: int = ITERS) -> float:
    """Seconds per body_fn(words, lengths) execution, measured on-device."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    @jax.jit
    def looped(w, l):
        def body(acc, _):
            out = body_fn(w, l | (acc[0, 0] & 1).astype(l.dtype))
            return out, None
        acc, _ = lax.scan(body, jnp.zeros((B, 8), jnp.uint32),
                          None, length=iters)
        return acc

    w = jax.device_put(words)
    l = jax.device_put(lengths)
    r = looped(w, l)
    np.asarray(r.ravel()[0])  # compile + warm; sync via D2H (see module doc)
    t0 = time.perf_counter()
    r = looped(w, l)
    np.asarray(r.ravel()[0])
    return (time.perf_counter() - t0) / iters


def h2d_seconds(words) -> float:
    import jax

    w = jax.device_put(words)
    np.asarray(w.ravel()[0])
    t0 = time.perf_counter()
    iters = 5
    for _ in range(iters):
        w = jax.device_put(words)
        np.asarray(w.ravel()[0])
    return (time.perf_counter() - t0) / iters


def main() -> None:
    payloads, sizes, words, lengths = make_batch()

    nat = native_files_per_sec(payloads, sizes)
    print(f"native AVX2 C++ plane: {nat:,.0f} files/s "
          f"({nat * MSG_BYTES / 1e9:.2f} GB/s)")

    from spacedrive_tpu.ops import blake3_jax as bj
    from spacedrive_tpu.ops import blake3_pallas as bp

    t = device_loop_timer(bj._blake3_jnp_jit, words, lengths)
    print(f"jnp scan path: {t*1e3:.2f} ms/batch -> {B/t:,.0f} files/s")

    if bp.supported():
        t = device_loop_timer(bp.blake3_words_pallas, words, lengths)
        print(f"pallas kernel: {t*1e3:.2f} ms/batch -> {B/t:,.0f} files/s "
              f"({B * MSG_BYTES / t / 1e9:.1f} GB/s)")
        th = h2d_seconds(words)
        print(f"H2D: {words.nbytes/th/1e9:.2f} GB/s "
              f"({th*1e3:.0f} ms/batch)")
        steady = B / max(t, th)
        print(f"overlapped-pipeline estimate: {steady:,.0f} files/s")
    else:
        print("pallas: unsupported on this backend")


if __name__ == "__main__":
    main()
