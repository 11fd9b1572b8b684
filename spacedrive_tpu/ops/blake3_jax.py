"""JAX device backend for batched BLAKE3 and CAS-ID generation.

The device computation is the shared pad-and-mask algorithm from
``blake3_batch`` instantiated with ``jax.numpy`` and jitted; jit
shape-specializes per (B, C) grid, and the CAS pipeline deliberately uses
a small set of canonical grids so compilation is amortized:

- large-file mode: every payload is exactly 57,344 sampled bytes
  (+ 8-byte size prefix) → a fixed [B, 57, 256] grid (cas.rs:23-62
  semantics; see ops/cas.py for the sampling spec),
- small-file mode: whole files ≤ 100 KiB → a fixed [B, 101, 256] grid.

Digest/CAS formatting (hex truncation to 16 chars) matches
/root/reference/core/src/object/cas.rs:61.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import cas, jit_registry
from .. import flags, tracing
from ..telemetry import (
    CAS_DEVICE_BYTES,
    CAS_MESSAGE_BYTES,
    CAS_PHASE_DISPATCH,
    CAS_PHASE_FETCH,
    CAS_PHASE_PACK,
    VAL_BATCHED_DEVICE,
    VAL_BATCHED_FILE_BYTES,
    VAL_BATCHED_FILES,
    VAL_BATCHED_GRID_BYTES,
    VAL_BATCHED_PACK,
)
from .blake3_batch import (  # noqa: F401 — re-exported for callers
    CHUNK_LEN,
    WORDS_PER_CHUNK,
    build_cas_messages,
    digests_to_cas_ids,
    digests_to_hex,
)

# Canonical chunk-grid sizes for the two CAS modes.
LARGE_MSG_LEN = cas.SIZE_PREFIX_LEN + cas.LARGE_PAYLOAD_SIZE  # 57352
LARGE_CHUNKS = -(-LARGE_MSG_LEN // CHUNK_LEN)  # 57
SMALL_MSG_MAX = cas.SIZE_PREFIX_LEN + cas.MINIMUM_FILE_SIZE  # 102408
SMALL_CHUNKS = -(-SMALL_MSG_MAX // CHUNK_LEN)  # 101


def _chunk_cvs_scan(words, lengths, counter_base=0, whole=True):
    """JAX-shaped chunk stage: lax.scan over the 16 blocks of every chunk.

    Same math as blake3_batch.chunk_cvs (the numpy oracle path) — the
    per-block metadata comes from the shared chunk_prelude/block_meta
    helpers so the backends cannot diverge. Only the loop strategy
    differs: a scan keeps one compression body in the compiled graph
    instead of sixteen, cutting compile time ~an order of magnitude
    while XLA keeps the carry in registers/VMEM.
    """
    from .blake3_batch import (
        BLOCKS_PER_CHUNK,
        WORDS_PER_BLOCK,
        _select,
        block_meta,
        chunk_prelude,
        compress_cv,
    )
    from .blake3_ref import IV

    B, C, W = words.shape
    u32 = lambda v: jnp.asarray(v, dtype=jnp.uint32)  # noqa: E731
    (
        chunk_bytes, n_chunks, single, k_last,
        counter_lo, counter_hi, empty0,
    ) = chunk_prelude(jnp, lengths, C, counter_base, whole)

    blocks = jnp.moveaxis(
        words.reshape(B, C, BLOCKS_PER_CHUNK, WORDS_PER_BLOCK), 2, 0
    )  # [16, B, C, 16]
    ks = jnp.arange(BLOCKS_PER_CHUNK, dtype=jnp.int32)

    # Derive the IV carry from the input so its sharding "varying axes"
    # match the scan outputs under shard_map.
    zeros = jnp.zeros_like(words[:, :, 0])
    cv0 = tuple(u32(IV[i]) + zeros for i in range(8))

    def body(cv, xs):
        k, blk = xs
        block_len, active, flags = block_meta(
            jnp, chunk_bytes, k_last, single, empty0, k
        )
        m = [blk[:, :, j] for j in range(WORDS_PER_BLOCK)]
        new_cv = compress_cv(
            jnp, list(cv), m, counter_lo, counter_hi, u32(block_len), flags
        )
        return tuple(_select(jnp, active, new_cv, list(cv))), None

    cv, _ = jax.lax.scan(body, cv0, (ks, blocks))
    return list(cv), n_chunks


def _blake3_impl(words, lengths):
    """Shared body of the jitted and shard_mapped entry points."""
    from .blake3_batch import tree_reduce

    cvs, n_chunks = _chunk_cvs_scan(words, lengths)
    return jnp.stack(tree_reduce(jnp, cvs, n_chunks), axis=1)


@jit_registry.tracked("blake3.jnp")
@jax.jit
def _blake3_jnp_jit(words, lengths):
    return _blake3_impl(words, lengths)


def _blake3_impl_best(words, lengths):
    """Traceable best-backend body: pallas kernel on TPU, jnp scan
    elsewhere. Usable inside an enclosing jit (bench harness loops)."""
    from . import blake3_pallas

    if blake3_pallas.supported():
        return blake3_pallas.blake3_words_pallas(words, lengths)
    return _blake3_impl(words, lengths)


def _donated_best(words, lengths):
    """Donated twin of the best-backend body: identity pass-through
    outputs alias the donated inputs (same shape/dtype, so XLA's
    input-output aliasing engages on every backend, CPU included),
    meaning the staged device copies are CONSUMED at dispatch and
    recycled by the allocator at kernel completion — instead of
    surviving until the digest fetch like the undonated entry's."""
    return _blake3_impl_best(words, lengths), words, lengths


_blake3_best_donated = jit_registry.tracked("blake3.donated")(
    jax.jit(_donated_best, donate_argnums=(0, 1)))


def _donated_local(words, lengths):
    """Local (single-device) CAS hasher over the donated entry: the
    ring aliases are dropped on the floor — the identify pipeline only
    wants the digests, the recycled buffers belong to the allocator."""
    digests, _ring_w, _ring_l = _blake3_best_donated(words, lengths)
    return digests


def blake3_words(words, lengths):
    """[B, C, 256] uint32 words + [B] int32 lengths → [B, 8] uint32 digests.

    Dispatches to the Pallas chunk-stage kernel on TPU (measured ~2×
    the jnp scan path and ~8.5× the AVX2 C++ plane at batch 2048; see
    ops/blake3_pallas.py) and to the jnp scan path elsewhere (CPU mesh
    tests, hosts without a TPU). Digests are bit-identical across
    backends — parity is pinned by tests/test_blake3_pallas.py and the
    oracle vectors.
    """
    from . import blake3_pallas

    if blake3_pallas.supported():
        return blake3_pallas.blake3_words_pallas(words, lengths)
    return _blake3_jnp_jit(words, lengths)


def make_sharded_blake3(mesh, axis: str = "data"):
    """Data-parallel batched BLAKE3 over a device mesh.

    Hashing is embarrassingly parallel across files, so the batch dim is
    sharded over `axis` and no collectives are needed; the result lands
    fully replicated only when gathered by the caller. The per-shard
    body is the best-backend one — the Pallas chunk-stage kernel on TPU
    meshes (~2× the jnp scan per chip), the jnp scan elsewhere — so
    sharding never trades away the single-chip kernel.

    check_vma=False: the Pallas kernel's out_shape declares no varying
    mesh axes, which the installed shard_map refuses under its default
    check (tests/test_tpu_compile.py compiles this for a 2x2 v5e).
    """
    P = jax.sharding.PartitionSpec

    return jit_registry.tracked("blake3.sharded")(jax.jit(
        functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=P(axis),
            check_vma=False,
        )(_blake3_impl_best)
    ))


def sharded_hasher():
    """The production multi-device hasher: data-parallel BLAKE3 over ALL
    local devices, or None on a single-device host.

    This is how the identifier's flagship pipeline uses a pod slice
    (SURVEY §2.6 mapping): batch dim sharded over the mesh's data axis,
    zero collectives (hashing is embarrassingly parallel per file).
    Cached so the mesh + compiled program build once per process.

    SDTPU_SHARDED_CAS=off forces the single-device program — the test
    suite sets it because its 8-VIRTUAL-device CPU mesh would pay a
    fresh ~50 s shard_map compile per batch grid for zero coverage
    gain (the sharded dispatch has its own dedicated test and the
    driver's dryrun_multichip stage 6)."""
    global _SHARDED
    if _SHARDED is None:
        devs = jax.devices()
        if (len(devs) < 2
                or flags.get("SDTPU_SHARDED_CAS") == "off"):
            _SHARDED = (None, 1)
        else:
            from ..parallel.mesh import batch_mesh

            _SHARDED = (make_sharded_blake3(batch_mesh(devs)), len(devs))
    return _SHARDED


_SHARDED = None


# jit shape-specializes per (B, C); C is canonical per CAS mode, but the
# identifier's per-step large/small split makes B arbitrary. Padding B up
# to a bucket keeps the number of compiled programs tiny — without this,
# a scan over mixed batches recompiles (~10 s on TPU) nearly every step.
_B_BUCKETS = (8, 32, 64, 128, 256, 512, 1024, 2048)


def _bucket_b(B: int) -> int:
    for b in _B_BUCKETS:
        if B <= b:
            return b
    return -(-B // _B_BUCKETS[-1]) * _B_BUCKETS[-1]


def checksums_words_batched(blobs) -> list:
    """Full BLAKE3 digests (64-hex) of B byte strings in ONE device
    dispatch: rows padded to a shared power-of-two chunk grid, hashed by
    the batch machinery (sharded over the mesh when >1 device). Its
    pack and device phases, files and bytes are the validator's batched
    path in the sd_validator_* families.

    This is the validator's dispatch amortizer (VERDICT r4 item 4):
    hashing one file per call pays a dispatch round trip per file —
    packing a page of small files into one batched grid pays it once
    per page. Callers group similar sizes per call (validator sorts by
    size) so the shared C pads little.
    """
    B = len(blobs)
    if B == 0:
        return []
    from .blake3_batch import CHUNK_LEN, WORDS_PER_CHUNK, digests_to_hex

    hasher, n_dev = sharded_hasher()
    if hasher is None:
        hasher = blake3_words
    with tracing.phase("validator.phase/pack", VAL_BATCHED_PACK):
        maxlen = max(len(b) for b in blobs)
        C = max(1, -(-max(maxlen, 1) // CHUNK_LEN))
        C = 1 << (C - 1).bit_length()   # pow2 → few compiled grids
        Bp = _bucket_b(B)
        if n_dev > 1:
            from ..parallel.mesh import pad_to_multiple

            Bp = pad_to_multiple(Bp, n_dev)
        buf = np.zeros((Bp, C * CHUNK_LEN), dtype=np.uint8)
        lengths = np.zeros((Bp,), dtype=np.int32)
        for i, b in enumerate(blobs):
            buf[i, :len(b)] = np.frombuffer(b, dtype=np.uint8)
            lengths[i] = len(b)
        words = buf.view("<u4").reshape(Bp, C, WORDS_PER_CHUNK)
    VAL_BATCHED_FILES.inc(B)
    VAL_BATCHED_FILE_BYTES.inc(int(lengths.sum()))
    VAL_BATCHED_GRID_BYTES.inc(buf.nbytes)
    if flags.get("SDTPU_DISPATCH_LOG"):
        DISPATCH_LOG.append({"B": B, "Bp": Bp, "n_dev": n_dev, "C": C,
                             "kind": "checksum"})
    with jit_registry.device_scope("cas.checksums"), \
            tracing.phase("validator.phase/device", VAL_BATCHED_DEVICE):
        digests = hasher(words, lengths)[:B]
        with jit_registry.io("cas.checksums"):
            return digests_to_hex(digests)


# Dispatch observability: when SDTPU_DISPATCH_LOG=1, every cas_ids_words
# call appends {"B", "Bp", "n_dev"} here — per-device shard balance is
# Bp/n_dev by construction (batch padded to a devices-multiple), and
# the dryrun/driver artifacts record it from this log.
DISPATCH_LOG: list = []  # sdlint: ok[unbounded-growth] flag-gated diagnostic (SDTPU_DISPATCH_LOG=1): dryrun artifacts read the whole log, so it must not self-truncate


def cas_hasher(hasher=None):
    """(hasher, n_dev) of a CAS dispatch. With no explicit `hasher`, a
    multi-device host dispatches through the mesh-sharded program over
    n_dev devices; single-device hosts use the local jit/Pallas path.
    An explicit `hasher` runs on one device."""
    if hasher is not None:
        return hasher, 1
    hasher, n_dev = sharded_hasher()
    if hasher is None:
        # Single-device dispatch goes through the donated entry by
        # default (SDTPU_DONATE_BUFFERS): the batch's staged device
        # copy is recycled at kernel completion, not pinned until the
        # CAS-ID fetch. Donation consumes the device copy only, never
        # the host grid it was transferred from.
        hasher = (_donated_local
                  if flags.get("SDTPU_DONATE_BUFFERS")
                  else blake3_words)
    return hasher, n_dev


def cas_rows(B: int, n_dev: int) -> int:
    """Rows of the CAS grid that carries B messages: B's bucket, padded
    to a multiple of n_dev so every shard gets equal rows."""
    Bp = _bucket_b(B)
    if n_dev > 1:
        from ..parallel.mesh import pad_to_multiple

        Bp = pad_to_multiple(Bp, n_dev)
    return Bp


def cas_ids_jax(payloads, sizes, payload_lens=None, hasher=None,
                marks=None) -> list:
    """End-to-end device CAS: payload rows + sizes → 16-hex CAS IDs.

    Packs the rows into CAS messages padded to the bucket's row count
    (cas.phase/pack), then dispatches and fetches them through
    `cas_ids_words`. `marks`, when a list, receives the (pack,
    dispatch, fetch) end times of this call."""
    hasher, n_dev = cas_hasher(hasher)
    with tracing.phase("cas.phase/pack", CAS_PHASE_PACK) as pack:
        words, lengths = build_cas_messages(payloads, sizes, payload_lens)
        B = words.shape[0]
        Bp = cas_rows(B, n_dev)
        if Bp != B:
            words = np.concatenate(
                [words, np.zeros((Bp - B,) + words.shape[1:], words.dtype)])
            lengths = np.concatenate(
                [lengths, np.zeros((Bp - B,), lengths.dtype)])
    return cas_ids_words(words, lengths, B, hasher, n_dev, pack.t1, marks)


def cas_ids_words(words, lengths, B: int, hasher, n_dev: int,
                  packed_at: float, marks=None) -> list:
    """The dispatch and fetch half of the device CAS: a [Bp, C, 256]
    uint32 message grid and its [Bp] int32 lengths → the CAS IDs of its
    first B rows. Rows past B are padding, with length 0 (their digests
    are discarded). The grid may be a pooled host page: it is read until
    the digests are fetched, so the caller keeps it until this returns.

    The host phases are counted in sd_cas_phase_seconds_total (dispatch,
    fetch) and annotated on a running profiler capture; the grid's bytes
    in sd_cas_device_bytes_total and its messages' in
    sd_cas_message_bytes_total. `marks`, when a list, receives
    (packed_at, dispatch end, fetch end)."""
    CAS_DEVICE_BYTES.inc(words.nbytes)
    CAS_MESSAGE_BYTES.inc(int(lengths.sum()))
    if flags.get("SDTPU_DISPATCH_LOG"):
        DISPATCH_LOG.append({"B": B, "Bp": words.shape[0], "n_dev": n_dev})
    with jit_registry.device_scope("cas.ids"):
        with tracing.phase("cas.phase/dispatch", CAS_PHASE_DISPATCH) as disp:
            digests = hasher(words, lengths)[:B]
        with tracing.phase("cas.phase/fetch", CAS_PHASE_FETCH) as fetch:
            with jit_registry.io("cas.ids"):
                ids = digests_to_cas_ids(digests)
    if marks is not None:
        marks.append((packed_at, disp.t1, fetch.t1))
    return ids
