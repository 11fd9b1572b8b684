"""Sequence-parallel BLAKE3: one huge file sharded across the mesh.

The long-context analog in this framework (SURVEY.md §5 "long-context /
sequence parallelism"): where an LLM shards one sequence's tokens across
devices, the validator shards one file's chunk chain. BLAKE3's tree mode
makes this exact — the tree over chunk CVs is adjacent pairing with
odd-promote, so any power-of-two-aligned span of chunks reduces to an
independent subtree top:

  stage 1 (local, zero collectives): each device hashes its contiguous
      span of chunks (counter base = global chunk index) and folds them
      to one subtree top with a no-ROOT tree reduction;
  stage 2 (one all-gather over ICI): the D shard tops are gathered and
      the top-of-tree reduction (log2 D tiny parent compressions) runs
      replicated on every device.

Semantics match the streaming oracle bit-for-bit
(/root/reference/core/src/object/validation/hash.rs full-file checksum,
here computed without any single device ever holding the whole file).

Shard capacity must be a power of two chunks so shard boundaries land on
subtree boundaries; files that fit in a single shard take the ordinary
batched path instead.

This is the validator's streamed path: its read, pack, host_hash and
device phases, files and bytes are counted under path="streamed" in the
sd_validator_* families (telemetry.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import jit_registry
from .. import tracing
from ..telemetry import (
    VAL_STREAMED_DEVICE,
    VAL_STREAMED_FILE_BYTES,
    VAL_STREAMED_FILES,
    VAL_STREAMED_GRID_BYTES,
    VAL_STREAMED_HOST_HASH,
    VAL_STREAMED_PACK,
    VAL_STREAMED_READ,
)
from .blake3_batch import CHUNK_LEN, WORDS_PER_CHUNK, tree_reduce
from .blake3_jax import _chunk_cvs_scan

DEFAULT_SHARD_CHUNKS = 64  # 64 KiB per device-shard in tests; tune up on TPU


def _shard_fn(words_local, length, shard_chunks: int,
              base_lo=None, base_hi=None):
    """Per-device stage: [cps, 256] chunk words → 8-word subtree top.

    `length` is the byte count within THIS window (int32 — one window is
    bounded at 2 GiB); `base_lo`/`base_hi` is the uint32 pair for the
    window's first global chunk index (0 for a single-call hash). The
    shard's chunk counters are window base + shard offset, so repeated
    windowed calls see exactly the chunk counters the streaming oracle
    would use.
    """
    idx = jax.lax.axis_index("data")
    start = (idx * shard_chunks * CHUNK_LEN).astype(jnp.int32)
    local_len = jnp.clip(length - start, 0, shard_chunks * CHUNK_LEN)
    # Chunk counter base: global chunk index of this shard's first chunk,
    # carried as a (lo, hi) uint32 pair with explicit carry.
    off = (idx * shard_chunks).astype(jnp.uint32)
    if base_lo is None:
        lo = off
        hi = jnp.zeros((), jnp.uint32)
    else:
        lo = base_lo + off
        hi = base_hi + jnp.where(lo < off, jnp.uint32(1), jnp.uint32(0))
    cvs, n = _chunk_cvs_scan(words_local[None], local_len[None],
                             counter_base=(lo, hi), whole=False)
    top = tree_reduce(jnp, cvs, n, root=False)  # 8 × [1]
    return jnp.stack([w[0] for w in top])  # [8]


@jit_registry.tracked("seqhash.reduce")
@functools.partial(jax.jit,
                   static_argnames=("mesh", "shard_chunks", "root"))
def _sharded_reduce(words, length, n_tops, base_lo, base_hi, *,
                    mesh: Mesh, shard_chunks: int, root: bool):
    """Shared device body: shard chunk stage + all-gather + top tree.

    words: [D*cps, 256] uint32 sharded on the chunk axis; length: int32
    bytes in this window; n_tops: int32 shards holding real chunks;
    base_lo/base_hi: uint32 pair, global chunk index of the window start
    (0 for a single-call hash). root=True ROOT-finalizes the top merge
    (single-call digest); root=False yields a streaming window's
    subtree-top CV.
    """
    def inner(words_local):
        top = _shard_fn(words_local, length, shard_chunks,
                        base_lo, base_hi)
        return jax.lax.all_gather(top, "data")  # [D, 8] replicated

    tops = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P("data", None),),
        out_specs=P(None, None),
        check_vma=False,
    )(words)
    # Top-of-tree: adjacent pairing over shard tops.
    cvs = [tops[:, i][None, :] for i in range(8)]  # 8 × [1, D]
    out = tree_reduce(jnp, cvs, n_tops[None], root=root)
    return jnp.stack([w[0] for w in out])  # [8]


def make_sharded_checksum(mesh: Mesh,
                          shard_chunks: int = DEFAULT_SHARD_CHUNKS):
    """Returns fn(data: bytes) -> 32-byte BLAKE3 digest, computed with
    the file's chunk chain sharded across `mesh`'s devices."""
    if shard_chunks & (shard_chunks - 1):
        raise ValueError("shard_chunks must be a power of two")
    D = int(np.prod(mesh.devices.shape))
    capacity = D * shard_chunks * CHUNK_LEN

    def fn(data: bytes) -> bytes:
        n_chunks = max(1, -(-len(data) // CHUNK_LEN))
        if n_chunks <= shard_chunks:
            # Fits one shard: the top stage would need ROOT handling the
            # sharded path deliberately never applies — use the batched
            # single-lane path, on the host.
            from .blake3_batch import blake3_batch_np

            VAL_STREAMED_GRID_BYTES.inc(n_chunks * CHUNK_LEN)
            with tracing.phase("validator.phase/host_hash",
                               VAL_STREAMED_HOST_HASH):
                return blake3_batch_np([data])[0]
        if len(data) > capacity:
            raise ValueError(
                f"data ({len(data)} B) exceeds mesh capacity "
                f"({capacity} B); raise shard_chunks")
        if len(data) > 2**31 - 1:
            raise ValueError(
                "single-call path is int32-bounded at 2 GiB; use "
                "StreamingShardedChecksum")
        with tracing.phase("validator.phase/pack", VAL_STREAMED_PACK):
            buf = np.zeros(capacity, dtype=np.uint8)
            buf[:len(data)] = np.frombuffer(data, dtype=np.uint8)
            words = buf.view("<u4").reshape(D * shard_chunks,
                                            WORDS_PER_CHUNK)
        VAL_STREAMED_GRID_BYTES.inc(capacity)
        sharding = NamedSharding(mesh, P("data", None))
        with jit_registry.device_scope("seqhash.reduce"), \
                tracing.phase("validator.phase/device", VAL_STREAMED_DEVICE):
            words_dev = jax.device_put(jnp.asarray(words), sharding)
            n_tops = np.int32(-(-n_chunks // shard_chunks))
            zero = jnp.zeros((), jnp.uint32)
            digest = _sharded_reduce(
                words_dev, jnp.asarray(len(data), jnp.int32),
                jnp.asarray(n_tops), zero, zero,
                mesh=mesh, shard_chunks=shard_chunks, root=True)
            with jit_registry.io("seqhash.window"):
                return np.asarray(digest).astype("<u4").tobytes()

    return fn


class StreamingShardedChecksum:
    """Streaming BLAKE3 over repeated sequence-sharded windows.

    Solves the "one file larger than mesh capacity / RAM" case the
    single-call path refuses: feed bytes in any increments; each time a
    full window (D · shard_chunks chunks) has accumulated AND more data
    follows, the window is hashed on-device (chunk counters offset by the
    window's global chunk base, no ROOT) into one subtree-top CV, and the
    host folds window tops with the standard incremental-BLAKE3 stack
    rule (merge-on-trailing-zeros of the window count — one 64-byte
    parent compression per merge, negligible host work). Memory is
    bounded at one window regardless of total stream length.

    The tail window (whatever remains at digest() time) reduces with the
    same adjacent-pairing/odd-promote tree, which equals the spec tree
    for any trailing span starting on a window boundary; the stack then
    merges right-to-left with ROOT on the last parent — exactly the
    finalize walk of the streaming oracle (blake3_ref.Blake3.digest).

    Semantics: /root/reference/core/src/object/validation/hash.rs:10-24
    (1 MiB streaming blocks into one hasher), recomputed here without any
    device ever holding more than one window.
    """

    def __init__(self, mesh: Mesh,
                 shard_chunks: int = DEFAULT_SHARD_CHUNKS):
        if shard_chunks & (shard_chunks - 1):
            raise ValueError("shard_chunks must be a power of two")
        D = int(np.prod(mesh.devices.shape))
        if D & (D - 1):
            raise ValueError("streaming windows need a power-of-two mesh")
        self._mesh = mesh
        self._shard_chunks = shard_chunks
        self._window_chunks = D * shard_chunks
        self._window_bytes = self._window_chunks * CHUNK_LEN
        if self._window_bytes > 2**31 - 1:
            # Window byte offsets are int32 on device (x64 off).
            raise ValueError(
                f"window ({self._window_bytes} B) exceeds the 2 GiB "
                "int32 device bound; lower shard_chunks")
        self._buf = bytearray()
        self._windows_done = 0     # full windows already folded (see
        # the `windows_folded` property)
        self._stack: list = []     # subtree CVs, shallowest first
        self._sharding = NamedSharding(mesh, P("data", None))

    @property
    def windows_folded(self) -> int:
        """Full windows already reduced on-device (diagnostics)."""
        return self._windows_done

    def update(self, data: bytes) -> "StreamingShardedChecksum":
        with tracing.phase("validator.phase/pack", VAL_STREAMED_PACK):
            self._buf += data
        # Keep at least one byte buffered: the final window must be the
        # ROOT path in digest(), so a window is only folded when data
        # strictly beyond it has arrived.
        while len(self._buf) > self._window_bytes:
            with tracing.phase("validator.phase/pack", VAL_STREAMED_PACK):
                window = bytes(self._buf[:self._window_bytes])
                del self._buf[:self._window_bytes]
            cv = self._window_top(window)
            with tracing.phase("validator.phase/device", VAL_STREAMED_DEVICE):
                self._push_window_cv(cv)
        return self

    def _window_top(self, data: bytes) -> list:
        """Device-reduce one window to its 8-word subtree-top CV."""
        with tracing.phase("validator.phase/pack", VAL_STREAMED_PACK):
            buf = np.zeros(self._window_bytes, dtype=np.uint8)
            buf[:len(data)] = np.frombuffer(data, dtype=np.uint8)
            words = buf.view("<u4").reshape(
                self._window_chunks, WORDS_PER_CHUNK)
        VAL_STREAMED_GRID_BYTES.inc(self._window_bytes)
        with jit_registry.device_scope("seqhash.reduce"), \
                tracing.phase("validator.phase/device", VAL_STREAMED_DEVICE):
            words_dev = jax.device_put(jnp.asarray(words), self._sharding)
            n_chunks = max(1, -(-len(data) // CHUNK_LEN))
            n_tops = np.int32(-(-n_chunks // self._shard_chunks))
            base = self._windows_done * self._window_chunks
            top = _sharded_reduce(
                words_dev, jnp.asarray(len(data), jnp.int32),
                jnp.asarray(n_tops),
                jnp.asarray(base & 0xFFFFFFFF, jnp.uint32),
                jnp.asarray(base >> 32, jnp.uint32),
                mesh=self._mesh, shard_chunks=self._shard_chunks,
                root=False)
            with jit_registry.io("seqhash.window"):
                return [int(w) for w in np.asarray(top)]

    def _push_window_cv(self, cv: list) -> None:
        from .blake3_ref import BLOCK_LEN as B3_BLOCK, IV, PARENT, compress

        self._windows_done += 1
        # Incremental-stack rule: after w windows, merge one level per
        # trailing zero bit of w.
        w = self._windows_done
        while w % 2 == 0:
            left = self._stack.pop()
            cv = compress(list(IV), left + cv, 0, B3_BLOCK, PARENT)[:8]
            w //= 2
        self._stack.append(cv)

    def digest(self) -> bytes:
        from .blake3_ref import BLOCK_LEN as B3_BLOCK, IV, PARENT, ROOT, compress

        with tracing.phase("validator.phase/pack", VAL_STREAMED_PACK):
            tail = bytes(self._buf)
        if not self._stack:
            # Whole stream fit in one window: single-call ROOT path.
            return make_sharded_checksum(self._mesh, self._shard_chunks)(tail)
        cv = self._window_top(tail)
        # Finalize: fold the stack right-to-left; ROOT on the last parent.
        with tracing.phase("validator.phase/device", VAL_STREAMED_DEVICE):
            for i, left in enumerate(reversed(self._stack)):
                flags = PARENT | (ROOT if i == len(self._stack) - 1 else 0)
                cv = compress(list(IV), left + cv, 0, B3_BLOCK, flags)[:8]
        return b"".join(int(w).to_bytes(4, "little") for w in cv)

    def hexdigest(self) -> str:
        return self.digest().hex()


def make_streaming_checksum(mesh: Mesh,
                            shard_chunks: int = DEFAULT_SHARD_CHUNKS):
    """Returns a fresh StreamingShardedChecksum factory bound to `mesh`."""
    return lambda: StreamingShardedChecksum(mesh, shard_chunks)


def sharded_file_checksum(mesh: Mesh, path: str,
                          shard_chunks: int = DEFAULT_SHARD_CHUNKS) -> str:
    """Full-file checksum (validator semantics, hash.rs:10-24) with the
    chunk chain sequence-sharded across the mesh. Returns 64-hex digest.

    Files larger than one mesh window stream through repeated sharded
    window calls with bounded memory (one window buffered at a time).
    """
    D = int(np.prod(mesh.devices.shape))
    window = D * shard_chunks * CHUNK_LEN
    h = StreamingShardedChecksum(mesh, shard_chunks)
    size = 0
    with open(path, "rb") as f:
        while True:
            with tracing.phase("validator.phase/read", VAL_STREAMED_READ):
                block = f.read(window)
            if not block:
                break
            size += len(block)
            h.update(block)
    VAL_STREAMED_FILES.inc()
    VAL_STREAMED_FILE_BYTES.inc(size)
    return h.hexdigest()
