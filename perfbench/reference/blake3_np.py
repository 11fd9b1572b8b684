"""Plain BLAKE3 in numpy: the benchmark's reference for CAS IDs and checksums.

Written from the BLAKE3 specification (Aumasson et al., "BLAKE3: one
function, fast everywhere", 2020, sections 2.1-2.6) and imports nothing
of the system under test. Many messages are hashed at once: every 1 KiB
chunk of every message is one row, the 16 blocks of a chunk are
compressed in turn over all rows together, and the chunk chaining
values are then merged pairwise, level by level, which gives the same
left-complete tree as the specification's stack.

Spacedrive's two uses of it (core/src/object/cas.rs and
core/src/object/validation/hash.rs):

- CAS ID: blake3(le64(size) || payload), hex, first 16 characters; the
  payload is the whole file up to 100 KiB, else an 8 KiB header, four
  10 KiB samples and an 8 KiB footer.
- integrity checksum: blake3 of the whole file, 64 hex characters.
"""

from __future__ import annotations

import struct
from typing import List, Sequence, Tuple

import numpy as np

IV = (0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
      0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19)
MSG_PERMUTATION = (2, 6, 3, 10, 7, 0, 4, 13, 1, 11, 12, 5, 9, 14, 15, 8)
CHUNK_START, CHUNK_END, PARENT, ROOT = 1, 2, 4, 8
CHUNK_LEN = 1024
BLOCK_LEN = 64

# cas.rs constants
MINIMUM_FILE_SIZE = 100 * 1024
HEADER_OR_FOOTER_SIZE = 8 * 1024
SAMPLE_COUNT = 4
SAMPLE_SIZE = 10 * 1024

_U32 = np.uint32


def _rotr(x: np.ndarray, n: int) -> np.ndarray:
    return (x >> _U32(n)) | (x << _U32(32 - n))


def _g(s: list, a: int, b: int, c: int, d: int, mx, my) -> None:
    s[a] = s[a] + s[b] + mx
    s[d] = _rotr(s[d] ^ s[a], 16)
    s[c] = s[c] + s[d]
    s[b] = _rotr(s[b] ^ s[c], 12)
    s[a] = s[a] + s[b] + my
    s[d] = _rotr(s[d] ^ s[a], 8)
    s[c] = s[c] + s[d]
    s[b] = _rotr(s[b] ^ s[c], 7)


def compress(cv: Sequence[np.ndarray], m: Sequence[np.ndarray],
             counter: np.ndarray, block_len: np.ndarray,
             flags: np.ndarray) -> List[np.ndarray]:
    """The compression function over N rows at once; returns the first
    eight output words (the chaining value, or a root's 32-byte output)."""
    n = counter.shape[0]
    s = [np.asarray(v, _U32) for v in cv]
    s += [np.full(n, IV[i], _U32) for i in range(4)]
    s += [(counter & 0xFFFFFFFF).astype(_U32),
          (counter >> 32).astype(_U32),
          block_len.astype(_U32), flags.astype(_U32)]
    m = list(m)
    for r in range(7):
        _g(s, 0, 4, 8, 12, m[0], m[1])
        _g(s, 1, 5, 9, 13, m[2], m[3])
        _g(s, 2, 6, 10, 14, m[4], m[5])
        _g(s, 3, 7, 11, 15, m[6], m[7])
        _g(s, 0, 5, 10, 15, m[8], m[9])
        _g(s, 1, 6, 11, 12, m[10], m[11])
        _g(s, 2, 7, 8, 13, m[12], m[13])
        _g(s, 3, 4, 9, 14, m[14], m[15])
        if r < 6:
            m = [m[i] for i in MSG_PERMUTATION]
    return [s[i] ^ s[i + 8] for i in range(8)]


def blake3_many(messages: Sequence[bytes]) -> List[bytes]:
    """32-byte BLAKE3 digests of every message."""
    if not messages:
        return []
    lens = np.array([len(b) for b in messages], np.int64)
    n_chunks = np.maximum(1, -(-lens // CHUNK_LEN))
    total = int(n_chunks.sum())
    buf = np.zeros((total, CHUNK_LEN), np.uint8)
    flat = buf.reshape(-1)
    first = np.concatenate([[0], np.cumsum(n_chunks)[:-1]])
    for i, b in enumerate(messages):
        start = int(first[i]) * CHUNK_LEN
        flat[start:start + len(b)] = np.frombuffer(b, np.uint8)
    msg = np.repeat(np.arange(len(messages)), n_chunks)
    counter = (np.arange(total) - first[msg]).astype(np.uint64)
    chunk_len = np.clip(lens[msg] - counter.astype(np.int64) * CHUNK_LEN,
                        0, CHUNK_LEN)
    single = n_chunks[msg] == 1
    n_blocks = np.maximum(1, -(-chunk_len // BLOCK_LEN))
    words = buf.view("<u4").reshape(total, 16, 16)

    cv = [np.full(total, IV[i], _U32) for i in range(8)]
    for blk in range(16):
        rows = np.nonzero(n_blocks > blk)[0]
        if rows.size == 0:
            break
        last = n_blocks[rows] - 1 == blk
        flags = (np.where(blk == 0, CHUNK_START, 0)
                 | np.where(last, CHUNK_END, 0)
                 | np.where(last & single[rows], ROOT, 0))
        block_len = np.clip(chunk_len[rows] - blk * BLOCK_LEN, 0, BLOCK_LEN)
        out = compress([c[rows] for c in cv],
                       [words[rows, blk, j] for j in range(16)],
                       counter[rows], block_len,
                       np.broadcast_to(flags, rows.shape))
        for j in range(8):
            cv[j][rows] = out[j]

    # Parent levels: pair neighbours within a message, carry an odd last.
    node_msg = msg
    while True:
        counts = np.bincount(node_msg, minlength=len(messages))
        if counts.max() <= 1:
            break
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        pos = np.arange(node_msg.size) - starts[node_msg]
        cnt = counts[node_msg]
        left = np.nonzero((pos % 2 == 0) & (pos + 1 < cnt))[0]
        keep = np.nonzero(pos % 2 == 0)[0]
        if left.size:
            flags = PARENT | np.where(cnt[left] == 2, ROOT, 0)
            out = compress([np.full(left.size, IV[i], _U32) for i in range(8)],
                           [c[left] for c in cv] + [c[left + 1] for c in cv],
                           np.zeros(left.size, np.uint64),
                           np.full(left.size, BLOCK_LEN), flags)
            for j in range(8):
                cv[j][left] = out[j]
        cv = [c[keep] for c in cv]
        node_msg = node_msg[keep]
    digests = np.stack(cv, axis=1).astype("<u4")
    return [row.tobytes() for row in digests]


def sample_spec(size: int) -> List[Tuple[int, int]]:
    """(offset, length) ranges of a file that its CAS ID hashes."""
    if size <= MINIMUM_FILE_SIZE:
        return [(0, size)]
    jump = (size - 2 * HEADER_OR_FOOTER_SIZE) // SAMPLE_COUNT
    return ([(0, HEADER_OR_FOOTER_SIZE)]
            + [(HEADER_OR_FOOTER_SIZE + k * jump, SAMPLE_SIZE)
               for k in range(SAMPLE_COUNT)]
            + [(size - HEADER_OR_FOOTER_SIZE, HEADER_OR_FOOTER_SIZE)])


def cas_message(data: bytes) -> bytes:
    """The bytes a file's CAS ID hashes, from the whole file's content."""
    size = len(data)
    return struct.pack("<Q", size) + b"".join(
        data[o:o + n] for o, n in sample_spec(size))


def cas_ids(contents: Sequence[bytes]) -> List[str]:
    return [d.hex()[:16] for d in blake3_many([cas_message(c) for c in contents])]


def checksums(contents: Sequence[bytes]) -> List[str]:
    return [d.hex() for d in blake3_many(contents)]
