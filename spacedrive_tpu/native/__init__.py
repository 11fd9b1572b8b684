"""ctypes bindings to the native I/O plane (native/sdio.cpp → libsdio.so).

The native library supplies the batched file-staging and CPU-hash plane
that the reference implements in Rust (tokio::fs + the blake3 crate,
/root/reference/core/src/object/cas.rs, validation/hash.rs). Every entry
point degrades gracefully: if the shared library is missing and no C++
toolchain is available, `available()` is False and callers fall back to
the pure-Python paths (ops/cas.py, ops/staging.py).

pybind11 is not in this image, so the ABI is plain C over ctypes with
numpy arrays as buffers.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

# Status codes — must match `enum Status` in native/sdio.cpp.
OK = 0
ERR_OPEN = -1
ERR_SHORT_READ = -2
ERR_GREW = -3
ERR_EMPTY = -4
ERR_IO = -5

STATUS_MESSAGES = {
    ERR_OPEN: "cannot open file",
    ERR_SHORT_READ: "short read",
    ERR_GREW: "file grew past its declared size class",
    ERR_EMPTY: "empty file",
    ERR_IO: "I/O error",
}

# Mirrors of the constants baked into native/sdio.cpp; sourced from the
# oracle module so a change there fails loudly here instead of silently
# diverging from the compiled library.
from ..ops.cas import LARGE_PAYLOAD_SIZE as LARGE_PAYLOAD  # noqa: E402
from ..ops.cas import MINIMUM_FILE_SIZE as SMALL_CAP  # noqa: E402

assert LARGE_PAYLOAD == 57344 and SMALL_CAP == 102400, (
    "ops.cas sampling constants diverged from native/sdio.cpp — rebuild "
    "and update the C++ constants together")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _native_dir() -> str:
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "native")


def _host_key() -> str:
    """Build-dir key for this host's CPU. The .so is built with
    -march=native, so a binary built on another CPU (a copied tree, a
    shared disk) can die here with SIGILL instead of raising; keying
    the build dir by the CPU's feature flags makes such a host build
    its own from native/sdio.cpp."""
    import hashlib
    import platform

    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = line
                    break
    except OSError:
        pass
    return hashlib.sha1(
        f"{platform.machine()} {flags}".encode()).hexdigest()[:12]


def _build_subdir() -> str:
    return os.path.join("build", _host_key())


def _lib_path() -> str:
    env = os.environ.get("SD_NATIVE_LIB")
    if env:
        return env
    return os.path.join(_native_dir(), _build_subdir(), "libsdio.so")


def _declare(lib: ctypes.CDLL) -> ctypes.CDLL:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    charpp = ctypes.POINTER(ctypes.c_char_p)

    lib.sd_blake3.argtypes = [u8p, ctypes.c_uint64, u8p]
    lib.sd_blake3.restype = None
    lib.sd_blake3_many.argtypes = [
        ctypes.c_int64, u8p, ctypes.c_int64, i32p, u64p, u8p, ctypes.c_int]
    lib.sd_blake3_many.restype = None
    lib.sd_stage_large.argtypes = [
        ctypes.c_int64, charpp, u64p, u8p, i32p, ctypes.c_int]
    lib.sd_stage_large.restype = None
    lib.sd_stage_small.argtypes = [
        ctypes.c_int64, charpp, ctypes.c_uint64, u8p, i32p, i32p,
        ctypes.c_int]
    lib.sd_stage_small.restype = None
    lib.sd_stage_batch.argtypes = [
        ctypes.c_int64, charpp, u64p, u8p, ctypes.c_int64,
        ctypes.c_uint64, i32p, i32p, ctypes.c_int]
    lib.sd_stage_batch.restype = None
    lib.sd_cas_digests.argtypes = [
        ctypes.c_int64, charpp, u64p, u8p, i32p, ctypes.c_int]
    lib.sd_cas_digests.restype = None
    lib.sd_checksum_files.argtypes = [
        ctypes.c_int64, charpp, u64p, u8p, i32p, ctypes.c_int]
    lib.sd_checksum_files.restype = None
    lib.sd_secure_erase.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.sd_secure_erase.restype = ctypes.c_int32
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.sd_encode_ops.argtypes = [
        ctypes.c_int64, u64p, u8p, ctypes.c_char_p, u8p, u8p, i64p,
        u8p, ctypes.c_int64]
    lib.sd_encode_ops.restype = ctypes.c_int64
    lib.sd_decode_ops.argtypes = [
        u8p, ctypes.c_int64, ctypes.c_int64, u64p, i64p, i32p, i64p,
        i32p, i64p, i64p, i64p, i64p, i64p, u8p]
    lib.sd_decode_ops.restype = ctypes.c_int64
    return lib


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        path = _lib_path()
        if "SD_NATIVE_LIB" not in os.environ:
            # Always run make: its dependency tracking is a ~no-op when
            # the .so is fresh and rebuilds it when sdio.cpp changed
            # (loading a stale binary would silently diverge from the
            # wrapper). Callers that must never block on a cold build
            # warm this up at bootstrap (Node.__init__).
            try:
                subprocess.run(
                    ["make", "-C", _native_dir(),
                     f"BUILD={_build_subdir()}"], check=True,
                    capture_output=True, timeout=120)
            except Exception:
                if not os.path.exists(path):
                    return None
        try:
            _lib = _declare(ctypes.CDLL(path))
        except OSError:
            _lib = None
        return _lib


def available() -> bool:
    return _load() is not None


def _u8(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _i32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _u64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


def _paths_array(paths: Sequence[str]):
    arr = (ctypes.c_char_p * len(paths))()
    arr[:] = [os.fsencode(p) for p in paths]
    return arr


def blake3_digest(data: bytes) -> bytes:
    lib = _load()
    assert lib is not None
    out = np.zeros(32, dtype=np.uint8)
    buf = np.frombuffer(data, dtype=np.uint8) if data else \
        np.zeros(0, dtype=np.uint8)
    lib.sd_blake3(_u8(buf), len(data), _u8(out))
    return out.tobytes()


def blake3_many(payloads: np.ndarray, lens: np.ndarray,
                prefix_sizes: Optional[np.ndarray] = None,
                n_threads: int = 0) -> np.ndarray:
    """Hash each row of a dense [n, stride] uint8 array → [n, 32] digests.

    With `prefix_sizes`, row i hashes le64(prefix_sizes[i]) ‖ row bytes —
    the CAS-ID preimage (cas.rs:33).
    """
    lib = _load()
    assert lib is not None
    payloads = np.ascontiguousarray(payloads, dtype=np.uint8)
    n, stride = payloads.shape
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    out = np.zeros((n, 32), dtype=np.uint8)
    pre = None
    if prefix_sizes is not None:
        pre = np.ascontiguousarray(prefix_sizes, dtype=np.uint64)
    lib.sd_blake3_many(
        n, _u8(payloads), stride, _i32(lens),
        _u64(pre) if pre is not None else None, _u8(out), n_threads)
    return out


def stage_large(paths: Sequence[str], sizes: np.ndarray,
                n_threads: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Sampled reads → ([n, 57344] uint8 payloads, [n] int32 status)."""
    lib = _load()
    assert lib is not None
    n = len(paths)
    sizes = np.ascontiguousarray(sizes, dtype=np.uint64)
    out = np.zeros((n, LARGE_PAYLOAD), dtype=np.uint8)
    status = np.zeros(n, dtype=np.int32)
    if n:
        lib.sd_stage_large(n, _paths_array(paths), _u64(sizes), _u8(out),
                           _i32(status), n_threads)
    return out, status


def stage_small(paths: Sequence[str], cap: int = SMALL_CAP,
                n_threads: int = 0
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Whole-file reads → ([n, cap+1] payloads, [n] lens, [n] status).

    The extra column lets the native side detect files that grew past the
    size class (ERR_GREW); callers slice [:, :cap].
    """
    lib = _load()
    assert lib is not None
    n = len(paths)
    out = np.zeros((n, cap + 1), dtype=np.uint8)
    lens = np.zeros(n, dtype=np.int32)
    status = np.zeros(n, dtype=np.int32)
    if n:
        lib.sd_stage_small(n, _paths_array(paths), cap, _u8(out),
                           _i32(lens), _i32(status), n_threads)
    return out, lens, status


def stage_batch(paths: Sequence[str], sizes: np.ndarray,
                out: np.ndarray, payload_cap: int,
                n_threads: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Packed batched staging into a CALLER-OWNED [n, stride] uint8
    buffer (a pooled, page-aligned ring page): row i becomes
    le64(size) ‖ payload ‖ zeros — build_cas_messages' exact layout,
    written by the C plane with no intermediate Python bytes objects.

    `out` must be C-contiguous with stride a 1024 multiple covering
    8 + payload_cap (plus the +1 grew-detection byte for small rows —
    the chunk grid always leaves >= 1016 bytes of padding, so any
    conforming grid qualifies). Returns ([n] int32 msg_lens — the
    kernel's `lengths` operand — and [n] int32 status); non-OK rows
    are scrubbed to their 8-byte prefix for per-file fallback at the
    staging seam."""
    lib = _load()
    assert lib is not None
    n = len(paths)
    if out.ndim != 2 or out.dtype != np.uint8 or out.shape[0] < n or \
            not out.flags.c_contiguous:
        # A real exception, not an assert: a mis-shaped buffer would
        # let the C writer scribble past the pooled page.
        raise ValueError(
            f"stage_batch: out must be C-contiguous uint8 [>= {n}, "
            f"stride], got {out.dtype} {out.shape}")
    stride = int(out.shape[1])
    if stride < 8 + int(payload_cap) + 1 or stride % 1024:
        raise ValueError(
            f"stage_batch: stride {stride} cannot hold the {payload_cap}"
            "-byte payload class (+ prefix and grew byte) on the chunk "
            "grid")
    sizes = np.ascontiguousarray(sizes, dtype=np.uint64)
    msg_lens = np.zeros(n, dtype=np.int32)
    status = np.zeros(n, dtype=np.int32)
    if n:
        lib.sd_stage_batch(n, _paths_array(paths), _u64(sizes), _u8(out),
                           stride, payload_cap, _i32(msg_lens),
                           _i32(status), n_threads)
    return msg_lens, status


def cas_digests(paths: Sequence[str], sizes: np.ndarray,
                n_threads: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Fused stage+hash: ([n, 32] digests, [n] status). ERR_EMPTY marks
    empty files (no CAS ID)."""
    lib = _load()
    assert lib is not None
    n = len(paths)
    sizes = np.ascontiguousarray(sizes, dtype=np.uint64)
    digests = np.zeros((n, 32), dtype=np.uint8)
    status = np.zeros(n, dtype=np.int32)
    if n:
        lib.sd_cas_digests(n, _paths_array(paths), _u64(sizes),
                           _u8(digests), _i32(status), n_threads)
    return digests, status


def checksum_files(paths: Sequence[str],
                   n_threads: int = 0,
                   sizes_hint: Optional[np.ndarray] = None,
                   ) -> Tuple[List[Optional[str]], np.ndarray]:
    """Full-file BLAKE3 checksums → ([n] hex-or-None, [n] status).

    `sizes_hint` (DB-known sizes) routes small files to the batched
    cross-file SIMD path without a stat sweep; it only partitions —
    stale hints re-route at read time, digests never depend on it."""
    lib = _load()
    assert lib is not None
    n = len(paths)
    digests = np.zeros((n, 32), dtype=np.uint8)
    status = np.zeros(n, dtype=np.int32)
    if n:
        hint = None
        if sizes_hint is not None:
            hint = _u64(np.ascontiguousarray(sizes_hint, dtype=np.uint64))
        lib.sd_checksum_files(n, _paths_array(paths), hint, _u8(digests),
                              _i32(status), n_threads)
    hexes: List[Optional[str]] = [
        digests[i].tobytes().hex() if status[i] == OK else None
        for i in range(n)
    ]
    return hexes, status


def encode_ops(timestamps, record_ids, kind: str, op_ids,
               values_packed) -> bytes:
    """Batched op-log blob encoding (sync/opblob.py format): n ops of
    one uniform `kind`, 16-byte record/op ids, values pre-packed per
    op. Returns the msgpack blob bytes — byte-identical to the Python
    fragment encoder (opblob.encode_uniform_py)."""
    lib = _load()
    assert lib is not None
    n = len(op_ids)
    if n == 0:
        return b"\x90"  # empty msgpack array
    ts = np.fromiter(timestamps, dtype=np.uint64, count=n)
    rids = np.frombuffer(b"".join(record_ids), dtype=np.uint8)
    oids = np.frombuffer(b"".join(op_ids), dtype=np.uint8)
    if rids.size != 16 * n or oids.size != 16 * n:
        # Same hardening as the cap check below: under `python -O` an
        # assert would vanish and the C encoder would read shifted
        # bytes, minting a structurally valid blob with WRONG record
        # ids — silent op-log corruption.
        raise ValueError(
            f"encode_ops: record/op ids must be 16 bytes each "
            f"(got {rids.size}/{oids.size} bytes for n={n})")
    vbuf = b"".join(values_packed)
    offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(v) for v in values_packed], out=offs[1:])
    vals = (np.frombuffer(vbuf, dtype=np.uint8) if vbuf
            else np.zeros(1, dtype=np.uint8))
    kindb = kind.encode("utf-8")
    cap = 64 + n * (48 + len(kindb) + 70) + len(vbuf)
    out = np.zeros(cap, dtype=np.uint8)
    written = lib.sd_encode_ops(
        n, _u64(ts), _u8(rids), kindb, _u8(oids), _u8(vals),
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), _u8(out),
        cap)
    if written <= 0:
        # A real exception, not an assert: under `python -O` an assert
        # would vanish and a truncated garbage blob would land in the
        # op log — permanent sync corruption, not a crash.
        raise RuntimeError(
            f"sd_encode_ops: output buffer undersized (cap={cap}, n={n})")
    return out[:written].tobytes()


def _blob_entry_count(data: bytes) -> int:
    """Entry count from the blob's msgpack array header (the encoders
    emit exactly fixarray / array16 / array32)."""
    if not data:
        raise ValueError("decode_ops: empty blob")
    t = data[0]
    if t & 0xF0 == 0x90:
        return t & 0x0F
    if t == 0xDC:
        return int.from_bytes(data[1:3], "big")
    if t == 0xDD:
        return int.from_bytes(data[1:5], "big")
    raise ValueError(f"decode_ops: not an op blob (leading byte {t:#x})")


def decode_ops(data: bytes):
    """Batched blob decode (sync/opblob.py format): one C call parses a
    whole shared_op_blob page into dense offset arrays over `data` —
    no per-op msgpack objects. Returns
    (n, ts, rid_off, rid_len, kind_off, kind_len, payload_off,
    payload_len, opid_off, values_off, values_len, flags) where flags
    bit0 marks a uniform bulk payload (opid/values offsets valid) and
    bit1 the update shape. Raises ValueError on malformed input —
    callers (opblob.decode_entries) fall back to the Python decoder."""
    lib = _load()
    assert lib is not None
    n = _blob_entry_count(data)
    if 7 * n > len(data):
        # The header's count is WIRE-CONTROLLED (a blob_page frame from
        # a paired peer): allocating the offset arrays before this
        # check would let a 5-byte b"\xdd\xff\xff\xff\xff" frame force
        # tens of GB of np.zeros. Every real entry costs ≥7 bytes
        # (fixarray4 + ts + empty bin rid + empty fixstr + empty bin).
        raise ValueError(
            f"decode_ops: header claims {n} entries in {len(data)} bytes")
    buf = (np.frombuffer(data, dtype=np.uint8) if data
           else np.zeros(1, dtype=np.uint8))
    ts = np.zeros(max(n, 1), dtype=np.uint64)
    rid_off = np.zeros(max(n, 1), dtype=np.int64)
    rid_len = np.zeros(max(n, 1), dtype=np.int32)
    kind_off = np.zeros(max(n, 1), dtype=np.int64)
    kind_len = np.zeros(max(n, 1), dtype=np.int32)
    payload_off = np.zeros(max(n, 1), dtype=np.int64)
    payload_len = np.zeros(max(n, 1), dtype=np.int64)
    opid_off = np.zeros(max(n, 1), dtype=np.int64)
    values_off = np.zeros(max(n, 1), dtype=np.int64)
    values_len = np.zeros(max(n, 1), dtype=np.int64)
    flags = np.zeros(max(n, 1), dtype=np.uint8)
    i64 = ctypes.POINTER(ctypes.c_int64)
    got = lib.sd_decode_ops(
        _u8(buf), len(data), n, _u64(ts),
        rid_off.ctypes.data_as(i64), _i32(rid_len),
        kind_off.ctypes.data_as(i64), _i32(kind_len),
        payload_off.ctypes.data_as(i64),
        payload_len.ctypes.data_as(i64),
        opid_off.ctypes.data_as(i64), values_off.ctypes.data_as(i64),
        values_len.ctypes.data_as(i64), _u8(flags))
    if got != n:
        # A real exception (never an assert — see encode_ops): a
        # malformed page must route to the tolerant Python decoder,
        # not yield a truncated op stream.
        raise ValueError(f"sd_decode_ops: malformed blob (rc={got})")
    return (n, ts, rid_off, rid_len, kind_off, kind_len, payload_off,
            payload_len, opid_off, values_off, values_len, flags)


def secure_erase(path: str, passes: int = 1) -> None:
    lib = _load()
    assert lib is not None
    rc = lib.sd_secure_erase(os.fsencode(path), passes)
    if rc != OK:
        raise OSError(
            f"secure_erase({path!r}): "
            f"{STATUS_MESSAGES.get(rc, f'status {rc}')}")
