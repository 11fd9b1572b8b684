"""Central jax.jit contract registry — the device twin of flags.py.

Every `jax.jit` entry point in the tree DECLARES its contract here:
how many traces it is allowed (shape buckets × static-arg combos),
which argnames are static, which dtypes cross the boundary, and
whether its results are expected to transfer back to the host. The
promise `ops/staging.py` makes in prose ("two compiled shapes only")
becomes machine-checked in two halves:

- **statically** — tools/sdlint's `jit-stability` / `dtype-discipline`
  / `host-transfer` passes parse the `declare_jit(...)` calls below
  (AST, same as the flag-registry pass) and fail the build on
  undeclared jit sites, call-time `jax.jit(fn)` construction outside a
  declared factory, static-arg drift, and stray D2H transfers outside
  a declared `io(...)` scope;
- **at runtime** — `tracked(name)` wraps the jitted callable and
  counts retraces (jit cache growth) against the declared budget into
  `sd_jit_retraces_total{fn}` / `sd_jit_cache_size{fn}`, and
  `device_scope()` / `io(name)` arm JAX's device-to-host transfer
  guard (raise mode in tier-1, log mode in production — the same
  split as sanitize.py, which arms this module at install()).

Design constraints (same as flags.py / telemetry.py): pure stdlib at
import time — `jax` is imported lazily and ONLY when a guard scope is
actually armed, so every layer (including jax-free hosts running the
numpy backends) can import this module.
"""

from __future__ import annotations

import functools
import threading
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, Optional, Tuple

from .. import flags
from ..telemetry import JIT_CACHE_SIZE, JIT_DECLARED_TRANSFERS, JIT_RETRACES

__all__ = [
    "JitContract", "CONTRACTS", "declare_jit", "tracked", "io",
    "device_scope", "arm", "disarm", "armed", "trace_counts",
    "temporary_contract",
]


@dataclass(frozen=True)
class JitContract:
    """One jit entry point's declared behavior.

    `site` is the `relpath::qualname` of the definition — sdlint uses
    it to associate factory functions (which construct their jit at
    call time) with their declaration. `max_traces` is a PROCESS-WIDE
    budget across every instance the site ever creates: exceeding it
    means the canonical-shape promise broke (a silent retrace storm),
    which is a sanitizer violation in raise mode.
    """

    name: str                      # short dotted id ("blake3.jnp")
    site: str                      # "spacedrive_tpu/ops/x.py::qual"
    kind: str = "entry"            # "entry" | "factory" | "wrapper"
    max_traces: int = 8
    static_argnames: Tuple[str, ...] = ()
    in_dtypes: Tuple[str, ...] = ()
    out_dtypes: Tuple[str, ...] = ()
    shape_buckets: str = ""        # the canonical-grid policy, prose
    host_transfer: bool = False    # results fetched via io(name)
    # Positional args this entry point may CONSUME (jax donate_argnums):
    # a donated buffer is invalid after the call — callers must treat
    # it as moved, which is why donation is part of the declared
    # contract surface (the sdlint jit-stability pass fails the build
    # on a jit site donating argnums its contract does not declare).
    # Declaring donation does not force it: sites may bind undonated
    # variants of the same contract (SDTPU_DONATE_BUFFERS=off).
    donate_argnums: Tuple[int, ...] = ()


CONTRACTS: Dict[str, JitContract] = {}


def declare_jit(name: str, site: str, *, kind: str = "entry",
                max_traces: int = 8,
                static_argnames: Tuple[str, ...] = (),
                in_dtypes: Tuple[str, ...] = (),
                out_dtypes: Tuple[str, ...] = (),
                shape_buckets: str,
                host_transfer: bool = False,
                donate_argnums: Tuple[int, ...] = ()) -> JitContract:
    if name in CONTRACTS:
        raise ValueError(f"jit contract {name!r} declared twice")
    if kind not in ("entry", "factory", "wrapper"):
        raise ValueError(f"{name}: unknown contract kind {kind!r}")
    if not shape_buckets.strip():
        raise ValueError(
            f"{name}: every contract must state its shape-bucket "
            f"policy (what keeps the compiled-program count bounded)")
    c = JitContract(name, site, kind, max_traces,
                    tuple(static_argnames), tuple(in_dtypes),
                    tuple(out_dtypes), shape_buckets, host_transfer,
                    tuple(donate_argnums))
    CONTRACTS[name] = c
    return c


# -- runtime arming ---------------------------------------------------------
# sanitize.install() arms this module with its mode and its violation
# recorder; the callback indirection keeps the import graph acyclic
# (ops code imports this module, this module never imports sanitize).

_armed = False
_mode = "count"
_record: Optional[Callable[[str, str, bool], None]] = None
_trace_lock = threading.Lock()
_traces: Dict[str, int] = {}


def arm(mode: str, record: Callable[[str, str, bool], None]) -> None:
    global _armed, _mode, _record
    _mode = mode
    _record = record
    _armed = True


def disarm() -> None:
    global _armed, _record
    _armed = False
    _record = None


def armed() -> bool:
    return _armed


def trace_counts() -> Dict[str, int]:
    """Process-wide trace counts per contract name (diagnostics; the
    same numbers live in sd_jit_cache_size{fn}). There is deliberately
    no reset: counts mirror the live jit caches, and a reset would
    desync them from the per-wrapper cache-size watermarks — benches
    that want per-run deltas snapshot this dict and subtract."""
    with _trace_lock:
        return dict(_traces)


def _retrace_guard_on() -> bool:
    return _armed and flags.get("SDTPU_RETRACE_GUARD") != "off"


def _transfer_guard_level() -> Optional[str]:
    """jax transfer-guard level for device scopes, or None when off.
    `auto` follows the sanitizer mode: disallow under raise (tier-1),
    log under count (production)."""
    if not _armed:
        return None
    mode = flags.get("SDTPU_TRANSFER_GUARD")
    if mode == "off":
        return None
    if mode == "auto":
        return "disallow" if _mode == "raise" else "log"
    return {"raise": "disallow", "log": "log"}.get(mode)


# -- retrace counting -------------------------------------------------------

def _note_traces(contract: JitContract, state: dict, jitted) -> None:
    size_fn = getattr(jitted, "_cache_size", None)
    if size_fn is None:
        return
    try:
        size = size_fn()
    except Exception:
        return
    # One lock covers the wrapper's cache-size watermark AND the
    # global count: two threads observing the same compile must
    # account it once, not once each (the sanitizer cannot afford its
    # own data race — a double-counted delta is a spurious budget
    # violation in raise mode).
    with _trace_lock:
        delta = size - state["last"]
        if delta <= 0:
            return
        state["last"] = size
        _traces[contract.name] = _traces.get(contract.name, 0) + delta
        total = _traces[contract.name]
    JIT_RETRACES.labels(fn=contract.name).inc(delta)
    JIT_CACHE_SIZE.labels(fn=contract.name).set(total)
    if total > contract.max_traces and _record is not None:
        _record(
            "jit_retrace_budget",
            f"{contract.name}: {total} traces exceed the declared "
            f"budget of {contract.max_traces} (site {contract.site}; "
            f"a shape/static-arg reached the boundary outside the "
            f"canonical buckets: {contract.shape_buckets})",
            True)


def tracked(name: str):
    """Decorator binding a jitted callable to its declared contract.

    Wraps the function so every call, when the sanitizer armed this
    module, diffs the jit cache size and accounts new traces against
    the contract's budget. Disarmed cost: one module-global check per
    call — noise next to a device dispatch. The raw jitted callable
    stays reachable as `.__wrapped__` (functools.wraps)."""
    contract = CONTRACTS.get(name)
    if contract is None:
        raise KeyError(
            f"undeclared jit contract {name!r} (declare it in "
            f"spacedrive_tpu/ops/jit_registry.py)")

    def deco(fn):
        state = {"last": 0}

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            out = fn(*args, **kwargs)
            if _armed and _retrace_guard_on():
                _note_traces(contract, state, fn)
            return out

        wrapper._sdtpu_jit_contract = contract
        return wrapper

    return deco


# -- transfer guard scopes --------------------------------------------------

@contextmanager
def device_scope(label: str = "") -> Iterator[None]:
    """Guarded region around a device pipeline: inside it, an
    UNDECLARED device-to-host transfer (np.asarray on a live device
    value, implicit bool/float, .item()) is a sanitizer violation —
    raise mode raises at the transfer point (JAX's guard error), count
    mode logs. Declared fetches open an `io(name)` scope inside.

    Host-to-device stays unguarded: inputs are expected to stream in
    (device_put or implicit) — the discipline this enforces is about
    RESULTS leaking back mid-pipeline.

    Mode split: `disallow` under raise mode records + raises at the
    transfer point; count mode can only arm JAX's `log` level — the
    guard has no hook short of raising, so production detections
    surface as JAX transfer-guard log lines, not counters (the
    sd_sanitize host_transfer counter increments only on the raising
    path). Retrace budgets, by contrast, count in BOTH modes."""
    level = _transfer_guard_level()
    if level is None:
        yield
        return
    try:
        import jax
    except ImportError:
        # jax-free host running the numpy backends: nothing to guard.
        yield
        return

    try:
        with jax.transfer_guard_device_to_host(level):
            yield
    except Exception as e:
        msg = str(e).lower()
        # Match the guard's own error shape ("Disallowed host-to-device
        # transfer: ..."), not any app error that mentions transfers.
        if "disallow" in msg and "transfer" in msg and _record is not None:
            # Record for telemetry/violations(), then let the original
            # error surface — in raise mode the test sees the real
            # guard error with the offending line in its traceback.
            _record(
                "host_transfer",
                f"undeclared D2H transfer in device scope "
                f"{label or '?'}: {e}",
                False)
        raise


@contextmanager
def io(name: str) -> Iterator[None]:
    """A DECLARED host-transfer point: the contract `name` must exist
    with host_transfer=True. Inside, the D2H guard is lifted (the
    fetch is part of the entry point's declared surface) and the
    transfer is counted into sd_jit_declared_transfers_total{fn}.
    Opening an io scope for an undeclared contract is itself a
    violation — the registry stays authoritative."""
    contract = CONTRACTS.get(name)
    if contract is None or not contract.host_transfer:
        if _armed and _record is not None:
            _record(
                "host_transfer",
                f"io({name!r}): not a declared host-transfer contract "
                f"(declare it with host_transfer=True in "
                f"spacedrive_tpu/ops/jit_registry.py)",
                True)
        yield
        return
    if _armed:
        JIT_DECLARED_TRANSFERS.labels(fn=name).inc()
    if _transfer_guard_level() is None:
        yield
        return
    try:
        import jax
    except ImportError:
        yield
        return

    with jax.transfer_guard_device_to_host("allow"):
        yield


@contextmanager
def temporary_contract(name: str, **kwargs) -> Iterator[JitContract]:
    """Declare a contract for the duration of a with-block (tests)."""
    kwargs.setdefault("shape_buckets", "test-local")
    c = declare_jit(name, kwargs.pop("site", f"test::{name}"), **kwargs)
    try:
        yield c
    finally:
        CONTRACTS.pop(name, None)
        with _trace_lock:
            _traces.pop(name, None)


# ---------------------------------------------------------------------------
# THE jit namespace. Keep grouped by module; every entry is enforced by
# the sdlint jit-stability pass (undeclared jit sites fail the build)
# and, when the sanitizer is armed, by the retrace counter at runtime.
# max_traces budgets are process-wide ceilings sized from a full
# sanitized tier-1 run (which exercises far more shapes than any
# production pipeline) plus headroom; the canonical production shape
# count per entry is what `shape_buckets` documents.
# ---------------------------------------------------------------------------

declare_jit(
    "blake3.jnp", "spacedrive_tpu/ops/blake3_jax.py::_blake3_jnp_jit",
    max_traces=96, in_dtypes=("uint32", "int32"), out_dtypes=("uint32",),
    shape_buckets="canonical CAS grids [B,57,256] / [B,101,256] with B "
                  "pow2-bucketed by _bucket_b; checksum grids pad C to "
                  "pow2 (tests add oracle-parity odd shapes)")

declare_jit(
    "blake3.sharded", "spacedrive_tpu/ops/blake3_jax.py::make_sharded_blake3",
    kind="factory", max_traces=16,
    in_dtypes=("uint32", "int32"), out_dtypes=("uint32",),
    shape_buckets="one mesh per process (sharded_hasher caches); same "
                  "pow2 B buckets as blake3.jnp, shards = devices")

declare_jit(
    "blake3.donated", "spacedrive_tpu/ops/blake3_jax.py::_donated_best",
    max_traces=96, in_dtypes=("uint32", "int32"), out_dtypes=("uint32",),
    donate_argnums=(0, 1),
    shape_buckets="same canonical CAS grids as blake3.jnp; the donated "
                  "twin cas_ids_jax dispatches when SDTPU_DONATE_BUFFERS "
                  "is on — inputs are consumed (identity pass-through "
                  "outputs alias them), so each CAS batch's staged "
                  "device copy is recycled at kernel completion instead "
                  "of surviving until the digest fetch")

declare_jit(
    "cas.ids", "spacedrive_tpu/ops/blake3_jax.py::cas_ids_jax",
    kind="wrapper", host_transfer=True,
    out_dtypes=("str",),
    shape_buckets="delegates to blake3.jnp buckets; CAS IDs are host "
                  "strings — the D2H fetch is this wrapper's contract")

declare_jit(
    "cas.checksums",
    "spacedrive_tpu/ops/blake3_jax.py::checksums_words_batched",
    kind="wrapper", host_transfer=True,
    out_dtypes=("str",),
    shape_buckets="pow2 chunk grids, B pow2-bucketed; hex digests are "
                  "host strings — the D2H fetch is this wrapper's "
                  "contract")

declare_jit(
    "blake3.pallas.chunk_fast",
    "spacedrive_tpu/ops/blake3_pallas.py::_chunk_cvs_pallas_fast",
    max_traces=96, static_argnames=("interpret",),
    in_dtypes=("uint32", "int32"), out_dtypes=("uint32",),
    shape_buckets="same canonical CAS grids as blake3.jnp (TPU-only "
                  "fast path; interpret=True only in tests)")

declare_jit(
    "blake3.pallas.chunk",
    "spacedrive_tpu/ops/blake3_pallas.py::_chunk_cvs_pallas",
    max_traces=96, static_argnames=("interpret",),
    in_dtypes=("uint32", "int32", "bool"), out_dtypes=("uint32",),
    shape_buckets="counter-base variant of blake3.pallas.chunk_fast "
                  "(seqhash windows: one fixed window grid per mesh)")

declare_jit(
    "blake3.pallas.words",
    "spacedrive_tpu/ops/blake3_pallas.py::blake3_words_pallas",
    max_traces=96, static_argnames=("interpret",),
    in_dtypes=("uint32", "int32"), out_dtypes=("uint32",),
    shape_buckets="same canonical CAS grids as blake3.jnp (chunk stage "
                  "+ tree reduce fused in one program)")

declare_jit(
    "hamming.tile", "spacedrive_tpu/ops/hamming.py::hamming_tile",
    max_traces=32, in_dtypes=("uint32",), out_dtypes=("int32",),
    shape_buckets="[n,W]x[m,W] probe tiles; production uses the fixed "
                  "4096 tile, tests add small parity shapes")

declare_jit(
    "hamming.near_mask", "spacedrive_tpu/ops/hamming.py::_near_mask_tile",
    max_traces=32, static_argnames=("threshold",),
    in_dtypes=("uint32",), out_dtypes=("bool",),
    shape_buckets="one-tile batches (N <= tile); threshold static by "
                  "design (tiny int domain)")

declare_jit(
    "hamming.tile_counts",
    "spacedrive_tpu/ops/hamming.py::_tile_counts_block",
    max_traces=16, static_argnames=("block",),
    in_dtypes=("bfloat16", "int32"), out_dtypes=("int32",),
    shape_buckets="row0/threshold/n are traced scalars — one program "
                  "per (tile grid, block) pair, block fixed at "
                  "COUNT_ROWS_PER_DISPATCH")

declare_jit(
    "hamming.refine", "spacedrive_tpu/ops/hamming.py::_refine_counts",
    max_traces=16, static_argnames=("size", "sub"),
    in_dtypes=("bfloat16", "int32"), out_dtypes=("int32",),
    shape_buckets="coords padded to pow2 per dispatch (run_level), "
                  "size walks tile -> REFINE_SUB in fixed /16 steps")

declare_jit(
    "hamming.leaf_masks", "spacedrive_tpu/ops/hamming.py::_leaf_masks",
    max_traces=16, static_argnames=("size",),
    in_dtypes=("bfloat16", "int32"), out_dtypes=("uint8",),
    shape_buckets="coords padded to pow2 per dispatch, size fixed at "
                  "REFINE_SUB by the pyramid walk")

declare_jit(
    "hamming.sharded", "spacedrive_tpu/ops/hamming.py::make_sharded_hamming",
    kind="factory", max_traces=16,
    in_dtypes=("uint32",), out_dtypes=("int32",),
    shape_buckets="one program per (mesh, digest grid); callers build "
                  "one sharded fn per mesh and reuse it")

declare_jit(
    "hamming.pyramid", "spacedrive_tpu/ops/hamming.py::make_sharded_pyramid",
    kind="factory", max_traces=16,
    in_dtypes=("bfloat16", "int32"), out_dtypes=("int32",),
    shape_buckets="counts + refine stages per mesh; same pow2 coord "
                  "padding as the single-device pyramid")

declare_jit(
    "hamming.pairs", "spacedrive_tpu/ops/hamming.py::near_dup_pairs_device",
    kind="wrapper", host_transfer=True,
    out_dtypes=("int64",),
    shape_buckets="bounded dispatch count (pyramid levels), pair "
                  "coordinates are host output — D2H declared here")

declare_jit(
    "seqhash.reduce", "spacedrive_tpu/ops/seqhash.py::_sharded_reduce",
    max_traces=32, static_argnames=("mesh", "shard_chunks", "root"),
    in_dtypes=("uint32", "int32"), out_dtypes=("uint32",),
    shape_buckets="one fixed window grid per (mesh, shard_chunks); "
                  "root True/False doubles it; meshes cached in "
                  "parallel/mesh.py so equal device sets reuse one "
                  "program")

declare_jit(
    "seqhash.window", "spacedrive_tpu/ops/seqhash.py::StreamingShardedChecksum",
    kind="wrapper", host_transfer=True,
    out_dtypes=("uint32",),
    shape_buckets="window tops and digests are 8-word fetches — the "
                  "D2H per window is this wrapper's contract")

declare_jit(
    "phash.batch", "spacedrive_tpu/ops/phash.py::phash_jax",
    kind="factory", max_traces=16, host_transfer=True,
    in_dtypes=("float32",), out_dtypes=("bool",),
    shape_buckets="[B,32,32] grids, one trace per distinct B (callers "
                  "batch whole decode sets); bit fetch declared")

declare_jit(
    "staging.h2d_probe", "spacedrive_tpu/ops/staging.py::h2d_gbps",
    kind="wrapper", host_transfer=True,
    shape_buckets="one 8 MiB probe buffer, once per process (disk "
                  "cached); the round-trip fetch IS the measurement")
