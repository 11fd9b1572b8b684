"""Device-side duplicate analytics: exact-dup grouping and Hamming all-pairs.

The reference detects duplicates only by exact CAS-ID equality
(/root/reference/core/src/object/file_identifier/mod.rs:167-225); there is
no perceptual near-dup search anywhere in it. This module supplies both:

- `exact_dup_groups`: batch grouping of equal digests (the device analog
  of the identifier's cas_id matching, used by the dedup pass over 100k+
  libraries).
- Hamming all-pairs over bit-digests (pHash near-dup search — net-new
  capability per BASELINE.json): XOR + popcount, tiled so the N×N
  comparison streams through fixed-size blocks, with a shard_map layout
  that puts row-blocks on one mesh axis and column-blocks on the other so
  each device computes an [N/r, N/c] tile with no replication of the
  full matrix.

Digests are [N, W] uint32 grids (W=2 for 64-bit pHash / CAS prefixes,
W=8 for full 256-bit BLAKE3).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import jit_registry


@jit_registry.tracked("hamming.tile")
@jax.jit
def hamming_tile(x, y):
    """[n, W] × [m, W] uint32 → [n, m] int32 Hamming distances."""
    xors = x[:, None, :] ^ y[None, :, :]
    return jnp.sum(jax.lax.population_count(xors), axis=-1).astype(jnp.int32)


def make_sharded_hamming(mesh):
    """All-pairs Hamming over a 2-D (rows, cols) mesh.

    The same digest array is passed twice — once sharded by rows, once by
    cols — so each device holds two 1/r- and 1/c-sized slices and emits
    its tile of the distance matrix; no device ever sees the full N×N.
    """

    @jit_registry.tracked("hamming.sharded")
    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("rows", None), P("cols", None)),
        out_specs=P("rows", "cols"),
    )
    def sharded(x_rows, y_cols):
        xors = x_rows[:, None, :] ^ y_cols[None, :, :]
        return jnp.sum(
            jax.lax.population_count(xors), axis=-1
        ).astype(jnp.int32)

    return sharded


@jit_registry.tracked("hamming.near_mask")
@functools.partial(jax.jit, static_argnames=("threshold",))
def _near_mask_tile(x, y, threshold: int):
    return hamming_tile(x, y) <= threshold


def near_dup_pairs(
    digests: np.ndarray,
    threshold: int,
    tile: int = 4096,
    stats: Optional[Dict[str, int]] = None,
) -> List[Tuple[int, int]]:
    """All (i < j) index pairs with Hamming distance ≤ threshold.

    Exact up to the MAX_TOTAL_PAIRS output budget: degenerate
    near-identical clusters past ~4M qualifying pairs are truncated by
    the multi-tile sweep, and `stats["truncated_pairs"]` (when a dict is
    passed) records how many were dropped so job reports can surface it.

    One-tile batches run as a single masked call; anything larger
    delegates to the two-pass tiled sweep (`near_dup_pairs_device`),
    which keeps the whole tile grid inside one jit instead of paying a
    dispatch round trip per tile (325 tiles at 100k digests).
    """
    digests = np.ascontiguousarray(digests, dtype=np.uint32)
    N = digests.shape[0]
    if N <= tile:
        with jit_registry.device_scope("hamming.pairs"):
            dev_mask = _near_mask_tile(digests, digests, threshold)
            with jit_registry.io("hamming.pairs"):
                mask = np.triu(np.asarray(dev_mask), k=1)
        ii, jj = np.nonzero(mask)
        return list(zip(ii.tolist(), jj.tolist()))
    return near_dup_pairs_device(digests, threshold, tile=tile, stats=stats)


def exact_dup_groups(ids: List[str]) -> Dict[str, List[int]]:
    """Group indexes by identical id; only groups with >1 member.

    The host-side exact pass (id strings are 16-hex CAS IDs). For large
    batches the heavy lifting — the digests themselves — already happened
    on device; grouping N short strings is O(N) dict work.
    """
    groups: Dict[str, List[int]] = {}
    for i, cid in enumerate(ids):
        groups.setdefault(cid, []).append(i)
    return {k: v for k, v in groups.items() if len(v) > 1}


def phash_bands(digests: np.ndarray, n_bands: int = 4) -> Dict[tuple, List[int]]:
    """LSH banding: split each digest into bands; near-dups (small Hamming
    distance) collide in at least one band with high probability.

    Fully vectorized (VERDICT r1 item 6): per band, the byte-slice is
    zero-extended into a uint64 key, grouped with one argsort + boundary
    scan — no per-row Python. Returns {(band, key): [indexes]} for
    buckets with > 1 member.
    """
    digests = np.ascontiguousarray(digests, dtype=np.uint32)
    N, W = digests.shape
    bits = digests.view(np.uint8).reshape(N, W * 4)
    per = max(1, (W * 4) // n_bands)
    assert per <= 8, "band wider than a uint64 key; raise n_bands"
    buckets: Dict[tuple, List[int]] = {}
    for b in range(n_bands):
        band = bits[:, b * per : (b + 1) * per]
        keys = np.zeros((N, 8), dtype=np.uint8)
        keys[:, : band.shape[1]] = band
        keys = keys.view("<u8").ravel()
        order = np.argsort(keys, kind="stable")
        sk = keys[order]
        # Boundaries of equal-key runs; keep runs of length > 1.
        starts = np.flatnonzero(np.r_[True, sk[1:] != sk[:-1]])
        ends = np.r_[starts[1:], N]
        for s, e in zip(starts, ends):
            if e - s > 1:
                buckets[(b, int(sk[s]))] = order[s:e].tolist()
    return buckets


def lsh_candidate_pairs(digests: np.ndarray, n_bands: int = 4,
                        max_bucket: int = 4096) -> np.ndarray:
    """Unique candidate (i < j) pairs from LSH banding, as an [P, 2] array.

    Buckets larger than `max_bucket` (degenerate keys — e.g. thousands of
    identical digests) are truncated to their first `max_bucket` members
    to bound P at O(sum k²); truncated members still pair with the kept
    ones, and identical digests are exact dups the CAS pass already
    catches.
    """
    out = []
    for (_, _), idxs in phash_bands(digests, n_bands).items():
        k = min(len(idxs), max_bucket)
        a = np.asarray(idxs[:k], dtype=np.int64)
        ii, jj = np.triu_indices(k, k=1)
        lo = np.minimum(a[ii], a[jj])
        hi = np.maximum(a[ii], a[jj])
        out.append(np.stack([lo, hi], axis=1))
    if not out:
        return np.zeros((0, 2), dtype=np.int64)
    pairs = np.concatenate(out, axis=0)
    # Dedup across bands: pack to one uint64 key per pair.
    packed = (pairs[:, 0].astype(np.uint64) << np.uint64(32)) \
        | pairs[:, 1].astype(np.uint64)
    packed = np.unique(packed)
    return np.stack([(packed >> np.uint64(32)).astype(np.int64),
                     (packed & np.uint64(0xFFFFFFFF)).astype(np.int64)],
                    axis=1)


def pair_distances(digests: np.ndarray, pairs: np.ndarray,
                   chunk: int = 1 << 20) -> np.ndarray:
    """Hamming distance for each (i, j) row of `pairs` — vectorized
    XOR + popcount in bounded chunks, [P] int32."""
    digests = np.ascontiguousarray(digests, dtype=np.uint32)
    out = np.zeros((len(pairs),), dtype=np.int32)
    for s in range(0, len(pairs), chunk):
        p = pairs[s : s + chunk]
        x = digests[p[:, 0]] ^ digests[p[:, 1]]
        out[s : s + chunk] = np.bitwise_count(x).sum(axis=1)
    return out


def near_dup_pairs_lsh(digests: np.ndarray, threshold: int,
                       n_bands: int = 4) -> List[Tuple[int, int]]:
    """CPU fallback for beyond-all-pairs scale: LSH candidates + one
    vectorized distance pass. Probabilistic recall — a pair at distance
    d ≤ threshold is found iff some 16-bit band matches exactly, which
    for uniformly-spread d=10 flips is only ~25% per pair (measured:
    0.43 recall vs the exact device pass at 1M with a 0..10 flip
    mixture — tools/near_dup_scale.py records it per run). The device path
    (`near_dup_pairs_device`) is EXACT at the same scale and is what the
    near-dup job uses whenever a TPU is present; this survives only as
    the no-device fallback."""
    pairs = lsh_candidate_pairs(digests, n_bands)
    if not len(pairs):
        return []
    d = pair_distances(digests, pairs)
    keep = pairs[d <= threshold]
    return [(int(i), int(j)) for i, j in keep]


# ---------------------------------------------------------------------------
# Exact all-pairs at 1M: two single-dispatch device passes on the MXU.
#
# Two ideas make this exact search feasible where the naive loop dies:
#
# 1. One dispatch per pass, not per tile. Per-tile jit calls pay a
#    host→device round trip each — 325 calls for 100k digests. Both
#    passes here sweep their whole tile grid INSIDE one jit.
#
# 2. Hamming distance as a matmul. With each bit mapped to ±1,
#    dot(s_x, s_y) = BITS - 2·hamming(x, y), so the [T, T] distance
#    tile is one [T, BITS] @ [BITS, T] product — MXU work at int-exact
#    bf16/f32, ~100× the VPU XOR+popcount formulation. The sum of 64
#    ±1 terms is exact in f32, so thresholding is still exact.
#
#   pass 1: full tile grid → per-tile count of (i < j) pairs ≤
#           threshold ([NT, NT] int32, a few KB out).
#   pass 2: only the flagged tiles (host-chosen list, static shape) →
#           per-tile pair coordinates, padded to the max count.
# The N×N distance matrix never exists (O(tile²) working set).


def _bit_planes(digests) -> jnp.ndarray:
    """[N, W] uint32 → [N, W*32] bf16 of ±1 (bit b of word w at column
    w*32+b)."""
    n, w = digests.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (digests[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    return (bits.astype(jnp.bfloat16) * 2 - 1).reshape(n, w * 32)


def _origin_pair_mask(dots, oi, oj, size, bits, threshold, n):
    """dots [size, size] f32 → in-range (global i < j) mask for a block
    whose rows start at global index oi and columns at oj."""
    gi = oi + jnp.arange(size, dtype=jnp.int32)
    gj = oj + jnp.arange(size, dtype=jnp.int32)
    return ((dots >= bits - 2 * threshold)
            & (gi[:, None] < gj[None, :])
            & (gi[:, None] < n) & (gj[None, :] < n))


def _pair_mask(dots, i, j, T, bits, threshold, n):
    """dots [T, T] f32 → boolean mask of in-range (global i < j) pairs
    for whole-tile coords (the oi=i·T, oj=j·T case of the origin form)."""
    return _origin_pair_mask(dots, i * T, j * T, T, bits, threshold, n)


@jit_registry.tracked("hamming.tile_counts")
@functools.partial(jax.jit, static_argnames=("block",))
def _tile_counts_block(planes, row0, threshold, n, block: int):
    """Pair counts for `block` consecutive row-tiles starting at row0.

    planes: [NT, T, BITS] ±1 bf16 → [block, NT] int32. `threshold`/`n`/
    `row0` are traced scalars so one compilation serves every dataset of
    the same tile grid. The sweep is dispatched in row blocks so no
    single program runs for minutes (a full 1M sweep is ~60k matmuls);
    rows past NT clamp to the last tile and are discarded by the host.
    """
    NT, T, BITS = planes.shape

    def row(k):
        i = jnp.minimum(row0 + k, NT - 1)
        x = jax.lax.dynamic_index_in_dim(planes, i, keepdims=False)

        def col(j):
            dots = jax.lax.dot_general(
                x, planes[j], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return jnp.sum(_pair_mask(dots, i, j, T, BITS, threshold, n),
                           dtype=jnp.int32)

        return jax.lax.map(col, jnp.arange(NT, dtype=jnp.int32))

    return jax.lax.map(row, jnp.arange(block, dtype=jnp.int32))


def _refine_body(flat, coords, threshold, n, size: int, sub: int):
    """Core of the refinement step, shared by the single-device jit and
    the shard_map multi-device layout."""
    NP, BITS = flat.shape

    def one(rc):
        oi = rc[0] * size
        oj = rc[1] * size
        x = jax.lax.dynamic_slice_in_dim(flat, oi, size)
        y = jax.lax.dynamic_slice_in_dim(flat, oj, size)
        dots = jax.lax.dot_general(
            x, y, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ok = _origin_pair_mask(dots, oi, oj, size, BITS, threshold, n)
        k = size // sub
        return jnp.sum(ok.reshape(sub, k, sub, k), axis=(1, 3),
                       dtype=jnp.int32)

    return jax.lax.map(one, coords)


@jit_registry.tracked("hamming.refine")
@functools.partial(jax.jit, static_argnames=("size", "sub"))
def _refine_counts(flat, coords, threshold, n, size: int, sub: int):
    """Subdivide count blocks: for each (row0, col0) block origin pair
    in `coords` (units of `size` rows/cols of the flat plane array),
    return [F, sub, sub] int32 pair counts of its sub-blocks.

    Pure matmul + reshape-reduce — the extraction pyramid never runs
    nonzero/cumsum on device (a [4096,4096] nonzero measured ~150 ms
    per tile; this refinement is ~2 ms per tile).
    """
    return _refine_body(flat, coords, threshold, n, size, sub)


def make_sharded_pyramid(mesh):
    """The near-dup pyramid's counts + refine stages laid out for a
    1-D device mesh — the multi-chip form of `near_dup_pairs_device`.

    counts: the tile-row axis is sharded (each device owns NT/D row
    tiles); column tiles arrive by `all_gather` over the mesh axis, so
    the full [NT, NT] count grid is produced with each device doing an
    equal slice of the matmul sweep.

    refine: the flagged-block axis is sharded — each device refines its
    own block set against a replicated plane array (blocks are
    independent, zero collectives).

    Returns (counts_fn, refine_fn):
      counts_fn(planes [NT, T, BITS], threshold, n) -> [NT, NT] int32
      refine_fn(flat [NP, BITS], coords [F, 2], threshold, n) ->
          [F, sub, sub] int32   (size/sub fixed at tile → REFINE_SUB)
    """

    @jit_registry.tracked("hamming.pyramid")
    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("data", None, None), P(), P()),
        out_specs=P("data", None))
    def counts_fn(planes_shard, threshold, n):
        local_nt, T, BITS = planes_shard.shape
        base = jax.lax.axis_index("data") * local_nt
        planes_all = jax.lax.all_gather(
            planes_shard, "data", tiled=True)
        NT = planes_all.shape[0]

        def row(k):
            x = planes_shard[k]

            def col(j):
                dots = jax.lax.dot_general(
                    x, planes_all[j], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return jnp.sum(
                    _pair_mask(dots, base + k, j, T, BITS, threshold, n),
                    dtype=jnp.int32)

            return jax.lax.map(col, jnp.arange(NT, dtype=jnp.int32))

        return jax.lax.map(row, jnp.arange(local_nt, dtype=jnp.int32))

    def make_refine(size: int, sub: int):
        @jit_registry.tracked("hamming.pyramid")
        @jax.jit
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(None, None), P("data", None), P(), P()),
            out_specs=P("data", None, None))
        def refine_fn(flat, coords_shard, threshold, n):
            return _refine_body(flat, coords_shard, threshold, n,
                                size, sub)

        return refine_fn

    return counts_fn, make_refine


@jit_registry.tracked("hamming.leaf_masks")
@functools.partial(jax.jit, static_argnames=("size",))
def _leaf_masks(flat, coords, threshold, n, size: int):
    """[F, size, size] uint8 pair masks for leaf blocks — tiny enough
    to ship to the host, where numpy nonzero finishes the job."""
    NP, BITS = flat.shape

    def one(rc):
        oi = rc[0] * size
        oj = rc[1] * size
        x = jax.lax.dynamic_slice_in_dim(flat, oi, size)
        y = jax.lax.dynamic_slice_in_dim(flat, oj, size)
        dots = jax.lax.dot_general(
            x, y, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return _origin_pair_mask(dots, oi, oj, size, BITS,
                                 threshold, n).astype(jnp.uint8)

    return jax.lax.map(one, coords)


# Row-tiles per counts dispatch and refinement blocks per extract
# dispatch: sized so one dispatch stays at a few thousand [T,T]
# matmul tiles.
COUNT_ROWS_PER_DISPATCH = 16
REFINE_BLOCKS_PER_DISPATCH = 1024
REFINE_SUB = 16  # 4096 → 256 → 16-wide leaf blocks
# Host-side pair-list budget; denser output is degenerate (see
# near_dup_pairs_device docstring).
MAX_TOTAL_PAIRS = 4 << 20


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def near_dup_pairs_device(digests: np.ndarray, threshold: int,
                          tile: int = 4096,
                          stats: Optional[Dict[str, int]] = None,
                          ) -> List[Tuple[int, int]]:
    """Exact all-pairs (i < j, distance ≤ threshold) at large N on the
    device — a bounded number of jit dispatches, each sweeping thousands
    of tiles (see block comment above). Returns the same pairs as
    `near_dup_pairs`, validated at 1M by tools/near_dup_scale.py.

    Output is bounded at MAX_TOTAL_PAIRS: a degenerate near-identical
    cluster of m digests holds ~m²/2 qualifying pairs (50k burst photos
    → 1.25e9 pairs → ~100 GB of host tuples); past the budget the
    densest tiles are dropped with a warning — their exact-equality
    core is already collapsed by the CAS dedup pass, and a pair list
    that size is noise for any consumer. When truncation happens,
    `stats["truncated_pairs"]` carries the dropped-pair estimate (in
    addition to the RuntimeWarning) so callers can record it."""
    digests = np.ascontiguousarray(digests, dtype=np.uint32)
    N, W = digests.shape
    if N < 2:
        return []
    if tile & (tile - 1):
        raise ValueError(f"tile must be a power of two, got {tile} "
                         "(the refinement pyramid subdivides by "
                         f"{REFINE_SUB})")
    with jit_registry.device_scope("hamming.pairs"):
        return _near_dup_pairs_device_guarded(digests, threshold, tile,
                                              stats)


def _near_dup_pairs_device_guarded(digests, threshold, tile, stats):
    """Body of near_dup_pairs_device, run inside its device scope."""
    N, W = digests.shape
    NT = -(-N // tile)
    padded = np.zeros((NT * tile, W), dtype=np.uint32)
    padded[:N] = digests
    flat = _bit_planes(jnp.asarray(padded))
    planes = flat.reshape(NT, tile, W * 32)

    thr = jnp.int32(threshold)
    nn = jnp.int32(N)
    blocks = []
    for r0 in range(0, NT, COUNT_ROWS_PER_DISPATCH):
        dev_blk = _tile_counts_block(
            planes, jnp.int32(r0), thr, nn, COUNT_ROWS_PER_DISPATCH)
        with jit_registry.io("hamming.pairs"):
            blk = np.asarray(dev_blk)
        blocks.append(blk[: NT - r0])
    counts = np.concatenate(blocks, axis=0)

    coords = np.argwhere(counts > 0).astype(np.int32)
    if len(coords) == 0:
        return []
    tile_totals = counts[coords[:, 0], coords[:, 1]]
    if int(tile_totals.sum()) > MAX_TOTAL_PAIRS:
        # Keep sparsest tiles first until the pair budget is spent.
        import warnings

        order = np.argsort(tile_totals)
        keep = np.cumsum(tile_totals[order]) <= MAX_TOTAL_PAIRS
        dropped = int(tile_totals.sum()
                      - tile_totals[order][keep].sum())
        warnings.warn(
            f"near_dup_pairs_device: truncating ~{dropped} pairs in "
            "degenerate near-identical clusters (MAX_TOTAL_PAIRS "
            f"= {MAX_TOTAL_PAIRS})", RuntimeWarning)
        if stats is not None:
            stats["truncated_pairs"] = (
                stats.get("truncated_pairs", 0) + dropped)
        coords = coords[order][keep]
        if len(coords) == 0:
            return []

    def run_level(fn, coords, *args):
        """Dispatch a refinement level in pow2-padded chunks."""
        outs = []
        for f0 in range(0, len(coords), REFINE_BLOCKS_PER_DISPATCH):
            chunk = coords[f0 : f0 + REFINE_BLOCKS_PER_DISPATCH]
            fpad = _pow2(len(chunk))
            padded_c = np.zeros((fpad, 2), dtype=np.int32)
            padded_c[: len(chunk)] = chunk
            dev_res = fn(flat, jnp.asarray(padded_c), thr, nn, *args)
            with jit_registry.io("hamming.pairs"):
                res = np.asarray(dev_res)
            outs.append(res[: len(chunk)])
        return np.concatenate(outs, axis=0)

    # Refinement pyramid: tile → tile/16 → tile/256 leaf blocks; each
    # level keeps only sub-blocks whose count is nonzero, so the work
    # set stays O(pairs), and the leaves ship as tiny host-side masks.
    size = tile
    while size > REFINE_SUB:
        sub_counts = run_level(_refine_counts, coords, size, REFINE_SUB)
        f, a, b = np.nonzero(sub_counts)
        coords = np.stack([coords[f, 0] * REFINE_SUB + a,
                           coords[f, 1] * REFINE_SUB + b],
                          axis=1).astype(np.int32)
        size //= REFINE_SUB
        if len(coords) == 0:
            return []

    masks = run_level(_leaf_masks, coords, size)
    f, ii, jj = np.nonzero(masks)
    pi = coords[f, 0].astype(np.int64) * size + ii
    pj = coords[f, 1].astype(np.int64) * size + jj
    order = np.lexsort((pj, pi))
    return [(int(a), int(b)) for a, b in zip(pi[order], pj[order])]
