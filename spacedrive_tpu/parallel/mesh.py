"""Device-mesh construction for the identification and dedup pipelines.

The framework's parallelism axes (the TPU-native analog of the reference's
job/step concurrency, SURVEY.md §2.5-2.6):

- ``data``: files are independent → batch dim sharded, no collectives
  (hashing, pHash, EXIF tensors).
- ``rows``/``cols`` 2-D tile mesh: Hamming all-pairs over N digests is an
  N×N tile grid; each device owns a row-block and all-gathers column
  blocks over ICI (see ops/hamming.py).

On this machine there is one real TPU chip; multi-chip layouts are
exercised on a virtual CPU mesh (tests) and by the driver's
``dryrun_multichip``.
"""

from __future__ import annotations

import functools
import math

import jax
import numpy as np
from jax.sharding import Mesh


@functools.lru_cache(maxsize=16)
def _batch_mesh_cached(devices: tuple) -> Mesh:
    return Mesh(np.array(devices), axis_names=("data",))


def batch_mesh(devices=None) -> Mesh:
    """1-D mesh over all devices for data-parallel batch work.

    Cached per device tuple (round-10 retrace hygiene): callers like
    the validator build a mesh per STEP, and jit entry points that take
    the mesh as a static argument (ops/seqhash._sharded_reduce) key
    their trace cache on it — returning the same Mesh object for the
    same device set keeps those at one compiled program per mesh
    instead of risking one per step."""
    devices = tuple(jax.devices()) if devices is None else tuple(devices)
    return _batch_mesh_cached(devices)


@functools.lru_cache(maxsize=16)
def _tile_mesh_cached(devices: tuple) -> Mesh:
    n = len(devices)
    rows = 1
    for r in range(int(math.isqrt(n)), 0, -1):
        if n % r == 0:
            rows = r
            break
    cols = n // rows
    return Mesh(np.array(devices).reshape(rows, cols),
                axis_names=("rows", "cols"))


def tile_mesh(devices=None) -> Mesh:
    """2-D (rows, cols) mesh for all-pairs tiles; rows*cols = n_devices.

    Prefers the squarest factorization so tile all-gathers move the
    least data per device. Cached per device tuple (see batch_mesh)."""
    devices = tuple(jax.devices()) if devices is None else tuple(devices)
    return _tile_mesh_cached(devices)


def pad_to_multiple(n: int, m: int) -> int:
    return -(-n // m) * m
