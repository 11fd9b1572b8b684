"""sdlint fixture — jit-stability KNOWN POSITIVES."""

import functools

import jax
import numpy as np

from spacedrive_tpu.ops import jit_registry


@jax.jit
def unregistered(x):
    # jit entry point with no tracked(...) binding
    return x + 1


@jit_registry.tracked("no.such.contract")
@jax.jit
def unknown_name(x):
    # tracked, but the contract does not exist in the registry
    return x * 2


@jit_registry.tracked("hamming.near_mask")
@functools.partial(jax.jit, static_argnames=("tile",))
def drifted_static(x, y, tile: int = 4):
    # declared static_argnames is ("threshold",) — site drifted
    return x[:tile] ^ y[:tile]


@jit_registry.tracked("hamming.tile")
@functools.partial(jax.jit, static_argnums=(1,))
def positional_static(x, n):
    # static_argnums instead of static_argnames
    return x * n


def call_time(fn, words, lengths):
    # the early pipeline's shape: a fresh jit wrapper per invocation
    jfn = jax.jit(fn)
    return jfn(words, lengths)


def jit_per_batch(fn, batches):
    out = []
    for batch in batches:
        jfn = jax.jit(fn)  # strictly worse: one compile per iteration
        out.append(jfn(batch))
    return out


@jit_registry.tracked("hamming.near_mask")
@functools.partial(jax.jit, static_argnames=("threshold",))
def mask(x, y, threshold: int = 2):
    # correctly bound — the bad call sites below abuse it
    return (x ^ y) <= threshold


@jit_registry.tracked("hamming.tile")
@functools.partial(jax.jit, donate_argnums=(0,))
def donates_undeclared(x, y):
    # hamming.tile's contract declares no donate_argnums: consuming the
    # caller's x is an undeclared semantic change
    return x ^ y


def unhashable_static(x, y):
    return mask(x, y, threshold=[1, 2])


def raw_len_shape(xs, d):
    # Python-value-dependent shape built at the jit boundary
    return mask(np.zeros((len(xs), 2), dtype=np.uint32), d, threshold=2)
