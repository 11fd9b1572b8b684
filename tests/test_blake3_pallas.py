"""Pallas TPU kernel parity vs the streaming oracle.

The kernel needs real TPU hardware (or `interpret=True`, whose
interpreter is far too slow for CI — a single small batch takes minutes
on CPU). The test suite pins jax to the virtual CPU mesh (conftest), so
these tests self-skip there; chip_smoke.py exercises the kernel on the
real chip, and tests/test_tpu_compile.py compiles it for a v5e.
"""

import os

import jax
import numpy as np
import pytest

@pytest.fixture
def tpu():
    """Skips unless the default device is a TPU — asked when a test
    runs, never while the module is imported."""
    if jax.devices()[0].platform != "tpu":
        pytest.skip("Pallas BLAKE3 kernel requires TPU hardware")


def test_pallas_matches_oracle_edge_lengths(tpu):
    from spacedrive_tpu.ops.blake3_batch import pack_messages
    from spacedrive_tpu.ops.blake3_jax import digests_to_hex
    from spacedrive_tpu.ops.blake3_pallas import blake3_words_pallas
    from spacedrive_tpu.ops.blake3_ref import blake3_hex

    lengths = [0, 1, 63, 64, 65, 1024, 1025, 2048, 3071, 4096, 57352]
    msgs = [os.urandom(n) for n in lengths]
    words, lens = pack_messages(msgs)
    digests = np.asarray(blake3_words_pallas(words, lens))
    for m, hexd in zip(msgs, digests_to_hex(digests)):
        assert hexd == blake3_hex(m), f"len={len(m)}"


def test_pallas_chunk_stage_matches_numpy_nonwhole(tpu):
    """Streaming mode (counter base, not the root): per-chunk CVs match
    the numpy backend exactly, including partially-filled tails."""
    from spacedrive_tpu.ops.blake3_batch import chunk_cvs
    from spacedrive_tpu.ops.blake3_pallas import chunk_cvs_pallas

    rng = np.random.default_rng(7)
    B, C = 3, 5
    words = rng.integers(0, 2**32, size=(B, C, 256), dtype=np.uint32)
    lengths = np.array([0, 1, C * 1024], dtype=np.int64)

    ref_cvs, ref_n = chunk_cvs(np, words, lengths, counter_base=16,
                               whole=False)
    got_cvs, got_n = chunk_cvs_pallas(words, lengths, counter_base=16,
                                      whole=False)
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(ref_n))
    for g, r in zip(got_cvs, ref_cvs):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
