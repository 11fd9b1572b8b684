"""JAX backend parity vs oracle, incl. sharded execution on a CPU mesh."""

import os
import random

import numpy as np
import pytest

from spacedrive_tpu.ops.blake3_jax import (
    blake3_words,
    build_cas_messages,
    cas_ids_jax,
    cas_rows,
    digests_to_cas_ids,
    digests_to_hex,
    make_sharded_blake3,
)
from spacedrive_tpu.ops.blake3_batch import pack_messages
from spacedrive_tpu.ops.blake3_ref import blake3_hex
from spacedrive_tpu.ops import cas
from spacedrive_tpu.parallel import batch_mesh


def test_jax_matches_oracle_edge_lengths():
    lengths = [0, 1, 64, 1024, 1025, 2048, 3071, 57352]
    msgs = [os.urandom(n) for n in lengths]
    words, lens = pack_messages(msgs)
    digests = blake3_words(words, lens)
    for m, hexd in zip(msgs, digests_to_hex(digests)):
        assert hexd == blake3_hex(m), f"len={len(m)}"


def test_cas_pipeline_large_mode_matches_oracle(tmp_path):
    """Fixed-shape large-file mode: sampled payloads → CAS IDs on device."""
    rng = random.Random(5)
    B = 4
    paths, sizes = [], []
    for i in range(B):
        size = rng.randrange(cas.MINIMUM_FILE_SIZE + 1, 400_000)
        p = tmp_path / f"f{i}"
        p.write_bytes(os.urandom(size))
        paths.append(p)
        sizes.append(size)

    payloads = np.zeros((B, cas.LARGE_PAYLOAD_SIZE), dtype=np.uint8)
    for i, (p, size) in enumerate(zip(paths, sizes)):
        with open(p, "rb") as f:
            payloads[i] = np.frombuffer(
                cas.read_sampled_payload(f, size), dtype=np.uint8
            )
    got = cas_ids_jax(payloads, np.array(sizes, dtype=np.uint64))
    want = [cas.generate_cas_id(p) for p in paths]
    assert got == want


def test_cas_pipeline_small_mode(tmp_path):
    """Variable-length small files padded into one grid."""
    sizes = [0, 1, 5000, cas.MINIMUM_FILE_SIZE]
    B = len(sizes)
    payloads = np.zeros((B, cas.MINIMUM_FILE_SIZE), dtype=np.uint8)
    paths = []
    for i, size in enumerate(sizes):
        p = tmp_path / f"s{i}"
        data = os.urandom(size)
        p.write_bytes(data)
        paths.append(p)
        payloads[i, :size] = np.frombuffer(data, dtype=np.uint8)
    got = cas_ids_jax(
        payloads,
        np.array(sizes, dtype=np.uint64),
        payload_lens=np.array(sizes, dtype=np.int32),
    )
    want = [cas.generate_cas_id(p) for p in paths]
    assert got == want


def test_cas_dispatch_routes_donated_entry(monkeypatch):
    """cas_ids_jax dispatch plumbing for SDTPU_DONATE_BUFFERS: with the
    flag on (the production default; conftest pins it off suite-wide
    for compile cost) the single-device path hashes through the donated
    entry — `_donated_local` over the `blake3.donated` contract — and
    the CAS IDs come out unchanged. The stand-in delegates to the
    already-compiled undonated program, keeping this a pure plumbing
    test: the donated program itself is never compiled here."""
    from spacedrive_tpu.ops import blake3_jax as bj

    sizes = [0, 77, 4096]
    B = len(sizes)
    payloads = np.zeros((B, cas.MINIMUM_FILE_SIZE), dtype=np.uint8)
    for i, size in enumerate(sizes):
        payloads[i, :size] = np.frombuffer(os.urandom(size), np.uint8)
    lens = np.array(sizes, dtype=np.int32)
    want = cas_ids_jax(payloads, np.array(sizes, np.uint64),
                       payload_lens=lens)

    calls = []

    def fake_donated(words, lengths):
        calls.append(tuple(words.shape))
        return bj.blake3_words(words, lengths)

    monkeypatch.setenv("SDTPU_DONATE_BUFFERS", "on")
    monkeypatch.setattr(bj, "_donated_local", fake_donated)
    got = cas_ids_jax(payloads, np.array(sizes, np.uint64),
                      payload_lens=lens)
    assert calls, "donated entry was not dispatched with the flag on"
    assert got == want
    # the suite-wide off pin really does route the undonated program
    calls.clear()
    monkeypatch.setenv("SDTPU_DONATE_BUFFERS", "off")
    got_off = cas_ids_jax(payloads, np.array(sizes, np.uint64),
                          payload_lens=lens)
    assert not calls and got_off == want


@pytest.mark.parametrize("B,n_dev,rows", [
    (1, 1, 8), (8, 1, 8), (9, 1, 32), (2049, 1, 4096),
    (9, 4, 32), (2049, 4, 4096),
])
def test_cas_rows_bucket_and_mesh_padding(B, n_dev, rows):
    """The CAS grid's row count, shared by the packed stager (the pooled
    page it stages into) and the dispatch (the program it runs): B's
    bucket, past the largest bucket a multiple of it, padded to a
    multiple of the device count so every shard gets equal rows."""
    got = cas_rows(B, n_dev)
    assert got == rows
    assert got >= B and got % n_dev == 0


def test_sharded_blake3_on_cpu_mesh(cpu_devices):
    mesh = batch_mesh(cpu_devices)
    assert len(cpu_devices) == 8, "conftest should provide 8 virtual CPU devices"
    B = 16  # divisible by mesh size
    msgs = [os.urandom(3000) for _ in range(B)]
    words, lens = pack_messages(msgs, max_chunks=3)
    sharded = make_sharded_blake3(mesh)
    digests = sharded(words, lens)
    for m, hexd in zip(msgs, digests_to_hex(digests)):
        assert hexd == blake3_hex(m)


def test_identifier_sharded_dispatch_on_cpu_mesh(tmp_path, monkeypatch):
    """The PRODUCTION multi-device path: with >1 local device (and the
    suite's compile-saving gate reopened) cas_ids_for_files
    backend="jax" auto-routes through the mesh-sharded program with
    pad-to-devices batching — CAS IDs byte-equal the streaming oracle.
    This is the dispatch a real pod slice uses; dryrun_multichip
    stage 6 proves the same thing under the driver."""
    import random

    from spacedrive_tpu.ops import blake3_jax as bj
    from spacedrive_tpu.ops import staging
    from spacedrive_tpu.ops.cas import generate_cas_id

    monkeypatch.setenv("SDTPU_SHARDED_CAS", "auto")
    monkeypatch.setattr(bj, "_SHARDED", None)
    rng = random.Random(4)
    files = []
    for i in range(9):  # deliberately not a devices multiple
        size = 1500 + 701 * i
        p = tmp_path / f"f{i}.bin"
        p.write_bytes(rng.randbytes(size))
        files.append((str(p), size))
    hasher, n_dev = bj.sharded_hasher()
    assert hasher is not None and n_dev == 8
    ids, errs = staging.cas_ids_for_files(files, backend="jax")
    assert not errs
    for i, (p, size) in enumerate(files):
        assert ids[i] == generate_cas_id(p, size), i
