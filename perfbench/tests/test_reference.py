"""The numpy BLAKE3 reference against the BLAKE3 test vectors (input
byte i is i % 251), and the CAS message layout of cas.rs."""

import struct

import numpy as np
import pytest

from perfbench import check, corpus
from perfbench.reference import blake3_np

VECTORS = {
    0: "af1349b9f5f9a1a6a0404dea36dcc9499bcb25c9adc112b7cc9a93cae41f3262",
    1: "2d3adedff11b61f14c886e35afa036736dcd87a74d27b5c1510225d0f592e213",
    1023: "10108970eeda3eb932baac1428c7a2163b0e924c9a9e25b35bba72b28f70bd11",
    1024: "42214739f095a406f3fc83deb889744ac00df831c10daa55189b5d121c855af7",
    1025: "d00278ae47eb27b34faecf67b4fe263f82d5412916c1ffd97c8cb7fb814b8444",
    2048: "e776b6028c7cd22a4d0ba182a8bf62205d2ef576467e838ed6f2529b85fba24a",
    2049: "5f4d72f40d7a5f82b15ca2b2e44b1de3c2ef86c426c95c1af0b6879522563030",
    8193: "bab6c09cb8ce8cf459261398d2e7aef35700bf488116ceb94a36d0f5f1b7bc3b",
    102400: "bc3e3d41a1146b069abffad3c0d44860cf664390afce4d9661f7902e7943e085",
}


def pattern(n: int) -> bytes:
    return bytes(i % 251 for i in range(n))


@pytest.mark.parametrize("n", sorted(VECTORS))
def test_vectors(n):
    assert blake3_np.blake3_many([pattern(n)])[0].hex() == VECTORS[n]


def test_one_batch_equals_one_by_one():
    rng = np.random.default_rng(3)
    msgs = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
            for n in (0, 5, 64, 65, 1024, 3000, 5 * 1024 + 1, 40000)]
    batch = blake3_np.blake3_many(msgs)
    assert batch == [blake3_np.blake3_many([m])[0] for m in msgs]


def test_cas_message_layout():
    small = pattern(5000)
    assert blake3_np.cas_message(small) == struct.pack("<Q", 5000) + small
    big = pattern(300_000)
    msg = blake3_np.cas_message(big)
    assert len(msg) == 8 + 57_344
    jump = (300_000 - 16_384) // 4
    assert msg[8:8 + 8192] == big[:8192]
    assert msg[8 + 8192:8 + 8192 + 10240] == big[8192:8192 + 10240]
    assert msg[-8192:] == big[-8192:]
    assert msg[8 + 8192 + 3 * 10240:8 + 8192 + 4 * 10240] == \
        big[8192 + 3 * jump:8192 + 3 * jump + 10240]


@pytest.mark.parametrize("kind", ["cas", "checksum"])
def test_check_reference_in_processes(monkeypatch, kind):
    """The check's reference, in spawned processes over blocks, equals
    the digests of each file's bytes, duplicates included."""
    part = {"name": "p", "files": 40, "dup_rate": 0.3, "layout_seed": 1,
            "size_classes": [[1.0, 256, 150000]]}
    _d, files = corpus.plan_location([part])
    monkeypatch.setattr(check, "REF_BLOCK_BYTES", 400_000)
    got = check.reference(kind, 2**33 + 9, files)
    digest = blake3_np.cas_ids if kind == "cas" else blake3_np.checksums
    want = digest([corpus.content(2**33 + 9, f) for f in files])
    assert got == {f.rel: w for f, w in zip(files, want)}
