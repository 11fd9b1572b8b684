"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (chip_smoke.py runs the chip path, and
tests/test_tpu_compile.py compiles it for a described v5e). XLA_FLAGS
and JAX_PLATFORMS must be set before jax initializes a backend.
"""

import os

import pytest

# Tests never need the real chip; JAX reads this when it is imported.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from spacedrive_tpu.xla_env import ensure_host_device_count

ensure_host_device_count(8)

# The identifier's auto mesh-sharded CAS dispatch would compile a fresh
# shard_map program (~50 s on the CPU mesh) per batch grid across the
# whole suite; tests pin the single-device program and the sharded
# dispatch is covered by test_blake3_jax's dedicated case (which flips
# this back) plus the driver's dryrun_multichip stage 6.
os.environ.setdefault("SDTPU_SHARDED_CAS", "off")

# Same compile-cost hygiene for the donated CAS dispatch: its donated
# kernel twin costs a fresh ~45 s BLAKE3 compile on CPU for zero extra
# coverage of the REAL kernel (donation cannot change digests). The
# suite pins the undonated program; test_blake3_jax pins the donated
# dispatch plumbing.
os.environ.setdefault("SDTPU_DONATE_BUFFERS", "off")

# Tier-1 runs SANITIZED (spacedrive_tpu/sanitize.py): every asyncio
# callback is timed (loop-stall detector), the store's locks record
# acquisition order (cycle check raises), and a lock held across an
# await is a violation. `raise` mode surfaces lock-order cycles as
# exceptions at the acquire; asynchronous detections (stalls,
# held-across-await) are asserted ZERO per test by the autouse fixture
# below. Install BEFORE any Database is constructed so its locks come
# from the sanitizer.
os.environ.setdefault("SDTPU_SANITIZE", "1")
os.environ.setdefault("SDTPU_SANITIZE_MODE", "raise")
# CI containers run 2 cores over a 9p filesystem with ±40% IO weather;
# the production 1.0s stall threshold false-positives there on genuine
# thread-pool contention. 2.5s flaked twice across tier-1 rounds on
# weather-side Task.task_wakeup stalls (3.49s, then 4.498s — each with
# no code on the loop), so the CI margin sits at 6.0s; real loop hogs —
# the class the detector exists for — measured 1.5s+ of pure compute,
# which the 1.0s production threshold flags on real hosts regardless.
os.environ.setdefault("SDTPU_SANITIZE_STALL_S", "6.0")
from spacedrive_tpu import sanitize  # noqa: E402

sanitize.install()

import jax  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (`-m 'not slow'`) — benchmark-scale "
        "cases like the full-library clone")


@pytest.fixture
def cpu_devices():
    return jax.devices("cpu")


@pytest.fixture(autouse=True)
def _sanitizer_clean():
    """Every test must finish with zero NEW sanitizer violations —
    the runtime half of the sdlint acceptance gate. Tests that
    deliberately trigger violations (test_sanitize.py) reset the list
    before returning, so this stays green for them too."""
    before = len(sanitize.violations())
    yield
    fresh = sanitize.violations()[before:]
    assert not fresh, (
        "sanitizer violations during test: "
        + "; ".join(f"{v['kind']}: {v['detail']}" for v in fresh[:3]))


async def pair_two_nodes(a, b, library_name: str = "shared"):
    """Start both nodes' p2p planes (no discovery) and pair a library
    from A into B. Pairing itself records the sync routes both ways
    (initiator: the dialed address; responder: socket IP + announced
    listen port), so no manual set_route wiring is needed. Returns
    (lib_a, lib_b). Shared by the p2p/fault/live-loop suites."""
    await a.start()
    await b.start()
    await a.start_p2p(host="127.0.0.1", enable_discovery=False)
    pb = await b.start_p2p(host="127.0.0.1", enable_discovery=False)
    lib_a = a.create_library(library_name)
    b.p2p.on_pairing_request = lambda peer, info: True
    assert await a.p2p.pair("127.0.0.1", pb, lib_a)
    lib_b = b.libraries.list()[0]
    return lib_a, lib_b


def mk_instance(db, pub_id: bytes) -> int:
    """Insert a bare instance row (the sync suites' fixture shape)."""
    return db.insert("instance", {
        "pub_id": pub_id, "identity": b"", "node_id": b"",
        "node_name": "test", "node_platform": 0,
        "last_seen": 0, "date_created": 0,
    })


def make_sync_manager(tmp_path, name="solo", others=()):
    """A SyncManager over a fresh library DB holding its own instance
    row plus `others` — with no others this is the SOLO configuration
    the page-blob op-log format targets. Shared by the blob-format and
    fuzz suites so the two never drift."""
    import uuid

    from spacedrive_tpu.store.db import Database
    from spacedrive_tpu.sync.manager import SyncManager

    pub = uuid.uuid4().bytes
    db = Database(str(tmp_path / f"{name}.db"))
    mk_instance(db, pub)
    for other in others:
        mk_instance(db, other)
    return SyncManager(db, pub)


def drain_sync(src, dst) -> int:
    """Paged pull-loop drain src → dst through the real
    get_ops/receive_crdt_operations path (the in-process analog of the
    TCP pull loop); returns ops applied, asserts no ingest errors."""
    from spacedrive_tpu.sync.manager import GetOpsArgs

    applied = 0
    while True:
        clocks = dict(dst.timestamps)
        clocks[dst.instance] = max(dst.clock.last,
                                   clocks.get(dst.instance, 0))
        page = src.get_ops(GetOpsArgs(clocks=list(clocks.items()),
                                      count=1000))
        page = [op for op in page if op.instance != dst.instance]
        if not page:
            return applied
        n, errs = dst.receive_crdt_operations(page)
        assert not errs, errs[:3]
        applied += n
