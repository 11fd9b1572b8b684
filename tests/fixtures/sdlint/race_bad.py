"""Known-POSITIVE fixture for the shared-mutation pass.

Every contract kind broken once, plus the two registry-enforcement
codes. The `h2d_bytes` bare `+=` from a run_in_executor target is the
encoded lost-update shape of an early multi-stream pipeline's stats —
the pass must keep catching it. The fixture self-declares its contracts (declare_owner is
parsed from project files as well as the central registry, exactly so
fixtures can do this)."""

import asyncio
import threading

from spacedrive_tpu.threadctx import (
    atomic_counter,
    declare_owner,
    guarded_by,
    immutable_after_init,
    loop_only,
    single_thread,
)

declare_owner(
    "fixture.RaceStats",
    "tests/fixtures/sdlint/race_bad.py::RaceStats",
    {
        "h2d_bytes": guarded_by("_lock"),
        "events": loop_only(),
        "wall_s": single_thread(),
        "ticks": atomic_counter(),
        "shape": immutable_after_init(),
    })


class RaceStats:
    def __init__(self):
        self._lock = threading.Lock()
        self.h2d_bytes = 0
        self.events = []
        self.wall_s = 0.0
        self.ticks = 0
        self.shape = (0, 0)


def _transfer(stats: RaceStats) -> None:
    # BAD unguarded-write: the lost-update stats shape — a per-device
    # executor stream bumping a guarded counter with no lock held.
    stats.h2d_bytes += 57344


def _report(stats: RaceStats) -> None:
    stats.events.append("done")   # BAD wrong-context-write (loop_only)
    stats.shape = (2, 2)          # BAD post-init-write (immutable)
    stats.ticks = 0               # BAD non-atomic-write (rebind)


def _finish(stats: RaceStats) -> None:
    stats.wall_s = 2.0            # BAD multi-thread-write (with drive)
    stats.extra = 1               # BAD undeclared-attr


async def drive(stats: RaceStats, pool) -> None:
    loop = asyncio.get_running_loop()
    await loop.run_in_executor(pool, _transfer, stats)
    await asyncio.to_thread(_report, stats)
    await asyncio.to_thread(_finish, stats)
    stats.wall_s = 1.0            # loop-side half of the wall_s pair


class BareShared:
    """No declare_owner, mutated from loop AND worker contexts —
    the undeclared-class code."""

    def __init__(self):
        self.seen = {}

    def record(self, k) -> None:
        self.seen[k] = True


def _pump(b: BareShared) -> None:
    b.record("z")


async def uses(b: BareShared) -> None:
    b.record("x")
    await asyncio.to_thread(_pump, b)
