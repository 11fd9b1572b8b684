"""threadctx: the ownership registry's runtime twin + the threaded
stress suite (round 13).

Covers: the armed write recorder (seeded cross-thread race caught,
guarded/sanctioned shapes quiet), container wraps, the hardened
call_threadsafe hand-off, static↔runtime registry drift, and the
satellite stress tests — N-thread telemetry increments with exact
totals and concurrent shed-channel puts with a monotone per-NAME
high-water (the PR 7 peak-fix regression)."""

import ast
import asyncio
import os
import threading

import pytest

from spacedrive_tpu import channels, sanitize, telemetry, threadctx
from spacedrive_tpu.telemetry import (
    CHAN_HIGH_WATER,
    RACE_CANDIDATES,
    RACE_HANDOFF_CLOSED,
    RACE_TRACKED_WRITES,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def clean_violations():
    yield
    sanitize.reset_violations()


def test_armed_by_conftest():
    assert threadctx.armed()
    names = {c.__name__ for c in threadctx.armed_classes()}
    assert {"StagePool", "Counter", "Histogram", "Database",
            "SyncManager", "HLC"} <= names, names


# -- the seeded race: a real cross-thread unguarded += is caught ------------

class _Seeded:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0


def test_seeded_unguarded_race_raises(clean_violations):
    """The PR 8 shape at runtime: two threads bumping a guarded attr
    with no lock — empty lockset intersection → data_race."""
    with threadctx.temporary_owner(
            _Seeded, n=threadctx.guarded_by("_lock")):
        obj = _Seeded()
        obj.n += 1  # single-thread rebind: tracked, quiet
        caught = []
        # Barrier: both writers must be ALIVE concurrently — a thread
        # that exits before the other starts can hand its pthread
        # ident to the successor, and the recorder (correctly) sees
        # one thread.
        barrier = threading.Barrier(2)

        def bump():
            try:
                barrier.wait()
                for _ in range(50):
                    obj.n += 1
            except sanitize.SanitizerViolation as e:
                caught.append(e)

        threads = [threading.Thread(target=bump) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert caught, "cross-thread bare += must raise data_race"
        assert "data_race" in str(caught[0])
    hits = [v for v in sanitize.violations()
            if v["kind"] == "data_race" and "_Seeded.n" in v["detail"]]
    assert hits
    if telemetry.enabled():
        assert RACE_CANDIDATES.labels(
            cls_attr="_Seeded.n").value >= 1


def test_guarded_writes_from_threads_are_quiet():
    """The same shape done right — every writer holds the declared
    guard — records tracked writes and raises nothing."""
    with threadctx.temporary_owner(
            _Seeded, n=threadctx.guarded_by("_lock")):
        obj = _Seeded()
        before = RACE_TRACKED_WRITES.value

        def bump():
            for _ in range(200):
                with obj._lock:
                    obj.n += 1

        threads = [threading.Thread(target=bump) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert obj.n == 800  # 4 threads x 200: nothing lost
        if telemetry.enabled():
            assert RACE_TRACKED_WRITES.value > before
    assert not [v for v in sanitize.violations()
                if v["kind"] == "data_race"]


class _LoopOwned:
    def __init__(self):
        self.state = "idle"


def test_second_thread_on_single_thread_attr_raises(clean_violations):
    with threadctx.temporary_owner(
            _LoopOwned, state=threadctx.single_thread()):
        obj = _LoopOwned()
        obj.state = "main"  # first rebind: owner thread established

        def other():
            try:
                obj.state = "intruder"
            except sanitize.SanitizerViolation:
                other.caught = True

        other.caught = False
        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert other.caught


class _Frozen:
    def __init__(self):
        self.shape = (1, 2)


def test_immutable_after_init_write_raises(clean_violations):
    with threadctx.temporary_owner(
            _Frozen, shape=threadctx.immutable_after_init()):
        obj = _Frozen()
        with pytest.raises(sanitize.SanitizerViolation):
            obj.shape = (3, 4)


class _Tally:
    def __init__(self):
        self.hits = 0


def test_atomic_counter_multi_thread_is_waived():
    """atomic_counter is the declared, visible waiver: counted, never
    raised — a lost update skews a statistic, not state."""
    with threadctx.temporary_owner(
            _Tally, hits=threadctx.atomic_counter()):
        obj = _Tally()
        obj.hits += 1

        def bump():
            for _ in range(100):
                obj.hits += 1

        threads = [threading.Thread(target=bump) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not [v for v in sanitize.violations()
                if v["kind"] == "data_race"]


class _Listy:
    def __init__(self):
        self._lock = threading.Lock()
        self.samples = []


def test_container_mutations_are_recorded(clean_violations):
    """Declared list attrs are wrapped: bare .append from two threads
    is a data_race even though __setattr__ never fires."""
    with threadctx.temporary_owner(
            _Listy, samples=threadctx.guarded_by("_lock")):
        obj = _Listy()
        assert type(obj.samples).__name__ == "_TrackedList"
        caught = []
        barrier = threading.Barrier(2)  # overlap: see the seeded test

        def push():
            try:
                barrier.wait()
                for i in range(50):
                    obj.samples.append(i)
            except sanitize.SanitizerViolation as e:
                caught.append(e)

        threads = [threading.Thread(target=push) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert caught, "bare container mutation must be recorded"


# -- call_threadsafe: the hardened hand-off ---------------------------------

def test_call_threadsafe_posts_to_live_loop():
    hits = []

    async def main():
        loop = asyncio.get_running_loop()

        def from_thread():
            assert threadctx.call_threadsafe(loop, hits.append, 1)

        t = threading.Thread(target=from_thread)
        t.start()
        t.join()
        await asyncio.sleep(0.05)

    asyncio.run(main())
    assert hits == [1]


def test_call_threadsafe_tolerates_closed_loop():
    loop = asyncio.new_event_loop()
    loop.close()
    before = RACE_HANDOFF_CLOSED.value
    assert threadctx.call_threadsafe(loop, lambda: None) is False
    assert threadctx.call_threadsafe(None, lambda: None) is False
    if telemetry.enabled():
        assert RACE_HANDOFF_CLOSED.value == before + 2


def test_call_threadsafe_reraises_other_runtime_errors():
    class _FakeLoop:
        def is_closed(self):
            return False

        def call_soon_threadsafe(self, cb, *args):
            raise RuntimeError("something else entirely")

    with pytest.raises(RuntimeError, match="something else"):
        threadctx.call_threadsafe(_FakeLoop(), lambda: None)


# -- static <-> runtime drift -----------------------------------------------

def test_registry_static_runtime_drift():
    """The AST-parsed owner table and the runtime registry cannot
    drift: same names, same sites, same attr kinds and locks (the
    jit/channel/timeout drift check, for ownership)."""
    from tools.sdlint.passes._threads import declared_owners_from_tree

    central = os.path.join(ROOT, "spacedrive_tpu", "threadctx.py")
    static = declared_owners_from_tree(
        ast.parse(open(central, encoding="utf-8").read()))
    assert set(static) == set(threadctx.CONTRACTS)
    for name, spec in static.items():
        runtime = threadctx.CONTRACTS[name]
        assert spec["site"] == runtime.site, name
        static_attrs = {a: kind_lock
                       for a, kind_lock in spec["attrs"].items()}
        runtime_attrs = {a: (c.kind, c.lock)
                         for a, c in runtime.attrs.items()}
        assert static_attrs == runtime_attrs, name


def test_every_declared_class_is_constructed_and_armed():
    """Contracts must point at live code: every declared site resolves
    to a class the sanitizer actually WRAPPED at install, and that
    class (or a subclass) is constructed somewhere in the tree — a
    dead contract is a silently-unchecked contract."""
    from tools.sdlint.core import dotted, load_project

    armed_names = {c.__name__ for c in threadctx.armed_classes()}
    project = load_project(ROOT)
    constructed = set()
    subclasses = {}
    for src in project.files:
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call):
                d = dotted(node.func)
                if d is not None:
                    constructed.add(d.rsplit(".", 1)[-1])
                # factory idiom: `_get_or_create(Counter, ...)`
                # constructs via the class ARGUMENT
                for arg in list(node.args) + [kw.value
                                              for kw in node.keywords]:
                    ad = dotted(arg)
                    if ad is not None:
                        constructed.add(ad.rsplit(".", 1)[-1])
            elif isinstance(node, ast.ClassDef):
                for b in node.bases:
                    bd = dotted(b)
                    if bd is not None:
                        subclasses.setdefault(
                            bd.rsplit(".", 1)[-1], set()).add(node.name)

    def constructed_somewhere(cls_name, seen=None):
        seen = seen or set()
        if cls_name in seen:
            return False
        seen.add(cls_name)
        if cls_name in constructed:
            return True
        return any(constructed_somewhere(sub, seen)
                   for sub in subclasses.get(cls_name, ()))

    for name, oc in threadctx.CONTRACTS.items():
        cls_name = oc.site.split("::", 1)[1]
        assert cls_name in armed_names, (
            f"contract {name!r}: class {cls_name!r} not armed")
        assert constructed_somewhere(cls_name), (
            f"contract {name!r}: {cls_name!r} (and no subclass) is "
            "ever constructed in the tree — prune or adopt it")


# -- satellite stress: telemetry exact totals under threads -----------------

def test_telemetry_counter_exact_totals_under_threads():
    """N threads x M increments land exactly — the per-metric leaf
    lock loses nothing — and the armed race recorder stays quiet
    (the autouse conftest fixture asserts zero new violations)."""
    c = telemetry.REGISTRY.counter("sd_race_stress_counter_total")
    h = telemetry.REGISTRY.histogram(
        "sd_race_stress_hist_seconds", buckets=(0.5, 1.5, 2.5))
    n_threads, n_iters = 8, 2000
    barrier = threading.Barrier(n_threads)

    def work():
        barrier.wait()
        for _ in range(n_iters):
            c.inc()
            h.observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if telemetry.enabled():
        assert c.value == n_threads * n_iters
        assert h.count == n_threads * n_iters
        assert h.sum == float(n_threads * n_iters)
        # every observation landed in the 1.5 bucket exactly
        sample = h._sample()
        assert sample["buckets"][1] == [1.5, n_threads * n_iters]


# -- satellite stress: shed channel under concurrent put_nowait -------------

def test_shed_channel_concurrent_put_accounting():
    """Concurrent put_nowait on a shed_new channel: delivered + shed
    == attempts exactly, and the per-NAME high-water gauge is monotone
    across the storm AND across instance churn (the PR 7 peak fix)."""
    chan = channels.channel("bench.shed")
    shed_before = chan.shed_total
    n_threads, n_iters = 6, 500
    delivered = [0] * n_threads
    barrier = threading.Barrier(n_threads)
    hw_samples = []
    stop = threading.Event()

    def sampler():
        while not stop.is_set():
            hw_samples.append(
                CHAN_HIGH_WATER.labels(name="bench.shed").value)

    def work(idx):
        barrier.wait()
        for i in range(n_iters):
            if chan.put_nowait((idx, i)):
                delivered[idx] += 1

    sam = threading.Thread(target=sampler)
    sam.start()
    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    sam.join()

    attempts = n_threads * n_iters
    shed = chan.shed_total - shed_before
    if telemetry.enabled():
        assert sum(delivered) + shed == attempts
        assert len(chan) == sum(delivered)
        # monotone while sampled mid-storm
        assert all(a <= b for a, b in zip(hw_samples, hw_samples[1:]))
        # instance churn cannot regress the per-NAME peak
        peak = CHAN_HIGH_WATER.labels(name="bench.shed").value
        assert peak >= len(chan)
        fresh = channels.channel("bench.shed")
        fresh.put_nowait("tiny")
        assert CHAN_HIGH_WATER.labels(
            name="bench.shed").value == peak

