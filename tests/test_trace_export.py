"""Flight recorder: pipeline timeline, cross-node trace propagation,
and the Chrome-trace exporter (spacedrive_tpu/flight.py +
tools/trace_export.py).

Pins the acceptance shapes on CPU:
- identify chunks on the host planes and the jax backend record
  stage/H2D/kernel/retire lanes that export as a schema-valid
  Chrome-trace JSON, race-recorder-clean (the autouse sanitizer
  fixture asserts the zero-violations half);
- the exporter's golden schema: required keys, monotone ts, and a
  named process/thread for every pid/tid;
- a two-node sync pull produces ONE trace id whose spans include both
  the serving (sync.serve) and the ingesting (sync.pull) node
  [skipif-cryptography, like the rest of the TCP p2p plane];
- `python -m tools.trace_export --json` self-checks in tier-1 and
  exits non-zero on a schema violation.
"""

import asyncio
import json
import os
import subprocess
import sys

import pytest

from spacedrive_tpu import flight, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

try:
    # Seed the objects package: in runtimes without `cryptography` the
    # first attempt fails but leaves the non-crypto submodules cached,
    # after which mount_router imports cleanly (container quirk; no-op
    # where the dependency exists — same idiom as test_telemetry).
    import spacedrive_tpu.objects  # noqa: F401
except ModuleNotFoundError:
    pass


def _has_cryptography() -> bool:
    try:
        import cryptography  # noqa: F401
        return True
    except ImportError:
        return False


# -- recorder unit surface --------------------------------------------------

def test_recorder_contract_quiet_under_threads():
    """The threadctx half of the timeline ring: concurrent record()
    storms from worker threads — every ring put under the declared
    _lock — stay data_race-quiet (the autouse sanitizer fixture asserts
    zero violations) and lose no event."""
    import threading

    rec = flight.FlightRecorder()

    def work(base):
        for i in range(50):
            b = base + i
            rec.record("stage", batch=b, t0=1.0, t1=2.0)
            rec.record("h2d", batch=b, t0=2.0, t1=3.0, device="0")
            rec.record("kernel", batch=b, t0=3.0, t1=3.5, device="0")
            rec.record("retire", batch=b, t0=3.5, t1=4.0)

    threads = [threading.Thread(target=work, args=(k * 1000,))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = rec.snapshot()
    assert len(snap) == 4 * 50 * 4
    for lane in flight.LANES:
        assert sum(e["lane"] == lane for e in snap) == 200, lane


def test_recorder_ring_is_bounded_and_clearable():
    """History ages out oldest-first at the declared channel capacity;
    clear() empties the ring (the per-run artifact hygiene hook)."""
    from spacedrive_tpu import channels

    cap = channels.capacity("ops.pipeline.timeline")
    rec = flight.FlightRecorder()
    for i in range(cap + 10):
        rec.record("stage", batch=i, t0=float(i), t1=float(i) + 0.5)
    snap = rec.snapshot()
    assert len(snap) == cap
    assert snap[0]["batch"] == 10  # oldest 10 aged out
    rec.clear()
    assert rec.snapshot() == []


# -- golden exporter schema -------------------------------------------------

def _synthetic_doc():
    rec = flight.FlightRecorder()
    t = 10.0
    for batch in (1, 2):
        b = t + batch * 0.1
        rec.record("stage", batch=batch, t0=b, t1=b + 0.03,
                   stream=batch % 2, trace="feed")
        rec.record("h2d", batch=batch, t0=b + 0.03, t1=b + 0.05,
                   device=str(batch % 2), trace="feed")
        rec.record("kernel", batch=batch, t0=b + 0.05, t1=b + 0.06,
                   device=str(batch % 2), trace="feed")
        rec.record("retire", batch=batch, t0=b + 0.06, t1=b + 0.07,
                   trace="feed")
    spans = [
        {"span": "job/x", "ms": 50.0, "ts_us": 1_000_000,
         "trace": "aa", "id": "1", "ok": True},
        {"span": "job.step", "ms": 10.0, "ts_us": 1_010_000,
         "trace": "aa", "id": "2", "parent": "1", "ok": False,
         "error": "KeyError"},
    ]
    return flight.chrome_trace(spans=spans, timeline=rec.snapshot(),
                               node_name="golden")


def test_chrome_trace_golden_schema():
    doc = _synthetic_doc()
    assert flight.validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    xs = [e for e in evs if e["ph"] == "X"]
    # required keys on every complete event
    for e in xs:
        assert {"name", "ts", "dur", "pid", "tid", "args"} <= set(e)
    # monotone ts over the complete events
    tss = [e["ts"] for e in xs]
    assert tss == sorted(tss)
    # pid mapping: both processes named, every (pid, tid) named
    named_pids = {e["pid"] for e in meta if e["name"] == "process_name"}
    assert named_pids == {flight.PID_SPANS, flight.PID_TIMELINE}
    named_tids = {(e["pid"], e["tid"]) for e in meta
                  if e["name"] == "thread_name"}
    assert {(e["pid"], e["tid"]) for e in xs} <= named_tids
    # the per-device lanes exist
    lane_names = {e["args"]["name"] for e in meta
                  if e["name"] == "thread_name"}
    assert {"dev0 h2d", "dev1 h2d", "dev0 kernel", "dev1 kernel",
            "retire"} <= lane_names
    assert any(n.startswith("stage/w") for n in lane_names)
    # span events carry trace/id lineage in args
    span_evs = [e for e in xs if e["pid"] == flight.PID_SPANS]
    assert {e["name"] for e in span_evs} == {"job/x", "job.step"}
    child = next(e for e in span_evs if e["name"] == "job.step")
    assert child["args"]["parent"] == "1"
    assert child["args"]["error"] == "KeyError"


def test_validator_rejects_seeded_violations():
    """Each schema rule actually fires: missing keys, unsorted ts,
    unnamed pid/tid, unknown ph, bad top level."""
    assert flight.validate_chrome_trace([]) != []
    assert flight.validate_chrome_trace({"traceEvents": "nope"}) != []

    def broken(mutate):
        doc = json.loads(json.dumps(_synthetic_doc()))
        mutate(doc["traceEvents"])
        return flight.validate_chrome_trace(doc)

    xs_at = lambda evs: [i for i, e in enumerate(evs)  # noqa: E731
                         if e["ph"] == "X"]

    probs = broken(lambda evs: evs[xs_at(evs)[0]].pop("dur"))
    assert any("missing keys" in p for p in probs)
    probs = broken(lambda evs: evs.insert(
        len(evs), {"ph": "X", "name": "late", "ts": -5, "dur": 1,
                   "pid": flight.PID_SPANS, "tid": 1}))
    assert any("non-negative" in p for p in probs)
    probs = broken(lambda evs: evs.reverse())
    assert any("sorted" in p for p in probs)
    probs = broken(lambda evs: evs.append(
        {"ph": "X", "name": "orphan", "ts": 10**12, "dur": 1,
         "pid": 99, "tid": 1}))
    assert any("no process_name" in p for p in probs)
    probs = broken(lambda evs: evs.append({"ph": "Q"}))
    assert any("unknown ph" in p for p in probs)


# -- identify lanes ----------------------------------------------------------

def test_identify_host_plane_records_timeline(tmp_path):
    """The host hashing planes get the same lanes (scope=identify):
    one stage + one kernel event per cas_ids_for_files chunk."""
    from spacedrive_tpu.ops.staging import cas_ids_for_files

    flight.RECORDER.clear()
    p = tmp_path / "f.bin"
    p.write_bytes(b"y" * 5000)
    ids, errors = cas_ids_for_files([(str(p), 5000)], backend="numpy")
    assert not errors and ids[0]
    snap = [e for e in flight.RECORDER.snapshot()
            if e.get("scope") == "identify"]
    assert [e["lane"] for e in snap] == ["stage", "kernel"]
    assert all(e["device"] == "numpy" for e in snap)
    assert snap[0]["batch"] == snap[1]["batch"]
    doc = flight.chrome_trace(node_name="identify")
    assert flight.validate_chrome_trace(doc) == []


@pytest.mark.parametrize("sizes,classes", [
    ((700, 5000), 1),            # small only
    ((120_000, 200_000), 1),     # large only (files > 100 KiB)
    ((700, 120_000, 5000), 2),   # large and small
], ids=["small", "large", "mixed"])
def test_identify_jax_plane_records_phase_lanes(tmp_path, sizes, classes):
    """On the jax backend the identify lanes come from the phase
    boundaries (stage+pack, dispatch, fetch): one stage, h2d and retire
    triple per size class, back to back under the chunk's one batch id,
    and no host-clock kernel lane — the kernel is on the device
    trace."""
    from spacedrive_tpu.ops import cas
    from spacedrive_tpu.ops.staging import cas_ids_for_files

    files = []
    for i, size in enumerate(sizes):
        p = tmp_path / f"f{i}.bin"
        p.write_bytes(bytes([i + 1]) * size)
        files.append((str(p), size))
    flight.RECORDER.clear()
    ids, errors = cas_ids_for_files(files, backend="jax")
    assert not errors and len(ids) == len(files)
    assert all(ids[i] == cas.generate_cas_id(p, n)
               for i, (p, n) in enumerate(files))
    snap = [e for e in flight.RECORDER.snapshot()
            if e.get("scope") == "identify"]
    assert [e["lane"] for e in snap] == ["stage", "h2d", "retire"] * classes
    assert all(e["device"] == "jax" for e in snap)
    assert len({e["batch"] for e in snap}) == 1
    for a, b in zip(snap, snap[1:]):
        assert abs(a["ts_us"] + a["dur_us"] - b["ts_us"]) <= 2
    doc = flight.chrome_trace(node_name="identify")
    assert flight.validate_chrome_trace(doc) == []


# -- cross-node propagation -------------------------------------------------

def test_traceparent_round_trip_and_malformed():
    assert tracing.traceparent() is None
    assert tracing.parse_traceparent(None) is None
    assert tracing.parse_traceparent("") is None
    assert tracing.parse_traceparent("zz-qq") is None
    assert tracing.parse_traceparent("12345") is None
    assert tracing.parse_traceparent("0-0") is None
    with tracing.span("p2p/probe"):
        tp = tracing.traceparent()
        assert tracing.parse_traceparent(tp) == tracing.current_trace()
    # malformed tp degrades to a local root, never raises
    with tracing.continue_trace("not-a-trace"):
        with tracing.span("p2p/local-root"):
            pass
    rec = tracing.recent_spans(limit=1)[-1]
    assert "parent" not in rec


def test_continue_trace_parents_remote_span():
    """The cross-node contract in one process: a span opened under
    continue_trace(tp) carries the remote trace id and the remote span
    as its parent — and the adoption survives asyncio.to_thread, the
    hand-off job steps actually use."""
    with tracing.span("sync.serve"):
        tp = tracing.traceparent()
    serve_trace, serve_span = tp.split("-")

    def worker_span():
        with tracing.span("job.step"):
            pass

    async def remote_side():
        with tracing.continue_trace(tp):
            with tracing.span("sync.pull"):
                # context flows into to_thread workers too
                await asyncio.to_thread(worker_span)

    asyncio.run(remote_side())
    ring = tracing.recent_spans(limit=20)
    pull = next(r for r in reversed(ring) if r["span"] == "sync.pull")
    assert pull["trace"] == serve_trace
    assert pull["parent"] == serve_span
    step = next(r for r in reversed(ring) if r["span"] == "job.step")
    assert step["trace"] == serve_trace
    assert step["parent"] == pull["id"]


@pytest.mark.skipif(not _has_cryptography(),
                    reason="p2p TCP plane needs the cryptography module")
def test_two_node_sync_pull_shares_one_trace(tmp_path):
    """The tentpole's cross-node acceptance: a write on node A fans
    out over real loopback TCP, and the resulting sync stream is ONE
    trace — A's sync.serve span and B's sync.pull span (plus B's
    ingest spans under it) share a trace id carried in the new_ops
    header's tp field."""
    from spacedrive_tpu.node import Node

    a = Node(str(tmp_path / "a"))
    b = Node(str(tmp_path / "b"))

    async def main():
        from conftest import pair_two_nodes

        lib_a, lib_b = await pair_two_nodes(a, b, "traced")
        tracing.clear_span_ring()
        sync = lib_a.sync
        pub = os.urandom(16)
        ops = sync.shared_create("tag", pub, {"name": "traced-tag"})
        with sync.write_ops(ops) as conn:
            conn.execute("INSERT INTO tag (pub_id, name) VALUES (?, ?)",
                         (pub, "traced-tag"))
        row = None
        for _ in range(200):
            await asyncio.sleep(0.05)
            row = lib_b.db.query_one(
                "SELECT * FROM tag WHERE pub_id = ?", (pub,))
            if row is not None:
                ring = tracing.recent_spans(limit=512)
                if any(r["span"] == "sync.serve" for r in ring) and \
                        any(r["span"] == "sync.pull" for r in ring):
                    break
        assert row is not None and row["name"] == "traced-tag"
        ring = tracing.recent_spans(limit=512)
        serves = [r for r in ring if r["span"] == "sync.serve"]
        pulls = [r for r in ring if r["span"] == "sync.pull"]
        assert serves and pulls, [r["span"] for r in ring]
        serve = serves[-1]
        same_trace = [p for p in pulls if p["trace"] == serve["trace"]]
        assert same_trace, (serve, pulls)
        # the pull span is a CHILD of the serving node's span — the
        # traceparent crossed the wire, not just a coincidental id
        assert any(p.get("parent") == serve["id"] for p in same_trace)
        await a.shutdown()
        await b.shutdown()

    asyncio.run(main())


# -- rspc route + CLI -------------------------------------------------------

def test_node_trace_export_route(tmp_path):
    """node.trace.export serves a schema-valid document over rspc."""
    from spacedrive_tpu.api.router import mount_router
    from spacedrive_tpu.node import Node

    node = Node(str(tmp_path / "data"))
    router = mount_router(node)

    async def main():
        with tracing.span("rpc/warmup"):
            pass
        doc = await router.dispatch("node.trace.export")
        assert flight.validate_chrome_trace(doc) == []
        assert doc["otherData"]["node"] == node.config.name
        assert any(e.get("ph") == "X" for e in doc["traceEvents"])

    asyncio.run(main())


def test_trace_export_cli_self_check(tmp_path):
    """`python -m tools.trace_export --json` is the tier-1 schema
    gate: exit 0 + a valid document on stdout; a corrupted artifact
    fed back through --input exits non-zero naming the violation."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "tools.trace_export", "--json"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert flight.validate_chrome_trace(doc) == []

    # corrupt it: drop a thread_name metadata event
    doc["traceEvents"] = [
        e for e in doc["traceEvents"]
        if not (e.get("ph") == "M" and e.get("name") == "thread_name")]
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    out = subprocess.run(
        [sys.executable, "-m", "tools.trace_export", "--input",
         str(bad)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 1
    assert "thread_name" in out.stderr
