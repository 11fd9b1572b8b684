"""Flight recorder: pipeline timeline, cross-node trace propagation,
and the Chrome-trace exporter (spacedrive_tpu/flight.py +
tools/trace_export.py).

Pins the PR's acceptance shapes on CPU:
- a depth-3 sim-link identify run exports a schema-valid Chrome-trace
  JSON with per-device stage/H2D/kernel/retire lanes and per-batch
  bound attribution, race-recorder-clean (the autouse sanitizer
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

def test_recorder_window_bound_attribution():
    """A retired batch emits one `window` event naming the binding
    component of max(stage, h2d, kernel) and the margin over the
    runner-up."""
    rec = flight.FlightRecorder()
    run = flight.new_run_token()
    t = 100.0
    rec.record("stage", batch=7, t0=t, t1=t + 0.020, stream=1, run=run)
    rec.record("h2d", batch=7, t0=t + 0.020, t1=t + 0.070, device="0",
               run=run)
    rec.record("kernel", batch=7, t0=t + 0.070, t1=t + 0.080,
               device="0", run=run)
    rec.record("retire", batch=7, t0=t + 0.080, t1=t + 0.085, run=run)
    snap = rec.snapshot()
    assert [e["lane"] for e in snap] == [
        "stage", "h2d", "kernel", "retire", "window"]
    win = snap[-1]
    assert win["batch"] == 7
    assert win["binding"] == "h2d"
    # the window inherits the batch's DEVICE stream (h2d/kernel carry
    # it; the shared retire pool does not) so attribution names which
    # stream was bound
    assert win["device"] == "0"
    # margin = h2d (50 ms) - stage (20 ms), in µs with rounding slack
    assert win["margin_us"] == pytest.approx(30_000, abs=200)
    assert set(win["phases_us"]) == {"stage", "h2d", "kernel", "retire"}
    # the whole-batch window spans first stage start → retire end
    assert win["dur_us"] == pytest.approx(85_000, abs=200)


def test_recorder_contract_quiet_under_threads():
    """The threadctx half of the timeline ring: a post-arm recorder's
    _open dict is container-tracked, and concurrent record() storms
    from worker threads — every mutation under the declared _lock —
    stay data_race-quiet (the autouse sanitizer fixture asserts zero
    violations) while every batch still closes to exactly one window."""
    import threading

    from spacedrive_tpu import threadctx

    rec = flight.FlightRecorder()
    if threadctx.armed():
        assert type(rec._open).__name__ == "_TrackedDict"
    run = flight.new_run_token()

    def work(base):
        for i in range(50):
            b = base + i
            rec.record("stage", batch=b, t0=1.0, t1=2.0, run=run)
            rec.record("h2d", batch=b, t0=2.0, t1=3.0, device="0",
                       run=run)
            rec.record("kernel", batch=b, t0=3.0, t1=3.5, device="0",
                       run=run)
            rec.record("retire", batch=b, t0=3.5, t1=4.0, run=run)

    threads = [threading.Thread(target=work, args=(k * 1000,))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wins = [e for e in rec.snapshot() if e["lane"] == "window"]
    assert len(wins) == 200
    assert rec._open == {}  # every batch's window closed at retire


def test_recorder_runless_scopes_never_accumulate_windows():
    """Scopes that never retire (identify host-plane chunks pass no
    run token) are pure lane events: thousands of them must leave the
    open-window map EMPTY — the review-round leak regression (a
    long-running node hashes chunks forever)."""
    rec = flight.FlightRecorder()
    for i in range(1000):
        rec.record("stage", batch=i, t0=1.0, t1=2.0, scope="identify")
        rec.record("kernel", batch=i, t0=2.0, t1=3.0,
                   scope="identify")
    assert rec._open == {}
    # and even WITH run tokens, abandoned windows stop at the cap
    for i in range(flight._OPEN_CAP * 2):
        rec.record("stage", batch=0, t0=1.0, t1=2.0,
                   run=flight.new_run_token())
    assert len(rec._open) == flight._OPEN_CAP


def test_recorder_runs_do_not_collide_on_batch_numbers():
    """Two runs both dispatching a 'batch 3' keep separate windows:
    each retire closes ITS run's phases (the review-round collision
    regression — mixing runs corrupted one window and dropped the
    other)."""
    rec = flight.FlightRecorder()
    r1, r2 = flight.new_run_token(), flight.new_run_token()
    rec.record("stage", batch=3, t0=1.0, t1=1.1, run=r1)
    rec.record("stage", batch=3, t0=2.0, t1=2.5, run=r2)
    rec.record("h2d", batch=3, t0=1.1, t1=1.2, device="0", run=r1)
    rec.record("h2d", batch=3, t0=2.5, t1=2.6, device="0", run=r2)
    rec.record("kernel", batch=3, t0=1.2, t1=1.25, device="0", run=r1)
    rec.record("kernel", batch=3, t0=2.6, t1=2.65, device="0", run=r2)
    rec.record("retire", batch=3, t0=1.25, t1=1.3, run=r1)
    rec.record("retire", batch=3, t0=2.65, t1=2.7, run=r2)
    wins = [e for e in rec.snapshot() if e["lane"] == "window"]
    assert len(wins) == 2
    # run 2's stage (500 ms) binds; run 1's (100 ms) binds too — and
    # neither window spans the other run's timestamps
    assert all(w["binding"] == "stage" for w in wins)
    assert wins[0]["dur_us"] == pytest.approx(300_000, abs=200)
    assert wins[1]["dur_us"] == pytest.approx(700_000, abs=200)
    assert rec._open == {}


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
    run = flight.new_run_token()
    t = 10.0
    for batch in (1, 2):
        b = t + batch * 0.1
        rec.record("stage", batch=batch, t0=b, t1=b + 0.03,
                   stream=batch % 2, trace="feed", run=run)
        rec.record("h2d", batch=batch, t0=b + 0.03, t1=b + 0.05,
                   device=str(batch % 2), trace="feed", run=run)
        rec.record("kernel", batch=batch, t0=b + 0.05, t1=b + 0.06,
                   device=str(batch % 2), trace="feed", run=run)
        rec.record("retire", batch=batch, t0=b + 0.06, t1=b + 0.07,
                   trace="feed", run=run)
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
    # the per-device lanes and the bound-attribution lane exist
    lane_names = {e["args"]["name"] for e in meta
                  if e["name"] == "thread_name"}
    assert {"dev0 h2d", "dev1 h2d", "dev0 kernel", "dev1 kernel",
            "retire", "dev0 window", "dev1 window"} <= lane_names
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


# -- the depth-3 acceptance shape -------------------------------------------

def test_depth3_sim_link_run_exports_valid_trace(tmp_path, monkeypatch):
    """A depth-3 sim-link identify run over two device streams exports
    a schema-valid Chrome trace with per-device stage/H2D/kernel/
    retire lanes and per-batch bound attribution — and the multi-
    stream timeline writes are race-recorder-clean (the autouse
    sanitizer fixture + the armed threadctx recorder assert that
    half)."""
    import jax

    from spacedrive_tpu.ops import overlap
    from tools.overlap_bench import _cheap_kernel

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs the multi-device virtual mesh")
    monkeypatch.setenv("SDTPU_SIM_LINK_GBPS", "0.02")
    flight.RECORDER.clear()
    corpus = overlap.make_sparse_corpus(str(tmp_path), 32 * 8,
                                        120_000, 32)
    _res, stats = overlap.run_overlapped(
        corpus, kernel=_cheap_kernel, depth=3, devices=devs[:2],
        calibrate_every=len(corpus))
    snap = flight.RECORDER.snapshot()

    # every measured batch got all four phases + a window
    measured = len(corpus) - 1
    by_lane = {}
    for ev in snap:
        by_lane.setdefault(ev["lane"], []).append(ev)
    for lane in ("stage", "h2d", "kernel", "retire", "window"):
        assert len(by_lane[lane]) == measured, (
            lane, {k: len(v) for k, v in by_lane.items()})
    # both device streams carried h2d/kernel work
    assert {e["device"] for e in by_lane["h2d"]} == {"0", "1"}
    # all events share the pipeline.run span's trace id
    traces = {e.get("trace") for e in snap}
    assert len(traces) == 1 and None not in traces
    ring = tracing.recent_spans(limit=tracing.span_ring_capacity())
    run_spans = [r for r in ring if r["span"] == "pipeline.run"]
    assert run_spans and run_spans[-1]["trace"] in traces
    # bound attribution: with the simulated link binding, h2d windows
    # dominate; every window names a real component with real phases
    # and the device stream it was bound on
    for win in by_lane["window"]:
        assert win["binding"] in ("stage", "h2d", "kernel")
        assert win["phases_us"]["h2d"] > 0
        assert win["device"] in ("0", "1")
    assert any(w["binding"] == "h2d" for w in by_lane["window"])
    assert {w["device"] for w in by_lane["window"]} == {"0", "1"}

    # and the export is schema-valid with the per-device lanes visible
    doc = flight.chrome_trace(node_name="depth3")
    assert flight.validate_chrome_trace(doc) == []
    lane_names = {e["args"]["name"] for e in doc["traceEvents"]
                  if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"dev0 h2d", "dev1 h2d", "dev0 kernel", "dev1 kernel",
            "retire", "dev0 window", "dev1 window"} <= lane_names
    out = tmp_path / "trace.json"
    out.write_text(json.dumps(doc))
    assert json.loads(out.read_text())["otherData"]["node"] == "depth3"


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


def test_identify_jax_plane_records_phase_lanes(tmp_path):
    """On the jax backend the identify lanes come from the phase
    boundaries (stage+pack, dispatch, fetch): stage, h2d and retire,
    back to back, and no host-clock kernel lane — the kernel is on the
    device trace."""
    from spacedrive_tpu.ops.staging import cas_ids_for_files

    files = []
    for i, size in enumerate((700, 5000)):
        p = tmp_path / f"f{i}.bin"
        p.write_bytes(bytes([i + 1]) * size)
        files.append((str(p), size))
    flight.RECORDER.clear()
    ids, errors = cas_ids_for_files(files, backend="jax")
    assert not errors and len(ids) == 2
    snap = [e for e in flight.RECORDER.snapshot()
            if e.get("scope") == "identify"]
    assert [e["lane"] for e in snap] == ["stage", "h2d", "retire"]
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


def test_overlap_bench_trace_flag(tmp_path, monkeypatch):
    """`overlap_bench --trace` ships a schema-valid trace artifact
    next to the sweep JSON (exercised in-process via run_sweep + the
    same export path the flag drives)."""
    from tools import overlap_bench

    flight.RECORDER.clear()
    rows = overlap_bench.run_sweep(
        depths=[3], links=[0.125], batch=64, batches=4,
        cheap_kernel=True, calibrate_every=4)
    assert rows and rows[0]["measured_files_per_sec"] > 0
    doc = flight.chrome_trace(node_name="overlap_bench")
    assert flight.validate_chrome_trace(doc) == []
    lanes = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert any(n.endswith("window") for n in lanes), lanes
