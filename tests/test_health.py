"""Health observatory (spacedrive_tpu/health.py): telemetry delta
snapshots (exact under concurrency, cumulative families untouched),
windowed bucket-delta percentiles, the sampler's bounded rings, the
saturation engine's attribution — including the three-saturation
scenario gates (wedged ws consumer / held store write lock /
sim-link-throttled depth-N run) — the node.health query +
subscription surfaces, the sd_top CLI self-check, and the
SDTPU_LOG_JSON trace-correlated logging satellite."""

import asyncio
import io
import json
import logging
import os
import subprocess
import sys
import threading
import time

import pytest

from spacedrive_tpu import channels, health, telemetry, tracing
from spacedrive_tpu.telemetry import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

try:
    # Seed the objects package: in runtimes without `cryptography` the
    # first attempt fails but leaves the non-crypto submodules cached,
    # after which mount_router imports cleanly (container quirk; no-op
    # where the dependency exists).
    import spacedrive_tpu.objects  # noqa: F401
except ModuleNotFoundError:
    pass


def _run(coro):
    return asyncio.run(coro)


# -- delta snapshots (satellite 1) -------------------------------------------

def test_counter_snapshot_delta_telescopes():
    reg = MetricsRegistry()
    c = reg.counter("sd_jobs_hd_total")
    c.inc(3)
    d1 = c.snapshot_delta()
    assert d1["value"] == 3
    c.inc(2)
    d2 = c.snapshot_delta(d1["cursor"])
    assert d2["value"] == 2
    # cumulative value untouched by any number of delta readers
    assert c.value == 5
    # registry reset mid-window: the delta restarts, never negative
    c._zero()
    c.inc(1)
    d3 = c.snapshot_delta(d2["cursor"])
    assert d3["value"] == 1


def test_histogram_snapshot_delta_windows():
    reg = MetricsRegistry()
    h = reg.histogram("sd_jobs_hdh_seconds", buckets=(0.1, 1.0, 10.0))
    h.observe(0.05)
    h.observe(5.0)
    d1 = h.snapshot_delta()
    assert d1["count"] == 2 and d1["counts"] == [1, 0, 1, 0]
    h.observe(0.5)
    d2 = h.snapshot_delta(d1["cursor"])
    # ONLY the window's observations, per bucket, exactly
    assert d2["count"] == 1 and d2["counts"] == [0, 1, 0, 0]
    assert abs(d2["sum"] - 0.5) < 1e-9
    # the cumulative family never changed meaning: totals monotone
    s = h.snapshot_value()
    assert s["count"] == 3 and s["buckets"][-1] == ["+Inf", 3]
    # reset mid-window restarts the delta instead of going negative
    h._zero()
    h.observe(0.05)
    d3 = h.snapshot_delta(d2["cursor"])
    assert d3["count"] == 1 and d3["counts"][0] == 1


def test_delta_snapshots_exact_totals_under_concurrency():
    """Writers hammer a histogram + counter while a reader takes
    windowed deltas mid-flight: the windows must telescope to the
    exact totals (nothing lost, nothing double-counted) — and the
    race recorder is armed suite-wide, so the declared
    telemetry.Histogram ownership contract audits every write."""
    reg = MetricsRegistry()
    h = reg.histogram("sd_jobs_hdc_seconds", buckets=(0.5,))
    c = reg.counter("sd_jobs_hdc_total")
    n_threads, per = 8, 2000
    stop = threading.Event()
    got = {"count": 0, "buckets": [0, 0], "value": 0.0}
    hcur = ccur = None

    def drain():
        nonlocal hcur, ccur
        dh = h.snapshot_delta(hcur)
        hcur = dh["cursor"]
        dc = c.snapshot_delta(ccur)
        ccur = dc["cursor"]
        got["count"] += dh["count"]
        got["buckets"][0] += dh["counts"][0]
        got["buckets"][1] += dh["counts"][1]
        got["value"] += dc["value"]

    def reader():
        while not stop.is_set():
            drain()

    def writer(i):
        for k in range(per):
            h.observe(0.25 if k % 2 else 0.75)
            c.inc()

    r = threading.Thread(target=reader)
    r.start()
    ws = [threading.Thread(target=writer, args=(i,))
          for i in range(n_threads)]
    for w in ws:
        w.start()
    for w in ws:
        w.join()
    stop.set()
    r.join()
    drain()  # the residual window after the last mid-flight read
    total = n_threads * per
    assert got["count"] == total
    assert got["buckets"] == [total // 2, total // 2]
    assert got["value"] == total
    # cumulative untouched by the windowed reader
    assert h.count == total and c.value == total


def test_windowed_quantile_interpolation():
    buckets = (0.1, 1.0, 10.0)
    assert health.windowed_quantile(buckets, [0, 0, 0, 0], 0.99) is None
    # one observation in (0.1, 1.0]: interpolates inside that bucket
    p50 = health.windowed_quantile(buckets, [0, 1, 0, 0], 0.5)
    assert 0.1 < p50 <= 1.0
    # uniform mass: p50 lands mid-scale, p99 near the top bucket
    p50 = health.windowed_quantile(buckets, [10, 10, 10, 0], 0.5)
    assert abs(p50 - 0.55) < 1e-9  # halfway into the middle bucket
    # +Inf observations clamp to the top finite bound
    assert health.windowed_quantile(buckets, [0, 0, 0, 5], 0.99) == 10.0


# -- sampler + rings ---------------------------------------------------------

def test_sampler_windows_and_bounded_rings():
    mon = health.HealthMonitor(interval_s=0.05)
    c = telemetry.REGISTRY.counter("sd_jobs_hsr_total")
    cap = channels.capacity("health.series")
    for _ in range(5):
        c.inc(10)
        time.sleep(0.002)
        snap = mon.sample()
    rec = snap["window"]["sd_jobs_hsr_total"]
    assert rec["kind"] == "counter" and rec["delta"] == 10
    assert rec["rate"] > 0
    # every ring stays within the declared health.series capacity
    for _ in range(cap + 20):
        mon.sample()
    assert mon._series, "sampler built no series rings"
    assert all(len(ring) <= channels.capacity("health.series")
               for ring in mon._series.values())
    # the state gauge family is live for every base subsystem
    g = telemetry.REGISTRY.get("sd_health_state")
    for sub in health.BASE_SUBSYSTEMS:
        child = g.labels(subsystem=sub)
        assert child.value in (0.0, 1.0, 2.0)


def test_health_monitor_emits_periodic_snapshots():
    from spacedrive_tpu.node import EventBus

    async def main():
        bus = EventBus()
        got = []
        bus.subscribe(got.append)
        mon = health.HealthMonitor(bus, interval_s=0.05)
        mon.start()
        await asyncio.sleep(0.3)
        mon.stop()
        snaps = [e for e in got if e["type"] == "HealthSnapshot"]
        assert snaps, "no HealthSnapshot events emitted"
        assert health.validate_health_snapshot(snaps[0]["health"]) == []
        assert snaps[0]["health"]["window_s"] is not None
    _run(main())


def test_sheds_expected_contracts():
    """History rings age by design: their sheds are not saturation
    evidence (the health engine skips them), and the contract is
    declared, not engine-hardcoded."""
    for name in ("health.series", "health.snapshots",
                 "ops.pipeline.timeline", "jobs.worker.commands"):
        assert channels.CHANNELS[name].sheds_expected, name
    for name in ("api.ws", "jobs.manager.queue", "media.thumbs"):
        assert not channels.CHANNELS[name].sheds_expected, name


# -- the three-saturation scenario gates (acceptance criteria) ---------------

def test_scenario_wedged_ws_consumer_attributed_to_api_ws():
    """A websocket subscriber that stops reading: the api.ws channel
    fills to its declared capacity and sheds — node.health must
    attribute the api subsystem's saturation to `api.ws` by its
    declared name within one sampling interval."""
    from spacedrive_tpu.api.server import WsSubscriptionPump

    async def main():
        mon = health.HealthMonitor(interval_s=0.05)
        stall = asyncio.Event()

        async def stalled_send(payload):
            await stall.wait()

        pump = WsSubscriptionPump(stalled_send, owner="test-health-ws")
        cap = pump.chan.capacity
        # distinct (un-coalescible) events, synchronously — the
        # wedged drainer never gets scheduled in between
        for i in range(3 * cap):
            pump.offer({"id": 1, "type": "event",
                        "data": {"type": "Notification", "n": i}})
        assert len(pump.chan) == cap
        snap = mon.sample()  # ONE sampling interval
        assert snap["states"]["api"] == "saturated"
        top = snap["attribution"]["api"][0]
        assert top["resource"] == "api.ws"
        assert top["owner"] == channels.CHANNELS["api.ws"].owner
        key = "sd_chan_depth{name=api.ws}"
        assert top["evidence"][key] == cap
        assert top["evidence"]["capacity"] == cap
        assert top["evidence"]["sd_chan_shed_total{name=api.ws}"] > 0
        # evidence series inline: the depth ring tail rides along
        assert key in top["points"] and top["points"][key]
        stall.set()
        await pump.stop()
    _run(main())


def test_scenario_held_write_lock_attributed_to_store(tmp_path):
    """A held store write lock: concurrent writers observe long
    sd_store_write_lock_wait waits — the store subsystem saturates,
    attributed to store.db.write_lock, while the CUMULATIVE histogram
    keeps its meaning (monotone totals, never reset) and the windowed
    p99 moves back down once the contention passes."""
    from spacedrive_tpu.store.db import Database

    db = Database(str(tmp_path / "lock.db"))
    hist = telemetry.REGISTRY.get("sd_store_write_lock_wait_seconds")
    cum_before = hist.count
    mon = health.HealthMonitor(interval_s=0.05)
    release = threading.Event()

    def holder():
        with db.tx() as conn:
            conn.execute(
                "INSERT INTO tag (pub_id, name) VALUES (?, ?)",
                (os.urandom(16), "held"))
            release.wait(timeout=10)

    def waiter():
        with db.tx() as conn:
            conn.execute(
                "INSERT INTO tag (pub_id, name) VALUES (?, ?)",
                (os.urandom(16), "waited"))

    t1 = threading.Thread(target=holder)
    t1.start()
    time.sleep(0.15)  # the holder owns the write lock
    t2 = threading.Thread(target=waiter)
    t2.start()
    time.sleep(0.6)
    release.set()
    t1.join(timeout=10)
    t2.join(timeout=10)
    assert not t2.is_alive()

    snap = mon.sample()  # within one sampling interval of the wait
    assert snap["states"]["store"] == "saturated"
    top = snap["attribution"]["store"][0]
    assert top["resource"] == "store.db.write_lock"
    assert top["doc"]  # named by its declared registry doc
    p99 = snap["window"]["sd_store_write_lock_wait_seconds"]["p99"]
    assert p99 is not None and p99 >= health.LOCK_WAIT_SATURATED_S
    # cumulative family unchanged in meaning: totals only grew
    assert hist.count > cum_before
    cum_after = hist.count
    # an idle window later: the WINDOWED p99 empties out while the
    # cumulative count stands — exactly what cumulative-forever
    # histograms could not express
    time.sleep(0.05)
    snap2 = mon.sample()
    assert snap2["window"][
        "sd_store_write_lock_wait_seconds"]["p99"] is None
    assert snap2["states"]["store"] == "ok"
    assert hist.count == cum_after
    db.close()


# -- surfaces ----------------------------------------------------------------

def test_node_health_query_and_subscription(tmp_path):
    from spacedrive_tpu.api.router import mount_router
    from spacedrive_tpu.node import Node

    node = Node(str(tmp_path / "data"))
    router = mount_router(node)
    # the path is BOTH a query and a subscription (split namespaces)
    assert "node.health" in router.procedures
    assert "node.health" in router.subscriptions

    async def main():
        snap = await router.dispatch("node.health")
        assert health.validate_health_snapshot(snap) == []
        assert set(health.BASE_SUBSYSTEMS) <= set(snap["states"])
        got = []
        unsub = await router.subscribe("node.health", None, got.append)
        # one immediately on subscribe, validated payload
        assert got and got[0]["type"] == "HealthSnapshot"
        assert health.validate_health_snapshot(got[0]["health"]) == []
        unsub()
    _run(main())
    _run(node.shutdown())


def test_ws_pump_coalesces_health_snapshots_newest_wins():
    from spacedrive_tpu.api.server import WsSubscriptionPump

    async def main():
        stall = asyncio.Event()

        async def stalled_send(payload):
            await stall.wait()

        pump = WsSubscriptionPump(stalled_send, owner="test-health-co")
        for seq in (1, 2, 3):
            pump.offer({"id": 1, "type": "event",
                        "data": {"type": "HealthSnapshot", "seq": seq}})
        assert len(pump.chan) == 1  # coalesced
        frame = pump.chan.get_nowait()
        assert frame["data"]["seq"] == 3  # newest wins
        stall.set()
        await pump.stop()
    _run(main())


def test_health_state_served_on_metrics_endpoint(tmp_path):
    """GET /metrics carries the sd_health_state{subsystem} gauges a
    scraper alerts on."""
    mon = health.HealthMonitor(interval_s=0.05)
    mon.sample()
    text = telemetry.render_prometheus()
    assert "# TYPE sd_health_state gauge" in text
    assert 'sd_health_state{subsystem="store"}' in text


def test_sd_top_cli_self_check(tmp_path):
    """`python -m tools.sd_top --json` is the tier-1 gate: exit 0 +
    a schema-valid artifact whose three induced saturations are
    attributed to the right declared resources; a corrupted artifact
    fed back through --input exits non-zero naming the violation."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "tools.sd_top", "--json"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["metric"] == "sd_top"
    assert health.validate_health_snapshot(doc["health"]) == []
    assert doc["health"]["states"]["store"] == "saturated"

    # corrupt: state/severity consistency broken
    doc["health"]["states"]["store"] = "ok"
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    out2 = subprocess.run(
        [sys.executable, "-m", "tools.sd_top", "--input", str(bad)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out2.returncode == 1
    assert "inconsistent" in out2.stderr


def test_sd_top_live_url_fetch(tmp_path):
    """The operator path: sd_top's fetchers pull node.health AND
    node.metrics from a live API host over rspc HTTP and render one
    frame with the cumulative context in the header."""
    from spacedrive_tpu.api.server import ApiServer
    from spacedrive_tpu.node import Node
    from tools.sd_top import fetch_health, fetch_metrics, render_top

    async def main():
        node = Node(str(tmp_path / "data"))
        server = ApiServer(node)
        port = await server.start(port=0)
        try:
            url = f"http://127.0.0.1:{port}"
            snap = await asyncio.to_thread(fetch_health, url)
            assert health.validate_health_snapshot(snap) == []
            metrics = await asyncio.to_thread(fetch_metrics, url)
            frame = render_top(snap, source=url, metrics=metrics)
            assert "SUBSYSTEM" in frame and "families=" in frame
        finally:
            await server.stop()
            await node.shutdown()
    _run(main())


def test_render_top_frame():
    from tools.sd_top import render_top

    mon = health.HealthMonitor(interval_s=0.05)
    time.sleep(0.02)
    snap = mon.sample()
    frame = render_top(snap, source="unit-test")
    assert "SUBSYSTEM" in frame and "unit-test" in frame
    for sub in health.BASE_SUBSYSTEMS:
        assert sub in frame


def test_bench_health_section_flow():
    """The bench embedding flow (cursors before the run, one sample
    after) produces a schema-clean health section — the shape
    perf_smoke --telemetry ships."""
    mon = health.HealthMonitor(interval_s=0.05)
    time.sleep(0.02)
    snap = mon.sample()
    section = {"window_s": snap["window_s"], "states": snap["states"],
               "attribution": snap["attribution"]}
    assert health.validate_health_snapshot(snap) == []
    assert json.dumps(section)  # JSON-safe artifact body


# -- SDTPU_LOG_JSON (satellite 2) -------------------------------------------

def test_json_log_formatter_stamps_span_trace_id():
    buf = io.StringIO()
    assert tracing.install_json_logging(force=True, stream=buf)
    try:
        logger = logging.getLogger("spacedrive_tpu")
        with tracing.span("rpc/log-probe"):
            expected = tracing.current_trace_id()
            logger.warning("inside span %d", 7)
        rec = json.loads(buf.getvalue().strip().splitlines()[-1])
        assert rec["msg"] == "inside span 7"
        assert rec["level"] == "WARNING"
        assert rec["trace"] == expected
        assert "span" in rec
        logger.warning("outside any span")
        rec2 = json.loads(buf.getvalue().strip().splitlines()[-1])
        assert "trace" not in rec2
    finally:
        tracing.uninstall_json_logging()


def test_json_log_trace_survives_to_thread():
    buf = io.StringIO()
    assert tracing.install_json_logging(force=True, stream=buf)
    try:
        logger = logging.getLogger("spacedrive_tpu")

        async def main():
            with tracing.span("job/log-thread-probe"):
                expected = tracing.current_trace_id()
                await asyncio.to_thread(
                    logger.warning, "from a worker thread")
            return expected

        expected = _run(main())
        rec = json.loads(buf.getvalue().strip().splitlines()[-1])
        assert rec["trace"] == expected
    finally:
        tracing.uninstall_json_logging()


def test_json_logging_flag_gate(monkeypatch):
    monkeypatch.setenv("SDTPU_LOG_JSON", "0")
    assert not tracing.install_json_logging()
    monkeypatch.setenv("SDTPU_LOG_JSON", "1")
    assert tracing.install_json_logging()
    assert tracing.install_json_logging()  # idempotent
    tracing.uninstall_json_logging()


# -- perf_smoke embeds a health stage (satellite 4) --------------------------

def _has_cryptography() -> bool:
    try:
        import cryptography  # noqa: F401
        return True
    except ModuleNotFoundError:
        return False


@pytest.mark.skipif(
    not _has_cryptography(),
    reason="perf_smoke imports objects.dedup, whose package init "
           "needs the cryptography module")
def test_perf_smoke_embeds_health_stage(tmp_path):
    from tools.perf_smoke import run as smoke_run

    out = tmp_path / "smoke.json"
    _run(smoke_run(files=40, backend="auto", images=0,
                   keep=str(tmp_path / "work"),
                   with_telemetry=True, json_out=str(out)))
    doc = json.loads(out.read_text())
    stages = {s["stage"]: s for s in doc["stages"]}
    assert "health" in stages, sorted(stages)
    h = stages["health"]
    assert set(health.BASE_SUBSYSTEMS) <= set(h["states"])
    assert h["window_s"] and h["window_s"] > 0
    assert all(v in health.STATES for v in h["states"].values())
