"""Structured tracing spans + event-bus emission."""

import logging
import os

import pytest

from spacedrive_tpu.tracing import device_span, span


class _Bus:
    def __init__(self):
        self.events = []

    def emit(self, e):
        self.events.append(e)


def test_span_times_and_emits():
    bus = _Bus()
    with span("unit.work", events=bus, batch=7):
        x = sum(range(1000))
    assert x
    assert len(bus.events) == 1
    e = bus.events[0]
    assert e["type"] == "TraceSpan" and e["span"] == "unit.work"
    assert e["batch"] == 7 and e["ms"] >= 0


def test_span_logs_at_debug(caplog):
    with caplog.at_level(logging.DEBUG, logger="spacedrive_tpu"):
        with span("logged.work"):
            pass
    assert any("logged.work" in r.message for r in caplog.records)


def test_device_span_without_profiler_is_plain_span():
    bus = _Bus()
    with device_span("dev.work", events=bus):
        pass
    assert bus.events[0]["span"] == "dev.work"


def test_span_survives_exceptions():
    bus = _Bus()
    try:
        with span("failing", events=bus):
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    assert bus.events and bus.events[0]["span"] == "failing"


def test_span_ok_and_error_fields():
    """A raising body is distinguishable from a clean one (the finally
    block used to emit identical records for both)."""
    bus = _Bus()
    with span("clean", events=bus):
        pass
    try:
        with span("raising", events=bus):
            raise KeyError("x")
    except KeyError:
        pass
    clean, raising = bus.events
    assert clean["ok"] is True and "error" not in clean
    assert raising["ok"] is False and raising["error"] == "KeyError"


def test_span_nesting_carries_trace_and_parent():
    bus = _Bus()
    with span("outer", events=bus):
        with span("inner", events=bus):
            pass
    inner, outer = bus.events  # inner finishes first
    assert inner["span"] == "inner" and outer["span"] == "outer"
    assert inner["trace"] == outer["trace"]
    assert inner["parent"] == outer["id"]
    assert "parent" not in outer  # root
    # sibling roots get fresh traces
    with span("other", events=bus):
        pass
    assert bus.events[-1]["trace"] != outer["trace"]


def test_spans_land_in_ring_buffer():
    from spacedrive_tpu.tracing import clear_span_ring, recent_spans

    clear_span_ring()
    with span("ringed", tag=1):
        pass
    got = recent_spans(limit=10)
    assert got and got[-1]["span"] == "ringed" and got[-1]["tag"] == 1
    trace = got[-1]["trace"]
    assert recent_spans(trace_id=trace)[-1]["id"] == got[-1]["id"]
    assert recent_spans(trace_id="nope") == []


def test_span_accepts_bare_callable_sink():
    got = []
    with span("callable.sink", events=got.append):
        pass
    assert got and got[0]["span"] == "callable.sink"


def test_profiler_probe_caches_negative_result(monkeypatch):
    """With SDTPU_PROFILE unset the env is read ONCE; later device_span
    calls are a cached attribute check until reset_profiler_cache()
    (the documented test hook) re-arms the probe."""
    from spacedrive_tpu import tracing

    reads = []
    real_environ = dict(os.environ)
    real_environ.pop("SDTPU_PROFILE", None)

    class CountingEnv(dict):
        def get(self, key, default=None):
            if key == "SDTPU_PROFILE":
                reads.append(key)
            return super().get(key, default)

    monkeypatch.setattr(tracing.os, "environ", CountingEnv(real_environ))
    tracing.reset_profiler_cache()
    assert tracing._ensure_profiler() is False
    assert tracing._ensure_profiler() is False
    assert tracing._ensure_profiler() is False
    assert len(reads) == 1, "negative probe not cached"
    tracing.reset_profiler_cache()
    assert tracing._ensure_profiler() is False
    assert len(reads) == 2, "reset hook must re-read the environment"


def test_span_records_start_timestamp():
    """Every record carries ts_us (wall µs at span start) — what the
    Chrome-trace exporter sorts and renders on one axis."""
    import time as _time

    from spacedrive_tpu.tracing import recent_spans

    before = _time.time() * 1e6
    with span("unit.work"):
        pass
    rec = recent_spans(limit=1)[-1]
    after = _time.time() * 1e6
    assert before - 2e6 <= rec["ts_us"] <= after + 2e6


def test_span_ring_capacity_flag(monkeypatch):
    """SDTPU_SPAN_RING sizes the ring; configure_span_ring() is the
    documented re-read hook (the flag itself is read once at import),
    keeping the newest records on shrink."""
    from spacedrive_tpu import tracing

    default_cap = tracing.span_ring_capacity()
    try:
        monkeypatch.setenv("SDTPU_SPAN_RING", "8")
        assert tracing.configure_span_ring() == 8
        for i in range(20):
            with span("unit.work", i=i):
                pass
        got = tracing.recent_spans(limit=100)
        assert len(got) == 8
        assert got[-1]["i"] == 19  # newest kept
    finally:
        monkeypatch.delenv("SDTPU_SPAN_RING", raising=False)
        tracing.configure_span_ring()
    assert tracing.span_ring_capacity() == default_cap
    from spacedrive_tpu import flags

    assert flags.FLAGS["SDTPU_SPAN_RING"].default == 512


def test_span_family_registry_shape():
    """declare_span enforces the family scheme and uniqueness; every
    family the engine uses is present."""
    from spacedrive_tpu import tracing

    assert {"cas_ids", "job", "job.step", "p2p", "rpc",
            "sync.pull", "sync.serve"} <= set(tracing.SPAN_FAMILIES)
    with pytest.raises(ValueError):
        tracing.declare_span("Bad/Family")
    with pytest.raises(ValueError):
        tracing.declare_span("job")  # duplicate


def test_staging_emits_device_spans(tmp_path):
    """The identifier's hashing kernel runs inside a device_span."""
    import logging as _logging

    from spacedrive_tpu.ops.staging import cas_ids_for_files

    p = tmp_path / "f.bin"
    p.write_bytes(b"x" * 5000)
    logger = _logging.getLogger("spacedrive_tpu")
    records = []
    handler = _logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    prev_level = logger.level
    logger.addHandler(handler)
    logger.setLevel(_logging.DEBUG)
    try:
        ids, errors = cas_ids_for_files([(str(p), 5000)], backend="numpy")
        assert not errors and ids[0]
    finally:
        logger.removeHandler(handler)
        logger.setLevel(prev_level)
    assert any("cas_ids/numpy" in m for m in records)
