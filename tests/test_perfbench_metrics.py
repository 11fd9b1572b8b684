"""The benchmark's per-layer readers of the program's phase counters: on a
synthetic run with two counter snapshots each returns the window delta in
its unit, and None when the program lacks the family (an older build)."""

import pytest

from perfbench import harness, spec


def _labeled(labelnames, rows):
    return {"kind": "counter", "labelnames": list(labelnames),
            "labeled": [{"labels": dict(zip(labelnames, key)), "value": v}
                        for key, v in rows.items()]}


def _snapshots():
    cas0 = {("stage",): 1.0, ("pack",): 2.0, ("dispatch",): 0.5,
            ("fetch",): 0.25}
    cas1 = {("stage",): 4.0, ("pack",): 6.0, ("dispatch",): 1.0,
            ("fetch",): 1.25}
    val0 = {("page", "rows"): 0.1, ("batched", "read"): 1.0,
            ("streamed", "read"): 2.0, ("batched", "pack"): 0.5,
            ("streamed", "pack"): 0.5, ("batched", "device"): 0.2,
            ("streamed", "device"): 0.0, ("streamed", "host_hash"): 3.0}
    val1 = {("page", "rows"): 0.2, ("batched", "read"): 3.0,
            ("streamed", "read"): 5.0, ("batched", "pack"): 1.5,
            ("streamed", "pack"): 2.5, ("batched", "device"): 1.2,
            ("streamed", "device"): 0.5, ("streamed", "host_hash"): 9.0}
    vb0 = {("batched", "file"): 100.0, ("batched", "grid"): 300.0,
           ("streamed", "file"): 1000.0, ("streamed", "grid"): 1000.0}
    vb1 = {("batched", "file"): 600.0, ("batched", "grid"): 2300.0,
           ("streamed", "file"): 3000.0, ("streamed", "grid"): 5000.0}
    c0 = {
        "sd_cas_phase_seconds_total": _labeled(("phase",), cas0),
        "sd_cas_device_bytes_total": {"kind": "counter", "value": 1000.0},
        "sd_cas_message_bytes_total": {"kind": "counter", "value": 500.0},
        "sd_event_loop_lag_seconds": {"kind": "histogram", "count": 10,
                                      "sum": 0.5},
        "sd_validator_phase_seconds_total": _labeled(("path", "phase"),
                                                     val0),
        "sd_validator_bytes_total": _labeled(("path", "kind"), vb0),
    }
    c1 = {
        "sd_cas_phase_seconds_total": _labeled(("phase",), cas1),
        "sd_cas_device_bytes_total": {"kind": "counter", "value": 7000.0},
        "sd_cas_message_bytes_total": {"kind": "counter", "value": 3500.0},
        "sd_event_loop_lag_seconds": {"kind": "histogram", "count": 510,
                                      "sum": 2.5},
        "sd_validator_phase_seconds_total": _labeled(("path", "phase"),
                                                     val1),
        "sd_validator_bytes_total": _labeled(("path", "kind"), vb1),
    }
    return c0, c1


def _run(counters0, counters1, kind):
    cell = spec.Cell("synthetic", 1, {}, {})
    run = harness.Run(cell, 1, 50, True, 0.0)
    run.t0, run.t1 = 100.0, 150.0
    run.counters0, run.counters1 = counters0, counters1
    if kind == "scan":
        run.rounds = [
            {"kind": "scan", "warm": True, "files": 999},
            {"kind": "scan", "warm": False, "files": 2000},
            {"kind": "scan", "warm": False, "files": 2000}]
    else:
        run.rounds = [
            {"kind": "verify", "warm": True, "file_bytes": 7},
            {"kind": "verify", "warm": False, "file_bytes": 2_000_000_000}]
    return run


# (metric, round kind, expected value on the synthetic window)
CASES = [
    ("cas_stage_ms_per_kfile.scan", "scan", 3.0 * 1e3 / 4),
    ("cas_pack_ms_per_kfile.scan", "scan", 4.0 * 1e3 / 4),
    ("cas_dispatch_ms_per_kfile.scan", "scan", 0.5 * 1e3 / 4),
    ("cas_fetch_ms_per_kfile.scan", "scan", 1.0 * 1e3 / 4),
    ("cas_padding_ratio.scan", "scan", 6000.0 / 3000.0),
    ("api_loop_lag_ms_per_s.scan", "scan", 2.0 * 1e3 / 50),
    ("validate_read_ms_per_gb.validate", "verify", (2.0 + 3.0) * 1e3 / 2),
    ("validate_pack_ms_per_gb.validate", "verify", (1.0 + 2.0) * 1e3 / 2),
    ("validate_device_ms_per_gb.validate", "verify", (1.0 + 0.5) * 1e3 / 2),
    ("validate_host_hash_ms_per_gb.validate", "verify", 6.0 * 1e3 / 2),
    ("validate_padding_ratio.validate", "verify",
     (2000.0 + 4000.0) / (500.0 + 2000.0)),
]


@pytest.mark.parametrize("name,kind,want", CASES,
                         ids=[c[0] for c in CASES])
def test_reader_returns_the_window_delta(name, kind, want):
    c0, c1 = _snapshots()
    got = spec.metric_reader(name)(_run(c0, c1, kind))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name,kind", [c[:2] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_reader_is_silent_without_the_family(name, kind):
    empty = {"sd_job_duration_seconds": {"kind": "histogram", "count": 0,
                                         "sum": 0.0}}
    assert spec.metric_reader(name)(_run(empty, dict(empty), kind)) is None


def test_every_new_reader_has_a_benchmark_entry():
    """Each reader above is a per_layer entry whose cells are the ones
    whose rounds it reads."""
    import json
    import os

    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, kind, _want in CASES:
        cells = per_layer[name]["workloads"]
        if kind == "scan":
            assert cells == ["scan.desktop", "scan.small"], name
        else:
            assert cells == ["validate.verify"], name
