"""tools/load_bench.py wired into tier-1: the fleet-scale harness at
small scale — 32 simulated peers over stub transports with seeded
chaos armed — must run its storm, converge every clone, and pass its
own gate (zero violations, no wedged coalesce channel, per-peer clone
fairness over the floor, every saturation attributed to a declared
resource by name, every frozen incident bundle attributed likewise),
emitting a valid BENCH-style artifact (the committed BENCH_r08.json
is the same run at default scale)."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The tier-1 storm spec: DEFAULT_CHAOS plus a commit-weather delay
# (never raises, so no workload can hard-fail on it) that keeps the
# store visibly degraded through the write-heavy phases — the
# declared BUSY pressure the incident observatory must attribute.
STORM_CHAOS = (
    "sync.clone.page=disconnect:0.04;"
    "sync.ingest.apply=error:0.03,delay:5ms:0.2;"
    "api.http.dispatch=delay:10ms:0.5;"
    "api.ws.send=wedge:0.06;"
    "store.commit=error:0.1,delay:25ms:0.5")


def test_load_bench_gate_32_peers(tmp_path):
    out = tmp_path / "load.json"
    env = dict(os.environ)
    # Count-mode sanitizer inside the subprocess: the gate asserts
    # ZERO recorded violations instead of a mid-storm raise tearing
    # the run down half-measured. degraded-windows=1 makes the
    # storm's sustained store pressure visible to the observatory
    # within the run's few health checkpoints.
    env.update({"JAX_PLATFORMS": "cpu", "SDTPU_SANITIZE": "1",
                "SDTPU_SANITIZE_MODE": "count",
                "SDTPU_INCIDENT_DEGRADED_WINDOWS": "1"})
    proc = subprocess.run(
        [sys.executable, "-m", "tools.load_bench",
         "--peers", "32", "--waves", "1",
         "--events", "200", "--requests", "6", "--ops-per-peer", "24",
         "--chaos", STORM_CHAOS,
         "--json", str(out), "--gate"],
        cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]

    doc = json.loads(out.read_text())
    assert doc["bench"] == "load_bench"
    assert doc["gate"]["passed"], doc["gate"]["failures"]
    assert doc["violations"] == []
    assert doc["wedged_channels"] == []
    assert doc["config"]["peers"] == 32
    assert doc["config"]["chaos"]  # seeded chaos was armed

    # The storm really ran: every workload produced work.
    w = doc["workloads"]
    assert w["pull_storm"]["ops_pulled"] == 32 * 256
    assert w["clone_burst"]["fast_pages"] >= 1
    assert w["clone_burst"]["fairness"]["ratio"] >= \
        doc["config"]["fairness_floor"]
    assert w["api_fanin"]["ok"] >= 1
    assert w["ws_flood"]["delivered"] >= 1
    assert w["ingest_storm"]["ops_applied"] >= 1
    # Every clone peer converged on the full seeded corpus despite
    # injected faults (byte-level convergence is pinned by
    # test_chaos.py; the harness asserts the op counts line up).
    seeded = doc["config"]["seed_ops"]
    assert all(n == seeded
               for n in w["clone_burst"]["ops_applied_per_peer"])

    # Chaos injections were counted (the artifact can reconcile
    # observed degradation against injected cause)...
    injected = doc["counters"]["sd_chaos_injected_total"]["labeled"]
    assert sum(row["value"] for row in injected) >= 1
    # ...and every injected BUSY was absorbed by the declared
    # store.busy backoff (degraded to latency, not job failure).
    busy = [row["value"] for row in injected
            if row["labels"] == {"name": "store.commit",
                                 "kind": "error"}]
    gave_up = doc["counters"]["sd_backoff_gave_up_total"]["labeled"]
    assert not any(row["value"] > 0 for row in gave_up
                   if row["labels"]["name"] == "store.busy"), \
        (busy, gave_up)

    # Health samples carried attribution for whatever saturated (the
    # gate already enforced declared-name attribution).
    assert any(s["states"] for s in doc["health_samples"])

    # The storm auto-produced its own postmortem record: at least
    # three DISTINCT evidence bundles, one per injected pressure —
    # the fleet poller's exhausted obs.http ladder, the wedged/shed
    # API plane, and the BUSY-weathered store — each attributing the
    # declared resource by name, with the repeated ladder exhaustion
    # collapsed into the dedup counter instead of a duplicate bundle.
    from spacedrive_tpu.incidents import validate_incident_header

    inc = doc["incidents"]
    assert inc["enabled"]
    headers = inc["headers"]
    assert len({h["fingerprint"] for h in headers}) >= 3
    for h in headers:
        assert validate_incident_header(h) == [], h
    by_sub = {h["trigger"]["subsystem"] for h in headers}
    assert {"obs", "api", "store"} <= by_sub, headers
    resources = {h["trigger"]["resource"] for h in headers}
    assert "obs.http" in resources
    assert "api.http.inflight" in resources
    assert any(r.startswith("store.") for r in resources)
    assert sum(inc["deduped"].values()) >= 1, inc

    # And the artifact itself is sd_incidents --input-valid.
    check = subprocess.run(
        [sys.executable, "-m", "tools.sd_incidents",
         "--input", str(out)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=60)
    assert check.returncode == 0, check.stderr


def test_bench_trend_gate_and_readme_sync():
    """Every committed BENCH round must stay machine-readable by the
    trajectory collator, and the README's generated trend table must
    match what the collator renders today — regenerate with
    `python -m tools.bench_trend --write-readme` when a round lands."""
    from tools.bench_trend import (
        BEGIN,
        END,
        load_rounds,
        normalize,
        render_table,
    )

    rounds = load_rounds(ROOT)
    # The fleet and load-storm rounds r07-r09 stay; the remote-chip
    # kernel rounds and the identify-ring rounds left with their
    # harnesses.
    assert len(rounds) >= 3
    rows = [normalize(n, doc) for n, doc in rounds]
    assert [p for r in rows for p in r["problems"]] == []
    with open(os.path.join(ROOT, "README.md"), encoding="utf-8") as f:
        text = f.read()
    assert BEGIN in text and END in text
    embedded = text.split(BEGIN, 1)[1].split(END, 1)[0].strip()
    assert embedded == render_table(rows), (
        "README bench-trend table is stale — run "
        "python -m tools.bench_trend --write-readme")


def test_recorded_bench_artifact_is_valid():
    """The committed BENCH_r08.json (default-scale run of this
    harness) must stay schema-valid and gate-passing — a regression
    in the artifact writer or gate shows up here."""
    path = os.path.join(ROOT, "BENCH_r08.json")
    with open(path) as f:
        doc = json.load(f)
    assert doc["bench"] == "load_bench" and doc["schema"] == 1
    assert doc["gate"]["passed"] and not doc["gate"]["failures"]
    assert doc["violations"] == [] and doc["wedged_channels"] == []
    assert doc["config"]["peers"] >= 32
    assert doc["workloads"]["clone_burst"]["fairness"]["ratio"] >= \
        doc["config"]["fairness_floor"]
    # the recorded storm demonstrated reconnect recovery
    assert doc["workloads"]["clone_burst"]["reconnects"] >= 1
    injected = {(r["labels"]["name"], r["labels"]["kind"]): r["value"]
                for r in doc["counters"]
                ["sd_chaos_injected_total"]["labeled"]}
    assert injected.get(("sync.clone.page", "disconnect"), 0) >= 1
