"""Chrome-trace exporter CLI: the flight recorder's operator face.

Turns the span ring (spacedrive_tpu/tracing.py) plus the pipeline
timeline (spacedrive_tpu/flight.py) into a schema-valid Chrome-trace/
Perfetto JSON artifact, and VALIDATES every document it touches — the
schema gate (`flight.validate_chrome_trace`) is the same one the
golden-file test pins, so a malformed trace fails here (exit 1), not
on the bench host.

    python -m tools.trace_export --json                # self-check
    python -m tools.trace_export --json --out t.json   # + write it
    python -m tools.trace_export --url http://host:port --out t.json
    python -m tools.trace_export --input exported.json # validate only
    python -m tools.trace_export --fleet --json        # fleet-merge self-check
    python -m tools.trace_export --fleet --trace-id ID --url http://host:port

- `--json` runs the built-in SELF-CHECK: a synthetic two-batch
  pipeline timeline plus a nested span tree goes through the real
  recorder + exporter, the result is validated and printed as JSON.
  Non-zero exit on any schema violation — tier-1 runs this so the
  exporter cannot rot silently.
- `--url` pulls a LIVE node's trace over the rspc HTTP route
  (`GET /rspc/node.trace.export`), validates, and writes it — the
  operator path for "what was that node just doing".
- `--input` validates an existing artifact (CI gating a stored trace).
- `--fleet` switches to the fleet observatory: with `--url` +
  `--trace-id` it pulls ONE assembled multi-node trace from
  `fleet.trace.export` (the serving node fetches every paired peer's
  obs.trace slice and merges the lanes, skew-aligned); with `--json`
  it runs the fleet-merge SELF-CHECK — two synthetic node captures
  with a known clock skew go through `flight.fleet_chrome_trace`, and
  the result must validate with both per-node pid lanes present, the
  skew recorded in metadata, and the remote lane shifted onto the
  local axis.

Open the artifact in chrome://tracing or https://ui.perfetto.dev.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_self_check_trace() -> dict:
    """A deterministic exporter input exercising every lane kind: a
    nested + a cross-"node" continued span tree through the real
    tracing machinery, and a two-batch two-device pipeline timeline
    through a private FlightRecorder (the process one is left alone)."""
    from spacedrive_tpu import flight, tracing

    with tracing.span("rpc/trace.selfCheck"):
        tp = tracing.traceparent()
        with tracing.span("job/self-check"):
            with tracing.span("job.step", step=1):
                pass
    # The continued half: what a remote node's spans look like.
    with tracing.continue_trace(tp):
        with tracing.span("sync.pull", library="self-check"):
            pass

    rec = flight.FlightRecorder()
    t0 = time.perf_counter()
    for batch, dev in ((1, "0"), (2, "1")):
        b = t0 + batch * 0.010
        rec.record("stage", batch=batch, t0=b, t1=b + 0.004,
                   stream=batch % 2, trace="selfcheck")
        rec.record("h2d", batch=batch, t0=b + 0.004, t1=b + 0.007,
                   device=dev, trace="selfcheck")
        rec.record("kernel", batch=batch, t0=b + 0.007, t1=b + 0.008,
                   device=dev, trace="selfcheck")
        rec.record("retire", batch=batch, t0=b + 0.008, t1=b + 0.009,
                   trace="selfcheck")
    spans = [r for r in tracing.recent_spans(
        limit=tracing.span_ring_capacity()) if "ts_us" in r]
    return flight.chrome_trace(spans=spans, timeline=rec.snapshot(),
                               node_name="self-check")


def build_fleet_self_check_trace() -> dict:
    """Deterministic fleet-merge input: two synthetic node captures —
    a 'serving' node with an rpc span + pipeline timeline and a
    'remote' node whose clock runs a known 2 s ahead — through the
    real merger. The remote lane must come out shifted onto the local
    axis with the skew recorded in metadata; fleet_problems() is the
    gate."""
    from spacedrive_tpu import flight, tracing

    with tracing.span("rpc/fleet.traceSelfCheck"):
        tp = tracing.traceparent()
        tid = tracing.current_trace_id()
    local_spans = [r for r in tracing.recent_spans(limit=8)
                   if r.get("trace") == tid]

    # The remote node's half: spans continued across the "wire", with
    # every wall timestamp 2 s in the future (its clock runs ahead).
    skew_s = 2.0
    with tracing.continue_trace(tp):
        with tracing.span("sync.pull", library="fleet-self-check"):
            pass
    remote_spans = []
    for r in tracing.recent_spans(limit=8):
        if r.get("trace") == tid and r.get("span") == "sync.pull":
            r = dict(r)
            r["ts_us"] = int(r["ts_us"] + skew_s * 1e6)
            remote_spans.append(r)

    rec = flight.FlightRecorder()
    t0 = time.perf_counter()
    rec.record("stage", batch=1, t0=t0, t1=t0 + 0.004, trace=tid)
    rec.record("h2d", batch=1, t0=t0 + 0.004, t1=t0 + 0.007,
               device="0", trace=tid)
    rec.record("kernel", batch=1, t0=t0 + 0.007, t1=t0 + 0.008,
               device="0", trace=tid)
    rec.record("retire", batch=1, t0=t0 + 0.008, t1=t0 + 0.009,
               trace=tid)

    return flight.fleet_chrome_trace(
        [{"node": "local", "spans": local_spans,
          "timeline": rec.snapshot(), "skew_s": 0.0},
         {"node": "remote", "spans": remote_spans, "timeline": [],
          "skew_s": skew_s}],
        trace=tid, fleet_name="fleet self-check")


def fleet_problems(doc: dict) -> list:
    """Semantic gate over an assembled fleet trace, on top of the
    schema gate: per-node lanes present and the skew metadata
    recorded — what --fleet --json pins in tier-1."""
    from spacedrive_tpu import flight

    problems = flight.validate_chrome_trace(doc)
    other = doc.get("otherData", {}) if isinstance(doc, dict) else {}
    names = other.get("nodes")
    if not isinstance(names, list) or len(names) < 2:
        problems.append(f"fleet trace: want >=2 node lanes, got "
                        f"{names!r}")
        return problems
    if not isinstance(other.get("clock_skew_s"), dict):
        problems.append("fleet trace: clock_skew_s metadata missing")
    for i, name in enumerate(names):
        pid_spans = 2 * i + 1
        if not any(ev.get("ph") == "X" and ev.get("pid") == pid_spans
                   for ev in doc.get("traceEvents", [])):
            problems.append(
                f"fleet trace: node {name} contributed no span events")
    tid = other.get("trace")
    if tid:
        traces = {ev.get("args", {}).get("trace")
                  for ev in doc.get("traceEvents", [])
                  if ev.get("ph") == "X"
                  and isinstance(ev.get("pid"), int)
                  and ev["pid"] % 2 == 1}
        if traces - {tid}:
            problems.append(
                f"fleet trace: span lanes carry foreign trace ids "
                f"{sorted(traces - {tid})}")
    return problems


def fetch_fleet_trace(url: str, trace_id: str) -> dict:
    """GET /rspc/fleet.trace.export for one trace id from a live
    node's API host (the node assembles across its paired peers)."""
    import urllib.parse

    q = urllib.parse.quote(json.dumps({"trace": trace_id}))
    endpoint = url.rstrip("/") + "/rspc/fleet.trace.export?input=" + q
    with urllib.request.urlopen(endpoint, timeout=120) as resp:
        payload = json.load(resp)
    doc = payload.get("result") if isinstance(payload, dict) else None
    if doc is None:
        raise SystemExit(f"no result in response from {endpoint}")
    return doc


def fetch_live_trace(url: str) -> dict:
    """GET /rspc/node.trace.export from a live node's API host."""
    endpoint = url.rstrip("/") + "/rspc/node.trace.export"
    with urllib.request.urlopen(endpoint, timeout=30) as resp:
        payload = json.load(resp)
    doc = payload.get("result") if isinstance(payload, dict) else None
    if doc is None:
        raise SystemExit(f"no result in response from {endpoint}")
    return doc


def main(argv=None) -> int:
    from spacedrive_tpu import flight

    ap = argparse.ArgumentParser(
        description="Export/validate flight-recorder Chrome traces")
    ap.add_argument("--json", action="store_true",
                    help="build the self-check trace, validate it, and "
                         "print it as JSON (exit 1 on schema violation)")
    ap.add_argument("--url", default="", metavar="http://host:port",
                    help="pull a live node's node.trace.export, "
                         "validate, and write/print it")
    ap.add_argument("--input", default="", metavar="PATH",
                    help="validate an existing Chrome-trace JSON file")
    ap.add_argument("--out", default="", metavar="PATH",
                    help="write the (validated) trace document here")
    ap.add_argument("--fleet", action="store_true",
                    help="fleet mode: assembled multi-node traces "
                         "(--url needs --trace-id; --json runs the "
                         "fleet-merge self-check)")
    ap.add_argument("--trace-id", default="", metavar="HEX",
                    help="trace id to assemble across the fleet "
                         "(--fleet --url mode)")
    args = ap.parse_args(argv)

    if sum(map(bool, (args.json, args.url, args.input))) != 1:
        ap.error("exactly one of --json / --url / --input is required")

    if args.fleet and args.url and not args.trace_id:
        ap.error("--fleet --url needs --trace-id (which trace to "
                 "assemble)")

    if args.json:
        doc = build_fleet_self_check_trace() if args.fleet \
            else build_self_check_trace()
    elif args.url:
        doc = fetch_fleet_trace(args.url, args.trace_id) if args.fleet \
            else fetch_live_trace(args.url)
    else:
        try:
            with open(args.input, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"trace_export: unreadable {args.input}: {e}",
                  file=sys.stderr)
            return 1

    problems = fleet_problems(doc) if args.fleet \
        else flight.validate_chrome_trace(doc)
    for p in problems:
        print(f"trace_export: SCHEMA: {p}", file=sys.stderr)
    if problems:
        print(f"trace_export: {len(problems)} schema violation(s)",
              file=sys.stderr)
        return 1

    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
        print(f"trace_export: wrote {args.out} "
              f"({len(doc['traceEvents'])} events)", file=sys.stderr)
    if args.json:
        print(json.dumps(doc))
    elif not args.out:
        print(f"trace_export: valid "
              f"({len(doc['traceEvents'])} events)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
