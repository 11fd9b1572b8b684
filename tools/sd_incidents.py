"""sd_incidents — the incident observatory's postmortem triage CLI.

Reads the black box the incident observatory keeps
(spacedrive_tpu/incidents.py): every bundle is a snapshot-frozen
causal evidence slice (trigger attribution, flight timeline + spans
filtered to implicated traces, log-ring tail, chaos/backoff/timeout/
shed counters, SQL top-statements, health states, flags, capacity
profile) — this tool lists, renders, and diffs them without the
process that produced them.

    python -m tools.sd_incidents --url http://host:port          # list
    python -m tools.sd_incidents --dir DATA/incidents            # offline list
    python -m tools.sd_incidents --show ID  [--url|--dir ...]    # one bundle
    python -m tools.sd_incidents --diff A B [--url|--dir ...]    # two bundles
    python -m tools.sd_incidents --input bundle.json             # validate only
    python -m tools.sd_incidents --json [--out PATH]             # self-check

- `--dir` triages a COPIED store directory (the bundle files are
  self-contained JSON; scp them off a sick node and read them here).
- `--input` validates a stored artifact — a single bundle file, a
  header, or a `{"incidents": [...]}` artifact (CI gating).
- `--json` without `--url` runs the built-in SELF-CHECK: the same
  three synthetic saturations sd_top's gate drives (a shedding
  channel, a slow store write lock, a fired timeout budget) plus one
  exhausted backoff ladder are pushed through a real HealthMonitor
  and a real observatory; the run must freeze exactly FOUR distinct
  bundles, each schema-valid and attributing the right declared
  resource by name, and repeat pressure inside the dedup window must
  collapse into sd_incident_deduped_total instead of new files.
  Non-zero exit on any violation — tier-1 runs this so the capture
  path cannot rot silently, same pattern as `sd_top --json`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.parse
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _fetch_rspc(url: str, path: str, params: dict = None) -> object:
    q = ""
    if params:
        q = "?input=" + urllib.parse.quote(json.dumps(params))
    endpoint = url.rstrip("/") + "/rspc/" + path + q
    with urllib.request.urlopen(endpoint, timeout=30) as resp:
        payload = json.load(resp)
    if not isinstance(payload, dict) or "result" not in payload:
        raise SystemExit(f"no result in response from {endpoint}")
    return payload["result"]


def _load_store(dir_path: str) -> list:
    """Every complete bundle file in a store directory, newest-first
    (the offline half of incidents.list: torn/.tmp files are skipped,
    exactly what boot-time recovery would discard)."""
    bundles = []
    try:
        names = sorted(os.listdir(dir_path))
    except OSError as e:
        raise SystemExit(f"sd_incidents: unreadable {dir_path}: {e}")
    for fn in names:
        if not fn.endswith(".json"):
            continue
        try:
            with open(os.path.join(dir_path, fn), encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(doc, dict) and doc.get("bundle") == "incident":
            bundles.append(doc)
    bundles.sort(key=lambda b: -(b.get("ts") or 0))
    return bundles


def _headers(args) -> list:
    from spacedrive_tpu.incidents import bundle_header

    if args.url:
        return _fetch_rspc(args.url, "incidents.list") or []
    return [bundle_header(b) for b in _load_store(args.dir)]


def _bundle(args, bundle_id: str) -> dict:
    if args.url:
        return _fetch_rspc(args.url, "incidents.get",
                           {"id": bundle_id})
    doc = next((b for b in _load_store(args.dir)
                if b.get("id") == bundle_id), None)
    if doc is None:
        raise SystemExit(f"sd_incidents: no bundle {bundle_id!r} "
                         f"in {args.dir}")
    return doc


def _fmt_ts(ts) -> str:
    if not isinstance(ts, (int, float)):
        return "-"
    return time.strftime("%m-%d %H:%M:%S", time.localtime(ts))


def render_list(headers: list, width: int = 120) -> str:
    out = [f"{'ID':<26} {'WHEN':<15} {'KIND':<18} {'SEV':<4} "
           f"{'ACK':<4} RESOURCE — REASON"]
    for h in headers:
        t = h.get("trigger") or {}
        out.append(
            f"{h.get('id', '?'):<26} {_fmt_ts(h.get('ts')):<15} "
            f"{t.get('kind', '?'):<18} {t.get('severity', '-'):<4} "
            f"{'yes' if h.get('ack') else 'no':<4} "
            f"{t.get('resource', '?')} — {t.get('reason', '')}"[:width])
    if len(out) == 1:
        out.append("(no incident bundles)")
    return "\n".join(out)


def _flat_counters(counters: dict, prefix: str = "") -> dict:
    """Counter stage → flat {family{labels}: value} for diffing; the
    stage values are family snapshot_value() shapes (scalars for plain
    counters, nested dicts for labeled ones)."""
    flat = {}
    for k, v in sorted((counters or {}).items()):
        key = f"{prefix}{k}"
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            flat[key] = v
        elif isinstance(v, dict):
            flat.update(_flat_counters(v, prefix=f"{key}/"))
    return flat


def render_bundle(b: dict, width: int = 100) -> str:
    """One bundle as a triage page: attribution first, then the
    evidence sections sized, then the loudest counters."""
    t = b.get("trigger") or {}
    node = b.get("node") or {}
    out = [
        f"incident {b.get('id')}  [{t.get('kind')}]  "
        f"sev={t.get('severity')}  "
        f"{'acked' if b.get('ack') else 'OPEN'}",
        f"  at    {_fmt_ts(b.get('ts'))}  on "
        f"{node.get('name') or '?'} ({(node.get('id') or '')[:12]})",
        f"  what  {t.get('subsystem')}/{t.get('resource')}",
        f"  why   {t.get('reason')}"[:width],
    ]
    ev = t.get("evidence") or {}
    if ev:
        out.append("  evidence:")
        for k, v in list(ev.items())[:8]:
            out.append(f"    {k} = {json.dumps(v)[:width - 10]}")
    out.append(
        f"  frozen: {len(b.get('timeline') or [])} timeline events, "
        f"{len(b.get('spans') or [])} spans "
        f"({len(b.get('traces') or [])} traces), "
        f"{len(b.get('logs') or [])} log lines")
    health = b.get("health")
    if isinstance(health, dict):
        states = health.get("states") or {}
        hot = {s: st for s, st in sorted(states.items())
               if st != "ok"}
        out.append(f"  health: {json.dumps(hot) if hot else 'all ok'}")
    sql = b.get("sql_top") or []
    if sql:
        out.append("  sql_top: " + ", ".join(
            f"{s.get('statement')}={s.get('total'):g}"
            for s in sql if isinstance(s, dict)))
    flat = _flat_counters(b.get("counters"))
    loud = sorted(((k, v) for k, v in flat.items() if v),
                  key=lambda kv: -abs(kv[1]))[:10]
    if loud:
        out.append("  counters (loudest):")
        for k, v in loud:
            out.append(f"    {k:<58} {v:g}")
    return "\n".join(out)


def render_diff(a: dict, b: dict, width: int = 100) -> str:
    """Two bundles side by side: the trigger lines, every counter
    family that moved between the freezes, and health-state changes —
    'what got worse between these two postmortems'."""
    out = []
    for tag, doc in (("A", a), ("B", b)):
        t = doc.get("trigger") or {}
        out.append(f"{tag}  {doc.get('id')}  {_fmt_ts(doc.get('ts'))}  "
                   f"[{t.get('kind')}] {t.get('subsystem')}/"
                   f"{t.get('resource')}"[:width])
    fa, fb = (_flat_counters(a.get("counters")),
              _flat_counters(b.get("counters")))
    moved = []
    for k in sorted(set(fa) | set(fb)):
        va, vb = fa.get(k, 0), fb.get(k, 0)
        if va != vb:
            moved.append((k, va, vb))
    out.append("")
    if moved:
        out.append(f"{'COUNTER':<56} {'A':>10} {'B':>10} {'Δ':>10}")
        for k, va, vb in moved:
            out.append(f"{k[:56]:<56} {va:>10g} {vb:>10g} "
                       f"{vb - va:>+10g}")
    else:
        out.append("(no counter movement between the bundles)")
    sa = ((a.get("health") or {}).get("states") or {})
    sb = ((b.get("health") or {}).get("states") or {})
    changed = {s: (sa.get(s, "-"), sb.get(s, "-"))
               for s in sorted(set(sa) | set(sb))
               if sa.get(s) != sb.get(s)}
    if changed:
        out.append("")
        out.append("HEALTH STATES (A -> B):")
        for s, (va, vb) in changed.items():
            out.append(f"  {s:<12} {va} -> {vb}")
    return "\n".join(out)


# -- validation + self-check -------------------------------------------------

def input_problems(doc: object) -> list:
    """Validate a stored artifact: a full bundle file, a bare header,
    a list of either, a `{"incidents": [...]}` artifact body, or a
    BENCH artifact whose `incidents` section is the bench shape
    `{"enabled", "headers", "deduped"}` (load_bench --json output
    validates directly)."""
    from spacedrive_tpu.incidents import (
        validate_incident_bundle,
        validate_incident_header,
    )

    def one(d, where):
        if not isinstance(d, dict):
            return [f"{where}: not an object"]
        if d.get("bundle") == "incident" or "timeline" in d:
            return [f"{where}: {p}"
                    for p in validate_incident_bundle(d)]
        return [f"{where}: {p}" for p in validate_incident_header(d)]

    if isinstance(doc, dict) and isinstance(doc.get("incidents"), dict) \
            and isinstance(doc["incidents"].get("headers"), list):
        doc = {"incidents": doc["incidents"]["headers"]}
    if isinstance(doc, dict) and isinstance(doc.get("incidents"), list):
        problems = []
        for i, d in enumerate(doc["incidents"]):
            problems.extend(one(d, f"incidents[{i}]"))
        return problems
    if isinstance(doc, list):
        problems = []
        for i, d in enumerate(doc):
            problems.extend(one(d, f"[{i}]"))
        return problems
    return one(doc, "bundle")


def build_self_check() -> dict:
    """Drive the capture path end to end against a real observatory:
    sd_top's three known saturations plus one exhausted backoff
    ladder, then repeat pressure to prove dedup."""
    import shutil
    import tempfile

    from spacedrive_tpu import channels, health, incidents, timeouts
    from spacedrive_tpu.telemetry import (
        STORE_WRITE_LOCK_WAIT_SECONDS,
        TIMEOUTS_FIRED,
    )

    tmp = tempfile.mkdtemp(prefix="sd_incidents_check_")
    monitor = health.HealthMonitor(
        interval_s=0.05, node_id="sd-incidents",
        node_name="sd-incidents")
    obs = incidents.install(
        dir_path=tmp, monitor=monitor, node_id="sd-incidents",
        node_name="sd-incidents")
    if obs is None:
        raise SystemExit("sd_incidents: SDTPU_INCIDENTS is off — the "
                         "self-check needs the observatory")
    try:
        # 1-3: the same seeded trio as sd_top --json (channel shed,
        # store write-lock wait, fired network budget)...
        ch = channels.channel("bench.shed")
        for i in range(2 * ch.capacity):
            ch.put_nowait(i)
        STORE_WRITE_LOCK_WAIT_SECONDS.observe(0.8)
        TIMEOUTS_FIRED.labels(name="p2p.ping").inc()
        time.sleep(0.06)  # a real (if tiny) window for the rates
        monitor.sample()  # -> three health.saturated bundles
        # 4: one exhausted ladder (obs.http: finite max_tries)
        ladder = timeouts.Backoff("obs.http")
        while ladder.next_delay() is not None:
            pass          # -> one backoff.give_up bundle
        # Repeat pressure INSIDE the dedup window: the shedding
        # channel's depth gauge persists so the next sample fires the
        # same fingerprint again, and a second exhausted ladder
        # re-fires obs.http — both must dedup, not write files.
        monitor.sample()
        ladder2 = timeouts.Backoff("obs.http")
        while ladder2.next_delay() is not None:
            pass
        headers = obs.list()
        bundles = [obs.get(h["id"]) for h in headers]
        return {
            "metric": "sd_incidents",
            "source": "self-check",
            "incidents": bundles,
            "deduped": obs.deduped(),
        }
    finally:
        incidents.uninstall()
        shutil.rmtree(tmp, ignore_errors=True)


def self_check_problems(artifact: dict) -> list:
    """Schema + semantic gate over the self-check artifact: exactly
    four distinct bundles, each valid, each attributing the seeded
    fault's declared resource by name, repeats deduped."""
    problems = input_problems(artifact)
    bundles = [b for b in artifact.get("incidents", [])
               if isinstance(b, dict)]
    want = {
        "bench.shed": "health.saturated",
        "store.db.write_lock": "health.saturated",
        "p2p.ping": "health.saturated",
        "obs.http": "backoff.give_up",
    }
    got = {(b.get("trigger") or {}).get("resource"):
           (b.get("trigger") or {}).get("kind") for b in bundles}
    for resource, kind in want.items():
        if got.get(resource) != kind:
            problems.append(
                f"self-check: seeded {resource} not captured as "
                f"{kind} (got {got.get(resource)!r})")
    if len(bundles) != len(want):
        problems.append(
            f"self-check: want exactly {len(want)} bundles, got "
            f"{len(bundles)} — dedup failed or a surprise trigger "
            "fired")
    fps = [b.get("fingerprint") for b in bundles]
    if len(set(fps)) != len(fps):
        problems.append("self-check: duplicate fingerprints across "
                        "bundles — dedup identity is broken")
    deduped = artifact.get("deduped")
    if not isinstance(deduped, dict) or sum(deduped.values()) < 2:
        problems.append(
            "self-check: repeat pressure inside the window did not "
            f"dedup (deduped={deduped!r})")
    for b in bundles:
        if not b.get("counters"):
            problems.append(f"self-check: bundle {b.get('id')} froze "
                            "no counter families")
            break
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Incident bundle triage / artifact gate")
    ap.add_argument("--url", default="", metavar="http://host:port",
                    help="triage a live node over rspc HTTP")
    ap.add_argument("--dir", default="", metavar="PATH",
                    help="triage a (copied) incident store directory")
    ap.add_argument("--show", default="", metavar="ID",
                    help="render one full bundle")
    ap.add_argument("--diff", nargs=2, default=None,
                    metavar=("A", "B"),
                    help="diff two bundles (counter movement, health "
                         "state changes)")
    ap.add_argument("--json", action="store_true",
                    help="emit JSON (without --url/--dir: run the "
                         "built-in self-check; exit 1 on violation)")
    ap.add_argument("--input", default="", metavar="PATH",
                    help="validate an existing bundle/artifact file")
    ap.add_argument("--out", default="", metavar="PATH",
                    help="write the (validated) artifact here")
    args = ap.parse_args(argv)

    if args.input:
        try:
            with open(args.input, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"sd_incidents: unreadable {args.input}: {e}",
                  file=sys.stderr)
            return 1
        problems = input_problems(doc)
        for p in problems:
            print(f"sd_incidents: SCHEMA: {p}", file=sys.stderr)
        if problems:
            return 1
        print(f"sd_incidents: valid ({args.input})")
        return 0

    if not args.url and not args.dir:
        if not args.json:
            ap.error("need --url, --dir, --input, or --json")
        artifact = build_self_check()
        problems = self_check_problems(artifact)
        for p in problems:
            print(f"sd_incidents: SCHEMA: {p}", file=sys.stderr)
        if problems:
            print(f"sd_incidents: {len(problems)} violation(s)",
                  file=sys.stderr)
            return 1
        if args.out:
            with open(args.out, "w", encoding="utf-8") as f:
                json.dump(artifact, f, indent=1)
            print(f"sd_incidents: wrote {args.out}", file=sys.stderr)
        print(json.dumps(artifact))
        return 0

    if args.diff:
        a, b = (_bundle(args, args.diff[0]), _bundle(args, args.diff[1]))
        print(json.dumps({"a": a, "b": b}) if args.json
              else render_diff(a, b))
        return 0
    if args.show:
        doc = _bundle(args, args.show)
        print(json.dumps(doc) if args.json else render_bundle(doc))
        return 0
    headers = _headers(args)
    if args.json:
        artifact = {"metric": "sd_incidents",
                    "source": args.url or args.dir,
                    "incidents": headers}
        if args.out:
            with open(args.out, "w", encoding="utf-8") as f:
                json.dump(artifact, f, indent=1)
        print(json.dumps(artifact))
        return 0
    print(render_list(headers))
    return 0


if __name__ == "__main__":
    sys.exit(main())
