"""Perf-trajectory collation: one table over every committed BENCH
round.

The repo records one `BENCH_rNN.json` per growth round, but the
artifact shape evolved with the harnesses: driver-wrapped rounds
(`{n, cmd, rc, tail, parsed}`, r07 wraps a fleet-observatory snapshot)
and raw load_bench artifacts (r08/r09). The kernel and identify-ring
rounds were removed with the harnesses that wrote them.
Reading the trajectory therefore meant opening many files with several
schemas. This tool normalizes every round into one row — headline
metric, unit, and the round's own gate/validity verdict — validates
each against its shape (exit 1 on any schema problem: the committed
history must stay machine-readable), and renders the markdown table
the README perf section embeds between its `bench-trend` markers.

    python -m tools.bench_trend                 # table to stdout
    python -m tools.bench_trend --json          # rows as JSON
    python -m tools.bench_trend --write-readme  # splice into README.md
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BEGIN = "<!-- bench-trend:begin (generated: python -m tools.bench_trend --write-readme) -->"
END = "<!-- bench-trend:end -->"


def load_rounds(root: str) -> List[Tuple[int, Dict[str, Any]]]:
    rounds = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        with open(path, encoding="utf-8") as f:
            rounds.append((int(m.group(1)), json.load(f)))
    return rounds


def _fmt(v: float) -> str:
    if v >= 1e6:
        return f"{v / 1e6:.2f}M"
    if v >= 1e4:
        return f"{v / 1e3:.0f}k"
    if v >= 100:
        return f"{v:.0f}"
    return f"{v:g}"


def normalize(n: int, doc: Dict[str, Any]) -> Dict[str, Any]:
    """One BENCH round -> one row. `problems` non-empty means the
    committed artifact no longer matches its declared shape."""
    row: Dict[str, Any] = {"round": n, "bench": "?", "value": None,
                           "unit": "", "note": "", "problems": []}
    probs = row["problems"]

    # Driver-wrapped rounds carry the real artifact under `parsed`.
    if "parsed" in doc and isinstance(doc.get("parsed"), dict):
        if doc.get("rc") not in (0, None):
            probs.append(f"r{n:02d}: recorded rc={doc.get('rc')}")
        doc = doc["parsed"]

    metric = doc.get("metric") or doc.get("bench")
    if metric == "fleet_observatory":
        row["bench"] = "fleet observatory"
        nodes = doc.get("nodes")
        row["unit"] = "nodes"
        row["value"] = len(nodes) if isinstance(nodes, list) else None
        remote = doc.get("remote_row") or {}
        row["note"] = ("remote reachable"
                       if remote.get("reachable") else "remote stale")
        if row["value"] is None:
            probs.append(f"r{n:02d}: fleet nodes missing")
    elif metric == "load_bench":
        row["bench"] = "fleet load storm"
        row["unit"] = "ops/s"
        pull = (doc.get("workloads") or {}).get("pull_storm") or {}
        row["value"] = pull.get("ops_per_s")
        gate = doc.get("gate") or {}
        notes = ["gate PASS" if gate.get("passed") else "gate FAIL"]
        inc = doc.get("incidents")
        if isinstance(inc, dict):
            notes.append(f"{len(inc.get('headers') or [])} incident "
                         "bundle(s)")
        row["note"] = ", ".join(notes)
        if not gate.get("passed"):
            probs.append(f"r{n:02d}: recorded load_bench gate failed")
        if not isinstance(row["value"], (int, float)):
            probs.append(f"r{n:02d}: pull_storm rate missing")
    else:
        probs.append(f"r{n:02d}: unrecognized artifact shape "
                     f"(metric={metric!r})")
    return row


def render_table(rows: List[Dict[str, Any]]) -> str:
    out = ["| Round | Bench | Headline | Notes |",
           "|---|---|---|---|"]
    for r in rows:
        v = (f"{_fmt(r['value'])} {r['unit']}"
             if isinstance(r["value"], (int, float)) else "—")
        out.append(f"| r{r['round']:02d} | {r['bench']} | {v} "
                   f"| {r['note']} |")
    return "\n".join(out)


def write_readme(table: str, readme_path: str) -> bool:
    with open(readme_path, encoding="utf-8") as f:
        text = f.read()
    if BEGIN not in text or END not in text:
        print(f"bench_trend: no {BEGIN!r} markers in {readme_path}",
              file=sys.stderr)
        return False
    head, rest = text.split(BEGIN, 1)
    _, tail = rest.split(END, 1)
    new = f"{head}{BEGIN}\n{table}\n{END}{tail}"
    if new != text:
        with open(readme_path, "w", encoding="utf-8") as f:
            f.write(new)
    return True


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Collate BENCH_r*.json rounds into the perf "
                    "trajectory table")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--json", action="store_true",
                    help="emit normalized rows as JSON")
    ap.add_argument("--write-readme", action="store_true",
                    help="splice the table between README.md's "
                         "bench-trend markers")
    args = ap.parse_args(argv)

    rounds = load_rounds(args.root)
    if not rounds:
        print("bench_trend: no BENCH_r*.json found", file=sys.stderr)
        return 1
    rows = [normalize(n, doc) for n, doc in rounds]
    problems = [p for r in rows for p in r["problems"]]
    for p in problems:
        print(f"bench_trend: SCHEMA: {p}", file=sys.stderr)

    if args.json:
        print(json.dumps({"metric": "bench_trend", "rows": rows}))
    else:
        table = render_table(rows)
        if args.write_readme:
            if not write_readme(table,
                                os.path.join(args.root, "README.md")):
                return 1
            print(f"bench_trend: wrote {len(rows)} rows into README.md",
                  file=sys.stderr)
        else:
            print(table)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
