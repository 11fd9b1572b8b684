"""From a JAX profiler trace to device busy time, kernel time and idle gaps.

A TPU trace (xplane.pb) holds one plane per chip, "/device:TPU:<n>", whose
"XLA Ops" line has one event per operation the chip ran, named by its
HLO text ("%_chunk_cvs_pallas_fast.1 = u32[...] custom-call(...)"), and a
"/host:CPU" plane whose lines carry host annotations such as the
benchmark's own `perfbench.*` TraceAnnotations. Times are nanoseconds on
the profiler's clock; `reduce_trace` maps the benchmark's perf_counter_ns
onto it through a marker annotation taken at a known perf_counter_ns.

- busy: the union of the op intervals inside the window, per chip,
  averaged over chips;
- kernel time: op durations summed by stable name (the HLO instruction
  name without its "%" and ".N" suffix), over all chips;
- idle gaps: the window minus chip 0's busy union, each stretch of a
  gap charged to the innermost host span (a round, a job, an API call)
  that covers it.
"""

from __future__ import annotations

import glob
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MARKER = "perfbench.clock"

Interval = Tuple[float, float]
Span = Tuple[str, float, float]   # (label, start, end), trace ns

_SUFFIX = re.compile(r"\.\d+$")


def op_name(hlo_text: str) -> str:
    """'%_chunk_cvs_pallas_fast.1 = u32[..] custom-call(..)' ->
    '_chunk_cvs_pallas_fast'."""
    head = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], w0: float, w1: float
         ) -> List[Interval]:
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def gaps(busy: Sequence[Interval], w0: float, w1: float) -> List[Interval]:
    out, t = [], w0
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < w1:
        out.append((t, w1))
    return out


def label_of(t: float, spans: Sequence[Span]) -> str:
    """The innermost (shortest) span covering t, or 'no span'."""
    best: Optional[Span] = None
    for sp in spans:
        if sp[1] <= t <= sp[2] and (best is None
                                     or sp[2] - sp[1] < best[2] - best[1]):
            best = sp
    return best[0] if best else "no span"


def reduce_ops(ops_by_device: Dict[str, List[Tuple[float, float, str]]],
               w0: float, w1: float, spans: Sequence[Span] = ()) -> dict:
    """ops_by_device: {device: [(start_ns, duration_ns, hlo text)]}.
    Returns busy_s, window_s, kernel_s {name: s} and idle_s {label: s}."""
    if not ops_by_device:
        raise ValueError("the trace holds no device operations")
    devices = sorted(ops_by_device)
    busy_by_dev = {}
    kernel_s: Dict[str, float] = {}
    for dev in devices:
        ivs = []
        for start, dur, name in ops_by_device[dev]:
            s, e = start, start + dur
            if e <= w0 or s >= w1:
                continue
            ivs.append((s, e))
            cs, ce = max(s, w0), min(e, w1)
            key = op_name(name)
            kernel_s[key] = kernel_s.get(key, 0.0) + (ce - cs) / 1e9
        busy_by_dev[dev] = merge(clip(ivs, w0, w1))
    busy_s = sum(sum(e - s for s, e in b) for b in busy_by_dev.values()) \
        / len(devices) / 1e9
    idle_s: Dict[str, float] = {}
    for s, e in gaps(busy_by_dev[devices[0]], w0, w1):
        cuts = sorted({s, e} | {t for sp in spans for t in sp[1:]
                               if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            label = label_of((a + b) / 2, spans)
            idle_s[label] = idle_s.get(label, 0.0) + (b - a) / 1e9
    return {"busy_s": busy_s, "window_s": (w1 - w0) / 1e9,
            "devices": len(devices), "kernel_s": kernel_s, "idle_s": idle_s}


def read_xplane(path: str):
    """(ops_by_device, host annotation starts {name: [ns]}) of one trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Tuple[float, float, str]]] = {}
    marks: Dict[str, List[float]] = {}
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (e.start_ns, e.duration_ns, e.name)
                        for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("perfbench."):
                        marks.setdefault(e.name, []).append(e.start_ns)
    return ops, marks


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return found[-1]


def reduce_trace(trace_dir: str, marker_perf_ns: int, w0_perf_ns: int,
                 w1_perf_ns: int, spans_perf_ns: Sequence[Span]) -> dict:
    """Reduce the trace in `trace_dir` over the benchmark's window, given
    in perf_counter_ns, through the marker annotation taken at
    `marker_perf_ns`."""
    ops, marks = read_xplane(find_xplane(trace_dir))
    if MARKER not in marks:
        raise ValueError(f"the trace lacks the {MARKER} annotation")
    offset = marks[MARKER][0] - marker_perf_ns
    spans = [(lab, s + offset, e + offset) for lab, s, e in spans_perf_ns]
    return reduce_ops(ops, w0_perf_ns + offset, w1_perf_ns + offset, spans)
