"""One run of one cell: set-up, the measured window, the trace, the check.

The system under test is the spacedrive_tpu node as `serve` runs it: a
Node with its API server on a loopback port, driven over the websocket
frames the web UI sends. Everything in one process holds the chip; the
corpus writers and the browse client are spawned processes that never
import JAX.

The round runners (rounds.py) do the traffic's work; this module owns the
clock, the compile log, the counters, the profiler and the result line.
"""

from __future__ import annotations

import asyncio
import json
import os
import shutil
import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from . import spec as spec_mod
from .spec import Cell

ROOT = spec_mod.ROOT
WORK = os.path.join(ROOT, ".perfbench_work")
COMPILE_CACHE = os.path.join(ROOT, ".jax_cache")


def log(record: dict) -> None:
    print(json.dumps(record), flush=True)


@dataclass
class Run:
    """What a run measured; the metric readers read it."""
    cell: Cell
    seed: int
    seconds: int
    trace: bool
    t_process: float
    device_kind: str = ""
    setup_s: float = 0.0
    t0: float = 0.0
    t1: float = 0.0
    rounds: List[dict] = field(default_factory=list)
    queries: List[Tuple[float, float, float, bool]] = field(
        default_factory=list)
    counters0: Dict[str, Any] = field(default_factory=dict)
    counters1: Dict[str, Any] = field(default_factory=dict)
    compiles: List[float] = field(default_factory=list)
    spans: List[Tuple[str, int, int]] = field(default_factory=list)
    trace_reduction: Optional[dict] = None
    memory_peak_bytes: Optional[int] = None
    marks: List[Tuple[str, float]] = field(default_factory=list)
    compile_log: Any = None

    def mark(self, name: str) -> None:
        """End of a set-up phase, for the set-up record."""
        self.marks.append((name, time.perf_counter()))

    def window_rounds(self) -> List[dict]:
        return [r for r in self.rounds if not r.get("warm")]

    def compiles_in_window(self) -> int:
        return sum(1 for t in self.compiles if self.t0 <= t <= self.t1)

    def counter_delta(self, family: str, labels: Optional[dict] = None,
                      key: str = "value") -> Optional[float]:
        """Window delta of a program counter or histogram field."""
        def value(snap):
            fam = snap.get(family)
            if fam is None:
                return None
            if "labeled" in fam:
                hits = [e for e in fam["labeled"]
                        if labels is None or all(
                            e["labels"].get(k) == v
                            for k, v in labels.items())]
                return sum(e.get(key, 0.0) for e in hits) if hits else 0.0
            return fam.get(key, 0.0)
        a, b = value(self.counters0), value(self.counters1)
        return None if a is None or b is None else b - a

    @contextmanager
    def span(self, label: str):
        """A host span of the benchmark's own, annotated in the trace."""
        cm = nullcontext()
        if self.trace:
            import jax

            cm = jax.profiler.TraceAnnotation(f"perfbench.{label}")
        s = time.perf_counter_ns()
        with cm:
            yield
        self.spans.append((label, s, time.perf_counter_ns()))


class CompileLog:
    """Host-clock times of every backend compile, and the persistent
    cache's hits and misses (jax.monitoring)."""

    def __init__(self, run: Run):
        import jax.monitoring as monitoring

        self._lock = threading.Lock()
        self.run = run
        self.cache = {"hits": 0, "misses": 0}
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.run.compiles.append(time.perf_counter())

    def _event(self, event, **kw):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache["misses"] += 1


class JobLog:
    """Start and end of every job on the host clock: its first JobProgress
    event (a job reports its task count as it starts) and its JobUpdate."""

    def __init__(self, node):
        self.starts: Dict[str, float] = {}
        self.ends: List[Tuple[float, float, str, int]] = []
        node.events.subscribe(self._on_event)

    def _on_event(self, e: dict) -> None:
        now = time.perf_counter()
        if e.get("type") == "JobProgress":
            self.starts.setdefault(e["id"], now)
        elif e.get("type") == "JobUpdate":
            self.ends.append((self.starts.get(e["id"], now), now,
                              e["name"], e["status"]))

    def between(self, t0: float, t1: float
                ) -> List[Tuple[float, float, str, int]]:
        """(start, end, name, status) of the jobs that ended in [t0, t1]."""
        return [x for x in self.ends if t0 <= x[1] <= t1]


class Rpc:
    """The websocket frames the web UI sends, over one connection."""

    def __init__(self, ws):
        self.ws = ws
        self._id = 0

    async def call(self, kind: str, path: str, input_: dict):
        import aiohttp

        self._id += 1
        await self.ws.send_json({"id": self._id, "type": kind,
                                 "path": path, "input": input_})
        while True:
            msg = await asyncio.wait_for(self.ws.receive(), timeout=600)
            if msg.type != aiohttp.WSMsgType.TEXT:
                raise RuntimeError(f"{path}: websocket closed ({msg})")
            frame = json.loads(msg.data)
            if frame.get("id") != self._id:
                continue
            if frame["type"] == "error":
                raise RuntimeError(f"{path}: {frame}")
            return frame["result"]


@dataclass
class Context:
    """What a round runner gets: the run, the node and its API."""
    run: Run
    node: Any
    rpc: Rpc
    port: int
    jobs: JobLog
    work: str


def require_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"the benchmark runs on a TPU; JAX's default "
                         f"device is {devs[0].platform!r}")
    if len(devs) != chips:
        raise SystemExit(f"the cell asks for {chips} chip(s); JAX sees "
                         f"{len(devs)}")
    return devs


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


async def _drive(run: Run, runner_cls, work: str):
    """Boot the node, run set-up and the window, collect; returns the
    round runner, whose check() runs once the node is down."""
    import aiohttp
    import jax

    from spacedrive_tpu import telemetry
    from spacedrive_tpu.api.server import ApiServer
    from spacedrive_tpu.node import Node

    node = Node(os.path.join(work, "node"))
    await node.start()
    server = ApiServer(node)
    port = await server.start("127.0.0.1", 0)
    run.mark("node_start")
    runner = None
    try:
        async with aiohttp.ClientSession() as http:
            async with http.ws_connect(f"http://127.0.0.1:{port}/rspc",
                                       max_msg_size=0) as ws:
                ctx = Context(run, node, Rpc(ws), port, JobLog(node), work)
                runner = runner_cls(ctx)
                await runner.setup()
                trace_dir = os.path.join(work, "trace")
                if run.trace:
                    jax.profiler.start_trace(
                        trace_dir, profiler_options=_profile_options())
                    marker = time.perf_counter_ns()
                    with jax.profiler.TraceAnnotation("perfbench.clock"):
                        pass
                run.counters0 = telemetry.snapshot()
                run.t0 = time.perf_counter()
                run.setup_s = run.t0 - run.t_process
                prev, phases = run.t_process, {}
                for name, t in run.marks + [("to_window", run.t0)]:
                    phases[name], prev = t - prev, t
                log({"phase": "setup", "seconds": run.setup_s,
                     "split_s": phases, "compiles": len(run.compiles),
                     "compile_cache": run.compile_log.cache})
                run.rounds += await runner.window(run.t0, run.seconds)
                run.t1 = max(r["t1"] for r in run.window_rounds())
                run.counters1 = telemetry.snapshot()
                if run.trace:
                    jax.profiler.stop_trace()
                run.memory_peak_bytes = max(
                    (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in jax.local_devices())
                await runner.finish()
                if run.trace:
                    from . import trace as trace_mod

                    run.trace_reduction = await asyncio.to_thread(
                        trace_mod.reduce_trace, trace_dir, marker,
                        int(run.t0 * 1e9), int(run.t1 * 1e9),
                        [(lab, s, e) for lab, s, e in run.spans])
                await runner.collect()
    finally:
        if runner is not None:
            runner.close()
        await server.stop()
        await node.shutdown()
    return runner


def run_cell(cell: Cell, seed: int, seconds: int, trace: bool,
             t_process: float, require_chip: bool = True,
             control: Optional[str] = None) -> dict:
    """Run the cell; returns the result line's object, its checks last."""
    for k, v in cell.config.get("env", {}).items():
        os.environ[k] = str(v)
    # Only a run's first in a checkout compiles: every program is kept,
    # however quickly it compiled, and nothing is evicted (the evicting
    # cache failed to write entries on the chip's hosts). JAX writes no
    # entry without the directory.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.makedirs(COMPILE_CACHE, exist_ok=True)
    import jax

    devs = require_devices(cell.chips) if require_chip else jax.devices()
    run = Run(cell, seed, seconds, trace, t_process,
              device_kind=devs[0].device_kind)
    run.mark("start_jax")
    run.compile_log = CompileLog(run)
    from spacedrive_tpu import native

    if not native.available():
        raise SystemExit("the native plane (native/sdio.cpp) did not build")
    run.mark("native_build")
    from .rounds import RUNNERS

    runner_cls = RUNNERS[cell.traffic["round"]]
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    patched = nullcontext()
    if control:
        from . import controls

        patched = controls.applied(control, cell.traffic["round"])
    try:
        with patched:
            runner = asyncio.run(_drive(run, runner_cls, WORK))
        t = time.perf_counter()
        checks = runner.check()
        log({"phase": "check", "seconds": time.perf_counter() - t})
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    return result_line(run, devs, checks)


def result_line(run: Run, devs, checks: List[Tuple[str, float, float]]
                ) -> dict:
    metrics = run.cell.per_layer if run.trace else run.cell.end_to_end
    out: Dict[str, Any] = {}
    for m in metrics:
        value = spec_mod.metric_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted = len(run.window_rounds()) + len(run.queries)
    failed = sum(1 for r in run.window_rounds() if not r["ok"]) + sum(
        1 for q in run.queries if not q[3])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    line: Dict[str, Any] = {
        "correct": all(v <= lim for _n, v, lim in checks),
        "attempted": attempted, "failed": failed, "metrics": out,
        "device": device}
    red = run.trace_reduction
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = {
            "device_ops": sorted(red["kernel_s"].items(),
                                 key=lambda kv: -kv[1])[:10],
            "idle_gaps": sorted(red["idle_s"].items(),
                                key=lambda kv: -kv[1])[:10]}
    line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return line


def print_result(line: dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
