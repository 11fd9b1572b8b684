"""Fleet self-check peer: one real node process serving obs.* over
rspc HTTP with known seeded saturations.

The `sd_top --fleet --json` self-check (tier-1) needs a REMOTE node —
a separate process with its own telemetry registry, span ring, and
flight recorder — so per-(node, subsystem) attribution is proven
against genuinely separate state, not two views of one process. This
helper is that peer:

    python -m tools.fleet_peer --name peer-b --trace <hex id>

Boots a Node in a temp dir under `--name`, starts the rspc HTTP host
on an ephemeral port, seeds the same three saturations the sd_top
self-check has always used (a shedding bench channel, a slow store
write lock, a fired p2p.ping budget), records spans + a two-phase
pipeline timeline under `--trace` (so assembled fleet traces carry
this node's lanes), then prints ONE JSON line
``{"port": ..., "id": ..., "name": ...}`` and parks until stdin
closes — the parent's handle for teardown.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import tempfile
import time
import uuid

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

try:
    # Seed the objects package: in runtimes without `cryptography` the
    # first attempt fails but leaves the non-crypto submodules cached,
    # after which mount_router imports cleanly (container quirk; no-op
    # where the dependency exists).
    import spacedrive_tpu.objects  # noqa: F401
except ModuleNotFoundError:
    pass


def seed_saturations() -> None:
    """The three known saturations, through the real registry (same
    set as tools/sd_top.py build_self_check, so the fleet gate asserts
    the same attribution names on the remote row)."""
    from spacedrive_tpu import channels
    from spacedrive_tpu.telemetry import (
        STORE_WRITE_LOCK_WAIT_SECONDS,
        TIMEOUTS_FIRED,
    )

    ch = channels.channel("bench.shed")
    for i in range(2 * ch.capacity):
        ch.put_nowait(i)
    STORE_WRITE_LOCK_WAIT_SECONDS.observe(0.8)
    TIMEOUTS_FIRED.labels(name="p2p.ping").inc()


def seed_trace(trace_id: str) -> None:
    """Spans continuing `trace_id` (what a cross-node request would
    leave here) plus a one-batch pipeline timeline carrying it."""
    from spacedrive_tpu import flight, tracing

    with tracing.continue_trace(f"{trace_id}-1"):
        with tracing.span("sync.pull", library="fleet-self-check"):
            with tracing.span("job.step", step=1):
                pass
    t0 = time.perf_counter()
    rec = flight.RECORDER
    rec.record("stage", batch=1, t0=t0, t1=t0 + 0.004,
               trace=trace_id)
    rec.record("h2d", batch=1, t0=t0 + 0.004, t1=t0 + 0.007,
               device="0", trace=trace_id)
    rec.record("kernel", batch=1, t0=t0 + 0.007, t1=t0 + 0.008,
               device="0", trace=trace_id)
    rec.record("retire", batch=1, t0=t0 + 0.008, t1=t0 + 0.009,
               trace=trace_id)


async def serve(name: str, trace_id: str) -> None:
    from spacedrive_tpu.api.server import ApiServer
    from spacedrive_tpu.node import Node

    with tempfile.TemporaryDirectory() as td:
        # Name the node BEFORE boot: health snapshots capture identity
        # at construction.
        def write_config() -> None:
            with open(os.path.join(td, "node_state.sdconfig"),
                      "w") as f:
                json.dump({"version": 1, "id": uuid.uuid4().hex,
                           "name": name, "features": []}, f)
        await asyncio.to_thread(write_config)
        node = Node(td)
        await node.start()
        server = ApiServer(node)
        port = await server.start("127.0.0.1", 0)
        seed_saturations()
        if trace_id:
            seed_trace(trace_id)
        node.health.sample()
        print(json.dumps({"port": port, "id": node.config.id.hex(),
                          "name": node.config.name}), flush=True)
        # Park until the parent closes stdin (its teardown handle) —
        # read off-loop so the rspc host keeps serving.
        await asyncio.get_running_loop().run_in_executor(
            None, sys.stdin.read)
        await server.stop()
        await node.shutdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Fleet self-check peer (one obs-serving node)")
    ap.add_argument("--name", default="fleet-peer",
                    help="node name (the fleet row label)")
    ap.add_argument("--trace", default="",
                    help="hex trace id to seed spans/timeline under")
    args = ap.parse_args(argv)
    asyncio.run(serve(args.name, args.trace))
    return 0


if __name__ == "__main__":
    sys.exit(main())
