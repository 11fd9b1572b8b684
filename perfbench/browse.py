"""The browse client: an Explorer scrolling the library while it is scanned.

Runs in a spawned JAX-free process, so it holds no chip and does not
share the node's interpreter lock, as a browser would not. Open loop: a
`search.paths` query is due every 1/qps seconds from the window's start,
whether or not earlier ones have answered, each asking for `take` rows
ordered by `order` at an offset drawn from the seed among the rows the
library held at the last count. Latency is measured from the due time,
so a stalled server also delays the queries queued behind it. A
`search.pathsCount` every `count_every` queries refreshes the row count
(not timed). The parent sends ("start", t0), ("target", library_id) and
("stop", t_end) over the pipe; the child answers ("ready",) and, at the
end, ("done", queries) with (due, sent, done, ok) per query in
perf_counter seconds, which the kernel's monotonic clock makes
comparable across processes.
"""

from __future__ import annotations

import asyncio
import json
import random
import time


async def _client(conn, port: int, seed: int, qps: float, take: int,
                  order: str, count_every: int) -> list:
    import aiohttp

    loop = asyncio.get_running_loop()
    state = {"t0": None, "target": None, "stop": None, "rows": 0}
    changed = asyncio.Event()

    def on_msg():
        while conn.poll():
            msg = conn.recv()
            if msg[0] == "start":
                state["t0"] = msg[1]
            elif msg[0] == "target":
                state["target"], state["rows"] = msg[1], 0
            elif msg[0] == "stop":
                state["stop"] = msg[1]
            changed.set()

    loop.add_reader(conn.fileno(), on_msg)
    rng = random.Random(seed)
    queries: list = []
    pending: dict = {}
    ids = iter(range(1, 1 << 62))
    async with aiohttp.ClientSession() as http:
        async with http.ws_connect(f"http://127.0.0.1:{port}/rspc",
                                   max_msg_size=0) as ws:

            async def reader():
                async for msg in ws:
                    if msg.type != aiohttp.WSMsgType.TEXT:
                        break
                    frame = json.loads(msg.data)
                    fut = pending.pop(frame.get("id"), None)
                    if fut is not None and not fut.done():
                        fut.set_result(frame)
                for fut in pending.values():
                    if not fut.done():
                        fut.set_result({"type": "error"})

            async def call(path: str, input_: dict) -> dict:
                mid = next(ids)
                fut = loop.create_future()
                pending[mid] = fut
                await ws.send_json({"id": mid, "type": "query",
                                    "path": path, "input": input_})
                return await fut

            async def query(due: float, lib: str, skip: int):
                sent = time.perf_counter()
                frame = await call("search.paths", {
                    "library_id": lib, "skip": skip, "take": take,
                    "order": {"field": order}})
                queries.append((due, sent, time.perf_counter(),
                                frame.get("type") == "response"))

            async def count(lib: str):
                frame = await call("search.pathsCount", {"library_id": lib})
                if frame.get("type") == "response" \
                        and state["target"] == lib:
                    state["rows"] = int(frame["result"])

            read_task = asyncio.ensure_future(reader())
            conn.send(("ready",))
            while state["t0"] is None:
                changed.clear()
                await changed.wait()
            tasks = []
            i = 0
            while True:
                due = state["t0"] + i / qps
                while state["stop"] is None and time.perf_counter() < due:
                    changed.clear()
                    try:
                        await asyncio.wait_for(
                            changed.wait(), due - time.perf_counter())
                    except asyncio.TimeoutError:
                        pass
                if state["stop"] is not None and due > state["stop"]:
                    break
                while state["target"] is None:
                    changed.clear()
                    await changed.wait()
                lib = state["target"]
                skip = rng.randrange(state["rows"]) if state["rows"] else 0
                tasks.append(asyncio.ensure_future(query(due, lib, skip)))
                if i % count_every == 0:
                    tasks.append(asyncio.ensure_future(count(lib)))
                i += 1
            await asyncio.wait_for(asyncio.gather(*tasks), 120)
            read_task.cancel()
    loop.remove_reader(conn.fileno())
    return queries


def child_main(conn, port: int, seed: int, qps: float, take: int,
               order: str, count_every: int) -> None:
    queries = asyncio.run(_client(conn, port, seed, qps, take, order,
                                  count_every))
    conn.send(("done", queries))
    conn.close()


class BrowseClient:
    """Parent side: spawn, steer and collect the browse process. The
    waits run in a thread (`await client.ready()`, `await
    client.finish(t)`): the server the child talks to shares the
    caller's event loop."""

    def __init__(self, port: int, seed: int, params: dict):
        import multiprocessing

        ctx = multiprocessing.get_context("spawn")
        self.conn, child = ctx.Pipe()
        self.proc = ctx.Process(target=child_main, args=(
            child, port, seed, float(params["qps"]), int(params["take"]),
            str(params["order"]), int(params["count_every"])), daemon=True)
        self.proc.start()
        child.close()

    def _recv(self, timeout: float):
        if not self.conn.poll(timeout):
            raise RuntimeError("the browse client did not answer")
        return self.conn.recv()

    async def ready(self) -> None:
        msg = await asyncio.to_thread(self._recv, 120)
        if msg[0] != "ready":
            raise RuntimeError(f"the browse client sent {msg[0]!r}")

    def send(self, *msg) -> None:
        self.conn.send(msg)

    async def finish(self, t_end: float) -> list:
        """All queries due up to t_end, once each has answered."""
        self.send("stop", t_end)
        _kind, queries = await asyncio.to_thread(self._recv, 180)
        await asyncio.to_thread(self.proc.join, 30)
        return [q for q in queries if q[0] <= t_end]

    def kill(self) -> None:
        if self.proc.is_alive():
            self.proc.terminate()
            self.proc.join(10)
