"""Central network-await timeout registry — the lifecycle twin of
ops/jit_registry.py's contract table.

Every await on a socket/tunnel/ws frame in `p2p/`, `api/`, `sync/`
runs under a budget DECLARED here — name, default seconds, and a
docstring — and applied through `with_timeout(name, awaitable)` or a
`deadline(name)` block. Scattered `asyncio.wait_for(..., 30)` literals
made the hang surface unauditable (a peer that stops acking a clone
page parked the originator forever; the spacedrop verdict wait was the
only network await with ANY budget); tools/sdlint's timeout-discipline
pass now fails the build on a network-root await that is not covered
by a declared budget, and on a `with_timeout` name missing from this
table.

Effective budget = declared default × `SDTPU_TIMEOUT_SCALE`
(flags.py): thin-pipe or debug hosts scale every budget at once
instead of chasing literals. A fired budget counts into
`sd_timeout_fired_total{name}` before the TimeoutError propagates —
/metrics shows WHICH contract is tripping in production.

README's timeout table is generated from this registry
(`python -m tools.sdlint --timeout-table`).

Design constraints (same as flags.py): stdlib + flags/telemetry only,
importable from every layer without cycles.

Budget ordering invariants (asserted nowhere, documented here):
`p2p.spacedrop.verdict` must EXCEED `p2p.spacedrop.decide` — the
sender's verdict wait brackets the receiver's interactive decision
window; equal budgets would race the legitimate decide path.
"""

from __future__ import annotations

import asyncio
import random
import time
from dataclasses import dataclass
from typing import Any, Awaitable, Callable, Dict, Optional, Tuple, Type

from . import flags
from .telemetry import BACKOFF_GAVE_UP, BACKOFF_RETRIES, TIMEOUTS_FIRED

__all__ = [
    "TimeoutContract", "TIMEOUTS", "declare_timeout", "budget",
    "with_timeout", "deadline", "timeout_table_markdown",
    "BackoffContract", "BACKOFFS", "declare_backoff", "Backoff",
    "with_backoff", "RetrySchedule", "backoff_table_markdown",
]


@dataclass(frozen=True)
class TimeoutContract:
    name: str        # dotted id: "<layer>.<operation>"
    default_s: float
    doc: str


TIMEOUTS: Dict[str, TimeoutContract] = {}


def declare_timeout(name: str, default_s: float, doc: str
                    ) -> TimeoutContract:
    if name in TIMEOUTS:
        raise ValueError(f"timeout {name!r} declared twice")
    if default_s <= 0:
        raise ValueError(f"timeout {name!r}: budget must be positive")
    c = TimeoutContract(name, float(default_s), doc)
    TIMEOUTS[name] = c
    return c


def budget(name: str) -> float:
    """Effective seconds for a declared budget. An unknown name is a
    programming error, not a lookup miss — exactly flags.raw()."""
    c = TIMEOUTS.get(name)
    if c is None:
        raise KeyError(f"undeclared timeout {name!r} (declare it in "
                       "spacedrive_tpu/timeouts.py)")
    return c.default_s * flags.get("SDTPU_TIMEOUT_SCALE")


async def with_timeout(name: str, awaitable: Awaitable) -> Any:
    """`asyncio.wait_for` under a declared budget; a fired budget
    counts into sd_timeout_fired_total{name} before raising."""
    try:
        return await asyncio.wait_for(awaitable, budget(name))
    except asyncio.TimeoutError:
        TIMEOUTS_FIRED.labels(name=name).inc()
        raise


class _Deadline:
    """Block-scoped budget for multi-await sequences (handshakes,
    pair round-trips): schedules a cancel at the budget and converts
    the resulting CancelledError back into asyncio.TimeoutError at the
    block edge. Python 3.10 has no asyncio.timeout(); this is the same
    cancel-at-deadline shape (and shares its pre-3.11 edge: a timer
    firing in the instant between the block's last await and __aexit__
    still raises TimeoutError, but the task-level cancel may surface
    at the caller's next await — budgets here are tens of seconds over
    millisecond blocks, so the window is vanishing)."""

    def __init__(self, name: str):
        self.name = name
        self._fired = False
        self._handle: Optional[asyncio.TimerHandle] = None
        self._task: Optional[asyncio.Task] = None

    def _fire(self) -> None:
        self._fired = True
        if self._task is not None and not self._task.done():
            self._task.cancel()

    async def __aenter__(self) -> "_Deadline":
        self._task = asyncio.current_task()
        self._handle = asyncio.get_running_loop().call_later(
            budget(self.name), self._fire)
        return self

    async def __aexit__(self, exc_type, exc, tb) -> bool:
        if self._handle is not None:
            self._handle.cancel()
        if self._fired and exc_type in (None, asyncio.CancelledError):
            if exc_type is None and self._task is not None:
                # The timer fired in the window between the block's
                # last await completing and __aexit__: our cancel is
                # PENDING (no suspension point saw it). Neutralize it
                # (best-effort — CPython parks it in _must_cancel) so
                # the deterministic TimeoutError below is the only
                # consequence, not a surprise CancelledError at the
                # caller's next unrelated await.
                if getattr(self._task, "_must_cancel", False):
                    self._task._must_cancel = False
            TIMEOUTS_FIRED.labels(name=self.name).inc()
            raise asyncio.TimeoutError(
                f"deadline {self.name!r} "
                f"({budget(self.name)}s) exceeded") from exc
        return False


def deadline(name: str) -> _Deadline:
    """``async with deadline("p2p.handshake"):`` — every await inside
    the block shares the named budget. sdlint's timeout-discipline
    pass treats the block as covered."""
    return _Deadline(name)


def timeout_table_markdown() -> str:
    """README's generated timeout table (one row per declared budget)."""
    out = ["| Budget | Default | Covers |", "| --- | --- | --- |"]
    for name in sorted(TIMEOUTS):
        c = TIMEOUTS[name]
        doc = " ".join(c.doc.split())
        out.append(f"| `{name}` | {c.default_s:g}s | {doc} |")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# Declared retry/backoff policies — the recovery twin of the budget
# table above. Before this registry the tree's retry loops were bare
# `P2P_RECONNECTS.inc(); continue` shapes: fixed-interval hammering
# with no ladder, no jitter, no give-up, and no way for a chaos test
# to pin the discipline. Every retrying path now names a policy
# declared here; each scheduled retry counts into
# sd_backoff_retries_total{name} and an exhausted ladder into
# sd_backoff_gave_up_total{name}.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BackoffContract:
    name: str          # dotted id: "<layer>.<operation>"
    base_s: float      # first retry delay
    cap_s: float       # ladder ceiling
    factor: float      # multiplier per retry
    jitter: float      # ± fraction of the delay (thundering-herd break)
    max_tries: int     # retries before give-up; 0 = retry forever
    doc: str


BACKOFFS: Dict[str, BackoffContract] = {}

# Incident-observatory hook (incidents.py set_give_up_observer):
# notified once per exhausted ladder, exactly when
# sd_backoff_gave_up_total counts it — a give-up means an operation
# stopped retrying and degraded, which is a postmortem moment.
_give_up_observer: Optional[Callable[[str, int], None]] = None


def set_give_up_observer(
        cb: Optional[Callable[[str, int], None]]) -> None:
    global _give_up_observer
    _give_up_observer = cb


def declare_backoff(name: str, base_s: float, cap_s: float,
                    factor: float, jitter: float, max_tries: int,
                    doc: str) -> BackoffContract:
    if name in BACKOFFS:
        raise ValueError(f"backoff {name!r} declared twice")
    if base_s <= 0 or cap_s < base_s:
        raise ValueError(f"backoff {name!r}: want 0 < base <= cap")
    if factor < 1.0:
        raise ValueError(f"backoff {name!r}: factor must be >= 1")
    if not 0.0 <= jitter < 1.0:
        raise ValueError(f"backoff {name!r}: jitter must be in [0, 1)")
    if max_tries < 0:
        raise ValueError(f"backoff {name!r}: max_tries must be >= 0")
    c = BackoffContract(name, float(base_s), float(cap_s),
                        float(factor), float(jitter), int(max_tries),
                        doc)
    BACKOFFS[name] = c
    return c


class Backoff:
    """One failing operation's ladder state for a declared policy.

    `next_delay()` is called on each failure: it returns the jittered
    delay to wait before the next try (counting the retry), or None
    when the ladder is exhausted (counting the give-up) — the caller
    stops retrying and degrades. `reset()` on success so an
    intermittent peer climbs down. Deterministic under a seeded `rng`
    (what the chaos tests pin); the default shares `random`'s global
    stream, which is jitter's whole job."""

    def __init__(self, name: str,
                 rng: Optional[random.Random] = None):
        c = BACKOFFS.get(name)
        if c is None:
            raise KeyError(f"undeclared backoff {name!r} (declare it "
                           "in spacedrive_tpu/timeouts.py)")
        self.contract = c
        self.tries = 0
        self._gave_up_counted = False
        self._rng = rng
        self._m_retry = BACKOFF_RETRIES.labels(name=name)
        self._m_gave_up = BACKOFF_GAVE_UP.labels(name=name)

    def next_delay(self) -> Optional[float]:
        c = self.contract
        if c.max_tries and self.tries >= c.max_tries:
            # Counted ONCE per exhausted ladder, not once per call:
            # RetrySchedule keeps probing a given-up key at the cap
            # cadence, and each probe failure landing here must not
            # re-count the same outage (the counter means "ladders
            # exhausted", and the hand-off it documents fires once).
            if not self._gave_up_counted:
                # Same per-instance single-thread contract as `tries`
                # above (threadctx.py; the armed recorder audits it).
                self._gave_up_counted = True  # sdlint: ok[shared-mutation]
                self._m_gave_up.inc()
                observer = _give_up_observer
                if observer is not None:
                    try:
                        observer(c.name, self.tries)
                    except Exception:
                        pass  # black box never breaks the ladder
            return None
        # Exponent clamped: an unbounded ladder (max_tries 0) parked
        # at the cap for days would otherwise drive factor**tries past
        # float range and raise OverflowError out of a poll loop.
        d = min(c.cap_s, c.base_s * (c.factor ** min(self.tries, 64)))
        # Writers span loop+worker contexts across INSTANCES, never on
        # one instance: each ladder is strictly per-use-site (contract
        # in threadctx.py; the armed race recorder audits it).
        self.tries += 1  # sdlint: ok[shared-mutation]
        if c.jitter:
            r = (self._rng.random() if self._rng is not None
                 else random.random())
            d *= 1.0 + c.jitter * (2.0 * r - 1.0)
        d *= flags.get("SDTPU_TIMEOUT_SCALE")
        self._m_retry.inc()
        return d

    def exhausted(self) -> bool:
        c = self.contract
        return bool(c.max_tries) and self.tries >= c.max_tries

    def reset(self) -> None:
        self.tries = 0
        self._gave_up_counted = False


async def with_backoff(name: str, fn: Callable[[], Awaitable],
                       retry_on: Tuple[Type[BaseException], ...]
                       = (ConnectionError, OSError,
                          asyncio.TimeoutError),
                       rng: Optional[random.Random] = None) -> Any:
    """Call `fn()` under the declared policy: a retryable failure
    sleeps the ladder's next jittered delay and tries again; an
    exhausted ladder re-raises the final failure (after counting the
    give-up). CancelledError always propagates."""
    b = Backoff(name, rng=rng)
    while True:
        try:
            return await fn()
        except asyncio.CancelledError:
            raise
        except retry_on:
            d = b.next_delay()
            if d is None:
                raise
            await asyncio.sleep(d)


class RetrySchedule:
    """Per-key backoff bookkeeping for POLL-shaped loops (the sync
    announcer's peer fan-out, the fleet poller's round): the loop
    itself keeps ticking, and this schedule answers "is `key` allowed
    an attempt right now?" from each key's private ladder.

    `failure(key)` advances the ladder and returns the delay until the
    key's next allowed attempt — or None when the ladder just gave up
    (the caller hands the key off: the announcer marks the peer stale
    with the fleet observatory). A given-up key stays parked at the
    policy cap (it is retried again, at cap cadence, so a healed peer
    is eventually found without hammering a dead one). `success(key)`
    evicts the key's state entirely — the maps are bounded by
    currently-failing keys, not history."""

    def __init__(self, name: str,
                 rng: Optional[random.Random] = None):
        self.name = name
        self.contract = BACKOFFS[name] if name in BACKOFFS else None
        if self.contract is None:
            raise KeyError(f"undeclared backoff {name!r} (declare it "
                           "in spacedrive_tpu/timeouts.py)")
        self._rng = rng
        self._ladders: Dict[Any, Backoff] = {}
        self._retry_at: Dict[Any, float] = {}

    def allowed(self, key: Any, now: Optional[float] = None) -> bool:
        t = time.monotonic() if now is None else now
        return t >= self._retry_at.get(key, 0.0)

    def failure(self, key: Any, now: Optional[float] = None
                ) -> Optional[float]:
        t = time.monotonic() if now is None else now
        b = self._ladders.get(key)
        if b is None:
            b = self._ladders[key] = Backoff(self.name, rng=self._rng)
        d = b.next_delay()
        if d is None:
            # Gave up: park at the cap — cap-cadence probing finds a
            # healed peer eventually; the caller does the hand-off.
            self._retry_at[key] = t + self.contract.cap_s * \
                flags.get("SDTPU_TIMEOUT_SCALE")
            return None
        self._retry_at[key] = t + d
        return d

    def gave_up(self, key: Any) -> bool:
        b = self._ladders.get(key)
        return b is not None and b.exhausted()

    def success(self, key: Any) -> None:
        self._ladders.pop(key, None)
        self._retry_at.pop(key, None)

    def evict(self, key: Any) -> None:
        self.success(key)


def backoff_table_markdown() -> str:
    """README's generated backoff table (one row per declared
    policy)."""
    out = ["| Policy | Base | Cap | Factor | Jitter | Max tries "
           "| Covers |",
           "| --- | --- | --- | --- | --- | --- | --- |"]
    for name in sorted(BACKOFFS):
        c = BACKOFFS[name]
        doc = " ".join(c.doc.split())
        tries = str(c.max_tries) if c.max_tries else "∞"
        out.append(
            f"| `{name}` | {c.base_s:g}s | {c.cap_s:g}s | "
            f"×{c.factor:g} | ±{c.jitter:.0%} | {tries} | {doc} |")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# THE budget namespace. Keep alphabetical within each layer; every
# entry is enforced by the sdlint timeout-discipline pass (a network
# await outside a declared budget fails the build).
# ---------------------------------------------------------------------------

# -- api (rspc HTTP + websocket host) ---------------------------------------

declare_timeout(
    "api.http.read", 30.0,
    "Reading a request body (rspc POST input JSON): bounds a "
    "slow-loris client on the API host.")

declare_timeout(
    "api.http.write", 60.0,
    "One streamed response chunk (thumbnail/file/static serving): a "
    "stalled client releases the handler instead of pinning it.")

declare_timeout(
    "api.ws.prepare", 30.0,
    "Websocket upgrade handshake on the rspc ws route.")

declare_timeout(
    "api.ws.send", 30.0,
    "One websocket frame to a subscriber (responses, subscription "
    "events): a dead client cannot wedge the emit path.")

# -- bench (tools-only put budgets; not wire awaits) ------------------------

declare_timeout(
    "bench.chan.put", 5.0,
    "tools/chan_bench.py producer's bounded put on the block-policy "
    "bench channel — the measured put-block path.")

declare_timeout(
    "bench.load.wire.put", 60.0,
    "tools/load_bench.py stub-transport frame put: bounds a simulated "
    "peer whose consumer half wedged, mirroring the TCP plane's "
    "drain deadlines.")

# -- fleet (cross-node observability federation) ----------------------------

declare_timeout(
    "fleet.poll", 15.0,
    "One whole obs.health/obs.metrics fetch from a paired peer "
    "(fleet.py poll round): connect + request + response, any "
    "transport. A hung peer costs the poller this budget, then its "
    "row goes stale-degraded.")

declare_timeout(
    "fleet.trace.fetch", 60.0,
    "One peer's obs.trace slice during distributed trace assembly "
    "(fleet.py assemble_trace): span-ring + timeline copies are "
    "bigger than health snapshots, so the budget is too.")

# -- p2p (tunnel control plane) ---------------------------------------------

declare_timeout(
    "p2p.connect", 20.0,
    "Outbound TCP dial + authenticated tunnel handshake "
    "(P2PManager.open_stream).")

declare_timeout(
    "p2p.file.response", 60.0,
    "The remote library's file-request decision frame "
    "(request_file's status/req header).")

declare_timeout(
    "p2p.frame_send", 60.0,
    "One control/ops frame into a tunnel including the drain "
    "backpressure wait — a receiver that stops reading frees the "
    "sender here.")

declare_timeout(
    "p2p.handshake", 20.0,
    "The signed-ephemeral key exchange on a fresh tunnel "
    "(proto.tunnel_handshake, both roles).")

declare_timeout(
    "p2p.header_recv", 30.0,
    "Inbound dispatch header after an accepted handshake: a silent "
    "dialer cannot hold a server slot open.")

declare_timeout(
    "p2p.obs", 30.0,
    "One obs.metrics/obs.health/obs.trace exchange on a tunnel "
    "(p2p/obs.py P2PObsClient and the manager's serving side): the "
    "request frame, the snapshot-building, and the response frame "
    "all inside one budget.")

declare_timeout(
    "p2p.pair", 60.0,
    "The whole pairing round-trip (instance-row exchange incl. the "
    "responder's DB writes).")

declare_timeout(
    "p2p.ping", 20.0,
    "Ping round-trip over a fresh tunnel.")

declare_timeout(
    "p2p.spacedrop.decide", 60.0,
    "Interactive accept/reject window for an inbound spacedrop offer "
    "(the reference's 60s prompt).")

declare_timeout(
    "p2p.spacedrop.verdict", 75.0,
    "Sender's wait for the receiver's accept/reject — brackets the "
    "receiver's full p2p.spacedrop.decide window, so it MUST stay "
    "longer than it.")

declare_timeout(
    "p2p.transfer.chunk", 60.0,
    "One spaceblock block (send or receive) plus its ack: transfers "
    "of any size stay live as long as per-block progress continues.")

# -- sync (CRDT pull + clone fast path) -------------------------------------

declare_timeout(
    "sync.clone.ack", 180.0,
    "Originator's wait for one blob-page watermark ack — covers the "
    "receiver's batched one-tx page apply at bulk page sizes.")

declare_timeout(
    "sync.clone.ack_send", 60.0,
    "Receiver pushing a page ack back up the tunnel.")

declare_timeout(
    "sync.clone.drain", 120.0,
    "Flushing a pipelined clone window into the socket against a "
    "slow receiver's backpressure.")

declare_timeout(
    "sync.clone.frame", 180.0,
    "Receiver's wait for the next clone-stream frame (page, "
    "interleaved ops, or blob_done) from the originator.")

declare_timeout(
    "sync.clone.serve", 120.0,
    "One clone-serve page-fetch slot on the fair-share gate "
    "(channels.py sync.clone.serve): with many peers cloning "
    "concurrently, each stream's next page fetch waits its FIFO turn "
    "here instead of letting a hot stream monopolize the executor — "
    "a wait past this budget means the node is clone-overcommitted.")

declare_timeout(
    "sync.ingest.backlog", 180.0,
    "Ingester waiting for space in its bounded request channel "
    "(channels.py sync.ingest.requests): the _pull consumer drains it "
    "between wire frames, so a wedged consumer frees the actor here "
    "instead of parking it forever.")

declare_timeout(
    "sync.pull.page", 180.0,
    "Responder's wait for one ops page — the originator runs get_ops "
    "off-loop over bulk op logs before answering.")

declare_timeout(
    "sync.pull.request", 180.0,
    "Originator's wait for the responder's next pull request — the "
    "responder ingests the previous page (one tx per page) before "
    "asking again.")

# -- store (single-writer group-commit actor) -------------------------------

declare_timeout(
    "store.actor.put", 30.0,
    "A writer waiting for space in the storage actor's bounded batch "
    "queue (channels.py store.actor.queue): the write-path admission "
    "edge — a wedged writer thread frees its producers here instead "
    "of parking every job forever.")

declare_timeout(
    "store.actor.write", 600.0,
    "A writer's whole trip through the group-commit actor "
    "(store/actor.py): grant wait + every batch body coalesced ahead "
    "of it + the group's COMMIT. Sized for bulk-chunk batch bodies "
    "(a 4096-file indexer chunk riding the same group); firing means "
    "the writer thread is wedged, not slow.")


# ---------------------------------------------------------------------------
# THE backoff namespace. Keep alphabetical within each layer; every
# retrying loop in the tree must name a policy here (the bare
# fixed-interval retry is the shape this registry retired).
# ---------------------------------------------------------------------------

declare_backoff(
    "fleet.peer.poll", 10.0, 300.0, 2.0, 0.25, 0,
    "Fleet-observatory polling of an UNREACHABLE peer (fleet.py): "
    "after a failed obs.health fetch the peer's next poll waits this "
    "ladder instead of burning a fleet.poll budget every round; "
    "max_tries 0 = never gives up (the row is already stale-degraded; "
    "cap-cadence probing notices the heal).")

declare_backoff(
    "obs.http", 0.2, 2.0, 2.0, 0.25, 3,
    "HttpObsClient fetch retries (fleet.py): transient connect "
    "failures against a restarting peer retry inside the caller's "
    "fleet.poll budget; exhaustion surfaces the final error to the "
    "poller, which marks the row unreachable.")

declare_backoff(
    "p2p.announce.reconnect", 0.5, 60.0, 2.0, 0.25, 6,
    "Sync announce fan-out to a peer that failed its last round "
    "(p2p/sync_net.py originate): a flapping peer is retried up this "
    "ladder instead of being hammered on every local write; "
    "exhaustion hands the peer to the fleet observatory as a stale "
    "row and parks retries at the cap until it heals (peers pull on "
    "reconnect regardless).")

declare_backoff(
    "store.busy", 0.05, 1.0, 2.0, 0.25, 5,
    "Write-transaction commit retry on sqlite BUSY (store/db.py tx): "
    "an external writer holding the file lock — or an injected "
    "store.commit chaos fault — degrades to bounded latency "
    "(sd_store_busy_retries_total) instead of failing the job; "
    "exhaustion re-raises the BUSY.")
