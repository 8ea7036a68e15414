"""Per-client video relay: bounded fan-out with skip-ahead semantics.

Carries the reference's hard-won flow-control rules (selkies.py:61-110,
529-673 — the issue-#282 class of bugs) into a fresh implementation:

- **Broadcast contract**: one encode feeds N clients; a slow client skips
  ahead, it never paces the others. ``offer()`` is synchronous — no awaits
  in the fan-out path.
- **Byte budget** per relay = ``budget_s`` seconds of the stream bitrate
  with a floor, so a stalled TCP connection cannot queue unbounded memory.
- **Drop semantics**: when over budget, drop whole queued frames oldest
  first. For H.264 delta stripes, a drop breaks the decode chain of that
  stripe row, so the relay gates further deltas of the row until an IDR
  for it passes, and asks the encoder for one (rate-limited).
- **Bounded sends**: a send that exceeds ``send_timeout`` means a dead or
  hopeless socket; a cancelled send could tear a frame mid-write, so the
  socket is never reused afterwards (reference selkies.py:79-101).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from collections import deque
from typing import Awaitable, Callable, Optional

from ..obs import health as _health
from ..protocol import (FRAME_TYPE_IDR, OP_H264, OP_JPEG,
                        unpack_h264_header, unpack_jpeg_header)
from ..resilience import faults as _faults
from ..trace import tracer as _tracer
from ..trace.core import now_ns as _trace_now_ns
from . import metrics

logger = logging.getLogger("selkies_tpu.server.relay")

IDR_REQUEST_MIN_INTERVAL_S = 0.5
SEND_TIMEOUT_S = 1.0
RELAY_FLOOR_BYTES = 4 * 1024 * 1024

metrics.describe("selkies_relay_deaths_total",
                 "Relays marked dead (stalled/failed media sends)")
metrics.describe("selkies_relay_alive", "Currently-alive video relays")
metrics.describe("selkies_relay_sent_bytes_total",
                 "Media bytes sent per display across relays")
metrics.describe("selkies_relay_dropped_frames_total",
                 "Frames dropped by relay byte budgets per display")

# alive-relay accounting: counted at start(), released exactly once at
# death or close (whichever comes first)
_alive_lock = threading.Lock()
_alive_count = 0


def _alive_delta(d: int) -> None:
    global _alive_count
    with _alive_lock:
        _alive_count = max(0, _alive_count + d)
        metrics.set_gauge("selkies_relay_alive", _alive_count)


def _wire_frame_id(item: bytes) -> Optional[int]:
    """frame id from a packed media frame (trace correlation only)."""
    try:
        if item[0] == OP_H264:
            return unpack_h264_header(item)[1]
        if item[0] == OP_JPEG:
            return unpack_jpeg_header(item)[1]
    except (ValueError, IndexError):
        pass
    return None


class VideoRelay:
    """One per (client, display). Feed with ``offer()``; runs its own
    sender task against the client's ``send_bytes``."""

    def __init__(self, send_bytes: Callable[[bytes], Awaitable[None]],
                 budget_bytes: int = RELAY_FLOOR_BYTES,
                 request_idr: Optional[Callable[[], None]] = None,
                 on_dead: Optional[Callable[[], None]] = None,
                 display: Optional[str] = None,
                 send_timeout_s: float = SEND_TIMEOUT_S):
        self._send = send_bytes
        self.send_timeout_s = float(send_timeout_s)
        self.budget = max(budget_bytes, RELAY_FLOOR_BYTES)
        self._request_idr = request_idr
        self._on_dead = on_dead
        #: display this relay serves — trace correlation key for send spans
        self.display = display
        self._counted_alive = False
        self._q: deque[bytes] = deque()
        self._q_bytes = 0
        #: id(item) -> when it was queued, kept while tracing: an item's
        #: time in the queue is its relay.queue span
        self._queued_at: dict[int, int] = {}
        self._wake = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self.dead = False
        self._last_idr_req = 0.0
        # per-stripe-row H.264 chain gate: row y -> True once its IDR passed
        self._row_open: dict[int, bool] = {}
        self.sent_bytes = 0
        self.dropped_frames = 0

    def start(self) -> None:
        self._task = asyncio.create_task(self._run())
        self._counted_alive = True
        _alive_delta(+1)

    # ------------------------------------------------------------- producers
    def drained(self) -> bool:
        """True when nothing is queued — the backpressure resume signal
        (callers must not peek at queue internals)."""
        return self._q_bytes == 0

    @property
    def queue_depth(self) -> int:
        return len(self._q)

    @property
    def queued_bytes(self) -> int:
        return self._q_bytes

    def counters(self) -> dict:
        """Wire counters for the per-session QoE snapshot (the numbers
        the debug snapshot used to keep to itself)."""
        return {"sent_bytes": self.sent_bytes,
                "dropped_frames": self.dropped_frames,
                "queue_depth": len(self._q),
                "queued_bytes": self._q_bytes,
                "dead": self.dead}

    def offer(self, item: bytes) -> None:
        """Synchronous enqueue. NEVER awaits (fan-out contract)."""
        if self.dead:
            return
        if item[0] == OP_H264:
            ftype, _, y, _, _ = unpack_h264_header(item)
            if ftype == FRAME_TYPE_IDR:
                self._row_open[y] = True
            elif not self._row_open.get(y, False):
                # delta for a broken/unstarted row: useless to this client
                self._ask_idr()
                return
        self._q.append(item)
        self._q_bytes += len(item)
        if _tracer.enabled and self.display is not None:
            self._queued_at[id(item)] = _trace_now_ns()
        # fault point relay.stripe:reorder — swap the two newest queued
        # stripes so the wire delivers them out of order (stripe
        # streaming makes per-stripe sends the common case; the decode
        # contract must survive reordering: JPEG stripes are
        # independent, H.264 rows re-sync through the chain gate + IDR).
        # Queue-depth check FIRST: a clause must not be consumed (and
        # counted as fired) on an offer that cannot inject anything.
        if len(self._q) >= 2 \
                and _faults.registry.pull("relay.stripe") is not None:
            self._q[-1], self._q[-2] = self._q[-2], self._q[-1]
            if item[0] == OP_H264:
                # a swapped delta may now precede its row's reference:
                # treat it like a break and ask for a clean restart
                self._ask_idr()
        while self._q_bytes > self.budget and len(self._q) > 1:
            victim = self._q.popleft()
            self._q_bytes -= len(victim)
            if self._queued_at:
                self._queued_at.pop(id(victim), None)
            self.dropped_frames += 1
            metrics.inc_counter("selkies_relay_dropped_frames_total",
                                labels={"display": self.display or "?"})
            if victim and victim[0] == OP_H264:
                _, _, y, _, _ = unpack_h264_header(victim)
                self._row_open[y] = False   # chain broken for that row
                self._ask_idr()
        self._wake.set()

    def _ask_idr(self) -> None:
        now = time.monotonic()
        if self._request_idr and now - self._last_idr_req > IDR_REQUEST_MIN_INTERVAL_S:
            self._last_idr_req = now
            self._request_idr()

    # --------------------------------------------------------------- sender
    async def _run(self) -> None:
        try:
            while not self.dead:
                if not self._q:
                    self._wake.clear()
                    await self._wake.wait()
                    continue
                item = self._q.popleft()
                self._q_bytes -= len(item)
                t_in = self._queued_at.pop(id(item), None) \
                    if self._queued_at else None
                traced = _tracer.enabled and self.display is not None
                try:
                    t0 = _trace_now_ns() if traced else 0
                    await asyncio.wait_for(self._guarded_send(item),
                                           self.send_timeout_s)
                    if traced:
                        fid = _wire_frame_id(item)
                        if fid is not None:
                            # ws.send ends before the queue's span is
                            # recorded, so it holds the send alone
                            _tracer.attach_span(
                                self.display, fid, "ws.send", t0,
                                _trace_now_ns() - t0, lane="ws")
                            if t_in is not None:
                                _tracer.attach_span(
                                    self.display, fid, "relay.queue", t_in,
                                    t0 - t_in, lane="ws.wait")
                    self.sent_bytes += len(item)
                    metrics.inc_counter("selkies_relay_sent_bytes_total",
                                        len(item),
                                        labels={"display":
                                                self.display or "?"})
                except (asyncio.TimeoutError, ConnectionError, OSError,
                        _faults.FaultError):
                    # cancelled mid-send = possibly torn frame; this socket
                    # must never carry media again.
                    logger.info("relay send failed/stalled; marking dead")
                    self._mark_dead()
                    return
        except asyncio.CancelledError:
            pass

    async def _guarded_send(self, item: bytes) -> None:
        """The media send plus its fault point (``relay.send``: a
        ``stall`` sleeps past the send bound so wait_for trips exactly
        like a wedged TCP socket; an ``error`` raises)."""
        await _faults.registry.perturb_async("relay.send")
        await self._send(item)

    def _mark_dead(self) -> None:
        """A send stalled/failed: this socket never carries media again.
        Surfaced at /api/metrics (ISSUE 2 satellite: relay death must be
        visible beyond the bench's fallback string). Idempotent — a
        control-path death verdict and the sender task's own failure can
        both land on the same relay."""
        if self.dead:
            return
        self.dead = True
        self._q.clear()
        self._q_bytes = 0
        self._queued_at.clear()
        metrics.inc_counter("selkies_relay_deaths_total")
        _health.engine.recorder.record(
            "relay_death", display=self.display,
            sent_bytes=self.sent_bytes, dropped_frames=self.dropped_frames)
        if self._counted_alive:
            self._counted_alive = False
            _alive_delta(-1)
        if self._on_dead:
            self._on_dead()

    def mark_dead(self) -> None:
        """External death verdict (e.g. a control send to the same socket
        timed out) — same accounting as an in-relay send failure."""
        self._mark_dead()
        self._wake.set()

    async def close(self) -> None:
        self.dead = True
        if self._counted_alive:
            self._counted_alive = False
            _alive_delta(-1)
        self._wake.set()
        if self._task:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
