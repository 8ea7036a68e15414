"""Span tracer core: frame timelines, spans, the ring buffer.

Design constraints (ISSUE 2 tentpole):

- **Disabled = free.** Every per-frame entry point (``span()``,
  ``frame_begin``, ``bind``, ``frame_end``, ``attach``) starts with one
  flag check and returns a shared singleton / ``None`` — no allocation,
  no lock, no clock read. The capture loop calls these at 60 Hz per
  display; the disabled cost must be unmeasurable.
- **Thread/task-safe.** The capture thread dispatches frame N while the
  asyncio loop is still sending frame N-3, and multi-seat finalize fans
  out from yet another thread. The *current* timeline travels in a
  ``contextvars.ContextVar`` (per-thread AND per-task), and all ring
  mutations take one uncontended lock.
- **Frame-id correlation.** A frame's life spans several loop turns
  (dispatch at tick N, readback at N+PIPELINE_DEPTH, ws send later, ACK
  last). Spans recorded outside the dispatch context attach by
  ``(display_id, frame_id)`` through a bounded index that shares the
  ring's eviction.
- **One clock.** ``time.monotonic_ns`` (``CLOCK_MONOTONIC``) everywhere,
  the clock the profiler's clock mark (``obs/profiler``) and the
  benchmark read, so spans can be placed on a device trace; wall-clock
  never enters a duration. Code that stamps an instant for a span
  recorded later uses :func:`now_ns`.
- **Waits apart from work.** A span that only waits (a device sync, a
  queue, an admission) is recorded with ``wait=True`` on the lane
  ``<lane>.wait``, its own track under the thread; a work span carries
  the thread CPU time it used (``cpu_ns``) while the tracer is on, so
  wall minus waits minus CPU is time the thread was not running.
- **Counters.** ``count(tl, name, n)`` adds to the frame's ``counts``
  (the bytes a readback moved).
"""

from __future__ import annotations

import contextvars
import threading
import time
from collections import OrderedDict
from typing import Callable, Optional

__all__ = ["FrameTimeline", "FrameTracer", "now_ns", "tracer", "wait_lane"]

#: completed frame timelines kept for export (per process, across displays)
DEFAULT_CAPACITY = 512

_now_ns = time.monotonic_ns
_cpu_ns = time.thread_time_ns

#: suffix of the lane a wait span goes on: ``<lane>.wait``
WAIT_SUFFIX = ".wait"


def now_ns() -> int:
    """The tracer's clock, for stamps that become spans later."""
    return _now_ns()


def wait_lane(lane: Optional[str] = None) -> str:
    """The wait lane of ``lane`` (default: the current thread's)."""
    return (lane or threading.current_thread().name) + WAIT_SUFFIX

#: the timeline the current thread/task is dispatching (set by frame_begin)
_current: contextvars.ContextVar[Optional["FrameTimeline"]] = \
    contextvars.ContextVar("selkies_trace_frame", default=None)


class FrameTimeline:
    """One frame's spans and counters. ``spans`` holds ``(name, lane,
    t0_ns, dur_ns, cpu_ns)`` tuples; ``lane`` maps to a Perfetto track
    (thread name, ``seatN``, ``clientN``, ``<lane>.wait``…); ``cpu_ns``
    is the thread CPU time of a work span, None for waits and spans
    measured from stamps. ``counts`` maps a counter's name to its sum.
    Mutated via the tracer only."""

    __slots__ = ("display_id", "frame_id", "t0_ns", "t1_ns", "spans",
                 "counts")

    def __init__(self, display_id: str):
        self.display_id = display_id
        self.frame_id: Optional[int] = None
        self.t0_ns = _now_ns()
        self.t1_ns: Optional[int] = None
        self.spans: list[tuple[str, str, int, int, Optional[int]]] = []
        self.counts: dict[str, int] = {}

    @property
    def done(self) -> bool:
        return self.t1_ns is not None

    def wall_ms(self) -> float:
        """frame_begin -> frame_end span in ms (0.0 while open)."""
        if self.t1_ns is None:
            return 0.0
        return (self.t1_ns - self.t0_ns) / 1e6

    def to_dict(self) -> dict:
        spans = []
        for n, la, t0, d, cpu in self.spans:
            s = {"name": n, "lane": la, "t0_ns": t0, "dur_ns": d}
            if cpu is not None:
                s["cpu_ns"] = cpu
            spans.append(s)
        return {
            "display_id": self.display_id,
            "frame_id": self.frame_id,
            "t0_ns": self.t0_ns,
            "t1_ns": self.t1_ns,
            "spans": spans,
            "counts": dict(self.counts),
        }


class _NullSpan:
    """Shared do-nothing context manager: the disabled/unattached path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()

#: default for ``span(tl=...)``: distinct from an explicit None
_USE_CURRENT = object()


class _Span:
    """Live span context manager bound to one timeline: a work span
    (thread CPU time read at both ends) or a wait span (no CPU time, on
    the wait lane)."""

    __slots__ = ("_tracer", "_tl", "_name", "_lane", "_wait", "_t0",
                 "_c0")

    def __init__(self, tracer_: "FrameTracer", tl: FrameTimeline,
                 name: str, lane: Optional[str], wait: bool):
        self._tracer = tracer_
        self._tl = tl
        self._name = name
        self._lane = wait_lane(lane) if wait else lane
        self._wait = wait

    def __enter__(self):
        self._c0 = None if self._wait else _cpu_ns()
        self._t0 = _now_ns()
        return self

    def __exit__(self, *exc):
        t1 = _now_ns()
        cpu = None if self._c0 is None else _cpu_ns() - self._c0
        self._tracer._record(self._tl, self._name, self._lane,
                             self._t0, t1 - self._t0, cpu)
        return False


class FrameTracer:
    """Process-wide span tracer. One instance (:data:`tracer`) serves every
    capture module, session, and server plane; tests build their own."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = int(capacity)
        self._enabled = False
        self._lock = threading.Lock()
        # insertion-ordered (display_id, frame_id) -> timeline; doubles as
        # the ring (eviction pops the oldest entry) and the attach index
        self._ring: "OrderedDict[tuple[str, int], FrameTimeline]" = \
            OrderedDict()
        self._unbound: list[FrameTimeline] = []   # begun, not yet bind()ed
        #: optional (stage_name, dur_ms) sink — wired to the metrics
        #: registry by :meth:`enable` when the server plane is importable
        self.stage_sink: Optional[Callable[[str, float], None]] = None
        self._dropped = 0

    # -- lifecycle -----------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self, capacity: Optional[int] = None) -> None:
        if capacity is not None:
            self.capacity = int(capacity)
        if self.stage_sink is None:
            self.stage_sink = _metrics_sink()
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._unbound.clear()
            self._dropped = 0

    # -- frame lifecycle -----------------------------------------------------
    def frame_begin(self, display_id: str) -> Optional[FrameTimeline]:
        """Open a timeline and make it the current dispatch context.
        Returns None when disabled (every later call accepts that)."""
        if not self._enabled:
            return None
        tl = FrameTimeline(display_id)
        _current.set(tl)
        with self._lock:
            self._unbound.append(tl)
            if len(self._unbound) > 64:      # leak guard: begun, never bound
                del self._unbound[:32]
        return tl

    def bind(self, tl: Optional[FrameTimeline], frame_id: int,
             aliases: tuple[str, ...] = ()) -> None:
        """Register the timeline under its (display, frame_id) so spans
        recorded on other threads/turns can attach. Called once the
        encoder assigned the id (encode() returns it).

        ``aliases`` registers extra display keys for the SAME timeline —
        the multi-seat capture encodes N seats in one sharded step, so
        one timeline answers for ``seat0..seatN-1`` relay sends. Alias
        entries count against ``capacity`` (they live in the same ring)."""
        if tl is None or not self._enabled:
            return
        tl.frame_id = int(frame_id)
        with self._lock:
            try:
                self._unbound.remove(tl)
            except ValueError:
                pass
            for disp in (tl.display_id, *aliases):
                key = (disp, tl.frame_id)
                self._ring[key] = tl         # wrap collision: last wins
                self._ring.move_to_end(key)
            while len(self._ring) > self.capacity:
                self._ring.popitem(last=False)
                self._dropped += 1

    def frame_end(self, display_id: str, frame_id: int) -> None:
        """Close the timeline (delivery finished). Late spans (ws send,
        ACK) may still attach while it sits in the ring."""
        if not self._enabled:
            return
        with self._lock:
            tl = self._ring.get((display_id, int(frame_id)))
        if tl is not None and tl.t1_ns is None:
            tl.t1_ns = _now_ns()
        if _current.get() is tl:
            _current.set(None)

    def current(self) -> Optional[FrameTimeline]:
        """The timeline this thread/task is dispatching, or None (also
        whenever the tracer is off)."""
        if not self._enabled:
            return None
        return _current.get()

    # -- spans ---------------------------------------------------------------
    def span(self, name: str, tl=_USE_CURRENT, lane: Optional[str] = None,
             wait: bool = False):
        """Context manager timing one stage. Targets ``tl`` when given;
        defaults to the current dispatch context. No-op when disabled,
        when no context exists (engine code runs unchanged under scripts
        that never call frame_begin), or when ``tl`` is explicitly None
        (a finalize whose frame already left the ring must NOT fall back
        to the current context — that is a different, newer frame).
        ``wait=True`` records a wait span on ``<lane>.wait``."""
        if not self._enabled:
            return _NULL_SPAN
        if tl is _USE_CURRENT:
            tl = _current.get()
        if tl is None:
            return _NULL_SPAN
        return _Span(self, tl, name, lane, wait)

    def attach_span(self, display_id: str, frame_id: int, name: str,
                    t0_ns: int, dur_ns: int,
                    lane: Optional[str] = None,
                    extend_frame: bool = False) -> bool:
        """Record a span measured elsewhere (the relay's send, timed on
        the loop) onto the frame's timeline by id. Returns False when the
        frame already left the ring.

        ``extend_frame`` stretches a CLOSED frame's envelope to cover the
        span: client-side spans (net / decode / present, ISSUE 7) land
        after ``frame_end`` by construction, and without the extension
        the occupancy analyzer would clip them out — e2e must become
        glass-to-glass, not stay at ws.send."""
        if not self._enabled:
            return False
        with self._lock:
            tl = self._ring.get((display_id, int(frame_id)))
        if tl is None:
            return False
        self._record(tl, name, lane, t0_ns, dur_ns)
        if extend_frame and tl.t1_ns is not None:
            tl.t1_ns = max(tl.t1_ns, t0_ns + max(0, dur_ns))
        return True

    def record_span(self, tl: Optional[FrameTimeline], name: str,
                    t0_ns: int, lane: Optional[str] = None, *,
                    end_ns: Optional[int] = None,
                    wait: bool = False) -> None:
        """Record a span with an EXPLICIT start, ending at ``end_ns`` or
        now — the deep pipeline's readback span starts at the slot's
        submit instant (frames-in-flight time is readback time, not
        bubble), which no context manager entered on this thread can
        know; a queue's wait runs between two stamps. ``wait=True`` puts
        it on ``<lane>.wait``."""
        if tl is None or not self._enabled:
            return
        if end_ns is None:
            end_ns = _now_ns()
        self._record(tl, name, wait_lane(lane) if wait else lane, t0_ns,
                     max(0, end_ns - t0_ns))

    def count(self, tl: Optional[FrameTimeline], name: str, n: int) -> None:
        """Add ``n`` to the frame's counter ``name`` (bytes fetched, rows
        dispatched…). No-op when ``tl`` is None or the tracer is off."""
        if tl is None or not self._enabled:
            return
        counts = tl.counts
        counts[name] = counts.get(name, 0) + int(n)

    def instant(self, display_id: str, frame_id: int, name: str,
                lane: Optional[str] = None) -> bool:
        """Zero-duration marker (exported as a trace-event instant)."""
        return self.attach_span(display_id, frame_id, name, _now_ns(), 0,
                                lane=lane)

    def lookup(self, display_id: str, frame_id: int
               ) -> Optional[FrameTimeline]:
        if not self._enabled:
            return None
        with self._lock:
            return self._ring.get((display_id, int(frame_id)))

    def _record(self, tl: FrameTimeline, name: str, lane: Optional[str],
                t0_ns: int, dur_ns: int, cpu_ns: Optional[int] = None
                ) -> None:
        if lane is None:
            lane = threading.current_thread().name
        tl.spans.append((name, lane, t0_ns, dur_ns, cpu_ns))
        sink = self.stage_sink
        if sink is not None and dur_ns > 0:
            try:
                sink(name, dur_ns / 1e6)
            except Exception:
                pass

    # -- export --------------------------------------------------------------
    def snapshot(self) -> list[FrameTimeline]:
        """Timelines oldest-first (open frames included, marked undone;
        alias keys deduped)."""
        with self._lock:
            seen: set[int] = set()
            out: list[FrameTimeline] = []
            for tl in self._ring.values():
                if id(tl) not in seen:
                    seen.add(id(tl))
                    out.append(tl)
            return out

    def stats(self, frames: Optional[int] = None) -> dict:
        """``frames`` lets callers that already hold a snapshot skip the
        second dedup pass (GET /api/trace does both)."""
        if frames is None:
            frames = len(self.snapshot())
        return {"enabled": self._enabled, "frames": frames,
                "capacity": self.capacity, "dropped": self._dropped}


def _metrics_sink() -> Optional[Callable[[str, float], None]]:
    """Wire stage durations into the Prometheus registry as the
    ``selkies_stage_ms`` histogram. Lazy + guarded: the trace package
    must work in images without the server plane's dependencies."""
    try:
        from ..server import metrics
    except Exception:
        return None
    metrics.describe("selkies_stage_ms",
                     "Per-frame stage latency (trace spans)")
    return lambda name, ms: metrics.observe_hist(
        "selkies_stage_ms", ms, {"stage": name})


#: the process-wide tracer every instrumentation site uses; call sites
#: import this object and use ``tracer.span(...)`` — one entry point
tracer = FrameTracer()
