"""On-demand ``jax.profiler`` capture.

Wraps ``jax.profiler.start_trace`` / ``stop_trace`` behind a small
state machine so the HTTP plane (``POST /api/profile``) and
``bench.py --profile`` share one implementation:

- exactly one capture at a time (XLA's profiler is a process singleton;
  a second start corrupts the first capture's session);
- start/stop both return structured status dicts instead of raising —
  the API endpoint maps them straight to JSON;
- the capture directory defaults to a fresh ``selkies-profile-*``
  tempdir so an operator can hit the endpoint with an empty body;
- each capture opens with a clock mark: a ``selkies.clock`` trace
  annotation carrying the host's ``CLOCK_MONOTONIC`` ns (``host_ns``, also
  kept as ``clock_mark_ns`` and served in ``GET /api/trace``'s
  ``otherData``), so the span tracer's timelines, which read that clock,
  can be placed on the capture's device lanes: host ns minus the mark's
  trace timestamp is the offset.

Both entry points do real file I/O inside jax (``stop_trace`` serialises
the whole capture): callers on an event loop must run them in an
executor — the HTTP handler in ``server/core.py`` does.
"""

from __future__ import annotations

import logging
import tempfile
import threading
import time
from typing import Optional

logger = logging.getLogger("selkies_tpu.obs.profiler")

#: the trace annotation that ties the capture's clock to the host's
CLOCK_MARK = "selkies.clock"


class ProfilerSession:
    """Process-wide jax.profiler capture guard."""

    def __init__(self):
        self._lock = threading.Lock()
        self.trace_dir: Optional[str] = None
        self.started_at: Optional[float] = None
        self.captures = 0
        #: dir of the last COMPLETED capture — what GET /api/perf
        #: parses for device-time attribution without a path parameter
        self.last_trace_dir: Optional[str] = None
        #: a jax start/stop call is in flight (outside the lock); a new
        #: start must not race a still-serialising stop
        self._busy = False
        #: host CLOCK_MONOTONIC ns of the last capture's clock mark
        self.clock_mark_ns: Optional[int] = None

    @property
    def active(self) -> bool:
        return self.trace_dir is not None

    def start(self, trace_dir: Optional[str] = None) -> dict:
        """The lock guards only the state transition, never the jax
        call: ``stop_trace`` serialises the whole capture to disk and a
        concurrent ``status()`` (served inline on the event loop) must
        not block behind it."""
        with self._lock:
            if self._busy:
                return {"ok": False, "active": self.trace_dir is not None,
                        "error": "capture transition in progress"}
            if self.trace_dir is not None:
                return {"ok": False, "active": True,
                        "error": "capture already running",
                        "trace_dir": self.trace_dir}
            target = trace_dir or tempfile.mkdtemp(prefix="selkies-profile-")
            self.trace_dir = target          # claim before the jax call
            self.started_at = time.monotonic()
            self._busy = True
        try:
            import jax
            jax.profiler.start_trace(target)
            mark = time.monotonic_ns()
            with jax.profiler.TraceAnnotation(CLOCK_MARK, host_ns=mark):
                pass
        except Exception as e:
            with self._lock:
                self.trace_dir = None
                self.started_at = None
                self._busy = False
            logger.warning("profiler start failed: %s", e)
            return {"ok": False, "active": False,
                    "error": f"{type(e).__name__}: {e}"}
        with self._lock:
            self._busy = False
            self.clock_mark_ns = mark
        logger.info("jax profiler capture started -> %s", target)
        return {"ok": True, "active": True, "trace_dir": target}

    def stop(self) -> dict:
        with self._lock:
            if self._busy:
                return {"ok": False, "active": self.trace_dir is not None,
                        "error": "capture transition in progress"}
            if self.trace_dir is None:
                return {"ok": False, "active": False,
                        "error": "no capture running"}
            target, t0 = self.trace_dir, self.started_at
            self.trace_dir = None
            self.started_at = None
            self._busy = True
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:
            logger.warning("profiler stop failed: %s", e)
            return {"ok": False, "active": False, "trace_dir": target,
                    "error": f"{type(e).__name__}: {e}"}
        finally:
            with self._lock:
                self._busy = False
        with self._lock:
            self.captures += 1
            self.last_trace_dir = target
        dur = round(time.monotonic() - t0, 3) if t0 else None
        logger.info("jax profiler capture stopped (%.1fs) -> %s",
                    dur or 0.0, target)
        return {"ok": True, "active": False, "trace_dir": target,
                "duration_s": dur}

    def status(self) -> dict:
        with self._lock:
            return {"active": self.trace_dir is not None,
                    "trace_dir": self.trace_dir,
                    "last_trace_dir": self.last_trace_dir,
                    "captures": self.captures}


#: process-wide session (jax.profiler itself is a process singleton)
profiler = ProfilerSession()
