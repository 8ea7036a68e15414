"""WebSockets streaming service — the default transport.

Fresh design carrying the reference's invariants (DataStreamingServer,
selkies.py:813-4883; SURVEY.md §2.1/§3.2):

- one WS endpoint ``/api/websockets``; handshake sends ``MODE websockets``,
  cursor state, then the ``server_settings`` JSON payload;
- per-(client, display) :class:`VideoRelay` — a slow client skips ahead and
  never paces others; the fan-out path never awaits;
- ACK-driven backpressure in uint16 circular frame-id space, with the
  desync window scaled by the measured client fps and a 4 s no-ACK stall
  trigger (reference selkies.py:1590-1717);
- capture modules are persistent per display and stay warm across client
  reconnects for ``reconnect_grace_s`` (reference selkies.py:827-830,
  940-946);
- viewer-authority verb gating (reference input_handler.py:110-128);
- gzip (0x05) control compression negotiated via ``_gz,1``.
"""

from __future__ import annotations

import asyncio
import base64
import collections
import json
import logging
import os
import threading
import time
from typing import Optional

from aiohttp import WSMsgType, web

from .. import protocol as P
from ..engine import CaptureSettings, ScreenCapture
from ..engine.types import EncodedChunk
from ..obs import health as _health
from ..obs import logctx as _logctx
from ..obs import qoe as _qoe
from ..obs import slo as _slo
from ..resilience import faults as _faults
from ..settings import AppSettings, SettingsError
from ..taskutil import spawn_retained
from ..trace import tracer as _tracer
from ..trace.core import now_ns as _trace_now_ns
from . import metrics
from .core import BaseStreamingService
from .relay import VideoRelay

logger = logging.getLogger("selkies_tpu.server.ws")

ACK_STALL_S = 4.0
#: a client that looks ACK-stalled is looked at again this much later:
#: a stall of this process (a blocking call, a collection) leaves its
#: ACKs unread and its frames unsent, and the loop needs a moment to
#: catch up before the client's silence counts against it
ACK_RECHECK_S = 1.0
RECONNECT_DEBOUNCE_S = 0.5
CONTROL_SEND_TIMEOUT_S = 2.0  # reference 2 s control bound (selkies.py:79-101)
#: backpressure logging: one INFO line per window, and windows that
#: start within this many seconds of the last logged one are summarised
#: (count carried on the next INFO line) instead of flooding the log
BACKPRESSURE_LOG_EVERY_S = 5.0

metrics.describe("selkies_protocol_errors_total",
                 "Malformed client text-protocol messages dropped, by "
                 "message kind")


class _FpsEstimator:
    """Client display fps from ACK cadence; ``now`` injected so tests are
    deterministic (the reference documents the same seam,
    selkies.py:1694-1696)."""

    def __init__(self, window: int = 30):
        self._times: list[float] = []
        self._window = window

    def tick(self, now: float) -> None:
        self._times.append(now)
        if len(self._times) > self._window:
            self._times.pop(0)

    def fps(self) -> float:
        if len(self._times) < 2:
            return 60.0
        span = self._times[-1] - self._times[0]
        return (len(self._times) - 1) / span if span > 0 else 60.0

    @property
    def has_samples(self) -> bool:
        """True once the estimate is measured rather than the 60 fps
        default (the QoE snapshot must not report a guess as data)."""
        return len(self._times) >= 2


def _relay_counters(relays: dict) -> dict:
    """Summed wire counters across a client's relays — the QoE
    session's pull-based relay provider."""
    out = {"sent_bytes": 0, "dropped_frames": 0, "queue_depth": 0,
           "queued_bytes": 0, "relays": len(relays), "dead": 0}
    for r in relays.values():
        c = r.counters()
        out["sent_bytes"] += c["sent_bytes"]
        out["dropped_frames"] += c["dropped_frames"]
        out["queue_depth"] += c["queue_depth"]
        out["queued_bytes"] += c["queued_bytes"]
        out["dead"] += c["dead"]
    return out


class ClientConnection:
    _next_id = 0

    def __init__(self, ws: web.WebSocketResponse, role: str, raddr: str,
                 display: str = ":0"):
        ClientConnection._next_id += 1
        self.id = ClientConnection._next_id
        self.ws = ws
        self.role = role                  # 'full' | 'viewonly'
        self.display = display            # the display this client views
        self.raddr = raddr
        self.gzip_ok = False
        #: gateway-side session id (?fleet_sid=): the affinity key a
        #: migrate command must carry; empty for direct connections
        self.fleet_sid = ""
        self.video_active = False
        self.audio_active = False
        self.relays: dict[str, VideoRelay] = {}
        self.last_sent_id = 0
        self.last_ack_id = 0
        self.last_ack_time = time.monotonic()
        self.paused = False
        self.fps_est = _FpsEstimator()
        self.reported_fps = 0.0
        self.reported_latency_ms = 0.0
        #: per-session QoE stats (obs.qoe), set by the service at accept
        self.qoe = None
        #: outstanding CLIENT_CLOCK pings (seq -> the t0/t1/t2 we stamped
        #: into the server_clock reply): a sample must echo one of these
        #: or the estimator would trust fully client-fabricated tuples
        self.clock_pings: collections.OrderedDict = collections.OrderedDict()
        # backpressure log rate limiting (one INFO per window, flapping
        # windows summarised)
        self._bp_last_log = 0.0
        self._bp_suppressed = 0

    async def send_text_maybe_gz(self, text: str) -> None:
        if self.gzip_ok:
            out = P.maybe_compress_text(text)
            if isinstance(out, bytes):
                await self.ws.send_bytes(out)
                return
        await self.ws.send_str(text)


class WebSocketsService(BaseStreamingService):
    name = "websockets"

    def __init__(self, settings: AppSettings, input_handler=None,
                 capture_factory=None, audio_pipeline=None,
                 display_manager=None):
        self.settings = settings
        self.clients: dict[int, ClientConnection] = {}
        self.captures: dict[str, ScreenCapture] = {}
        self.display_geometry: dict[str, tuple[int, int]] = {}
        #: extended-desktop origin of each display inside the X framebuffer
        self.display_offsets: dict[str, tuple[int, int]] = {}
        self._ext_desktop = None        # ExtendedDesktop, built lazily
        self._custom_factory = capture_factory is not None
        default_kind = "wayland" if getattr(settings, "wayland", False) \
            else "auto"
        self._capture_factory = capture_factory \
            or (lambda: ScreenCapture(default_kind))
        self.input_handler = input_handler
        self.audio = audio_pipeline
        if display_manager is None:
            from ..display import DisplayManager
            display_manager = DisplayManager(settings.display_id)
        self.display_manager = display_manager
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._running = False
        self._bg_tasks: set[asyncio.Task] = set()
        self._starting_captures: set[str] = set()
        # recording tap: _rec_buf is loop-affine (swapped on the loop
        # before dispatch), but _rec_file is opened/written on executor
        # threads and closed by stop() on the loop — the lock makes
        # close-vs-inflight-write an ordering, not a ValueError
        # (graftlint THREAD-SHARED-MUTATION)
        self._rec_lock = threading.Lock()
        self._rec_file = None
        self._rec_closed = False     # stop() ran: a late executor flush
        #                              must NOT reopen the file (fd leak
        #                              + write-after-teardown)
        self._rec_buf = bytearray()
        self._last_conn_by_ip: dict[str, float] = {}
        self._grace_task: Optional[asyncio.Task] = None
        self._stats_task: Optional[asyncio.Task] = None
        #: pre-degradation values the ladder's step-up rungs restore
        self._pre_degrade: dict = {}
        self._ladder_bound = False

    # ---------------------------------------------------------------- routes
    def register_routes(self, app: web.Application) -> None:
        app.router.add_get("/api/websockets", self.ws_endpoint)
        if self.settings.enable_computer_use:
            app.router.add_get("/api/screenshot", self.handle_screenshot)
            app.router.add_post("/api/computer_use",
                                self.handle_computer_use)

    # ------------------------------------------------------- agent endpoints
    async def handle_screenshot(self, request: web.Request) -> web.Response:
        """Current framebuffer as PNG (reference start_computer_use's
        screenshot surface)."""
        if request.get("role") != "full":
            return web.Response(status=403, text="view-only")
        cap = self.captures.get(self._default_display()) \
            or self.captures.get("__seats__") \
            or next(iter(self.captures.values()), None)
        if cap is None or not hasattr(cap, "screenshot"):
            return web.Response(status=503, text="no active capture")
        def _grab_png():
            shot = cap.screenshot()
            if shot is None:
                return None
            import io as _io

            from PIL import Image
            buf = _io.BytesIO()
            Image.fromarray(shot, "RGB").save(buf, "PNG")
            return buf.getvalue()

        png = await asyncio.get_running_loop().run_in_executor(None, _grab_png)
        if png is None:
            return web.Response(status=503, text="no frame yet")
        return web.Response(body=png, content_type="image/png")

    async def handle_computer_use(self, request: web.Request) -> web.Response:
        """Agent input injection: {"action": "move|click|type|key|scroll",
        ...} (reference computer-use HTTP server parity)."""
        if request.get("role") != "full":
            return web.Response(status=403, text="view-only")
        if self.input_handler is None or not self.settings.enable_input:
            return web.Response(status=503, text="input disabled")
        try:
            body = await request.json()
        except Exception:
            return web.Response(status=400, text="json body required")
        action = body.get("action")
        h = self.input_handler
        try:
            if action == "move":
                await h.on_message(f"m,{int(body['x'])},{int(body['y'])}")
            elif action == "click":
                btn = int(body.get("button", 1))
                await h.on_message(f"m,{int(body['x'])},{int(body['y'])}")
                await h.on_message(f"mb,{btn},1")
                await h.on_message(f"mb,{btn},0")
            elif action == "scroll":
                await h.on_message(
                    f"ms,{int(body.get('dx', 0))},{int(body.get('dy', 0))}")
            elif action == "key":
                ks = int(body["keysym"])
                await h.on_message(f"kd,{ks}")
                await h.on_message(f"ku,{ks}")
            elif action == "type":
                for ch in str(body.get("text", ""))[:4096]:
                    cp = ord(ch)
                    ks = cp if cp < 0x100 else 0x01000000 + cp
                    await h.on_message(f"kd,{ks}")
                    await h.on_message(f"ku,{ks}")
            else:
                return web.Response(status=400,
                                    text=f"unknown action {action!r}")
        except (KeyError, ValueError) as e:
            return web.Response(status=400, text=f"bad arguments: {e}")
        return web.json_response({"ok": True})

    @property
    def _seats(self) -> int:
        return max(1, int(getattr(self.settings, "tpu_seats", 1)))

    def _default_display(self) -> str:
        return "seat0" if self._seats > 1 else self.settings.display_id

    async def start(self) -> None:
        self._loop = asyncio.get_event_loop()
        self._running = True
        with self._rec_lock:
            self._rec_closed = False    # a restart records again
        if self.input_handler is not None \
                and self.input_handler.send_clipboard is None:
            async def _push_clipboard(data: bytes, mime: str) -> None:
                # clipboard contents go ONLY to input-authorized clients
                # (view-only is denied the request verb; it must not get
                # the payload by broadcast either), and only when the
                # server->client direction is enabled
                if self.settings.enable_clipboard not in ("both", "out"):
                    return
                import base64
                msg = "clipboard," + base64.b64encode(data).decode()
                for c in list(self.clients.values()):
                    if c.role != "full":
                        continue
                    try:
                        await asyncio.wait_for(c.send_text_maybe_gz(msg),
                                               CONTROL_SEND_TIMEOUT_S)
                    except (asyncio.TimeoutError, ConnectionError,
                            RuntimeError, OSError):
                        pass
            self.input_handler.send_clipboard = _push_clipboard
        if self._seats > 1 and not self.display_geometry:
            # multi-seat: one display entry per seat, one sharded capture
            for i in range(self._seats):
                self.display_geometry[f"seat{i}"] = (
                    self.settings.initial_width, self.settings.initial_height)
        if self.input_handler is not None:
            self.input_handler.start()
        if self.audio is not None:
            # enable_microphone without enable_audio: mic playback only,
            # no capture/encode loop (ADVICE r5)
            await self.audio.start(
                mic_only=not self.settings.enable_audio)
            sup = self._supervisor()
            if sup is not None \
                    and hasattr(self.audio, "restart_encode_loop"):
                # supervised audio: the pipeline reports its encode-loop
                # death instead of self-retrying on a fixed 1 s beat
                sup.adopt("audio", self.audio.restart_encode_loop)
                self.audio.on_death = \
                    lambda exc: sup.report_death(
                        "audio", f"{type(exc).__name__}: {exc}")
        self._bind_ladder()
        self._stats_task = asyncio.create_task(self._stats_loop())
        # watched RTC config file: edits reach connected clients as an
        # rtc_config push, so ICE-server rotation needs no reconnect
        # (reference RTCConfigFileMonitor, webrtc_utils.py:354-460)
        cfg_path = str(getattr(self.settings, "rtc_config_file", "") or "")
        if cfg_path:
            from .turn import RtcConfigMonitor

            def _push_cfg(cfg: dict) -> None:
                self._spawn_retained(self._broadcast_control(
                    "rtc_config," + json.dumps(cfg)))
            self._rtc_cfg_monitor = RtcConfigMonitor(cfg_path, _push_cfg)
            self._rtc_cfg_monitor.start()
        self._register_health_checks()
        logger.info("websockets service started")

    def _spawn_retained(self, coro, component: str = "ws_service"
                        ) -> asyncio.Task:
        """Background task retained on the service; cancelled in
        stop()."""
        return spawn_retained(self._bg_tasks, coro, component)

    def _supervisor(self):
        """The core's restart-policy engine; None when the service runs
        without a core (some unit tests) — wiring then degrades to the
        pre-PR-5 unsupervised behaviour."""
        return getattr(getattr(self, "core", None), "supervisor", None)

    # --------------------------------------------------------------- health
    def _register_health_checks(self) -> None:
        """Transport-scope checks on the process-wide engine (replaced on
        every service (re)start so the closures track THIS instance)."""
        _health.engine.register("relay", self._check_relays)
        _health.engine.register("capture_fps", self._check_capture_fps)
        _health.engine.register("audio", self._check_audio)

    def _check_relays(self) -> _health.Verdict:
        """Relay alive vs deaths: the r04/r05 class of failure where
        media sends stall and every viewer silently goes dark."""
        active = [c for c in self.clients.values() if c.video_active]
        if not active:
            return _health.ok("no active viewers")
        total = dead = 0
        for c in active:
            for r in c.relays.values():
                total += 1
                dead += r.dead
        if total and dead == total:
            return _health.failed(
                f"all {total} video relays dead", dead=dead, total=total)
        if dead:
            return _health.degraded(
                f"{dead}/{total} video relays dead", dead=dead, total=total)
        return _health.ok(f"{total} relays alive", total=total)

    def _check_capture_fps(self) -> _health.Verdict:
        active = [c for c in self.clients.values() if c.video_active]
        if not active:
            return _health.ok("no active viewers")
        caps = {d: c for d, c in self.captures.items() if c.is_capturing()}
        if not caps:
            if self._starting_captures:
                return _health.degraded(
                    "capture starting (first compile on a new geometry "
                    "can take minutes)")
            return _health.failed("viewers active but no capture running")
        target = float(self.settings.framerate)
        ratio = float(getattr(self.settings, "health_fps_degraded_ratio",
                              0.5))
        worst_did, worst_fps = min(
            ((d, float(getattr(c, "encoded_fps", 0.0)))
             for d, c in caps.items()), key=lambda kv: kv[1])
        msg = f"{worst_did}: {worst_fps:.1f} fps vs target {target:.0f}"
        if worst_fps < target * ratio:
            return _health.degraded(msg, fps=worst_fps, target=target)
        return _health.ok(msg, fps=worst_fps, target=target)

    def _check_audio(self) -> _health.Verdict:
        s = self.settings
        if not s.enable_audio and not s.enable_microphone:
            return _health.ok("audio disabled")
        if self.audio is None:
            want = "audio" if s.enable_audio else "microphone"
            return _health.degraded(
                f"{want} enabled but the pipeline failed to start "
                "(no libopus/PulseAudio?)")
        if s.enable_audio and not getattr(self.audio, "alive", True):
            return _health.failed("audio encode task is dead")
        if s.enable_microphone \
                and getattr(self.audio, "mic_ok", None) is False:
            return _health.degraded(
                "virtual mic provisioning failed (no PulseAudio?) — "
                "client mic input will not reach desktop apps")
        return _health.ok("mic-only pipeline" if not s.enable_audio
                          else "audio pipeline running")

    # --------------------------------------------------------- compile plane
    def _note_prewarm(self, display_id: str) -> None:
        """Tell the pre-warm worker (selkies_tpu/prewarm) the CURRENT
        operating point, so the live geometry's ladder neighbourhood
        compiles before speculative lattice corners — the rung the
        ladder would visit next under load is a neighbour of where the
        server IS."""
        worker = getattr(getattr(self, "core", None), "prewarm", None)
        if worker is None:
            return
        try:
            w, h = self._capture_geometry(display_id)
            worker.note_operating_point(w, h)
        except Exception:
            logger.debug("prewarm operating-point note failed",
                         exc_info=True)

    # ----------------------------------------------------- degradation ladder
    def _bind_ladder(self) -> None:
        """Bind concrete actuators to the core's degradation ladder:
        rung 1 halves target fps (floor ``ladder_min_fps``), rung 2 cuts
        JPEG quality / H.264 bitrate, rung 3 downscales the capture.
        Step-up restores the values captured at downshift time."""
        ladder = getattr(getattr(self, "core", None), "ladder", None)
        if ladder is None:
            return
        ladder.bind_controls({
            "pipeline": (self._ladder_pipeline_down,
                         self._ladder_pipeline_up),
            "fps": (self._ladder_fps_down, self._ladder_fps_up),
            "quality": (self._ladder_quality_down, self._ladder_quality_up),
            "downscale": (self._ladder_scale_down, self._ladder_scale_up),
        })
        self._ladder_bound = True

    def _ladder_restore(self, key: str, current) -> "Optional[int]":
        """Pop a (original, what_we_set) pre-degradation record; -> the
        original to restore, or None when the operator/client changed
        the value since the downshift — their choice wins, the ladder
        must not clobber it on step-up."""
        rec = self._pre_degrade.pop(key, None)
        if rec is None:
            return None
        orig, set_to = rec
        if current != set_to:
            logger.info("ladder: %s changed to %s while degraded; "
                        "not restoring %s", key, current, orig)
            return None
        return orig

    def _ladder_pipeline_down(self):
        """Rung 0 (deep pipeline): drop to frame-serial. Sheds the
        in-flight frames' worth of queueing latency and HBM without
        costing any fidelity — always the first concession."""
        s = self.settings
        cur = int(getattr(s, "pipeline_depth", 2))
        if cur <= 1:
            return False            # already serial: not applied
        self._pre_degrade.setdefault("pipeline_depth", (cur, 1))
        s.set_server("pipeline_depth", 1)
        for cap in self.captures.values():
            cap.update_tunables(pipeline_depth=1)
        logger.warning("ladder: pipeline depth %d -> 1 (serial)", cur)

    def _ladder_pipeline_up(self):
        old = self._ladder_restore(
            "pipeline_depth", int(getattr(self.settings,
                                          "pipeline_depth", 2)))
        if old is None:
            return False            # nothing to restore: not applied
        self.settings.set_server("pipeline_depth", int(old))
        for cap in self.captures.values():
            cap.update_tunables(pipeline_depth=int(old))
        logger.info("ladder: pipeline depth restored to %d", old)

    def _ladder_fps_down(self):
        s = self.settings
        cur = int(s.framerate)
        new = int(max(float(getattr(s, "ladder_min_fps", 15.0)), cur / 2))
        if new >= cur:
            return False            # already at the floor: not applied
        self._pre_degrade.setdefault("framerate", (cur, new))
        s.set_server("framerate", new)
        for cap in self.captures.values():
            cap.update_framerate(float(new))
        logger.warning("ladder: target fps %d -> %d", cur, new)

    def _ladder_fps_up(self):
        old = self._ladder_restore("framerate", int(self.settings.framerate))
        if old is None:
            return False            # nothing to restore: not applied
        self.settings.set_server("framerate", int(old))
        for cap in self.captures.values():
            cap.update_framerate(float(old))
        logger.info("ladder: target fps restored to %d", old)

    def _ladder_quality_down(self) -> None:
        s = self.settings
        q, kbps = int(s.jpeg_quality), int(s.video_bitrate_kbps)
        new_q = max(15, q - 25)
        new_kbps = max(500, kbps // 2)
        self._pre_degrade.setdefault("jpeg_quality", (q, new_q))
        self._pre_degrade.setdefault("video_bitrate_kbps", (kbps, new_kbps))
        s.set_server("jpeg_quality", new_q)
        s.set_server("video_bitrate_kbps", new_kbps)
        for cap in self.captures.values():
            cap.update_tunables(jpeg_quality=new_q,
                                paint_over_quality=s.paint_over_quality)
            cap.update_video_bitrate(new_kbps)
        logger.warning("ladder: quality %d -> %d, bitrate %d -> %d kbps",
                       q, new_q, kbps, new_kbps)

    def _ladder_quality_up(self):
        s = self.settings
        q = self._ladder_restore("jpeg_quality", int(s.jpeg_quality))
        kbps = self._ladder_restore("video_bitrate_kbps",
                                    int(s.video_bitrate_kbps))
        if q is None and kbps is None:
            return False            # nothing to restore: not applied
        if q is not None:
            s.set_server("jpeg_quality", int(q))
        if kbps is not None:
            s.set_server("video_bitrate_kbps", int(kbps))
        for cap in self.captures.values():
            if q is not None:
                cap.update_tunables(jpeg_quality=int(q),
                                    paint_over_quality=s.paint_over_quality)
            if kbps is not None:
                cap.update_video_bitrate(int(kbps))
        logger.info("ladder: quality/bitrate restored")

    def _ladder_scale_down(self) -> None:
        # geometry work joins capture threads: retained background task
        self._spawn_retained(self._apply_ladder_scale(2), "ladder-scale")

    def _ladder_scale_up(self) -> None:
        self._spawn_retained(self._apply_ladder_scale(None), "ladder-scale")

    async def _apply_ladder_scale(self, factor) -> None:
        """``factor=N`` divides every display geometry by N (capture
        downscale — on a live X server the screen itself resizes so it
        is a true scale, headless captures shrink their grab);
        ``factor=None`` restores the pre-degradation geometry."""
        if factor is not None:
            scaled = {did: (max(64, w // factor), max(64, h // factor))
                      for did, (w, h) in self.display_geometry.items()}
            self._pre_degrade.setdefault(
                "geometry", (dict(self.display_geometry), dict(scaled)))
            new_geo = scaled
        else:
            rec = self._pre_degrade.pop("geometry", None)
            if not rec:
                return
            orig_geo, set_geo = rec
            if self.display_geometry != set_geo:
                # a client resized while degraded: its geometry wins
                logger.info("ladder: geometry changed while degraded; "
                            "not restoring %s", orig_geo)
                return
            new_geo = orig_geo
        self.display_geometry.update(new_geo)
        if self.display_manager is not None \
                and self.display_manager.available() \
                and len(new_geo) == 1:
            did, geo = next(iter(new_geo.items()))
            await self.display_manager.resize(
                *geo, float(self.settings.framerate))
        loop = asyncio.get_running_loop()
        targets = ["__seats__"] if self._seats > 1 \
            else list(self.display_geometry)
        for tdid in targets:
            cap = self.captures.get(tdid)
            if not (cap and cap.is_capturing()):
                continue
            geo = self._capture_geometry(tdid)
            ox, oy = self.display_offsets.get(tdid, (0, 0))
            await loop.run_in_executor(
                None, lambda c=cap, o=(ox, oy), g=geo:
                c.update_capture_region(o[0], o[1], *g))
        await self._broadcast_control(self._server_settings_payload())
        # re-anchor the pre-warm order on the NEW operating point (the
        # restore geometry's neighbourhood is now the speculative one)
        self._note_prewarm(self._default_display())
        logger.warning("ladder: capture geometry %s",
                       "downscaled /%d" % factor if factor else "restored")

    async def stop(self) -> None:
        self._running = False
        for name, fn in (("relay", self._check_relays),
                         ("capture_fps", self._check_capture_fps),
                         ("audio", self._check_audio)):
            _health.engine.unregister(name, fn)
        sup = self._supervisor()
        if sup is not None:
            # deliberate teardown: pending restarts must not resurrect
            # captures/relays into a stopping service
            for did in list(self.captures):
                sup.drop(f"capture:{did}")
            for c in self.clients.values():
                for did in c.relays:
                    sup.drop(f"relay:{c.id}:{did}")
            sup.drop("audio")
        if self.audio is not None:
            self.audio.on_death = None
        if self._ladder_bound:
            ladder = getattr(getattr(self, "core", None), "ladder", None)
            if ladder is not None:
                ladder.unbind_controls()
            self._ladder_bound = False
        bg = list(self._bg_tasks)
        for task in bg:
            task.cancel()
        if bg:
            # deliver the CancelledError so finally-blocks run before
            # the loop can be closed
            await asyncio.gather(*bg, return_exceptions=True)
        if self._stats_task:
            self._stats_task.cancel()
        if getattr(self, "_rtc_cfg_monitor", None) is not None:
            await self._rtc_cfg_monitor.stop()
            self._rtc_cfg_monitor = None
        for c in list(self.clients.values()):
            await c.ws.close()
        for cap in self.captures.values():
            cap.stop_capture()
        self.captures.clear()
        if self.audio is not None:
            await self.audio.stop()
        if self.input_handler is not None:
            await self.input_handler.stop()
        if self._rec_buf:
            buf, self._rec_buf = self._rec_buf, bytearray()
        else:
            buf = b""

        def _close_recording() -> None:
            # final flush + close run OFF-LOOP: _rec_lock is held across
            # disk writes by executor flushes, so acquiring it on the
            # loop could stall every session behind a slow filesystem
            if buf:
                try:
                    self._flush_recording(buf)
                except OSError:
                    # losing the recording tail on teardown is
                    # acceptable; losing the stop path is not
                    logger.warning("final recording flush failed",
                                   exc_info=True)
            with self._rec_lock:
                self._rec_closed = True
                if self._rec_file is not None:
                    try:
                        self._rec_file.close()
                    except OSError:
                        pass
                    self._rec_file = None

        await asyncio.get_running_loop().run_in_executor(
            None, _close_recording)

    def _flush_recording(self, buf: bytes) -> None:
        """Executor-side disk append for the recording tap. The lock
        orders this against stop()'s close: an in-flight flush completes
        before the file handle dies (previously a write-after-close
        ValueError when teardown raced the stats-loop flush), and a
        flush that arrives AFTER the close drops its tail instead of
        reopening the file (an fd nothing would ever close again)."""
        try:
            with self._rec_lock:
                if self._rec_closed:
                    logger.debug("recording flush after stop: %d bytes "
                                 "dropped", len(buf))
                    return
                if self._rec_file is None:
                    self._rec_file = open(self.settings.recording_path,
                                          "ab")
                self._rec_file.write(buf)
                self._rec_file.flush()
        except OSError as e:
            logger.warning("recording tap failed: %s; disabling", e)
            self.settings.set_server("recording_path", "")
        # NOTE: callers swap self._rec_buf BEFORE dispatching here; touching
        # it from this executor thread would drop concurrently-appended
        # chunks

    # -------------------------------------------------------------- settings
    def _server_settings_payload(self) -> str:
        payload = {
            "type": "server_settings",
            "app_name": self.settings.app_name,
            "settings": self.settings.build_client_settings_payload(),
            "displays": [
                {"id": did, "width": w, "height": h}
                for did, (w, h) in sorted(self.display_geometry.items())
            ] or [{"id": self.settings.display_id,
                   "width": self.settings.initial_width,
                   "height": self.settings.initial_height}],
            # surround (>2ch) streams carry the RFC 7845 OpusHead the
            # browser AudioDecoder needs as `description`
            "audio_head": (base64.b64encode(self.audio.opus_head).decode()
                           if self.audio is not None
                           and getattr(self.audio, "opus_head", None)
                           else None),
            "features": {
                "audio": self.audio is not None and self.settings.enable_audio,
                "microphone": self.audio is not None and self.settings.enable_microphone,
                "clipboard": self.settings.enable_clipboard != "none",
                "gamepad": self.settings.enable_gamepad,
                "file_transfer": self.settings.enable_file_transfer,
                "file_transfers": str(getattr(
                    self.settings, "file_transfers", "upload,download")),
                "resize": self.settings.enable_resize,
            },
        }
        return "server_settings " + json.dumps(payload)

    # --------------------------------------------------------------- capture
    def _capture_geometry(self, display_id: str) -> tuple[int, int]:
        s = self.settings
        if display_id == "__seats__":
            # seats share one geometry; any seat entry carries it
            display_id = "seat0"
        return self.display_geometry.get(
            display_id, (s.initial_width, s.initial_height))

    def _content_state_for(self, display_id: str) -> dict:
        """Content/damage block of a display's capture (ROADMAP 4) —
        {} when the capture is absent or pre-classifier."""
        cap = self.captures.get(display_id) \
            or self.captures.get("__seats__")
        state = getattr(cap, "content_state", None)
        if state is None:
            return {}
        try:
            return state() or {}
        except Exception:
            return {}

    def primary_content_class(self):
        """The default display's content class (the core's ladder feed);
        None before classification."""
        return self._content_state_for(self._default_display()).get(
            "class")

    def _capture_settings(self, display_id: str) -> CaptureSettings:
        s = self.settings
        w, h = self._capture_geometry(display_id)
        return CaptureSettings(
            single_stream=(s.encoder == "h264-tpu"),
            capture_width=w, capture_height=h,
            target_fps=float(s.framerate),
            output_mode="jpeg" if s.encoder.startswith("jpeg") else "h264",
            video_bitrate_kbps=s.video_bitrate_kbps,
            video_crf=s.video_crf,
            use_cbr=bool(getattr(s, "use_cbr", False)),
            video_min_qp=s.video_min_qp, video_max_qp=s.video_max_qp,
            keyframe_interval_s=s.keyframe_interval_s,
            jpeg_quality=s.jpeg_quality,
            fullcolor=s.fullcolor,
            use_damage_gating=s.use_damage_gating,
            use_paint_over=s.use_paint_over,
            paint_over_quality=s.paint_over_quality,
            stripe_height=s.stripe_height,
            stripe_devices=int(getattr(s, "tpu_stripe_devices", 1)),
            pipeline_depth=int(getattr(s, "pipeline_depth", 2)),
            stripe_streaming=bool(getattr(s, "stripe_streaming", True)),
            h264_motion_vrange=s.h264_motion_vrange,
            h264_motion_hrange=s.h264_motion_hrange,
            h264_partial_encode=bool(getattr(s, "h264_partial_encode",
                                             True)),
            h264_content_adaptive=bool(getattr(s, "h264_content_adaptive",
                                               True)),
            h264_roi_qp=bool(getattr(s, "h264_roi_qp", False)),
            h264_roi_qp_bias=int(getattr(s, "h264_roi_qp_bias", 4)),
            capture_x=self.display_offsets.get(display_id, (0, 0))[0],
            capture_y=self.display_offsets.get(display_id, (0, 0))[1],
            display_id=display_id,
            # the logical id ("display2") is NOT an X address: every
            # capture opens the configured server display and reads its
            # own sub-rect
            x_display=s.display_id,
            watermark_path=s.watermark_path,
            watermark_location=s.watermark_location,
        )

    def _apply_display_layout(self) -> None:
        """Extended-desktop layout: primary + display2 origins inside one
        union framebuffer (reference display_utils.py:340-835 dual-layout
        math). Headless servers get capture offsets only; a live X server
        additionally gets the union framebuffer and ``selkies-N`` logical
        monitors so the WM tiles per display."""
        prim = self._default_display()
        self.display_offsets.setdefault(prim, (0, 0))
        others = sorted(d for d in self.display_geometry if d != prim)
        if not others or self._seats > 1:
            self.display_offsets[prim] = (0, 0)
            return
        from ..display import ExtendedDesktop, compute_dual_layout
        s = self.settings
        w1, h1 = self.display_geometry.get(
            prim, (s.initial_width, s.initial_height))
        w2, h2 = self.display_geometry[others[0]]
        _, _, o1, o2 = compute_dual_layout(
            w1, h1, w2, h2, getattr(s, "display2_position", "right"))
        self.display_offsets[prim] = o1
        self.display_offsets[others[0]] = o2
        if self.display_manager is not None \
                and self.display_manager.available():
            if self._ext_desktop is None:
                self._ext_desktop = ExtendedDesktop(self.display_manager)
            rects = [(o1[0], o1[1], w1, h1), (o2[0], o2[1], w2, h2)]
            task = asyncio.get_running_loop().create_task(
                self._ext_desktop.apply(rects, float(s.framerate)))
            task.add_done_callback(
                lambda t: t.cancelled() or t.exception())

    def _ensure_capture(self, display_id: str) -> None:
        if any(c.video_active for c in self.clients.values()):
            # multi-seat: ONE sharded capture feeds every seat display
            if self._seats > 1:
                display_id = "__seats__"
            cap = self.captures.get(display_id)
            if cap is None:
                if display_id == "__seats__" and not self._custom_factory:
                    from ..parallel.capture import MultiSeatCapture
                    cap = MultiSeatCapture(self._seats)
                else:
                    cap = self._capture_factory()
                self.captures[display_id] = cap
            self._adopt_capture(display_id, cap)
            if not cap.is_capturing() \
                    and display_id not in self._starting_captures:
                loop = self._loop
                assert loop is not None

                def cb(chunk: EncodedChunk) -> None:
                    # thread -> loop boundary: the ONLY entry point
                    # (reference selkies.py:4294); traced, the hop's
                    # wait for the loop is fanout.hop
                    if _tracer.enabled:
                        loop.call_soon_threadsafe(self._fanout_hopped,
                                                  chunk, _trace_now_ns())
                    else:
                        loop.call_soon_threadsafe(self._do_fanout, chunk)

                def cursor_cb(cur: dict) -> None:
                    loop.call_soon_threadsafe(self._on_cursor, cur)

                if self.settings.enable_cursors:
                    cap.set_cursor_callback(cursor_cb)
                # session construction does device transfers/mesh setup:
                # off the loop, guarded against double-dispatch
                self._starting_captures.add(display_id)
                cs = self._capture_settings(display_id)
                self._note_prewarm(display_id)
                # cold-start UX: session construction may trigger a
                # minutes-long first XLA compile of this geometry — tell
                # viewers instead of leaving a silent black screen
                # (VERDICT r3 weak 4); the client clears the message when
                # the first stripe draws
                self._spawn_retained(self._broadcast_control(
                    "system_msg,preparing encoder for "
                    f"{cs.capture_width}x{cs.capture_height} (first "
                    "start on a new geometry compiles; warm caches "
                    "take seconds)"))

                def _start():
                    try:
                        cap.start_capture(cb, cs)
                        logger.info("capture started for display %s",
                                    display_id)
                        # a resize may have landed while the session was
                        # constructing (is_capturing() was False then, so
                        # _h_resize skipped it): reconcile to the CURRENT
                        # geometry before handing the thread back
                        cur = self._capture_geometry(display_id)
                        if cur != (cs.capture_width, cs.capture_height):
                            cap.update_capture_region(0, 0, *cur)
                    except Exception:
                        logger.exception(
                            "capture start failed for display %s "
                            "(clients will see no video until the next "
                            "START_VIDEO)", display_id)
                    finally:
                        loop.call_soon_threadsafe(
                            self._starting_captures.discard, display_id)

                loop.run_in_executor(None, _start)

    def _adopt_capture(self, display_id: str, cap) -> None:
        """Supervise the capture thread: a loop death (source raise,
        device error mid-encode) reports to the restart-policy engine
        instead of logging and going dark. The restart joins the old
        thread and rebuilds the session — executor-side, never on the
        loop."""
        sup = self._supervisor()
        loop = self._loop
        if sup is None or loop is None or not hasattr(cap, "restart"):
            return
        comp = f"capture:{display_id}"

        def _restart(cap=cap):
            return loop.run_in_executor(None, cap.restart)

        sup.adopt(comp, _restart)
        # capture-thread -> loop hop: report_death is loop-affine
        cap.on_death = lambda exc, c=comp: loop.call_soon_threadsafe(
            sup.report_death, c, f"{type(exc).__name__}: {exc}")

    def _maybe_stop_captures(self) -> None:
        """Stop capture after the reconnect grace window if nobody watches
        (reference keeps encoders warm 3 s across reloads)."""
        if any(c.video_active for c in self.clients.values()):
            return

        async def _grace():
            await asyncio.sleep(self.settings.reconnect_grace_s)
            if not any(c.video_active for c in self.clients.values()):
                sup = self._supervisor()
                for did, cap in self.captures.items():
                    cap.stop_capture()
                    # deliberate stop, same discipline as stop(): the
                    # restart engine must forget the capture (a drain
                    # handle waits on exactly this; _ensure_capture
                    # re-adopts on the next viewer)
                    if sup is not None:
                        sup.drop(f"capture:{did}")
                    logger.info("capture stopped for display %s", did)

        if self._grace_task is None or self._grace_task.done():
            self._grace_task = asyncio.create_task(_grace())

    # ---------------------------------------------------------------- cursor
    def _on_cursor(self, cur: dict) -> None:
        """Runs on the loop: PNG-encode the XFixes cursor image and
        broadcast a ``cursor,{json}`` message (reference
        display_utils.py:1730, format_pixelflux_cursor)."""
        import base64
        import io

        from PIL import Image
        try:
            img = Image.fromarray(cur["rgba"], "RGBA")
            buf = io.BytesIO()
            img.save(buf, "PNG")
            payload = json.dumps({
                "png_b64": base64.b64encode(buf.getvalue()).decode(),
                "xhot": cur["xhot"], "yhot": cur["yhot"],
                "serial": cur["serial"],
            })
        except Exception:
            logger.debug("cursor encode failed", exc_info=True)
            return
        self._last_cursor_msg = "cursor," + payload
        self._spawn_retained(self._broadcast_control(self._last_cursor_msg))

    # ---------------------------------------------------------------- fanout
    def _fanout_hopped(self, chunk: EncodedChunk, t_hop: int) -> None:
        """Traced entry from the finalizer: the chunk's wait between
        ``call_soon_threadsafe`` (``t_hop``) and this loop turn is its
        ``fanout.hop``, then the fan-out itself."""
        _tracer.record_span(_tracer.lookup(chunk.display_id, chunk.frame_id),
                            "fanout.hop", t_hop, lane="loop", wait=True)
        self._do_fanout(chunk)

    def _do_fanout(self, chunk: EncodedChunk) -> None:
        """Runs on the loop; wire-frames once, offers to every relay.
        Synchronous — no awaits (reference selkies.py:4234-4292)."""
        with _tracer.span("fanout",
                          _tracer.lookup(chunk.display_id, chunk.frame_id),
                          lane="loop"):
            if chunk.output_mode == "jpeg":
                frame = P.pack_jpeg_stripe(chunk.frame_id, chunk.stripe_y,
                                           chunk.payload)
            else:
                frame = P.pack_h264_stripe(chunk.frame_id, chunk.stripe_y,
                                           chunk.width, chunk.height,
                                           chunk.payload, idr=chunk.is_idr)
        metrics.inc_counter("selkies_frames_encoded_total")
        # out-of-band recording tap: raw Annex-B / MJPEG of the primary
        # display (reference recording socket, settings.py:640-645)
        if self.settings.recording_path \
                and chunk.display_id == self._default_display():
            # buffered on the loop (cheap append), flushed to disk from an
            # executor — a slow disk must never pace the fan-out
            self._rec_buf += chunk.payload
        now_m = time.monotonic()
        for c in self.clients.values():
            if not c.video_active or c.paused:
                continue
            relay = c.relays.get(chunk.display_id)
            if relay is None or relay.dead:
                continue
            c.last_sent_id = chunk.frame_id
            relay.offer(frame)
            if c.qoe is not None:
                c.qoe.note_sent(chunk.frame_id, now_m)

    async def announce_migration(self, target_url: str,
                                 resync: bool = True) -> int:
        """Fleet drain (ISSUE 11): tell every connected client to
        reconnect elsewhere. Each client gets its OWN ``migrate,{json}``
        (the sid rides along so the gateway's affinity map routes the
        reconnect to the re-placed seat); captures stay warm — the
        normal reconnect-grace machinery holds them when the clients
        drop, so a client that bounces straight back (aborted drain)
        still finds a frame. -> clients notified."""
        from ..fleet.protocol import migrate_command

        async def _one(c: ClientConnection) -> int:
            try:
                await asyncio.wait_for(
                    c.send_text_maybe_gz(
                        migrate_command(target_url,
                                        c.fleet_sid or str(c.id),
                                        resync=resync)),
                    CONTROL_SEND_TIMEOUT_S)
                return 1
            except (asyncio.TimeoutError, ConnectionError,
                    RuntimeError, OSError):
                logger.info("migrate notify to client %d failed", c.id)
                return 0

        # concurrent like _broadcast_control: a drain of N clients with
        # stalled sockets must cost ONE control timeout, not N of them
        notified = sum(await asyncio.gather(
            *(_one(c) for c in list(self.clients.values()))))
        if notified:
            logger.warning("fleet drain: told %d client(s) to migrate "
                           "to %s", notified, target_url or "(gateway)")
        return notified

    async def _broadcast_control(self, text: str) -> None:
        """Bounded CONCURRENT broadcast: one stalled client must never pace
        the loop or the other clients (reference bounded-send rule,
        selkies.py:79-101) — the per-client bounds run in parallel so the
        whole broadcast costs one timeout, not one per stalled client. A
        send that exceeds the bound marks the socket dead and closes it —
        a cancelled send may have torn a frame, so it is never reused."""
        async def _one(c: ClientConnection) -> None:
            try:
                await asyncio.wait_for(c.send_text_maybe_gz(text),
                                       CONTROL_SEND_TIMEOUT_S)
            except (asyncio.TimeoutError, ConnectionError,
                    RuntimeError, OSError):
                logger.info("control send to client %d failed; closing", c.id)
                for relay in c.relays.values():
                    relay.mark_dead()
                try:
                    await c.ws.close()
                except (ConnectionError, RuntimeError, OSError):
                    pass  # already torn down by the peer

        await asyncio.gather(*(_one(c) for c in list(self.clients.values())))

    # ------------------------------------------------------------- endpoint
    async def ws_endpoint(self, request: web.Request) -> web.WebSocketResponse:
        ws = web.WebSocketResponse(max_msg_size=P.WS_MESSAGE_SIZE_HARD_CAP,
                                   compress=False)  # media must not deflate
        await ws.prepare(request)
        # fault point: an injected accept failure closes the fresh
        # socket (1013 Try Again Later) — the client reconnect path
        try:
            await _faults.registry.perturb_async("ws.accept")
        except _faults.FaultError:
            await ws.close(code=1013, message=b"fault injected")
            return ws
        role = request.get("role", "full")
        raddr = request.remote or "?"

        # secure-token mode: the HTTP-auth role is not enough — the client
        # must present a minted token (reference selkies.py:2147-2200)
        if self.settings.secure_api:
            core = getattr(self, "core", None)
            token_role = core.check_ws_token(
                request.query.get("token", "")) if core else None
            if token_role is None:
                await ws.close(code=4401, message=b"token required")
                return ws
            role = token_role

        # reconnect debounce per IP (reference selkies.py:2202-2217)
        now = time.monotonic()
        last = self._last_conn_by_ip.get(raddr, 0.0)
        self._last_conn_by_ip[raddr] = now
        if now - last < RECONNECT_DEBOUNCE_S:
            await asyncio.sleep(RECONNECT_DEBOUNCE_S)

        # sharing enforcement
        if not self.settings.enable_sharing and self.clients:
            await ws.close(code=4000, message=b"sharing disabled")
            return ws

        # validate ?display= against KNOWN displays always — an arbitrary
        # string must never become a capture key (it would spawn a whole
        # extra pipeline per distinct value). The ONE sanctioned new name
        # is "display2": the reference's extended second display
        # (display_utils.py:340-835), registered lazily up to max_displays.
        display = request.query.get("display") or self._default_display()
        known = set(self.display_geometry) or {self._default_display()}
        if display not in known:
            if (display == "display2" and self._seats == 1
                    and self.settings.max_displays >= 2):
                s = self.settings
                self.display_geometry.setdefault(
                    self._default_display(),
                    (s.initial_width, s.initial_height))
                self.display_geometry[display] = (s.initial_width,
                                                  s.initial_height)
                self._apply_display_layout()
            else:
                display = self._default_display()
        client = ClientConnection(ws, role, raddr, display=display)
        # fleet affinity (ISSUE 11): the gateway's WS proxy forwards the
        # session id it placed under (?fleet_sid=); a drain's migrate
        # command must carry THAT id — the engine-local client id means
        # nothing to the gateway's affinity map. Bounded+sanitised: it
        # goes back out on the wire in the migrate command.
        fleet_sid = request.query.get("fleet_sid", "")[:128]
        client.fleet_sid = "".join(
            c for c in fleet_sid if c.isalnum() or c in "._:-")
        # broadcast rung pin (ISSUE 17): the gateway's rendition
        # upstream dials ?rung=<name>; attach as a broadcast viewer on
        # that rung before the first START_VIDEO so the relay is keyed
        # to the rung's capture from frame one
        rung_q = request.query.get("rung", "")[:32]
        rung_q = "".join(c for c in rung_q if c.isalnum() or c in "._-")
        # only the first full client gets input authority unless collab
        if role == "full" and not self.settings.enable_collab:
            if any(c.role == "full" for c in self.clients.values()):
                client.role = "viewonly"
        self.clients[client.id] = client
        # per-session QoE stats: wire counters pull from the client's
        # live relays, fps prefers the client's own report
        client.qoe = _qoe.registry.register("ws", client.display,
                                            client.id, raddr=raddr)
        client.qoe.fps_provider = (
            lambda c=client: c.fps_est.fps() if c.fps_est.has_samples
            else None)
        client.qoe.target_fps = lambda: float(self.settings.framerate)
        client.qoe.relay_provider = \
            lambda c=client: _relay_counters(c.relays)
        # content-adaptive encoding (ROADMAP 4): class + dirty fraction
        # from the display's capture, pulled at snapshot/export time
        client.qoe.content_provider = \
            lambda c=client: self._content_state_for(c.display)
        # log correlation: selkies_tpu.* records emitted while handling
        # this connection carry its session/seat id (obs.logctx filter)
        _logctx.bind(client.id, client.display)
        metrics.set_gauge("selkies_clients", len(self.clients))
        logger.info("client %d connected (%s, %s)", client.id, client.role, raddr)
        if len(self.clients) == 1 and self.settings.run_after_connect:
            self._fire_hook(self.settings.run_after_connect)

        try:
            await ws.send_str("MODE websockets")
            await ws.send_str(self._server_settings_payload())
            # late joiners get the current cursor immediately
            if getattr(self, "_last_cursor_msg", None):
                await ws.send_str(self._last_cursor_msg)
            if rung_q and bool(getattr(self.settings,
                                       "enable_broadcast", False)):
                await self._h_broadcast_view(client, rung_q)
            async for msg in ws:
                if msg.type == WSMsgType.TEXT:
                    await self._on_text(client, msg.data)
                elif msg.type == WSMsgType.BINARY:
                    await self._on_binary(client, msg.data)
                elif msg.type == WSMsgType.ERROR:
                    break
        finally:
            await self._disconnect(client)
        return ws

    async def _disconnect(self, client: ClientConnection) -> None:
        self.clients.pop(client.id, None)
        # a paused client leaving must not strand the depth clamp
        if client.paused:
            self._apply_pipeline_clamp()
        _qoe.registry.unregister(client.qoe)
        self._broadcast_detach(client)
        self._drop_relay_supervision(client)
        for relay in client.relays.values():
            await relay.close()
        client.relays.clear()
        # release held keys/gamepads when the driver seat leaves
        if client.role == "full" and self.input_handler is not None:
            self.input_handler.release_all()
        metrics.set_gauge("selkies_clients", len(self.clients))
        self._maybe_stop_captures()
        logger.info("client %d disconnected", client.id)
        if not self.clients and self.settings.run_after_disconnect:
            self._fire_hook(self.settings.run_after_disconnect)

    def _fire_hook(self, cmd: str) -> None:
        """First-connect / last-disconnect lifecycle hooks (reference
        run_after_connect/disconnect, stream_server.py). Fire-and-forget
        as an independent task: _disconnect runs inside a ws handler that
        is being CANCELLED during connection teardown, so awaiting the
        subprocess there would lose the hook to the cancellation."""
        async def _run():
            try:
                proc = await asyncio.create_subprocess_shell(
                    cmd, stdout=asyncio.subprocess.DEVNULL,
                    stderr=asyncio.subprocess.DEVNULL)
                await proc.wait()
            except OSError as e:
                logger.warning("lifecycle hook failed: %s", e)

        self._spawn_retained(_run())

    # -------------------------------------------------------------- messages
    async def _on_binary(self, client: ClientConnection, data: bytes) -> None:
        if not data:
            return
        if data[0] == P.OP_GZ_CONTROL:
            try:
                await self._on_text(client, P.decompress_control(data))
            except ValueError as e:
                logger.warning("bad 0x05 frame from client %d: %s", client.id, e)
        elif data[0] == P.OP_MIC:
            if self.audio is not None and self.settings.enable_microphone \
                    and client.role == "full":
                self.audio.play_mic_pcm(data[1:])
            elif not getattr(client, "mic_denied_told", False):
                # reference parity (selkies.py MICROPHONE_DISABLED): tell
                # the sender ONCE so its UI can stop the capture instead
                # of streaming into a void
                client.mic_denied_told = True
                try:
                    await client.ws.send_str("MICROPHONE_DISABLED")
                except (ConnectionError, RuntimeError):
                    pass

    async def _on_text(self, client: ClientConnection, text: str) -> None:
        verb = P.parse_verb(text)
        name = verb.name

        # viewer authority gate (reference input_handler.py:110-128)
        if client.role == "viewonly" and name not in P.VIEWER_ALLOWED_PREFIXES:
            return

        handler = {
            "_gz": self._h_gz, "SETTINGS": self._h_settings,
            "CLIENT_FRAME_ACK": self._h_ack,
            "CLIENT_FRAME_TIMING": self._h_frame_timing,
            "CLIENT_CLOCK": self._h_client_clock,
            "CLIENT_STATS": self._h_client_stats,
            "START_VIDEO": self._h_start_video, "STOP_VIDEO": self._h_stop_video,
            "REQUEST_KEYFRAME": self._h_keyframe,
            "START_AUDIO": self._h_start_audio, "STOP_AUDIO": self._h_stop_audio,
            "r": self._h_resize, "s": self._h_dpi,
            "vb": self._h_video_bitrate, "ab": self._h_audio_bitrate,
            "pong": self._h_pong, "_f": self._h_client_fps,
            "_l": self._h_client_latency,
            "SET_NATIVE_CURSOR_RENDERING": self._h_cursor_mode,
            "BROADCAST_VIEW": self._h_broadcast_view,
            "BROADCAST_QOE": self._h_broadcast_qoe,
        }.get(name)
        if handler is not None:
            await handler(client, verb.args)
            return
        if self.input_handler is not None and self.settings.enable_input:
            try:
                await self.input_handler.on_message(text)
            except (ValueError, IndexError, KeyError) as e:
                # malformed verb args must never tear down the WS connection
                # (the reference parses tolerantly; SURVEY §2.3)
                logger.warning("bad input verb from client %d: %r (%s)",
                               client.id, text[:80], e)

    # ---- control verbs ------------------------------------------------------
    async def _h_gz(self, client: ClientConnection, args: str) -> None:
        client.gzip_ok = args.strip() == "1"

    async def _h_settings(self, client: ClientConnection, args: str) -> None:
        # SETTINGS mutates SERVER state (encoder/bitrate/framerate for every
        # client); view-only clients may send the verb (the reference client
        # always does) but must not steer the shared stream — the reference
        # splits per-client display prefs from server settings
        # (selkies.py:1833-2141); here server-side knobs need input authority.
        if client.role != "full":
            await client.send_text_maybe_gz("settings_applied {}")
            return
        try:
            body = json.loads(args)
        except json.JSONDecodeError:
            await client.ws.send_str("ERROR bad SETTINGS payload")
            return
        applied = {}
        for k, v in body.items():
            try:
                applied[k] = self.settings.apply_client_setting(k, v)
            except SettingsError as e:
                logger.info("client %d setting rejected: %s", client.id, e)
        if applied:
            await self._apply_live_settings(applied)
            await client.send_text_maybe_gz(
                "settings_applied " + json.dumps(applied, default=list))

    async def _apply_live_settings(self, applied: dict) -> None:
        for cap in self.captures.values():
            if "framerate" in applied:
                cap.update_framerate(float(applied["framerate"]))
            if "video_bitrate_kbps" in applied:
                cap.update_video_bitrate(int(applied["video_bitrate_kbps"]))
            if "jpeg_quality" in applied or "paint_over_quality" in applied:
                cap.update_tunables(
                    jpeg_quality=self.settings.jpeg_quality,
                    paint_over_quality=self.settings.paint_over_quality)
        # structural changes (encoder, fullcolor) need a capture rebuild;
        # restart joins the capture thread, so it runs in an executor to
        # keep the event loop responsive (SURVEY §7 hard-part #4)
        if {"encoder", "fullcolor"} & set(applied):
            loop = asyncio.get_running_loop()
            for did, cap in self.captures.items():
                if cap.is_capturing():
                    new_settings = self._capture_settings(did)
                    await loop.run_in_executor(
                        None, lambda c=cap, s=new_settings: c.restart(s))
        if "audio_bitrate" in applied and self.audio is not None:
            self.audio.update_bitrate(int(applied["audio_bitrate"]))
        if "audio_red_distance" in applied and self.audio is not None:
            # live regate: the pipeline reads red_distance per frame
            self.audio.red_distance = int(applied["audio_red_distance"])
        if "keyboard_layout" in applied:
            await self._apply_keyboard_layout(str(applied["keyboard_layout"]))
        if applied.get("window_manager"):
            # live WM swap (reference webrtc_mode WM detect/swap).
            # Safelist enforcement lives in the setting's choices= — a
            # rejected value never reaches here. Reuse the long-lived
            # manager so its _wm_name cache invalidates (set_dpi's DE
            # chain reads it) and the DI hook stays honoured.
            await self.display_manager.swap_window_manager(
                str(applied["window_manager"]))

    async def _apply_keyboard_layout(self, layout: str) -> None:
        """Align the X keymap with the client's detected layout
        (reference lib/keyboard-layout.js + server XKB alignment) so
        scancode-reading apps agree with the browser; character input is
        already layout-independent (keysyms + spare-keycode overlay)."""
        if not layout.isalnum() or len(layout) > 8:
            return
        import shutil as _shutil
        if not _shutil.which("setxkbmap"):
            return
        try:
            proc = await asyncio.create_subprocess_exec(
                "setxkbmap", layout,
                env=dict(os.environ, DISPLAY=self.settings.display_id),
                stdout=asyncio.subprocess.DEVNULL,
                stderr=asyncio.subprocess.DEVNULL)
            await proc.communicate()
        except OSError:
            pass

    def _protocol_error(self, client: ClientConnection, kind: str,
                        text: str, exc: Exception) -> None:
        """Malformed client message: count it (by kind) and drop it —
        the receive loop must survive any byte sequence a client can
        produce (ISSUE 7 satellite; the input-verb path already parses
        tolerantly)."""
        metrics.inc_counter("selkies_protocol_errors_total",
                            labels={"kind": kind})
        logger.debug("malformed %s from client %d: %r (%s)",
                     kind, client.id, text[:80], exc)

    async def _h_ack(self, client: ClientConnection, args: str) -> None:
        try:
            acked = int(args)
        except ValueError as e:
            self._protocol_error(client, "client_frame_ack", args, e)
            return
        now = time.monotonic()
        client.last_ack_id = acked
        client.last_ack_time = now
        client.fps_est.tick(now)
        if client.qoe is not None:
            client.qoe.note_ack(acked, now)
        if _tracer.enabled:
            # close the glass-to-glass loop on the frame's timeline
            _tracer.instant(client.display, acked, "ack", lane="ws")
        self._update_backpressure(client)

    async def _h_client_clock(self, client: ClientConnection,
                              args: str) -> None:
        """NTP-style clock exchange (obs.clocksync): ``ping`` gets a
        ``server_clock`` reply stamped with two perf_counter reads;
        ``sample`` feeds the session's offset/drift estimator. The
        server — not the browser — owns estimation."""
        try:
            kind, seq, ts = P.parse_client_clock(args)
        except (ValueError, IndexError) as e:
            self._protocol_error(client, "client_clock", args, e)
            return
        if kind == "ping":
            t1 = time.perf_counter_ns() / 1e6
            head = f"server_clock {seq},{ts[0]:.3f},{t1:.3f},"
            t2 = time.perf_counter_ns() / 1e6   # just before the send
            # remember what we stamped: the eventual sample must echo it
            client.clock_pings[seq] = (float(f"{ts[0]:.3f}"),
                                       float(f"{t1:.3f}"),
                                       float(f"{t2:.3f}"))
            while len(client.clock_pings) > 32:
                client.clock_pings.popitem(last=False)
            try:
                await client.ws.send_str(head + f"{t2:.3f}")
            except (ConnectionError, RuntimeError, OSError):
                pass
        elif client.qoe is not None:
            # t1/t2 are OUR perf_counter stamps: accept them only from a
            # sample that echoes an outstanding ping, or a client could
            # fabricate self-consistent tuples, steer its clock fit to an
            # arbitrary offset, and poison the shared g2g histogram/SLO
            # with fictitious multi-second frames. (t0/t3 stay client-
            # asserted by design — the client owns its own clock.)
            expect = client.clock_pings.pop(seq, None)
            if expect is None or any(abs(a - b) > 0.002
                                     for a, b in zip(expect, ts[:3])):
                self._protocol_error(
                    client, "client_clock", args,
                    ValueError("sample does not echo an outstanding ping"))
                return
            client.qoe.clock.add_sample(*ts)

    async def _h_frame_timing(self, client: ClientConnection,
                              args: str) -> None:
        """Batched per-frame client timing (receive / decode-complete /
        present, client-clock ms): mapped onto the server timebase by
        the session's clock estimator, each report becomes a g2g sample
        (qoe + selkies_session_g2g_ms), a g2g SLO event, and — when
        tracing — a ``client`` lane on the frame's /api/trace timeline
        with the frame envelope extended to true glass-to-glass."""
        try:
            entries = P.parse_frame_timing(args)
        except ValueError as e:
            self._protocol_error(client, "client_frame_timing", args, e)
            return
        if client.qoe is None:
            return
        budget_ms = float(getattr(self.settings, "slo_g2g_ms", 250.0))
        for fid, recv_c, decode_c, present_c in entries:
            m = client.qoe.note_frame_timing(fid, recv_c, decode_c,
                                             present_c)
            if m is None:
                continue            # clock not synced yet
            if m["g2g_ms"] is not None:
                _slo.engine.record("g2g", good=m["g2g_ms"] <= budget_ms)
            if _tracer.enabled:
                self._attach_client_spans(client.display, fid, m)

    @staticmethod
    def _attach_client_spans(display: str, fid: int, m: dict) -> None:
        """Join one mapped timing report onto the frame timeline:
        ``net`` (send -> client receive), ``client.decode``,
        ``client.present`` — the lanes that turn a post-readback bubble
        into attributable stages."""
        def ns(ms: float) -> int:
            return int(ms * 1e6)

        spans = []
        if m["send_ms"] is not None and m["recv_ms"] >= m["send_ms"]:
            spans.append(("net", m["send_ms"], m["recv_ms"]))
        spans.append(("client.decode", m["recv_ms"], m["decode_ms"]))
        spans.append(("client.present", m["decode_ms"], m["present_ms"]))
        for name, a, b in spans:
            _tracer.attach_span(display, fid, name, ns(a),
                                max(0, ns(b) - ns(a)),
                                lane="client", extend_frame=True)

    async def _h_client_stats(self, client: ClientConnection,
                              args: str) -> None:
        """Periodic client-side decoder stats (queue depth, dropped
        decodes, draw fps) — surfaced per session in
        ``/api/sessions?verbose=1`` as the client overload signal."""
        try:
            body = json.loads(args)
            if not isinstance(body, dict):
                raise ValueError("object body required")
        except (ValueError, RecursionError) as e:
            # RecursionError: json.loads on a deeply nested payload
            # ('['*100000) is NOT a ValueError and would tear down the
            # receive loop — exactly what the hardening contract forbids
            self._protocol_error(client, "client_stats", args, e)
            return
        if client.qoe is not None:
            client.qoe.note_client_stats(body)

    def _apply_pipeline_clamp(self) -> None:
        """Relay-backpressure clamp on the deep pipeline (ROADMAP 2):
        while any client of a display is paused, its capture runs at
        depth 1 — frames in flight would just age in the relay queue of
        a stalled wire, costing glass-to-glass latency and HBM for
        nothing. Lifted the moment no viewer is paused."""
        paused = {c.display for c in self.clients.values() if c.paused}
        for did, cap in self.captures.items():
            clamp_fn = getattr(cap, "set_pipeline_clamp", None)
            if clamp_fn is None:
                continue
            clamped = did in paused or (did == "__seats__" and paused)
            clamp_fn(1 if clamped else None)

    def _update_backpressure(self, client: ClientConnection) -> None:
        """Desync window scales with measured client fps; RTT forgiveness is
        capped upstream by the ACK cadence itself (reference
        selkies.py:1590-1717)."""
        dist = P.frame_id_distance(client.last_sent_id, client.last_ack_id)
        window = max(10, int(client.fps_est.fps() *
                             self.settings.ack_desync_frames / 60.0))
        if not client.paused and dist > window:
            client.paused = True
            self._apply_pipeline_clamp()
            metrics.inc_counter("selkies_backpressure_events_total")
            now = time.monotonic()
            if client.qoe is not None:
                client.qoe.backpressure_begin(now)
            # one INFO line per window; flapping windows within the
            # rate-limit interval are summarised, never one-per-frame
            if now - client._bp_last_log >= BACKPRESSURE_LOG_EVERY_S:
                suffix = (f" ({client._bp_suppressed} windows suppressed)"
                          if client._bp_suppressed else "")
                logger.info("client %d backpressured (dist %d > %d)%s",
                            client.id, dist, window, suffix)
                client._bp_last_log = now
                client._bp_suppressed = 0
            else:
                client._bp_suppressed += 1
                logger.debug("client %d backpressured (dist %d > %d)",
                             client.id, dist, window)
        elif client.paused:
            # Resume when the client caught up with everything queued — the
            # relay drained (dropped frames never get ACKed, so distance to
            # last_sent_id alone could deadlock the pause).
            drained = all(r.drained() for r in client.relays.values())
            if dist < window // 2 or drained:
                client.paused = False
                self._apply_pipeline_clamp()
                if client.qoe is not None:
                    dur = client.qoe.backpressure_end(time.monotonic())
                    if dur is not None:
                        logger.debug("client %d backpressure window "
                                     "closed after %.3fs", client.id, dur)
                # refresh only the displays this client actually views
                for did in client.relays:
                    self._request_idr(did)

    async def _h_start_video(self, client: ClientConnection, args: str) -> None:
        client.video_active = True
        if client.qoe is not None:
            client.qoe.video_active = True
        # each client views ONE display (its ?display= pin); multi-seat
        # clients on different seats share the single sharded capture
        did = client.display
        if did not in client.relays:
            self._make_relay(client, did)
        self._ensure_capture(did)
        # fresh joiner needs a full frame — of ITS display only (an IDR
        # on every capture would storm unrelated displays/seats)
        self._request_idr(did)
        await client.ws.send_str("VIDEO_STARTED")

    async def _h_stop_video(self, client: ClientConnection, args: str) -> None:
        client.video_active = False
        if client.qoe is not None:
            client.qoe.video_active = False
        self._drop_relay_supervision(client)
        for relay in client.relays.values():
            await relay.close()
        client.relays.clear()
        self._maybe_stop_captures()
        await client.ws.send_str("VIDEO_STOPPED")

    def _make_relay(self, client: ClientConnection, did: str) -> None:
        """Build (or rebuild) the client's video relay, supervised: a
        relay death (stalled/failed media send) reports to the restart
        engine, which re-offers a FRESH relay on the same client after
        backoff — with an IDR request so every stripe row's decode chain
        restarts clean. The dead relay's socket contract holds: the ws
        itself is only reused because the chain gate + IDR resync make a
        torn frame recoverable at the codec layer; a socket the CLIENT
        side tore down just fails the first send and feeds the policy
        until the budget parks it (or the client reconnects)."""
        sup = self._supervisor()
        on_dead = None
        if sup is not None:
            comp = f"relay:{client.id}:{did}"

            def _reoffer(c=client, d=did, comp=comp):
                if c.id not in self.clients or not c.video_active:
                    sup.drop(comp)
                    return
                old = c.relays.get(d)
                if old is not None and not old.dead:
                    return
                self._make_relay(c, d)
                # the fresh relay starts every H.264 row gated shut; a
                # keyframe reopens them (and repaints JPEG viewers)
                self._request_idr(d)
                logger.info("relay for client %d display %s re-offered",
                            c.id, d)

            sup.adopt(comp, _reoffer)

            def on_dead(comp=comp):
                sup.report_death(comp, "media send stalled/failed")

        relay = VideoRelay(
            client.ws.send_bytes,
            budget_bytes=int(self.settings.video_relay_budget_s
                             * self.settings.video_bitrate_kbps * 125),
            request_idr=lambda d=did: self._request_idr(d),
            on_dead=on_dead,
            display=did)
        relay.start()
        client.relays[did] = relay

    def _drop_relay_supervision(self, client: ClientConnection) -> None:
        sup = self._supervisor()
        if sup is not None:
            for did in client.relays:
                sup.drop(f"relay:{client.id}:{did}")

    def _request_idr(self, display_id: str) -> None:
        cap = self.captures.get(display_id) \
            or self.captures.get("__seats__")
        if cap:
            cap.request_idr_frame()

    async def _h_keyframe(self, client: ClientConnection, args: str) -> None:
        # only the requesting client's display: REQUEST_KEYFRAME from one
        # viewer must not IDR-storm every capture (VERDICT r3 weak 7)
        self._request_idr(client.display)

    # ---------------------------------------------- broadcast plane (ISSUE 17)
    def _broadcast_state(self) -> dict:
        """Lazy broadcast-plane state: the desktop's rendition ladder
        plus the viewer registry routing clients onto its rungs."""
        st = getattr(self, "_bcast_state", None)
        if st is None:
            from ..broadcast.ladder import ladder_from_settings
            from ..broadcast.registry import ViewerRegistry
            ladder = ladder_from_settings(self.settings)
            reg = ViewerRegistry(
                ladder, source=self._default_display(),
                label_cap=int(getattr(self.settings,
                                      "qoe_seat_label_cap", 8)),
                on_switch=self._on_broadcast_switch)
            st = {"ladder": ladder, "registry": reg, "clients": {}}
            self._bcast_state = st
        return st

    def _rung_display(self, rend) -> str:
        """Display id carrying a rung's capture. The source rung rides
        the desktop's own capture; downscaled rungs get derived display
        ids (``:0@mid``) so ``_ensure_capture`` builds them through the
        exact same capture/step factories as any seat — the rendition
        encode surface is the lattice's, not a new one."""
        base = self._default_display()
        if rend.downscale <= 1:
            return base
        did = f"{base}@{rend.name}"
        self.display_geometry.setdefault(did, (rend.width, rend.height))
        return did

    def _on_broadcast_switch(self, state, old: int, new: int) -> None:
        """ViewerRegistry on_switch hook (sync, called outside its
        lock): re-key the viewer's relay onto the new rung, IDR first
        frame. Registry already counted the idr_resync."""
        st = self._broadcast_state()
        client = st["clients"].get(state.sid)
        if client is None:
            return
        try:
            asyncio.get_running_loop()
        except RuntimeError:
            return                    # no loop: sync test rigs route only
        self._spawn_retained(
            self._apply_broadcast_rung(client, st["ladder"].rung(new)),
            "broadcast_switch")

    async def _apply_broadcast_rung(self, client: ClientConnection,
                                    rend) -> None:
        """Move a viewer's relay onto a rung's capture. Every switch is
        IDR-resynced: the new rung's chain gates start shut and only a
        keyframe reopens them, so the first delivered frame is a clean
        decoder entry point."""
        did = self._rung_display(rend)
        old_did = client.display
        if client.qoe is not None:
            client.qoe.rung = rend.name
        if did == old_did and did in client.relays:
            self._request_idr(did)
            return
        client.display = did
        old = client.relays.pop(old_did, None)
        if old is not None:
            sup = self._supervisor()
            if sup is not None:
                sup.drop(f"relay:{client.id}:{old_did}")
            await old.close()
        if did not in client.relays:
            self._make_relay(client, did)
        if client.video_active:
            self._ensure_capture(did)
            self._request_idr(did)
        self._maybe_stop_captures()

    async def _h_broadcast_view(self, client: ClientConnection,
                                args: str) -> None:
        """``BROADCAST_VIEW[,rung]``: attach this client as a broadcast
        viewer on a ladder rung (default: the source rung)."""
        if not bool(getattr(self.settings, "enable_broadcast", False)):
            await client.ws.send_str("BROADCAST_DISABLED")
            return
        st = self._broadcast_state()
        ladder = st["ladder"]
        name = (args or "").strip().partition(",")[0]
        idx = ladder.index_of(name) if name else 0
        st["clients"][str(client.id)] = client
        state = st["registry"].attach(str(client.id), rung=idx)
        rend = ladder.rung(state.rung)
        await self._apply_broadcast_rung(client, rend)
        st["registry"].export_metrics()
        await client.ws.send_str(f"BROADCAST_RUNG,{rend.name}")

    async def _h_broadcast_qoe(self, client: ClientConnection,
                               args: str) -> None:
        """``BROADCAST_QOE,<score 0-100>``: the viewer's QoE verdict.
        Ladder-per-session routing with dwell hysteresis; a landed
        switch re-keys the relay and IDR-resyncs (on_switch hook)."""
        st = getattr(self, "_bcast_state", None)
        if st is None or str(client.id) not in st["clients"]:
            return
        try:
            score = float((args or "").partition(",")[0])
        except ValueError:
            return
        content = self._content_state_for(
            self._default_display()).get("class")
        st["registry"].route(str(client.id), score=score,
                             content_class=content)
        st["registry"].export_metrics()

    def _broadcast_detach(self, client: ClientConnection) -> None:
        st = getattr(self, "_bcast_state", None)
        if st is not None \
                and st["clients"].pop(str(client.id), None) is not None:
            st["registry"].detach(str(client.id))
            st["registry"].export_metrics()

    async def _h_start_audio(self, client: ClientConnection, args: str) -> None:
        if self.audio is None or not self.settings.enable_audio:
            await client.ws.send_str("AUDIO_DISABLED")
            return
        client.audio_active = True
        self.audio.add_listener(client)

    async def _h_stop_audio(self, client: ClientConnection, args: str) -> None:
        client.audio_active = False
        if self.audio is not None:
            self.audio.remove_listener(client)

    async def _h_resize(self, client: ClientConnection, args: str) -> None:
        if not self.settings.enable_resize:
            return
        try:
            w, h = (int(v) for v in args.lower().split("x"))
        except ValueError:
            return
        # resize the CLIENT'S display, never a phantom entry; in multi-seat
        # mode the sharded capture is shared, so every seat resizes together
        did = client.display
        if did not in self.display_geometry and self.display_geometry:
            did = self._default_display()
        geo = (max(64, min(w, 16384)), max(64, min(h, 16384)))
        if self._seats > 1:
            for seat_did in self.display_geometry:
                self.display_geometry[seat_did] = geo
        else:
            self.display_geometry[did] = geo
        # resize the REAL X screen first (CVT-RB modeline via xrandr,
        # reference display_utils.py:223-1076); headless setups skip this
        # and only the capture geometry changes. With an extended desktop
        # the union layout drives the framebuffer instead of one display.
        multi = self._seats == 1 and len(self.display_geometry) > 1
        if multi:
            self._apply_display_layout()
        elif self.display_manager is not None \
                and self.display_manager.available():
            await self.display_manager.resize(*geo,
                                              float(self.settings.framerate))
        # retarget EVERY display's capture: a layout pass moves the OTHER
        # displays' origins too (their sub-rects shift when this one grows)
        targets = [did] if not multi else list(self.display_geometry)
        if self._seats > 1:
            targets = ["__seats__"]
        loop = asyncio.get_running_loop()
        for tdid in targets:
            cap = self.captures.get(tdid)
            if not (cap and cap.is_capturing()):
                continue
            tgeo = geo if tdid in (did, "__seats__") \
                else self.display_geometry[tdid]
            ox, oy = self.display_offsets.get(tdid, (0, 0))
            # size change rebuilds the capture session (joins a thread):
            # never on the event loop
            await loop.run_in_executor(
                None, lambda c=cap, o=(ox, oy), g=tgeo:
                c.update_capture_region(o[0], o[1], *g))
        # broadcast realized geometry (bounded sends)
        await self._broadcast_control(self._server_settings_payload())

    async def _h_dpi(self, client: ClientConnection, args: str) -> None:
        try:
            dpi = self.settings.apply_client_setting("dpi", int(args))
        except (SettingsError, ValueError):
            return
        if self.display_manager is not None \
                and self.display_manager.available():
            await self.display_manager.set_dpi(int(dpi))

    async def _h_video_bitrate(self, client: ClientConnection, args: str) -> None:
        try:
            kbps = int(args)
        except ValueError:
            return
        try:
            self.settings.apply_client_setting("video_bitrate_kbps", kbps)
        except SettingsError:
            return
        for cap in self.captures.values():
            cap.update_video_bitrate(kbps)

    async def _h_audio_bitrate(self, client: ClientConnection, args: str) -> None:
        if self.audio is None:
            return
        try:
            self.audio.update_bitrate(int(args))
        except ValueError:
            pass

    async def _h_pong(self, client: ClientConnection, args: str) -> None:
        pass

    async def _h_client_fps(self, client: ClientConnection, args: str) -> None:
        try:
            client.reported_fps = float(args)
            if client.qoe is not None:
                client.qoe.reported_fps = client.reported_fps
            metrics.set_gauge("selkies_fps", client.reported_fps,
                              {"client": str(client.id)})
            metrics.observe_hist("selkies_fps_hist", client.reported_fps)
        except ValueError:
            pass

    async def _h_client_latency(self, client: ClientConnection, args: str) -> None:
        try:
            client.reported_latency_ms = float(args)
            metrics.set_gauge("selkies_latency_ms", client.reported_latency_ms,
                              {"client": str(client.id)})
        except ValueError:
            pass

    async def _h_cursor_mode(self, client: ClientConnection, args: str) -> None:
        pass  # cursor streaming lands with the cursor monitor

    # ----------------------------------------------------------------- stats
    async def _check_ack_stalls(self) -> None:
        """ACK stall forces backpressure (reference 4 s rule): a client
        with a frame unacknowledged and no ACK for ``ACK_STALL_S`` is
        paused, if it is still so ``ACK_RECHECK_S`` later."""
        def stalled(c) -> bool:
            return (c.video_active and not c.paused
                    and c.last_sent_id != c.last_ack_id
                    and c.last_ack_time < time.monotonic() - ACK_STALL_S)

        if not any(stalled(c) for c in self.clients.values()):
            return
        await asyncio.sleep(ACK_RECHECK_S)
        for c in list(self.clients.values()):
            if stalled(c):
                c.paused = True
                self._apply_pipeline_clamp()
                metrics.inc_counter("selkies_backpressure_events_total")
                if c.qoe is not None:
                    c.qoe.note_stall()
                    c.qoe.backpressure_begin(time.monotonic())
                _health.engine.recorder.record(
                    "ack_stall", client=c.id, display=c.display,
                    last_sent=c.last_sent_id, last_ack=c.last_ack_id)

    async def _stats_loop(self) -> None:
        """Periodic per-client system stats (reference selkies.py:4586-4722)."""
        import psutil
        while self._running:
            await asyncio.sleep(self.settings.stats_interval_s)
            await self._check_ack_stalls()
            # SLO event feed (obs.slo): one fps + one qoe good/bad event
            # per active session per tick. g2g events arrive per frame
            # from _h_frame_timing; these two close the objective set.
            target = float(self.settings.framerate)
            now_m = time.monotonic()
            idle_after = 2.0 * float(self.settings.stats_interval_s)
            for c in list(self.clients.values()):
                if not c.video_active or c.qoe is None:
                    continue
                # idle gate: damage gating means a static desktop
                # legitimately delivers no frames — fps 0 / score 0 on a
                # session we offered nothing is not a broken promise,
                # and recording it bad would burn the budget while
                # perfectly healthy
                last = c.qoe.last_send_mono
                if last is None or now_m - last > idle_after:
                    continue
                fps = c.qoe.client_fps()
                if fps is not None and target > 0:
                    _slo.engine.record("fps", good=fps >= target * 0.5)
                score = c.qoe.score()
                if score is not None:
                    _slo.engine.record(
                        "qoe", good=score >= _qoe.registry.degraded_score)
            try:
                stats = {
                    "type": "system_stats",
                    "cpu_percent": psutil.cpu_percent(),
                    "mem_percent": psutil.virtual_memory().percent,
                    "clients": len(self.clients),
                    "encoded_fps": {
                        did: cap.encoded_fps
                        for did, cap in self.captures.items()},
                    # TPU/accelerator telemetry (gpu_stats.py equivalent);
                    # executor: device queries must not stall the loop
                    "devices": await asyncio.get_running_loop()
                    .run_in_executor(None, metrics.device_stats),
                }
                await self._broadcast_control("system_stats " + json.dumps(stats))
                # vendor-spanning GPU chain (reference selkies.py:4586+
                # gpu_stats messages); separate verb so clients with no
                # GPU interest skip the parse
                from . import gpu_stats as _gs
                gpus = await asyncio.get_running_loop().run_in_executor(
                    None, _gs.gpu_stats_payload)
                if gpus:
                    await self._broadcast_control(
                        "gpu_stats " + json.dumps({"gpus": gpus}))
                if self.settings.stats_csv_path:
                    await asyncio.get_running_loop().run_in_executor(
                        None, self._append_stats_csv, stats)
                if self._rec_buf:
                    buf, self._rec_buf = self._rec_buf, bytearray()
                    await asyncio.get_running_loop().run_in_executor(
                        None, self._flush_recording, buf)
            except Exception:
                logger.exception("stats loop error")

    def _append_stats_csv(self, stats: dict) -> None:
        """Schema-stable CSV stats dump (reference webrtc_utils.py:958-1259
        role)."""
        import csv
        path = self.settings.stats_csv_path
        row = {
            "ts": round(time.time(), 3),
            "cpu_percent": stats.get("cpu_percent"),
            "mem_percent": stats.get("mem_percent"),
            "clients": stats.get("clients"),
            "encoded_fps": ";".join(
                f"{k}={v:.1f}" for k, v in stats.get("encoded_fps", {}).items()),
        }
        try:
            new = not os.path.exists(path)
            with open(path, "a", newline="") as f:
                w = csv.DictWriter(f, fieldnames=list(row))
                if new:
                    w.writeheader()
                w.writerow(row)
        except OSError as e:
            logger.warning("stats csv failed: %s; disabling", e)
            self.settings.set_server("stats_csv_path", "")
