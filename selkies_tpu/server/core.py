"""Single-port HTTP core / service supervisor.

Re-implements the responsibilities of the reference's
``CentralizedStreamServer`` (stream_server.py:390-1421) on aiohttp,
designed fresh:

- auth middleware: HTTP basic auth, a view-only password granting
  input-less sessions, and a master bearer token — all compared
  timing-safely (reference :689-792);
- WebSocket Origin guard (reference :647-686);
- static client serving from the packaged ``web/`` directory or
  ``--web_root``;
- ``/api/status``, ``/api/health`` (named verdicts via
  ``selkies_tpu.obs``: ``?verbose=1`` for the full check set and the
  incident flight recorder, ``?probe=live|ready`` for container
  orchestration), ``/api/metrics``, ``/api/switch`` (live transport
  swap when ``enable_dual_mode``, reference :804-895), ``/api/profile``
  (on-demand jax.profiler capture, full-role gated), ``/api/perf``
  (static step cost attribution + pipeline occupancy, ISSUE 6),
  ``/api/slo`` (error-budget burn-rate verdicts, ISSUE 7);
- chunked file upload with path-traversal + symlink defences and a
  JSON/HTML download index (reference :897-1299);
- TLS with live certificate reload (reference :552-632);
- ``BaseStreamingService`` ABC so transports are pluggable and fakeable
  (the testability seam SURVEY.md §4.5 calls out).
"""

from __future__ import annotations

import abc
import asyncio
import base64
import hmac
import html
import json
import logging
import os
import pathlib
import ssl
import time
import urllib.parse
from typing import Optional
from urllib.parse import urlparse

from aiohttp import web

from ..obs import energy as _energy
from ..obs import health as _health
from ..obs import qoe as _qoe
from ..obs import slo as _slo
from ..resilience import faults as _faults
from ..resilience.ladder import DegradationLadder
from ..resilience.supervisor import RestartPolicy, Supervisor
from ..settings import AppSettings, is_sensitive

logger = logging.getLogger("selkies_tpu.server.core")

WEB_ROOT = pathlib.Path(__file__).resolve().parent.parent / "web"


class BaseStreamingService(abc.ABC):
    """Transport service contract (reference stream_server.py:372-387)."""

    name: str = "base"
    core: "Optional[CentralizedStreamServer]" = None  # set on register

    @abc.abstractmethod
    async def start(self) -> None: ...

    @abc.abstractmethod
    async def stop(self) -> None: ...

    def register_routes(self, app: web.Application) -> None:
        """Add the service's endpoints to the shared app."""


def _timing_safe_eq(a: str, b: str) -> bool:
    return hmac.compare_digest(a.encode(), b.encode())


class CentralizedStreamServer:
    def __init__(self, settings: AppSettings):
        self.settings = settings
        self.services: dict[str, BaseStreamingService] = {}
        self.active_mode: Optional[str] = None
        self._service_task: Optional[asyncio.Task] = None
        self.app = web.Application(middlewares=[self._auth_middleware])
        self._runner: Optional[web.AppRunner] = None
        self._ssl_ctx: Optional[ssl.SSLContext] = None
        self._cert_watch_task: Optional[asyncio.Task] = None
        self.started_at = time.time()
        #: secure-mode WS tokens: token -> {role, created, uses}
        self.ws_tokens: dict[str, dict] = {}
        #: fleet drain state (POST /api/drain): while True the
        #: readiness probe fails (gateway routes nothing new here) and
        #: the fleet heartbeat carries draining=true
        self.draining = False
        self._drain_handle = None
        self._fleet_seq = 0
        #: supervised heartbeat push loop (ISSUE 19): state for the
        #: fleet_push health check + /api/fleet push diagnostics. The
        #: clock sample is the NTP-style [t0,t1,t2,t3] completed by the
        #: previous push's response, echoed in the next heartbeat so
        #: the gateway's per-host clocksync estimator converges.
        self._fleet_push_task: Optional[asyncio.Task] = None
        self._fleet_push_stats = {"sent": 0, "errors": 0,
                                  "rejected": 0, "last_ok": None,
                                  "last_error": "", "backoff_s": 0.0}
        self._fleet_clock_sample: Optional[list] = None
        #: the process-wide health engine; services register their
        #: checks against it in start() (tests may swap it out)
        self.health = _health.engine
        self.health.register("service", self._check_service, liveness=True)
        self.health.register("stage_latency", self._check_stage_latency)
        # per-session wire QoE (obs.qoe): registered here — not in a
        # transport — so the check exists whichever mode is active.
        # Per-instance wrapper: bound methods of the registry singleton
        # compare equal across server instances, which would defeat the
        # owner-matched unregister in shutdown()
        _qoe.registry.configure(
            seat_label_cap=getattr(settings, "qoe_seat_label_cap", None),
            degraded_score=getattr(settings, "qoe_degraded_score", None),
            failed_score=getattr(settings, "qoe_failed_score", None))
        self._check_qoe = lambda: _qoe.registry.health_check()
        self.health.register("qoe", self._check_qoe)
        # SLO burn-rate engine (obs.slo): the stock objectives (g2g /
        # fps / qoe) are declared HERE — not in a transport — so the
        # promise set exists whichever mode is active; transports just
        # record events against the named objectives.
        _slo.engine.configure_defaults(settings)
        self._check_slo = lambda: _slo.engine.health_check()
        self.health.register("slo", self._check_slo)
        # resilience plane (selkies_tpu/resilience): the supervisor owns
        # every restart decision (transport service here; captures,
        # relays and audio adopt through it from the services), the
        # ladder sheds fidelity on bad verdicts. Policy knobs from
        # settings; per-component policies share the factory.
        self.supervisor = Supervisor(
            recorder=self.health.recorder,
            policy_factory=lambda: RestartPolicy(
                max_restarts=int(getattr(
                    settings, "supervisor_max_restarts", 5)),
                window_s=float(getattr(
                    settings, "supervisor_window_s", 300.0)),
                base_backoff_s=float(getattr(
                    settings, "supervisor_backoff_base_s", 0.5)),
                max_backoff_s=float(getattr(
                    settings, "supervisor_backoff_max_s", 30.0))))
        self._check_supervision = self.supervisor.health_check
        self.health.register("supervision", self._check_supervision)
        self.ladder: Optional[DegradationLadder] = None
        if getattr(settings, "enable_degradation_ladder", True):
            self.ladder = DegradationLadder(
                down_after_s=float(getattr(
                    settings, "ladder_down_after_s", 4.0)),
                hold_s=float(getattr(settings, "ladder_hold_s", 10.0)),
                ok_window_s=float(getattr(
                    settings, "ladder_ok_window_s", 30.0)),
                defer_deadline_s=float(getattr(
                    settings, "prewarm_defer_deadline_s", 30.0)),
                # energy-aware mode (ISSUE 14): armed only by a
                # positive power_budget_w — None leaves the stock walk
                # byte-for-byte untouched
                energy_policy=_energy.ladder_policy_from_settings(
                    settings),
                recorder=self.health.recorder)
        self._ladder_task: Optional[asyncio.Task] = None
        # compile plane (selkies_tpu/prewarm, ISSUE 8): enumerate the
        # ladder-reachable signature lattice and gate every ladder
        # transition on it — a cold rung defers instead of compiling in
        # the foreground. The worker THREAD starts in run() (unit tests
        # build servers without ever wanting background XLA builds).
        self.prewarm = None
        self._prewarm_artifact: Optional[dict] = None
        if getattr(settings, "enable_prewarm", True):
            from ..obs import monitor as _devmon
            from ..prewarm.lattice import lattice_from_settings
            from ..prewarm.worker import PrewarmGate, PrewarmWorker
            plan = lattice_from_settings(
                settings,
                steps=self.ladder.steps if self.ladder is not None
                else ("fps", "quality", "downscale"))
            self.prewarm = PrewarmWorker(
                plan, storm_check=_devmon.storm_recent,
                recorder=self.health.recorder)
            self._check_prewarm = self.prewarm.health_check
            self.health.register("prewarm", self._check_prewarm)
            # the prewarm-complete ROUTING GATE (ISSUE 11 / ROADMAP 3):
            # ?probe=ready answers failed until the current operating
            # point's programs are warm, so a load balancer never
            # routes onto a cold host. Gate-scope: the default
            # /api/health report stays about process health — a
            # warming host is healthy, just not routable yet.
            self._check_prewarm_ready = self.prewarm.current_op_ready
            self.health.register("prewarm_ready",
                                 self._check_prewarm_ready, gate=True)
            if self.ladder is not None:
                self.ladder.gate = PrewarmGate(self.prewarm,
                                               plan.rung_targets)
        # drain gate: readiness fails the moment an evacuation starts,
        # whatever else is healthy (a draining host must drop out of
        # the gateway's feasible set before its seats start moving)
        self._check_draining = lambda: (
            _health.failed("host draining (evacuation in progress)")
            if self.draining else _health.ok("not draining"))
        self.health.register("draining", self._check_draining, gate=True)
        # fleet push health: only meaningful when a gateway is
        # configured — an unconfigured host must not carry a forever-
        # degraded check
        self._check_fleet_push = self._fleet_push_check
        if getattr(settings, "fleet_gateway", ""):
            self.health.register("fleet_push", self._check_fleet_push)
        #: serialises switch_to_mode: two overlapping switches must not
        #: interleave stop/start and strand a service
        self._switch_lock = asyncio.Lock()
        if getattr(settings, "fault_inject", ""):
            _faults.registry.arm(settings.fault_inject)
        # env seam (ISSUE 20): the chaos bench arms fault points inside
        # engine-host subprocesses the actuator spawns, before any
        # control-plane endpoint is reachable. Idempotent with the
        # entrypoint's own arm_from_env call.
        _faults.arm_from_env()
        self._setup_routes()

    # ------------------------------------------------------------------ auth
    def _role_for_request(self, request: web.Request) -> Optional[str]:
        """None = reject; 'full' | 'viewonly' otherwise."""
        s = self.settings
        # master bearer token always wins
        token = s.master_token
        auth = request.headers.get("Authorization", "")
        if token and auth.startswith("Bearer ") \
                and _timing_safe_eq(auth[7:], token):
            return "full"
        if not s.enable_basic_auth:
            return "full"
        if auth.startswith("Basic "):
            try:
                user, _, pw = base64.b64decode(auth[6:]).decode().partition(":")
            except Exception:
                return None
            user_ok = _timing_safe_eq(user, s.basic_auth_user or "")
            if user_ok and _timing_safe_eq(pw, s.basic_auth_password or ""):
                return "full"
            if user_ok and s.viewonly_password \
                    and _timing_safe_eq(pw, s.viewonly_password):
                return "viewonly"
        return None

    @web.middleware
    async def _auth_middleware(self, request: web.Request, handler):
        role = self._role_for_request(request)
        if role is None:
            return web.Response(
                status=401, headers={"WWW-Authenticate": 'Basic realm="selkies"'})
        request["role"] = role
        if request.headers.get("Upgrade", "").lower() == "websocket" \
                and not self._is_ws_origin_allowed(request):
            logger.warning("rejected WS upgrade from origin %s",
                           request.headers.get("Origin"))
            return web.Response(status=403, text="origin not allowed")
        return await handler(request)

    def _is_ws_origin_allowed(self, request: web.Request) -> bool:
        """Same-host by default; explicit allow-list via settings
        (reference stream_server.py:647-686)."""
        origin = request.headers.get("Origin")
        if not origin:
            return True  # non-browser clients
        allowed = self.settings.allowed_ws_origins
        if allowed and origin in allowed:
            return True
        try:
            o = urlparse(origin)
        except ValueError:
            return False
        host = request.headers.get("Host", "")
        return o.netloc == host or (o.hostname in ("localhost", "127.0.0.1"))

    # ---------------------------------------------------------------- routes
    def _setup_routes(self) -> None:
        r = self.app.router
        r.add_get("/api/status", self.handle_status)
        r.add_get("/api/health", self.handle_health)
        r.add_post("/api/switch", self.handle_switch)
        r.add_get("/api/trace", self.handle_trace)
        r.add_post("/api/trace", self.handle_trace_control)
        r.add_get("/api/perf", self.handle_perf)
        r.add_get("/api/sessions", self.handle_sessions)
        r.add_get("/api/slo", self.handle_slo)
        r.add_post("/api/profile", self.handle_profile)
        r.add_get("/api/faults", self.handle_faults)
        r.add_post("/api/faults", self.handle_faults_control)
        r.add_get("/api/resilience", self.handle_resilience)
        r.add_get("/api/prewarm", self.handle_prewarm)
        r.add_get("/api/fleet", self.handle_fleet)
        r.add_post("/api/drain", self.handle_drain)
        if self.settings.secure_api:
            r.add_post("/api/tokens", self.handle_mint_token)
            r.add_get("/api/tokens", self.handle_list_tokens)
            r.add_delete("/api/tokens", self.handle_revoke_token)
        if self.settings.enable_metrics:
            r.add_get("/api/metrics", self.handle_metrics)
        if self.settings.enable_file_transfer:
            r.add_post("/api/upload", self.handle_upload)
            r.add_get("/api/files", self.handle_file_index)
            r.add_get("/api/files/{name:.+}", self.handle_file_download)

    def register_static(self) -> None:
        """Added last so /api/* wins; serves the packaged web client plus
        the optional dashboard / touch-gamepad addons when the repo layout
        carries them (reference serves dashboards as separate addon
        bundles, docs/component.md:163-165)."""
        root = WEB_ROOT
        addons = root.parent.parent / "addons"
        dash = addons / "selkies-dashboard"
        if dash.is_dir():
            async def _dash_index(request, d=dash):
                return web.FileResponse(d / "index.html")
            self.app.router.add_get("/dashboard/", _dash_index)
            self.app.router.add_static("/dashboard/", dash,
                                       show_index=False)
        tg = addons / "universal-touch-gamepad"
        if tg.is_dir():
            self.app.router.add_static("/touch-gamepad/", tg,
                                       show_index=False)
        if root.is_dir():
            self.app.router.add_get("/", self._index)
            self.app.router.add_static("/", root, show_index=False)

    async def _index(self, request: web.Request) -> web.StreamResponse:
        return web.FileResponse(WEB_ROOT / "index.html")

    async def handle_status(self, request: web.Request) -> web.Response:
        return web.json_response({
            "app": self.settings.app_name,
            "mode": self.active_mode,
            "uptime_s": round(time.time() - self.started_at, 1),
            "dual_mode": self.settings.enable_dual_mode,
            "role": request["role"],
        })

    # ---------------------------------------------------------------- health
    def _check_service(self) -> "_health.Verdict":
        """Liveness-scope: the transport supervisor itself. A dead
        active service means a restart can actually help."""
        if self.active_mode in self.services:
            return _health.ok(f"mode {self.active_mode}")
        return _health.failed(
            f"active mode {self.active_mode!r} is not a running service")

    def _check_stage_latency(self) -> "_health.Verdict":
        """Stage p99 vs budget from the trace summarizer (PR-2). Honest
        ok when tracing is off — a missing verdict must not read as a
        healthy pipeline, so the reason says WHY there is no number."""
        from ..trace import tracer
        from ..trace.summary import summarize_timelines
        if not tracer.enabled:
            return _health.ok("tracing disabled (enable via /api/trace)")
        # work stages only: a queue's wait (a *.wait lane) is not a stage
        # running over budget, and must not flip /api/health
        summary = summarize_timelines(
            (t for t in tracer.snapshot() if t.done), waits=False)
        if not summary:
            return _health.ok("tracing on, no completed frames yet")
        budget = float(getattr(self.settings, "health_stage_budget_ms",
                               50.0))
        name, stat = max(summary.items(), key=lambda kv: kv[1]["p99_ms"])
        msg = f"worst stage {name} p99={stat['p99_ms']}ms " \
              f"(budget {budget}ms)"
        if stat["p99_ms"] > 2 * budget:
            return _health.failed(msg, stage=name, p99_ms=stat["p99_ms"])
        if stat["p99_ms"] > budget:
            return _health.degraded(msg, stage=name, p99_ms=stat["p99_ms"])
        return _health.ok(msg, stage=name, p99_ms=stat["p99_ms"])

    async def handle_health(self, request: web.Request) -> web.Response:
        """Named verdicts (selkies_tpu/obs). Default payload keeps the
        legacy ``ok``/``mode`` fields; ``?verbose=1`` adds every check's
        verdict + the incident ring; ``?probe=live`` answers only the
        liveness scope (k8s livenessProbe must not crash-loop a pod over
        a dead external relay — that is readiness's job)."""
        if request.query.get("probe") == "live":
            # liveness-scope checks ONLY — a wedged readiness closure
            # must not be able to time this probe out
            report = self.health.liveness()
            report["mode"] = self.active_mode
            return web.json_response(
                report, status=200 if report["live"] else 503)
        if request.query.get("probe") == "ready":
            # readiness + routing gates (prewarm-complete, draining):
            # the load balancer's answer — failed until the current
            # operating point is warm, so traffic never lands on a
            # cold host mid-first-compile (ROADMAP 3's /api/prewarm
            # probe, folded into the probe the LB already polls)
            report = self.health.readiness()
            report["mode"] = self.active_mode
            return web.json_response(
                report, status=200 if report["ready"] else 503)
        report = self.health.report(
            verbose=request.query.get("verbose") in ("1", "true"))
        report["mode"] = self.active_mode
        return web.json_response(report,
                                 status=200 if report["ready"] else 503)

    async def handle_profile(self, request: web.Request) -> web.Response:
        """POST {"action": "start"|"stop"|"status"[, "dir": path]} —
        on-demand jax.profiler capture (full-role gated; start/stop do
        file I/O inside jax, so they run in an executor)."""
        if request["role"] != "full":
            return web.Response(status=403, text="view-only")
        from ..obs import profiler
        try:
            body = await request.json()
        except Exception:
            body = {}
        if not isinstance(body, dict):
            return web.Response(status=400, text="JSON object body required")
        action = body.get("action")
        loop = asyncio.get_running_loop()
        if action == "start":
            trace_dir = body.get("dir") \
                or (self.settings.profile_dir or None)
            res = await loop.run_in_executor(
                None, lambda: profiler.start(trace_dir))
        elif action == "stop":
            res = await loop.run_in_executor(None, profiler.stop)
        elif action == "status":
            res = profiler.status()
        else:
            return web.Response(
                status=400,
                text=f"unknown action {action!r} (want start|stop|status)")
        return web.json_response(res,
                                 status=200 if res.get("ok", True) else 409)

    async def handle_perf(self, request: web.Request) -> web.Response:
        """Performance observability (obs.perf, ISSUE 6): static
        per-step cost analysis (flops / HBM bytes / roofline-ms recorded
        at compile time) plus occupancy / critical-path analysis over
        the live trace ring. ``?profile=1`` additionally parses the last
        completed jax.profiler capture into a per-step device-time table
        (full-role: it reads capture files off disk)."""
        from ..obs import perf as _perf
        from ..obs import profiler
        from ..trace import tracer
        from ..trace.summary import occupancy_report
        done = [t for t in tracer.snapshot() if t.done]
        doc = {
            "perf": _perf.registry.report(),
            "occupancy": occupancy_report(done),
            # energy plane (ISSUE 14): watts / joules-per-frame /
            # fps-per-W (source-labelled proxy|rapl|device) plus the
            # per-frame/per-session attribution over the live ring
            "energy": _energy.meter.report(timelines=done),
            "tracing": tracer.enabled,
        }
        if request.query.get("profile") in ("1", "true"):
            if request["role"] != "full":
                return web.Response(status=403, text="view-only")
            last = profiler.status().get("last_trace_dir")
            if last:
                loop = asyncio.get_running_loop()
                doc["profile"] = await loop.run_in_executor(
                    None, lambda: _perf.parse_profile_dir(last))
            else:
                doc["profile"] = None
        return web.json_response(doc)

    async def handle_slo(self, request: web.Request) -> web.Response:
        """Declarative SLO verdicts (obs.slo): per-objective fast/slow
        burn rates, remaining error budget, and the multi-window
        alerting verdict. Ungated like /api/health — the burn-rate
        panel is the first thing an on-call dashboard polls."""
        return web.json_response(_slo.engine.report())

    async def handle_sessions(self, request: web.Request) -> web.Response:
        """Per-session wire QoE (the ``getStats()`` analog): summary
        list by default, ``?verbose=1`` for the full per-session detail
        (ACK percentiles, backpressure windows, relay counters, CC
        internals). Full-role gated like the other operator surfaces —
        it carries peer addresses and per-client wire state."""
        if request["role"] != "full":
            return web.Response(status=403, text="view-only")
        verbose = request.query.get("verbose") in ("1", "true")
        return web.json_response(_qoe.registry.report(verbose=verbose))

    async def handle_faults(self, request: web.Request) -> web.Response:
        """Armed fault-injection state (full-role: fault specs reveal —
        and steer — failure behaviour)."""
        if request["role"] != "full":
            return web.Response(status=403, text="view-only")
        return web.json_response({
            "active": _faults.registry.active(),
            "remaining": _faults.registry.remaining(),
            "fired": list(_faults.registry.fired_log),
            "seed": _faults.registry.seed,
        })

    async def handle_faults_control(self, request: web.Request
                                    ) -> web.Response:
        """POST {"action": "arm", "spec": "point:mode[:k=v,...];..."
        [, "seed": N]} | {"action": "disarm"[, "point": p]}."""
        if request["role"] != "full":
            return web.Response(status=403, text="view-only")
        try:
            body = await request.json()
        except Exception:
            body = {}
        if not isinstance(body, dict):
            return web.Response(status=400, text="JSON object body required")
        action = body.get("action", "arm")
        if action == "arm":
            spec = body.get("spec", "")
            try:
                armed = _faults.registry.arm(spec, seed=body.get("seed"))
            except (ValueError, TypeError) as e:
                return web.Response(status=400, text=f"bad fault spec: {e}")
            if not armed:
                return web.Response(status=400, text="empty fault spec")
            return web.json_response({"armed": [s.to_dict() for s in armed]})
        if action == "disarm":
            removed = _faults.registry.disarm(body.get("point"))
            return web.json_response({"removed": removed})
        return web.Response(
            status=400, text=f"unknown action {action!r} (want arm|disarm)")

    async def handle_prewarm(self, request: web.Request) -> web.Response:
        """Compile-plane state (selkies_tpu/prewarm): lattice progress,
        per-program states, pause/storm status, the startup warm-cache
        artifact outcome, and the ladder's deferred-transition state.
        Ungated like /api/health — it is the first panel an operator
        checks when a quality downshift is 'taking a while'."""
        ladder = None
        if self.ladder is not None:
            snap = self.ladder.snapshot()
            ladder = {"deferred": snap["deferred"],
                      "deferred_transitions": snap["deferred_transitions"],
                      "gated": snap["gated"], "level": snap["level"]}
        return web.json_response({
            "enabled": self.prewarm is not None,
            "worker": self.prewarm.snapshot() if self.prewarm else None,
            "artifact": self._prewarm_artifact,
            "ladder": ladder,
        })

    async def handle_fleet(self, request: web.Request) -> web.Response:
        """This engine host's fleet heartbeat document (ISSUE 11): the
        capacity/health/SLO/warm snapshot the gateway's scheduler bins
        on. Full-role gated — it enumerates sessions and capacity, the
        same sensitivity as /api/sessions. A push deployment POSTs this
        same document to the gateway's /fleet/heartbeat; a pull
        deployment lets the gateway poll here."""
        if request["role"] != "full":
            return web.Response(status=403, text="view-only")
        doc = self._fleet_heartbeat_doc()
        doc["push"] = dict(self._fleet_push_stats)
        return web.json_response(doc)

    def _fleet_advertise_url(self) -> str:
        """A ROUTABLE base url for heartbeats: the bind address is
        0.0.0.0 by default, which the gateway would dutifully proxy to
        itself."""
        s = self.settings
        url = str(getattr(s, "fleet_url", "") or "")
        if not url:
            import socket as _socket
            host = s.addr if s.addr not in ("0.0.0.0", "::", "") \
                else _socket.gethostname()
            scheme = "https" if s.enable_https else "http"
            url = f"{scheme}://{host}:{s.port}"
        return url

    def _fleet_heartbeat_doc(self) -> dict:
        from ..fleet.protocol import heartbeat_from_core
        self._fleet_seq += 1
        hb = heartbeat_from_core(self, url=self._fleet_advertise_url(),
                                 seq=self._fleet_seq)
        doc = hb.to_dict()
        if self._drain_handle is not None:
            doc["drain"] = {"done": self._drain_handle.done}
        return doc

    # -------------------------------------------------- fleet push loop
    def _fleet_push_check(self):
        """The ``fleet_push`` health verdict: a host whose pushes are
        failing is invisible to the gateway — past the gateway's
        host-timeout horizon that IS host death, so the verdict
        escalates with silence age."""
        st = self._fleet_push_stats
        interval = float(getattr(self.settings,
                                 "fleet_push_interval_s", 2.0))
        if st["last_ok"] is None:
            if st["errors"] or st["rejected"]:
                return _health.degraded(
                    "no successful push yet: " + st["last_error"],
                    **{k: v for k, v in st.items() if k != "last_error"})
            return _health.ok("push loop starting")
        age = time.monotonic() - st["last_ok"]
        if age > 10 * interval:
            return _health.failed(
                f"no successful push for {age:.1f}s "
                f"(gateway sees this host as dead)",
                age_s=round(age, 1), **{"errors": st["errors"]})
        if age > 3 * interval or st["backoff_s"]:
            return _health.degraded(
                f"push degraded (last ok {age:.1f}s ago): "
                + st["last_error"],
                age_s=round(age, 1), backoff_s=st["backoff_s"])
        return _health.ok(f"pushing every {interval}s",
                          sent=st["sent"])

    def _start_fleet_push(self) -> None:
        self._fleet_push_task = asyncio.create_task(
            self._fleet_push_guarded())

    async def _fleet_push_guarded(self) -> None:
        try:
            await self._fleet_push_loop()
        except asyncio.CancelledError:
            raise
        except Exception as e:
            # supervised like the prewarm worker: the supervisor
            # restarts the loop with backoff; budget exhaustion parks
            # it and the fleet_push check goes failed on silence age
            self.supervisor.report_death(
                "fleet_push", f"{type(e).__name__}: {e}")

    async def _fleet_push_loop(self) -> None:
        """POST heartbeats to the gateway on a cadence, with
        exponential backoff on gateway loss, completing one NTP-style
        clock sample per round trip (t0/t3 here, t1/t2 from the
        gateway's response) and echoing it in the NEXT heartbeat — the
        gateway side runs the PR-7 clocksync estimator over these to
        map this host's trace timebase onto its own."""
        import aiohttp
        s = self.settings
        gw = str(getattr(s, "fleet_gateway", "")).rstrip("/")
        interval = float(getattr(s, "fleet_push_interval_s", 2.0))
        max_backoff = max(30.0, 4 * interval)
        token = str(getattr(s, "fleet_token", ""))
        headers = {"Authorization": f"Bearer {token}"} if token else {}
        st = self._fleet_push_stats
        timeout = aiohttp.ClientTimeout(total=max(2.0, 2 * interval))
        async with aiohttp.ClientSession(timeout=timeout) as http:
            while True:
                await asyncio.sleep(st["backoff_s"] or interval)
                # fleet.heartbeat fault point (ISSUE 20): "drop" skips
                # this push entirely (control-plane partition — the
                # gateway must declare the host lost and fail seats
                # over while the data plane keeps streaming); "delay"
                # stalls the push to exercise staleness windows.
                flt = _faults.registry.pull("fleet.heartbeat")
                if flt is not None:
                    if flt.mode == "drop":
                        continue
                    await _faults.registry.sleep_async(flt.delay_s)
                try:
                    doc = self._fleet_heartbeat_doc()
                    if self._fleet_clock_sample is not None:
                        doc["clock"] = self._fleet_clock_sample
                    t0 = time.perf_counter() * 1000.0
                    async with http.post(gw + "/fleet/heartbeat",
                                         json=doc,
                                         headers=headers) as resp:
                        body = await resp.json(content_type=None)
                        t3 = time.perf_counter() * 1000.0
                        if resp.status == 200:
                            st["sent"] += 1
                            st["last_ok"] = time.monotonic()
                            st["last_error"] = ""
                            st["backoff_s"] = 0.0
                            self._fleet_push_metric("ok")
                            clk = (body or {}).get("clock") or {}
                            t1, t2 = clk.get("t1"), clk.get("t2")
                            self._fleet_clock_sample = (
                                [t0, float(t1), float(t2), t3]
                                if isinstance(t1, (int, float))
                                and isinstance(t2, (int, float))
                                else None)
                        else:
                            # the gateway answered: not gateway loss.
                            # A 4xx means OUR document (or token) is
                            # bad — retrying faster cannot help, so
                            # keep the normal cadence, count it, and
                            # let the health check surface it.
                            st["rejected"] += 1
                            st["last_error"] = \
                                f"HTTP {resp.status}: " \
                                f"{str(body)[:120]}"
                            self._fleet_push_metric("rejected")
                except asyncio.CancelledError:
                    raise
                except Exception as e:
                    st["errors"] += 1
                    st["last_error"] = f"{type(e).__name__}: {e}"[:200]
                    st["backoff_s"] = round(min(
                        max_backoff,
                        (st["backoff_s"] or interval) * 2), 2)
                    self._fleet_clock_sample = None
                    self._fleet_push_metric("error")

    def _fleet_push_metric(self, outcome: str) -> None:
        try:
            from . import metrics
            metrics.describe("selkies_fleet_push_total",
                             "Heartbeat pushes to the gateway, by "
                             "outcome (ok/rejected/error)")
            metrics.inc_counter("selkies_fleet_push_total",
                                labels={"outcome": outcome})
        except Exception:
            logger.debug("fleet push metric update failed",
                         exc_info=True)

    async def handle_drain(self, request: web.Request) -> web.Response:
        """POST {"target_url": optional} — start evacuating this host:
        readiness flips failed immediately (the gateway stops routing
        here), connected clients get the ``migrate,{json}`` control
        message (they reconnect through the gateway, landing on their
        re-placed seat with an IDR resync), and the supervisor's drain
        handle starts tracking when every supervised component has
        actually stopped (poll /api/fleet for ``drain.done``)."""
        if request["role"] != "full":
            return web.Response(status=403, text="view-only")
        try:
            body = await request.json()
        except Exception:
            body = {}
        if not isinstance(body, dict):
            return web.Response(status=400, text="JSON object body required")
        target_url = str(body.get("target_url", ""))
        # fleet.drain:hang fault point (ISSUE 20): a wedged engine —
        # the request is accepted and readiness drops, but the drain
        # never starts, clients are never told to migrate and
        # ``drain.done`` never fires. The actuator's bounded await
        # must escalate (drain_wedged) and force-tear the host down
        # only after the gateway's failover path evacuated the seats.
        flt = _faults.registry.pull("fleet.drain")
        if flt is not None and flt.mode == "hang":
            self.draining = True
            return web.json_response({"draining": True,
                                      "wedged": True,
                                      "clients_notified": 0,
                                      "drain_done": False})
        first = not self.draining
        self.draining = True
        if first:
            self.health.recorder.record(
                "host_drain_requested", target_url=target_url)
        # scoped drain (ISSUE 19): done == every SEAT-SERVING component
        # (captures, per-client relays) stopped. The control plane —
        # the service itself, the prewarm worker and above all the
        # fleet heartbeat push — must outlive the evacuation, or the
        # gateway loses sight of the drain it is watching; an unscoped
        # drain can therefore never report done on a live host.
        self._drain_handle = self.supervisor.drain(
            scope=lambda name: name.startswith(("capture:", "relay:")))
        svc = self.services.get(self.active_mode or "")
        notified = 0
        if svc is not None and hasattr(svc, "announce_migration"):
            try:
                notified = await svc.announce_migration(target_url)
            except Exception:
                logger.exception("migration announce failed")
        return web.json_response({
            "draining": True,
            "clients_notified": notified,
            "drain_done": self._drain_handle.done,
        })

    async def handle_resilience(self, request: web.Request) -> web.Response:
        """Supervisor + ladder + faults in one operator snapshot."""
        if request["role"] != "full":
            return web.Response(status=403, text="view-only")
        return web.json_response({
            "supervisor": {
                "components": self.supervisor.components(),
                "total_restarts": self.supervisor.total_restarts,
            },
            "ladder": self.ladder.snapshot() if self.ladder else None,
            "faults": {"active": _faults.registry.active(),
                       "fired": len(_faults.registry.fired_log)},
        })

    async def handle_metrics(self, request: web.Request) -> web.Response:
        from .metrics import render_prometheus
        return web.Response(text=render_prometheus(),
                            content_type="text/plain")

    async def handle_trace(self, request: web.Request) -> web.Response:
        """Current frame timelines as Chrome trace-event JSON — save the
        body and load it in Perfetto / chrome://tracing. ``otherData``
        carries the tracer state so dashboards can poll one endpoint."""
        from ..obs import monitor
        from ..trace import tracer
        from ..trace.export import to_trace_events
        snap = tracer.snapshot()
        doc = to_trace_events(snap, process_name=self.settings.app_name)
        # device-lane overlay: XLA compile events from jax.monitoring,
        # so a Perfetto view shows "recompile happened HERE" against the
        # frame timeline (the overlays stamp perf_counter, which CPython
        # backs with the tracer's CLOCK_MONOTONIC on Linux)
        doc["traceEvents"].extend(monitor.trace_events())
        # qoe-lane overlay: backpressure windows against the frame
        # timeline, so a Perfetto view shows WHEN a seat was paused
        doc["traceEvents"].extend(_qoe.registry.trace_events())
        # resilience-lane overlay: ladder transitions, so a Perfetto
        # view shows WHERE fidelity was shed against the frame timeline
        if self.ladder is not None:
            doc["traceEvents"].extend(self.ladder.trace_events())
        doc["otherData"] = tracer.stats(frames=len(snap))
        doc["otherData"]["compile"] = monitor.compile_stats()
        # the last /api/profile capture's clock mark (host ns): places
        # these spans on that capture's device lanes
        from ..obs.profiler import profiler
        doc["otherData"]["clock_mark_ns"] = profiler.clock_mark_ns
        return web.json_response(doc)

    async def handle_trace_control(self, request: web.Request) -> web.Response:
        """POST {"action": "start"|"stop"|"clear"[, "capacity": N]}."""
        if request["role"] != "full":
            return web.Response(status=403, text="view-only")
        from ..trace import tracer
        try:
            body = await request.json()
        except Exception:
            body = {}
        if not isinstance(body, dict):
            return web.Response(status=400, text="JSON object body required")
        action = body.get("action")
        if action == "start":
            cap = body.get("capacity")
            if cap is not None:
                try:
                    cap = int(cap)
                except (TypeError, ValueError):
                    cap = 0
                if cap <= 0:
                    return web.Response(
                        status=400, text="capacity must be a positive int")
            tracer.enable(cap)
        elif action == "stop":
            tracer.disable()
        elif action == "clear":
            tracer.clear()
        else:
            return web.Response(
                status=400, text=f"unknown action {action!r} "
                "(want start|stop|clear)")
        return web.json_response(tracer.stats())

    async def handle_switch(self, request: web.Request) -> web.Response:
        if request["role"] != "full":
            return web.Response(status=403, text="view-only")
        if not self.settings.enable_dual_mode:
            return web.Response(status=403, text="dual mode disabled")
        body = await request.json()
        mode = body.get("mode")
        if mode not in self.services:
            return web.Response(status=400, text=f"unknown mode {mode!r}")
        await self.switch_to_mode(mode)
        return web.json_response({"mode": self.active_mode})

    # ---------------------------------------------------------------- tokens
    TOKEN_TTL_S = 24 * 3600
    TOKEN_CAP = 512

    def _prune_tokens(self) -> None:
        cutoff = time.time() - self.TOKEN_TTL_S
        for t in [t for t, m in self.ws_tokens.items()
                  if m["created"] < cutoff]:
            del self.ws_tokens[t]
        while len(self.ws_tokens) > self.TOKEN_CAP:  # oldest-first overflow
            self.ws_tokens.pop(next(iter(self.ws_tokens)))

    async def handle_mint_token(self, request: web.Request) -> web.Response:
        """Secure-token mode (reference /api/tokens, selkies.py:4516-4550):
        a full-authority caller mints role-carrying WS tokens; clients
        present them as ?token= on the WS endpoint. Tokens expire after
        TOKEN_TTL_S and can be revoked with DELETE."""
        if request["role"] != "full":
            return web.Response(status=403, text="view-only")
        try:
            body = await request.json()
        except Exception:
            body = {}
        role = body.get("role", "full")
        if role not in ("full", "viewonly"):
            return web.Response(status=400, text="role must be full|viewonly")
        import secrets
        self._prune_tokens()
        token = secrets.token_urlsafe(24)
        self.ws_tokens[token] = {"role": role,
                                 "created": time.time(),
                                 "uses": 0}
        return web.json_response({"token": token, "role": role,
                                  "ttl_s": self.TOKEN_TTL_S})

    async def handle_list_tokens(self, request: web.Request) -> web.Response:
        if request["role"] != "full":
            return web.Response(status=403, text="view-only")
        self._prune_tokens()
        return web.json_response({
            "tokens": [{"token": t[:6] + "…", "role": m["role"],
                        "uses": m["uses"]}
                       for t, m in self.ws_tokens.items()]})

    async def handle_revoke_token(self, request: web.Request) -> web.Response:
        if request["role"] != "full":
            return web.Response(status=403, text="view-only")
        try:
            body = await request.json()
        except Exception:
            body = {}
        revoked = self.ws_tokens.pop(body.get("token", ""), None)
        return web.json_response({"revoked": revoked is not None})

    def check_ws_token(self, token: str) -> Optional[str]:
        """-> role for a live minted token, else None (timing-safe)."""
        self._prune_tokens()
        for t, meta in self.ws_tokens.items():
            if _timing_safe_eq(t, token):
                meta["uses"] += 1
                return meta["role"]
        return None

    # ---------------------------------------------------------------- upload
    def _transfer_root(self) -> pathlib.Path:
        return pathlib.Path(
            os.path.expanduser(self.settings.file_transfer_dir)).resolve()

    def _safe_target(self, name: str) -> pathlib.Path:
        """Reject traversal; refuse symlink targets (reference O_NOFOLLOW
        defence, stream_server.py:947-1098)."""
        root = self._transfer_root()
        target = (root / name).resolve()
        if not str(target).startswith(str(root) + os.sep) and target != root:
            raise web.HTTPBadRequest(text="path escapes transfer dir")
        if target.is_symlink():
            raise web.HTTPBadRequest(text="refusing symlink target")
        return target

    def _transfer_allowed(self, request: web.Request, direction: str) -> bool:
        """Direction gating (reference stream_server.py:980,1171) plus a
        per-role layer: view-only sessions are denied unless the
        direction is explicitly opened to them."""
        allowed = {d.strip() for d in
                   str(getattr(self.settings, "file_transfers",
                               "upload,download")).split(",")}
        if direction not in allowed:
            return False
        if request.get("role") == "full":
            return True
        vo = {d.strip() for d in
              str(getattr(self.settings, "viewonly_file_transfers",
                          "")).split(",")}
        return direction in vo

    async def handle_upload(self, request: web.Request) -> web.Response:
        if not self._transfer_allowed(request, "upload"):
            return web.Response(status=403, text="upload not allowed")
        name = request.headers.get("X-Upload-Name")
        if not name:
            return web.Response(status=400, text="X-Upload-Name required")
        # the client percent-encodes (headers are Latin-1 only; filenames
        # are not); plain names pass through unquote unchanged
        name = urllib.parse.unquote(name)
        try:
            offset = int(request.headers.get("X-Upload-Offset", "0"))
            total = int(request.headers.get("X-Upload-Total", "-1"))
        except ValueError:
            return web.Response(status=400, text="bad offset/total")
        if offset < 0:
            return web.Response(status=400, text="bad offset/total")
        target = self._safe_target(name)
        part = target.with_name(target.name + ".part")
        target.parent.mkdir(parents=True, exist_ok=True)
        mode = "r+b" if part.exists() else "wb"
        max_slice = self.settings.upload_chunk_bytes
        written = 0
        # O_NOFOLLOW equivalent: refuse to write through symlinks
        if part.is_symlink():
            return web.Response(status=400, text="refusing symlink part")
        chunks: list[bytes] = []
        async for chunk in request.content.iter_chunked(1 << 20):
            written += len(chunk)
            if written > max_slice:
                return web.Response(status=413, text="slice too large")
            chunks.append(chunk)

        def _write() -> int:
            # blocking disk I/O off the event loop; a slow disk must not
            # stall frame pacing (buffer is bounded by max_slice above)
            with open(part, mode) as f:
                f.seek(offset)
                for c in chunks:
                    f.write(c)
            return part.stat().st_size

        loop = asyncio.get_running_loop()
        size = await loop.run_in_executor(None, _write)
        if total >= 0 and size >= total:
            part.replace(target)
            return web.json_response({"complete": True, "size": size})
        return web.json_response({"complete": False, "size": size})

    async def handle_file_index(self, request: web.Request) -> web.Response:
        if not self._transfer_allowed(request, "download"):
            raise web.HTTPForbidden(text="download not allowed")
        root = self._transfer_root()
        entries = []
        if root.is_dir():
            for p in sorted(root.iterdir()):
                if p.name.endswith(".part") or p.is_symlink():
                    continue
                entries.append({"name": p.name, "dir": p.is_dir(),
                                "size": p.stat().st_size if p.is_file() else 0})
        if "text/html" in request.headers.get("Accept", ""):
            rows = "".join(
                '<li><a href="/api/files/'
                f'{urllib.parse.quote(e["name"])}">'
                f'{html.escape(e["name"])}</a> ({e["size"]} B)</li>'
                for e in entries if not e["dir"])
            return web.Response(
                text=f"<html><body><h1>Downloads</h1><ul>{rows}</ul></body></html>",
                content_type="text/html")
        return web.json_response({"files": entries})

    async def handle_file_download(self, request: web.Request) -> web.StreamResponse:
        if not self._transfer_allowed(request, "download"):
            raise web.HTTPForbidden(text="download not allowed")
        target = self._safe_target(request.match_info["name"])
        if not target.is_file():
            raise web.HTTPNotFound()
        return web.FileResponse(target)

    # -------------------------------------------------------------- services
    def register_service(self, name: str, service: BaseStreamingService) -> None:
        self.services[name] = service
        service.core = self          # back-ref for token checks etc.
        service.register_routes(self.app)

    async def switch_to_mode(self, mode: str) -> None:
        """Stop the active transport, start the requested one (reference
        stream_server.py:804-895). Serialised: two overlapping switches
        used to interleave stop/start and strand a service. Service
        death is SUPERVISED — restarts with backoff, and only a
        crash-loop past the budget clears active_mode."""
        async with self._switch_lock:
            if mode == self.active_mode:
                return
            old = self.active_mode
            if old and old in self.services:
                await self.services[old].stop()
                self.supervisor.drop(f"service:{old}")
                if self._service_task:
                    # await the cancelled task: its finally-blocks must
                    # finish before the next service starts, or the two
                    # lifetimes interleave
                    self._service_task.cancel()
                    try:
                        await self._service_task
                    except asyncio.CancelledError:
                        pass
                    except Exception:
                        logger.exception("service %s teardown error", old)
                    self._service_task = None
            svc = self.services[mode]
            self.active_mode = mode

            async def _restart_service(mode=mode, svc=svc):
                # same lock as switch_to_mode: a supervised restart must
                # not interleave with an operator-driven switch
                async with self._switch_lock:
                    if self.active_mode != mode:
                        return
                    try:
                        await svc.stop()    # clear half-started state
                    except Exception:
                        logger.exception("pre-restart stop of %s failed",
                                         mode)
                    self._start_service_task(mode, svc)

            def _give_up(mode=mode):
                if self.active_mode == mode:
                    self.active_mode = None

            self.supervisor.adopt(f"service:{mode}", _restart_service,
                                  on_give_up=_give_up)
            self._start_service_task(mode, svc)

    def _start_service_task(self, mode: str, svc: BaseStreamingService
                            ) -> None:
        async def _run_service():
            try:
                await svc.start()
            except asyncio.CancelledError:
                raise
            except Exception as e:
                logger.exception("service %s died", mode)
                if self.active_mode == mode:
                    self.supervisor.report_death(
                        f"service:{mode}", f"{type(e).__name__}: {e}")

        self._service_task = asyncio.create_task(_run_service())

    # ------------------------------------------------------------------- tls
    def _build_ssl(self) -> Optional[ssl.SSLContext]:
        s = self.settings
        if not s.enable_https:
            return None
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(s.https_cert, s.https_key)
        return ctx

    async def _watch_and_reload_certs(self) -> None:
        """Hot-reload the cert when the file changes, never dropping the
        listener (reference stream_server.py:552-632)."""
        s = self.settings
        last = None
        while True:
            await asyncio.sleep(5.0)
            try:
                stat = os.stat(s.https_cert)
                key = (stat.st_mtime_ns, stat.st_size)
                if last is None:
                    last = key
                elif key != last:
                    last = key
                    assert self._ssl_ctx is not None
                    self._ssl_ctx.load_cert_chain(s.https_cert, s.https_key)
                    logger.info("TLS certificate reloaded")
            except FileNotFoundError:
                continue
            except ssl.SSLError:
                logger.exception("cert reload failed; keeping old cert")

    # ------------------------------------------------------------------- run
    async def run(self) -> web.AppRunner:
        # warm-cache artifact (prewarm plane): unpack BEFORE anything
        # can compile so the first session build cache-hits; a
        # fingerprint mismatch is refused (incident recorded) and the
        # server boots cold instead. Executor-side: it is tar+disk I/O.
        if getattr(self.settings, "warm_cache_artifact", ""):
            from ..prewarm import artifact as _artifact
            loop = asyncio.get_running_loop()
            self._prewarm_artifact = await loop.run_in_executor(
                None, lambda: _artifact.unpack_if_configured(
                    self.settings, recorder=self.health.recorder))
        if self.prewarm is not None:
            loop = asyncio.get_running_loop()
            # supervised: a dead worker thread restarts with backoff,
            # budget exhaustion parks it (prewarm check goes degraded)
            self.prewarm.on_death = \
                lambda exc, loop=loop: loop.call_soon_threadsafe(
                    self.supervisor.report_death, "prewarm",
                    f"{type(exc).__name__}: {exc}")
            self.supervisor.adopt("prewarm", self.prewarm.restart)
            self.prewarm.note_operating_point(
                int(self.settings.initial_width),
                int(self.settings.initial_height))
            self.prewarm.start()
        self.register_static()
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        self._ssl_ctx = self._build_ssl()
        site = web.TCPSite(self._runner, self.settings.addr,
                           self.settings.port, ssl_context=self._ssl_ctx)
        await site.start()
        if self._ssl_ctx is not None:
            self._cert_watch_task = asyncio.create_task(
                self._watch_and_reload_certs())
        if self.ladder is not None:
            self._ladder_task = asyncio.create_task(self._ladder_loop())
        if getattr(self.settings, "fleet_gateway", ""):
            self.supervisor.adopt("fleet_push", self._start_fleet_push)
            self._start_fleet_push()
        logger.info("listening on %s:%d (%s)", self.settings.addr,
                    self.settings.port,
                    "https" if self._ssl_ctx else "http")
        return self._runner

    async def _ladder_loop(self) -> None:
        """Degradation-controller driver: evaluate the health checks on
        a cadence and feed the verdict set to the ladder."""
        assert self.ladder is not None
        interval = float(getattr(self.settings, "ladder_interval_s", 2.0))
        while True:
            await asyncio.sleep(interval)
            try:
                self._feed_content_profile()
                self.ladder.observe(self.health.run())
            except Exception:
                logger.exception("degradation ladder tick failed")

    def _feed_content_profile(self) -> None:
        """Content-profile-aware rungs (ROADMAP 4): tell the ladder the
        primary session's content class so downshifts skip rungs the
        class makes pointless (engine/content.CONTENT_LADDER_SKIPS)."""
        assert self.ladder is not None
        svc = self.services.get(self.active_mode or "")
        getter = getattr(svc, "primary_content_class", None)
        if getter is None:
            # mode switched to a service without a classifier: a stale
            # profile must not keep steering the rung walk
            self.ladder.set_content_profile(None)
            return
        try:
            cls = getter()
        except Exception:
            cls = None
        if cls is None:
            self.ladder.set_content_profile(None)
            return
        from ..engine.content import CONTENT_LADDER_SKIPS
        self.ladder.set_content_profile(
            cls, CONTENT_LADDER_SKIPS.get(cls, ()))

    async def shutdown(self) -> None:
        # owner-matched: a newer in-process server may have replaced
        # these names; only OUR closures are removed
        self.health.unregister("service", self._check_service)
        self.health.unregister("stage_latency", self._check_stage_latency)
        self.health.unregister("qoe", self._check_qoe)
        self.health.unregister("slo", self._check_slo)
        self.health.unregister("supervision", self._check_supervision)
        self.health.unregister("draining", self._check_draining)
        self.health.unregister("fleet_push", self._check_fleet_push)
        if self.prewarm is not None:
            self.health.unregister("prewarm", self._check_prewarm)
            self.health.unregister("prewarm_ready",
                                   self._check_prewarm_ready)
            self.prewarm.stop(join_s=2.0)
        self.supervisor.close()
        if self._ladder_task:
            self._ladder_task.cancel()
        if self._fleet_push_task:
            self._fleet_push_task.cancel()
        if self._cert_watch_task:
            self._cert_watch_task.cancel()
        if self.active_mode and self.active_mode in self.services:
            await self.services[self.active_mode].stop()
        if self._service_task:
            self._service_task.cancel()
            try:
                await self._service_task
            except asyncio.CancelledError:
                pass
            except Exception:
                logger.exception("service task teardown error")
        if self._runner:
            await self._runner.cleanup()
