"""One run of one cell: boot the server, warm up, measure, check, report.

The cell names a configuration file (``configs/<name>.json``: the
server's flags, the codec, the warm-up rule) and a traffic file
(``traffic/<mix>.json``: a scene for the general generator). The program
is started in-process with ``selkies_tpu.__main__.start``; the traffic's
source goes into the capture seam (``selkies_tpu.engine.capture
.make_source``) and a tap on the fan-out (``WebSocketsService
._do_fanout``) logs what the server began sending. One browser-shaped
client runs in a child process.

Besides that seam the harness reads a few of the program's private
attributes, for want of seams (PERF.md, Open questions): each is looked
up through ``_seam``, and a program that lacks one fails the run with its
name rather than measuring something else.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import socket
import struct
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
#: frames that have not arrived whole this long after the window count
#: as failed
GRACE_S = 2.0
#: how long the check waits for a frame the server began sending
ARRIVAL_WAIT_S = 60.0
#: quiet time after the last stripe of a window frame was sent
SETTLE_S = 1.0
#: jax.monitoring events that mean a program was built or loaded
COMPILE_EVENTS = ("backend_compile", "cache_hits", "cache_misses")


class RunError(RuntimeError):
    """The run cannot produce a result (no chip, bad cell, no frames)."""


def _seam(obj, name: str):
    """``obj.name``, an attribute of the program the harness reads or
    replaces; RunError naming it where the program no longer has it."""
    try:
        return getattr(obj, name)
    except AttributeError:
        owner = getattr(obj, "__name__", type(obj).__name__)
        raise RunError(f"the program has no {owner}.{name}, which the "
                       "benchmark reads for want of a seam (PERF.md, Open "
                       "questions)") from None


# ---------------------------------------------------------------- the cell
def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """-> (benchmark, cell, configuration, scene), each found by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunError(f"no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    scene = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, scene


def limits_for(cell_name: str) -> dict:
    return json.loads((BENCH / "limits" / f"{cell_name}.json").read_text())


def require_chip(chips: int):
    """The TPU devices, or RunError: a run never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RunError(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise RunError(f"the cell needs {chips} chips; JAX reports "
                       f"{len(devs)}")
    return devs


def process_age_s() -> float:
    """Seconds since this process started (kernel start time)."""
    with open("/proc/self/stat", encoding="ascii") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    return time.clock_gettime(time.CLOCK_BOOTTIME) \
        - start_ticks / os.sysconf("SC_CLK_TCK")


# ---------------------------------------------------------------- the run
@dataclasses.dataclass
class Run:
    """Everything a run recorded; metric readers take what they need."""
    cell: dict
    config: dict
    seed: int
    seconds: float
    desktop: object
    devices: list = dataclasses.field(default_factory=list)
    setup_s: float = 0.0
    w0: float = 0.0                       # window, monotonic seconds
    w1: float = 0.0
    settled_at: float = 0.0               # the wait for stragglers ended
    t0: float = 0.0                       # desktop clock origin
    keys: dict = dataclasses.field(default_factory=dict)
    phase: dict = dataclasses.field(default_factory=dict)
    fanout: list = dataclasses.field(default_factory=list)
    records: list = dataclasses.field(default_factory=list)
    compiles: list = dataclasses.field(default_factory=list)
    spans: list = dataclasses.field(default_factory=list)
    trace: dict | None = None
    memory_peak_bytes: int = 0
    warmup_notes: dict = dataclasses.field(default_factory=dict)

    def due(self, key: int) -> float:
        return self.t0 + key / self.desktop.hz


@dataclasses.dataclass
class Phase:
    """One window of a readings run (``readings.py``): a desktop from its
    seed, the source's mode, and the window's bounds once it has run."""
    desktop: object
    seed: int
    mode: str | None
    w0: float = 0.0
    w1: float = 0.0


class _Compiles:
    """jax.monitoring listener: the monotonic time of every program
    build or persistent-cache load in the process."""

    def __init__(self):
        self.times: list[float] = []

    def duration(self, event: str, duration: float, **_) -> None:
        if any(k in event for k in COMPILE_EVENTS):
            self.times.append(time.monotonic())

    def plain(self, event: str, **_) -> None:
        if any(k in event for k in COMPILE_EVENTS):
            self.times.append(time.monotonic())


class _Client:
    """The browser client child and the thread that reads its records."""

    def __init__(self, url: str):
        self.proc = subprocess.Popen(
            [sys.executable, str(BENCH / "harness" / "client.py"), url],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        #: (arrival, message) of every stripe, filled once the session ends
        self.records: list[tuple[float, bytes]] = []
        #: (frame id, stripe y) of every stripe received so far
        self.seen: set[tuple[int, int]] = set()
        #: frame id -> arrival of its first stripe
        self.arrived: dict[int, float] = {}
        self.first_t: float | None = None
        self._thread = threading.Thread(target=self._read, daemon=True)
        self._thread.start()

    def _read(self) -> None:
        """Arrival records while the session runs, then the messages."""
        from harness.client import END, HEAD, LENGTH, RECORD
        out = self.proc.stdout
        times = []
        while True:
            head = out.read(RECORD.size)
            if len(head) < RECORD.size:
                return
            t, n = RECORD.unpack(head)
            if n == END:
                break
            hdr = out.read(HEAD)
            times.append(t)
            if self.first_t is None:
                self.first_t = t
            # both stripe headers: u16 frame id at byte 2, u16 y at byte 4
            fid, y = struct.unpack_from(">HH", hdr, 2)
            self.seen.add((fid, y))
            self.arrived.setdefault(fid, t)
        for t in times:
            (n,) = LENGTH.unpack(out.read(LENGTH.size))
            self.records.append((t, out.read(n)))

    def stop(self) -> int:
        try:
            self.proc.stdin.write(b"stop\n")
            self.proc.stdin.close()
        except (BrokenPipeError, OSError):
            pass
        try:
            rc = self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            rc = self.proc.wait()
        self._thread.join(timeout=30)
        return rc


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def server_argv(config: dict, port: int) -> list[str]:
    argv = ["--addr", "127.0.0.1", "--port", str(port)]
    for k, v in config["flags"].items():
        argv += [f"--{k}", str(v).lower() if isinstance(v, bool) else str(v)]
    return argv


def _prewarm_readback(session) -> None:
    """Build the readback slice programs for every bucket the session's
    stream buffer can be fetched at, before the window: which bucket a
    frame needs depends on its size, and a size first met in the window
    would compile there."""
    import jax.numpy as jnp
    from selkies_tpu.engine import readback
    cap = int(_seam(session, "_out_cap"))
    dummy = jnp.zeros((cap,), jnp.uint8)
    stream = _seam(readback, "fetch_stream_bytes")
    stripe = _seam(readback, "fetch_stripe_bytes")
    for floor, fetch in ((_seam(readback, "MIN_BUCKET"),
                          lambda n: stream(dummy, n)),
                         (_seam(readback, "MIN_STRIPE_BUCKET"),
                          lambda n: stripe(dummy, 0, n))):
        b = floor
        while b < cap:
            fetch(b)
            b *= 2


async def serve(run: Run, trace: bool, mode: str | None = None,
                trace_dir: str | None = None, phases: tuple = ()) -> None:
    """Boot, warm up, measure ``run.seconds`` (or each of ``phases`` in
    turn, a readings run), wait for the stragglers, shut down. Fills
    ``run``; the check runs after this returns."""
    import jax
    import selkies_tpu.engine.capture as capture_mod
    from selkies_tpu.__main__ import start
    from selkies_tpu.trace import tracer

    from harness.source import TickLog, TrafficSource

    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles.duration)
    jax.monitoring.register_event_listener(compiles.plain)
    log = TickLog()
    desktop = run.desktop
    warm = run.config["warmup"]
    sources: list = []

    def make_source(kind, width, height, display=None):
        if (width, height) != (desktop.width, desktop.height):
            raise RunError(f"capture asks {width}x{height}, the traffic is "
                           f"{desktop.width}x{desktop.height}")
        sources.append(TrafficSource(desktop, log, mode=mode))
        return sources[-1]

    seam = _seam(capture_mod, "make_source")
    capture_mod.make_source = make_source
    port = _free_port()
    serving = await start(server_argv(run.config, port))
    client = None
    try:
        ws = serving.ws
        fan_lock = threading.Lock()
        inner = _seam(ws, "_do_fanout")

        def tap(chunk):
            with fan_lock:
                run.fanout.append((time.monotonic(), chunk.frame_id,
                                   chunk.stripe_y, len(chunk.payload)))
            inner(chunk)

        ws._do_fanout = tap
        client = _Client(f"ws://127.0.0.1:{port}/api/websockets")
        await _warm_up(run, client, ws, compiles, warm)
        run.setup_s = process_age_s()
        if phases:
            await _phases(run, sources, phases)
        else:
            run.w0 = time.monotonic()
            if trace:
                tracer.clear()
                tracer.enable(capacity=1 << 16)
                await _traced_window(run, trace_dir)
            else:
                await asyncio.sleep(run.seconds)
            run.w1 = run.w0 + run.seconds
            await asyncio.sleep(max(0.0, run.w1 - time.monotonic()))
            if trace:
                tracer.disable()
                run.spans = [tl.to_dict() for tl in tracer.snapshot()]
        await _await_stragglers(run, client)
        run.memory_peak_bytes = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in run.devices)
    finally:
        rc = 0
        if client is not None:
            rc = client.stop()
            run.records = list(client.records)
        await serving.close()
        capture_mod.make_source = seam
    if rc != 0:
        raise RunError(f"the client exited with {rc}")
    run.t0 = log.t0
    run.keys = dict(log.keys)
    run.phase = dict(log.phase)
    run.compiles = list(compiles.times)


#: seconds between a readings run's switch to a new phase and its window:
#: the frames captured before the switch drain in them
SWITCH_S = 3.0


async def _phases(run: Run, sources: list, phases: tuple) -> None:
    """Each phase in turn: switch the source, let the frames of the last
    phase drain, measure ``run.seconds``. Phase k's ticks log phase k."""
    for k, ph in enumerate(phases, 1):
        sources[-1].switch(ph.desktop, ph.mode, k)
        await asyncio.sleep(SWITCH_S)
        ph.w0 = time.monotonic()
        await asyncio.sleep(run.seconds)
        ph.w1 = ph.w0 + run.seconds
    run.w0, run.w1 = phases[-1].w0, phases[-1].w1


async def _warm_up(run: Run, client: _Client, ws, compiles: _Compiles,
                   warm: dict) -> None:
    """Until the first frame has arrived, ``min_s`` have passed since,
    ``min_frames`` frames have arrived since the last program was built
    or loaded, and for ``quiet_s`` no program was built or loaded and the
    content classifier kept its class; within ``max_s``. (A compile is
    seen when it ends: the frames after it show that none is running.)"""
    t_boot = time.monotonic()
    prewarmed = None          # the buffer size the readback is warm for
    klass, t_class = None, t_boot
    while True:
        await asyncio.sleep(0.25)
        now = time.monotonic()
        if now - t_boot > warm["max_s"]:
            raise RunError(f"no steady state within {warm['max_s']} s: "
                           f"{len(client.records)} stripes, "
                           f"{len(compiles.times)} compiles")
        if client.proc.poll() is not None:
            raise RunError(f"the client exited with {client.proc.returncode}"
                           " during warm-up")
        if client.first_t is None:
            continue
        cap = next(iter(_seam(ws, "captures").values()), None)
        if cap is None:
            continue
        session = _seam(cap, "_session")
        if session is not None and _seam(session, "_out_cap") != prewarmed:
            # again after an overflow grew the stream buffer
            prewarmed = session._out_cap
            await asyncio.get_running_loop().run_in_executor(
                None, _prewarm_readback, session)
        state = _seam(cap, "content_state")().get("class")
        if state != klass:
            klass, t_class = state, now
        last_compile = compiles.times[-1] if compiles.times else t_boot
        since = sum(1 for t in list(client.arrived.values())
                    if t > last_compile)
        quiet = warm["quiet_s"]
        if now - client.first_t >= warm["min_s"] \
                and since >= warm["min_frames"] \
                and now - last_compile >= quiet and now - t_class >= quiet:
            run.warmup_notes = {"content_class": klass,
                                "first_frame_s": client.first_t - t_boot,
                                "compiles": len(compiles.times),
                                "s_after_last_load": now - last_compile,
                                "frames_after_last_load": since}
            return


async def _traced_window(run: Run, trace_dir: str) -> None:
    """The window with the profiler on for its middle ``trace_s``."""
    import jax

    from harness.trace import CLOCK_MARK
    loop = asyncio.get_running_loop()
    span = min(run.config["trace_s"], run.seconds)
    await asyncio.sleep((run.seconds - span) / 2)
    await loop.run_in_executor(None, jax.profiler.start_trace, trace_dir)
    t_mark = time.monotonic_ns()
    with jax.profiler.TraceAnnotation(CLOCK_MARK):
        pass
    lo = time.monotonic()
    await asyncio.sleep(max(0.0, lo + span - time.monotonic()))
    hi = time.monotonic()
    await loop.run_in_executor(None, jax.profiler.stop_trace)
    run.trace = {"mark_ns": t_mark, "lo": lo, "hi": hi, "dir": trace_dir}


async def _await_stragglers(run: Run, client: _Client) -> None:
    """Wait until the server has begun a frame after the close (the
    finalizer sends frames in order, so the frame straddling the close
    has then been sent whole), every stripe of every frame it began
    sending in the window has arrived, and no stripe of those frames has
    been sent for ``SETTLE_S``; at most ``ARRIVAL_WAIT_S`` past the
    close."""
    deadline = run.w1 + ARRIVAL_WAIT_S
    while time.monotonic() < deadline:
        first: dict[int, float] = {}
        due, last_sent = set(), 0.0
        for t, fid, y, _ in list(run.fanout):
            first.setdefault(fid, t)
            if run.w0 <= first[fid] < run.w1:
                due.add((fid, y))
                last_sent = max(last_sent, t)
        moved_on = any(t >= run.w1 for t in first.values())
        if moved_on and due <= client.seen \
                and time.monotonic() - last_sent >= SETTLE_S:
            break
        await asyncio.sleep(0.1)
    run.settled_at = time.monotonic()


# ------------------------------------------------------------ end to end
def quantile(values: list, q: float) -> float:
    """Linear-interpolated quantile (numpy's default method)."""
    return float(np.quantile(np.asarray(values, np.float64), q))


def window_frames(run: Run, frames: dict, w0: float | None = None,
                  w1: float | None = None) -> list[int]:
    """The frames the server began sending in the window (or in
    ``[w0, w1)``)."""
    w0 = run.w0 if w0 is None else w0
    w1 = run.w1 if w1 is None else w1
    return sorted(f.fid for f in frames.values()
                  if f.sent and w0 <= f.t_send < w1)


def end_to_end(run: Run, frames: dict, window: list
               ) -> tuple[dict, int, list]:
    """-> (metric values, frames not whole in time, the latencies as
    (ms, frame id, is IDR), longest first)."""
    deadline = run.w1 + GRACE_S
    delivered = [frames[f] for f in window
                 if (frames[f].whole_at() or float("inf")) <= deadline]
    first_whole: dict[int, tuple] = {}
    for f in delivered:
        key = run.keys[f.fid]
        t = f.whole_at()
        if key not in first_whole or t < first_whole[key][0]:
            first_whole[key] = (t, f)
    detail = sorted(((t - run.due(k)) * 1e3, f.fid, f.idr)
                    for k, (t, f) in first_whole.items())[::-1]
    lat = [d[0] for d in detail]
    if not lat:
        raise RunError("no desktop update reached the client whole in the "
                       "window")
    bits = sum(f.nbytes for f in delivered) * 8
    values = {
        "setup_s": run.setup_s,
        "frame_latency_p50_ms": quantile(lat, 0.5),
        "frame_latency_p95_ms": quantile(lat, 0.95),
        "fps_delivered": len(first_whole) / run.seconds,
        "kbit_per_frame": bits / 1e3 / len(delivered),
    }
    return values, len(window) - len(delivered), detail
