"""selkies_tpu/trace tests: span nesting/ordering, ring eviction,
disabled-mode overhead, trace-event JSON round-trip, /api/trace endpoint
contract, summarizer percentiles, CLI — plus the compile-cache host
fingerprint satellite (ISSUE 2)."""

import json
import os
import threading
import time
import tracemalloc
import types

from selkies_tpu import compile_cache
from selkies_tpu.trace import STAGES
from selkies_tpu.trace import tracer as global_tracer
from selkies_tpu.trace.__main__ import main as trace_cli
from selkies_tpu.trace.core import _NULL_SPAN, FrameTracer, now_ns
from selkies_tpu.trace.export import (events_from_document,
                                      timelines_from_events, to_trace_events)
from selkies_tpu.trace.summary import (frame_latency_ms, render_table,
                                       summarize_durations,
                                       summarize_events,
                                       summarize_timelines)


# -- core ---------------------------------------------------------------------

def test_span_nesting_and_ordering():
    tr = FrameTracer()
    tr.enable()
    tl = tr.frame_begin(":0")
    tr.bind(tl, 1)
    with tr.span("outer", tl):
        time.sleep(0.002)
        with tr.span("inner", tl):
            time.sleep(0.001)
    tr.frame_end(":0", 1)
    assert tl.done and tl.frame_id == 1
    names = [s[0] for s in tl.spans]
    assert names == ["inner", "outer"]      # exit order: inner closes first
    spans = {n: (t0, dur) for n, _lane, t0, dur, _cpu in tl.spans}
    o_t0, o_dur = spans["outer"]
    i_t0, i_dur = spans["inner"]
    assert o_t0 <= i_t0 and i_t0 + i_dur <= o_t0 + o_dur   # containment
    assert i_dur >= 1_000_000 and o_dur >= 3_000_000
    assert tl.wall_ms() >= 3.0


def test_current_context_spans_without_explicit_timeline():
    tr = FrameTracer()
    tr.enable()
    tl = tr.frame_begin(":0")
    with tr.span("capture"):            # resolves via contextvar
        pass
    tr.bind(tl, 9)
    assert [s[0] for s in tl.spans] == ["capture"]
    # explicit None target (evicted frame) must NOT fall back to current
    with tr.span("stray", None):
        pass
    assert len(tl.spans) == 1


def test_ring_buffer_eviction():
    tr = FrameTracer(capacity=4)
    tr.enable()
    for fid in range(10):
        tl = tr.frame_begin(":0")
        tr.bind(tl, fid)
        tr.frame_end(":0", fid)
    snap = tr.snapshot()
    assert [t.frame_id for t in snap] == [6, 7, 8, 9]
    assert tr.lookup(":0", 0) is None
    assert not tr.attach_span(":0", 0, "ws.send", 0, 1000)
    assert tr.stats()["dropped"] == 6
    tr.clear()
    assert tr.snapshot() == [] and tr.stats()["frames"] == 0


def test_disabled_mode_no_allocation_beyond_flag_check():
    tr = FrameTracer()
    assert not tr.enabled
    # the disabled span is one shared singleton — identity proves no
    # per-call allocation
    assert tr.span("a") is tr.span("b") is _NULL_SPAN
    assert tr.span("w", wait=True) is _NULL_SPAN
    assert tr.frame_begin(":0") is None
    assert tr.current() is None
    tr.bind(None, 1)
    tr.frame_end(":0", 1)
    assert not tr.attach_span(":0", 1, "x", 0, 1)
    # a timeline begun while on takes nothing once the tracer is off
    tr.enable()
    held = tr.frame_begin(":0")
    tr.disable()
    # a full per-frame call sequence retains nothing, the counters, the
    # explicit-end record and the wait lane included
    tracemalloc.start()
    for _ in range(1000):
        with tr.span("x"):
            pass
        with tr.span("w", held, lane="slot0", wait=True):
            pass
        tr.count(held, "readback.bytes", 4096)
        tr.count(None, "readback.bytes", 4096)
        tr.record_span(held, "pipeline.queue", 0, lane="slot0", end_ns=5,
                       wait=True)
        t = tr.frame_begin(":0")
        tr.current()
        tr.bind(t, 0)
        tr.frame_end(":0", 0)
    current, _peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert current < 2048, f"disabled tracer retained {current} bytes"
    assert held.spans == [] and held.counts == {}


def test_wait_spans_ride_the_wait_lane_without_cpu_time():
    tr = FrameTracer()
    tr.enable()
    tl = tr.frame_begin(":0")
    tr.bind(tl, 1)
    with tr.span("encode.dispatch", tl):
        with tr.span("encode.probe_sync", tl, wait=True):
            time.sleep(0.002)
    with tr.span("packetize", tl, lane="slot1"):
        with tr.span("readback.slice_wait", tl, lane="slot1", wait=True):
            pass
    tr.record_span(tl, "pipeline.queue", 1_000, lane="slot1",
                   end_ns=4_000, wait=True)
    tr.record_span(tl, "encode.readback", 1_000, lane="slot1",
                   end_ns=9_000)
    me = threading.current_thread().name
    spans = {s["name"]: s for s in tl.to_dict()["spans"]}
    assert spans["encode.probe_sync"]["lane"] == me + ".wait"
    assert spans["readback.slice_wait"]["lane"] == "slot1.wait"
    assert spans["pipeline.queue"]["lane"] == "slot1.wait"
    assert (spans["pipeline.queue"]["t0_ns"],
            spans["pipeline.queue"]["dur_ns"]) == (1_000, 3_000)
    assert spans["encode.readback"]["lane"] == "slot1"
    # work spans measured on this thread carry its CPU time; waits and
    # spans measured from stamps carry none
    for name in ("encode.dispatch", "packetize"):
        assert 0 <= spans[name]["cpu_ns"] <= spans[name]["dur_ns"] + 1e6
    for name in ("encode.probe_sync", "readback.slice_wait",
                 "pipeline.queue", "encode.readback"):
        assert "cpu_ns" not in spans[name], name
    # sleeping is off the CPU: the dispatch's CPU time is far below its
    # wall time, which holds the 2 ms wait
    assert spans["encode.dispatch"]["cpu_ns"] < 1_000_000
    assert spans["encode.dispatch"]["dur_ns"] >= 2_000_000


def test_spans_read_the_monotonic_clock():
    tr = FrameTracer()
    tr.enable()
    tl = tr.frame_begin(":0")
    before = time.monotonic_ns()
    with tr.span("capture", tl):
        pass
    after = time.monotonic_ns()
    (_, _, t0, dur, _), = tl.spans
    assert before <= t0 and t0 + dur <= after
    # stamps taken for spans recorded later read the same clock
    assert after <= now_ns() <= time.monotonic_ns()


def test_counts_accumulate_and_ride_the_frame_envelope():
    tr = FrameTracer()
    tr.enable()
    tl = tr.frame_begin(":0")
    tr.bind(tl, 3)
    tr.count(tl, "readback.bytes", 32768)
    tr.count(tl, "readback.bytes", 4096)
    tr.count(tl, "test.rows", 68)
    with tr.span("packetize", tl, lane="slot0"):
        pass
    tr.frame_end(":0", 3)
    assert tl.to_dict()["counts"] == {"readback.bytes": 36864,
                                      "test.rows": 68}
    doc = json.loads(json.dumps(to_trace_events(tr.snapshot())))
    env = [e for e in doc["traceEvents"] if e.get("name") == "frame 3"]
    assert env[0]["args"]["counts"] == {"readback.bytes": 36864,
                                        "test.rows": 68}
    work = [e for e in doc["traceEvents"] if e.get("name") == "packetize"]
    assert work[0]["args"]["cpu_ns"] == tl.spans[0][4]
    # the export reads back whole: counters and CPU time included
    back, = timelines_from_events(events_from_document(doc))
    assert back["counts"] == tl.to_dict()["counts"]
    assert back["spans"][0]["cpu_ns"] == tl.spans[0][4]


def test_stage_latency_health_reads_work_stages_only():
    """A queue's wait is no stage over budget: /api/health's
    stage_latency verdict leaves the *.wait lanes out."""
    from test_server import make_app
    server, *_ = make_app()
    global_tracer.enable(capacity=16)
    try:
        tl = global_tracer.frame_begin(":0")
        global_tracer.bind(tl, 1)
        global_tracer.attach_span(":0", 1, "packetize", 0, 1_000_000,
                                  lane="slot0")
        global_tracer.attach_span(":0", 1, "pipeline.queue", 0,
                                  900_000_000, lane="slot0.wait")
        global_tracer.frame_end(":0", 1)
        v = server._check_stage_latency()
        assert v.status == "ok", v
        assert "packetize" in v.reason
    finally:
        global_tracer.disable()
        global_tracer.clear()


def test_profiler_capture_opens_with_a_clock_mark(monkeypatch):
    """The capture's first event is a ``selkies.clock`` annotation with
    the host's CLOCK_MONOTONIC ns, kept for GET /api/trace."""
    import jax

    from selkies_tpu.obs.profiler import CLOCK_MARK, ProfilerSession
    marks = []

    class _Annotation:
        def __init__(self, name, **kw):
            marks.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    sess = ProfilerSession()
    before = time.monotonic_ns()
    assert sess.start("/nonexistent-trace-dir")["ok"]
    after = time.monotonic_ns()
    (name, kw), = marks
    assert name == CLOCK_MARK == "selkies.clock"
    assert before <= kw["host_ns"] == sess.clock_mark_ns <= after


def test_bind_aliases_route_multi_seat_attach():
    tr = FrameTracer()
    tr.enable()
    tl = tr.frame_begin("__seats__")
    tr.bind(tl, 3, aliases=("seat0", "seat1"))
    assert tr.lookup("seat0", 3) is tl
    assert tr.attach_span("seat1", 3, "ws.send", 0, 2_000_000, lane="ws")
    assert tr.instant("seat0", 3, "ack")
    assert len(tr.snapshot()) == 1          # aliases dedupe in snapshot
    names = [s[0] for s in tl.spans]
    assert names == ["ws.send", "ack"]


def test_enable_mid_stream_and_reenable():
    tr = FrameTracer()
    assert tr.frame_begin(":0") is None
    tr.enable(capacity=8)
    tl = tr.frame_begin(":0")
    tr.bind(tl, 1)
    tr.disable()
    # post-disable calls are no-ops, ring keeps what it had
    assert tr.frame_begin(":0") is None
    assert tr.lookup(":0", 1) is None       # lookups gate on enabled
    assert len(tr.snapshot()) == 1          # but the data survives


# -- export / summarize -------------------------------------------------------

def _built_tracer():
    tr = FrameTracer()
    tr.enable()
    tl = tr.frame_begin(":0")
    tr.bind(tl, 1)
    tr.attach_span(":0", 1, "capture", 1_000, 2_000_000)
    tr.attach_span(":0", 1, "encode.dispatch", 2_001_000, 5_000_000)
    tr.attach_span(":0", 1, "packetize", 7_001_000, 500_000,
                   lane="seat0")
    tr.instant(":0", 1, "ack", lane="ws")
    tr.frame_end(":0", 1)
    return tr


def test_trace_event_json_schema_roundtrip():
    tr = _built_tracer()
    doc = to_trace_events(tr.snapshot())
    assert doc["displayTimeUnit"] == "ms"
    loaded = json.loads(json.dumps(doc))    # the wire round-trip
    events = events_from_document(loaded)
    assert events, "no events survived"
    lanes = set()
    for e in events:
        assert e["ph"] in ("X", "M", "i")
        assert e["pid"] == 1 and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float)) and e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
        if e["ph"] == "M" and e["name"] == "thread_name":
            lanes.add(e["args"]["name"])
    assert {"frames", "seat0", "ws"} <= lanes
    # the per-frame envelope rides the frames track
    assert any(e["ph"] == "X" and e["name"] == "frame 1" for e in events)
    # summarizing the export matches summarizing the live timelines
    assert summarize_events(events) == summarize_timelines(tr.snapshot())
    # bare-array form is accepted too
    assert events_from_document(loaded["traceEvents"]) == events


def test_summarizer_percentiles_hand_built():
    durs = [float(v) for v in range(1, 101)]     # 1..100 ms
    s = summarize_durations({"stage": durs})["stage"]
    assert s["count"] == 100
    assert s["p50_ms"] == 51.0              # nearest-rank, bench convention
    assert s["p99_ms"] == 100.0
    assert s["mean_ms"] == 50.5
    assert s["total_ms"] == 5050.0
    # sorted by total descending
    two = summarize_durations({"small": [1.0], "big": [500.0]})
    assert list(two) == ["big", "small"]
    assert "stage" in render_table(s and {"stage": s})


def test_frame_latency_and_instants_excluded():
    tr = _built_tracer()
    lats = frame_latency_ms(tr.snapshot())
    assert len(lats) == 1 and lats[0] > 0
    summ = summarize_timelines(tr.snapshot())
    assert "ack" not in summ                 # zero-duration markers excluded
    assert summ["encode.dispatch"]["p50_ms"] == 5.0
    assert summ["capture"]["p50_ms"] == 2.0


def test_stage_sink_feeds_metrics_histogram():
    from selkies_tpu.server import metrics
    metrics.clear()
    tr = FrameTracer()
    tr.enable()
    assert tr.stage_sink is not None
    tl = tr.frame_begin(":0")
    tr.bind(tl, 1)
    tr.attach_span(":0", 1, "encode.readback", 0, 5_000_000)   # 5 ms
    text = metrics.render_prometheus()
    assert 'selkies_stage_ms_bucket{stage="encode.readback",le="5"} 1' \
        in text
    assert 'selkies_stage_ms_count{stage="encode.readback"} 1' in text


# -- CLI ----------------------------------------------------------------------

def test_cli_summarize(tmp_path, capsys):
    doc = to_trace_events(_built_tracer().snapshot())
    p = tmp_path / "t.json"
    p.write_text(json.dumps(doc))
    assert trace_cli(["summarize", str(p), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["version"] == 1
    assert out["stages"]["encode.dispatch"]["count"] == 1
    assert trace_cli(["summarize", str(p)]) == 0
    assert "encode.dispatch" in capsys.readouterr().out
    assert trace_cli(["summarize", str(tmp_path / "missing.json")]) == 2
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert trace_cli(["summarize", str(bad)]) == 2


def test_cli_selftest_roundtrip(tmp_path):
    out = tmp_path / "selftest.json"
    assert trace_cli(["selftest", str(out)]) == 0
    events = events_from_document(json.loads(out.read_text()))
    summ = summarize_events(events)
    for stage in STAGES:
        assert stage in summ


# -- engine integration: the session spans land on the frame timeline --------

def test_jpeg_session_records_stage_spans():
    from selkies_tpu.engine.encoder import JpegEncoderSession
    from selkies_tpu.engine.types import CaptureSettings

    import jax.numpy as jnp
    s = CaptureSettings(capture_width=64, capture_height=32,
                        stripe_height=16, use_damage_gating=True)
    sess = JpegEncoderSession(s)
    g = sess.grid
    frame = jnp.zeros((g.height, g.width, 3), jnp.uint8) + 7
    global_tracer.enable(capacity=32)
    try:
        tl = global_tracer.frame_begin(s.display_id)
        out = sess.encode(frame)
        global_tracer.bind(tl, out["frame_id"])
        chunks = sess.finalize(out, force_all=True)
        global_tracer.frame_end(s.display_id, out["frame_id"])
        assert chunks
        names = [sp[0] for sp in tl.spans]
        assert {"encode.dispatch", "encode.readback", "packetize"} \
            <= set(names)
        # exactly ONE span per stage per frame — fragments would double
        # the count and skew the stage percentiles
        for stage in ("encode.dispatch", "encode.readback", "packetize"):
            assert names.count(stage) == 1, names
        summ = summarize_timelines([tl])
        assert summ["encode.dispatch"]["count"] == 1
    finally:
        global_tracer.disable()
        global_tracer.clear()


# -- the server's waits: the hop to the loop, the relay queue ----------------

async def test_relay_queue_wait_rides_the_ws_wait_lane():
    """A stripe's time in the relay queue, from ``offer`` to the pop for
    its send, is its ``relay.queue`` span on ``ws.wait``."""
    import asyncio

    from selkies_tpu import protocol as P
    from selkies_tpu.server.relay import VideoRelay
    gate = asyncio.Event()

    async def _send(data):
        await gate.wait()

    idle = VideoRelay(_send, display=":0")
    idle.offer(P.pack_jpeg_stripe(41, 0, b"\xff\xd8x\xff\xd9"))
    assert idle._queued_at == {}            # tracer off: no stamps
    global_tracer.enable(capacity=16)
    try:
        tl = global_tracer.frame_begin(":0")
        global_tracer.bind(tl, 42)
        relay = VideoRelay(_send, display=":0")
        relay.start()
        for y in (0, 64):
            relay.offer(P.pack_jpeg_stripe(42, y, b"\xff\xd8x\xff\xd9"))
        await asyncio.sleep(0.02)
        gate.set()
        for _ in range(100):
            await asyncio.sleep(0.005)
            if sum(s[0] == "ws.send" for s in tl.spans) == 2:
                break
        await relay.close()
        spans = tl.to_dict()["spans"]
        queued = [s for s in spans if s["name"] == "relay.queue"]
        sends = [s for s in spans if s["name"] == "ws.send"]
        assert len(queued) == len(sends) == 2
        assert {s["lane"] for s in queued} == {"ws.wait"}
        for q, w in zip(queued, sends):
            assert q["t0_ns"] + q["dur_ns"] == w["t0_ns"]
        # the second stripe waited behind the first one's blocked send
        assert queued[1]["dur_ns"] >= 15_000_000
        assert relay._queued_at == {}
    finally:
        global_tracer.disable()
        global_tracer.clear()


def test_fanout_hop_is_traced_and_calls_the_instance_fanout():
    """The traced hop records ``fanout.hop`` on ``loop.wait`` and then
    runs ``_do_fanout`` as looked up on the instance at call time (a
    wrapper put there, as the benchmark's tap is, sees every chunk)."""
    from selkies_tpu.engine.types import EncodedChunk
    from test_server import make_app
    _server, svc, *_ = make_app()
    seen = []
    svc._do_fanout = seen.append
    chunk = EncodedChunk(b"x", 5, 0, 64, 64, True, "jpeg")
    global_tracer.enable(capacity=16)
    try:
        tl = global_tracer.frame_begin(":0")
        global_tracer.bind(tl, 5)
        t_hop = now_ns() - 2_000_000
        svc._fanout_hopped(chunk, t_hop)
        assert seen == [chunk]
        (name, lane, t0, dur, cpu), = tl.spans
        assert (name, lane, t0, cpu) == ("fanout.hop", "loop.wait", t_hop,
                                         None)
        assert dur >= 2_000_000
    finally:
        global_tracer.disable()
        global_tracer.clear()


# -- /api/trace endpoint contract ---------------------------------------------

async def test_api_trace_endpoint(client_factory):
    from test_server import make_app
    server, _svc, _fake, _ = make_app()
    c = await client_factory(server)
    try:
        global_tracer.disable()
        global_tracer.clear()
        r = await c.post("/api/trace", json={"action": "start",
                                             "capacity": 64})
        body = await r.json()
        assert r.status == 200 and body["enabled"] is True \
            and body["capacity"] == 64
        tl = global_tracer.frame_begin(":0")
        global_tracer.bind(tl, 7)
        global_tracer.attach_span(":0", 7, "capture", 0, 1_000_000)
        global_tracer.frame_end(":0", 7)
        r = await c.get("/api/trace")
        assert r.status == 200
        doc = await r.json()
        events = events_from_document(doc)
        names = [e.get("name") for e in events]
        assert "capture" in names and "frame 7" in names
        assert doc["otherData"]["frames"] == 1
        # the last /api/profile capture's clock mark, None before any
        assert "clock_mark_ns" in doc["otherData"]
        r = await c.post("/api/trace", json={"action": "clear"})
        assert (await r.json())["frames"] == 0
        r = await c.post("/api/trace", json={"action": "stop"})
        assert (await r.json())["enabled"] is False
        r = await c.post("/api/trace", json={"action": "bogus"})
        assert r.status == 400
        r = await c.post("/api/trace")                  # no body
        assert r.status == 400
        r = await c.post("/api/trace", json=["start"])  # non-object body
        assert r.status == 400
        for bad_cap in ("abc", 0, -3):
            r = await c.post("/api/trace",
                             json={"action": "start", "capacity": bad_cap})
            assert r.status == 400, bad_cap
        assert global_tracer.enabled is False           # none took effect
    finally:
        global_tracer.disable()
        global_tracer.clear()


async def test_api_trace_post_needs_full_role(client_factory):
    import base64
    from test_server import make_app
    server, *_ = make_app(enable_basic_auth=True, basic_auth_user="u",
                          basic_auth_password="pw", viewonly_password="vo")
    c = await client_factory(server)
    vo = {"Authorization": "Basic " + base64.b64encode(b"u:vo").decode()}
    r = await c.post("/api/trace", json={"action": "start"}, headers=vo)
    assert r.status == 403
    r = await c.get("/api/trace", headers=vo)     # snapshots are readable
    assert r.status == 200


# -- compile-cache host fingerprint (satellite) -------------------------------

def test_host_fingerprint_stable_and_sanitized():
    fp = compile_cache.host_fingerprint()
    assert fp == compile_cache.host_fingerprint()       # stable in-process
    assert fp and "/" not in fp and " " not in fp
    fp2 = compile_cache.host_fingerprint("TPU v5e/lite pod")
    assert fp2.startswith(fp) and "/" not in fp2 and " " not in fp2
    assert fp2 != fp


class _Cfg:
    def __init__(self):
        self.updates = {}

    def update(self, k, v):
        self.updates[k] = v


def test_compile_cache_dir_keyed_by_fingerprint(monkeypatch):
    """No JAX_COMPILATION_CACHE_DIR: the fixed <checkout>/.jax_cache,
    keyed by the host fingerprint, and set in code."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fake_jax = types.SimpleNamespace(config=_Cfg())
    d = compile_cache.enable(fake_jax)
    root = os.path.abspath(os.path.join(
        os.path.dirname(compile_cache.__file__), os.pardir, ".jax_cache"))
    assert d == os.path.join(root, compile_cache.host_fingerprint())
    assert fake_jax.config.updates["jax_compilation_cache_dir"] == d
    assert compile_cache.enable(types.SimpleNamespace(config=_Cfg())) == d
    # a different device kind gets its own subtree
    fake2 = types.SimpleNamespace(config=_Cfg())
    d2 = compile_cache.enable(fake2, device_kind="TPU v5e")
    assert d2 != d and d2.startswith(root)


def test_compile_cache_dir_placed_from_outside(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: used as it is (no fingerprint
    subdirectory), and no directory is set in code — jax reads it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    fake_jax = types.SimpleNamespace(config=_Cfg())
    assert compile_cache.enable(fake_jax) == str(tmp_path)
    assert compile_cache.cache_dir("TPU v5 lite") == str(tmp_path)
    assert "jax_compilation_cache_dir" not in fake_jax.config.updates
    assert fake_jax.config.updates[
        "jax_persistent_cache_min_compile_time_secs"] == 0


def test_compile_cache_enable_logs_failures(monkeypatch, caplog):
    class _Broken:
        def update(self, k, v):
            raise RuntimeError("no such option")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    with caplog.at_level("ERROR", logger="selkies_tpu.compile_cache"):
        compile_cache.enable(types.SimpleNamespace(config=_Broken()))
    assert "not enabled" in caplog.text