"""Deep-pipeline invariants (ROADMAP 2 / ISSUE 10).

The depth-N rework must never be OBSERVABLE in the byte stream: in-order
per-seat delivery, byte-identical output vs serial mode for both codecs
(donation must not alias a slot still being read back), bounded depth
under backpressure, and a mid-pipeline finalize death that drains — not
wedges — the ring. All on CPU at tiny geometry.
"""

import threading
import time

import numpy as np
import pytest

from selkies_tpu.engine import CaptureSettings, ScreenCapture
from selkies_tpu.engine.pipeline import PipelineError, PipelineRing
from selkies_tpu.resilience import faults as _faults

SMALL = dict(capture_width=64, capture_height=64, stripe_height=32,
             target_fps=240.0, jpeg_quality=75)


# ------------------------------------------------------------------ ring unit

def test_ring_delivers_in_submission_order():
    got = []
    ring = PipelineRing(lambda out: got.append(out["n"]), depth=3)
    for n in range(24):
        ring.submit({"n": n})
    ring.close(drain=True)
    assert got == list(range(24))


def test_ring_slot_indices_cycle_the_depth():
    slots = []
    ring = PipelineRing(lambda out: slots.append(out["slot"]), depth=3)
    for n in range(9):
        ring.submit({"n": n})
    ring.close(drain=True)
    assert slots == [0, 1, 2] * 3


def test_ring_submit_blocks_at_depth_and_resumes():
    """The ring IS the engine's backpressure: with `depth` frames in
    flight, submit() parks the producer until a slot drains."""
    gate = threading.Event()
    done = []

    def fin(out):
        gate.wait(5.0)
        done.append(out["n"])

    ring = PipelineRing(fin, depth=2)
    ring.submit({"n": 0})
    ring.submit({"n": 1})       # depth reached; finalizer holds slot 0
    blocked = threading.Event()
    submitted = threading.Event()

    def third():
        blocked.set()
        ring.submit({"n": 2})
        submitted.set()

    t = threading.Thread(target=third, daemon=True)
    t.start()
    assert blocked.wait(2.0)
    assert not submitted.wait(0.3), "submit must block at depth"
    gate.set()                   # finalizer drains
    assert submitted.wait(5.0)
    ring.close(drain=True)
    assert done == [0, 1, 2]


def test_ring_set_depth_shrinks_live():
    gate = threading.Event()
    ring = PipelineRing(lambda out: gate.wait(5.0), depth=4)
    ring.submit({})
    ring.submit({})
    ring.set_depth(1)
    t0 = time.monotonic()
    ok = []

    def try_submit():
        try:
            ring.submit({})
            ok.append(time.monotonic() - t0)
        except PipelineError:
            pass

    t = threading.Thread(target=try_submit, daemon=True)
    t.start()
    time.sleep(0.2)
    assert not ok, "shrunk depth must gate new submissions"
    gate.set()
    t.join(5.0)
    ring.close(drain=True)
    assert ok, "gate must lift once in-flight drains below the new depth"


def test_ring_finalize_death_drains_never_wedges():
    """A mid-pipeline finalize death parks the ring failed: queued slots
    are DISCARDED, blocked producers wake, and the next submit raises on
    the producer thread (-> capture_death -> supervised restart)."""
    def fin(out):
        if out["n"] == 1:
            raise RuntimeError("injected readback death")

    ring = PipelineRing(fin, depth=2)
    ring.submit({"n": 0})
    ring.submit({"n": 1})
    with pytest.raises(PipelineError) as ei:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            ring.submit({"n": 99})
            time.sleep(0.01)
    assert "injected readback death" in str(ei.value)
    assert ring.failed
    ring.close(drain=False)      # returns promptly: nothing wedged


# ------------------------------------------------- byte-identity vs serial

def _frames(src_cls, n, w, h):
    """Animated frames with a static tail — exercises damage gating and
    the donation path across slot reuse."""
    src = src_cls(w, h)
    return [src.get_frame(t if t < n - 3 else n - 4) for t in range(n)]


def _run_serial(sess, frames, force_first=True):
    got = []
    for t, f in enumerate(frames):
        out = sess.encode(f, force=(force_first and t == 0))
        out["slot"] = 0
        got.extend(sess.finalize(out, force_all=(force_first and t == 0)))
    return [(c.frame_id, c.stripe_y, c.payload) for c in got]


def _run_pipelined(sess, frames, depth, stream, force_first=True):
    got = []

    def fin(out):
        force_all = out.pop("force_all")
        if stream:
            got.extend(sess.finalize_stream(out, force_all=force_all))
        else:
            got.extend(sess.finalize(out, force_all=force_all))

    ring = PipelineRing(fin, depth=depth)
    for t, f in enumerate(frames):
        out = sess.encode(f, force=(force_first and t == 0))
        out["force_all"] = force_first and t == 0
        ring.submit(out)
    ring.close(drain=True)
    return [(c.frame_id, c.stripe_y, c.payload) for c in got]


@pytest.mark.parametrize("stream", [False, True],
                         ids=["batch", "stripe-streaming"])
def test_jpeg_pipelined_byte_identical_to_serial(stream):
    from selkies_tpu.engine.encoder import JpegEncoderSession
    from selkies_tpu.engine.sources import SyntheticSource
    s1, s2 = CaptureSettings(**SMALL), CaptureSettings(**SMALL)
    frames = _frames(SyntheticSource, 10, s1.capture_width,
                     s1.capture_height)
    serial = _run_serial(JpegEncoderSession(s1), frames)
    piped = _run_pipelined(JpegEncoderSession(s2), frames, depth=3,
                           stream=stream)
    assert serial == piped


@pytest.mark.parametrize("stream", [False, True],
                         ids=["batch", "stripe-streaming"])
def test_h264_pipelined_byte_identical_to_serial(stream):
    """Depth-3 in flight with donated prev/age/refs: donation must not
    alias a slot still being read back — any aliasing shows up as a
    byte diff in the P-frame residuals here."""
    from selkies_tpu.engine.h264_encoder import H264EncoderSession
    from selkies_tpu.engine.sources import SyntheticSource
    cfg = dict(SMALL, output_mode="h264", video_crf=28)
    s1, s2 = CaptureSettings(**cfg), CaptureSettings(**cfg)
    frames = _frames(SyntheticSource, 10, s1.capture_width,
                     s1.capture_height)
    serial = _run_serial(H264EncoderSession(s1), frames)
    piped = _run_pipelined(H264EncoderSession(s2), frames, depth=3,
                           stream=stream)
    assert serial == piped


def _session(codec, **over):
    from selkies_tpu.engine.encoder import JpegEncoderSession
    from selkies_tpu.engine.h264_encoder import H264EncoderSession
    if codec == "jpeg":
        return JpegEncoderSession(CaptureSettings(**dict(SMALL, **over)))
    return H264EncoderSession(CaptureSettings(**dict(
        SMALL, output_mode="h264", video_crf=28, **over)))


def _run_traced(sess, frames, stream):
    """Depth 2 with the frame timelines a capture loop keeps: -> (chunks,
    the dispatched ``out`` dicts, their timelines)."""
    from selkies_tpu.trace import tracer
    disp = sess.settings.display_id
    got, outs, tls = [], [], []

    def fin(out):
        force_all = out.pop("force_all")
        if stream:
            got.extend(sess.finalize_stream(out, force_all=force_all))
        else:
            got.extend(sess.finalize(out, force_all=force_all))
        tracer.frame_end(disp, out["frame_id"])

    ring = PipelineRing(fin, depth=2)
    for t, f in enumerate(frames):
        tl = tracer.frame_begin(disp)
        out = sess.encode(f, force=(t == 0))
        tracer.bind(tl, out["frame_id"])
        out["force_all"] = t == 0
        outs.append(out)
        tls.append(tl)
        ring.submit(out, tl=tl)
    ring.close(drain=True)
    return [(c.frame_id, c.stripe_y, c.payload) for c in got], outs, tls


def _inside(inner, outer) -> bool:
    return outer["t0_ns"] <= inner["t0_ns"] and \
        inner["t0_ns"] + inner["dur_ns"] <= outer["t0_ns"] + outer["dur_ns"]


@pytest.mark.parametrize("stream", [False, True],
                         ids=["batch", "stripe-streaming"])
@pytest.mark.parametrize("codec", ["jpeg", "h264"])
def test_traced_frames_hold_their_waits_nested(codec, stream, monkeypatch):
    """Every dispatched frame's timeline holds its waits on ``<lane>.wait``
    inside the span they belong to; ``encode.readback`` still starts at
    the slot's submit; the bytes are those of an untraced run."""
    from selkies_tpu.engine import readback
    from selkies_tpu.engine.sources import SyntheticSource
    from selkies_tpu.trace import tracer
    # the device path's bucketed slice, as on a chip (the CPU backend's
    # buffers are host memory, fetched without one)
    monkeypatch.setattr(readback, "_on_host", lambda arr: False)
    frames = _frames(SyntheticSource, 8, 64, 64)
    plain = _run_pipelined(_session(codec), frames, depth=2, stream=stream)
    tracer.clear()
    tracer.enable(capacity=64)
    try:
        traced, outs, tls = _run_traced(_session(codec), frames, stream)
    finally:
        tracer.disable()
    try:
        assert traced == plain
        me = threading.current_thread().name
        for out, tl in zip(outs, tls):
            d = tl.to_dict()
            spans = d["spans"]
            lane = f"slot{out['slot']}"

            def named(name):
                return [s for s in spans if s["name"] == name]

            env, = [s for s in named("encode.readback")
                    if s["t0_ns"] == out["submitted_ns"]]
            assert env["lane"] == lane
            admit, = named("pipeline.admit")
            queue, = named("pipeline.queue")
            assert admit["lane"] == me + ".wait"
            assert queue["lane"] == lane + ".wait"
            assert admit["t0_ns"] == out["submitted_ns"]
            assert admit["t0_ns"] + admit["dur_ns"] == queue["t0_ns"]
            for w in (admit, queue):
                assert _inside(w, env)
            if out.get("idle"):
                assert not named("readback.device_wait")
                continue
            sync, = named("readback.device_wait")
            assert sync["lane"] == lane + ".wait" and _inside(sync, env)
            copies = named("readback.copy")
            waits = named("readback.slice_wait")
            if not any(c[0] == out["frame_id"] for c in traced):
                assert not copies
                assert "readback.bytes" not in d["counts"]
                assert "readback.fetches" not in d["counts"]
                continue           # nothing damaged: nothing fetched
            # one fetch per frame: one copy, its slice wait inside it
            copy, = copies
            wait, = waits
            assert d["counts"]["readback.bytes"] > 0
            assert d["counts"]["readback.fetches"] == 1
            assert copy["lane"] == lane and "cpu_ns" in copy
            assert _inside(copy, env) and _inside(wait, copy)
            assert wait["lane"] == lane + ".wait"
            if codec == "h264" and not out.get("intra"):
                probe, = named("encode.probe_sync")
                dispatch, = named("encode.dispatch")
                assert probe["lane"] == me + ".wait"
                assert _inside(probe, dispatch)
    finally:
        tracer.clear()


#: delivered-stripe masks over a 4-stripe frame
SEND_MASKS = {"all": [1, 1, 1, 1], "first-and-last": [1, 0, 0, 1],
              "one-middle": [0, 0, 1, 0], "none": [0, 0, 0, 0]}


def _per_stripe_payloads(sess, out):
    """What a fetch per delivered stripe gives: each stripe cut from a
    host copy of the whole stream buffer, wrapped by the session."""
    full = np.asarray(out["data"])
    lens = np.asarray(out["lens"])
    send = np.asarray(out["send"])
    want = []
    for i in np.nonzero(send)[0]:
        if "intra" in out:                  # H.264: per-row slices
            starts = sess._row_starts(out, lens)
            rps = sess.grid.rows_per_stripe
            rows = [bytes(full[starts[r]:starts[r] + lens[r]])
                    for r in range(i * rps, (i + 1) * rps)]
            chunk = sess._chunk(out, int(i), rows, out["intra"])
        else:
            start = int(np.sum(lens[:i]))
            chunk = sess._chunk(out, int(i), full[start:start + lens[i]],
                                bool(np.asarray(out["is_paint"])[i]))
        want.append((chunk.stripe_y, chunk.payload))
    return want


@pytest.mark.parametrize("mask", list(SEND_MASKS))
@pytest.mark.parametrize("codec", ["jpeg", "h264"])
def test_one_device_fetch_per_frame_over_the_delivered_stripes(
        codec, mask, monkeypatch):
    """Whatever stripes a frame delivers, ``finalize_stream`` and
    ``finalize`` give the bytes a fetch per stripe gave, from ONE device
    fetch per frame — none for an idle frame."""
    from selkies_tpu.engine import readback
    from selkies_tpu.engine.sources import SyntheticSource
    monkeypatch.setattr(readback, "_on_host", lambda arr: False)
    fetches = []
    for name in ("fetch_stream_bytes", "fetch_stripe_bytes"):
        def counted(*a, _real=getattr(readback, name), **k):
            fetches.append(a[1:])
            return _real(*a, **k)
        monkeypatch.setattr(readback, name, counted)
    sess = _session(codec, capture_height=128)
    assert sess.grid.n_stripes == len(SEND_MASKS[mask])
    out = sess.encode(SyntheticSource(64, 128).get_frame(0), force=True)
    out["send"] = np.array(SEND_MASKS[mask], bool)
    want = _per_stripe_payloads(sess, out)
    streamed = [(c.stripe_y, c.payload) for c in sess.finalize_stream(out)]
    assert len(fetches) == (0 if mask == "none" else 1)
    batch = [(c.stripe_y, c.payload) for c in sess.finalize(out)]
    assert len(fetches) == (0 if mask == "none" else 2)
    assert streamed == batch == want
    assert len(want) == sum(SEND_MASKS[mask])


@pytest.mark.parametrize("codec", ["jpeg", "h264"])
def test_tracer_off_reads_no_thread_time_and_syncs_nothing(codec,
                                                           monkeypatch):
    """With the tracer off the session and the ring never read thread CPU
    time nor wait on a device array beyond what the transfer syncs."""
    import jax

    from selkies_tpu.engine import readback
    from selkies_tpu.engine.sources import SyntheticSource
    from selkies_tpu.trace import core, tracer

    def forbidden(*a, **k):
        raise AssertionError("called with the tracer off")

    assert not tracer.enabled
    monkeypatch.setattr(readback, "_on_host", lambda arr: False)
    monkeypatch.setattr(core, "_cpu_ns", forbidden)
    monkeypatch.setattr(type(jax.numpy.zeros(1)), "block_until_ready",
                        forbidden)
    frames = _frames(SyntheticSource, 6, 64, 64)
    for stream in (False, True):
        chunks, _, tls = _run_traced(_session(codec), frames, stream)
        assert chunks and tls == [None] * len(frames)


def test_sessions_tolerate_caller_reusing_frame_arrays():
    """Donation discipline: the step donates only session-owned state,
    never the caller's frame — a source handing back the SAME device
    array every tick (static X11 grab) must keep working."""
    from selkies_tpu.engine.encoder import JpegEncoderSession
    sess = JpegEncoderSession(CaptureSettings(**SMALL))
    import jax.numpy as jnp
    frame = jnp.zeros((sess.grid.height, sess.grid.width, 3), jnp.uint8)
    for _ in range(4):
        sess.finalize(sess.encode(frame))      # same array object each time
    assert int(sess.frame_id) == 4


# --------------------------------------------------------- capture loop

def _collect_chunks(depth, n_want=12, **over):
    cfg = dict(SMALL, pipeline_depth=depth, **over)
    got = []
    cap = ScreenCapture("synthetic")
    cap.start_capture(got.append, CaptureSettings(**cfg))
    deadline = time.monotonic() + 30
    while len(got) < n_want and time.monotonic() < deadline:
        time.sleep(0.01)
    cap.stop_capture()
    return got, cap


@pytest.mark.parametrize("depth", [2, 3])
def test_capture_loop_pipelined_delivery_in_order(depth):
    got, _ = _collect_chunks(depth, n_want=16)
    assert len(got) >= 16
    fids = [c.frame_id for c in got]
    # frame ids non-decreasing: pipelining must never reorder delivery
    assert fids == sorted(fids)


def test_capture_depth_clamp_and_effective_depth():
    cap = ScreenCapture("synthetic")
    cap._settings = CaptureSettings(**dict(SMALL, pipeline_depth=3))
    assert cap.effective_pipeline_depth() == 3
    cap.set_pipeline_clamp(1)        # relay backpressure window
    assert cap.effective_pipeline_depth() == 1
    cap.set_pipeline_clamp(None)
    assert cap.effective_pipeline_depth() == 3
    cap._settings.pipeline_depth = 1
    cap.set_pipeline_clamp(4)        # clamp never RAISES the depth
    assert cap.effective_pipeline_depth() == 1


def test_capture_loop_depth_clamp_under_injected_backpressure():
    """Clamping to 1 mid-run drops the loop to serial (ring closed,
    drained) without losing or reordering frames."""
    got, cap = [], ScreenCapture("synthetic")
    cap.start_capture(got.append,
                      CaptureSettings(**dict(SMALL, pipeline_depth=3)))
    deadline = time.monotonic() + 30
    while len(got) < 6 and time.monotonic() < deadline:
        time.sleep(0.01)
    cap.set_pipeline_clamp(1)        # what a paused client does
    n_at_clamp = len(got)
    while len(got) < n_at_clamp + 6 and time.monotonic() < deadline:
        time.sleep(0.01)
    cap.stop_capture()
    fids = [c.frame_id for c in got]
    assert fids == sorted(fids)
    assert len(got) >= n_at_clamp + 6, "loop must keep delivering at depth 1"


def test_readback_fetch_death_recovers_via_supervised_restart():
    """Mid-pipeline readback death (fault readback.fetch:error): the
    ring drains, the loop dies through on_death, and a restart delivers
    fresh frames — in-flight slots never wedge the stop/restart path."""
    died = threading.Event()
    got = []
    cap = ScreenCapture("synthetic")
    cap.on_death = lambda exc: died.set()
    _faults.registry.disarm()
    _faults.registry.arm("readback.fetch:error:after=6,count=1")
    try:
        cap.start_capture(got.append,
                          CaptureSettings(**dict(SMALL, pipeline_depth=2)))
        assert died.wait(30), "injected readback death must reach on_death"
        cap.restart()                # what the supervisor does
        n0 = len(got)
        deadline = time.monotonic() + 30
        while len(got) < n0 + 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(got) >= n0 + 4, "restarted loop must deliver again"
    finally:
        _faults.registry.disarm()
        cap.stop_capture()


# ------------------------------------------------------------ relay reorder

def test_relay_stripe_reorder_fault_swaps_queue_and_asks_idr():
    from selkies_tpu import protocol as P
    from selkies_tpu.server.relay import VideoRelay
    idr = []

    async def send(_b):
        pass

    _faults.registry.disarm()
    # the injection site only CONSUMES a clause when the queue can
    # actually be reordered (>= 2 queued), so hits count from the
    # second offer on
    _faults.registry.arm("relay.stripe:reorder:after=1,count=1")
    try:
        r = VideoRelay(send, request_idr=lambda: idr.append(1),
                       display="d0")
        frames = [P.pack_h264_stripe(fid, 0, 64, 32, b"x" * 8, idr=True)
                  for fid in range(3)]
        r.offer(frames[0])       # q=1: cannot reorder, clause untouched
        assert _faults.registry.remaining() == 1
        r.offer(frames[1])       # hit 1: skipped by after=1
        r.offer(frames[2])       # hit 2: fires
        q = list(r._q)
        assert q == [frames[0], frames[2], frames[1]]   # newest two swapped
        assert idr, "an out-of-order h264 stripe must request a resync"
    finally:
        _faults.registry.disarm()


# ----------------------------------------------------- occupancy window view

def test_window_overlap_zero_for_serial_frames():
    from selkies_tpu.trace.summary import window_overlap_fraction
    MS = 1_000_000
    dicts = [
        {"t0_ns": 0, "t1_ns": 10 * MS, "spans": [
            {"name": "encode.dispatch", "lane": "cap", "t0_ns": 0,
             "dur_ns": 10 * MS}]},
        {"t0_ns": 10 * MS, "t1_ns": 20 * MS, "spans": [
            {"name": "encode.dispatch", "lane": "cap", "t0_ns": 10 * MS,
             "dur_ns": 10 * MS}]},
    ]
    assert window_overlap_fraction(dicts) == 0.0


def test_window_overlap_sees_cross_frame_concurrency():
    """Frame N+1's dispatch under frame N's readback: invisible to the
    per-frame view (stages of ONE frame are still sequential), captured
    by the window view — the deep-pipeline acceptance number."""
    from selkies_tpu.trace.summary import (occupancy_report,
                                           window_overlap_fraction)
    MS = 1_000_000
    dicts = [
        {"t0_ns": 0, "t1_ns": 20 * MS, "spans": [
            {"name": "encode.dispatch", "lane": "cap", "t0_ns": 0,
             "dur_ns": 10 * MS},
            {"name": "encode.readback", "lane": "slot0", "t0_ns": 10 * MS,
             "dur_ns": 10 * MS}]},
        {"t0_ns": 10 * MS, "t1_ns": 30 * MS, "spans": [
            {"name": "encode.dispatch", "lane": "cap", "t0_ns": 10 * MS,
             "dur_ns": 10 * MS},
            {"name": "encode.readback", "lane": "slot1", "t0_ns": 20 * MS,
             "dur_ns": 10 * MS}]},
    ]
    # union [0,30] = 30ms of 40ms span time -> 25% overlap
    assert window_overlap_fraction(dicts) == pytest.approx(0.25)
    rep = occupancy_report(dicts)
    assert rep["overlap_fraction"] == pytest.approx(0.25)
    # per-frame identity still exact: shares + bubble account for e2e
    assert rep["bubble_share"] == 0.0


def test_window_overlap_ignores_nested_spans_and_waits():
    """A copy nested in its readback on the same lane, and the waits on
    their own lanes, are no cross-frame overlap: serial frames stay 0."""
    from selkies_tpu.trace.summary import window_overlap_fraction
    MS = 1_000_000

    def frame(t):
        return {"t0_ns": t * MS, "t1_ns": (t + 10) * MS, "spans": [
            {"name": "encode.dispatch", "lane": "cap", "t0_ns": t * MS,
             "dur_ns": 4 * MS},
            {"name": "encode.probe_sync", "lane": "cap.wait",
             "t0_ns": t * MS, "dur_ns": 3 * MS},
            {"name": "encode.readback", "lane": "slot0",
             "t0_ns": (t + 4) * MS, "dur_ns": 6 * MS},
            {"name": "readback.copy", "lane": "slot0",
             "t0_ns": (t + 8) * MS, "dur_ns": 2 * MS},
            {"name": "pipeline.queue", "lane": "slot0.wait",
             "t0_ns": (t + 4) * MS, "dur_ns": 2 * MS}]}

    assert window_overlap_fraction([frame(0), frame(10)]) == 0.0


# ----------------------------------------------------------- ladder rung 0

def test_ladder_default_steps_open_with_pipeline_rung():
    from selkies_tpu.resilience.ladder import DEFAULT_STEPS
    assert DEFAULT_STEPS[0] == "pipeline"
