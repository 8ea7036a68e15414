"""The readers of the program's wait spans, CPU times and counters
(``harness/waits.py``, ``metrics/<quantity>.py``): on a hand-built
timeline, numbers checked by hand; on a timeline recorded from a whole
run of a small cell on the CPU, where the readers of the spans that were
there before (``capture_ms``, ``dispatch_ms``, ``readback_wait_ms``,
``send_ms``) read the same with and without the wait lanes and the
nested copy span."""

import asyncio
import copy
import json
import types
from pathlib import Path

import jax
import pytest

from harness import layers
from tests import tiny

BENCH = Path(__file__).resolve().parent.parent
MS = 1_000_000
SEED = 2**33 + 5


def _span(name, lane, t0_ms, dur_ms, cpu_ms=None):
    s = {"name": name, "lane": lane, "t0_ns": int(t0_ms * MS),
         "dur_ns": int(dur_ms * MS)}
    if cpu_ms is not None:
        s["cpu_ns"] = int(cpu_ms * MS)
    return s


def _ctx(timelines, w0=0.0, w1=10.0):
    run = types.SimpleNamespace(w0=w0, w1=w1, spans=timelines, trace=None)
    return layers.Context(run=run, frames={}, trace=None, peaks={})


def _read(name, ctx):
    return layers.load_module(layers.reader_for(name)).read(ctx)


def _frame(t0_ms, spans, counts=None):
    return {"display_id": ":0", "frame_id": int(t0_ms), "t0_ns":
            int(t0_ms * MS), "t1_ns": int((t0_ms + 500) * MS),
            "spans": spans, "counts": counts or {}}


def hand_built():
    """Two H.264 frames of a depth-2 pipeline, times in ms from frame 1's
    start: capture thread ``cap``, slot lanes, the loop and the relay."""
    f1 = _frame(100, [
        _span("capture", "cap", 100, 10, cpu_ms=8),
        _span("encode.dispatch", "cap", 110, 50, cpu_ms=5),
        _span("encode.probe_sync", "cap.wait", 112, 40),
        _span("pipeline.admit", "cap.wait", 160, 30),
        _span("pipeline.queue", "slot0.wait", 190, 20),
        _span("encode.readback", "slot0", 160, 110),
        _span("readback.device_wait", "slot0.wait", 212, 40),
        _span("encode.readback", "slot0", 255, 16),
        _span("readback.copy", "slot0", 255, 15, cpu_ms=2),
        _span("readback.slice_wait", "slot0.wait", 256, 6),
        _span("packetize", "slot0", 271, 20, cpu_ms=12),
        _span("fanout.hop", "loop.wait", 280, 3),
        _span("fanout", "loop", 283, 1, cpu_ms=1),
        _span("fanout.hop", "loop.wait", 291, 5),
        _span("fanout", "loop", 296, 1, cpu_ms=1),
        _span("relay.queue", "ws.wait", 284, 2),
        _span("ws.send", "ws", 286, 4),
        _span("relay.queue", "ws.wait", 297, 7),
        _span("ws.send", "ws", 304, 3),
    ], {"readback.bytes": 524288})
    f2 = _frame(300, [
        _span("capture", "cap", 300, 10, cpu_ms=9),
        _span("encode.dispatch", "cap", 310, 30, cpu_ms=4),
        _span("encode.probe_sync", "cap.wait", 311, 20),
        _span("pipeline.admit", "cap.wait", 340, 10),
        _span("pipeline.queue", "slot1.wait", 350, 30),
        _span("encode.readback", "slot1", 340, 70),
        _span("readback.device_wait", "slot1.wait", 381, 20),
        _span("readback.copy", "slot1", 402, 8, cpu_ms=1),
        _span("readback.slice_wait", "slot1.wait", 403, 2),
        _span("packetize", "slot1", 411, 5, cpu_ms=5),
        _span("fanout.hop", "loop.wait", 420, 1),
        _span("fanout", "loop", 421, 1, cpu_ms=1),
        _span("relay.queue", "ws.wait", 422, 9),
        _span("ws.send", "ws", 431, 2),
    ], {"readback.bytes": 65536})
    # begun after the window: read by no reader
    late = _frame(20_000, [_span("encode.probe_sync", "cap.wait", 20_001,
                                 999)], {"readback.bytes": 4096})
    return [f1, f2, late]


@pytest.mark.parametrize("name,want", [
    ("probe_sync_ms.motion", (40 + 20) / 2),
    ("admit_wait_ms.motion", (30 + 10) / 2),
    ("queue_wait_ms", (20 + 30) / 2),
    # device_wait + slice_wait: 40 + 6 and 20 + 2
    ("device_wait_ms", (46 + 22) / 2),
    # copy less its slice wait: 15 - 6 and 8 - 2
    ("copy_ms", (9 + 6) / 2),
    # the last stripe's hop and relay queue: 5 + 7 and 1 + 9
    ("delivery_wait_ms", (12 + 10) / 2),
    # frame 1: capture 10-8, dispatch 50-40-5, copy 15-6-2, packetize
    # 20-12, fanout 0 and 0 -> 2+5+7+8 = 22; frame 2: 1+6+5+0+0 = 12
    ("offcpu_work_ms.motion", (22 + 12) / 2),
    ("readback_kb", (524.288 + 65.536) / 2),
])
def test_each_new_reader_on_a_hand_built_timeline(name, want):
    assert _read(name, _ctx(hand_built())) == pytest.approx(want)


def test_new_readers_find_nothing_in_a_program_without_them():
    """The parent program records none of these spans and counters: each
    reader returns None and the harness leaves the metric out."""
    old = hand_built()
    for tl in old:
        tl["spans"] = [{k: v for k, v in s.items() if k != "cpu_ns"}
                       for s in tl["spans"]
                       if not s["lane"].endswith(".wait")
                       and s["name"] != "readback.copy"]
        del tl["counts"]
    ctx = _ctx(old)
    for m in _new_metrics():
        assert _read(m, ctx) is None, m


def _new_metrics():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    return names[names.index("probe_sync_ms.motion"):]


EARLIER = ("capture_ms", "dispatch_ms.motion", "readback_wait_ms", "send_ms")


def _without_waits(timelines):
    out = copy.deepcopy(timelines)
    for tl in out:
        tl["spans"] = [s for s in tl["spans"]
                       if not s["lane"].endswith(".wait")
                       and s["name"] != "readback.copy"]
    return out


def test_earlier_readers_ignore_the_wait_lanes_by_hand():
    tls = hand_built()
    for m in EARLIER:
        assert _read(m, _ctx(tls)) == _read(m, _ctx(_without_waits(tls))), m
    # the dispatch's self time keeps its probe sync: a wait on another
    # lane is no child
    assert _read("dispatch_ms.motion", _ctx(tls)) == (50 + 30) / 2


@pytest.fixture(scope="module")
def recorded():
    """A small H.264 typing cell served whole on the CPU with the
    program's tracer on over the window (the profiler, which needs the
    chip's device lanes, left out)."""
    from harness import runner
    from harness.desktop import Desktop

    async def window(r, trace_dir):
        await asyncio.sleep(r.seconds)

    cell = tiny.cell("h264-1080p60.typing", "h264-1080p60", "typing")
    saved = runner._traced_window
    runner._traced_window = window
    try:
        r = runner.Run(cell=cell, config=tiny.config("h264-1080p60"),
                       seed=SEED, seconds=3.0,
                       desktop=Desktop(tiny.scene("typing"), tiny.WIDTH,
                                       tiny.HEIGHT, SEED),
                       devices=jax.devices())
        asyncio.run(runner.serve(r, True, trace_dir=None))
    finally:
        runner._traced_window = saved
    return r


def test_earlier_readers_ignore_the_wait_lanes_on_a_recorded_run(recorded):
    ctx = _ctx(recorded.spans, recorded.w0, recorded.w1)
    lanes = {s["lane"] for tl in recorded.spans for s in tl["spans"]}
    assert any(la.endswith(".wait") for la in lanes), lanes
    plain = _ctx(_without_waits(recorded.spans), recorded.w0, recorded.w1)
    for m in EARLIER:
        v = _read(m, ctx)
        assert v is not None and v == _read(m, plain), m


def test_new_readers_report_on_a_recorded_run(recorded):
    ctx = _ctx(recorded.spans, recorded.w0, recorded.w1)
    for m in _new_metrics():
        v = _read(m, ctx)
        # the CPU backend computes on the waiting thread: its waits use
        # CPU, and the off-CPU reading can fall below 0 here
        assert v is not None and (v >= 0 or m.startswith("offcpu")), m
