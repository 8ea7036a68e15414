"""The H.264 session's stream buffer: how ``h264_buffer_caps`` sizes it,
and what the session records of it while tracing (``stream.bytes`` and
``stream.cap`` per frame, ``encode.regrow`` around a regrowth), the
counters ``benchmark/metrics/stream_fill_pct.py`` reads."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from selkies_tpu.engine import h264_encoder  # noqa: E402
from selkies_tpu.engine.h264_encoder import (H264EncoderSession,  # noqa: E402
                                             h264_buffer_caps,
                                             plan_h264_grid)
from selkies_tpu.engine.types import CaptureSettings  # noqa: E402
from selkies_tpu.trace import tracer  # noqa: E402

W = H = 64
BASE = dict(capture_width=W, capture_height=H, stripe_height=32,
            output_mode="h264", video_crf=28, use_paint_over=False,
            h264_motion_vrange=0, h264_motion_hrange=0)


#: the largest frame and MB row of ``h264-1080p60-444.motion`` at CRF 25,
#: IDRs and motion P frames, warm-up included (h264_buffer_caps'
#: docstring)
PEAK_444_FRAME_BYTES = 1245417
PEAK_444_ROW_WORDS = 5298


def _grid_1080p():
    return plan_h264_grid(CaptureSettings(capture_width=1920,
                                          capture_height=1080,
                                          single_stream=True))


def test_444_buffer_holds_the_measured_peaks_at_first_build():
    e_cap, w_cap, out_cap = h264_buffer_caps(_grid_1080p(), fullcolor=True)
    assert out_cap >= PEAK_444_FRAME_BYTES
    assert w_cap >= PEAK_444_ROW_WORDS
    # within the two doublings a session grew to before it was sized
    assert w_cap <= 4 * 34560 and out_cap <= 4 * 522240
    assert e_cap == 209051


def test_420_buffer_is_unchanged():
    assert h264_buffer_caps(_grid_1080p()) == (105491, 23040, 348160)


def _frames(n=3, seed=5):
    rng = np.random.default_rng(seed)
    f0 = rng.integers(0, 256, (H, W, 3), dtype=np.uint8)
    out = [f0]
    for t in range(1, n):
        f = out[-1].copy()
        f[8 * t:8 * t + 8, :] = rng.integers(0, 256, (8, W, 3),
                                             dtype=np.uint8)
        out.append(f)
    return [jnp.asarray(f) for f in out]


def _traced(sess, frames, lag=0):
    """Encode each frame and finalize it ``lag`` frames later (a
    pipeline of depth ``lag + 1``); -> (chunks per frame, the frames'
    timelines as dicts)."""
    tracer.clear()
    tracer.enable(capacity=32)
    try:
        pending, chunks, tls = [], [], []
        for t, f in enumerate(frames):
            tl = tracer.frame_begin(sess.settings.display_id)
            out = sess.encode(f, force=(t == 0))
            tracer.bind(tl, out["frame_id"])
            pending.append(out)
            tls.append(tl)
            if len(pending) > lag:
                chunks.append(sess.finalize(pending.pop(0)))
        chunks += [sess.finalize(o) for o in pending]
    finally:
        tracer.disable()
        tracer.clear()
    return chunks, [tl.to_dict() for tl in tls]


@pytest.mark.parametrize("fullcolor", [False, True], ids=["420", "444"])
def test_each_frame_counts_its_bytes_and_the_buffer_once(fullcolor):
    sess = H264EncoderSession(CaptureSettings(**BASE, fullcolor=fullcolor))
    chunks, tls = _traced(sess, _frames())
    for got, d in zip(chunks, tls):
        coded = sum(len(c.payload) for c in got)
        assert d["counts"]["stream.cap"] == sess._out_cap
        # the payload adds headers and NAL framing to the coded rows
        assert 0 < d["counts"]["stream.bytes"] < coded
        assert not [s for s in d["spans"] if s["name"] == "encode.regrow"]


def test_an_overflow_is_one_regrow_span_and_later_frames_count_their_cap(
        monkeypatch):
    """The first frame overflows a buffer sized below it: its timeline
    holds one ``encode.regrow`` and no stream counters. The next frame,
    encoded before the regrowth (a depth-2 pipeline), counts the
    capacity it was encoded with; the forced refresh after it counts the
    doubled one."""
    frames = _frames(3)
    plain = H264EncoderSession(CaptureSettings(**BASE))
    _, tls = _traced(plain, frames[:1])
    idr = tls[0]["counts"]["stream.bytes"]
    e_cap, w_cap, _ = h264_buffer_caps(plain.grid)
    small = idr * 3 // 4
    monkeypatch.setattr(h264_encoder, "h264_buffer_caps",
                        lambda g, fullcolor=False: (e_cap, w_cap, small))
    sess = H264EncoderSession(CaptureSettings(**BASE))
    chunks, tls = _traced(sess, frames, lag=1)
    assert chunks[0] == []
    regrow, = [s for s in tls[0]["spans"] if s["name"] == "encode.regrow"]
    assert regrow["dur_ns"] >= 0
    assert "stream.bytes" not in tls[0]["counts"]
    assert tls[1]["counts"]["stream.cap"] == small
    assert tls[2]["counts"]["stream.cap"] == 2 * small == sess._out_cap
    assert chunks[2] and chunks[2][0].is_idr
    for d in tls[1:]:
        assert d["counts"]["stream.bytes"] <= d["counts"]["stream.cap"]
        assert not [s for s in d["spans"] if s["name"] == "encode.regrow"]
