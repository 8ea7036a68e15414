"""The check's reference decoders at each chroma sampling, and the 4:2:0
numbers pinned.

The H.264 decoder (``reference/avdec.c``, libavcodec) is shown to give
back exactly what an encoder reconstructed, for a High 4:4:4 Predictive
I + P stream and for a 4:2:0 one, and to refuse a picture of the other
sampling. The streams come from the program's golden numpy encoders
(``selkies_tpu.codecs.h264``): they only make the tests' input, and the
decoder stays independent of them.

``compare()`` and the H.264 byte count at 4:2:0 are pinned to the values
the check gave before it learned 4:4:4, on a fixed tiny stream of each
codec: a 4:2:0 configuration reads exactly as it did.
"""

import io

import numpy as np
import pytest

from harness import check
from harness.desktop import Desktop
from harness.layers import load_module
from harness.source import half_chroma
from harness.stream import Frame, Stripe
from reference import decoders
from tests import tiny

BENCH = tiny.BENCH
W, H = tiny.WIDTH, tiny.HEIGHT
SEED = 2**33 + 5
FRAMES = 6                      # fid 0 is the IDR; the window is 2..5


def rgb_frames(n):
    rng = np.random.default_rng(SEED)
    base = rng.integers(0, 256, (H // 8, W // 8, 3), np.uint8)
    frames = []
    for k in range(n):
        f = np.repeat(np.repeat(np.roll(base, k, axis=1), 8, 0), 8, 1)
        frames.append((f.astype(np.int16) + rng.integers(-6, 7, f.shape))
                      .clip(0, 255).astype(np.uint8))
    return frames


def yuv(rgb, chroma):
    return [np.round(p).clip(0, 255).astype(np.uint8)
            for p in decoders.planes(rgb, chroma)]


def golden_stream(chroma, n=3):
    """-> (access units, reconstructions) of an I + P stream."""
    from selkies_tpu.codecs import h264 as H264
    if chroma == "444":
        enc = H264.I444Encoder(W, H, 28)
        p_enc = H264.P444Encoder(enc)
    else:
        enc = H264.I16Encoder(W, H, 28)
        p_enc = H264.PFrameEncoder(enc)
    units, recons = [], []
    for k, rgb in enumerate(rgb_frames(n)):
        planes = yuv(rgb, chroma)
        units.append(enc.headers() + enc.encode_frame(*planes) if k == 0
                     else p_enc.encode_frame(*planes, frame_num=k))
        recon = enc.recon if chroma == "444" \
            else (enc.recon_y, enc.recon_u, enc.recon_v)
        recons.append([r.copy() for r in recon])
    return units, recons


@pytest.mark.parametrize("chroma", ["420", "444"])
def test_avdec_gives_back_the_encoders_reconstruction(chroma):
    units, recons = golden_stream(chroma)
    dec = decoders.H264Stream(W, H, chroma)
    try:
        for unit, want in zip(units, recons):
            got = dec.decode(unit)
            assert got is not None
            for g, w in zip(got, want):
                assert g.shape == w.shape
                assert np.array_equal(g, w)
    finally:
        dec.close()


@pytest.mark.parametrize("coded,promised", [("420", "444"), ("444", "420")])
def test_avdec_refuses_the_other_sampling(coded, promised):
    units, _ = golden_stream(coded, n=2)
    dec = decoders.H264Stream(W, H, promised)
    try:
        assert all(dec.decode(u) is None for u in units)
    finally:
        dec.close()


def jpeg(rgb, subsampling):
    from PIL import Image
    out = io.BytesIO()
    Image.fromarray(rgb).save(out, "JPEG", quality=90,
                              subsampling=subsampling)
    return out.getvalue()


def test_jpeg_planes_at_each_sampling():
    rgb = rgb_frames(1)[0]
    y, cb, cr = decoders.jpeg_planes(jpeg(rgb, 0), "444")
    want = decoders.planes(rgb, "444")
    assert y.shape == cb.shape == cr.shape == (H, W)
    assert all(np.abs(g - w).mean() < 3 for g, w in zip((y, cb, cr), want))
    # 4:2:0 colour under a 4:4:4 promise does not decode
    assert decoders.jpeg_planes(jpeg(rgb, 2), "444") is None
    # at 4:2:0 either JPEG decodes, its chroma averaged to half size
    for sub in (0, 2):
        y, cb, cr = decoders.jpeg_planes(jpeg(rgb, sub), "420")
        assert y.shape == (H, W) and cb.shape == cr.shape == (H // 2, W // 2)


def test_half_chroma_keeps_luma_and_420_colour():
    """The halfchroma control loses only what 4:2:0 cannot carry."""
    rgb = np.random.default_rng(SEED).integers(64, 192, (H, W, 3), np.uint8)
    halved = half_chroma(rgb)
    for g, w in zip(decoders.planes(halved, "420"),
                    decoders.planes(rgb, "420")):
        assert np.abs(g - w).max() <= 2.5
    full_got = decoders.planes(halved, "444")[1]
    full_want = decoders.planes(rgb, "444")[1]
    assert ((full_got - full_want) ** 2).mean() > 100


# -------------------------------------------------- 4:2:0 numbers pinned
def fixed_run(codec):
    """A fixed tiny run of ``codec`` at 4:2:0: the motion desktop at one
    seed, FRAMES frames as received (H.264: one stream from the golden
    encoders, an IDR then P frames; JPEG: 64-row stripes by PIL at
    quality 60), each frame one refresh on. -> (frames, window, keys,
    desktop)."""
    from PIL import Image
    from selkies_tpu.codecs import h264 as H264
    desktop = Desktop(tiny.scene("motion"), W, H, SEED)
    keys = {fid: 3 * fid for fid in range(FRAMES)}
    enc = H264.I16Encoder(W, H, 28)
    p_enc = H264.PFrameEncoder(enc)
    frames = {}
    for fid, key in keys.items():
        rgb = desktop.render(key)
        if codec == "h264":
            planes = [np.round(p).clip(0, 255).astype(np.uint8)
                      for p in _yuv420(rgb)]
            payload = enc.headers() + enc.encode_frame(*planes) if fid == 0 \
                else p_enc.encode_frame(*planes, frame_num=fid)
            got = {0: Stripe(fid + 0.5, payload, fid == 0, W, H)}
        else:
            got = {}
            for y in range(0, H, 64):
                out = io.BytesIO()
                Image.fromarray(rgb[y:y + 64]).save(out, "JPEG", quality=60,
                                                    subsampling=2)
                got[y] = Stripe(fid + 0.5, out.getvalue(), False, 0, 0)
        frames[fid] = Frame(fid, float(fid),
                            {y: len(s.payload) for y, s in got.items()}, got)
    return frames, list(range(2, FRAMES)), keys, desktop


def _yuv420(rgb):
    """BT.601 full range, chroma averaged over 2x2, written out here so
    that the input does not move with the reference's own code."""
    r, g, b = (rgb[..., i].astype(np.float64) for i in range(3))
    y = 0.299 * r + 0.587 * g + 0.114 * b
    cb = 128.0 - 0.168736 * r - 0.331264 * g + 0.5 * b
    cr = 128.0 + 0.5 * r - 0.418688 * g - 0.081312 * b
    return y, decoders.half(cb), decoders.half(cr)


#: compare() on fixed_run(codec) under the check as it was before the
#: sampling rule (CPU, PR 25)
PINNED = {
    "h264": {"worst_luma_mse": 34.453125,
             "worst_chroma_mse": 43.728937152254005,
             "frame_luma_mse": 15.086181640625,
             "frame_chroma_mse": 15.539016515688203,
             "undecodable": 0, "compared": 4},
    "jpeg": {"worst_luma_mse": 53.16015625,
             "worst_chroma_mse": 171.9518763392649,
             "frame_luma_mse": 12.852630615234375,
             "frame_chroma_mse": 18.291633581587124,
             "undecodable": 0, "compared": 4},
}
#: the H.264 byte count over fixed_run's frames, likewise
PINNED_BYTES = 1_758_898


@pytest.mark.parametrize("codec", ["h264", "jpeg"])
def test_420_numbers_are_pinned(codec):
    frames, window, keys, desktop = fixed_run(codec)
    got = check.compare(codec, frames, window, keys, desktop, SEED, "420")
    assert got == PINNED[codec]


def test_420_byte_counts_are_pinned():
    """The H.264 byte count over fixed_run's frames, 4:2:0 by default
    and as the configuration's sampling, against the count before the
    sampling rule; 4:4:4 counts the reference and reconstruction
    twice."""
    h264 = load_module(BENCH / "counts" / "h264.py")
    frames, *_ = fixed_run("h264")
    args = [dict(intra=f.idr, dirty_rows=H // 16, payload_bytes=f.nbytes)
            for f in frames.values()]
    total = sum(h264.bytes_needed(W, H, **a) for a in args)
    assert total == sum(h264.bytes_needed(W, H, chroma="420", **a)
                        for a in args) == PINNED_BYTES
    planes = sum(W * H * 3 // 2 * (1 if a["intra"] else 2) for a in args)
    assert sum(h264.bytes_needed(W, H, chroma="444", **a)
               for a in args) == total + planes

