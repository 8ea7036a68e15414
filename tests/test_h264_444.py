"""4:4:4 (fullcolor / Hi444PP) oracle chain, mirroring the 4:2:0 chain
(test_h264_device + test_h264_planes + test_h264_motion):

1. the golden numpy encoders (codecs/h264.I444Encoder / P444Encoder)
   must decode byte-exactly under libavcodec's independent Hi444PP
   decoder;
2. the device plane encoder (ops/h264_planes444) must be BIT-IDENTICAL
   to the golden encoders (I and zero-MV P), reconstruction included;
3. device streams with per-row QP and with motion search (which the
   golden encoders don't implement) must decode byte-exactly in ffmpeg
   against the device's own reconstruction;
4. the ChromaArrayType-3 coded_block_pattern me(v) table must equal the
   empirical derivation against libavcodec (tools/derive_cbp444.py).
"""

import numpy as np
import pytest

from selkies_tpu.codecs import h264 as H
from selkies_tpu.codecs import h264_ref_decoder as refdec
from selkies_tpu.native import avshim

jnp = pytest.importorskip("jax.numpy")

from selkies_tpu.ops.bitpack import words_to_bytes  # noqa: E402
from selkies_tpu.ops.h264_encode import scroll_candidates  # noqa: E402
from selkies_tpu.ops.h264_planes444 import (P_SLOTS_MB_444,  # noqa: E402
                                            SLOTS_MB_444,
                                            h264_encode_p_yuv444,
                                            h264_encode_yuv444)

needs_av = pytest.mark.skipif(not avshim.available(),
                              reason="libavcodec unavailable")

QP = 28


def _planes(h, w, seed=0):
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w]
    y = ((xx * 5 + yy * 11 + rng.integers(0, 48, (h, w))) % 256
         ).astype(np.uint8)
    u = ((xx * 3 + rng.integers(0, 64, (h, w))) % 256).astype(np.uint8)
    v = rng.integers(0, 256, (h, w), dtype=np.uint8)
    return y, u, v


def _device_i444(y, u, v, qp, idr_pic_id=0, want_recon=False):
    R, M = y.shape[0] // 16, y.shape[1] // 16
    pay, nb = H.slice_header_events(M, R)
    e_cap = 16 + M * SLOTS_MB_444 + 2
    out = h264_encode_yuv444(
        jnp.asarray(y, jnp.int32), jnp.asarray(u, jnp.int32),
        jnp.asarray(v, jnp.int32), qp, jnp.asarray(pay), jnp.asarray(nb),
        e_cap, 32768, idr_pic_id=idr_pic_id, want_recon=want_recon)
    res = out[0] if want_recon else out
    assert not bool(np.asarray(res.overflow))
    w_, b_ = np.asarray(res.words), np.asarray(res.total_bits)
    rows = [words_to_bytes(w_[r], int(b_[r]), pad_ones=False)
            for r in range(R)]
    if want_recon:
        return rows, tuple(np.asarray(p) for p in out[1])
    return rows


def _device_p444(y, u, v, recon, qp, cands=((0, 0),), frame_num=1):
    R, M = y.shape[0] // 16, y.shape[1] // 16
    pay, nb = H.p_slice_header_events(M, R)
    e_cap = 16 + M * P_SLOTS_MB_444 + 2
    out, rec = h264_encode_p_yuv444(
        jnp.asarray(y, jnp.int32), jnp.asarray(u, jnp.int32),
        jnp.asarray(v, jnp.int32), jnp.asarray(recon[0]),
        jnp.asarray(recon[1]), jnp.asarray(recon[2]), qp,
        jnp.asarray(pay), jnp.asarray(nb), frame_num, e_cap, 32768,
        candidates=cands)
    assert not bool(np.asarray(out.overflow))
    w_, b_ = np.asarray(out.words), np.asarray(out.total_bits)
    rows = [words_to_bytes(w_[r], int(b_[r]), pad_ones=False)
            for r in range(R)]
    return rows, tuple(np.asarray(p) for p in rec)


def _golden_rows(frame_bytes):
    """NAL-wrapped golden frame -> per-row RBSPs (emulation stripped)."""
    return [refdec.remove_emulation_prevention(part[1:])
            for part in frame_bytes.split(b"\x00\x00\x00\x01")[1:]]


def _ffmpeg_decode_seq(headers, aus):
    sess = avshim.H264Session()
    got = None
    for i, au in enumerate(aus):
        got = sess.decode(headers + au if i == 0 else au) or got
    got = sess.flush() or got
    sess.close()
    assert got is not None
    return got


# ---------------------------------------------------------------------------
# 1. golden encoders vs ffmpeg
# ---------------------------------------------------------------------------

@needs_av
@pytest.mark.parametrize("qp", [16, 28, 40])
def test_golden_i444_byte_exact_under_ffmpeg(qp):
    y, u, v = _planes(48, 64, seed=qp)
    enc = H.I444Encoder(64, 48, qp)
    au = enc.encode_frame(y, u, v)
    fy, fu, fv = avshim.decode_h264(enc.headers() + au)
    assert fy.shape == (48, 64) and fu.shape == (48, 64)
    assert np.array_equal(fy, enc.recon[0])
    assert np.array_equal(fu, enc.recon[1])
    assert np.array_equal(fv, enc.recon[2])


@needs_av
def test_golden_p444_byte_exact_under_ffmpeg():
    y0, u0, v0 = _planes(48, 64, seed=2)
    enc = H.I444Encoder(64, 48, QP)
    idr = enc.encode_frame(y0, u0, v0)
    # second frame: half the MBs change (exercises skip runs + coded MBs)
    y1 = y0.copy()
    y1[:, 16:48] = np.roll(y0[:, 16:48], 3, axis=0)
    u1 = u0.copy()
    u1[8:40] = 255 - u1[8:40]
    penc = H.P444Encoder(enc)
    pau = penc.encode_frame(y1, u1, v0, frame_num=1)
    got = _ffmpeg_decode_seq(enc.headers(), [idr, pau])
    assert np.array_equal(got[0], enc.recon[0])
    assert np.array_equal(got[1], enc.recon[1])
    assert np.array_equal(got[2], enc.recon[2])


# ---------------------------------------------------------------------------
# 2. device plane encoder vs golden: bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qp", [16, 28, 40])
def test_device_i444_bit_identical_to_golden(qp):
    y, u, v = _planes(48, 64, seed=10 + qp)
    dev, drec = _device_i444(y, u, v, qp, want_recon=True)
    enc = H.I444Encoder(64, 48, qp)
    host = _golden_rows(enc.encode_frame(y, u, v))
    assert len(dev) == len(host) == 3
    for r, (d, g) in enumerate(zip(dev, host)):
        assert d == g, f"row {r}: device != golden"
    for ci in range(3):
        assert np.array_equal(drec[ci], enc.recon[ci]), f"recon comp {ci}"


def test_device_p444_bit_identical_to_golden():
    y0, u0, v0 = _planes(48, 64, seed=20)
    _, drec = _device_i444(y0, u0, v0, QP, want_recon=True)
    enc = H.I444Encoder(64, 48, QP)
    enc.encode_frame(y0, u0, v0)
    # changed frame with static regions -> mix of skip and coded MBs
    y1 = y0.copy()
    y1[16:32] = np.roll(y0[16:32], 2, axis=1)
    v1 = v0.copy()
    v1[:16, :32] = 255 - v1[:16, :32]
    dev, dprec = _device_p444(y1, u0, v1, drec, QP)
    penc = H.P444Encoder(enc)
    host = _golden_rows(penc.encode_frame(y1, u0, v1, frame_num=1))
    assert len(dev) == len(host) == 3
    for r, (d, g) in enumerate(zip(dev, host)):
        assert d == g, f"row {r}: device != golden"
    for ci in range(3):
        assert np.array_equal(dprec[ci], enc.recon[ci]), f"recon comp {ci}"


# ---------------------------------------------------------------------------
# 3. device-only features (per-row QP, motion) vs ffmpeg
# ---------------------------------------------------------------------------

@needs_av
def test_device_i444_per_row_qp_decodes_in_ffmpeg():
    y, u, v = _planes(48, 64, seed=30)
    qp_rows = jnp.asarray([18, 30, 44], jnp.int32)
    dev, drec = _device_i444(y, u, v, qp_rows, want_recon=True)
    headers = H.write_sps(64, 48, chroma_format=3) + H.write_pps()
    annexb = headers + H.assemble_annexb(dev)
    fy, fu, fv = avshim.decode_h264(annexb)
    assert np.array_equal(fy, drec[0])
    assert np.array_equal(fu, drec[1])
    assert np.array_equal(fv, drec[2])


@needs_av
def test_device_p444_motion_decodes_in_ffmpeg():
    h, w = 48, 64
    y0, u0, v0 = _planes(h, w, seed=40)
    idev, irec = _device_i444(y0, u0, v0, QP, want_recon=True)
    # vertical scroll by 5 px on all three full-res components
    rng = np.random.default_rng(41)
    dy = 5
    y1 = np.concatenate([y0[dy:], rng.integers(
        0, 256, (dy, w), dtype=np.uint8)])
    u1 = np.concatenate([u0[dy:], np.full((dy, w), 128, np.uint8)])
    v1 = np.concatenate([v0[dy:], np.full((dy, w), 128, np.uint8)])
    zero_rows, _ = _device_p444(y1, u1, v1, irec, QP)
    mv_rows, prec = _device_p444(y1, u1, v1, irec, QP,
                                 cands=scroll_candidates(8, 4))
    assert sum(map(len, mv_rows)) < 0.6 * sum(map(len, zero_rows)), \
        "motion search must beat zero-MV on scrolled 4:4:4 content"
    headers = H.write_sps(w, h, chroma_format=3) + H.write_pps()
    idr_au = H.assemble_annexb(idev)
    p_au = b"".join(H.nal(1, rb, ref_idc=2) for rb in mv_rows)
    got = _ffmpeg_decode_seq(headers, [idr_au, p_au])
    for ci in range(3):
        assert np.array_equal(got[ci], prec[ci]), f"comp {ci}"


def test_device_p444_all_skip_is_tiny():
    y, u, v = _planes(32, 48, seed=50)
    _, rec = _device_i444(y, u, v, QP, want_recon=True)
    rows, _ = _device_p444(rec[0], rec[1], rec[2], rec, QP,
                           cands=scroll_candidates(4, 2))
    assert sum(map(len, rows)) < 2 * 16, \
        "self-referential P must be all-skip"


# ---------------------------------------------------------------------------
# 4. the CBP444 me(v) table equals its empirical derivation
# ---------------------------------------------------------------------------

@needs_av
def test_cbp444_table_matches_libavcodec_derivation():
    # tools/ is not a package: load the script by path so a bare
    # ``pytest`` under an editable install (repo root off sys.path)
    # still finds it
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parent.parent \
        / "tools" / "derive_cbp444.py"
    spec = importlib.util.spec_from_file_location("derive_cbp444", path)
    derive_cbp444 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(derive_cbp444)
    from selkies_tpu.codecs import h264_tables as T
    derived = derive_cbp444.derive()
    assert np.array_equal(derived, T.CBP444_INTER_CBP2CODE)


# ---------------------------------------------------------------------------
# 5. the fullcolor deployment's session (benchmark configuration
#    h264-1080p60-444) on a moving scene
# ---------------------------------------------------------------------------

#: largest per-plane MSE against the source a CRF-25 frame may show here
#: (sound: 1-18; the same scene at CRF 37 reads 85-240)
CRF25_PLANE_MSE = 40.0


def _ycbcr601_full(rgb):
    """Independent BT.601 full-range YCbCr of an (H, W, 3) uint8 frame."""
    r, g, b = (rgb[..., i].astype(np.float64) for i in range(3))
    return (0.299 * r + 0.587 * g + 0.114 * b,
            128 - 0.168736 * r - 0.331264 * g + 0.5 * b,
            128 + 0.5 * r - 0.418688 * g - 0.081312 * b)


@needs_av
def test_fullcolor_session_decodes_to_its_reference_and_the_source():
    """The configuration's flags (one stream per display, the damage-band
    P path, motion search 24x8, CRF 25, fullcolor) through
    H264EncoderSession on a panning scene, an IDR then P frames: each
    frame decodes under libavcodec at 4:4:4 to exactly the session's own
    reference planes (no encoder/decoder drift), and within the CRF-25
    noise of the source."""
    from selkies_tpu.engine.h264_encoder import H264EncoderSession
    from selkies_tpu.engine.types import CaptureSettings
    w, h = 96, 64
    sess = H264EncoderSession(CaptureSettings(
        capture_width=w, capture_height=h, single_stream=True,
        fullcolor=True, video_crf=25, h264_motion_vrange=24,
        h264_motion_hrange=8, h264_partial_encode=True,
        use_damage_gating=True))
    rng = np.random.default_rng(7)
    tex = np.repeat(np.repeat(rng.integers(
        0, 256, ((h + 64) // 4, (w + 64) // 4, 3), dtype=np.uint8), 4, 0),
        4, 1)
    dec = avshim.H264Session()
    try:
        for t in range(5):
            frame = tex[3 * t:3 * t + h, 2 * t:2 * t + w].copy()
            chunks = sess.finalize(sess.encode(jnp.asarray(frame),
                                               force=(t == 0)))
            assert [c.is_idr for c in chunks] == [t == 0]
            got = dec.decode(chunks[0].payload) or dec.flush()
            assert got is not None, f"frame {t} did not decode"
            refs = (sess._ref_y, sess._ref_u, sess._ref_v)
            for ci, (plane, ref, src) in enumerate(
                    zip(got, refs, _ycbcr601_full(frame))):
                assert plane.shape == (h, w), (t, ci, plane.shape)
                assert np.array_equal(plane, np.asarray(ref)[:h]), (t, ci)
                mse = float(np.mean((plane - src) ** 2))
                assert mse <= CRF25_PLANE_MSE, (t, ci, mse)
    finally:
        dec.close()
