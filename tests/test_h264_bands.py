"""Damage-proportional encoding (ROADMAP 4 / ISSUE 15): dirty-band
partial P encode.

Contracts pinned here:

- band geometry bucketing (ops/bands): pow-2 buckets, coverage, motion
  granularity, floors;
- the all-skip slice builder's bitstream format (codecs.h264
  .p_skip_slice_rbsp) field by field through the reference BitReader;
- **byte identity**: the partial path with a 100%-dirty damage map
  emits chunk-for-chunk identical bytes to the stock P step — zero-MV,
  motion-search, 4:4:4 and single-stream configurations;
- **decode validity**: partially-dirty frames (device band rows
  stitched against host-built skip slices) round-trip through the
  reference decoder to EXACTLY the server-side reconstruction, and the
  partial path's paint-over refines as P frames like the stock path;
- idle frames dispatch nothing (the out dict says so);
- bands x stripes composition: a stripe-sharded session gates the
  partial path OFF (keeping the device-parallel stock steps) and stays
  byte-identical to the unsharded stock session;
- ROI QP (per-MB qp plane + real mb_qp_delta syntax) stays oracle-exact;
- the prewarm lattice grows the bands axis (program_key + plan names).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from selkies_tpu.codecs import h264 as hcodec  # noqa: E402
from selkies_tpu.codecs import h264_ref_decoder as refdec  # noqa: E402
from selkies_tpu.engine.h264_encoder import (  # noqa: E402
    H264EncoderSession, StripeShardedH264Session)
from selkies_tpu.engine.types import CaptureSettings  # noqa: E402
from selkies_tpu.ops.bands import (band_buckets, dirty_fraction,  # noqa: E402
                                   plan_band)

W = H = 64
BASE = dict(capture_width=W, capture_height=H, stripe_height=32,
            output_mode="h264", video_crf=28, use_paint_over=False,
            h264_motion_vrange=0, h264_motion_hrange=0)

rng = np.random.default_rng(1234)


# ---------------------------------------------------------------- geometry
def test_band_buckets():
    assert band_buckets(9) == (1, 2, 4, 8, 9)
    assert band_buckets(8) == (1, 2, 4, 8)
    assert band_buckets(8, granularity=2) == (2, 4, 8)
    assert band_buckets(12, granularity=4) == (4, 8, 12)
    with pytest.raises(ValueError):
        band_buckets(0)


def test_plan_band_covers_needed_rows():
    R = 16
    for _ in range(200):
        rows = np.zeros(R, bool)
        n = rng.integers(1, 5)
        rows[rng.integers(0, R, n)] = True
        for g in (1, 4):
            row0, brows = plan_band(rows, granularity=g)
            assert row0 % g == 0
            assert brows in band_buckets(R, g)
            covered = np.zeros(R, bool)
            covered[row0:row0 + brows] = True
            assert (covered | ~rows).all(), (rows, row0, brows)


def test_plan_band_idle_and_floor():
    assert plan_band(np.zeros(8, bool)) is None
    rows = np.zeros(8, bool)
    rows[3] = True
    assert plan_band(rows)[1] == 1
    assert plan_band(rows, floor_rows=4)[1] == 4
    # floor above R clamps to the full frame
    assert plan_band(rows, floor_rows=99) == (0, 8)
    assert dirty_fraction(rows) == 1 / 8


# ------------------------------------------------------- skip-slice format
def test_p_skip_slice_rbsp_fields():
    mb_w, n_mbs, qp, fn = 4, 4, 31, 5
    rbsp = hcodec.p_skip_slice_rbsp(1 * mb_w, n_mbs, qp, fn)
    r = refdec.BitReader(rbsp)
    assert r.ue() == 1 * mb_w          # first_mb_in_slice
    assert r.ue() == 5                 # slice_type P
    assert r.ue() == 0                 # pps id
    assert r.u(4) == fn & 0xF          # frame_num
    assert r.u(1) == 0                 # num_ref_idx_override
    assert r.u(1) == 0                 # ref_pic_list_modification
    assert r.u(1) == 0                 # adaptive_ref_pic_marking
    assert r.se() == qp - 26           # slice_qp_delta
    assert r.ue() == 1                 # disable_deblocking_filter_idc
    assert r.ue() == n_mbs             # mb_skip_run == every MB skipped
    assert not r.more_rbsp_data()      # stop bit + zero pad only


# ----------------------------------------------------------- byte identity
def _chunks(sess, frames):
    out = []
    for t, f in enumerate(frames):
        out.append([(c.stripe_y, c.is_idr, c.payload) for c in
                    sess.finalize(sess.encode(f, force=(t == 0)))])
    return out


def _full_dirty_frames(n=3):
    f0 = rng.integers(0, 256, (H, W, 3), dtype=np.uint8)
    return [jnp.asarray(np.roll(f0, 5 * t, axis=0)) for t in range(n)]


@pytest.mark.parametrize("cfg", [
    {},                                                   # zero-MV
    {"h264_motion_vrange": 8, "h264_motion_hrange": 2},   # motion bands
    {"fullcolor": True},                                  # 4:4:4
    {"single_stream": True},                              # one stream
    {"fullcolor": True, "h264_motion_vrange": 8,          # 4:4:4 motion
     "h264_motion_hrange": 2},
], ids=["zeromv", "motion", "444", "single", "444-motion"])
def test_partial_full_dirty_byte_identical_to_stock(cfg):
    frames = _full_dirty_frames()
    kw = dict(BASE, **cfg)
    a = _chunks(H264EncoderSession(
        CaptureSettings(**kw, h264_partial_encode=True)), frames)
    b = _chunks(H264EncoderSession(
        CaptureSettings(**kw, h264_partial_encode=False)), frames)
    assert a == b


# --------------------------------------------------------- decode validity
def _partial_script():
    base = rng.integers(0, 256, (H, W, 3), dtype=np.uint8)
    script = [base.copy()]
    f = base.copy()
    f[16:32, 0:32] = rng.integers(0, 256, (16, 32, 3), dtype=np.uint8)
    script.append(f.copy())
    script.append(f.copy())                        # idle frame
    g = f.copy()
    g[H - 16:H, :] = rng.integers(0, 256, (16, W, 3), dtype=np.uint8)
    script.append(g)
    return [jnp.asarray(x) for x in script]


def _assert_oracle_matches_refs(sess, per_stripe):
    sh = sess.grid.stripe_h
    assert per_stripe, "no chunks delivered"
    for y0, payloads in per_stripe.items():
        y, u, v = refdec.decode(b"".join(payloads))
        assert np.array_equal(y, np.asarray(sess._ref_y)[y0:y0 + sh])
        assert np.array_equal(
            u, np.asarray(sess._ref_u)[y0 // 2:(y0 + sh) // 2])
        assert np.array_equal(
            v, np.asarray(sess._ref_v)[y0 // 2:(y0 + sh) // 2])


def test_partial_frames_decode_valid_and_idle_skips_device():
    sess = H264EncoderSession(
        CaptureSettings(**BASE, h264_partial_encode=True))
    frames = _partial_script()
    per_stripe = {}
    outs = []
    for t, f in enumerate(frames):
        out = sess.encode(f, force=(t == 0))
        outs.append(out)
        for c in sess.finalize(out):
            per_stripe.setdefault(c.stripe_y, []).append(c.payload)
    # t=1 damaged one MB row -> a 1-row band, not a full dispatch
    assert outs[1]["band"] == (1, 1)
    assert outs[1]["dirty_fraction"] == pytest.approx(0.25)
    # t=2 was content-identical -> idle: no device dispatch at all
    assert outs[2].get("idle") is True and "data" not in outs[2]
    # client reconstruction == server reference, bit for bit
    _assert_oracle_matches_refs(sess, per_stripe)


def test_partial_paint_over_refines_as_p_band():
    kw = dict(BASE, use_paint_over=True)
    sess = H264EncoderSession(CaptureSettings(
        **kw, h264_partial_encode=True))
    sess.settings.paint_over_delay_frames = 3
    base = rng.integers(0, 256, (H, W, 3), dtype=np.uint8)
    f = base.copy()
    f[0:16] = rng.integers(0, 256, (16, W, 3), dtype=np.uint8)
    per_stripe = {}
    paint_chunks = None
    frames = [base, f] + [f] * 6
    for t, fr in enumerate(frames):
        out = sess.encode(jnp.asarray(fr), force=(t == 0))
        chunks = sess.finalize(out)
        for c in chunks:
            per_stripe.setdefault(c.stripe_y, []).append(c.payload)
        if t >= 2 and chunks:
            # the settled stripe comes back once, at paint qp, as P
            assert np.any(np.asarray(out["is_paint"]))
            assert all(not c.is_idr for c in chunks)
            paint_chunks = chunks
    assert paint_chunks is not None, "paint-over never fired"
    _assert_oracle_matches_refs(sess, per_stripe)


def test_partial_composes_with_stripe_sharding():
    """A sharded session GATES the partial path off (a single-device
    band step would forfeit the N-way scaling under full motion, and
    the probe would dispatch sharded state the prewarmed program was
    not built for) and keeps the stock device-parallel steps — still
    byte-identical to the unsharded STOCK session (sharding stays a
    pure distribution axis, the PR-12 contract). The stock step
    refines clean rows against the lossy reference, so stock and
    partial only coincide at 100% dirty — the identity tests above."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (forced host) devices")
    frames = _partial_script()
    ref = H264EncoderSession(
        CaptureSettings(**BASE, h264_partial_encode=False))
    shard = StripeShardedH264Session(
        CaptureSettings(**BASE, h264_partial_encode=True,
                        stripe_devices=2))
    assert shard.stripe_devices == 2
    assert not shard._partial
    assert _chunks(ref, frames) == _chunks(shard, frames)


# ------------------------------------------------------------------ ROI QP
def test_roi_qp_oracle_round_trip():
    sess = H264EncoderSession(CaptureSettings(
        **BASE, h264_partial_encode=True, h264_roi_qp=True,
        h264_roi_qp_bias=6))
    frames = _partial_script()
    per_stripe = {}
    for t, f in enumerate(frames):
        for c in sess.finalize(sess.encode(f, force=(t == 0))):
            per_stripe.setdefault(c.stripe_y, []).append(c.payload)
    _assert_oracle_matches_refs(sess, per_stripe)


def test_roi_qp_emits_nonzero_mb_qp_delta():
    """The ROI plane must reach the WIRE as mb_qp_delta syntax, not
    just the quantiser: decode the band slice of a mixed
    damaged/settled row and confirm a non-zero delta was parsed."""
    from selkies_tpu.ops.h264_planes import h264_encode_p_yuv
    Rr, M = 2, 4
    hh, ww = Rr * 16, M * 16
    cur = rng.integers(0, 256, (hh, ww), dtype=np.int32)
    ref_y = cur.copy()
    cur[0:16, 0:16] = rng.integers(0, 256, (16, 16), dtype=np.int32)
    cur[0:16, 32:64] = np.clip(ref_y[0:16, 32:64] + 40, 0, 255)
    ref_u = rng.integers(0, 256, (hh // 2, ww // 2), dtype=np.int32)
    ref_v = rng.integers(0, 256, (hh // 2, ww // 2), dtype=np.int32)
    pay, nb = hcodec.p_slice_header_events(M, Rr)
    qp = 30
    qp_mb = np.full((Rr, M), qp, np.int32)
    qp_mb[0, 0] = qp - 6                  # "damaged" MB sharpens
    out, _ = h264_encode_p_yuv(
        jnp.asarray(cur), jnp.asarray(ref_u), jnp.asarray(ref_v),
        jnp.asarray(ref_y), jnp.asarray(ref_u), jnp.asarray(ref_v),
        qp, jnp.asarray(pay), jnp.asarray(nb), 1, 200, 2048,
        qp_mb=jnp.asarray(qp_mb))
    from selkies_tpu.ops.stripes import words_to_bytes_device
    by, lens = words_to_bytes_device(out.words, out.total_bits,
                                     pad_ones=False)
    row0 = bytes(np.asarray(by[0][:int(lens[0])]))
    r = refdec.BitReader(row0)
    r.ue(); r.ue(); r.ue(); r.u(4); r.u(1); r.u(1); r.u(1)
    assert r.se() == qp - 26
    r.ue()                                 # deblock idc
    assert r.ue() == 0                     # skip run 0 (MB 0 coded)
    assert r.ue() == 0                     # mb_type P_L0_16x16
    r.se(); r.se()                         # mvd
    cbp = refdec.T.CBP_INTER_CODE2CBP[r.ue()]
    assert cbp != 0
    assert r.se() == -6                    # mb_qp_delta reaches the wire


# ------------------------------------------------------------ lattice axis
def test_lattice_gains_bands_axis():
    from selkies_tpu.prewarm.lattice import Signature
    from selkies_tpu.prewarm.plan import program_names
    sig = Signature(width=64, height=64, codec="h264", stripe_height=32,
                    h264_motion_vrange=0, partial_encode=True)
    assert "bands" in sig.program_key
    names = program_names(sig)
    assert any("row_probe" in n for n in names)
    # zero-MV partial: MB-row-granular buckets 1, 2, 4
    assert [n for n in names if ".band" in n] == [
        f"h264.band{b}.p_step[64x64]" for b in (1, 2, 4)]
    # motion partial: stripe-granular buckets only
    sig_m = Signature(width=64, height=64, codec="h264", stripe_height=32,
                      h264_motion_vrange=8, partial_encode=True)
    assert [n for n in program_names(sig_m) if ".band" in n] == [
        f"h264.band{b}.p_step[64x64]" for b in (2, 4)]
    # partial off: no band programs, unchanged key shape
    sig_off = Signature(width=64, height=64, codec="h264",
                        stripe_height=32, partial_encode=False)
    assert "bands" not in sig_off.program_key
    assert not any(".band" in n for n in program_names(sig_off))
