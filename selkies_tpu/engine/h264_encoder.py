"""H.264 stripe-encoder session: the ``--encoder=h264-tpu`` device path.

Mirrors :class:`~selkies_tpu.engine.encoder.JpegEncoderSession`'s contract
(encode/finalize split, damage gating + paint-over state on device, one
output buffer per frame) with the H.264 Intra_16x16 pipeline of
ops/h264_encode.py underneath:

- every wire stripe is an INDEPENDENT H.264 stream (reference
  ``h264enc-striped``: per-stripe decoders client-side, SURVEY.md §2.5)
  of ``stripe_h`` rows; each MB row inside a stripe is one slice;
- damage gating: unchanged stripes are skipped; paint-over re-sends a
  settled stripe once at ``paint_over_qp`` — the per-row qp select runs
  ON DEVICE, so neither rate control nor paint-over ever syncs the host;
- adaptive I/P: the first frame and every forced refresh are IDR access
  units (SPS+PPS+slices); all other frames are P frames with zero-motion
  conditional replenishment — unchanged macroblocks code as P_Skip
  (bytes, not kilobytes), changed ones carry residual against the
  device-resident decoder-exact reconstruction. The relay's per-stripe
  chain gating plus keyframe recovery handle any P loss.

Only the byte buffer + lengths + flags leave the chip (bitrate-sized).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..codecs import h264 as hcodec
from ..obs import perf as _perf
from ..ops.bands import dirty_fraction as _dirty_fraction
from ..ops.bands import plan_band
from ..ops.h264_encode import P_SLOTS_MB, SLOTS_MB, scroll_candidates
from ..ops.h264_planes import (h264_encode_p_yuv, h264_encode_yuv,
                               rgb_to_yuv420)
from ..ops.stripes import concat_stripe_bytes, words_to_bytes_device
from ..resilience import faults as _faults
from ..trace import tracer as _tracer
from .types import CaptureSettings, EncodedChunk

logger = logging.getLogger("selkies_tpu.engine.h264")


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


@dataclasses.dataclass
class _Grid:
    width: int
    height: int
    stripe_h: int
    n_stripes: int
    rows_per_stripe: int
    mb_w: int
    out_w: int
    out_h: int


def h264_buffer_caps(g: "_Grid", fullcolor: bool = False
                     ) -> tuple[int, int, int]:
    """(e_cap, w_cap, out_cap) for a grid — shared by the single-seat
    session and the seat-sharded encoder so the sizing policy cannot
    diverge. w_cap is words per MB row, out_cap bytes per frame: the
    stream buffer, fetched up to the bytes used (engine/readback.py).
    Overflow doubles both (and forces a clean refresh), so an undersized
    buffer costs a recompile and an IDR. 4:2:0: sized for realistic
    intra frames (~1.5 bits/px).

    4:4:4 (fullcolor) is sized for what a 4:4:4 frame carries, which is
    about 2.4x a 4:2:0 one under full motion, not 1.5x: out_cap one byte
    a pixel (a third of the raw samples), w_cap 288 words a macroblock
    (1.5x a row's raw samples). At 1920x1088 that is 2,088,960 B a frame
    and 34,560 words a row. The benchmark's full-motion cell at CRF 25
    (h264-1080p60-444.motion; one v5e, 21 windows and their warm-up, 36
    IDRs and 484 motion P frames) peaked at a P frame of 1,245,417 B
    (IDRs 322,118 B) and a row of 21,190 B (5,298 words): margins 1.68x
    and 6.5x at first build."""
    if fullcolor:
        from ..ops.h264_planes444 import P_SLOTS_MB_444, SLOTS_MB_444
        e_cap = 9 + g.mb_w * max(SLOTS_MB_444, P_SLOTS_MB_444) + 2
        w_cap = max(3072, g.mb_w * 1152 // 4)
        out_cap = max(288 * 1024, g.width * g.height)
    else:
        e_cap = 9 + g.mb_w * max(SLOTS_MB, P_SLOTS_MB) + 2
        w_cap = max(2048, g.mb_w * 768 // 4)
        out_cap = max(192 * 1024, g.width * g.height // 6)
    return e_cap, w_cap, out_cap


def h264_stripe_payload(intra: bool, rows: list[bytes],
                        sps_pps: bytes) -> bytes:
    """Wire payload for one stripe: IDR access unit (headers + IDR
    slices) or non-IDR reference P slices."""
    if intra:
        return sps_pps + hcodec.assemble_annexb(rows)
    return b"".join(hcodec.nal(1, rb, ref_idc=2) for rb in rows)


def plan_h264_grid(s: CaptureSettings) -> _Grid:
    if s.single_stream:
        # one stream per display, derived from the CURRENT height so the
        # rule survives live-resize session rebuilds
        stripe_h = _round_up(max(16, s.capture_height), 16)
    else:
        stripe_h = max(16, _round_up(s.stripe_height, 16))
    w = _round_up(s.capture_width, 16)
    h = _round_up(s.capture_height, stripe_h)
    return _Grid(width=w, height=h, stripe_h=stripe_h,
                 n_stripes=h // stripe_h, rows_per_stripe=stripe_h // 16,
                 mb_w=w // 16, out_w=s.capture_width, out_h=s.capture_height)


def build_h264_step_fn(mode: str, width: int, stripe_h: int, n_stripes: int,
                       e_cap: int, w_cap: int, out_cap: int,
                       paint_delay: int, damage_gating: bool,
                       paint_over: bool, candidates: tuple = ((0, 0),),
                       fullcolor: bool = False):
    """Pure per-frame step for ``mode`` in {"i", "p"} — jitted by
    :func:`_jitted_h264_step` for the single-seat session, vmapped +
    shard_mapped by :class:`~selkies_tpu.parallel.MultiSeatH264Encoder`.

    Both modes share the damage/paint-over/stream-counter logic and
    maintain the decoder-exact reconstruction planes on device — the P
    mode's reference. HBM-resident state inputs (prev framebuffer, age,
    sent, fnum, ref planes) are donated (deep-pipeline HBM discipline);
    the next frame's damage reference leaves as ``prev_out``, a
    materialized copy of ``frame`` — never the caller's array, so
    sources stay free to cache/reuse their frame buffers.

    signature (I): step(frame, prev, age, sent, fnum, ref_y, ref_u, ref_v,
                        qp_motion, qp_paint, force, hdr_pay, hdr_nb)
    signature (P): same, ``force`` unused (P is never forced).
    -> (data u8 (out_cap,), row_lens i32 (R,), send (S,), is_paint (S,),
        age (S,), sent (S,), fnum (S,), recon_y, recon_u, recon_v,
        prev_out, overflow)
    """
    rows_per_stripe = stripe_h // 16

    def step(frame, prev, age, sent, fnum, ref_y, ref_u, ref_v,
             qp_motion, qp_paint, force, hdr_pay, hdr_nb):
        s = n_stripes
        stripes = frame.reshape(s, stripe_h, width, 3)
        if damage_gating:
            prev_s = prev.reshape(s, stripe_h, width, 3)
            damage = jnp.any(stripes != prev_s, axis=(1, 2, 3))
        else:
            damage = jnp.ones((s,), bool)
        age = jnp.where(damage, 0, age + 1)
        if paint_over and paint_delay > 0:
            is_paint = age == paint_delay
        else:
            is_paint = jnp.zeros((s,), bool)
        send = damage | is_paint | force
        qp_stripe = jnp.where(is_paint, qp_paint, qp_motion)
        qp_rows = jnp.repeat(qp_stripe, rows_per_stripe)
        if fullcolor:
            from ..ops.h264_planes444 import (h264_encode_p_yuv444,
                                              h264_encode_yuv444,
                                              rgb_to_yuv444)
            yf, uf, vf = rgb_to_yuv444(frame)
            enc_i, enc_p = h264_encode_yuv444, h264_encode_p_yuv444
        else:
            yf, uf, vf = rgb_to_yuv420(frame)
            enc_i, enc_p = h264_encode_yuv, h264_encode_p_yuv

        if mode == "i":
            # consecutive IDRs of one stripe stream must differ in
            # idr_pic_id (§7.4.3); a 4-bit cycle of the device-resident
            # sent counter keeps that even across overflow-dropped frames
            idr_rows = jnp.repeat(sent & 0xF, rows_per_stripe)
            sent = sent + send.astype(jnp.int32)
            # IDR resets the stream's frame_num; next P in the stream is 1
            fnum = jnp.where(send, 1, fnum)
            out, recon = enc_i(
                yf, uf, vf, qp_rows, hdr_pay, hdr_nb, e_cap, w_cap,
                idr_pic_id=idr_rows, want_recon=True)
        else:
            fn_rows = jnp.repeat(fnum, rows_per_stripe)
            sent = sent + send.astype(jnp.int32)
            fnum = jnp.where(send, fnum + 1, fnum)
            out, recon = enc_p(
                yf, uf, vf, ref_y, ref_u, ref_v, qp_rows,
                hdr_pay, hdr_nb, fn_rows, e_cap, w_cap,
                candidates=candidates, stripe_rows=rows_per_stripe)

        # the reference only advances for DELIVERED stripes: finalize drops
        # unsent ones, and a reference the client never saw would drift the
        # next P slice into visible corruption
        def gate(new, old, sh):
            ns = new.reshape(s, sh, -1)
            os_ = old.reshape(s, sh, -1)
            sel = jnp.where(send[:, None, None], ns, os_)
            return sel.reshape(new.shape)
        c_sh = stripe_h if fullcolor else stripe_h // 2
        new_ry = gate(recon[0], ref_y, stripe_h)
        new_ru = gate(recon[1], ref_u, c_sh)
        new_rv = gate(recon[2], ref_v, c_sh)

        sbytes, row_lens = words_to_bytes_device(out.words, out.total_bits,
                                                 pad_ones=False)
        buf = concat_stripe_bytes(sbytes, row_lens, out_cap)
        overflow = out.overflow | buf.overflow
        # materialized (bitwise_or defeats jaxpr input-forwarding): the
        # donated prev allocation is reused for it — zero HBM growth
        prev_out = jnp.bitwise_or(frame, jnp.uint8(0))
        return (buf.data, buf.byte_lens, send, is_paint, age, sent, fnum,
                new_ry, new_ru, new_rv, prev_out, overflow)

    # the XLA module compiles as jit_h264_{i,p}_step: the name a
    # jax.profiler capture's device lane carries, and the stem obs.perf's
    # capture parser matches step attribution against
    step.__name__ = f"h264_{mode}_step"
    return step


# bounded LRU (see engine/encoder.py:_jitted_step): geometry retargeting
# mints fresh keys; the pre-warm planner shares this factory cache
@functools.lru_cache(maxsize=32)
def _jitted_h264_step(mode: str, width: int, stripe_h: int, n_stripes: int,
                      e_cap: int, w_cap: int, out_cap: int,
                      paint_delay: int, damage_gating: bool,
                      paint_over: bool, candidates: tuple = ((0, 0),),
                      fullcolor: bool = False):
    step = build_h264_step_fn(mode, width, stripe_h, n_stripes, e_cap,
                              w_cap, out_cap, paint_delay, damage_gating,
                              paint_over, candidates, fullcolor=fullcolor)
    # static cost attribution (obs.perf): flops / HBM bytes / roofline-ms
    # recorded at compile time, so levers rank without a chip
    from .encoder import donate_argnums_for_backend
    return _perf.wrap_step(
        f"h264.{mode}_step[{width}x{stripe_h * n_stripes}"
        f"{'@444' if fullcolor else ''}]",
        jax.jit(step, donate_argnums=donate_argnums_for_backend(
            (1, 2, 3, 4, 5, 6, 7))))


# ---------------------------------------------------------------------------
# damage-proportional encoding (ROADMAP 4): dirty-band partial P encode.
# The per-frame device work scales with the dirty fraction: a tiny probe
# moves damage/age/paint decisions to the host, P frames dispatch a
# bucketed band step over just the rows that changed, clean rows of
# delivered stripes ship as host-precomputed all-skip slices
# (codecs.h264.p_skip_slice_rbsp), and idle frames skip the device
# entirely.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _jitted_row_damage_probe(width: int, height: int):
    """(R,) per-MB-row dirty flags — the one pre-dispatch sync the
    partial path pays. A single memory-bound pass over the frame (the
    same compare the stock step runs internally); its host-visible
    result is what lets band geometry, paint-over and the content
    classifier run before dispatch instead of on device."""
    R = height // 16

    def probe(frame, prev):
        return jnp.any((frame != prev).reshape(R, -1), axis=1)

    probe.__name__ = "h264_row_damage_probe"
    return _perf.wrap_step(f"h264.row_probe[{width}x{height}]",
                           jax.jit(probe))


def build_h264_band_step_fn(width: int, stripe_h: int, n_stripes: int,
                            band_rows: int, e_cap: int, w_cap: int,
                            out_cap: int, candidates: tuple = ((0, 0),),
                            fullcolor: bool = False, roi_qp: int = 0):
    """Pure band-partial P step: ``dynamic_slice`` a ``band_rows``-row
    band (start row is TRACED — one compiled program per bucket serves
    every band position) out of the frame and reference planes, run the
    stock plane-layout P encode over just those rows, and scatter the
    send-gated reconstruction back. Every per-row input (slice-header
    events, frame_num, qp) is sliced from the same full-frame arrays
    the stock step consumes, so a full-frame band is byte-identical to
    the stock P step by construction (tests/test_h264_bands.py).

    Motion candidates require ``band_rows`` to cover whole stripes: the
    encoder's search-window clamp must equal the decoder's picture-edge
    clamp, and the picture of a stripe stream is the stripe
    (ops/bands.py module docstring).

    signature: step(frame, prev, sent, fnum, ref_y, ref_u, ref_v,
                    qp_rows_band, send, row0, hdr_pay, hdr_nb)
    -> (data u8 (out_cap,), row_lens i32 (band_rows,), fnum_used (S,),
        sent (S,), fnum (S,), ref_y, ref_u, ref_v, prev_out, overflow)
    """
    rows_per_stripe = stripe_h // 16
    cdiv = 1 if fullcolor else 2
    use_motion = len(candidates) > 1
    if use_motion and band_rows % rows_per_stripe:
        raise ValueError("motion bands must cover whole stripes "
                         f"({band_rows} rows vs {rows_per_stripe}/stripe)")

    def step(frame, prev, sent, fnum, ref_y, ref_u, ref_v,
             qp_rows, send, row0, hdr_pay, hdr_nb):
        y0 = row0 * 16
        c0 = y0 // cdiv
        bh = band_rows * 16
        ch = bh // cdiv
        band = jax.lax.dynamic_slice(frame, (y0, 0, 0),
                                     (bh, width, 3))
        if fullcolor:
            from ..ops.h264_planes444 import (h264_encode_p_yuv444,
                                              rgb_to_yuv444)
            yf, uf, vf = rgb_to_yuv444(band)
            enc_p = h264_encode_p_yuv444
        else:
            yf, uf, vf = rgb_to_yuv420(band)
            enc_p = h264_encode_p_yuv
        rb_y = jax.lax.dynamic_slice(ref_y, (y0, 0), (bh, width))
        rb_u = jax.lax.dynamic_slice(ref_u, (c0, 0), (ch, width // cdiv))
        rb_v = jax.lax.dynamic_slice(ref_v, (c0, 0), (ch, width // cdiv))
        fn_band = jax.lax.dynamic_slice_in_dim(
            jnp.repeat(fnum, rows_per_stripe), row0, band_rows)
        hp = jax.lax.dynamic_slice_in_dim(hdr_pay, row0, band_rows)
        hn = jax.lax.dynamic_slice_in_dim(hdr_nb, row0, band_rows)
        kw = {}
        if roi_qp and not fullcolor:
            # ROI QP (ROADMAP 4/6 seam): freshly-damaged macroblocks
            # sharpen by ``roi_qp`` below the row base; settled ones keep
            # it (they mostly skip). Derived from the same frame/prev
            # planes — no extra state crosses frames.
            prev_band = jax.lax.dynamic_slice(prev, (y0, 0, 0),
                                              (bh, width, 3))
            mb_dirty = jnp.any(
                (band != prev_band).reshape(
                    band_rows, 16, width // 16, 48), axis=(1, 3))
            kw["qp_mb"] = jnp.clip(
                jnp.where(mb_dirty, qp_rows[:, None] - roi_qp,
                          qp_rows[:, None]), 8, 48)
        out, recon = enc_p(
            yf, uf, vf, rb_y, rb_u, rb_v, qp_rows, hp, hn, fn_band,
            e_cap, w_cap, candidates=candidates,
            stripe_rows=rows_per_stripe if use_motion else None, **kw)

        # reference advance, gated per DELIVERED stripe like the stock
        # step, scattered back over just the band rows
        sb = jax.lax.dynamic_slice_in_dim(
            jnp.repeat(send, rows_per_stripe), row0, band_rows)

        def scatter(ref, new, top, px_rows):
            old = jax.lax.dynamic_slice(ref, (top, 0), new.shape)
            gate = jnp.repeat(sb, px_rows)[:, None]
            return jax.lax.dynamic_update_slice(
                ref, jnp.where(gate, new, old), (top, 0))

        new_ry = scatter(ref_y, recon[0], y0, 16)
        new_ru = scatter(ref_u, recon[1], c0, 16 // cdiv)
        new_rv = scatter(ref_v, recon[2], c0, 16 // cdiv)
        fnum_used = jnp.bitwise_or(fnum, jnp.int32(0))   # pre-increment
        sent = sent + send.astype(jnp.int32)
        fnum = jnp.where(send, fnum + 1, fnum)

        sbytes, row_lens = words_to_bytes_device(out.words, out.total_bits,
                                                 pad_ones=False)
        buf = concat_stripe_bytes(sbytes, row_lens, out_cap)
        overflow = out.overflow | buf.overflow
        prev_out = jnp.bitwise_or(frame, jnp.uint8(0))
        return (buf.data, buf.byte_lens, fnum_used, sent, fnum,
                new_ry, new_ru, new_rv, prev_out, overflow)

    step.__name__ = f"h264_band{band_rows}_p_step"
    return step


# bounded LRU like _jitted_h264_step; one entry per band bucket
@functools.lru_cache(maxsize=64)
def _jitted_h264_band_step(width: int, stripe_h: int, n_stripes: int,
                           band_rows: int, e_cap: int, w_cap: int,
                           out_cap: int, candidates: tuple = ((0, 0),),
                           fullcolor: bool = False, roi_qp: int = 0):
    step = build_h264_band_step_fn(width, stripe_h, n_stripes, band_rows,
                                   e_cap, w_cap, out_cap, candidates,
                                   fullcolor=fullcolor, roi_qp=roi_qp)
    from .encoder import donate_argnums_for_backend
    # prev (arg 1) is only read by the ROI-QP dirty-mask path; without
    # roi the program prunes it, so donating it would invalidate the
    # session's buffer while reusing nothing (JAXPR-DONATION-ALIAS)
    donate = (1, 2, 3, 4, 5, 6) if (roi_qp and not fullcolor) \
        else (2, 3, 4, 5, 6)
    return _perf.wrap_step(
        f"h264.band{band_rows}.p_step[{width}x{stripe_h * n_stripes}"
        f"{'@444' if fullcolor else ''}"
        f"{f'+roi{roi_qp}' if roi_qp else ''}]",
        jax.jit(step, donate_argnums=donate_argnums_for_backend(donate)))


class H264EncoderSession:
    """Per-display H.264 encoder session (same lifecycle contract as
    JpegEncoderSession)."""

    def __init__(self, settings: CaptureSettings):
        self.settings = settings
        self.grid = plan_h264_grid(settings)
        g = self.grid
        self.n_rows = g.n_stripes * g.rows_per_stripe
        self.fullcolor = bool(settings.fullcolor)
        self._e_cap, self._w_cap, self._out_cap = h264_buffer_caps(
            g, self.fullcolor)
        self._i_step = self._build_step("i")
        self._p_step = self._build_step("p")
        self.frame_id = 0
        self._age = jnp.zeros((g.n_stripes,), jnp.int32)
        self._sent = jnp.zeros((g.n_stripes,), jnp.int32)
        self._fnum = jnp.zeros((g.n_stripes,), jnp.int32)
        self._prev = jnp.zeros((g.height, g.width, 3), jnp.uint8)
        cdiv = 1 if self.fullcolor else 2
        self._ref_y = jnp.zeros((g.height, g.width), jnp.uint8)
        self._ref_u = jnp.zeros((g.height // cdiv, g.width // cdiv),
                                jnp.uint8)
        self._ref_v = jnp.zeros((g.height // cdiv, g.width // cdiv),
                                jnp.uint8)
        self._force_after_drop = False
        # deep pipeline: encode() (capture thread) tests-and-clears the
        # flag while finalize (finalizer thread) sets it on overflow —
        # the lock keeps a concurrent set from being lost to the clear
        self._drop_lock = threading.Lock()
        self._cap_gen = 0   # buffer-growth generation (pipelined frames
        #                     encoded with stale caps must not re-grow)
        # per-stripe stream headers (cached; identical for every stripe)
        self._sps_pps = hcodec.write_sps(
            g.width, g.stripe_h,
            chroma_format=3 if self.fullcolor else 1) + hcodec.write_pps()
        # slice-header prefixes (idr_pic_id/qp are device events);
        # every stripe restarts first_mb at 0
        pay, nb = hcodec.slice_header_events(g.mb_w, g.rows_per_stripe)
        self._hdr_pay = jnp.asarray(np.tile(pay, (g.n_stripes, 1)))
        self._hdr_nb = jnp.asarray(np.tile(nb, (g.n_stripes, 1)))
        ppay, pnb = hcodec.p_slice_header_events(g.mb_w, g.rows_per_stripe)
        self._p_hdr_pay = jnp.asarray(np.tile(ppay, (g.n_stripes, 1)))
        self._p_hdr_nb = jnp.asarray(np.tile(pnb, (g.n_stripes, 1)))
        from .watermark import maybe_load
        # anchored against the VISIBLE size (padding is cropped client-side)
        self._watermark = maybe_load(settings, g.out_w, g.out_h)
        self.qp = int(np.clip(settings.video_crf, 8, 48))
        self.paint_qp = int(np.clip(
            settings.video_min_qp, 8, self.qp))
        # damage-proportional encoding (ROADMAP 4): P frames dispatch
        # over the dirty band only; damage/age/paint state moves to the
        # host (fed by the row probe), so the device age array is only
        # re-seeded before stock I dispatches. Requires damage gating —
        # without the tracker there is no damage signal to scale by.
        # Sharded sessions (split-frame parallelism) keep the stock
        # device-parallel steps: a single-device band step would forfeit
        # the N-way scaling under full-motion content, and the probe
        # would dispatch against sharded state the prewarmed program was
        # not built for — on-device damage gating already skips clean
        # stripes there (bands x stripes composition is future work).
        self._partial = bool(getattr(settings, "h264_partial_encode",
                                     False)) and settings.use_damage_gating \
            and int(getattr(self, "stripe_devices", 1)) <= 1
        self._host_age = np.zeros((g.n_stripes,), np.int64)
        vr = max(0, int(getattr(settings, "h264_motion_vrange", 0)))
        hr = max(0, int(getattr(settings, "h264_motion_hrange", 0)))
        self._band_candidates = scroll_candidates(vr, hr) if vr \
            else ((0, 0),)
        #: band quantum: whole stripes under motion search (window ==
        #: picture — ops/bands.py), MB rows for zero-MV replenishment
        self._band_granularity = g.rows_per_stripe \
            if len(self._band_candidates) > 1 else 1
        #: content-profile floor on the band bucket (set_content_profile)
        self._band_floor = 1
        self._roi_qp_bias = int(getattr(settings, "h264_roi_qp_bias", 4)) \
            if getattr(settings, "h264_roi_qp", False) else 0
        #: last-frame observability (obs/qoe pulls these per session)
        self.dirty_fraction = 1.0
        self.last_band_rows = self.n_rows

    def _build_step(self, mode: str):
        g, s = self.grid, self.settings
        vr = max(0, int(getattr(s, "h264_motion_vrange", 0)))
        hr = max(0, int(getattr(s, "h264_motion_hrange", 0)))
        cands = scroll_candidates(vr, hr) if (mode == "p" and vr) \
            else ((0, 0),)
        return _jitted_h264_step(mode, g.width, g.stripe_h, g.n_stripes,
                                 self._e_cap, self._w_cap, self._out_cap,
                                 s.paint_over_delay_frames,
                                 s.use_damage_gating, s.use_paint_over,
                                 candidates=cands,
                                 fullcolor=self.fullcolor)

    @property
    def visible_size(self) -> tuple[int, int]:
        return self.grid.out_w, self.grid.out_h

    # -- live tunables ------------------------------------------------------
    def update_quality(self, motion_q: int, paint_q: int | None = None):
        """JPEG-session-compatible knob: quality 1-100 maps inversely onto
        qp 48-8."""
        self.qp = int(np.clip(48 - (motion_q * 40) // 100, 8, 48))
        if paint_q is not None:
            self.paint_qp = int(np.clip(48 - (paint_q * 40) // 100, 8, 48))

    def set_qp(self, qp: int, paint_qp: int | None = None):
        self.qp = int(np.clip(qp, 8, 48))
        if paint_qp is not None:
            self.paint_qp = int(np.clip(paint_qp, 8, 48))

    # -- device step --------------------------------------------------------
    def encode(self, frame: jnp.ndarray, force: bool = False
               ) -> dict[str, Any]:
        """One adaptive I/P step. ``force`` (client keyframe request,
        keyframe_interval, post-overflow recovery) and the very first
        frame produce IDRs; every other frame is a P with on-device
        P_Skip for unchanged macroblocks. The mode must be decided HERE
        (not at finalize) so the device stream counters see it."""
        # fault point: device_error raises (the XLA-runtime-died class),
        # slow stalls the dispatch (compile-storm / saturated-queue class)
        _faults.registry.perturb("encoder.dispatch")
        # generation BEFORE the step refs (growth swaps steps-then-gen,
        # so the only possible tear is a benign stale-gen tag — never a
        # new-gen tag on a frame encoded with the old caps)
        cap_gen = self._cap_gen
        with self._drop_lock:
            if self._force_after_drop:
                self._force_after_drop = False
                force = True
        if self.frame_id == 0:
            # every stripe stream must OPEN with an IDR: an undamaged
            # stripe skipped here would otherwise debut as a P delta
            force = True
        intra = bool(force)
        if self._watermark is not None:
            frame = self._watermark.apply(frame)
        if self._partial:
            # damage-proportional path: probe -> host gating -> band
            # dispatch (or no dispatch at all on an idle frame)
            with _tracer.span("encode.dispatch"):
                return self._dispatch_partial(frame, intra, cap_gen)
        # the dispatch span covers the step call AND the async-copy kicks,
        # which on TPU only enqueue; the host waits for the step's
        # compute later, in finalize's readback.device_wait (backends
        # whose copy kick synchronizes, CPU, show the compute here)
        with _tracer.span("encode.dispatch"):
            return self._dispatch_stock(frame, intra, cap_gen)

    def _dispatch_stock(self, frame, intra: bool, cap_gen: int
                        ) -> dict[str, Any]:
        """The full-frame device step (always for I frames; for P frames
        only when the partial path is off)."""
        step = self._i_step if intra else self._p_step
        hdr_pay = self._hdr_pay if intra else self._p_hdr_pay
        hdr_nb = self._hdr_nb if intra else self._p_hdr_nb
        (data, row_lens, send, is_paint, age, sent, fnum,
         ry, ru, rv, prev_out, overflow) = step(
            frame, self._prev, self._age, self._sent, self._fnum,
            self._ref_y, self._ref_u, self._ref_v,
            jnp.int32(self.qp), jnp.int32(self.paint_qp),
            jnp.asarray(bool(intra)), hdr_pay, hdr_nb)
        # prev (and the rest of the state) was DONATED: the session's
        # reference is the step's output, never the caller's array
        self._prev = prev_out
        self._age = age
        self._sent = sent
        self._fnum = fnum
        self._ref_y, self._ref_u, self._ref_v = ry, ru, rv
        fid = self.frame_id
        self.frame_id = (self.frame_id + 1) & 0xFFFF
        # async-copy only the SMALL control arrays; the stream buffer
        # is fetched minimally at finalize (engine/readback.py) once
        # the row lengths are known
        for arr in (row_lens, send, is_paint, overflow):
            try:
                arr.copy_to_host_async()
            except Exception:
                pass
        return {"data": data, "lens": row_lens, "send": send,
                "is_paint": is_paint, "overflow": overflow, "frame_id": fid,
                "intra": intra, "cap_gen": cap_gen}

    def _dispatch_partial(self, frame, intra: bool, cap_gen: int
                          ) -> dict[str, Any]:
        """Damage-proportional dispatch (ROADMAP 4): the row probe's
        host-visible damage decides everything the stock step decided on
        device. Idle frames never touch the device; P frames run the
        band step over the smallest bucketed band covering the damage
        (paint-over stripes join the band at ``paint_qp``); I frames
        fall through to the stock I step with the device age re-seeded
        from the host mirror."""
        g, s = self.grid, self.settings
        rps = g.rows_per_stripe
        probe = _jitted_row_damage_probe(g.width, g.height)
        tl = _tracer.current()
        # the one host sync of the partial path — (R,) bools. The probe
        # reads the previous step's reference, so on a chip this sync
        # waits for that band step to finish: the dispatch-overlap window
        # a full-frame pipeline would have had is closed here (traced as
        # encode.probe_sync); PERF.md lever 5 documents why the trade wins
        # for desktop content (most frames become cheap or free).
        probed = probe(frame, self._prev)
        with _tracer.span("encode.probe_sync", tl, wait=True):
            dirty_rows = np.asarray(probed)
        stripe_dirty = dirty_rows.reshape(g.n_stripes, rps).any(axis=1)
        self.dirty_fraction = _dirty_fraction(dirty_rows)
        age_pre = self._host_age
        self._host_age = np.where(stripe_dirty, 0, age_pre + 1)
        if intra:
            # stock I step applies the same where(damage, 0, age+1)
            # update to the age it is handed, so seeding the PRE-update
            # host age keeps both mirrors identical
            self._age = jnp.asarray(
                np.minimum(age_pre, 2**31 - 1).astype(np.int32))
            return self._dispatch_stock(frame, True, cap_gen)
        paint = np.zeros_like(stripe_dirty)
        if s.use_paint_over and s.paint_over_delay_frames > 0:
            paint = self._host_age == s.paint_over_delay_frames
        send = stripe_dirty | paint
        fid = self.frame_id
        self.frame_id = (self.frame_id + 1) & 0xFFFF
        if not send.any():
            # idle frame: zero device work, zero readback. prev is
            # content-equal to this frame (no row changed), so the
            # damage reference stays valid without a copy.
            self.last_band_rows = 0
            return {"idle": True, "frame_id": fid, "intra": False,
                    "cap_gen": cap_gen, "send": send,
                    "overflow": np.asarray(False)}
        rows_needed = dirty_rows.copy()
        for i in np.nonzero(paint)[0]:
            # paint-over redelivers the WHOLE settled stripe at paint_qp
            rows_needed[i * rps:(i + 1) * rps] = True
        row0, band_rows = plan_band(
            rows_needed, granularity=self._band_granularity,
            floor_rows=self._band_floor)
        self.last_band_rows = band_rows
        qp_rows = np.full((self.n_rows,), self.qp, np.int32)
        for i in np.nonzero(paint)[0]:
            qp_rows[i * rps:(i + 1) * rps] = self.paint_qp
        step = self._band_step(band_rows)
        (data, row_lens, fnum_used, sent, fnum, ry, ru, rv, prev_out,
         overflow) = step(
            frame, self._prev, self._sent, self._fnum,
            self._ref_y, self._ref_u, self._ref_v,
            jnp.asarray(qp_rows[row0:row0 + band_rows]),
            jnp.asarray(send), jnp.int32(row0),
            self._p_hdr_pay, self._p_hdr_nb)
        self._prev = prev_out
        self._sent = sent
        self._fnum = fnum
        self._ref_y, self._ref_u, self._ref_v = ry, ru, rv
        for arr in (row_lens, fnum_used, overflow):
            try:
                arr.copy_to_host_async()
            except Exception:
                pass
        return {"data": data, "lens": row_lens, "send": send,
                "is_paint": paint, "overflow": overflow, "frame_id": fid,
                "intra": False, "cap_gen": cap_gen,
                "band": (int(row0), int(band_rows)),
                "fnum_used": fnum_used, "qp": int(self.qp),
                "dirty_fraction": self.dirty_fraction}

    def _band_step(self, band_rows: int):
        g = self.grid
        return _jitted_h264_band_step(
            g.width, g.stripe_h, g.n_stripes, band_rows, self._e_cap,
            self._w_cap, self._out_cap, self._band_candidates,
            fullcolor=self.fullcolor, roi_qp=self._roi_qp_bias)

    def set_content_profile(self, profile) -> None:
        """Apply a content profile (engine/content.py) to the band
        planner. A ``partial_encode=False`` profile (video/gaming)
        floors the band at the full frame instead of switching back to
        the stock step: the path stays uniform, the damage probe keeps
        the dirty-fraction signal live (so the classifier can switch
        back), and a full-frame band is byte-identical to the stock
        step anyway. qp bias via the usual set_qp path is the caller's
        job (the capture loop owns rate control)."""
        floor = max(1, int(getattr(profile, "band_floor_rows", 1)))
        if not getattr(profile, "partial_encode", True):
            floor = self.n_rows
        self._band_floor = floor

    # -- host tail ----------------------------------------------------------
    def finalize(self, out: dict[str, Any], force_all: bool = False
                 ) -> list[EncodedChunk]:
        """``force_all`` is ignored — forced refreshes are an encode()-time
        decision for this codec (idr parity lives on device). One
        ``packetize`` span for the frame."""
        del force_all
        got = self._readback(out)
        if got is None:
            return []
        tl, lane, deliver, chunk = got
        with _tracer.span("packetize", tl, lane=lane):
            return [chunk(i) for i in deliver]

    def finalize_stream(self, out: dict[str, Any], force_all: bool = False):
        """Stripe-granular finalize (deep pipeline, ROADMAP 2): the same
        one fetch per frame as :meth:`finalize`, then yields each
        stripe's access unit as it is assembled. Byte-identical to
        :meth:`finalize`; chain-gating semantics are untouched (chunks
        still carry is_idr per stripe and flow through the same relay
        row gates)."""
        del force_all
        got = self._readback(out)
        if got is None:
            return
        tl, lane, deliver, chunk = got
        for i in deliver:
            with _tracer.span("packetize", tl, lane=lane):
                c = chunk(i)
            yield c

    def _readback(self, out: dict[str, Any]):
        """The frame's readback, shared by finalize and finalize_stream:
        the control sync, then ONE device fetch. Stock frames (IDR,
        stock P) fetch the rows of the first to the last delivered
        stripe (engine/readback.fetch_stripe_bytes): clean stripes
        outside that range and the capacity padding never cross the host
        link, and a round trip per stripe would cost more than its
        bytes. Band frames fetch the band's prefix through the last band
        row of a delivered stripe (clean rows never existed on device).
        -> None when there is nothing to send (overflow, idle frame),
        else (tl, lane, the delivered stripe indices, chunk(i))."""
        g = self.grid
        # ONE readback span per frame, from the slot's submit (in-flight
        # time IS readback time) through the ring's admission and queue
        # (pipeline.admit, pipeline.queue), the control sync
        # (readback.device_wait) and the stream fetch (readback.copy) —
        # two fragments would double the stage count and skew percentiles
        tl = _tracer.lookup(self.settings.display_id, out["frame_id"])
        # per-slot lane (deep pipeline): occupancy attribution must see
        # WHICH in-flight slot ran, not just "the finalizer thread"
        lane = f"slot{out['slot']}" if "slot" in out else None
        rb_t0 = out.get("submitted_ns") or time.monotonic_ns()
        overflowed, idle, lens, send, intra = self._sync_control(out, tl,
                                                                 lane)
        band = out.get("band")
        data = None
        if not overflowed and not idle:
            starts = self._row_starts(out, lens)
            rps = g.rows_per_stripe
            deliver = [int(i) for i in np.nonzero(send)[0]]
            from .readback import fetch_stream_bytes, fetch_stripe_bytes
            if band is None:
                lo = int(starts[deliver[0] * rps])
                last = (deliver[-1] + 1) * rps - 1
                with _tracer.span("readback.copy", tl, lane=lane):
                    data = fetch_stripe_bytes(
                        out["data"], lo, int(starts[last] + lens[last]) - lo,
                        tl=tl, lane=lane)
                starts = starts - lo      # offsets into the fetched range
            else:
                last = self._band_last_row(send, band)
                if last is not None:
                    with _tracer.span("readback.copy", tl, lane=lane):
                        data = fetch_stream_bytes(
                            out["data"], int(starts[last] + lens[last]),
                            tl=tl, lane=lane)
        _tracer.record_span(tl, "encode.readback", rb_t0, lane=lane)
        if tl is not None and not overflowed and not idle:
            # how full the stream buffer ran: against the capacity the
            # frame was ENCODED with (its cap_gen), not today's
            _tracer.count(tl, "stream.bytes", int(lens.sum()))
            _tracer.count(tl, "stream.cap",
                          self._out_cap >> (self._cap_gen - out["cap_gen"]))
        if overflowed:
            self._handle_overflow(out, tl, lane)
            return None
        if idle:
            return None               # idle frame: fetched nothing at all
        return tl, lane, deliver, lambda i: self._chunk(
            out, i, self._stripe_row_bytes(out, i, data, starts, lens, band),
            intra)

    def _band_last_row(self, send, band) -> Optional[int]:
        """Band-frame fetch bound of :meth:`_readback`: the last
        BAND-LOCAL row belonging to a delivered stripe (None when no
        band row is — clean rows never existed on device)."""
        row0, brows = band
        rps = self.grid.rows_per_stripe
        in_band = np.nonzero(np.repeat(send, rps)[row0:row0 + brows])[0]
        return int(in_band[-1]) if in_band.size else None

    def _stripe_row_bytes(self, out: dict[str, Any], i: int, data,
                          starts, lens, band) -> list:
        """Stripe ``i``'s per-row slice RBSPs (``starts``: each row's
        offset in the fetched ``data``). Stock frames slice the fetched
        range; band frames stitch device-encoded band rows against
        host-built all-skip slices at the (byte-aligned) slice seams —
        the partial-encode assembly."""
        g = self.grid
        rps = g.rows_per_stripe
        if band is None:
            return [bytes(data[starts[r]:starts[r] + lens[r]])
                    for r in range(i * rps, (i + 1) * rps)]
        row0, brows = band
        fnum_used = np.asarray(out["fnum_used"])
        qp = int(out["qp"])
        rows = []
        for r in range(i * rps, (i + 1) * rps):
            if row0 <= r < row0 + brows:
                b = r - row0
                rows.append(bytes(data[starts[b]:starts[b] + lens[b]]))
            else:
                # clean row of a delivered stripe: all-skip slice, same
                # frame_num/qp the device wrote into the band rows
                rows.append(hcodec.p_skip_slice_rbsp(
                    (r % rps) * g.mb_w, g.mb_w, qp, int(fnum_used[i])))
        return rows

    def _row_starts(self, out: dict[str, Any], lens: np.ndarray
                    ) -> np.ndarray:
        """Absolute byte offset of each MB row inside ``out['data']``.
        Single-device sessions pack rows contiguously; the stripe-sharded
        session overrides this with per-shard byte regions."""
        del out
        return np.concatenate([[0], np.cumsum(lens[:-1])]).astype(np.int64)

    def _sync_control(self, out: dict[str, Any], tl=None, lane=None):
        """Control-array sync of :meth:`_readback` —
        the one device-sync point (``readback.device_wait`` on the
        frame's timeline ``tl``). -> (overflowed, idle, lens, send,
        intra)."""
        if out.get("idle"):
            # partial-path idle frame: nothing was dispatched at all
            return False, True, None, None, False
        with _tracer.span("readback.device_wait", tl, lane=lane,
                          wait=True):
            overflow = bool(np.asarray(out["overflow"]))
            if not overflow:
                lens = np.asarray(out["lens"])    # (R,) per MB row
                send = np.asarray(out["send"])
        if overflow:
            return True, True, None, None, True
        intra = out.get("intra", True)
        idle = not send.any()
        return False, idle, lens, send, intra

    def _chunk(self, out: dict[str, Any], i: int, rows: list,
               intra: bool) -> EncodedChunk:
        g = self.grid
        return EncodedChunk(
            payload=h264_stripe_payload(intra, rows, self._sps_pps),
            frame_id=out["frame_id"], stripe_y=i * g.stripe_h,
            width=g.width, height=g.stripe_h, is_idr=intra,
            output_mode="h264",
            seat_index=self.settings.seat_index,
            display_id=self.settings.display_id)

    def _handle_overflow(self, out: dict[str, Any], tl, lane) -> None:
        # grow once per episode: pipelined frames encoded with the old
        # caps also report overflow but must not re-double/re-jit
        if out["cap_gen"] == self._cap_gen:
            logger.warning("h264 overflow at frame %d; growing buffers",
                           out["frame_id"])
            with _tracer.span("encode.regrow", tl, lane=lane):
                self._w_cap *= 2
                self._out_cap *= 2
                # steps BEFORE gen (see encode()'s read order)
                self._i_step = self._build_step("i")
                self._p_step = self._build_step("p")
                self._cap_gen += 1
        with self._drop_lock:
            self._force_after_drop = True


# ---------------------------------------------------------------------------
# split-frame device parallelism (ROADMAP 2): one session's frame sharded
# across the mesh
# ---------------------------------------------------------------------------

# bounded LRU like _jitted_h264_step: stripe-device retargeting mints
# fresh keys; the pre-warm planner shares this factory cache
@functools.lru_cache(maxsize=32)
def _jitted_h264_sharded_step(mode: str, width: int, stripe_h: int,
                              n_stripes: int, e_cap: int, w_cap: int,
                              out_cap_local: int, paint_delay: int,
                              damage_gating: bool, paint_over: bool,
                              candidates: tuple = ((0, 0),),
                              fullcolor: bool = False, n_dev: int = 1,
                              device_ids: tuple = ()):
    """The single-seat step, shard_mapped over WHOLE stripes: each device
    runs the full damage-gated adaptive I/P step on its own band of
    ``n_stripes // n_dev`` stripes — per-stripe state, per-row slices,
    per-shard byte buffer. Stripes are independent streams and motion
    windows are stripe-bounded, so the compiled per-shard program is
    collective-free; the only cross-device structure is the stacked
    output layout the session's ``_row_starts`` understands."""
    import numpy as _np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    if n_stripes % n_dev:
        raise ValueError(
            f"{n_dev} stripe devices do not divide {n_stripes} stripes")
    local = build_h264_step_fn(
        mode, width, stripe_h, n_stripes // n_dev, e_cap, w_cap,
        out_cap_local, paint_delay, damage_gating, paint_over,
        candidates, fullcolor=fullcolor)
    # device_ids pins the mesh to the CALLER's device subset (part of
    # the cache key: sessions carved onto disjoint subsets must never
    # share a compiled step bound to devices 0..n-1)
    if device_ids:
        by_id = {d.id: d for d in jax.devices()}
        devs = [by_id[i] for i in device_ids]
    else:
        devs = jax.devices()[:n_dev]
    mesh = Mesh(_np.array(devs), ("stripe",))

    def local_wrapped(*args):
        outs = local(*args)
        return outs[:11] + (outs[11][None],)   # overflow gains a mesh dim

    s1 = P("stripe")
    p2 = P("stripe", None)
    p3 = P("stripe", None, None)
    sharded = shard_map(
        local_wrapped, mesh=mesh,
        in_specs=(p3, p3, s1, s1, s1, p2, p2, p2, P(), P(), P(), p2, p2),
        out_specs=(s1, s1, s1, s1, s1, s1, s1, p2, p2, p2, p3, s1))

    def step(*args):
        outs = sharded(*args)
        return outs[:11] + (jnp.any(outs[11]),)

    # profiler attribution: the stripes row, never the single-seat stem
    step.__name__ = f"h264_stripes{n_dev}_{mode}_step"
    from .encoder import donate_argnums_for_backend
    return _perf.wrap_step(
        f"h264.stripes{n_dev}.{mode}_step[{width}x{stripe_h * n_stripes}"
        f"{'@444' if fullcolor else ''}]",
        jax.jit(step, donate_argnums=donate_argnums_for_backend(
            (1, 2, 3, 4, 5, 6, 7))))


class StripeShardedH264Session(H264EncoderSession):
    """H.264 session with ONE frame's stripes sharded over
    ``settings.stripe_devices`` devices (split-frame device parallelism,
    ROADMAP 2 — the sequence-parallel inversion of the seats axis).

    Same lifecycle/finalize contract as :class:`H264EncoderSession` and
    BYTE-IDENTICAL chunk payloads (tests/test_stripes.py): sharding is a
    pure distribution axis. Each device's rows land in that shard's
    region of the output buffer; ``_row_starts`` gives their absolute
    offsets, so the finalizer's one range fetch per frame (which then
    carries the capacity padding between shard regions) slices them
    exactly."""

    def __init__(self, settings: CaptureSettings, devices=None):
        g = plan_h264_grid(settings)
        requested = max(1, int(getattr(settings, "stripe_devices", 1)))
        from ..parallel.stripes import stripe_mesh
        mesh = stripe_mesh(g.n_stripes, devices=devices,
                           requested=requested)
        #: the CHOSEN shard count (may be < requested — logged + gauged
        #: by stripe_mesh; bench records it in the ledger row)
        self.stripe_devices = int(mesh.devices.size)
        ids = tuple(int(d.id) for d in mesh.devices.flat)
        default = tuple(int(d.id)
                        for d in jax.devices()[:self.stripe_devices])
        # () = the default device prefix, so a default-device session
        # shares the factory cache entry the pre-warm planner built
        self._stripe_device_ids = () if ids == default else ids
        super().__init__(settings)

    def _build_step(self, mode: str):
        if self.stripe_devices <= 1:
            return super()._build_step(mode)
        g, s = self.grid, self.settings
        vr = max(0, int(getattr(s, "h264_motion_vrange", 0)))
        hr = max(0, int(getattr(s, "h264_motion_hrange", 0)))
        cands = scroll_candidates(vr, hr) if (mode == "p" and vr) \
            else ((0, 0),)
        return _jitted_h264_sharded_step(
            mode, g.width, g.stripe_h, g.n_stripes, self._e_cap,
            self._w_cap, self._out_cap_local, s.paint_over_delay_frames,
            s.use_damage_gating, s.use_paint_over, candidates=cands,
            fullcolor=self.fullcolor, n_dev=self.stripe_devices,
            device_ids=self._stripe_device_ids)

    @property
    def _out_cap_local(self) -> int:
        """Per-shard byte-buffer capacity (grows with _out_cap on
        overflow; ceil so n_dev * local >= out_cap)."""
        n = self.stripe_devices
        return -(-self._out_cap // n)

    def _row_starts(self, out, lens: np.ndarray) -> np.ndarray:
        n = self.stripe_devices
        if n <= 1:
            # (band outs can't reach here: __init__ gates the partial
            # path off for sharded sessions)
            return super()._row_starts(out, lens)
        # data is the stacked per-shard buffers; derive the local cap
        # from the ARRAY (pipelined frames may predate a growth episode)
        local_cap = int(out["data"].shape[0]) // n
        R = int(lens.shape[0])
        rl = R // n
        starts = np.zeros(R, np.int64)
        for d in range(n):
            seg = lens[d * rl:(d + 1) * rl]
            starts[d * rl:(d + 1) * rl] = d * local_cap + np.concatenate(
                [[0], np.cumsum(seg[:-1])])
        return starts
