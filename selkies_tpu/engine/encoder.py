"""JPEG stripe-encoder session: the device-resident encode step + host tail.

One ``JpegEncoderSession`` owns everything needed to turn device-resident
RGB frames into wire-ready JFIF stripes:

- a jitted, donated device step that (per frame, entirely on TPU):
  stripes the frame, diffs it against the previous frame for damage gating,
  advances the paint-over age state, selects motion vs paint-over quant
  tables per stripe, runs CSC + DCT + quant + Huffman bit-packing
  (ops/jpeg_pipeline + ops/jpeg_entropy), and byte-packs every stripe's
  scan into ONE fixed-capacity output buffer (ops/stripes);
- a host tail that slices the buffer, 0xFF-stuffs each scan, wraps JFIF
  headers, and emits :class:`EncodedChunk`s.

Damage gating and paint-over mirror the reference's knobs
(settings.py:560-585, SURVEY.md §2.2): unchanged stripes are not sent;
after ``paint_over_delay_frames`` static frames a stripe is re-sent once at
``paint_over_quality``. The decision lives ON DEVICE (carried state), so the
host never round-trips mid-frame.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..codecs import jpeg as jtab
from ..codecs.jpeg import stuff_ff_bytes
from ..obs import perf as _perf
from ..resilience import faults as _faults
from ..trace import tracer as _tracer
from ..ops.stripes import concat_stripe_bytes, words_to_bytes_device
from .types import CaptureSettings, EncodedChunk

logger = logging.getLogger("selkies_tpu.engine.encoder")


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def donate_argnums_for_backend(nums: tuple) -> tuple:
    """Buffer donation is a DEVICE-memory optimization: on HBM backends
    it lets N in-flight pipeline slots reuse the framebuffer/state
    allocations instead of multiplying them. On the host (cpu) backend
    XLA cannot alias these buffers (it warns 'Some donated buffers were
    not usable') AND the donation path forces SYNCHRONOUS dispatch —
    measured: a donated step call blocks for the full compute while the
    undonated call returns in ~0.1 ms — which would serialize the deep
    pipeline the donation is meant to serve. Donate only where HBM
    exists.

    ``SELKIES_FORCE_DONATION=1`` overrides the backend gate: the jaxpr
    analyzer (selkies_tpu/analysis/surface.py) traces the TPU-shaped
    donation surface on a CPU CI box to verify the declared donations
    actually alias in the compiled executable. Analysis-only — a CPU
    server must never set it (synchronous dispatch, see above)."""
    import os
    if os.environ.get("SELKIES_FORCE_DONATION") == "1":
        return nums
    import jax
    return nums if jax.default_backend() != "cpu" else ()


@dataclasses.dataclass
class _Grid:
    width: int              # padded width
    height: int             # padded height
    stripe_h: int
    n_stripes: int
    out_w: int              # visible (unpadded) width
    out_h: int


def _plan_grid(s: CaptureSettings) -> _Grid:
    block = 8 if s.fullcolor else 16
    stripe_h = max(block, _round_up(s.stripe_height, block))
    w = _round_up(s.capture_width, block)
    h = _round_up(s.capture_height, stripe_h)
    return _Grid(width=w, height=h, stripe_h=stripe_h,
                 n_stripes=h // stripe_h,
                 out_w=s.capture_width, out_h=s.capture_height)


plan_grid = _plan_grid  # public name for the parallel / h264 modules


def jpeg_buffer_caps(g: _Grid, fullcolor: bool) -> tuple[int, int, int]:
    """(e_cap, w_cap, out_cap) for a grid — shared by the single-seat
    session, the seat-sharded encoder and the pre-warm planner
    (selkies_tpu/prewarm/plan.py) so the sizing policy cannot diverge:
    a pre-warm that sized its buffers differently would compile a
    program no session ever calls. e_cap is the TRUE worst case (one
    event per coefficient slot: 1.5x pixels for 4:2:0, 3x for 4:4:4) so
    event overflow is impossible; only the word/output buffers can
    overflow, and those are growable."""
    stripe_px = g.stripe_h * g.width
    e_cap = stripe_px * (3 if fullcolor else 2)
    w_cap = stripe_px // 2
    out_cap = max(256 * 1024, stripe_px * g.n_stripes // 8)
    return e_cap, w_cap, out_cap


def build_step_fn(width: int, stripe_h: int, n_stripes: int, subsampling: str,
                  e_cap: int, w_cap: int, out_cap: int, paint_delay: int,
                  damage_gating: bool, paint_over: bool):
    """Build the (unjitted) per-frame encode step.

    Signature: step(frame u8 (H,W,3), prev u8 (H,W,3), age i32 (S,),
                    qy_motion/qc_motion/qy_paint/qc_paint f32 (64,))
    -> (data u8 (out_cap,), byte_lens i32 (S,), send bool (S,),
        is_paint bool (S,), age i32 (S,), prev_out u8 (H,W,3),
        overflow bool)

    ``prev`` and ``age`` are DONATED (deep-pipeline HBM discipline:
    in-flight slots reuse the previous generation's buffers instead of
    doubling HBM). The next frame's reference leaves the step as
    ``prev_out`` — a materialized copy of ``frame``, NOT the caller's
    array — so sources stay free to cache/reuse their frame buffers
    (static X11 grabs hand the same device array back every tick; a
    donated caller buffer would be deleted under them).

    The single-seat session jits this directly; the multi-seat encoder
    (selkies_tpu/parallel/seats.py) vmaps it and shard_maps the batch over
    a ``Mesh('seat')`` — per-seat encode has no cross-seat data flow, so
    the sharded step runs collective-free on ICI-connected devices.
    """
    from ..ops.jpeg_pipeline import jpeg_encode_device

    def encode_stripe(stripe, qy, qc):
        return jpeg_encode_device(stripe, qy, qc, subsampling=subsampling,
                                  e_cap=e_cap, w_cap=w_cap)

    def step(frame, prev, age, qy_m, qc_m, qy_p, qc_p):
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
        send = damage | is_paint
        qy = jnp.where(is_paint[:, None], qy_p[None, :], qy_m[None, :])
        qc = jnp.where(is_paint[:, None], qc_p[None, :], qc_m[None, :])
        packed = jax.vmap(encode_stripe)(stripes, qy, qc)
        sbytes, slens = words_to_bytes_device(packed.words, packed.total_bits)
        buf = concat_stripe_bytes(sbytes, slens, out_cap)
        overflow = jnp.any(packed.overflow) | buf.overflow
        # the next frame's reference MUST materialize (a plain `frame`
        # here would jaxpr-forward the caller's buffer out and donation
        # of prev next step would delete a source-cached array); XLA
        # reuses the donated prev allocation for it — zero HBM growth
        prev_out = jnp.bitwise_or(frame, jnp.uint8(0))
        return buf.data, buf.byte_lens, send, is_paint, age, prev_out, \
            overflow

    # the XLA module compiles as jit_jpeg_step: what a jax.profiler
    # capture's device lane shows, and what obs.perf's capture parser
    # matches step attribution against
    step.__name__ = "jpeg_step"
    return step


@functools.lru_cache(maxsize=32)
def _jitted_step(width: int, stripe_h: int, n_stripes: int, subsampling: str,
                 e_cap: int, w_cap: int, out_cap: int, paint_delay: int,
                 damage_gating: bool, paint_over: bool):
    """Compiled single-seat step; the HBM-resident ``prev`` framebuffer
    and ``age`` state are donated (graftlint donate-hint, consumed by the
    deep-pipeline rework): both are session-owned step outputs of the
    previous frame, so XLA reuses their allocations for this frame's
    outputs instead of doubling HBM per in-flight slot. Caller frame
    arrays are never donated — sources stay free to reuse their buffers.
    Wrapped for static cost attribution (obs.perf):
    flops / HBM bytes / roofline-ms are recorded at compile time.

    Bounded LRU (not ``functools.cache``): runtime geometry retargeting
    (ladder downscale, resizes, overflow growth) mints a fresh factory
    key per visit — an unbounded cache would pin every dead geometry's
    compiled executable forever. Live sessions hold their own reference;
    a re-built evicted geometry re-compiles through the persistent
    cache. The pre-warm planner (selkies_tpu/prewarm/plan.py) calls this
    SAME factory, so a warmed step is the object a later session gets."""
    return _perf.wrap_step(
        f"jpeg.step[{width}x{stripe_h * n_stripes}@{subsampling}]",
        jax.jit(build_step_fn(width, stripe_h, n_stripes, subsampling,
                              e_cap, w_cap, out_cap, paint_delay,
                              damage_gating, paint_over),
                donate_argnums=donate_argnums_for_backend((1, 2))))


class JpegEncoderSession:
    """Per-display encoder session (kept warm across client reconnects, like
    the reference's ``_persistent_capture_modules``, selkies.py:940-946)."""

    def __init__(self, settings: CaptureSettings):
        self.settings = settings
        self.grid = _plan_grid(settings)
        self.subsampling = "444" if settings.fullcolor else "420"
        g = self.grid
        # HBM is cheap; the transferred buffer is the tight one.
        self._e_cap, self._w_cap, self._out_cap = jpeg_buffer_caps(
            g, settings.fullcolor)
        self._step = self._build_step()
        self.frame_id = 0
        self._age = jnp.zeros((g.n_stripes,), jnp.int32)
        self._prev = jnp.zeros((g.height, g.width, 3), jnp.uint8)
        # set after a dropped (overflowed) frame: the client never saw it, so
        # damage diffs against it would leave stale stripes on glass forever.
        self._force_after_drop = False
        self._cap_gen = 0   # growth generation: pipelined frames encoded
        #                     with stale caps must not re-grow/re-jit
        from .watermark import maybe_load
        # anchor against the VISIBLE size: padded rows/cols are cropped
        # client-side, so a bottom/right anchor must not land there
        self._watermark = maybe_load(settings, g.out_w, g.out_h)
        self.update_quality(settings.jpeg_quality, settings.paint_over_quality)

    def _build_step(self):
        g, s = self.grid, self.settings
        return _jitted_step(g.width, g.stripe_h, g.n_stripes,
                            self.subsampling, self._e_cap, self._w_cap,
                            self._out_cap, s.paint_over_delay_frames,
                            s.use_damage_gating, s.use_paint_over)

    @property
    def visible_size(self) -> tuple[int, int]:
        """(width, height) the client should display; encode dims are
        block-padded beyond this and cropped client-side."""
        return self.grid.out_w, self.grid.out_h

    # -- live tunables ------------------------------------------------------
    def update_quality(self, motion_q: int, paint_q: int | None = None):
        self.settings.jpeg_quality = int(motion_q)
        if paint_q is not None:
            self.settings.paint_over_quality = int(paint_q)
        self._qy_m_np = jtab.scale_qtable(jtab.STD_LUMA_QUANT, self.settings.jpeg_quality)
        self._qc_m_np = jtab.scale_qtable(jtab.STD_CHROMA_QUANT, self.settings.jpeg_quality)
        self._qy_p_np = jtab.scale_qtable(jtab.STD_LUMA_QUANT, self.settings.paint_over_quality)
        self._qc_p_np = jtab.scale_qtable(jtab.STD_CHROMA_QUANT, self.settings.paint_over_quality)
        self._qy_m = jnp.asarray(self._qy_m_np, jnp.float32)
        self._qc_m = jnp.asarray(self._qc_m_np, jnp.float32)
        self._qy_p = jnp.asarray(self._qy_p_np, jnp.float32)
        self._qc_p = jnp.asarray(self._qc_p_np, jnp.float32)

    # -- device step --------------------------------------------------------
    def encode(self, frame: jnp.ndarray, force: bool = False
               ) -> dict[str, Any]:
        """Dispatch one encode step (non-blocking). ``frame`` must be a
        device array of shape (grid.height, grid.width, 3) uint8.
        ``force`` is a finalize-time decision for JPEG (all stripes are
        always in the buffer); accepted here for session-interface parity
        with the H.264 session."""
        del force
        # fault point: device_error raises (the XLA-runtime-died class),
        # slow stalls the dispatch (compile-storm / saturated-queue class)
        _faults.registry.perturb("encoder.dispatch")
        # generation BEFORE step: the finalizer thread's overflow-growth
        # swaps step-then-gen, so the only possible tear is (old gen,
        # new step) — a benign stale-gen tag — never a new-gen tag on a
        # frame encoded with the old caps (which would re-double)
        cap_gen = self._cap_gen
        if self._watermark is not None:
            frame = self._watermark.apply(frame)
        # the dispatch span covers the step call AND the async-copy kicks,
        # which on TPU only enqueue (about a millisecond a 1080p frame on
        # a v5e); the host waits for the step's compute in finalize's
        # readback.device_wait, and a stripe fetch's slice may queue
        # behind the NEXT frame's step (readback.slice_wait). Backends
        # whose copy kick synchronizes (CPU) show the compute here
        with _tracer.span("encode.dispatch"):
            data, lens, send, is_paint, age, prev_out, overflow = \
                self._step(frame, self._prev, self._age,
                           self._qy_m, self._qc_m, self._qy_p, self._qc_p)
            # prev/age were DONATED: the session's reference is the
            # step's output, never the caller's frame array
            self._prev = prev_out
            self._age = age
            fid = self.frame_id
            self.frame_id = (self.frame_id + 1) & 0xFFFF
            # kick off async readbacks of the SMALL control arrays so the
            # consumer doesn't eat the RTT; the stream buffer itself is
            # fetched minimally at finalize (engine/readback.py)
            for arr in (lens, send, is_paint, overflow):
                try:
                    arr.copy_to_host_async()
                except Exception:  # interpret/CPU may not support it
                    pass
        # Snapshot the quant tables that were live at DISPATCH time: finalize
        # runs PIPELINE_DEPTH frames later, and a quality change in between
        # must not make the JFIF DQT disagree with the tables the device
        # actually quantized with.
        return {"data": data, "lens": lens, "send": send,
                "is_paint": is_paint, "overflow": overflow, "frame_id": fid,
                "cap_gen": cap_gen,
                "qtabs": (self._qy_m_np, self._qc_m_np,
                          self._qy_p_np, self._qc_p_np)}

    # -- host tail ----------------------------------------------------------
    def _jfif_wrap(self, scan: bytes, paint: bool, qtabs) -> bytes:
        g = self.grid
        qy_m, qc_m, qy_p, qc_p = qtabs
        qy = qy_p if paint else qy_m
        qc = qc_p if paint else qc_m
        return jtab.assemble_jfif(g.stripe_h, g.width, scan, qy, qc,
                                  self.subsampling)

    def finalize(self, out: dict[str, Any], force_all: bool = False
                 ) -> list[EncodedChunk]:
        """Blocks on the async readback and produces wire-ready chunks
        (one ``packetize`` span for the frame)."""
        got = self._readback(out, force_all)
        if got is None:
            return []
        tl, lane, deliver, chunk = got
        with _tracer.span("packetize", tl, lane=lane):
            return [chunk(i) for i in deliver]

    def finalize_stream(self, out: dict[str, Any], force_all: bool = False):
        """Stripe-granular finalize (deep pipeline, ROADMAP 2): the same
        one fetch per frame as :meth:`finalize`, then each stripe is cut
        from the host copy, JFIF-wrapped and yielded, so the fanout ships
        the first stripe while later ones are still being wrapped.
        Byte-identical to :meth:`finalize` (tests pin it for both
        codecs)."""
        got = self._readback(out, force_all)
        if got is None:
            return
        tl, lane, deliver, chunk = got
        for i in deliver:
            with _tracer.span("packetize", tl, lane=lane):
                c = chunk(i)
            yield c

    def _readback(self, out: dict[str, Any], force_all: bool):
        """The frame's readback, shared by finalize and finalize_stream:
        the control sync, then ONE device fetch
        (engine/readback.fetch_stripe_bytes) over the delivered stripes,
        from the first one's start to the last one's end — clean stripes
        outside it and the capacity padding never cross the host link,
        and a round trip per stripe would cost more than its bytes.
        -> None when there is nothing to send (overflow, idle frame),
        else (tl, lane, the delivered stripe indices, chunk(i))."""
        g = self.grid
        # trace target: THIS frame's timeline, by id — never the current
        # dispatch context, which is up to pipeline_depth frames ahead.
        # ONE readback span per frame, from the slot's SUBMIT instant
        # (in-flight time is readback time, not bubble; serial calls
        # start here): the ring's admission and queue, the control sync
        # (readback.device_wait) and the stream fetch (readback.copy)
        tl = _tracer.lookup(self.settings.display_id, out["frame_id"])
        # per-slot lane (deep pipeline): occupancy attribution must see
        # WHICH in-flight slot ran, not just "the finalizer thread"
        lane = f"slot{out['slot']}" if "slot" in out else None
        rb_t0 = out.get("submitted_ns") or time.monotonic_ns()
        overflowed, idle, force_all, lens, send, is_paint = \
            self._sync_control(out, force_all, tl, lane)
        if not overflowed and not idle:
            deliver = range(g.n_stripes) if force_all \
                else [int(i) for i in np.nonzero(send)[0]]
            starts = np.concatenate([[0], np.cumsum(lens)])
            lo = int(starts[deliver[0]])
            hi = int(starts[deliver[-1]] + lens[deliver[-1]])
            from .readback import fetch_stripe_bytes
            with _tracer.span("readback.copy", tl, lane=lane):
                data = fetch_stripe_bytes(out["data"], lo, hi - lo,
                                          tl=tl, lane=lane)
            starts -= lo              # offsets into the fetched range
        _tracer.record_span(tl, "encode.readback", rb_t0, lane=lane)
        if overflowed:
            self._handle_overflow(out)
            return None
        if idle:
            return None               # idle frame: fetched nothing at all
        return tl, lane, deliver, lambda i: self._chunk(
            out, i, data[starts[i]:starts[i] + lens[i]], bool(is_paint[i]))

    def _sync_control(self, out: dict[str, Any], force_all: bool, tl=None,
                      lane=None):
        """Control-array sync of :meth:`_readback` —
        the one device-sync point (absorbs the step's compute stall;
        ``readback.device_wait`` on the frame's timeline ``tl``) and the
        force-after-drop promotion. -> (overflowed, idle, force_all,
        lens, send, is_paint)."""
        with _tracer.span("readback.device_wait", tl, lane=lane,
                          wait=True):
            overflow = bool(np.asarray(out["overflow"]))
            if not overflow:
                lens = np.asarray(out["lens"])
                send = np.asarray(out["send"])
                is_paint = np.asarray(out["is_paint"])
        if overflow:
            return True, True, force_all, None, None, None
        if self._force_after_drop:
            self._force_after_drop = False
            force_all = True
        idle = not (force_all or send.any())
        return False, idle, force_all, lens, send, is_paint

    def _chunk(self, out: dict[str, Any], i: int, raw: np.ndarray,
               paint: bool) -> EncodedChunk:
        g = self.grid
        scan = stuff_ff_bytes(raw)
        return EncodedChunk(
            payload=self._jfif_wrap(scan, paint, out["qtabs"]),
            frame_id=out["frame_id"], stripe_y=i * g.stripe_h,
            width=g.width, height=g.stripe_h, is_idr=True,
            output_mode="jpeg",
            seat_index=self.settings.seat_index,
            display_id=self.settings.display_id)

    def _handle_overflow(self, out: dict[str, Any]) -> None:
        """Event overflow is impossible (e_cap is worst-case), so this is
        a word/output buffer overflow: drop the frame, double the
        growable buffers, recompile ONCE per episode (pipelined frames
        encoded with the stale caps also overflow but must not
        re-double). The client never saw this frame, but _prev already
        advanced past it — force the next delivered frame to resend
        every stripe so damage gating can't freeze stale content."""
        if out.get("cap_gen", self._cap_gen) == self._cap_gen:
            logger.warning("encoder overflow at frame %d; raising "
                           "capacity", out["frame_id"])
            self._w_cap *= 2
            self._out_cap *= 2
            # step BEFORE gen (see encode()'s read order): a concurrent
            # encode must never observe the new generation with the old
            # step still in hand
            self._step = self._build_step()
            self._cap_gen += 1
        self._force_after_drop = True
