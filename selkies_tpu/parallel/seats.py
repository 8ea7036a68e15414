"""Multi-seat encoding over a TPU device mesh.

One *seat* = one remote desktop (framebuffer + encoder state). The
reference scales seats by running one container per desktop
(docs/component.md:181-187); here N seats are encoded by ONE sharded
program over a ``Mesh('seat')``: per-seat frames, damage state and quant
tables carry a leading seat axis sharded across devices, the per-seat step
is ``vmap``-ed and ``shard_map``-ed, and — because seats never exchange
data — the compiled program contains zero collectives: pure ICI-free
SPMD, each chip encoding its seat's desktop in lockstep.

Seats-per-device > 1 is allowed (the vmap runs the local batch); devices
must divide seats.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..codecs import jpeg as jtab
from ..codecs.jpeg import stuff_ff_bytes
from ..engine.encoder import build_step_fn, jpeg_buffer_caps, plan_grid
from ..engine.types import CaptureSettings, EncodedChunk
from ..trace import tracer as _tracer

from jax import shard_map

logger = logging.getLogger("selkies_tpu.parallel.seats")


def seat_mesh(n_seats: int, devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D ``Mesh('seat')`` using as many devices as divide ``n_seats``."""
    devs = list(devices) if devices is not None else list(jax.devices())
    n_dev = min(len(devs), n_seats)
    while n_seats % n_dev:
        n_dev -= 1
    return Mesh(np.array(devs[:n_dev]), ("seat",))


class MultiSeatEncoder:
    """N per-seat JPEG stripe encoders fused into one sharded device step.

    API mirrors :class:`~selkies_tpu.engine.encoder.JpegEncoderSession`
    with a leading seat axis: ``encode(frames)`` takes (S, H, W, 3) uint8,
    ``finalize`` returns a list of per-seat chunk lists.
    """

    def __init__(self, settings: CaptureSettings, n_seats: int,
                 devices: Optional[Sequence] = None,
                 mesh: Optional[Mesh] = None):
        if n_seats < 1:
            raise ValueError("n_seats must be >= 1")
        self.settings = settings
        self.n_seats = n_seats
        self.grid = plan_grid(settings)
        self.subsampling = "444" if settings.fullcolor else "420"
        g = self.grid
        # shared sizing policy (engine/encoder.py): the pre-warm planner
        # must compile with the exact caps a live encoder builds with
        self._e_cap, self._w_cap, self._out_cap = jpeg_buffer_caps(
            g, settings.fullcolor)

        self.mesh = mesh if mesh is not None else seat_mesh(n_seats, devices)
        if n_seats % self.mesh.devices.size:
            raise ValueError(
                f"{self.mesh.devices.size} devices do not divide "
                f"{n_seats} seats")
        self._spec = P("seat")
        self._sharding = NamedSharding(self.mesh, self._spec)
        self._step = self._build_step()

        self.frame_id = 0
        self._age = jax.device_put(
            np.zeros((n_seats, g.n_stripes), np.int32), self._sharding)
        self._force_after_drop = np.zeros((n_seats,), bool)
        self._cap_gen = 0   # growth generation: pipelined frames encoded
        #                     with stale caps must not re-grow/re-jit
        self.update_quality(settings.jpeg_quality,
                            settings.paint_over_quality)

    # ------------------------------------------------------------------ build
    def _build_step(self):
        g, s = self.grid, self.settings
        step = build_step_fn(g.width, g.stripe_h, g.n_stripes,
                             self.subsampling, self._e_cap, self._w_cap,
                             self._out_cap, s.paint_over_delay_frames,
                             s.use_damage_gating, s.use_paint_over)
        spec = self._spec
        sharded = shard_map(jax.vmap(step), mesh=self.mesh,
                            in_specs=(spec,) * 7, out_specs=(spec,) * 7)
        # the XLA module must compile as jit_jpeg_seatsN_step (NOT the
        # inner jpeg_step) so a profiler capture attributes multi-seat
        # device time to the seats row, and the single-seat stem
        # ("jpeg_step") can't claim these events
        sharded.__name__ = f"jpeg_seats{self.n_seats}_step"
        from ..obs import perf as _perf
        # prev + age donated (deep-pipeline HBM discipline): both are
        # session-owned outputs of the previous step, so N in-flight
        # slots reuse the same per-seat framebuffer allocations
        from ..engine.encoder import donate_argnums_for_backend
        return _perf.wrap_step(
            f"jpeg.seats{self.n_seats}_step[{g.width}x{g.height}"
            f"@{self.subsampling}]",
            jax.jit(sharded,
                    donate_argnums=donate_argnums_for_backend((1, 2))))

    # --------------------------------------------------------------- tunables
    def update_quality(self, motion_q: int, paint_q: int | None = None):
        self.settings.jpeg_quality = int(motion_q)
        if paint_q is not None:
            self.settings.paint_over_quality = int(paint_q)
        s, n = self.settings, self.n_seats
        self._qt_np = tuple(
            jtab.scale_qtable(base, q)
            for base, q in ((jtab.STD_LUMA_QUANT, s.jpeg_quality),
                            (jtab.STD_CHROMA_QUANT, s.jpeg_quality),
                            (jtab.STD_LUMA_QUANT, s.paint_over_quality),
                            (jtab.STD_CHROMA_QUANT, s.paint_over_quality)))
        # leading seat axis, replicated content, seat-sharded placement
        self._qt_dev = tuple(
            jax.device_put(np.tile(t.astype(np.float32), (n, 1)),
                           self._sharding)
            for t in self._qt_np)

    # ------------------------------------------------------------------ state
    @property
    def input_sharding(self) -> NamedSharding:
        """Sharding callers should ``device_put`` frame batches with."""
        return self._sharding

    def make_prev_buffer(self) -> jnp.ndarray:
        g = self.grid
        return jax.device_put(
            np.zeros((self.n_seats, g.height, g.width, 3), np.uint8),
            self._sharding)

    # ----------------------------------------------------------------- encode
    def encode(self, frames: jnp.ndarray,
               prev: Optional[jnp.ndarray] = None) -> dict[str, Any]:
        """Dispatch one multi-seat encode step (non-blocking).

        ``frames``: (n_seats, grid.height, grid.width, 3) uint8, ideally
        already placed with :attr:`input_sharding`. ``prev`` defaults to
        the internally-tracked previous batch; an explicitly-passed
        ``prev`` is DONATED to the step (its buffer is consumed).
        """
        if prev is None:
            prev = getattr(self, "_prev", None)
            if prev is None:
                prev = self.make_prev_buffer()
        # generation BEFORE step (growth swaps step-then-gen; the only
        # possible tear is a benign stale-gen tag)
        cap_gen = self._cap_gen
        # covers the step AND the async-copy kicks so backends whose copy
        # kick synchronizes (CPU) still attribute the compute wait here
        with _tracer.span("encode.dispatch"):
            data, lens, send, is_paint, age, prev_out, overflow = \
                self._step(frames, prev, self._age, *self._qt_dev)
            # prev/age were donated: the session's reference is the
            # step's materialized output, never the caller's batch
            self._prev = prev_out
            self._age = age
            fid = self.frame_id
            self.frame_id = (self.frame_id + 1) & 0xFFFF
            # small control arrays only; the stream buffer is fetched
            # minimally at finalize (engine/readback.py)
            for arr in (lens, send, is_paint, overflow):
                try:
                    arr.copy_to_host_async()
                except Exception:
                    pass
        return {"data": data, "lens": lens, "send": send,
                "is_paint": is_paint, "overflow": overflow, "frame_id": fid,
                "cap_gen": cap_gen, "qtabs": self._qt_np}

    # --------------------------------------------------------------- finalize
    def finalize(self, out: dict[str, Any], force_all: bool = False
                 ) -> list[list[EncodedChunk]]:
        """Blocks on readback; returns ``chunks[seat]`` lists."""
        g = self.grid
        # ONE readback span per frame (control-array sync + stream
        # fetch); fragments would double the stage count. Epoch: a
        # pipelined slot's in-flight time (submit -> bytes) IS readback.
        tl = _tracer.lookup(self.settings.display_id, out["frame_id"])
        rb_t0 = out.get("submitted_ns") or time.monotonic_ns()
        lens = np.asarray(out["lens"])        # (S, n_stripes)
        send = np.asarray(out["send"])
        is_paint = np.asarray(out["is_paint"])
        overflow = np.asarray(out["overflow"])  # (S,)
        # minimal readback (engine/readback.py), matching the
        # single-seat shape: per seat only bytes through the last
        # DELIVERED stripe count; all-idle frames fetch nothing.
        # Overflowed seats are skipped here, so the growth pass below
        # (which only flags THOSE seats) can run after the fetch.
        from ..engine.readback import fetch_stream_bytes
        total = 0
        for seat in range(self.n_seats):
            if overflow[seat]:
                continue
            if force_all or self._force_after_drop[seat]:
                total = max(total, int(lens[seat].sum()))
            elif send[seat].any():
                last = int(np.nonzero(send[seat])[0][-1])
                total = max(total, int(lens[seat, :last + 1].sum()))
        data = fetch_stream_bytes(out["data"], total) if total else None
        _tracer.record_span(tl, "encode.readback", rb_t0)
        qy_m, qc_m, qy_p, qc_p = out["qtabs"]

        if overflow.any():
            # same growth policy as the single-seat session: drop the
            # overflowed seats' frames, double the growable buffers ONCE
            # per episode (pipelined frames encoded with stale caps also
            # overflow but must not re-double), recompile, and force
            # their next delivered frame to full
            if out.get("cap_gen", self._cap_gen) == self._cap_gen:
                logger.warning(
                    "multi-seat overflow on seats %s; growing buffers",
                    np.nonzero(overflow)[0].tolist())
                self._w_cap *= 2
                self._out_cap *= 2
                # step BEFORE gen (see encode()'s read order)
                self._step = self._build_step()
                self._cap_gen += 1
            self._force_after_drop |= overflow

        results: list[list[EncodedChunk]] = []
        for seat in range(self.n_seats):
            if overflow[seat]:
                results.append([])
                continue
            force = force_all or self._force_after_drop[seat]
            self._force_after_drop[seat] = False
            # per-seat lane: each seat gets its own Perfetto track
            with _tracer.span("packetize", tl, lane=f"seat{seat}"):
                starts = np.concatenate([[0], np.cumsum(lens[seat])])
                chunks: list[EncodedChunk] = []
                for i in range(g.n_stripes):
                    if not (force or send[seat, i]):
                        continue
                    raw = data[seat, starts[i]:starts[i] + lens[seat, i]]
                    scan = stuff_ff_bytes(raw)
                    paint = bool(is_paint[seat, i])
                    qy = qy_p if paint else qy_m
                    qc = qc_p if paint else qc_m
                    payload = jtab.assemble_jfif(g.stripe_h, g.width, scan,
                                                 qy, qc, self.subsampling)
                    chunks.append(EncodedChunk(
                        payload=payload, frame_id=out["frame_id"],
                        stripe_y=i * g.stripe_h, width=g.width,
                        height=g.stripe_h, is_idr=True, output_mode="jpeg",
                        seat_index=seat, display_id=f"seat{seat}"))
            results.append(chunks)
        return results


def synthetic_seat_frames(enc: MultiSeatEncoder, tick: int) -> jnp.ndarray:
    """Per-seat animated test frames, generated ON the seat mesh: the
    synthetic pattern is vmapped over a per-seat phase so every seat shows
    distinct content (seat fan-out tests depend on that)."""
    from ..engine.sources import _synthetic_fn
    g = enc.grid
    fn = _synthetic_fn(g.height, g.width)
    phases = jax.device_put(
        np.arange(enc.n_seats, dtype=np.int32) * 37 + tick,
        enc.input_sharding)
    spec = enc._spec
    gen = jax.jit(shard_map(jax.vmap(fn), mesh=enc.mesh,
                            in_specs=(spec,), out_specs=spec))
    return gen(phases)
