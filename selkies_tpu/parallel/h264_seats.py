"""Multi-seat H.264 over the seat mesh — the flagship codec on the
flagship parallelism axis.

Same SPMD shape as the JPEG :class:`MultiSeatEncoder` (one desktop per
device slot, zero collectives): the adaptive-I/P device step of
``engine/h264_encoder.py`` gains a leading seat axis via
``shard_map(vmap(step))``. All per-seat codec state (damage ages, stream
counters, decoder-exact reference planes) lives sharded on device; only
the bitstream buffers cross the host link.

Mode policy: the step graph differs between I and P, so a batch encodes
in ONE mode — the first frame and any forced refresh run the IDR step
for every seat (IDRs are rare; a per-seat mode split would need both
programs per frame). Per-seat damage gating still keeps unforced seats'
refreshes cheap.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..codecs import h264 as hcodec
from ..engine.h264_encoder import (build_h264_step_fn, h264_buffer_caps,
                                   h264_stripe_payload, plan_h264_grid)
from ..engine.types import CaptureSettings, EncodedChunk
from ..ops.h264_encode import scroll_candidates
from ..trace import tracer as _tracer
from .seats import seat_mesh

from jax import shard_map

logger = logging.getLogger("selkies_tpu.parallel.h264_seats")


def build_seat_step(mode: str, settings: CaptureSettings, grid, mesh,
                    n_seats: int, e_cap: int, w_cap: int, out_cap: int,
                    candidates: tuple = ((0, 0),)):
    """The seat-sharded adaptive I/P step over ``mesh``: the single-seat
    step of ``engine/h264_encoder.py`` under ``shard_map(vmap(step))``.
    Every argument and result carries a leading seat axis sharded on
    ``"seat"``."""
    g, s = grid, settings
    step = build_h264_step_fn(
        mode, g.width, g.stripe_h, g.n_stripes, e_cap, w_cap, out_cap,
        s.paint_over_delay_frames, s.use_damage_gating, s.use_paint_over,
        candidates=candidates if mode == "p" else ((0, 0),))
    spec = P("seat")
    sharded = shard_map(jax.vmap(step), mesh=mesh, in_specs=(spec,) * 13,
                        out_specs=(spec,) * 12)
    # compile as jit_h264_seatsN_{i,p}_step so a profiler capture
    # attributes multi-seat device time to the seats row, distinct
    # from the single-seat h264_{i,p}_step stem
    sharded.__name__ = f"h264_seats{n_seats}_{mode}_step"
    from ..obs import perf as _perf
    # prev + codec state donated (deep-pipeline HBM discipline): all
    # are session-owned outputs of the previous step
    from ..engine.encoder import donate_argnums_for_backend
    return _perf.wrap_step(
        f"h264.seats{n_seats}_{mode}_step[{g.width}x{g.height}]",
        jax.jit(sharded, donate_argnums=donate_argnums_for_backend(
            (1, 2, 3, 4, 5, 6, 7))))


class MultiSeatH264Encoder:
    """N per-seat adaptive-I/P H.264 sessions fused into one sharded
    device step; API mirrors :class:`MultiSeatEncoder` (encode/finalize
    with a leading seat axis)."""

    def __init__(self, settings: CaptureSettings, n_seats: int,
                 devices: Optional[Sequence] = None, mesh=None):
        self.settings = settings
        self.n_seats = n_seats
        self.grid = plan_h264_grid(settings)
        g = self.grid
        self._e_cap, self._w_cap, self._out_cap = h264_buffer_caps(g)
        self._cap_gen = 0       # buffer-growth generation (pipelined
        #                         stale-cap frames must not re-grow)
        self.mesh = mesh if mesh is not None else seat_mesh(n_seats, devices)
        if n_seats % self.mesh.devices.size:
            raise ValueError(
                f"{self.mesh.devices.size} devices do not divide "
                f"{n_seats} seats")
        self._spec = P("seat")
        self._sharding = NamedSharding(self.mesh, self._spec)
        vr = max(0, int(getattr(settings, "h264_motion_vrange", 0)))
        hr = max(0, int(getattr(settings, "h264_motion_hrange", 0)))
        self._candidates = scroll_candidates(vr, hr) if vr else ((0, 0),)
        self._i_step = self._build("i")
        self._p_step = self._build("p")

        n, R = n_seats, g.n_stripes * g.rows_per_stripe
        self.frame_id = 0
        put = lambda a: jax.device_put(a, self._sharding)  # noqa: E731
        self._age = put(np.zeros((n, g.n_stripes), np.int32))
        self._sent = put(np.zeros((n, g.n_stripes), np.int32))
        self._fnum = put(np.zeros((n, g.n_stripes), np.int32))
        self._prev = put(np.zeros((n, g.height, g.width, 3), np.uint8))
        self._ref_y = put(np.zeros((n, g.height, g.width), np.uint8))
        self._ref_u = put(np.zeros((n, g.height // 2, g.width // 2),
                                   np.uint8))
        self._ref_v = put(np.zeros((n, g.height // 2, g.width // 2),
                                   np.uint8))
        self._force_after_drop = np.zeros((n,), bool)
        self._sps_pps = hcodec.write_sps(g.width, g.stripe_h) \
            + hcodec.write_pps()
        pay, nb = hcodec.slice_header_events(g.mb_w, g.rows_per_stripe)
        self._hdr_pay = put(np.tile(pay, (n, g.n_stripes, 1)))
        self._hdr_nb = put(np.tile(nb, (n, g.n_stripes, 1)))
        ppay, pnb = hcodec.p_slice_header_events(g.mb_w, g.rows_per_stripe)
        self._p_hdr_pay = put(np.tile(ppay, (n, g.n_stripes, 1)))
        self._p_hdr_nb = put(np.tile(pnb, (n, g.n_stripes, 1)))
        self.qp = int(np.clip(settings.video_crf, 8, 48))
        self.paint_qp = int(np.clip(settings.video_min_qp, 8, self.qp))
        del R

    def _build(self, mode: str):
        return build_seat_step(mode, self.settings, self.grid, self.mesh,
                               self.n_seats, self._e_cap, self._w_cap,
                               self._out_cap, self._candidates)

    # ------------------------------------------------------------------ state
    @property
    def input_sharding(self) -> NamedSharding:
        return self._sharding

    # ----------------------------------------------------------------- encode
    def encode(self, frames: jnp.ndarray, force: bool = False
               ) -> dict[str, Any]:
        """One sharded I/P step over all seats. ``force`` (or the first
        frame, or a post-overflow recovery on ANY seat) runs the IDR
        step batch-wide."""
        # generation BEFORE the step refs (growth swaps steps-then-gen;
        # the only possible tear is a benign stale-gen tag)
        cap_gen = self._cap_gen
        if self._force_after_drop.any():
            self._force_after_drop[:] = False
            force = True
        if self.frame_id == 0:
            force = True
        intra = bool(force)
        n = self.n_seats
        step = self._i_step if intra else self._p_step
        hdr_pay = self._hdr_pay if intra else self._p_hdr_pay
        hdr_nb = self._hdr_nb if intra else self._p_hdr_nb
        qp = jax.device_put(np.full((n,), self.qp, np.int32),
                            self._sharding)
        pqp = jax.device_put(np.full((n,), self.paint_qp, np.int32),
                             self._sharding)
        forces = jax.device_put(np.full((n,), bool(force)),
                                self._sharding)
        # covers the step AND the async-copy kicks so backends whose copy
        # kick synchronizes (CPU) still attribute the compute wait here
        with _tracer.span("encode.dispatch"):
            (data, row_lens, send, is_paint, age, sent, fnum,
             ry, ru, rv, prev_out, overflow) = step(
                frames, self._prev, self._age, self._sent, self._fnum,
                self._ref_y, self._ref_u, self._ref_v,
                qp, pqp, forces, hdr_pay, hdr_nb)
            # prev (and codec state) donated: keep the step's output
            self._prev = prev_out
            self._age = age
            self._sent = sent
            self._fnum = fnum
            self._ref_y, self._ref_u, self._ref_v = ry, ru, rv
            fid = self.frame_id
            self.frame_id = (self.frame_id + 1) & 0xFFFF
            # small control arrays only; the stream buffer is fetched
            # minimally at finalize (engine/readback.py)
            for arr in (row_lens, send, is_paint, overflow):
                try:
                    arr.copy_to_host_async()
                except Exception:
                    pass
        return {"data": data, "lens": row_lens, "send": send,
                "overflow": overflow, "frame_id": fid, "intra": intra,
                "cap_gen": cap_gen}

    # --------------------------------------------------------------- finalize
    def finalize(self, out: dict[str, Any], force_all: bool = False
                 ) -> list[list[EncodedChunk]]:
        del force_all                       # encode()-time decision
        g = self.grid
        tl = _tracer.lookup(self.settings.display_id, out["frame_id"])
        rb_t0 = out.get("submitted_ns") or time.monotonic_ns()
        lens = np.asarray(out["lens"])      # (S, R)
        send = np.asarray(out["send"])      # (S, n_stripes)
        overflow = np.asarray(out["overflow"])   # (S,)
        # minimal readback (engine/readback.py), matching the
        # single-seat shape: per seat only rows through the last SENT
        # stripe count; all-idle frames fetch nothing
        from ..engine.readback import fetch_stream_bytes
        rps_ = g.rows_per_stripe
        total = 0
        for seat in range(self.n_seats):
            if overflow[seat] or not send[seat].any():
                continue
            last_row = (int(np.nonzero(send[seat])[0][-1]) + 1) * rps_
            total = max(total, int(lens[seat, :last_row].sum()))
        data = fetch_stream_bytes(out["data"], total) if total else None
        _tracer.record_span(tl, "encode.readback", rb_t0)
        intra = out["intra"]
        if overflow.any():
            if out["cap_gen"] == self._cap_gen:
                logger.warning(
                    "multi-seat h264 overflow on seats %s; growing",
                    np.nonzero(overflow)[0].tolist())
                self._w_cap *= 2
                self._out_cap *= 2
                # steps BEFORE gen (see encode()'s read order)
                self._i_step = self._build("i")
                self._p_step = self._build("p")
                self._cap_gen += 1
            self._force_after_drop |= overflow
        results: list[list[EncodedChunk]] = []
        rps = g.rows_per_stripe
        for seat in range(self.n_seats):
            if overflow[seat]:
                results.append([])
                continue
            # per-seat lane: each seat gets its own Perfetto track
            with _tracer.span("packetize", tl, lane=f"seat{seat}"):
                starts = np.concatenate([[0], np.cumsum(lens[seat])])
                chunks: list[EncodedChunk] = []
                for i in range(g.n_stripes):
                    if not send[seat, i]:
                        continue
                    rows = [bytes(data[seat, starts[r]:starts[r]
                                       + lens[seat, r]])
                            for r in range(i * rps, (i + 1) * rps)]
                    payload = h264_stripe_payload(intra, rows,
                                                  self._sps_pps)
                    chunks.append(EncodedChunk(
                        payload=payload, frame_id=out["frame_id"],
                        stripe_y=i * g.stripe_h, width=g.width,
                        height=g.stripe_h, is_idr=intra,
                        output_mode="h264", seat_index=seat,
                        display_id=f"seat{seat}"))
            results.append(chunks)
        return results
