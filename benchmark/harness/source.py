"""The traffic's frame source, installed in the program's capture seam.

The desktop changes on its own clock: at each capture ``tick`` the source
takes the refresh index ``floor((now - t0) * refresh_hz)``, builds that
content on the host (one pass over the pre-built image, as an X11 grab
does) and uploads it with ``jax.device_put``. When the content has not
changed since the last call it returns the cached device array, as an
XDamage-gated grab does. Each tick's content key is logged: the wire's
frame id is the capture tick, and the latency and the check read this log.
"""

from __future__ import annotations

import threading
import time

import numpy as np

#: the check's controls a source can play (``TrafficSource.mode``)
MODES = (None, "stale", "grey", "halfchroma")


def half_chroma(rgb: np.ndarray) -> np.ndarray:
    """An RGB frame taken to BT.601 full-range YCbCr, Cb and Cr averaged
    over 2x2 pixels and repeated back to full size, and returned as RGB
    (rounded, clipped): the frame's colour at 4:2:0."""
    from reference.decoders import chroma_planes, half
    h, w = rgb.shape[:2]
    r, g, b = (rgb[..., i].astype(np.float64) for i in range(3))
    y = 0.299 * r + 0.587 * g + 0.114 * b
    cb, cr = (np.repeat(np.repeat(half(c), 2, axis=0), 2, axis=1)[:h, :w]
              - 128.0 for c in chroma_planes(rgb))
    out = np.stack([y + 1.402 * cr, y - 0.344136 * cb - 0.714136 * cr,
                    y + 1.772 * cb], axis=-1)
    return np.clip(np.round(out), 0, 255).astype(np.uint8)


class TickLog:
    """Per capture tick: the content key shown, and the phase (the
    desktop and mode the source played) it was sampled in."""

    def __init__(self):
        self._lock = threading.Lock()
        self.t0: float | None = None
        self.keys: dict[int, int] = {}
        self.phase: dict[int, int] = {}


class TrafficSource:
    """A ``FrameSource`` for ``selkies_tpu.engine.capture``: ``width``,
    ``height``, ``get_frame(tick)``, ``close()``.

    ``mode`` plays one of the check's controls, never in a benchmark run:
    ``stale``: each frame shows the content that was on the desktop one
    refresh before its logged content appeared, the least stale a frame
    can be; ``grey``: each frame shows its content's BT.601 luma in all
    three channels, as an encoder that drops the colour would send it;
    ``halfchroma``: each frame shows its content with Cb and Cr averaged
    over 2x2 pixels (``half_chroma``), the best a path that codes 4:2:0
    colour inside a 4:4:4 stream can show. ``switch`` moves to another
    desktop and mode (a new phase) between two ticks."""

    def __init__(self, desktop, log: TickLog, mode: str | None = None):
        import jax
        self._jax = jax
        self.width, self.height = desktop.width, desktop.height
        self._log = log
        self._lock = threading.Lock()
        self._phase = 0
        self.switch(desktop, mode, 0)
        with log._lock:
            if log.t0 is None:
                log.t0 = time.monotonic()

    def switch(self, desktop, mode: str | None, phase: int) -> None:
        if mode not in MODES:
            raise ValueError(f"no source mode {mode!r}")
        with self._lock:
            self._desktop, self._mode, self._phase = desktop, mode, phase
            self._key = self._frame = None

    def _render(self, d, key: int) -> np.ndarray:
        if self._mode == "stale" and key:
            return d.render(d.content_key(key - 1))
        frame = d.render(key)
        if self._mode == "grey":
            from reference.decoders import luma
            frame = np.repeat(luma(frame).astype(np.uint8)[..., None], 3,
                              axis=2)
        elif self._mode == "halfchroma":
            frame = half_chroma(frame)
        return frame

    def get_frame(self, tick: int):
        with self._lock:
            d, phase = self._desktop, self._phase
            r = int((time.monotonic() - self._log.t0) * d.hz)
            key = d.content_key(r)
            if key != self._key:
                self._frame = self._jax.device_put(self._render(d, key))
                self._key = key
            frame = self._frame
        with self._log._lock:
            self._log.keys[int(tick)] = key
            self._log.phase[int(tick)] = phase
        return frame

    def close(self) -> None:
        self._frame = None
