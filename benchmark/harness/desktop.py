"""The general desktop generator: one traffic mix is one JSON scene file.

A scene is a stack of optional layers, drawn in this order:

- ``background``: ``texture`` (a seeded multi-octave noise texture with
  hard-edged shapes, larger than the screen, panned by a velocity that is
  redrawn every ``redraw_s``) or ``wallpaper`` (a static seeded desktop);
- ``sprites``: ``count`` seeded images moving on their own bouncing paths;
- ``text_pane``: an editor pane of glyph cells from a seeded atlas, typed
  into at a seeded keystroke schedule, with a blinking caret; a line wrap
  scrolls the pane by one line.

The desktop changes on its own clock at ``refresh_hz``. ``content_key(r)``
is the refresh index at which the content shown at refresh ``r`` first
appeared (``r`` itself for anything that moves), and ``render(r)`` is that
content as an ``(H, W, 3) uint8`` array: a pure function of (scene, seed,
r). Every seed gets the same texture, sprites, and sets of sizes, speeds
and keystroke gaps, in another order and from another place, so that the
work does not change with the seed.
"""

from __future__ import annotations

import functools
import math

import numpy as np

#: seconds of schedule generated up front (set-up, window and grace fit)
HORIZON_S = 900


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), *salt])


def _noise_octave(key, h: int, w: int, cell: int):
    """Colour value noise in [-0.5, 0.5) with a lattice every ``cell``
    px, smoothstep-bilinear, separable."""
    import jax
    import jax.numpy as jnp
    g = jax.random.uniform(key, (h // cell + 2, w // cell + 2, 3)) - 0.5
    ys = jnp.arange(h, dtype=jnp.float32) / cell
    xs = jnp.arange(w, dtype=jnp.float32) / cell
    y0, x0 = ys.astype(jnp.int32), xs.astype(jnp.int32)
    fy, fx = ys - y0, xs - x0
    fy = (fy * fy * (3 - 2 * fy))[:, None, None]
    fx = (fx * fx * (3 - 2 * fx))[None, :, None]
    gx = g[:, x0] * (1 - fx) + g[:, x0 + 1] * fx          # (gh, w, 3)
    return gx[y0] * (1 - fy) + gx[y0 + 1] * fy


@functools.lru_cache(maxsize=8)
def _texture_fn(h: int, w: int, octaves: tuple, blobs: tuple, grain: float):
    """One jitted program that makes a texture from a key: multi-octave
    colour noise, fine grain, and hard-edged blobs (thresholded coarse
    noise) that shift the colour without flattening the grain, so a
    shift by one pixel changes every macroblock."""
    import jax
    import jax.numpy as jnp

    def make(key):
        keys = jax.random.split(key, len(octaves) + 2 * len(blobs) + 1)
        img = jnp.zeros((h, w, 3), jnp.float32)
        for i, (cell, amp) in enumerate(octaves):
            img += amp * _noise_octave(keys[i], h, w, cell)
        img += grain * (jax.random.uniform(keys[len(octaves)], (h, w, 1))
                        - 0.5)
        for j, (cell, level, contrast) in enumerate(blobs):
            k_shape, k_col = keys[len(octaves) + 1 + 2 * j:
                                  len(octaves) + 3 + 2 * j]
            field = _noise_octave(k_shape, h, w, cell)[..., :1]
            colour = (jax.random.uniform(k_col, (3,)) - 0.5) * 2 * contrast
            img += jnp.where(field > level, colour, 0.0)
        return jnp.clip(128 + img * 255, 0, 255).astype(jnp.uint8)

    return jax.jit(make)


def _texture(seed: int, salt: int, h: int, w: int, spec: dict) -> np.ndarray:
    """A seeded texture, made on the default device in one call."""
    import jax
    key = jax.random.key(int(_rng(seed, salt).integers(2**31)))
    fn = _texture_fn(h, w, tuple(zip(spec["octaves_px"], spec["amplitudes"])),
                     tuple(tuple(b) for b in spec.get("blobs", ())),
                     float(spec.get("grain", 0.0)))
    return np.array(fn(key))


def _bounce(p: np.ndarray, span: int) -> np.ndarray:
    """Reflect an unbounded coordinate into [0, span]."""
    if span <= 0:
        return np.zeros_like(p)
    m = np.mod(p, 2 * span)
    return np.where(m > span, 2 * span - m, m)


def _permuted(template: np.ndarray, seed: int, salt: int, n: int
              ) -> np.ndarray:
    """``n`` entries cycling through the template set, each cycle in a
    seeded order: the same sizes for every seed, another order."""
    rng = _rng(seed, salt)
    k = len(template)
    cycles = [template[rng.permutation(k)] for _ in range(-(-n // k))]
    return np.concatenate(cycles)[:n]


class _Pan:
    """A viewport over the texture whose velocity is redrawn every
    ``redraw_s``, cycling through the scene's set of velocities in one
    seeded order: the schedule repeats every ``len(velocities) *
    redraw_s``, so a window of a whole number of cycles holds each
    velocity equally often whatever its phase."""

    def __init__(self, spec: dict, hz: float, seed: int, span_x: int,
                 span_y: int):
        vel = np.array(spec["velocities"], np.int64)
        self.period = max(1, int(round(spec["redraw_s"] * hz)))
        n_seg = int(HORIZON_S * hz) // self.period + 2
        cycle = vel[_rng(seed, 12).permutation(len(vel))]
        v = np.tile(cycle, (-(-n_seg // len(vel)), 1))[:n_seg]
        self.v = v
        self.start = np.concatenate(
            [np.zeros((1, 2), np.int64),
             np.cumsum(v * self.period, axis=0)])[:-1]
        o = _rng(seed, 13)
        self.origin = np.array([o.integers(0, span_x + 1),
                                o.integers(0, span_y + 1)], np.int64)
        self.span = (span_x, span_y)

    def at(self, r: int) -> tuple[int, int]:
        seg, f = divmod(int(r), self.period)
        p = self.origin + self.start[seg] + f * self.v[seg]
        return (int(_bounce(p[0], self.span[0])),
                int(_bounce(p[1], self.span[1])))


class _Sprites:
    def __init__(self, spec: dict, seed: int, width: int, height: int):
        t = _rng(spec.get("template_seed", 0), 21)
        n = spec["count"]
        lo, hi = spec["size_px"]
        sizes = np.linspace(lo, hi, n).astype(np.int64)
        vmin, vmax = spec["min_speed"], spec["max_speed"]
        speeds = []
        while len(speeds) < n:
            v = t.integers(-vmax, vmax + 1, size=2)
            if min(abs(v[0]), abs(v[1])) >= vmin:
                speeds.append(v)
        rng = _rng(seed, 22)
        order = rng.permutation(n)
        sheet = _texture(spec["template_seed"], 3, hi, hi * n,
                         spec["texture"])
        self.items = []
        for i in range(n):
            s = int(sizes[order[i]])
            img = sheet[:s, i * hi:i * hi + s]
            yy, xx = np.mgrid[0:s, 0:s]
            if i % 2:
                mask = (yy - s / 2) ** 2 + (xx - s / 2) ** 2 <= (s / 2) ** 2
            else:
                mask = np.ones((s, s), bool)
            origin = np.array([rng.integers(0, width - s + 1),
                               rng.integers(0, height - s + 1)], np.int64)
            self.items.append((img, mask, origin, np.array(speeds[i]),
                               (width - s, height - s)))

    def draw(self, frame: np.ndarray, r: int) -> None:
        for img, mask, origin, v, span in self.items:
            x = int(_bounce(origin[0] + r * v[0], span[0]))
            y = int(_bounce(origin[1] + r * v[1], span[1]))
            s = img.shape[0]
            dst = frame[y:y + s, x:x + s]
            np.copyto(dst, img, where=mask[..., None])


def _glyphs(rng, n: int, ch: int, cw: int) -> np.ndarray:
    """``n`` stroke glyphs, (n, ch, cw) bool: each a seeded set of 3 to 6
    one-pixel strokes of a segment font, in a box that leaves letter
    spacing, an ascender gap and a line gap. Glyph 0 is the space."""
    x0, x1, y0, y1 = 1, cw - 3, 3, ch - 3
    ym = (y0 + y1) // 2
    xm = (x0 + x1) // 2
    strokes = [((y0, y0 + 1), (x0, x1 + 1)), ((ym, ym + 1), (x0, x1 + 1)),
               ((y1, y1 + 1), (x0, x1 + 1)), ((y0, ym + 1), (x0, x0 + 1)),
               ((ym, y1 + 1), (x0, x0 + 1)), ((y0, ym + 1), (x1, x1 + 1)),
               ((ym, y1 + 1), (x1, x1 + 1)), ((y0, ym + 1), (xm, xm + 1)),
               ((ym, y1 + 1), (xm, xm + 1))]
    out = np.zeros((n, ch, cw), bool)
    for g in range(1, n):
        pick = rng.choice(len(strokes), size=rng.integers(3, 7),
                          replace=False)
        for k in pick:
            (a, b), (c, d) = strokes[k]
            out[g, a:b, c:d] = True
    return out


class _TextPane:
    """An editor pane typed into at a seeded keystroke schedule."""

    def __init__(self, spec: dict, hz: float, seed: int):
        self.x, self.y = spec["x"], spec["y"]
        self.cols, self.rows = spec["cols"], spec["rows"]
        self.cw, self.ch = spec["cell"]
        self.fg = np.array(spec["fg"], np.uint8)
        self.bg = np.array(spec["bg"], np.uint8)
        self.caret_px = spec["caret_px"]
        bits = _glyphs(_rng(seed, 31), spec["glyphs"], self.ch, self.cw)
        self.atlas = np.where(bits[..., None], self.fg, self.bg)
        t = _rng(spec.get("template_seed", 0), 32)
        mean_gap = 1.0 / spec["chars_per_s"]
        sigma = spec["gap_sigma"]
        gaps = t.lognormal(math.log(mean_gap) - sigma ** 2 / 2, sigma,
                           size=spec["gap_set"])
        n_keys = int(HORIZON_S * spec["chars_per_s"] * 1.5)
        gaps = _permuted(gaps, seed, 33, n_keys)
        times = np.cumsum(gaps)
        #: refresh index at which keystroke k (1-based count) shows
        self.key_refresh = np.ceil(times * hz).astype(np.int64)
        self.caret_half = max(1, int(round(spec["caret_period_s"] / 2 * hz)))
        # the document: seeded words of glyphs separated by spaces
        doc_rng = _rng(seed, 34)
        n_chars = (self.rows + 2) * self.cols + n_keys + 1
        doc = doc_rng.integers(1, spec["glyphs"], size=n_chars)
        word_end = doc_rng.random(n_chars) < 1 / 6
        doc[word_end] = 0
        self.doc = doc
        #: the cursor sits on the last pane line, this far in, at start
        self.pos0 = (self.rows - 1) * self.cols + spec["start_col"]

    def keys_at(self, r: int) -> int:
        return int(np.searchsorted(self.key_refresh, r, side="right"))

    def _last_key(self, r: int) -> int:
        k = self.keys_at(r)
        return int(self.key_refresh[k - 1]) if k else 0

    def content_key(self, r: int) -> int:
        """The caret's blink restarts, shown, at every keystroke."""
        last = self._last_key(r)
        return last + (r - last) // self.caret_half * self.caret_half

    def draw(self, frame: np.ndarray, r: int) -> None:
        pos = self.pos0 + self.keys_at(r)
        line = pos // self.cols
        top = line - self.rows + 1
        cells = self.doc[top * self.cols:(line + 1) * self.cols].copy()
        cells[pos - top * self.cols:] = 0           # not typed yet
        grid = self.atlas[cells.reshape(self.rows, self.cols)]
        pane = grid.transpose(0, 2, 1, 3, 4).reshape(
            self.rows * self.ch, self.cols * self.cw, 3)
        frame[self.y:self.y + pane.shape[0],
              self.x:self.x + pane.shape[1]] = pane
        if (r - self._last_key(r)) // self.caret_half % 2 == 0:
            cy = self.y + (line - top) * self.ch
            cx = self.x + (pos % self.cols) * self.cw
            frame[cy:cy + self.ch, cx:cx + self.caret_px] = self.fg


class Desktop:
    """A traffic mix's scene at one geometry and seed."""

    def __init__(self, scene: dict, width: int, height: int, seed: int):
        self.width, self.height = width, height
        self.hz = float(scene["refresh_hz"])
        bg = scene["background"]
        self._pan = None
        if bg["kind"] == "texture":
            tw, th = bg["size"]
            # one texture for every seed: the seed picks the path over it
            self._base = _texture(bg["template_seed"], 1, th, tw, bg)
            self._pan = _Pan(bg["pan"], self.hz, seed, tw - width,
                             th - height)
        elif bg["kind"] == "wallpaper":
            self._base = _texture(seed, 2, height, width, bg)
            for x, y, w, h, rgb in bg.get("rects", []):
                self._base[y:y + h, x:x + w] = rgb
        else:
            raise ValueError(f"unknown background kind {bg['kind']!r}")
        self._sprites = _Sprites(scene["sprites"], seed, width, height) \
            if scene.get("sprites") else None
        self._text = _TextPane(scene["text_pane"], self.hz, seed) \
            if scene.get("text_pane") else None
        #: True when the content changes at every refresh
        self.moving = self._pan is not None or self._sprites is not None

    def content_key(self, r: int) -> int:
        """The refresh at which the content shown at refresh ``r``
        appeared; equal keys mean equal frames."""
        if self.moving or self._text is None:
            return int(r) if self.moving else 0
        return self._text.content_key(r)

    def render(self, r: int) -> np.ndarray:
        r = self.content_key(r)
        if self._pan is not None:
            x, y = self._pan.at(r)
            frame = self._base[y:y + self.height, x:x + self.width].copy()
        else:
            frame = self._base.copy()
        if self._sprites is not None:
            self._sprites.draw(frame, r)
        if self._text is not None:
            self._text.draw(frame, r)
        return frame
