"""The check's plain reference: independent decoders and colour maths.

Nothing here imports the program. H.264 access units go to libavcodec
through ``avdec.c`` (built on first use into the benchmark's own cache
directory); JPEG stripes go to PIL. The source's RGB frames are turned
into what a conforming decoder should show with plain numpy: BT.601
full-range YCbCr, as the encoder's VUI and JFIF signal, at the chroma
sampling the configuration promises: ``"420"``, the chroma planes
averaged over 2x2 pixels, or ``"444"``, every plane at full size.
Decoded pictures are compared in the same form, and a picture of the
other sampling does not decode.
"""

from __future__ import annotations

import ctypes
import io
import os
import subprocess
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
BUILD = HERE.parent / ".cache" / "build"
#: the chroma samplings a configuration can promise
SAMPLINGS = ("420", "444")


def _library() -> ctypes.CDLL:
    so = BUILD / "avdec.so"
    src = HERE / "avdec.c"
    if not so.exists() or so.stat().st_mtime < src.stat().st_mtime:
        BUILD.mkdir(parents=True, exist_ok=True)
        tmp = so.with_suffix(f".{os.getpid()}.so")
        subprocess.run(["gcc", "-O2", "-shared", "-fPIC", "-o", str(tmp),
                        str(src), "-lavcodec", "-lavutil"], check=True,
                       capture_output=True)
        tmp.replace(so)
    lib = ctypes.CDLL(str(so))
    lib.bd_open.restype = ctypes.c_void_p
    lib.bd_open.argtypes = []
    buf = ctypes.POINTER(ctypes.c_uint8)
    lib.bd_decode.restype = ctypes.c_int
    lib.bd_decode.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
                              ctypes.c_int, ctypes.c_int, ctypes.c_int,
                              buf, buf, buf]
    lib.bd_close.restype = None
    lib.bd_close.argtypes = [ctypes.c_void_p]
    return lib


class H264Stream:
    """One H.264 stream (one stripe position), decoded access unit by
    access unit. ``decode`` returns the picture's (Y, Cb, Cr) planes, or
    None when the unit did not decode to a picture of the stream's size
    at the ``chroma`` sampling."""

    def __init__(self, width: int, height: int, chroma: str):
        if chroma not in SAMPLINGS:
            raise ValueError(f"no chroma sampling {chroma!r}")
        self._lib = _library()
        self.w, self.h = width, height
        self._c444 = int(chroma == "444")
        self._h = self._lib.bd_open()
        if not self._h:
            raise RuntimeError("libavcodec has no H.264 decoder")
        cw, ch = (width, height) if self._c444 else (width // 2, height // 2)
        self._y = np.empty((height, width), np.uint8)
        self._u = np.empty((ch, cw), np.uint8)
        self._v = np.empty((ch, cw), np.uint8)

    def decode(self, access_unit: bytes) -> tuple | None:
        ptr = ctypes.POINTER(ctypes.c_uint8)
        rc = self._lib.bd_decode(
            self._h, access_unit, len(access_unit), self.w, self.h,
            self._c444, self._y.ctypes.data_as(ptr), self._u.ctypes.data_as(ptr),
            self._v.ctypes.data_as(ptr))
        if rc != 0:
            return None
        return tuple(p.astype(np.float64) for p in (self._y, self._u, self._v))

    def close(self) -> None:
        if self._h:
            self._lib.bd_close(self._h)
            self._h = None


def jpeg_planes(data: bytes, chroma: str) -> tuple | None:
    """A JPEG stripe decoded by PIL straight to YCbCr (libjpeg with no
    colour conversion), as (Y, Cb, Cr), or None if it does not decode.
    At ``"420"`` the chroma is averaged to 4:2:0 whatever the JPEG's own
    sampling, and a greyscale JPEG decodes to Cb = Cr = 128. At
    ``"444"`` the chroma stays at full size, and a JPEG whose components
    are not all sampled 1x1 (4:4:4) does not decode."""
    from PIL import Image, JpegImagePlugin
    try:
        img = Image.open(io.BytesIO(data))
        if chroma == "444" and JpegImagePlugin.get_sampling(img) != 0:
            return None
        img.draft("YCbCr", img.size)
        img.load()
    except (OSError, SyntaxError, ValueError):
        return None
    if img.mode != "YCbCr":
        img = img.convert("YCbCr")
    a = np.asarray(img, np.float64)
    if chroma == "444":
        return a[..., 0], a[..., 1], a[..., 2]
    return a[..., 0], half(a[..., 1]), half(a[..., 2])


def half(plane: np.ndarray) -> np.ndarray:
    """The mean of each 2x2 block (an odd edge repeats its last row or
    column): full-size chroma to 4:2:0."""
    h, w = plane.shape
    p = np.pad(plane, ((0, h % 2), (0, w % 2)), mode="edge")
    return p.reshape(p.shape[0] // 2, 2, p.shape[1] // 2, 2).mean(axis=(1, 3))


def luma(rgb: np.ndarray) -> np.ndarray:
    """BT.601 full-range luma, rounded, as float64."""
    r, g, b = (rgb[..., i].astype(np.float64) for i in range(3))
    return np.clip(np.round(0.299 * r + 0.587 * g + 0.114 * b), 0, 255)


def chroma_planes(rgb: np.ndarray) -> tuple:
    """BT.601 full-range Cb and Cr of an RGB frame, at full size."""
    r, g, b = (rgb[..., i].astype(np.float64) for i in range(3))
    cb = 128.0 - 0.168736 * r - 0.331264 * g + 0.5 * b
    cr = 128.0 + 0.5 * r - 0.418688 * g - 0.081312 * b
    return cb, cr


def planes(rgb: np.ndarray, chroma: str) -> tuple:
    """What a conforming decoder should show for an RGB frame: BT.601
    full-range (Y, Cb, Cr), Y rounded, Cb and Cr averaged to 4:2:0 at
    ``"420"`` and at full size at ``"444"``."""
    cb, cr = chroma_planes(rgb)
    if chroma == "420":
        cb, cr = half(cb), half(cr)
    return luma(rgb), cb, cr


def worst_block_mse(got: np.ndarray, want: np.ndarray, block: int = 16
                    ) -> float:
    """The largest mean squared error over ``block`` x ``block`` tiles
    (partial tiles at the edges included), channels averaged."""
    d = (got.astype(np.float64) - want.astype(np.float64)) ** 2
    if d.ndim == 3:
        d = d.mean(axis=2)
    h, w = d.shape
    hp, wp = -(-h // block) * block, -(-w // block) * block
    sums = np.zeros((hp, wp))
    sums[:h, :w] = d
    counts = np.zeros((hp, wp))
    counts[:h, :w] = 1
    tile = (hp // block, block, wp // block, block)
    return float((sums.reshape(tile).sum(axis=(1, 3))
                  / np.maximum(counts.reshape(tile).sum(axis=(1, 3)), 1)
                  ).max())
