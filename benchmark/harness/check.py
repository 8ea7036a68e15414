"""The comparison that decides ``correct``.

Once the window has closed and the server is down, the frames the client
received are decoded by the reference decoders (``reference/decoders``),
composed onto a canvas as a browser composes stripes, and compared with
the harness's own source frame for the content each frame carries (the
tick log). Both codecs are compared in BT.601 full-range YCbCr at the
chroma sampling the configuration promises (``sampling``: 4:2:0 unless
it says ``"chroma": "444"``), the form both decoders give: luma by its
worst 16x16 block, each chroma plane by its worst block of one
macroblock's chroma (8x8 at 4:2:0, 16x16 at 4:4:4), and each plane by
the frame's mean. Under a 4:4:4 promise a picture of 4:2:0 does not
decode; an H.264 4:4:4 picture under a 4:2:0 promise does not either (a
4:4:4 JPEG is averaged to 4:2:0 there, as before). A frame that shows
stale content, a dropped or misplaced stripe, a decoder-encoder
mismatch, lost colour or coarser quantisation moves these far more than
coding noise at the configured quality does.

What is compared: a seeded sample of the frames sent in the window, plus
the largest of them and every IDR among them. The decoders run over every
frame from the last IDR before the window, since each P frame depends on
the one before it.
"""

from __future__ import annotations

import math

import numpy as np

from reference import decoders

#: frames compared besides the largest and the IDRs
SAMPLE = 40


def sampling(config: dict) -> str:
    """The chroma sampling a configuration promises: its ``chroma``
    key, ``"420"`` where it has none. The check holds the stream to it,
    never to what the stream says of itself."""
    chroma = config.get("chroma", "420")
    if chroma not in decoders.SAMPLINGS:
        raise ValueError(f"no chroma sampling {chroma!r}; the check knows "
                         f"{decoders.SAMPLINGS}")
    return chroma


def _sample(window: list, frames: dict, seed: int) -> set:
    whole = [f for f in window if frames[f].whole_at() is not None]
    if not whole:
        return set()
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 7])
    pick = set(rng.choice(whole, size=min(SAMPLE, len(whole)),
                          replace=False).tolist())
    pick.add(max(whole, key=lambda f: frames[f].nbytes))
    pick.update(f for f in whole if frames[f].idr)
    return pick


def _start_fid(codec: str, frames: dict, first: int) -> int:
    """Where decoding starts: the last IDR at or before the window for
    H.264 (every stripe carries it), the window itself for JPEG (the
    canvas is filled from each stripe's last arrival before it)."""
    if codec != "h264":
        return first
    idrs = [f for f in frames if f <= first and frames[f].idr]
    return max(idrs) if idrs else min(frames)


def compare(codec: str, frames: dict, window: list, keys: dict, desktop,
            seed: int, chroma: str) -> dict:
    """-> numbers: worst_luma_mse, worst_chroma_mse, frame_luma_mse,
    frame_chroma_mse (each the largest over the compared frames),
    undecodable, compared; everything at the ``chroma`` sampling."""
    pick = _sample(window, frames, seed)
    worst = dict.fromkeys(MSE, 0.0)
    if not window:
        return {**dict.fromkeys(MSE, float("inf")), "undecodable": 0,
                "compared": 0}
    first, last = min(window), max(window)
    start = _start_fid(codec, frames, first)
    in_window = set(window)
    canvas = [None, None, None]         # Y, Cb, Cr
    streams: dict = {}
    if codec != "h264":
        # JPEG: every stripe is intra; start from each position's last
        # stripe before the window
        before: dict = {}
        for fid in sorted(f for f in frames if f < first):
            before.update(frames[fid].got)
        for y, s in before.items():
            planes = decoders.jpeg_planes(s.payload, chroma)
            if planes is not None:
                _paste(canvas, y, planes, chroma)
    undecodable = 0
    compared = 0
    try:
        for fid in sorted(f for f in frames if start <= f <= last):
            bad = False
            for y, s in sorted(frames[fid].got.items()):
                if codec == "h264":
                    dec = streams.get(y)
                    if dec is None:
                        dec = streams[y] = decoders.H264Stream(
                            s.width, s.height, chroma)
                    planes = dec.decode(s.payload)
                else:
                    planes = decoders.jpeg_planes(s.payload, chroma)
                if planes is None:
                    bad = True
                    continue
                _paste(canvas, y, planes, chroma)
            if fid in in_window and bad:
                undecodable += 1
            if fid in pick and canvas[0] is not None:
                want = decoders.planes(desktop.render(keys[fid]), chroma)
                got = [c[:w.shape[0], :w.shape[1]]
                       for c, w in zip(canvas, want)]
                if any(g.shape != w.shape for g, w in zip(got, want)):
                    worst = dict.fromkeys(MSE, float("inf"))
                else:
                    _worse(worst, got, want, chroma)
                compared += 1
    finally:
        for dec in streams.values():
            dec.close()
    if compared == 0:
        worst = dict.fromkeys(MSE, float("inf"))
    return {**worst, "undecodable": undecodable, "compared": compared}


#: the distances compare() measures, each the largest over the frames
MSE = ("worst_luma_mse", "worst_chroma_mse", "frame_luma_mse",
       "frame_chroma_mse")


def _worse(worst: dict, got: list, want: list, chroma: str) -> None:
    d = [(g - w) ** 2 for g, w in zip(got, want)]
    mb_chroma = 8 if chroma == "420" else 16     # one macroblock's chroma
    now = {"worst_luma_mse": decoders.worst_block_mse(got[0], want[0], 16),
           "worst_chroma_mse": max(decoders.worst_block_mse(g, w, mb_chroma)
                                   for g, w in zip(got[1:], want[1:])),
           "frame_luma_mse": float(d[0].mean()),
           "frame_chroma_mse": float(max(d[1].mean(), d[2].mean()))}
    for k, v in now.items():
        worst[k] = max(worst[k], v)


def _paste(canvas: list, y: int, planes: tuple, chroma: str) -> None:
    """Paste a stripe's (Y, Cb, Cr) at row ``y`` (at 4:2:0 the chroma at
    ``y / 2``), growing the canvas as a browser's does."""
    for i, plane in enumerate(planes):
        at = y if i == 0 or chroma == "444" else y // 2
        h = at + plane.shape[0]
        c = canvas[i]
        if c is None or c.shape[0] < h:
            grown = np.zeros((h, plane.shape[1]))
            if c is not None:
                grown[:c.shape[0], :c.shape[1]] = c
            c = canvas[i] = grown
        c[at:h, :plane.shape[1]] = plane


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """-> (correct, {name: {value, limit}}) over every number the cell's
    limits name: correct when some frame was compared and each number is
    within its limit."""
    checks = {}
    for name, limit in limits.items():
        value = numbers[name]
        checks[name] = {"value": value if math.isfinite(value) else None,
                        "limit": limit}
    correct = numbers["compared"] > 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    return bool(correct), checks
