"""Bytes that one H.264 frame's work needs, whatever implements it.

- the captured frame read once, and the previous frame read once to find
  the damage: 3 bytes a pixel each, over the coded picture;
- for the macroblock rows the traffic dirtied (every row of an IDR): the
  reference planes read once (P only) and the reconstruction written once,
  each at 1.5 bytes a pixel at 4:2:0 and at 3 at 4:4:4;
- the bitstream written once.

No operation count: v5e publishes peaks for its matrix unit only, and
this codec runs on the vector unit, so the roofline is bandwidth's.
"""


def bytes_needed(width: int, height: int, *, intra: bool, dirty_rows: int,
                 payload_bytes: int, chroma: str = "420") -> int:
    frames = 2 * width * height * 3
    rows_px = (height if intra else dirty_rows * 16) * width
    planes = rows_px * 3 // 2 if chroma == "420" else rows_px * 3
    reference = 0 if intra else planes
    reconstruction = planes
    return frames + reference + reconstruction + payload_bytes
