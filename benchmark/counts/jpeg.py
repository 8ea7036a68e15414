"""Bytes that one JPEG frame's work needs, whatever implements it.

- the captured frame read once, and the previous frame read once to find
  the damage: 3 bytes a pixel each, over the coded picture;
- the bitstream written once.

JPEG keeps no reference, so nothing else has to move, and the chroma
sampling changes nothing here. No operation count: v5e publishes peaks
for its matrix unit only, and this codec runs on the vector unit, so the
roofline is bandwidth's.
"""


def bytes_needed(width: int, height: int, *, intra: bool, dirty_rows: int,
                 payload_bytes: int, chroma: str = "420") -> int:
    del intra, dirty_rows, chroma
    return 2 * width * height * 3 + payload_bytes
