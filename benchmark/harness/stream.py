"""What the client received and what the server sent, frame by frame.

Reads the client's records (arrival time + wire message) and the server's
fan-out log, and answers per frame id: which stripes were sent, when the
frame arrived whole, its payload, and whether it is an IDR. The wire
headers are parsed here from the protocol's published layout (0x03 JPEG:
``>BBHH`` op, flags, frame id, y; 0x04 H.264: ``>BBHHHH`` op, frame
type, frame id, y, width, height), not with the program's code.
"""

from __future__ import annotations

import dataclasses
import struct

H264 = struct.Struct(">BBHHHH")
JPEG = struct.Struct(">BBHH")


@dataclasses.dataclass
class Stripe:
    t: float            # arrival, monotonic seconds
    payload: bytes
    idr: bool
    width: int
    height: int


@dataclasses.dataclass
class Frame:
    fid: int
    t_send: float                    # first stripe fanned out
    sent: dict                       # y -> payload bytes (server side)
    got: dict = dataclasses.field(default_factory=dict)   # y -> Stripe

    def whole_at(self) -> float | None:
        """When the last sent stripe arrived; None if one never did."""
        if not self.sent or any(y not in self.got for y in self.sent):
            return None
        return max(self.got[y].t for y in self.sent)

    @property
    def nbytes(self) -> int:
        return sum(len(s.payload) for s in self.got.values())

    @property
    def idr(self) -> bool:
        return any(s.idr for s in self.got.values())


def parse_stripe(t: float, data: bytes) -> tuple[int, int, Stripe]:
    if data[0] == 0x04:
        _, ftype, fid, y, w, h = H264.unpack_from(data)
        return fid, y, Stripe(t, bytes(data[H264.size:]), ftype == 1, w, h)
    _, _, fid, y = JPEG.unpack_from(data)
    return fid, y, Stripe(t, bytes(data[JPEG.size:]), False, 0, 0)


def frames(fanout: list, records: list) -> dict[int, Frame]:
    """Join the server's fan-out log ``[(t, fid, y, nbytes)]`` with the
    client's ``[(t, data)]`` records into frames keyed by frame id. A
    stripe that arrives twice keeps its first arrival."""
    out: dict[int, Frame] = {}
    for t, fid, y, nbytes in fanout:
        f = out.get(fid)
        if f is None:
            f = out[fid] = Frame(fid, t, {})
        f.sent[y] = nbytes
    for t, data in records:
        fid, y, stripe = parse_stripe(t, data)
        f = out.get(fid)
        if f is None:
            f = out[fid] = Frame(fid, t, {})
        f.got.setdefault(y, stripe)
    return out
