"""The fullest the stream buffer ran, %: the largest over the window's
frames of counter ``stream.bytes`` (the frame's coded bytes) over counter
``stream.cap`` (the buffer's capacity when the frame was encoded). A
frame at 100 % overflows: the program grows the buffer, recompiles its
steps and forces a refresh. Layer: stream buffer
(engine/h264_encoder.py)."""

from harness import waits


def read(ctx):
    fills = [100.0 * n / cap for n, cap in
             zip(waits.counts(ctx, "stream.bytes"),
                 waits.counts(ctx, "stream.cap")) if cap]
    return max(fills) if fills else None
