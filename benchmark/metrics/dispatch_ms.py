"""Median self time of span ``encode.dispatch`` per frame in the window,
ms: the session's host work to launch the device step, including the
damage-band path's row-probe host sync. Layer: session dispatch
(engine/h264_encoder.py, engine/encoder.py)."""

from harness import layers


def read(ctx):
    return layers.median(layers.per_frame_self_ms(ctx, ("encode.dispatch",)))
