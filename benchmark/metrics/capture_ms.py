"""Median self time of span ``capture`` (the source's grab and upload)
per frame in the window, ms. Layer: capture (engine/capture.py)."""

from harness import layers


def read(ctx):
    return layers.median(layers.per_frame_self_ms(ctx, ("capture",)))
