"""Median per frame of wait span ``encode.probe_sync``, ms: the H.264
damage-band path's host sync on the row probe, which waits for the
previous band step on the device. Layer: session dispatch
(engine/h264_encoder.py)."""

from harness import layers


def read(ctx):
    return layers.median(layers.per_frame_ms(ctx, ("encode.probe_sync",)))
