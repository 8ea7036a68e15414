"""Median per frame of span ``encode.readback``, ms: from the slot's
submit (at pipeline depth 2) through the host's wait for the device and
the copy of the stream bytes. Layer: readback (engine/readback.py)."""

from harness import layers


def read(ctx):
    return layers.median(layers.per_frame_ms(ctx, ("encode.readback",)))
