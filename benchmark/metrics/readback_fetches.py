"""Median per frame of counter ``readback.fetches``: the device-to-host
transfers of the frame's stream bytes. Layer: readback
(engine/readback.py)."""

from harness import layers, waits


def read(ctx):
    return layers.median(waits.counts(ctx, "readback.fetches"))
