"""Median per frame of counter ``readback.bytes`` over 1e3, kB: the
bytes that crossed from the device to the host for the frame's stream
(bucket-rounded fetches). Layer: readback (engine/readback.py)."""

from harness import layers, waits


def read(ctx):
    return layers.median([n / 1e3 for n in
                          waits.counts(ctx, "readback.bytes")])
