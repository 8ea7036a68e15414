"""Least time for the bytes the traced frames' work needs (counts/<codec>)
at the chip's peak HBM bandwidth (peaks.json), over the device busy time
of all the cell's programs in the traced span, %. Layer: device programs
(ops/*)."""

from harness import layers


def read(ctx):
    return layers.roofline_pct(ctx)
