"""Median per frame of wait spans ``readback.device_wait`` (the
finalizer's sync on the step's control arrays) plus
``readback.slice_wait`` (each bucketed readback slice, which can queue
behind the next frame's step), ms. Layer: readback (engine/readback.py,
the sessions' finalize)."""

from harness import layers


def read(ctx):
    return layers.median(layers.per_frame_ms(
        ctx, ("readback.device_wait", "readback.slice_wait")))
