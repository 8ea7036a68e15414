"""Median per frame of wait span ``pipeline.queue``, ms: an admitted
slot waiting for the finalizer thread to take it. Layer: pipeline
(engine/pipeline.py)."""

from harness import layers


def read(ctx):
    return layers.median(layers.per_frame_ms(ctx, ("pipeline.queue",)))
