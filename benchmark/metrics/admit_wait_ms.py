"""Median per frame of wait span ``pipeline.admit``, ms: the capture
thread blocked in ``PipelineRing.submit`` until a slot is free. Layer:
pipeline (engine/pipeline.py)."""

from harness import layers


def read(ctx):
    return layers.median(layers.per_frame_ms(ctx, ("pipeline.admit",)))
