"""Median per frame of work span ``readback.copy`` less the
``readback.slice_wait`` nested in it, ms: the device-to-host transfer of
the stream bytes and its host overhead. Layer: readback
(engine/readback.py)."""

from harness import layers, waits


def _copy_ns(spans):
    copies = [s for s in spans if s["name"] == "readback.copy"]
    if not copies:
        return None
    return sum(c["dur_ns"] - waits.nested_wait_ns(c, spans) for c in copies)


def read(ctx):
    return layers.median(waits.per_frame(ctx, _copy_ns))
